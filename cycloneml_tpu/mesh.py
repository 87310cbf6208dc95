"""Device-mesh runtime.

TPU-native replacement for the reference's driver bring-up + executor
registration (ref: SparkContext.scala:83 → SparkEnv.createDriverEnv →
CoarseGrainedSchedulerBackend registration, SURVEY §3.1). There is no
executor fleet to register: the "cluster" is a ``jax.sharding.Mesh`` over all
attached devices; gang scheduling (ref: BarrierTaskContext.scala:43) is
inherent — every jitted step is an SPMD program over the whole mesh.

Master-URL grammar (≈ SparkContext.scala:3058 master parsing):
  ``local-mesh[N]``   N host-platform devices (test fixture; requires
                      ``--xla_force_host_platform_device_count=N``)
  ``local-mesh[*]``   all visible devices of the default platform
  ``tpu``             all attached TPU devices (raises when there are none)
  ``multihost``       ``jax.distributed.initialize()`` then all global devices

The mesh is laid out ``(replica, data)``: ``data`` is the intra-slice axis
whose collectives ride ICI; ``replica`` crosses slices/hosts over DCN and is
1 on a single slice. ``tree_aggregate`` maps to a psum over ``data`` followed
by a psum over ``replica`` — the hierarchical ICI-then-DCN reduction that
replaces the reference's log-depth ``treeAggregate`` (ref: RDD.scala:1223).

Multi-process masters route through :mod:`cycloneml_tpu.multihost`:
``bootstrap`` owns the ``jax.distributed`` lifecycle (CPU-smoke gloo
collectives, coordinator preflight, barriered teardown) and ``hierarchy``
builds the device grid so replica rows align with process (DCN)
boundaries — ``n_replicas=None`` defaults to one replica row per process.
"""

from __future__ import annotations

import os
import re
from typing import Optional, Sequence, Tuple

import numpy as np

from cycloneml_tpu.util.logging import get_logger

logger = get_logger(__name__)

DATA_AXIS = "data"
REPLICA_AXIS = "replica"
MODEL_AXIS = "model"

_LOCAL_MESH_RE = re.compile(r"local-mesh\[(\d+|\*)\]")
_MULTIHOST_RE = re.compile(r"multihost\[([^,\]]+),(\d+),(\d+)\]")


#: where compiled programs persist when ``JAX_COMPILATION_CACHE_DIR`` is not
#: set: one fixed path inside the checkout (git-ignored). The directory is
#: part of the cache key's lookup, so it must never move between runs.
DEFAULT_COMPILATION_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_compilation_cache")


def compilation_cache_dir() -> str:
    """The persistent compile cache's directory: wherever
    ``JAX_COMPILATION_CACHE_DIR`` places it, else the fixed in-checkout
    default."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or DEFAULT_COMPILATION_CACHE_DIR)


def _configure_compilation_cache(jax, platform: str) -> None:
    """Persist compiled executables across processes on accelerators: a
    cold TPU process otherwise pays every program's compile again. A
    directory set from outside (``JAX_COMPILATION_CACHE_DIR``, which jax
    reads itself) is never overridden; only when none is set does the
    package point jax at its in-checkout default. Host-platform meshes are
    left alone entirely: XLA:CPU cache entries record the compile
    machine's features and were seen to reload with different codegen
    (reduction-order drift in tests), and CPU compiles are cheap."""
    if platform == "cpu":
        return
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(DEFAULT_COMPILATION_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir",
                          DEFAULT_COMPILATION_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def _global_devices(master: str):
    """The global device set once ``jax.distributed`` is up, with the
    bring-up failure named. Seen on a four-chip host with two processes of
    one app (PR 21): the first owns every chip, the second dies on libtpu's
    lockfile, and the first then waits out jax's 2-minute topology exchange
    for its dead peer."""
    from cycloneml_tpu.multihost import bootstrap
    try:
        return bootstrap.global_devices()
    except RuntimeError as e:
        raise RuntimeError(
            f"{master}: jax.distributed is up but a backend is not ({e}). "
            f"One host's TPU chips belong to ONE process: run one process "
            f"per host and let it drive all of the host's chips.") from e


class MeshRuntime:
    """Owns the global device mesh and sharding helpers."""

    def __init__(self, master: str = "tpu",
                 n_replicas: Optional[int] = None,
                 model_parallelism: int = 1):
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        self._jax = jax
        devices = self._resolve_devices(master)
        _configure_compilation_cache(jax, devices[0].platform)
        from cycloneml_tpu.multihost import hierarchy
        dev_grid, n_replicas = hierarchy.build_device_grid(
            devices, n_replicas, model_parallelism)
        self.mesh = Mesh(dev_grid, (REPLICA_AXIS, DATA_AXIS, MODEL_AXIS))
        self.master = master
        self.n_devices = len(devices)
        self.n_replicas = n_replicas
        topo = hierarchy.describe(dev_grid)
        self.n_processes = topo["n_processes"]
        self.dcn_aligned = topo["dcn_aligned"]
        self.platform = devices[0].platform
        self._P = PartitionSpec
        self._NamedSharding = NamedSharding
        logger.info("Mesh up: %d %s devices over %d process(es), shape %s",
                    self.n_devices, self.platform, self.n_processes,
                    dict(zip(self.mesh.axis_names, self.mesh.devices.shape)))

    @property
    def is_multihost(self) -> bool:
        """True when the mesh spans processes — collectives over the
        ``replica`` axis cross DCN (or its CPU-smoke stand-in)."""
        return self.n_processes > 1

    @property
    def process_index(self) -> int:
        from cycloneml_tpu.multihost import bootstrap
        return bootstrap.process_index()

    @staticmethod
    def _resolve_devices(master: str):
        import jax

        from cycloneml_tpu.multihost import bootstrap
        m = _LOCAL_MESH_RE.fullmatch(master)
        if m is not None:
            want = m.group(1)
            # LOCAL devices by definition: under an initialized
            # jax.distributed runtime (e.g. a survivor rebuilding after
            # host loss) jax.devices() still lists the dead peers'
            # devices — a local mesh must never include them
            devices = jax.local_devices()
            if want != "*":
                want_n = int(want)
                if len(devices) < want_n:
                    raise RuntimeError(
                        f"local-mesh[{want_n}] needs {want_n} devices but only "
                        f"{len(devices)} are visible; set XLA_FLAGS="
                        f"--xla_force_host_platform_device_count={want_n}")
                devices = devices[:want_n]
            return devices
        if master == "multihost":
            bootstrap.initialize()  # env/cloud auto-detection
            return _global_devices(master)
        m = _MULTIHOST_RE.fullmatch(master)
        if m is not None:
            # explicit form for local-cluster-style testing and bare-metal
            # pods: multihost[<coordinator host:port>,<num_procs>,<proc_id>]
            # (≈ the reference's local-cluster[n,c,m] master,
            # SparkContext.scala:3058 — real separate processes, one mesh)
            bootstrap.initialize(coordinator_address=m.group(1),
                                 num_processes=int(m.group(2)),
                                 process_id=int(m.group(3)))
            return _global_devices(master)
        if master == "tpu":
            try:
                return jax.devices("tpu")
            except RuntimeError as e:
                raise RuntimeError(
                    "master 'tpu' needs an attached TPU and jax found none "
                    f"({e}); on a host-platform machine pass "
                    "cyclone.master=local-mesh[N] (or run through "
                    "cycloneml_tpu.submit --master local-mesh[N])") from e
        raise ValueError(f"cannot parse master URL: {master!r}")

    # -- sharding helpers ------------------------------------------------------
    def data_sharding(self, extra_axes: int = 1):
        """Shard leading (row/block) dim over replica+data, replicate the rest."""
        spec = self._P((REPLICA_AXIS, DATA_AXIS), *([None] * extra_axes))
        return self._NamedSharding(self.mesh, spec)

    def replicated(self):
        return self._NamedSharding(self.mesh, self._P())

    def model_sharding(self, axis_index: int, ndim: int):
        """Shard dimension ``axis_index`` over the model axis (feature-dim TP
        for coefficient/Gram objects that exceed one device's HBM,
        SURVEY §5.7(a))."""
        spec = [None] * ndim
        spec[axis_index] = MODEL_AXIS
        return self._NamedSharding(self.mesh, self._P(*spec))

    @property
    def data_parallelism(self) -> int:
        return (self.mesh.devices.shape[0] * self.mesh.devices.shape[1])

    def device_put_sharded_rows(self, arr: np.ndarray):
        """Place a host array on the mesh, rows sharded over replica×data."""
        import jax
        return jax.device_put(arr, self.data_sharding(arr.ndim - 1))

    def device_put_replicated(self, tree):
        import jax
        return jax.device_put(tree, self.replicated())


def safe_fit_parallelism(requested: int, stacked_width: int = 0) -> int:
    """Effective parallelism for concurrent estimator fits on the active
    mesh; returns the width the caller may actually use (and report).

    THREAD pools are still capped: every jitted step is a gang-scheduled
    SPMD program over the WHOLE mesh; two programs dispatched concurrently
    from different threads interleave their per-device executions and
    deadlock XLA's collective rendezvous (observed: OneVsRest(parallelism=4)
    hanging the suite on local-mesh[8] once shard_map was un-broken; now
    mechanized as graftlint JX007). A >1 width is returned only on
    single-device meshes, where no cross-device rendezvous exists — though
    the in-repo estimators no longer build pools at all (they stack or run
    serially, and call this for the cap log + effective-width report); the
    reference's ``parallelism`` param parallelizes independent Spark jobs
    across a cluster, a resource this mesh model does not have.

    STACKED fits are the sanctioned parallel path: ``stacked_width > 0``
    declares that the caller runs that many models as ONE vmapped SPMD
    program — a single gang-scheduled dispatch with a leading model axis
    (docs/multi-model.md), so no cross-program rendezvous exists and full
    model-parallelism is safe on any mesh size. The stacked width is
    returned so callers can report the effective parallelism they achieved.
    """
    if stacked_width > 0:
        return stacked_width
    if requested <= 1:
        return requested
    rt = active()
    if rt is not None and rt.n_devices > 1:
        logger.info(
            "capping thread-pool fit parallelism %d -> 1: concurrent SPMD "
            "dispatch onto a shared %d-device mesh would deadlock its "
            "collectives; stacked fits (vmapped model axis, one program) "
            "are the sanctioned parallel path", requested, rt.n_devices)
        return 1
    return requested


def probe_device_count(master: str) -> Optional[int]:
    """Devices a master URL would select, WITHOUT building a mesh — lets
    callers validate a resource request before tearing down the active mesh.
    None when unknowable up-front (multihost initializes on construction);
    a master that definitively cannot be built (e.g. local-mesh[8] with 4
    visible devices) RAISES, so callers fail before any teardown."""
    if master == "multihost":
        return None
    return len(MeshRuntime._resolve_devices(master))


_active: Optional[MeshRuntime] = None


_active_lock = __import__("threading").Lock()

# monotonic mesh GENERATION: bumped by every reset() (rebuild, elastic
# reshape, decommission). Compiled aggregation programs capture the epoch
# they were built under and collectives._instrument_dispatch refuses to
# dispatch a program across a bump (StaleProgramError) — the RUNTIME twin
# of graftlint JX017's static cross-mesh check: on CPU a stale program
# silently runs on the old virtual devices and on TPU it dies deep inside
# XLA; the guard turns both into one classified, actionable error.
_mesh_epoch = 0


def mesh_epoch() -> int:
    """Current mesh generation (advances on every teardown/rebuild)."""
    return _mesh_epoch


def get_or_create(master: str = "tpu", **kw) -> MeshRuntime:
    global _active
    with _active_lock:
        if _active is None:
            _active = MeshRuntime(master, **kw)
        elif _active.master != master:
            raise RuntimeError(
                f"A mesh is already active for master {_active.master!r}; "
                f"cannot re-initialise for {master!r}. Stop all contexts and "
                "call mesh.reset() first.")
        return _active


def active() -> Optional[MeshRuntime]:
    return _active


def reset() -> None:
    global _active, _mesh_epoch
    _active = None
    _mesh_epoch += 1
    from cycloneml_tpu.parallel import collectives
    collectives.clear_program_cache()
