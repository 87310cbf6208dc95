"""``jax.distributed`` lifecycle for multi-process meshes.

The control-plane leg of the multihost runtime (ref: the reference's
driver↔executor registration, SURVEY §3.1, collapsed into coordinator
rendezvous): every process of a multihost application calls
:func:`initialize` with the same coordinator address and its own process
index, after which ``jax.devices()`` is the GLOBAL device set and
cross-process collectives ride the backend fabric (DCN on a pod; gloo
over TCP on the CPU smoke).

Contracts this module owns:

- **Single-process no-op**: nothing here touches ``jax.distributed``
  unless a ``multihost[...]`` master (or an explicit call) asks for it —
  every in-core fit runs exactly as before.
- **CPU-smoke collectives**: the XLA:CPU backend refuses multi-process
  programs unless a CPU collectives implementation is configured;
  :func:`initialize` selects gloo (``cyclone.multihost.cpuCollectives``)
  BEFORE the backend comes up, so 2-process CPU meshes are real meshes.
- **Coordinator preflight**: process 0 probes the coordinator port with
  a plain bind before handing it to the gRPC server — a taken port
  surfaces as a clean ``RuntimeError`` (the deploy master's relaunch
  machinery retries with a fresh port) instead of a native crash.
- **Barriered teardown**: :func:`shutdown` syncs every process at a
  coordination-service barrier before disconnecting, so no process
  tears down the backend while a peer is mid-collective.
  :func:`abandon` is the FAILURE-path teardown — no barrier (the peer
  is dead), bounded wait — used by MeshSupervisor's host-loss recovery.
"""

from __future__ import annotations

import os
import socket
import threading
from typing import List, Optional, Tuple

from cycloneml_tpu.util.logging import get_logger

logger = get_logger(__name__)

#: default CPU cross-process collectives implementation ("none" disables —
#: multi-process CPU programs then fail at dispatch, as stock XLA does)
DEFAULT_CPU_COLLECTIVES = "gloo"

#: default teardown-barrier timeout (ms); a dead peer bounds the graceful
#: path at this instead of hanging exit
DEFAULT_BARRIER_TIMEOUT_MS = 10_000

_lock = threading.Lock()
_barrier_seq = 0
_cpu_collectives = DEFAULT_CPU_COLLECTIVES
_barrier_timeout_ms = DEFAULT_BARRIER_TIMEOUT_MS


def configure(cpu_collectives: Optional[str] = None,
              barrier_timeout_ms: Optional[int] = None) -> None:
    """Install conf-driven defaults (CycloneContext calls this from
    ``cyclone.multihost.*`` before the mesh comes up; standalone callers
    that build the mesh first get the module defaults)."""
    global _cpu_collectives, _barrier_timeout_ms
    with _lock:
        if cpu_collectives is not None:
            _cpu_collectives = cpu_collectives
        if barrier_timeout_ms is not None:
            _barrier_timeout_ms = int(barrier_timeout_ms)


def is_initialized() -> bool:
    """True when this process is part of an initialized
    ``jax.distributed`` runtime."""
    import jax
    return bool(jax.distributed.is_initialized())


def _platform_hint() -> str:
    """The configured primary platform WITHOUT initializing backends
    (``jax.default_backend()`` would bring XLA up before the collectives
    implementation is chosen)."""
    import jax
    try:
        plats = jax.config.values.get("jax_platforms")
    except Exception:
        plats = None
    plats = plats or os.environ.get("JAX_PLATFORMS", "")
    return plats.split(",")[0].strip().lower() if plats else ""


def _enable_cpu_collectives() -> None:
    """Select the CPU cross-process collectives implementation BEFORE the
    backend exists — XLA:CPU otherwise rejects multi-process programs
    ('Multiprocess computations aren't implemented on the CPU backend')."""
    impl = _cpu_collectives
    if not impl or impl == "none":
        return
    import jax
    jax.config.update("jax_cpu_collectives_implementation", impl)


def _preflight_coordinator_port(address: str) -> None:
    """Process 0 binds the coordinator port for a moment before gRPC
    does: a taken port becomes a clean, classifiable RuntimeError (the
    deploy layer relaunches with a fresh port) instead of a native
    server crash. The probe-to-bind window is the same one the deploy
    port pool already accepts."""
    host, sep, port = address.rpartition(":")
    if not sep or not port.isdigit():
        raise RuntimeError(
            f"multihost coordinator address {address!r} must be "
            f"<host>:<port>")
    try:
        with socket.socket() as s:
            s.bind((host or "127.0.0.1", int(port)))
    except OSError as e:
        raise RuntimeError(
            f"multihost coordinator port unavailable at {address}: {e}; "
            f"resubmit with a fresh port (the deploy master's relaunch "
            f"does this automatically)") from e


def probe_free_ports(n: int) -> List[int]:
    """``n`` DISTINCT free ports on this machine, all held open while
    collecting so the kernel cannot hand the same ephemeral port twice
    (briefly unreserved after close — the window every launcher that
    assigns ports ahead of bind accepts). The deploy Worker keeps its
    coordinator-port pool stocked through this."""
    socks = []
    try:
        for _ in range(n):
            s = socket.socket()
            s.bind(("", 0))
            socks.append(s)
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> bool:
    """Join (or form) the distributed runtime. Returns True when THIS
    call initialized it, False when it already was. With no arguments,
    defers to jax's env/cloud auto-detection (TPU pod metadata)."""
    import jax
    with _lock:
        if is_initialized():
            return False
        # CPU collectives must be selected whenever the runtime MAY span
        # processes: an explicit count > 1, or the no-arg auto-detect
        # path, where the process count is unknown until after init (and
        # the config is harmless for a single process)
        if _platform_hint() == "cpu" and \
                (num_processes is None or num_processes > 1):
            _enable_cpu_collectives()
        if coordinator_address and (process_id or 0) == 0:
            _preflight_coordinator_port(coordinator_address)
        kw = {}
        if coordinator_address is not None:
            kw = dict(coordinator_address=coordinator_address,
                      num_processes=int(num_processes or 1),
                      process_id=int(process_id or 0))
        jax.distributed.initialize(**kw)
        logger.info("jax.distributed up: process %s of %s (coordinator %s)",
                    int(process_id or 0), int(num_processes or 1),
                    coordinator_address or "<auto>")
        return True


def from_env(environ=None) -> Optional[Tuple[str, int, int]]:
    """(coordinator, num_processes, process_id) parsed from the deploy
    launch environment (``CYCLONE_MASTER_URL`` or the conf channel's
    ``CYCLONE_CONF_cyclone__master``, both seeded by the Worker), or
    None when this process was not deploy-launched with a multihost
    master — the single-process no-op path."""
    import re
    env = os.environ if environ is None else environ
    for key in ("CYCLONE_MASTER_URL", "CYCLONE_CONF_cyclone__master"):
        m = re.fullmatch(r"multihost\[([^,\]]+),(\d+),(\d+)\]",
                         env.get(key, ""))
        if m is not None:
            return m.group(1), int(m.group(2)), int(m.group(3))
    return None


def ensure_from_env() -> bool:
    """Initialize from the deploy environment when it names a multihost
    master; False (no-op) otherwise."""
    spec = from_env()
    if spec is None:
        return False
    return initialize(*spec)


def global_devices() -> list:
    """Every device of the global runtime, ordered so that process
    (host/DCN) boundaries are contiguous — the order
    :func:`hierarchy.build_device_grid` relies on."""
    import jax
    return sorted(jax.devices(),
                  key=lambda d: (d.process_index, getattr(d, "id", 0)))


def process_count() -> int:
    import jax
    return int(jax.process_count()) if is_initialized() else 1


def process_index() -> int:
    import jax
    return int(jax.process_index()) if is_initialized() else 0


def barrier(name: str = "cyclone-multihost",
            timeout_ms: Optional[int] = None) -> bool:
    """Block until every process reaches the same barrier (coordination-
    service backed). Per-process sequence numbers keep repeated barriers
    distinct; every process must therefore call barrier() the same
    number of times, which the symmetric call sites (context teardown)
    guarantee. Returns False (no-op) when not distributed."""
    global _barrier_seq
    if not is_initialized():
        return False
    import jax
    # jax 0.9.0 has no public timed barrier: the coordination-service
    # client behind jax.distributed is the only handle that bounds the
    # wait on a dead peer (multihost_utils.sync_global_devices is a device
    # collective and would hang with it)
    client = jax._src.distributed.global_state.client
    with _lock:
        _barrier_seq += 1
        seq = _barrier_seq
    client.wait_at_barrier(f"{name}.{seq}",
                           int(timeout_ms or _barrier_timeout_ms))
    return True


def shutdown(barrier_first: bool = True) -> bool:
    """Graceful, barriered teardown: sync every process, then disconnect.
    A dead peer bounds the barrier at the configured timeout and the
    teardown proceeds — exit must never hang forever. Idempotent."""
    if not is_initialized():
        return False
    if barrier_first:
        try:
            barrier("cyclone-teardown")
        except Exception as e:
            logger.warning("teardown barrier failed (%s); continuing", e)
    import jax
    try:
        jax.distributed.shutdown()
    except Exception as e:
        logger.warning("jax.distributed.shutdown failed: %s", e)
        return False
    logger.info("jax.distributed shut down")
    return True


def install_preemption_handler(fn, signals: Optional[Tuple[int, ...]] = None
                               ) -> bool:
    """Route the platform's decommission signal into ``fn()``.

    On real pods a slice preemption arrives as SIGTERM (the ``tpu``
    master's advance notice); this installs a handler that calls ``fn``
    — typically ``lambda: channel.announce(CapacityEvent(...))`` or a
    supervisor's drain trigger — and then CHAINS to any previously
    installed handler, so the process's own shutdown hooks still run.
    Returns False (and installs nothing) off the main thread — Python
    only allows signal handlers there — or when no usable signal exists;
    the CPU smoke models the notice with the ``multihost.preempt_notice``
    fault point instead, which is also the deterministic test surface.
    """
    import signal as _signal
    if threading.current_thread() is not threading.main_thread():
        logger.warning("preemption handler not installed: signal handlers "
                       "require the main thread")
        return False
    sigs = signals if signals is not None else (_signal.SIGTERM,)
    installed = False
    for sig in sigs:
        try:
            prev = _signal.getsignal(sig)

            def _handler(signum, frame, _prev=prev):
                logger.warning("preemption signal %s received: draining",
                               signum)
                try:
                    fn()
                finally:
                    if callable(_prev):
                        _prev(signum, frame)

            _signal.signal(sig, _handler)
            installed = True
        except (ValueError, OSError) as e:
            logger.warning("cannot install preemption handler for signal "
                           "%s: %s", sig, e)
    return installed


def abandon(timeout_s: float = 5.0) -> bool:
    """Failure-path teardown after a HOST died: no barrier (the peer
    cannot arrive), and the disconnect itself runs on a daemon thread
    with a bounded join — a coordinator that died mid-handshake must not
    wedge the survivor's recovery. Returns True when the disconnect
    completed within the bound."""
    if not is_initialized():
        return False

    def _tear():
        import jax
        try:
            jax.distributed.shutdown()
        except Exception as e:  # expected: the coordinator may be gone
            logger.info("abandoning distributed runtime: %s", e)

    t = threading.Thread(target=_tear, daemon=True,
                         name="cyclone-multihost-abandon")
    t.start()
    t.join(timeout=timeout_s)
    if t.is_alive():
        logger.warning("distributed teardown still blocked after %.1fs; "
                       "abandoned to its daemon thread", timeout_s)
        return False
    return True
