"""Collective operations over the device mesh.

This module is the data-plane communication backend (SURVEY §2.7/§5.8): the
reference moves gradients with ``RDD.treeAggregate`` (ref: rdd/RDD.scala:1223
— log-depth reduction over executor partitions through the Netty shuffle);
here the same reduction is a ``jax.lax.psum`` compiled into the step program,
riding ICI within a slice and DCN across the ``replica`` axis. Barrier-mode
``allGather`` (ref: BarrierTaskContext.scala:183) maps to
``jax.lax.all_gather``; dense repartition (shuffle) maps to
``jax.lax.all_to_all``.

``tree_aggregate(fn, dataset_arrays)`` is the workhorse: it shard_maps ``fn``
over the row-sharded arrays, psums the per-shard partials hierarchically
(data axis = ICI, then replica axis = DCN), and returns the replicated
result — semantically identical to the reference's
``treeAggregate(zero)(seqOp, combOp, depth)`` with commutative combOp.
"""

from __future__ import annotations

import functools
import re
from typing import Any, Callable, Optional, Sequence

from cycloneml_tpu import mesh as _mesh_mod
from cycloneml_tpu.mesh import DATA_AXIS, MODEL_AXIS, REPLICA_AXIS, MeshRuntime
from cycloneml_tpu.observe import attribution, costs, skew, tracing


class StaleProgramError(RuntimeError):
    """A compiled aggregation program was dispatched across a mesh
    teardown/rebuild (elastic reshape, device-loss recovery,
    decommission). The program closes over the OLD mesh: on CPU it
    silently runs on the torn-down virtual devices, on TPU it dies deep
    inside XLA — either way the caller must REBUILD the program
    (``clear_program_cache`` + ``tree_aggregate`` on the new runtime,
    the idiom graftlint JX017 checks statically). Classified PERMANENT
    by the resilience layer: retrying dispatches the same dead program."""


def shard_map_compat(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with ``check_vma=False`` — the one spelling every
    mesh program in the package uses (graftlint JX015 keys on this name).
    The per-shard functions psum their own partials and run Pallas
    kernels, neither of which the varying-manual-axes check can type."""
    import jax
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def psum_over_mesh(x, axes: Sequence[str] = (DATA_AXIS, REPLICA_AXIS),
                   *, depth: int = 2):
    """Topology-aware psum: intra-slice (ICI) first, then cross-slice (DCN).

    Inside shard_map only. ``depth`` is the reference's ``treeAggregate``
    depth parameter realized on the two-tier mesh topology: ``depth >= 2``
    (default) reduces level by level — a psum over ``data`` (ICI, inside
    one process/slice) followed by a psum over ``replica`` (DCN, across
    slices) — so XLA schedules the fast intra-slice reduction before the
    slower DCN hop and only one partial per slice crosses the wire.
    ``depth=1`` is the flat single-level reduction: ONE psum over the
    joint axis tuple (the ``treeAggregate(depth=1)`` analog). The mesh
    has exactly two interconnect tiers, so depths beyond 2 reduce to the
    hierarchical form.
    """
    import jax
    out = x
    for level in _level_axes(tuple(axes), depth):
        out = jax.lax.psum(out, level)
    return out


def _level_axes(axes: tuple, depth: int) -> tuple:
    """Axis groups per reduction level — one joint group at depth 1
    (flat), one group per axis at depth >= 2 (hierarchical). Static host
    structure: the depth decision happens before tracing, outside the
    lax-calling function."""
    if depth <= 1:
        return (axes,)
    return tuple((ax,) for ax in axes)


def reduction_levels(depth: int) -> tuple:
    """(tier, axes) levels a ``depth`` reduction performs — the structure
    annotation the dispatch spans carry to the trace collector."""
    if depth <= 1:
        return (("flat", f"{DATA_AXIS}+{REPLICA_AXIS}"),)
    return (("ici", DATA_AXIS), ("dcn", REPLICA_AXIS))


class BoundedProgramCache:
    """LRU cache for compiled-program identity.

    Program identity (not just trace identity) must be stable across
    estimator fits: every fresh ``jax.jit`` object restarts tracing AND XLA
    compilation, and a TPU compile costs tens of seconds — per-fit closures
    were recompiling the same aggregation every fit. Callers make their key
    fns stable (lru-cached factories); shapes/dtypes are handled by jit's
    own cache underneath. LRU-bounded: callers that still pass per-fit
    closures insert entries that can never hit again; eviction is safe
    because every caller holds its own reference to the program it is using
    — only future reuse is lost. Entries close over the Mesh, so every
    instance registers itself for clearing on mesh teardown.
    """

    _instances: list = []

    def __init__(self, maxsize: int):
        import collections
        self._max = maxsize
        self._d = collections.OrderedDict()
        BoundedProgramCache._instances.append(self)

    def get(self, key):
        v = self._d.get(key)
        if v is not None:
            self._d.move_to_end(key)
        tr = tracing.active()  # one global read when tracing is off
        if tr is not None:
            # a miss is the event that buys a fresh trace + XLA compile on
            # the program's first dispatch — FitProfile pairs these counts
            # with the 'compile' spans that first dispatch opens
            tr.instant("cache.hit" if v is not None else "cache.miss",
                       cache="program")
        return v

    def put(self, key, value) -> None:
        self._d[key] = value
        while len(self._d) > self._max:
            self._d.popitem(last=False)

    def get_or_build(self, key, build: Callable):
        """``(program, fresh)``: the cached program of ``key``, or
        ``build()`` cached — ``fresh`` then says that its first dispatch
        pays trace + XLA compile."""
        prog = self.get(key)
        fresh = prog is None
        if fresh:
            prog = build()
            self.put(key, prog)
        return prog, fresh

    def clear(self) -> None:
        self._d.clear()

    def __len__(self) -> int:
        return len(self._d)


def _name_program(program, prefix: str, fn) -> None:
    """Name ``program`` ``<prefix>__<fn's name>`` before it is jitted: the
    HLO module (``jit_<name>``), the ``op_name`` of every instruction and
    the device trace carry the name, so an aggregation reads as what it
    aggregates instead of as ``sharded``."""
    inner = getattr(fn, "__name__", None) or type(fn).__name__
    program.__name__ = program.__qualname__ = \
        f"{prefix}__" + re.sub(r"\W", "_", inner)


def _instrument_dispatch(jitted, name: str = "tree_aggregate", key=None,
                         levels: tuple = ()):
    """Route every dispatch of an aggregation program through the chaos
    harness's ``collectives.step`` injection point (faults.py) and, when
    tracing is enabled, open a ``collective`` span per step (a ``compile``
    span nests inside the first dispatch — the call that pays trace + XLA
    compilation) plus the XLA cost harvest (observe/costs.py): the first
    traced dispatch registers the program's FLOPs/bytes/peak-HBM under its
    program-cache identity (``key``), checks the memory budget, and every
    traced dispatch carries a ``program`` attr so FitProfile can join
    executions onto costs. When neither faults nor tracing is installed
    the cost is two global reads per step; the raw program stays reachable
    as ``__wrapped__`` for callers that inline it into larger jitted
    programs (e.g. the device-resident line search)."""
    import jax

    from cycloneml_tpu.parallel import faults

    first = [True]
    pid_ref = [None]
    # mesh generation this program was built under: the runtime twin of
    # graftlint JX017 — a dispatch after ANY mesh teardown/rebuild is a
    # stale-program bug, surfaced as one classified error instead of a
    # silent wrong-mesh run (CPU) or a deep XLA crash (TPU)
    build_epoch = _mesh_mod.mesh_epoch()
    # reduction-structure annotation, built once: the collective spans
    # carry the per-level topology (ici/dcn axes) to the trace collector
    level_attrs = {f"level.{i}": f"{tier}:{axes}"
                   for i, (tier, axes) in enumerate(levels)}

    @functools.wraps(jitted)
    def dispatch(*args, **kwargs):
        # trace-time calls (this program inlined into a larger jitted
        # program, e.g. the fused line search) must not count as a step:
        # compiles are cached across fits, so counting them would make the
        # fault schedule depend on compile-cache state. The SAME guard is
        # the tracer-awareness contract — a span here would record host
        # wall clock during tracing (see jx001_tracing_pass fixture).
        if any(isinstance(a, jax.core.Tracer) for a in args):
            return jitted(*args, **kwargs)
        if _mesh_mod.mesh_epoch() != build_epoch:
            raise StaleProgramError(
                f"program '{name}' was compiled under mesh epoch "
                f"{build_epoch} but the mesh is now at epoch "
                f"{_mesh_mod.mesh_epoch()} (a rebuild/reshape tore its "
                f"devices down); rebuild the program on the new runtime "
                f"(clear_program_cache + tree_aggregate) instead of "
                f"re-dispatching the stale one")
        # inject BEFORE consuming the first-dispatch flag: a chaos fault
        # raised here leaves the flag set, so the RETRY (the dispatch that
        # actually pays trace + compile) still records its compile span.
        # `multihost.preempt_notice` fires first — a decommission NOTICE
        # precedes the loss it announces — then `multihost.host`: a lost
        # HOST surfaces to the train loop as the collective that can no
        # longer complete. Scheduling a PreemptionNotice / HostLostError
        # here is the chaos stand-in for a preempted / dead peer
        faults.inject("multihost.preempt_notice")
        faults.inject("multihost.host")
        faults.inject("collectives.step")
        was_first, first[0] = first[0], False
        # attribution window: one global read when usage metering is off,
        # one thread-local peek more when no scope is active — the same
        # disabled-path discipline as the tracer/faults reads above
        win = attribution.dispatch_window()
        tr = tracing.active()
        if tr is None:
            if win.live and pid_ref[0] is None:
                # a scoped dispatch wants the FLOPs/bytes join even with
                # tracing off: harvest once per program (shared registry)
                pid_ref[0] = costs.ensure(name, key, jitted, args)
            win.annotate_program(pid_ref[0])
            # untraced, but an installed skew detector still gets the
            # step-time sample for the SLO latch (one more global read).
            # The FIRST dispatch pays trace + XLA compile — seconds, not
            # a step time — and would fire a spurious SloBreach. The
            # attribution window still wraps it: compile time is device
            # capacity the scope consumed, and the ledger's per-scope and
            # totals rows move together so the sum invariant holds.
            if was_first:
                with win:
                    return jitted(*args, **kwargs)
            with win:
                with skew.timed_observe("collectives.step", name):
                    return jitted(*args, **kwargs)
        # cost harvest + budget guard only under a FULL tracer: the
        # flight-recorder ring records spans and must stay cheap — no AOT
        # analyze, no counter tracks (the always-on contract). A live
        # attribution window buys the harvest too — the scope's
        # FLOPs/bytes column joins on the same program identity.
        full = tr.full
        if (full or win.live) and pid_ref[0] is None:
            # harvest BEFORE the first dispatch and OUTSIDE the spans: the
            # AOT lower+compile feeding cost_analysis must not inflate
            # compile_seconds, and a budgetAction=raise guard must fire
            # before the oversized program ever executes
            pid_ref[0] = costs.ensure(name, key, jitted, args)
            costs.check_budget(pid_ref[0])
        win.annotate_program(pid_ref[0])
        with win:
            with tr.span("collective", name, program=pid_ref[0],
                         **level_attrs) as csp:
                if was_first:
                    with tr.span("compile", name):
                        out = jitted(*args, **kwargs)
                else:
                    out = jitted(*args, **kwargs)
        if not was_first:
            # compile-paying first dispatches are staging, not step time —
            # they must not trip the SLO latch
            skew.observe("collectives.step", name, csp.span.duration_s)
        if full:
            costs.note_execution(tr, pid_ref[0])
        return out

    dispatch.__wrapped__ = jitted
    return dispatch


def dispatch_fused(name: str, key, prog, args: tuple, *, fresh: bool,
                   transfer_name: str, evals_at: int,
                   readback: Callable = lambda out: out, **span_attrs):
    """One dispatch of a FUSED optimizer program — a whole line search or a
    chunk of L-BFGS iterations, which inline the aggregation and so never
    pass :func:`_instrument_dispatch` — and its one small readback.

    Opens ``dispatch <name>`` (attrs ``span_attrs``) ⊃ ``compile <name>``
    when ``fresh`` (the dispatch that pays trace + XLA compile) and ⊃
    ``transfer <transfer_name>`` around the ``device_get`` of
    ``readback(outputs)``, all inside an attribution window; host value
    ``evals_at`` is the evaluation count the dispatch span reports as
    ``evals``. Under a FULL tracer or a live window — never the
    flight-recorder ring, which records spans and must not pay an AOT
    analyze — the program's costs are harvested (once per program identity
    ``key``) BEFORE the dispatch, so the analyze stays out of the
    dispatch/compile spans, and the dispatch span carries a ``program``
    attr so FitProfile can join executions onto costs.

    Returns ``(outputs, host values)``."""
    import jax

    win = attribution.dispatch_window()
    tr = tracing.full_active()
    pid = None
    if tr is not None or win.live:
        pid = costs.ensure(name, key, prog, args)
    win.annotate_program(pid)
    with win:
        with tracing.span("dispatch", name, **span_attrs) as dsp:
            if fresh:
                with tracing.span("compile", name):
                    out = prog(*args)
            else:
                out = prog(*args)
            with tracing.span("transfer", transfer_name) as tsp:
                host = jax.device_get(readback(out))
                tsp.annotate_bytes(host)
    dsp.annotate(evals=int(host[evals_at]))
    if tr is not None:
        dsp.annotate(program=pid)
        costs.note_execution(tr, pid)
    return out, host


# (fn, mesh, n_sharded, auto_psum, with_state) -> jitted program
_program_cache = BoundedProgramCache(256)


def clear_program_cache() -> None:
    """Drop ALL cached programs everywhere (mesh teardown/rebuild). The
    cost registry goes with them: its ids embed the old mesh/program
    identities, so every entry is stale once the programs rebuild."""
    for cache in BoundedProgramCache._instances:
        cache.clear()
    costs.clear()


def tree_aggregate(fn: Callable, runtime: MeshRuntime, *arrays,
                   auto_psum: bool = True, with_state: bool = False,
                   n_sharded: Optional[int] = None,
                   donate_rows: bool = False,
                   depth: Optional[int] = None):
    """Aggregate ``fn(local_rows..., extras...) -> pytree`` over row-sharded arrays.

    ``arrays`` fixes how many leading arguments are row-sharded; the returned
    jitted callable takes ``(*arrays, *extras)`` where extras (e.g. current
    coefficients) are replicated. ``fn`` receives each device's local shard of
    every sharded array plus the extras, returns a pytree of partials;
    partials are psum'd hierarchically over the mesh. Callers compile once,
    call per iteration.

    With ``with_state=True``, ``fn`` returns ``(stats, rows)``: ``stats`` is
    psum'd (replicated result) while ``rows`` keeps the input row sharding
    (e.g. an updated per-row assignment vector).

    ``n_sharded`` names the row-sharded argument count without sample
    arrays (the out-of-core path compiles its per-shard program before any
    shard exists). ``donate_rows=True`` donates the sharded arguments to
    XLA: correct ONLY for single-shot operands — the streaming engine's
    staged shards are consumed exactly once per dispatch, so their buffers
    are dead the moment the dispatch leaves the host and donation releases
    the HBM for the next shard's in-flight transfer (the data-path
    extension of the L-BFGS state donation; graftlint JX009 polices the
    single-use discipline). In-core datasets redispatch the same arrays
    every iteration and must NEVER donate. On host-platform (CPU) meshes
    donation is skipped — XLA:CPU does not implement it and would warn on
    every program.

    ``depth`` is the reference's ``treeAggregate`` depth parameter mapped
    onto the two-tier mesh topology (see :func:`psum_over_mesh`):
    ``depth>=2`` (default) reduces hierarchically — psum over ``data``
    inside each slice (ICI), then the cross-slice combine over
    ``replica`` (DCN) — while ``depth=1`` emits one flat psum over the
    joint axes. ``None`` resolves ``cyclone.treeAggregate.depth`` from
    the active context (default 2). The two forms are numerically
    equivalent at the ulp level (only the reduction grouping differs);
    the hierarchical form keeps DCN traffic to one partial per slice.
    """
    import jax
    from jax.sharding import PartitionSpec as P
    if with_state and not auto_psum:
        # stats would be emitted unreduced under a replicated out_spec —
        # silently wrong with check_vma disabled
        raise ValueError("with_state=True requires auto_psum=True")
    if n_sharded is None:
        n_sharded = len(arrays)
    if depth is None:
        depth = _default_depth()
    donate = bool(donate_rows) and runtime.platform != "cpu"
    try:
        key = (fn, runtime.mesh, n_sharded, auto_psum, with_state, donate,
               depth)
        cached = _program_cache.get(key)
    except TypeError:  # unhashable fn: build uncached
        key, cached = None, None
    if cached is not None:
        return cached
    mesh = runtime.mesh
    row_spec = P((REPLICA_AXIS, DATA_AXIS))

    def _reduce(partial):
        if not auto_psum:
            # fn performs its own collectives (e.g. pmax/pmin stats)
            return partial
        return jax.tree_util.tree_map(
            lambda t: psum_over_mesh(t, (DATA_AXIS, REPLICA_AXIS),
                                     depth=depth), partial)

    def program(*all_args):
        def local(*a):
            if with_state:
                stats, rows = fn(*a)
                return _reduce(stats), rows
            return _reduce(fn(*a))

        n_extras = len(all_args) - n_sharded
        in_specs = tuple([row_spec] * n_sharded + [P()] * n_extras)
        out_specs = (P(), row_spec) if with_state else P()
        return shard_map_compat(local, mesh, in_specs, out_specs)(*all_args)

    _name_program(program, "tree_aggregate", fn)
    jitted = _instrument_dispatch(
        jax.jit(program,
                donate_argnums=tuple(range(n_sharded)) if donate else ()),
        key=key, levels=reduction_levels(depth) if auto_psum else ())
    if key is not None:
        _program_cache.put(key, jitted)
    return jitted


def _default_depth() -> int:
    """``cyclone.treeAggregate.depth`` from the active context, else the
    hierarchical default (2)."""
    from cycloneml_tpu.context import active_context
    ctx = active_context()
    if ctx is not None:
        from cycloneml_tpu.conf import AGGREGATION_DEPTH
        return int(ctx.conf.get(AGGREGATION_DEPTH))
    return 2


def tree_aggregate_with_state(fn: Callable, runtime: MeshRuntime, *arrays):
    """Shorthand for :func:`tree_aggregate` with ``with_state=True``."""
    return tree_aggregate(fn, runtime, *arrays, with_state=True)


def all_gather_hosts(runtime: MeshRuntime, fn: Callable, *arrays):
    """Barrier allGather analog: every shard computes ``fn(local)`` and all
    results are gathered to every participant (ref BarrierTaskContext:183)."""
    import jax
    from jax.sharding import PartitionSpec as P
    mesh = runtime.mesh
    row_spec = P((REPLICA_AXIS, DATA_AXIS))

    def program(*arrs):
        def local(*a):
            v = fn(*a)
            v = jax.lax.all_gather(v, DATA_AXIS)
            return jax.lax.all_gather(v, REPLICA_AXIS).reshape((-1,) + v.shape[1:])
        return shard_map_compat(local, mesh, (row_spec,) * len(arrs), P())(*arrs)

    _name_program(program, "all_gather_hosts", fn)
    return jax.jit(program)(*arrays)


def barrier(runtime: MeshRuntime) -> None:
    """Global sync point (ref BarrierTaskContext.barrier:169): a jitted psum
    of a token over the whole mesh, blocked on completion."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    tok = runtime.device_put_sharded_rows(
        __import__("numpy").zeros((runtime.data_parallelism,), dtype="float32"))

    @jax.jit
    def sync(t):
        def local(x):
            return psum_over_mesh(jnp.sum(x))
        return shard_map_compat(local, runtime.mesh,
                                (P((REPLICA_AXIS, DATA_AXIS)),), P())(t)

    sync(tok).block_until_ready()


def all_to_all_repartition(runtime: MeshRuntime, array, split_dim: int = 0):
    """Dense all-to-all over the data axis — on-device shuffle primitive for
    numeric repartition (replaces the sort-shuffle path for dense data,
    ref: shuffle/sort/SortShuffleManager.scala:73 / SURVEY §2.7 shuffle row).
    ``array`` is row-sharded; each shard's rows are split into n_data groups
    and exchanged so group g lands on device g.
    """
    import jax
    from jax.sharding import PartitionSpec as P
    mesh = runtime.mesh
    nd = runtime.data_parallelism
    row_spec = P((REPLICA_AXIS, DATA_AXIS))

    @jax.jit
    def go(x):
        def local(xl):
            b = xl.shape[0] // nd
            xs = xl.reshape((nd, b) + xl.shape[1:])
            out = jax.lax.all_to_all(xs, (REPLICA_AXIS, DATA_AXIS), 0, 0, tiled=False)
            return out.reshape((-1,) + xl.shape[1:])
        return shard_map_compat(local, mesh, (row_spec,), row_spec)(x)

    return go(array)
