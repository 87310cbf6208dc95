"""Device-resident chunked L-BFGS.

The host optimizer (``lbfgs.LBFGS``) pays one device dispatch and one
blocking readback per iteration even with the fused line search — host
work and a device idle gap between every two steps (per dispatch on the
v5e, in ``lr_epsilon_fit``: ``idle_dispatch_ms`` 0.7–1.6 and
``idle_readback_ms`` 2.1–3.3 a fit of one dispatch; ledger, PR 30). This
module runs WHOLE CHUNKS of K iterations inside one jitted program: the
two-loop recursion over a fixed-size (m, n) curvature ring buffer, the
strong-Wolfe search (``loss.wolfe_search`` — the same traced state machine
the per-iteration fused path uses), the curvature-condition history update,
and the Breeze-style convergence tests all stay on device; the host sees
one dispatch (``collectives.dispatch_fused``) and one small readback per
chunk.

Structure beaten, not emulated: the reference pays one Spark JOB per loss
evaluation (RDDLossFunction.scala:56) — ~30 jobs per iteration; the host
path here pays 1 dispatch per iteration; this path pays 1/K.

Semantics match ``lbfgs.LBFGS`` (same Wolfe machine, same two-loop, same
curvature condition sᵀy > 1e-10·yᵀy, same convergence tests) computed in
the accumulator tier's dtype — f64 under the CPU test config (trajectories match
the host path), f32 on TPU (last-ulp drift; the convergence thresholds are
~1e-6 relative, within f32's resolution for these well-scaled problems).

Two chunk programs, one set of parts. The parts of an iteration
(``_two_loop``, ``_descent_or_reset``, ``_init_alpha``, ``_push_pair``,
``_convergence_code``) are written once, for one model; ``_build_chunk``
calls them and ``_build_stacked_chunk`` calls ``jax.vmap`` of them. The
builders and their ``while_loop`` bodies stay two. The stacked objective is
ONE aggregator with the model axis inside (``aggregators.
stacked_binary_logistic_*``: nothing is ``vmap``ped over a sweep, and it
reads X once for all K lanes in the tiling X's layout dictates), so the
tiling no longer keeps them apart; what does is what each driver must
support — the serial one yields a resumable ``OptimState`` a turn and
inlines a traced penalty, the stacked one returns once and carries the
penalty's strength a lane as data (``ROADMAP.md`` D2).

``StackedHostLBFGS`` (the streamed regime) is host code: it drives K of
``lbfgs.LBFGS``'s coroutines and lives here only beside its device twin.
"""

from __future__ import annotations

from collections.abc import Sequence
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

from cycloneml_tpu.ml.optim.lbfgs import LBFGS, OptimState
from cycloneml_tpu.observe import costs, tracing
from cycloneml_tpu.parallel.collectives import (BoundedProgramCache,
                                                dispatch_fused)
from cycloneml_tpu.util.logging import get_logger

logger = get_logger(__name__)

_program_cache = BoundedProgramCache(32)


def _budget_guarded_chunk(name: str, key, prog, args, chunk: int, ctx,
                          build, allow_stream: bool = False):
    """Compile-time memory budget guard for a chunk program: harvest its
    predicted peak HBM (XLA memory_analysis via observe/costs.py), post
    ``MemoryBudgetExceeded`` when it exceeds ``cyclone.memory.budgetFraction``
    × device memory, and degrade to a smaller chunk instead of OOMing.

    ``allow_stream=True`` declares that the CALLER has an out-of-core
    fallback (estimators set ``DeviceLBFGS.oocore_fallback``): when the
    halving bottoms out at chunk 1 with the program still over budget and
    ``cyclone.oocore.mode`` permits, the guard raises
    ``costs.OutOfCoreRequired`` — the estimator catches it and re-routes
    the fit through the streaming epoch engine instead of warn-proceeding
    (or raising under ``budgetAction=raise``). Direct optimizer users
    (no fallback declared) keep the pre-oocore warn/raise contract.

    Much of the footprint is chunk-INDEPENDENT (data arrays, coefficients,
    curvature history), so a proportional guess is only a starting point:
    each candidate is rebuilt via ``build(chunk)`` and RE-ANALYZED, and the
    loop caps every guess at half the previous chunk so it makes progress
    even when shrinking barely helps, terminating at chunk 1 (per-iteration
    dispatches — warn-only proceeds there even if still over budget; there
    is no smaller program to degrade to). Chunk size never changes the
    trajectory (chunk-size-invariance tests), only dispatch granularity.

    Returns ``(chunk, key, prog, fresh)`` — unchanged inputs when the
    guard is disarmed, the backend reports nothing, or the budget holds.
    """
    fresh = None
    conf = getattr(ctx, "conf", None)
    if conf is None or not costs.guard_armed(conf):
        return chunk, key, prog, fresh
    bus = getattr(ctx, "listener_bus", None)
    pid = costs.ensure(name, key, prog, args)
    # degradation comes FIRST even under budgetAction=raise: raising is
    # the terminal escalation once no smaller chunk remains, not a veto
    # on the degradation the guard exists to perform
    verdict = costs.check_budget(pid, conf=conf, bus=bus, allow_raise=False)
    while verdict is not None and verdict.exceeded and chunk > 1:
        new_chunk = min(costs.select_chunk(chunk, verdict.predicted_bytes,
                                           verdict.budget_bytes),
                        max(1, chunk // 2))
        logger.warning(
            "%s: predicted peak HBM %d B/device over budget %d B — "
            "degrading deviceChunk %d -> %d",
            name, verdict.predicted_bytes, verdict.budget_bytes, chunk,
            new_chunk)
        chunk = new_chunk
        key, prog, fresh = build(chunk)
        pid = costs.ensure(name, key, prog, args)
        verdict = costs.check_budget(pid, conf=conf, bus=bus,
                                     allow_raise=False)
    if verdict is not None and verdict.exceeded:
        if allow_stream:
            from cycloneml_tpu.oocore.engine import degrade_allowed
            if degrade_allowed(ctx):
                # graceful at any data:memory ratio: the estimator owns a
                # streaming twin of this fit — hand the decision back up
                # instead of warn-proceeding toward an OOM or raising
                raise costs.OutOfCoreRequired(name, verdict)
        if verdict.action == "raise":
            raise costs.MemoryBudgetError(
                f"{name}: still {verdict.predicted_bytes} bytes/device over "
                f"the {verdict.budget_bytes}-byte budget at deviceChunk "
                f"{chunk} — no smaller program to degrade to "
                f"(cyclone.memory.budgetAction=raise)")
        logger.warning(
            "%s: still %d B/device over the %d B budget at deviceChunk %d — "
            "proceeding (warn-only); the footprint is dominated by "
            "chunk-independent state", name, verdict.predicted_bytes,
            verdict.budget_bytes, chunk)
    return chunk, key, prog, fresh


# -- the parts of one device iteration, written for ONE model ----------------
# Trace-time helpers: ``_build_chunk`` calls them as they are, and
# ``_build_stacked_chunk`` calls ``jax.vmap`` of them, so the stacked
# program's dots lower like the serial program's and the two trajectories
# stay bit-aligned.

def _two_loop(S, Y, k, g, m: int):
    """Two-loop recursion over the ``(m, n)`` ring buffers, of which the
    LAST ``k`` rows are live (newest last): the direction ``-H·g``."""
    import jax
    import jax.numpy as jnp

    idxs_bwd = jnp.arange(m - 1, -1, -1)

    def bwd(q, i):
        valid = i >= m - k
        sy = jnp.dot(Y[i], S[i])
        rho = jnp.where(valid, 1.0 / jnp.where(valid, sy, 1.0), 0.0)
        a = rho * jnp.dot(S[i], q)
        return q - a * Y[i], (a, rho)

    q, (alphas, rhos) = jax.lax.scan(bwd, g, idxs_bwd)
    last_sy = jnp.dot(S[m - 1], Y[m - 1])
    last_yy = jnp.dot(Y[m - 1], Y[m - 1])
    gamma = jnp.where(k > 0, last_sy / jnp.maximum(last_yy, 1e-300), 1.0)
    r = gamma * q

    def fwd(r, inp):
        i, a, rho = inp
        beta = rho * jnp.dot(Y[i], r)
        return r + (a - beta) * S[i], None

    # forward pass visits oldest→newest: reverse the bwd outputs
    r, _ = jax.lax.scan(fwd, r, (idxs_bwd[::-1], alphas[::-1], rhos[::-1]))
    return -r


def _descent_or_reset(d, g, k):
    """Host semantics of a non-descent direction: forget the history and
    take steepest descent. Returns ``(d, k, d·g, was_reset)``."""
    import jax.numpy as jnp

    dg0 = jnp.dot(d, g)
    bad = dg0 >= 0
    return (jnp.where(bad, -g, d), jnp.where(bad, 0, k),
            jnp.where(bad, -jnp.dot(g, g), dg0), bad)


def _init_alpha(first_step, bad, g):
    """First trial step: 1, but the scaled ``min(1, 1/‖g‖)`` on the very
    first iteration of a fit AND on every steepest-descent restart."""
    import jax.numpy as jnp

    gnorm = jnp.sqrt(jnp.maximum(jnp.dot(g, g), 1e-300))
    return jnp.where(first_step | bad, jnp.minimum(1.0, 1.0 / gnorm),
                     g.dtype.type(1.0))


def _push_pair(S, Y, k, s, y, m: int):
    """Roll ``(s, y)`` into the ring buffers if it passes the curvature
    condition (host ``_History.update``): sᵀy > 1e-10·yᵀy."""
    import jax.numpy as jnp

    keep = jnp.dot(s, y) > 1e-10 * jnp.dot(y, y)
    return (jnp.where(keep, jnp.roll(S, -1, axis=0).at[-1].set(s), S),
            jnp.where(keep, jnp.roll(Y, -1, axis=0).at[-1].set(y), Y),
            jnp.where(keep, jnp.minimum(k + 1, m), k))


# what a chunk's convergence code says; 0 at the end of a run is the budget
_REASONS = ("max iterations reached", "function value converged",
            "gradient converged")


def _convergence_code(f, f_new, g_new, x_new, tol, grad_tol):
    """Breeze-style convergence (host ``LBFGS._converged`` minus the
    budget stop, which the drivers apply): 1 function value converged,
    2 gradient converged, 0 neither."""
    import jax.numpy as jnp

    denom = jnp.maximum(jnp.maximum(jnp.abs(f_new), jnp.abs(f)), 1e-6)
    f_conv = jnp.abs(f - f_new) <= tol * denom
    gn = jnp.sqrt(jnp.maximum(jnp.dot(g_new, g_new), 0.0))
    xn = jnp.sqrt(jnp.maximum(jnp.dot(x_new, x_new), 0.0))
    g_conv = gn <= grad_tol * jnp.maximum(xn, 1.0)
    return jnp.where(f_conv, 1, jnp.where(g_conv, 2, 0)).astype(jnp.int32)


def _build_chunk(compiled, l2_t, m: int, K: int, c1: float, c2: float,
                 max_ls: int, cdt: np.dtype, *, n_arrays: int):
    """jit program: K L-BFGS iterations on device.

    Args: (*arrays, coef, S, Y, k_hist, f0, g0, first, ws, tol, grad_tol,
    it_limit, need_init) → (coef, S, Y, k_hist, f, g, losses(K), n_iters,
    evals, converged_code, f0, g0). ``l2_t`` is the penalty's jnp twin
    (``l2_regularization(...).traceable``) — the SAME implementation the
    fused line search inlines, so the two device paths cannot drift.

    The big state operands — the two ``(m, n)`` curvature ring buffers —
    are DONATED (not coef and the gradient: see the end of this function):
    each chunk consumes the previous chunk's output, so the old buffers
    are dead the moment the dispatch leaves the host (graftlint JX009 is
    the static safety net for exactly this discipline). XLA aliases them
    onto the matching outputs, shaving ``2·m·n`` accumulator-width elements
    off the program's peak HBM — visible as an ``hbm_peak_bytes`` drop in
    the cost rollup (`alias_size_in_bytes` is subtracted at the
    observe/costs.py waist). ``n_arrays`` positions the donated argnums
    past the data arrays, which are REUSED across dispatches and must
    never be donated.
    """
    import jax
    import jax.numpy as jnp

    from cycloneml_tpu.ml.optim.loss import wolfe_search

    def lbfgs_chunk(*args):
        (arrays, coef0, S0, Y0, k0, f_in, g_in, first,
         ws, tol, grad_tol, it_limit, need_init) = \
            (args[:-12], *args[-12:])

        def f_and_g(coef):
            out = compiled(*arrays, coef)
            loss = (out["loss"] / ws).astype(cdt)
            grad = (out["grad"] / ws).astype(cdt)
            if l2_t is not None:
                rl, rg = l2_t(coef)
                loss = loss + rl
                grad = grad + rg
            return loss, grad

        def body(carry):
            (coef, S, Y, k, f, g, it, evals, done, losses) = carry
            d, k, dg0, bad = _descent_or_reset(_two_loop(S, Y, k, g, m),
                                               g, k)
            init_alpha = _init_alpha(first & (it == 0), bad, g)

            def phi(alpha):
                v, grad = f_and_g(coef + alpha * d)
                return v, grad, jnp.dot(d, grad)

            alpha, f_new, g_new, ev = wolfe_search(
                phi, g, f, dg0, init_alpha, c1, c2, max_ls, cdt)
            s = alpha * d
            S, Y, k = _push_pair(S, Y, k, s, g_new - g, m)
            code = _convergence_code(f, f_new, g_new, coef + s,
                                     tol, grad_tol)
            losses = losses.at[it].set(f_new)
            return (coef + s, S, Y, k, f_new, g_new, it + 1,
                    evals + ev, code, losses)

        def cond(carry):
            it, done = carry[6], carry[8]
            return (it < jnp.minimum(K, it_limit)) & (done == 0)

        # fused initial evaluation: a fresh fit computes f(x0)/∇f(x0) inside
        # THIS dispatch instead of paying a separate round trip for it
        f0, g0 = jax.lax.cond(need_init,
                              lambda: f_and_g(coef0),
                              lambda: (f_in, g_in))
        evals0 = jnp.where(need_init, 1, 0).astype(jnp.int32)
        losses0 = jnp.full((K,), jnp.nan, cdt)
        init = (coef0, S0, Y0, k0, f0, g0, jnp.int32(0), evals0,
                jnp.int32(0), losses0)
        (coef, S, Y, k, f, g, it, evals, code, losses) = \
            jax.lax.while_loop(cond, body, init)
        return coef, S, Y, k, f, g, losses, it, evals, code, f0, g0

    # donate the S/Y ring buffers (positions past the data arrays): at
    # 2·m·n they dominate the optimizer state's HBM. Who may hold them:
    # the driver, until it hands them to the next dispatch, and the history
    # view of a TERMINAL turn's state (``_history``) — no dispatch follows
    # it, so nothing deletes them under the view. A turn that another
    # dispatch follows gives its state a view of fresh slices instead, cut
    # before the turn ends, so no retained state ever points at a donated
    # buffer (graftlint JX009 is the static net under this).
    # coef/grad are deliberately NOT donated: the generator yields them as
    # OptimState.x/.grad and the resilience retry/checkpoint path retains
    # those states across chunk dispatches — donating them would delete
    # the retained state's buffers behind the caller's back (exactly the
    # JX009 hazard class, one dispatch later)
    return jax.jit(lbfgs_chunk,
                   donate_argnums=(n_arrays + 1, n_arrays + 2))


def _chunk_scalars(out):
    """What the host reads back of a serial chunk's outputs: everything but
    the ``(n,)`` / ``(m, n)`` state, which stays on the device."""
    coef, S, Y, k, f, g, losses, it, evals, code, f0, g0 = out
    return f, losses, it, evals, code, k, f0


class _DeviceHistory:
    """The live curvature pairs of one ``DeviceLBFGS`` turn: rows ``lo:`` of
    the ``S`` and ``Y`` buffers the chunk returned, oldest first. Nothing is
    launched until a row is read — a checkpoint (``OptimState.to_pytree``)
    or a resume; a plain fit never does — and then both sides are cut into
    one device array a row, once (one ``optim.history.read`` instant)."""

    def __init__(self, S, Y, lo: int):
        self._bufs = (S, Y)
        self._lo = lo
        self.n_rows = S.shape[0] - lo
        self._rows = None

    def rows(self, side: int) -> list:
        if self._rows is None:
            tracing.instant("optim.history.read", rows=self.n_rows)
            live = range(self._lo, self._lo + self.n_rows)
            self._rows = tuple([buf[i] for i in live] for buf in self._bufs)
            self._bufs = None
        return self._rows[side]


class _HistoryView(Sequence):
    """``OptimState.hist_s`` / ``hist_y`` of a device turn: reads like the
    list of row slices it stands for (``len``, iteration, negative indices
    and slices, ``list(...)``), over ``_DeviceHistory.rows``."""

    def __init__(self, hist: _DeviceHistory, side: int):
        self._hist, self._side = hist, side

    def __len__(self) -> int:
        return self._hist.n_rows

    def __getitem__(self, i):
        return self._hist.rows(self._side)[i]

    def __iter__(self):
        return iter(self._hist.rows(self._side))


def _history(S, Y, hk: int, terminal: bool):
    """``(hist_s, hist_y, launches)`` for the state of a turn whose chunk
    returned the ring buffers ``S`` / ``Y`` with their last ``hk`` rows
    live. A terminal turn's views read ``S`` / ``Y`` themselves: nothing is
    launched. A turn that another dispatch follows has to let go of them —
    that dispatch DONATES both (``_build_chunk``) — so its views read one
    fresh slice a buffer: 2 launches, where a list of rows took ``2·hk``."""
    if hk == 0:
        return [], [], 0
    lo = S.shape[0] - hk
    if terminal:
        hist, launches = _DeviceHistory(S, Y, lo), 0
    else:
        hist, launches = _DeviceHistory(S[lo:], Y[lo:], 0), 2
    return _HistoryView(hist, 0), _HistoryView(hist, 1), launches


class DeviceLBFGS(LBFGS):
    """L-BFGS running ``chunk`` iterations per device dispatch.

    Works with a ``DistributedLossFunction`` over the dense tier whose L2
    term (if any) is the standardized uniform penalty — the same
    preconditions as the fused line search, checked by the caller
    (LogisticRegression selects this optimizer automatically when they
    hold and no checkpointing is requested; ``cyclone.ml.lbfgs.deviceChunk``
    sizes or disables it).
    """

    def __init__(self, max_iter: int = 100, m: int = 10, tol: float = 1e-6,
                 grad_tol: Optional[float] = None, chunk: int = 8,
                 c1: float = 1e-4, c2: float = 0.9, max_ls: int = 30):
        super().__init__(max_iter, m, tol, grad_tol)
        self.chunk = max(int(chunk), 1)
        self.c1, self.c2, self.max_ls = c1, c2, max_ls
        # set by estimators that own a streaming twin of the fit: lets the
        # budget guard raise OutOfCoreRequired (caught by the estimator)
        # when chunk-halving bottoms out still over budget
        self.oocore_fallback = False

    def iterations(self, f, x0: np.ndarray,
                   resume: Optional[OptimState] = None):
        import jax
        import jax.numpy as jnp

        arrays = f._agg_call.arrays()
        # optimizer state lives in the ACCUMULATOR tier (f32 / f64-under-
        # x64), never the possibly-bf16 data tier X is stored in
        from cycloneml_tpu.dataset.instance import compute_dtype
        cdt = np.dtype(compute_dtype())
        n = len(np.asarray(x0))
        l2_t = getattr(f.l2_reg_fn, "traceable", None) \
            if f.l2_reg_fn is not None else None
        if f.l2_reg_fn is not None and l2_t is None:
            raise ValueError(
                "DeviceLBFGS needs a regularizer with a traceable (jnp) "
                "twin; use the host LBFGS otherwise")
        chunk = self.chunk
        self.effective_chunk = chunk

        def build(k):
            key = ("lbfgs_chunk", f._agg_call.compiled, l2_t, self.m, k,
                   float(self.c1), float(self.c2), int(self.max_ls), cdt.str)
            return (key, *_program_cache.get_or_build(key, lambda: _build_chunk(
                f._agg_call.compiled, l2_t, self.m, k, self.c1, self.c2,
                self.max_ls, cdt, n_arrays=len(arrays))))

        key, prog, fresh = build(chunk)
        S = np.zeros((self.m, n), dtype=cdt)
        Y = np.zeros((self.m, n), dtype=cdt)

        if resume is not None:
            from cycloneml_tpu.ml.optim.lbfgs import _reopen
            state = _reopen(resume, self.max_iter)
            hk = min(len(resume.hist_s), self.m)
            for i, (s_, y_) in enumerate(zip(resume.hist_s[-self.m:],
                                             resume.hist_y[-self.m:])):
                S[self.m - hk + i] = np.asarray(s_)
                Y[self.m - hk + i] = np.asarray(y_)
            k_hist = hk
            # iteration-0 resumes must keep the host path's scaled first
            # step (init_alpha = min(1, 1/||g||))
            first = state.iteration == 0
            need_init = False
            yield state
            if state.converged:
                return
            # jnp.array (copy=True), NOT asarray: a resume state may hand
            # us live device arrays; the copy keeps the generator's
            # working buffers disjoint from whatever the caller retains
            # (coef/grad are never donated — see _build_chunk — but the
            # resume contract shouldn't depend on that)
            coef = jnp.array(state.x, cdt)
            f_d = cdt.type(state.value)
            g_d = jnp.array(state.grad, cdt)
        else:
            # fresh fit: f(x0) is computed INSIDE the first chunk dispatch;
            # the iteration-0 state is yielded when that chunk returns
            state = None
            k_hist = 0
            first = True
            need_init = True
            coef = np.asarray(x0, dtype=cdt)
            f_d = cdt.type(0.0)
            g_d = np.zeros(n, cdt)

        # the first chunk's launch uploads its own host operands: a
        # jnp.asarray / jnp.zeros here would be a device operation apiece,
        # issued from Python while the chip waits for the chunk
        S_d, Y_d = S, Y
        k_d = np.int32(k_hist)
        guarded = False
        while True:
            # one chunk turn is one `optim.iteration` span: argument tuple,
            # dispatch, readback and state build. It closes BEFORE the
            # turn's states are yielded — a span held across a yield would
            # be charged the consumer's time, and an abandoned generator
            # would leave it on the thread's span stack
            start = None
            base_iter = state.iteration if state is not None else 0
            with tracing.span("phase", "optim.iteration",
                              iteration=base_iter) as turn:
                # big state (coef/S/Y/grad) stays ON DEVICE between chunks —
                # only scalars and the per-iteration loss vector come back per
                # dispatch; the full f64 state materializes on yield only when
                # a consumer touches the arrays (np.asarray forces the copy)
                args = (*arrays, coef, S_d, Y_d, k_d, f_d, g_d,
                        np.bool_(first), cdt.type(f.weight_sum),
                        cdt.type(self.tol), cdt.type(self.grad_tol),
                        np.int32(max(self.max_iter - base_iter, 0)),
                        np.bool_(need_init))
                if not guarded:
                    # args are chunk-size-independent, so a degraded program
                    # dispatches the same operands — only K shrinks
                    guarded = True
                    chunk, key, prog, new_fresh = _budget_guarded_chunk(
                        "lbfgs.chunk", key, prog, args, chunk,
                        getattr(f, "_ctx", None), build,
                        allow_stream=self.oocore_fallback)
                    if new_fresh is not None:
                        fresh = new_fresh
                        self.effective_chunk = chunk
                ((coef_d, S_d, Y_d, k_d, f_d, g_d, *_, g0_d),
                 (f_h, losses, it, evals, code, k_h, f0_h)) = dispatch_fused(
                    "lbfgs.chunk", key, prog, args, fresh=fresh,
                    transfer_name="lbfgs.readback", evals_at=3,
                    readback=_chunk_scalars)
                fresh = False
                coef = coef_d
                first = False
                f.n_evals += int(evals)
                f.n_dispatches += 1
                if need_init:
                    start = state = OptimState(
                        x=np.asarray(x0, np.float64).copy(),
                        value=float(f0_h), grad=g0_d,
                        loss_history=[float(f0_h)])
                    need_init = False
                n_new = int(it)
                losses = [float(v) for v in losses[:n_new]]
                iteration = state.iteration + n_new
                # precedence matches host _converged: a budget stop outranks
                # the value/gradient tests (the estimator's non-convergence
                # warning keys off this reason)
                budget_spent = iteration >= self.max_iter
                terminal = bool(budget_spent or code)
                # the history stays on the device, unsliced, until a consumer
                # (the checkpoint/resume path) reads it; only a turn whose
                # S_d/Y_d go on to be donated cuts its rows loose first
                hist_s, hist_y, launches = _history(S_d, Y_d, int(k_h),
                                                    terminal)
                turn.annotate(history_launches=launches)
                state = OptimState(
                    x=coef_d, value=float(f_h), grad=g_d,
                    iteration=iteration,
                    loss_history=state.loss_history + losses,
                    hist_s=hist_s, hist_y=hist_y)
                if hasattr(f, "_ctx") and hasattr(f._ctx, "record_step"):
                    f._ctx.record_step({"loss": state.value,
                                        "chunk_iterations": n_new})
                if terminal:
                    state.converged = True
                    state.converged_reason = _REASONS[
                        0 if budget_spent else int(code)]
                    # terminal state: hand back host-f64 arrays as the host
                    # optimizer does
                    state.x = np.asarray(coef_d, np.float64)
                    state.grad = np.asarray(g_d, np.float64)
            if start is not None:
                yield start   # the iteration-0 state of a fresh fit
            yield state
            if state.converged:
                return
            f_d = cdt.type(f_h)


# -- stacked (model-axis) variant ---------------------------------------------

def _build_stacked_chunk(compiled, m: int, K_iters: int, c1: float, c2: float,
                         max_ls: int, cdt: np.dtype, *, n_arrays: int):
    """jit program: up to ``K_iters`` L-BFGS iterations for a STACK of
    models inside one dispatch.

    Every piece of optimizer state carries a leading model axis — coef
    ``(K, n)``, curvature ring buffers ``(K, m, n)``, per-model f/g/history
    count — and the objective is the stacked aggregation (one psum, model
    axis leading). The strong-Wolfe machine is ``loss.wolfe_search`` in its
    batched form: each model walks its own bracket+zoom trajectory in
    lockstep evaluation steps and freezes when ITS search terminates.
    Per-model convergence codes freeze early-converged models (state
    selected through unchanged) instead of stopping — or lockstepping —
    the rest; the chunk ends when every model converged or the iteration
    budget is spent.

    The L2 penalty is runtime data (``reg (K,)`` per model + the shared
    per-coordinate ``l2_scale``), NOT baked in, so one compiled program
    serves every reg vector (CV folds over a λ grid reuse one compile).

    Args: ``(*arrays, coef, S, Y, k_hist, f0, g0, first, ws, reg, l2s,
    tol, grad_tol, it_limit, need_init, code_in)`` →
    ``(coef, S, Y, k_hist, f, g, losses (K, K_iters), steps, iters (K,),
    evals (K,), evals_global, code (K,), f_init)``. ``code_in`` carries the
    previous chunk's per-model convergence codes back in — a model frozen
    in chunk t must START chunk t+1 frozen, or every chunk boundary would
    un-freeze it for one spurious iteration and the result would depend on
    the chunk size.
    """
    import jax
    import jax.numpy as jnp

    from cycloneml_tpu.ml.optim.loss import wolfe_search

    two_loop = jax.vmap(lambda S, Y, k, g: _two_loop(S, Y, k, g, m))
    descent_or_reset = jax.vmap(_descent_or_reset)
    init_alpha_of = jax.vmap(_init_alpha, in_axes=(None, 0, 0))
    push_pair = jax.vmap(lambda S, Y, k, s, y: _push_pair(S, Y, k, s, y, m))
    convergence_code = jax.vmap(_convergence_code,
                                in_axes=(0, 0, 0, 0, None, None))
    row_dot = jax.vmap(jnp.dot)

    def lbfgs_stacked_chunk(*args):
        (arrays, coef0, S0, Y0, k0, f_in, g_in, first,
         ws, reg, l2s, tol, grad_tol, it_limit, need_init, code_in) = \
            (args[:-15], *args[-15:])

        def f_and_g(coef):
            out = compiled(*arrays, coef)
            loss = (out["loss"] / ws).astype(cdt)
            grad = (out["grad"] / ws).astype(cdt)
            # runtime-data L2 (same math as l2_regularization's traceable
            # twin, vectorized over the model axis). A vmapped dot, not a
            # masked sum-reduce: it lowers like the serial twin's
            # ``jnp.dot(beta, beta)`` (zero intercept products are exact),
            # so stacked and serial trajectories stay bit-aligned instead
            # of flipping iterations at the convergence-tol boundary.
            loss = loss + 0.5 * reg * row_dot(coef * l2s[None, :], coef)
            grad = grad + reg[:, None] * coef * l2s[None, :]
            return loss, grad

        def body(carry):
            (coef, S, Y, k, f, g, step, iters, ev_pm, ev_g, code,
             losses) = carry
            live = code == 0
            d, k, dg0, bad = descent_or_reset(two_loop(S, Y, k, g), g, k)
            init_alpha = init_alpha_of(first & (step == 0), bad, g)

            def phi(alpha):
                v, grad = f_and_g(coef + alpha[:, None] * d)
                return v, grad, row_dot(d, grad)

            alpha, f_new, g_new, ev = wolfe_search(
                phi, g, f, dg0, init_alpha, c1, c2, max_ls, cdt,
                active=live)
            s_vec = alpha[:, None] * d
            x_new = coef + s_vec
            S_new, Y_new, k_new = push_pair(S, Y, k, s_vec, g_new - g)
            code_new = convergence_code(f, f_new, g_new, x_new,
                                        tol, grad_tol)
            losses = losses.at[:, step].set(
                jnp.where(live, f_new, jnp.nan).astype(cdt))
            # per-model freeze: a converged model's state is selected
            # through unchanged
            return (jnp.where(live[:, None], x_new, coef),
                    jnp.where(live[:, None, None], S_new, S),
                    jnp.where(live[:, None, None], Y_new, Y),
                    jnp.where(live, k_new, k),
                    jnp.where(live, f_new, f),
                    jnp.where(live[:, None], g_new, g),
                    step + 1,
                    iters + live.astype(jnp.int32),
                    ev_pm + ev,
                    ev_g + jnp.max(ev),
                    jnp.where(live, code_new, code),
                    losses)

        def cond(carry):
            step, code = carry[6], carry[10]
            return (step < jnp.minimum(K_iters, it_limit)) \
                & jnp.any(code == 0)

        K = coef0.shape[0]
        f_init, g_init = jax.lax.cond(need_init,
                                      lambda: f_and_g(coef0),
                                      lambda: (f_in, g_in))
        ev0 = jnp.where(need_init, 1, 0).astype(jnp.int32)
        init = (coef0, S0, Y0, k0, f_init, g_init, jnp.int32(0),
                jnp.zeros((K,), jnp.int32), jnp.full((K,), ev0),
                ev0, code_in,
                jnp.full((K, K_iters), jnp.nan, cdt))
        (coef, S, Y, k, f, g, step, iters, ev_pm, ev_g, code, losses) = \
            jax.lax.while_loop(cond, body, init)
        return (coef, S, Y, k, f, g, losses, step, iters, ev_pm, ev_g,
                code, f_init)

    # donate the FULL stacked state — coef (K,n), S/Y (K,m,n), g (K,n):
    # unlike the serial generator, minimize() is not resumable and never
    # yields mid-run, so these buffers cannot be retained by a caller —
    # the driver rebinds all four from the outputs every chunk and the
    # inputs really are dead on dispatch; the (K,m,n) ring buffers
    # dominate the optimizer state's HBM at stacked widths
    return jax.jit(lbfgs_stacked_chunk, donate_argnums=(
        n_arrays, n_arrays + 1, n_arrays + 2, n_arrays + 5))


@dataclass
class StackedOptimResult:
    """Terminal state of one stacked fit: every field carries the model
    axis; histories/reasons are per model (the per-model analog of the
    serial path's OptimState + converged_reason)."""

    x: np.ndarray                       # (K, n) float64
    values: np.ndarray                  # (K,)
    iterations: np.ndarray              # (K,) int — per-model LIVE iters
    converged_reasons: List[str] = field(default_factory=list)
    loss_histories: List[List[float]] = field(default_factory=list)
    evals: Optional[np.ndarray] = None  # (K,) per-model loss/grad evals


class StackedDeviceLBFGS:
    """Chunked L-BFGS over a stack of K models sharing one design matrix.

    The model-axis variant of :class:`DeviceLBFGS`: one dispatch advances
    ALL models up to ``chunk`` iterations (batched objective = one psum with
    a leading model axis), per-model convergence masks freeze
    early-converged models on device, and the host sees one small readback
    per chunk. Preconditions match the serial chunked path: dense replicated
    tier, standardized-or-original-space uniform L2 carried as runtime data
    (``StackedDistributedLossFunction.reg``/``l2_scale``), no bounds/L1.
    """

    def __init__(self, max_iter: int = 100, m: int = 10, tol: float = 1e-6,
                 grad_tol: Optional[float] = None, chunk: int = 8,
                 c1: float = 1e-4, c2: float = 0.9, max_ls: int = 30):
        self.max_iter = max_iter
        self.m = m
        self.tol = tol
        self.grad_tol = grad_tol if grad_tol is not None else tol
        self.chunk = max(int(chunk), 1)
        self.c1, self.c2, self.max_ls = c1, c2, max_ls

    def minimize(self, f, x0: np.ndarray) -> StackedOptimResult:
        """``f`` is a ``StackedDistributedLossFunction``; ``x0`` is the
        (K, n_coef) stacked start point."""
        x0 = np.asarray(x0, dtype=np.float64)
        K, n = x0.shape
        if K != f.n_models:
            raise ValueError(
                f"x0 stacks {K} models but the loss carries {f.n_models}")
        arrays = f._agg_call.arrays()
        from cycloneml_tpu.dataset.instance import compute_dtype
        cdt = np.dtype(compute_dtype())  # accumulator tier, == w's dtype
        chunk = self.chunk
        self.effective_chunk = chunk

        def build(kc):
            key = ("stacked_lbfgs_chunk", f._agg_call.compiled, self.m,
                   kc, float(self.c1), float(self.c2), int(self.max_ls),
                   cdt.str)
            return (key, *_program_cache.get_or_build(
                key, lambda: _build_stacked_chunk(
                    f._agg_call.compiled, self.m, kc, self.c1, self.c2,
                    self.max_ls, cdt, n_arrays=len(arrays))))

        key, prog, fresh = build(chunk)

        # the first chunk's launch uploads its own host operands (as
        # DeviceLBFGS's does): a jnp.asarray / jnp.zeros here would be a
        # device operation apiece, issued from Python before the chunk
        coef = x0.astype(cdt)
        S_d = np.zeros((K, self.m, n), cdt)
        Y_d = np.zeros((K, self.m, n), cdt)
        k_d = np.zeros((K,), np.int32)
        f_d = np.zeros((K,), cdt)
        g_d = np.zeros((K, n), cdt)
        reg_d = f.reg.astype(cdt)
        l2s = (f.l2_scale if f.l2_scale is not None else np.zeros(n))
        l2s_d = l2s.astype(cdt)
        first = True  # this chunk evaluates f(x0) and scales its first step
        total_iter = 0
        iters_total = np.zeros(K, dtype=np.int64)
        evals_total = np.zeros(K, dtype=np.int64)
        histories: List[List[float]] = [[] for _ in range(K)]
        code_h = np.zeros(K, dtype=np.int64)
        while True:
            # one chunk turn is one `optim.iteration` span, as the serial
            # driver's: argument tuple, dispatch, readback, bookkeeping
            with tracing.span("phase", "optim.iteration",
                              iteration=total_iter, n_models=K,
                              active_models=int((code_h == 0).sum())):
                args = (*arrays, coef, S_d, Y_d, k_d, f_d, g_d,
                        np.bool_(first), cdt.type(f.weight_sum), reg_d,
                        l2s_d, cdt.type(self.tol), cdt.type(self.grad_tol),
                        np.int32(max(self.max_iter - total_iter, 0)),
                        np.bool_(first), code_h.astype(np.int32))
                if first:
                    chunk, key, prog, new_fresh = _budget_guarded_chunk(
                        "lbfgs.stacked_chunk", key, prog, args, chunk,
                        getattr(f, "_ctx", None), build)
                    if new_fresh is not None:
                        fresh = new_fresh
                        self.effective_chunk = chunk
                # the (K, ·) state stays on the device; the rest comes back
                ((coef, S_d, Y_d, k_d, f_d, g_d, *_),
                 (losses, steps, iters, ev_pm, ev_g, code_h, f0_h)) = \
                    dispatch_fused(
                        "lbfgs.stacked_chunk", key, prog, args, fresh=fresh,
                        transfer_name="lbfgs.readback", evals_at=4,
                        readback=lambda out: out[6:], n_models=K)
                fresh = False
                f.n_evals += int(ev_g)
                f.n_dispatches += 1
                if first:
                    for kk in range(K):
                        histories[kk].append(float(f0_h[kk]))
                    first = False
                for kk in range(K):
                    for v in losses[kk, :int(steps)]:
                        if not np.isnan(v):
                            histories[kk].append(float(v))
                iters_total += np.asarray(iters, dtype=np.int64)
                evals_total += np.asarray(ev_pm, dtype=np.int64)
                total_iter += int(steps)
                if hasattr(f, "_ctx") and hasattr(f._ctx, "record_step"):
                    f._ctx.record_step({
                        "loss": float(np.nanmean(
                            losses[:, :max(int(steps), 1)]))
                        if int(steps) else float(np.mean(f0_h)),
                        "chunk_iterations": int(steps), "n_models": K})
            if (code_h != 0).all() or total_iter >= self.max_iter:
                break
        # a model still live when the budget ran out stopped on the budget
        # (the estimator's non-convergence warning keys off this reason)
        return StackedOptimResult(
            x=np.asarray(coef, dtype=np.float64),
            values=np.asarray(f_d, dtype=np.float64),
            iterations=iters_total,
            converged_reasons=[_REASONS[int(c)] for c in code_h],
            loss_histories=histories,
            evals=evals_total)


# -- streamed stacked L-BFGS: K host optimizers, one epoch per round ----------

class StackedHostLBFGS:
    """Host-driven L-BFGS over a stack of K models whose objective is
    EXPENSIVE per evaluation and cheap per model — the streamed regime,
    where one evaluation is a whole double-buffered epoch.

    K serial optimizers run as coroutines (``LBFGS._minimize_co``); each
    round stacks their pending trial points into one ``(K, n)`` matrix
    and makes ONE call to the stacked objective
    (``StackedStreamingLossFunction`` — one epoch serves every model),
    then feeds each model its row back. A converged model's slot keeps
    repeating its terminal point (vmapped programs take no ragged axis;
    the replies are ignored), so total epochs = max over models of that
    model's serial eval count, not the sum — the per-model epoch cost
    drops ~K× for homogeneous grids. Device-chunked state never appears:
    unlike :class:`StackedDeviceLBFGS` this driver is pure host float64,
    which is what lets it ride an objective that is itself a host fold.
    """

    def __init__(self, max_iter: int = 100, m: int = 10, tol: float = 1e-6,
                 grad_tol: Optional[float] = None, c1: float = 1e-4,
                 c2: float = 0.9, max_ls: int = 30):
        self.max_iter = max_iter
        self.m = m
        self.tol = tol
        self.grad_tol = grad_tol if grad_tol is not None else tol
        self.c1, self.c2, self.max_ls = c1, c2, max_ls

    def minimize(self, f, x0: np.ndarray) -> StackedOptimResult:
        """``f`` maps a ``(K, n)`` stack to ``((K,), (K, n))`` host-f64
        loss/grad (the ``StackedStreamingLossFunction`` contract)."""
        x0 = np.asarray(x0, dtype=np.float64)
        K, n = x0.shape
        opt = LBFGS(self.max_iter, self.m, self.tol, self.grad_tol)
        gens = [opt._minimize_co(x0[kk], self.c1, self.c2, self.max_ls)
                for kk in range(K)]
        pending = np.zeros((K, n))
        done: List[Optional[OptimState]] = [None] * K
        evals = np.zeros(K, dtype=np.int64)
        for kk, gen in enumerate(gens):
            pending[kk] = next(gen)  # prime: first yield is the start point
        rounds = 0
        while any(d is None for d in done):
            with tracing.span("dispatch", "lbfgs.stacked_host",
                              n_models=K, round=rounds,
                              live=sum(d is None for d in done)):
                L, G = f(pending)
            rounds += 1
            for kk, gen in enumerate(gens):
                if done[kk] is not None:
                    continue  # frozen slot: reply ignored
                evals[kk] += 1
                try:
                    pending[kk] = gen.send(
                        (float(L[kk]), np.asarray(G[kk], dtype=np.float64)))
                except StopIteration as fin:
                    done[kk] = fin.value
                    pending[kk] = fin.value.x  # terminal point rides along
        return StackedOptimResult(
            x=np.stack([d.x for d in done]),
            values=np.asarray([d.value for d in done], dtype=np.float64),
            iterations=np.asarray([d.iteration for d in done],
                                  dtype=np.int64),
            converged_reasons=[d.converged_reason for d in done],
            loss_histories=[list(d.loss_history) for d in done],
            evals=evals)
