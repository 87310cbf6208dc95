"""Distributed loss function — the treeAggregate gradient reduction.

Equivalent of ``RDDLossFunction`` (ref: ml/optim/loss/RDDLossFunction.scala:47,
whose ``calculate:56`` broadcasts coefficients and ``treeAggregate:61``s an
aggregator over the data) plus ``DifferentiableRegularization`` (L2Reg): here
the broadcast is the replicated ``coef`` argument of a jit-compiled shard_map
program and the reduction is a hierarchical psum — one XLA program per
L-BFGS iteration instead of one Spark job (SURVEY §3.3).
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import numpy as np

from cycloneml_tpu.dataset.dataset import InstanceDataset
from cycloneml_tpu.observe import tracing
from cycloneml_tpu.parallel import collectives


def _weight_sum_agg(*arrs):
    """w is the last sharded array for both the dense (x, y, w) and sparse
    (indices, values, y, w) dataset tiers."""
    import jax.numpy as jnp
    return {"ws": jnp.sum(arrs[-1])}


class DistributedLossFunction:
    """Callable (coef) -> (loss, grad) in float64 host space.

    - ``agg``: a block aggregator from ``aggregators`` (sums, not means)
    - ``l2_reg_fn``: optional (coef) -> (loss, grad) driver-side penalty
      (≈ L2RegFunction; handles featuresStd / intercept exclusion)
    - normalisation by total weight matches the reference (loss and grad are
      divided by weightSum inside the aggregator's merge in Spark; we divide
      once at the end — same value).
    """

    def __init__(self, dataset: InstanceDataset, agg: Callable,
                 l2_reg_fn: Optional[Callable] = None,
                 weight_sum: Optional[float] = None,
                 extra_args: tuple = ()):
        # ``extra_args``: replicated device arrays the aggregator takes
        # BEFORE the coefficients (e.g. inv_std/scaled_mean for the
        # fold-standardization-into-the-read aggregators). They join the
        # fixed argument tuple so DeviceLBFGS's fused program threads them
        # through unchanged and the compiled program stays dataset-generic.
        base = dataset.tree_aggregate_fn(agg)
        if extra_args:
            extra = tuple(extra_args)

            # delegate to base per call (NOT a snapshot tuple): base reads
            # ds.x/ds.y/ds.w through their properties each invocation, so
            # a StorageManager-evicted dataset transparently restores
            # instead of dispatching on deleted buffers
            def call(*coef):
                return base(*extra, *coef)

            call.compiled = base.compiled
            call.arrays = lambda: base.arrays() + extra
            self._agg_call = call
        else:
            self._agg_call = base
        self._ctx = dataset.ctx
        self.l2_reg_fn = l2_reg_fn
        if weight_sum is None:
            # _weight_sum_agg is module-level so its program is cached across
            # fits (a fresh lambda here cost a full XLA recompile per fit)
            ws = dataset.tree_aggregate_fn(_weight_sum_agg)()
            weight_sum = float(ws["ws"])
        self.weight_sum = weight_sum
        # what the sums are accumulated in on the device: the resolution of
        # the loss this returns (OWLQN's line search reads it)
        from cycloneml_tpu.dataset.instance import compute_dtype
        self.accumulator_dtype = np.dtype(compute_dtype())
        self.n_evals = 0
        self.n_dispatches = 0  # host->device dispatch round trips

    def __call__(self, coef: np.ndarray) -> Tuple[float, np.ndarray]:
        self.n_evals += 1
        self.n_dispatches += 1
        import jax
        with tracing.span("dispatch", "loss.eval", evals=1):
            out_dev = self._agg_call(coef)  # 'collective' span inside
            with tracing.span("transfer", "loss.readback") as tsp:
                out = jax.device_get(out_dev)  # one transfer, not two
                tsp.annotate_bytes(out)
        loss = float(out["loss"]) / self.weight_sum
        grad = np.asarray(out["grad"], dtype=np.float64) / self.weight_sum
        if self.l2_reg_fn is not None:
            rl, rg = self.l2_reg_fn(coef)
            loss += float(rl)
            grad += np.asarray(rg, dtype=np.float64)
        if hasattr(self._ctx, "record_step"):
            # one distributed gradient evaluation ≈ one stage's TaskMetrics
            self._ctx.record_step({"loss": loss})
        return loss, grad

    # -- device-resident line search ------------------------------------------
    def device_line_search(self, x: np.ndarray, direction: np.ndarray,
                           value: float, grad: np.ndarray, dg0: float,
                           init_alpha: float, c1: float, c2: float,
                           max_evals: int):
        """Run the ENTIRE strong-Wolfe search in one XLA dispatch.

        The host path pays one dispatch plus readbacks per φ(α) evaluation
        (~30 dispatch round trips per L-BFGS iteration); here the
        bracket+zoom state machine is a ``lax.while_loop`` whose φ is the
        inlined psum aggregation, so a whole iteration is one dispatch and
        one small readback. The reference pays one full Spark *job* per
        evaluation (ref RDDLossFunction.scala:56) — this is the structure we
        beat, not emulate. ``value`` / ``grad`` / ``dg0`` are the objective,
        its gradient and the slope AT ``x``: the gradient rides along
        because ``wolfe_search`` hands it back with the empty step when no
        trial lowered the value. Returns ``(alpha, value_new, grad_new)`` with the
        host-f64 types the optimizer expects, or ``None`` when regularization
        has no traceable twin (caller falls back to the host search).
        """
        if self.l2_reg_fn is not None and \
                not hasattr(self.l2_reg_fn, "traceable"):
            return None
        from cycloneml_tpu.parallel import faults

        # the fused program dispatches the aggregation from INSIDE one XLA
        # program, so the tree_aggregate-level injection points never see
        # these steps — fire them here, once per fused dispatch
        # (preempt_notice then multihost.host first, mirroring
        # _instrument_dispatch: a decommission notice precedes the loss
        # it announces, and a dead peer host surfaces as the collective
        # that cannot complete)
        faults.inject("multihost.preempt_notice")
        faults.inject("multihost.host")
        faults.inject("collectives.step")
        arrays = self._agg_call.arrays()
        # line-search arithmetic lives in the ACCUMULATOR tier — f32 on
        # TPU, f64 under x64 tests (matching the host path exactly) — never
        # the (possibly bf16) data tier: optimizer state at storage width
        # would destroy the Wolfe tests' resolution
        from cycloneml_tpu.dataset.instance import compute_dtype
        cdt = np.dtype(compute_dtype())
        l2_t = getattr(self.l2_reg_fn, "traceable", None) \
            if self.l2_reg_fn is not None else None
        # the cache is module-level and keyed on PROGRAM identity (the cached
        # aggregation program + the cached l2 traceable): repeated fits with
        # the same configuration reuse one compiled executable instead of
        # paying a ~30 s TPU recompile per fit. weight_sum is a runtime
        # argument for the same reason — baking it in would fork the cache.
        key = (self._agg_call.compiled, l2_t, float(c1), float(c2),
               int(max_evals), cdt.str)
        # (bounded: standardization=False fits key on a fresh l2 fn per fit
        # and would otherwise grow it without limit)
        fn, fresh = _ls_program_cache.get_or_build(
            key, lambda: _build_line_search(self._agg_call.compiled, l2_t,
                                            c1, c2, max_evals, cdt))
        args = (*arrays,
                np.asarray(x, dtype=cdt),
                np.asarray(direction, dtype=cdt),
                cdt.type(value), np.asarray(grad, dtype=cdt),
                cdt.type(dg0), cdt.type(init_alpha),
                cdt.type(self.weight_sum))
        if fresh and tracing.full_active() is not None:
            # a raise-mode budget guard must fire before the oversized
            # program executes (the harvest is registry-cached: the
            # dispatch below finds it done)
            from cycloneml_tpu.observe import costs
            costs.check_budget(
                costs.ensure("lbfgs.line_search", key, fn, args))
        _, (alpha, v, g, evals) = collectives.dispatch_fused(
            "lbfgs.line_search", key, fn, args, fresh=fresh,
            transfer_name="line_search.readback", evals_at=3)
        self.n_evals += int(evals)
        self.n_dispatches += 1
        loss = float(v)
        if hasattr(self._ctx, "record_step"):
            self._ctx.record_step({"loss": loss, "line_search_evals": int(evals)})
        return float(alpha), loss, np.asarray(g, dtype=np.float64)


def stacked_l2_scale(d: int, n_coef: int,
                     features_std: Optional[np.ndarray] = None,
                     standardize: bool = True) -> np.ndarray:
    """Per-coordinate scale for the stacked L2 penalty
    ``0.5 · reg_k · Σ_j coef_kj² · scale_j`` — the runtime-argument form of
    :func:`l2_regularization` (feature coords 1 — or 1/std² when
    ``standardization=false`` computes the penalty in original space —
    intercept coords 0), so ONE compiled stacked program serves every
    per-model reg vector instead of forking the program cache per λ."""
    scale = np.zeros(n_coef)
    if standardize or features_std is None:
        scale[:d] = 1.0
    else:
        s = np.where(features_std > 0, features_std, 1.0)
        scale[:d] = 1.0 / (s * s)
    return scale


def stacked_host_l2(loss: np.ndarray, grad: np.ndarray,
                    coef_stack: np.ndarray, reg: np.ndarray,
                    l2_scale: Optional[np.ndarray]):
    """Apply the per-model L2 penalty to a stacked host-f64 (loss, grad)
    pair: ``loss_k += 0.5·reg_k·Σ_j coef_kj²·scale_j``. Runtime data, not
    program structure — one compiled stacked program serves every reg
    vector. Shared by the in-core stacked loss and its streamed twin so
    their penalties are bit-identical for the parity suites."""
    if l2_scale is None or not np.any(reg > 0):
        return loss, grad
    cs = np.asarray(coef_stack, dtype=np.float64)
    loss = loss + 0.5 * reg * np.sum(cs * cs * l2_scale[None, :], axis=1)
    grad = grad + reg[:, None] * cs * l2_scale[None, :]
    return loss, grad


class StackedDistributedLossFunction:
    """Model-axis twin of :class:`DistributedLossFunction`.

    Callable ``(coef_stack (K, n_coef)) -> (loss (K,), grad (K, n_coef))``
    in host float64. ``agg`` is an aggregator whose coefficients and sums
    carry the model axis — ``aggregators.stacked_binary_logistic_scaled`` /
    ``…_pallas_scaled`` over the dataset as it is (model j's label made
    inside the pass from the dataset's own ``(n,)`` label vector) — so K
    independent binomial objectives over ONE shared design matrix evaluate
    as a single SPMD program: one psum with a leading model axis, never K
    rendezvous-prone concurrent programs (the PR-2 deadlock).

    The L2 term is carried as runtime data — per-model ``reg`` ``(K,)`` plus
    the shared per-coordinate ``l2_scale`` from :func:`stacked_l2_scale` —
    both host-side here and inlined by the stacked chunk program, keeping
    program-cache identity across reg vectors (CV folds reuse one compile).
    """

    def __init__(self, dataset: InstanceDataset, agg: Callable,
                 n_models: int, reg: Optional[np.ndarray] = None,
                 l2_scale: Optional[np.ndarray] = None,
                 weight_sum: Optional[float] = None,
                 extra_args: tuple = ()):
        base = dataset.tree_aggregate_fn(agg)
        if extra_args:
            extra = tuple(extra_args)

            def call(*coef):
                return base(*extra, *coef)

            call.compiled = base.compiled
            call.arrays = lambda: base.arrays() + extra
            self._agg_call = call
        else:
            self._agg_call = base
        self._ctx = dataset.ctx
        self.n_models = int(n_models)
        self.reg = (np.zeros(self.n_models) if reg is None
                    else np.asarray(reg, dtype=np.float64))
        self.l2_scale = (None if l2_scale is None
                         else np.asarray(l2_scale, dtype=np.float64))
        if weight_sum is None:
            ws = dataset.tree_aggregate_fn(_weight_sum_agg)()
            weight_sum = float(ws["ws"])
        self.weight_sum = weight_sum
        self.n_evals = 0        # batched objective evaluations (each covers
        self.n_dispatches = 0   # all K models); host->device round trips

    def __call__(self, coef_stack: np.ndarray):
        self.n_evals += 1
        self.n_dispatches += 1
        import jax
        with tracing.span("dispatch", "loss.eval", evals=1,
                          n_models=self.n_models):
            out_dev = self._agg_call(coef_stack)
            with tracing.span("transfer", "loss.readback") as tsp:
                out = jax.device_get(out_dev)
                tsp.annotate_bytes(out)
        loss = np.asarray(out["loss"], dtype=np.float64) / self.weight_sum
        grad = np.asarray(out["grad"], dtype=np.float64) / self.weight_sum
        loss, grad = stacked_host_l2(loss, grad, coef_stack, self.reg,
                                     self.l2_scale)
        if hasattr(self._ctx, "record_step"):
            # one batched gradient evaluation ≈ one stage over all K models
            self._ctx.record_step({"loss": float(np.mean(loss)),
                                   "n_models": self.n_models})
        return loss, grad


_ls_program_cache = collectives.BoundedProgramCache(64)


def _build_line_search(compiled, l2_t, c1: float, c2: float, max_evals: int,
                       cdt: np.dtype):
    import jax
    import jax.numpy as jnp

    def lbfgs_line_search(*args):
        arrays = args[:-7]
        x0, dirn, value0, grad0, dg0, init_alpha, ws = args[-7:]
        # divide by ws, matching the host path's `loss / weight_sum`
        # bit-for-bit (a reciprocal-multiply drifts in the last ulp,
        # which 40 unregularized iterations amplify)

        def phi(alpha):
            coef = x0 + alpha * dirn
            out = compiled(*arrays, coef)
            loss = (out["loss"] / ws).astype(cdt)
            grad = (out["grad"] / ws).astype(cdt)
            if l2_t is not None:
                rl, rg = l2_t(coef)
                loss = loss + rl
                grad = grad + rg
            return loss, grad, jnp.dot(dirn, grad)

        return wolfe_search(phi, grad0, value0, dg0, init_alpha,
                            c1, c2, max_evals, cdt)

    return jax.jit(lbfgs_line_search)


def _select_bcast(mask, a, b):
    """``jnp.where`` with the mask right-padded to the operand rank — lets
    one boolean select both scalar state fields and gradient pytree leaves
    (rank 0/1 unbatched; leading model axis + trailing coord axes when the
    search runs batched). Ranks are static trace-time metadata."""
    import jax.numpy as jnp
    extra = a.ndim - mask.ndim
    if extra > 0:
        mask = mask.reshape(mask.shape + (1,) * extra)
    return jnp.where(mask, a, b)


def wolfe_search(phi, g0, value0, dg0, init_alpha,
                 c1: float, c2: float, max_evals: int, cdt, active=None):
    """Traced strong-Wolfe bracket+zoom (Nocedal-Wright alg 3.5/3.6) as a
    ``lax.while_loop`` state machine — the device-resident twin of the host
    search, ``lbfgs._wolfe_search``.

    ``phi(alpha) -> (value, grad_pytree, dg)``; ``g0`` is the gradient
    pytree AT THE START, φ's at α = 0 (any sharding — the feature-sharded
    path threads a (beta_sharded, b0_scalar) pair through unchanged).
    Returns ``(alpha, value, grad_pytree, evals)``: a point whose value is
    NOT above ``value0``, or the empty step ``(0, value0, g0)``.

    Batched (model-axis) form: when ``value0``/``dg0``/``init_alpha`` carry a
    leading ``(K,)`` axis (and ``g0``'s leaves a leading ``K``), each model
    runs its OWN bracket+zoom trajectory in lockstep evaluation steps — one
    batched ``phi`` per step — and models whose search terminates freeze
    (state selected through, no further effect) instead of forcing the rest
    to stop. ``active`` (``(K,)`` bool, optional) marks models that must not
    search at all (already-converged models in a stacked fit): they start in
    the done phase with zero evals. Per-model ``evals`` counts only live
    steps, so the batched search's global step count is ``evals.max()``.

    The search also ends — on the trial in hand — once it would go on to
    SMALLER steps while the first-order decrease on offer, ``α·|dg0|``, is
    already below what the objective resolves: ``eps·|value0|``, ``eps`` of
    the accumulator ``cdt`` (the rule ``lbfgs.OWLQN._search`` has had since
    PR 30). A trial that fails Armijo there fails by rounding, every α the
    zoom would bisect to offers less, and without the exit it ran out all
    ``max_evals`` of them — at the last iteration of a float32 fit, where
    the caller's ``|Δf|`` test then stops the run anyway; in a stacked fit
    one such lane held every other lane's sweep for 30 evaluations (PR 41,
    on the v5e: 42 shared sweeps for 12).

    A strong-Wolfe point lowers the value. A search that ended otherwise —
    on the resolution exit, or on its budget — may hold a trial that reads
    ABOVE ``value0``; it then returns the empty step instead, for every
    caller alike: nothing moves, the curvature pair is ``(0, 0)`` (which
    ``_push_pair`` / ``_History.update`` refuse) and the caller's ``|Δf|``
    test reads 0, so no objective history this search feeds ever rises.
    """
    import jax
    import jax.numpy as jnp

    value0 = jnp.asarray(value0, cdt)
    resolution = float(np.finfo(cdt).eps) * jnp.abs(value0)
    zero = jnp.zeros(jnp.shape(value0), cdt)
    izero = jnp.zeros(jnp.shape(value0), jnp.int32)
    phase0 = izero if active is None else \
        jnp.where(active, 0, 2).astype(jnp.int32)
    state = dict(
        phase=phase0,   # 0 bracket, 1 zoom, 2 done
        evals=izero, bi=izero, zj=izero,
        alpha_prev=zero, v_prev=value0 + zero, d_prev=dg0 + zero,
        alpha_next=init_alpha + zero,
        lo=zero, hi=zero,
        v_lo=zero, d_lo=zero,
        v_hi=zero,
        res_alpha=zero, res_v=value0 + zero,
        res_g=g0,
    )

    def cond(s):
        return jnp.any(s["phase"] < 2)

    def body(s):
        in_bracket = s["phase"] == 0
        alpha = jnp.where(in_bracket, s["alpha_next"],
                          0.5 * (s["lo"] + s["hi"]))
        v, g, dg = phi(alpha)
        armijo_fail = v > value0 + c1 * alpha * dg0
        wolfe_ok = jnp.abs(dg) <= -c2 * dg0
        # what this α still offers is under the accumulator's resolution
        unresolved = alpha * jnp.abs(dg0) <= resolution

        # -- bracket phase (Nocedal-Wright alg 3.5) --
        b_zoom_a = armijo_fail | ((s["bi"] > 0) & (v >= s["v_prev"]))
        b_done = (~b_zoom_a) & wolfe_ok
        b_zoom_b = (~b_zoom_a) & (~b_done) & (dg >= 0)
        b_cont = ~(b_zoom_a | b_done | b_zoom_b)
        # budget exhausted while still bracketing: accept current eval
        # (the host path's fallback re-evaluates at the next doubled α;
        # this branch is unreachable in practice — 30 doublings)
        b_exhaust = b_cont & (s["bi"] + 1 >= max_evals)
        enter_zoom = b_zoom_a | b_zoom_b
        # a zoom from here only goes to smaller steps: end on this trial
        b_unresolved = enter_zoom & unresolved

        # -- zoom phase (alg 3.6) --
        z_hi_a = armijo_fail | (v >= s["v_lo"])
        z_done = (~z_hi_a) & wolfe_ok
        z_flip = (~z_hi_a) & (~z_done) & (dg * (s["hi"] - s["lo"]) >= 0)
        z_hi = jnp.where(z_hi_a, alpha, jnp.where(z_flip, s["lo"], s["hi"]))
        z_v_hi = jnp.where(z_hi_a, v, jnp.where(z_flip, s["v_lo"], s["v_hi"]))
        z_lo = jnp.where(z_hi_a, s["lo"], alpha)
        z_v_lo = jnp.where(z_hi_a, s["v_lo"], v)
        z_d_lo = jnp.where(z_hi_a, s["d_lo"], dg)
        z_exhaust = (jnp.abs(z_hi - z_lo) < 1e-12) | \
            (s["zj"] + 1 >= max_evals)

        phase = jnp.where(
            in_bracket,
            jnp.where(b_done | b_exhaust | b_unresolved, 2,
                      jnp.where(enter_zoom, 1, 0)),
            jnp.where(z_done | z_exhaust | unresolved, 2, 1)).astype(
                jnp.int32)

        # zoom bracket: freshly entered from bracket phase, or updated
        lo = jnp.where(in_bracket,
                       jnp.where(b_zoom_a, s["alpha_prev"], alpha),
                       z_lo)
        v_lo = jnp.where(in_bracket,
                         jnp.where(b_zoom_a, s["v_prev"], v), z_v_lo)
        d_lo = jnp.where(in_bracket,
                         jnp.where(b_zoom_a, s["d_prev"], dg), z_d_lo)
        hi = jnp.where(in_bracket,
                       jnp.where(b_zoom_a, alpha, s["alpha_prev"]),
                       z_hi)
        v_hi = jnp.where(in_bracket,
                         jnp.where(b_zoom_a, v, s["v_prev"]), z_v_hi)

        # result: bracket records only on termination; zoom records
        # every eval (the host zoom's running ``best``)
        set_res = jnp.where(in_bracket, b_done | b_exhaust | b_unresolved,
                            True)
        new = dict(
            phase=phase,
            evals=s["evals"] + 1,
            bi=s["bi"] + in_bracket.astype(jnp.int32),
            zj=s["zj"] + (~in_bracket).astype(jnp.int32),
            alpha_prev=jnp.where(in_bracket & b_cont, alpha,
                                 s["alpha_prev"]),
            v_prev=jnp.where(in_bracket & b_cont, v, s["v_prev"]),
            d_prev=jnp.where(in_bracket & b_cont, dg, s["d_prev"]),
            alpha_next=jnp.where(in_bracket & b_cont, alpha * 2.0,
                                 s["alpha_next"]),
            lo=lo, hi=hi, v_lo=v_lo, d_lo=d_lo, v_hi=v_hi,
            res_alpha=jnp.where(set_res, alpha, s["res_alpha"]),
            res_v=jnp.where(set_res, v, s["res_v"]),
            res_g=jax.tree_util.tree_map(
                lambda gn, gs: _select_bcast(set_res, gn, gs),
                g, s["res_g"]),
        )
        # per-model freeze: a lane whose search already terminated keeps its
        # state verbatim (the batched while runs until EVERY lane is done;
        # without the select its result would keep moving). Unbatched, the
        # while cond makes `live` trivially true — XLA folds the selects.
        live = s["phase"] < 2
        return {
            key: (jax.tree_util.tree_map(
                lambda nv, ov: _select_bcast(live, nv, ov),
                nv_, s[key]) if key == "res_g"
                else _select_bcast(live, nv_, s[key]))
            for key, nv_ in new.items()
        }

    final = jax.lax.while_loop(cond, body, state)
    raised = final["res_v"] > value0
    return (jnp.where(raised, zero, final["res_alpha"]),
            jnp.where(raised, value0, final["res_v"]),
            jax.tree_util.tree_map(
                lambda gs, gr: _select_bcast(raised, gs, gr),
                g0, final["res_g"]),
            final["evals"])


_scale_rows = None


def _get_scale_rows():
    global _scale_rows
    if _scale_rows is None:
        import jax
        # .astype(x.dtype): the standardized copy stays IN the data tier —
        # a bf16 block scaled by an f32/f64 vector would otherwise promote
        # and re-materialize X at 2-4x its storage width
        _scale_rows = jax.jit(lambda x, s: (x * s).astype(x.dtype))
    return _scale_rows


_center_scale_rows = None


def _get_center_scale_rows():
    global _center_scale_rows
    if _center_scale_rows is None:
        import jax
        _center_scale_rows = jax.jit(
            lambda x, s, mu: ((x - mu) * s).astype(x.dtype))
    return _center_scale_rows


def inv_std_vector(features_std: np.ndarray) -> np.ndarray:
    """1/σ per feature with zero-variance features excluded to 0 — the one
    place the reference's exclusion rule (LogisticRegression.scala:649
    featuresStd != 0 guard) is encoded."""
    return np.where(features_std > 0, 1.0 / np.where(
        features_std > 0, features_std, 1.0), 0.0)


def standardize_dataset(ds: InstanceDataset, features_std: np.ndarray,
                        center_mean: Optional[np.ndarray] = None):
    """Scale feature blocks by 1/std in HBM (≈ the reference persisting
    standardized blocks, LogisticRegression.scala:968). Zero-variance
    features scale to 0, matching the reference's exclusion.

    ``center_mean`` additionally centers: x̂ = (x − μ)/σ — the reference's
    ``fitWithMean`` conditioning fix (SPARK-34448,
    LogisticRegression.scala:946-955). The reference implements centering
    as a margin offset inside the aggregator to keep sparse blocks sparse;
    this dense tier centers the (already dense) standardized copy
    directly, which is the same objective with the same memory footprint
    and keeps the aggregator program-cache identity. Padded rows carry
    w=0, so their shifted values never contribute.

    Returns (standardized dataset, inv_std)."""
    import jax
    import jax.numpy as jnp

    inv_std = inv_std_vector(features_std)
    if center_mean is not None:
        scaled = _get_center_scale_rows()(
            ds.x, jnp.asarray(inv_std), jnp.asarray(center_mean))
    else:
        scaled = _get_scale_rows()(ds.x, jnp.asarray(inv_std))
    return ds.derive(x=scaled), inv_std


def validate_binary_labels(y: np.ndarray, what: str) -> None:
    """Reject anything outside {0, 1} — catches the ±1 SVM convention that
    would silently corrupt margin-based losses (the aggregators map y via
    2y−1)."""
    bad = ~np.isin(y, (0.0, 1.0))
    if bad.any():
        raise ValueError(
            f"{what} requires labels in {{0, 1}}, found "
            f"{np.unique(y[bad])[:5]}")


def l2_regularization(reg_param: float, d: int, fit_intercept: bool,
                      features_std: Optional[np.ndarray] = None,
                      standardize: bool = True) -> Optional[Callable]:
    if standardize:
        # cached: a stable fn (and .traceable) identity per parameter set is
        # what lets the device line-search program cache hit across fits
        return _l2_standardized(float(reg_param), int(d), bool(fit_intercept))
    return _l2_regularization(reg_param, d, fit_intercept, features_std,
                              standardize)


@functools.lru_cache(maxsize=None)
def _l2_standardized(reg_param: float, d: int, fit_intercept: bool):
    return _l2_regularization(reg_param, d, fit_intercept, None, True)


def _l2_regularization(reg_param: float, d: int, fit_intercept: bool,
                       features_std: Optional[np.ndarray] = None,
                       standardize: bool = True) -> Optional[Callable]:
    """L2 penalty matching the reference's L2RegFunction semantics
    (ref: ml/optim/regularizer — applied to feature coefficients only, never
    the intercept; when ``standardization=false`` the penalty is computed in
    the ORIGINAL feature space even though training runs in standardized
    space, i.e. each β_j is divided by std_j before squaring).

    The coef vector passed in is in standardized space (β_std = β_orig·std).
    """
    if reg_param == 0.0:
        return None
    std = None
    if not standardize:
        if features_std is None:
            raise ValueError("features_std required when standardization=false")
        std = np.where(features_std > 0, features_std, 1.0)

    def make(xp):
        def fn(coef):
            beta = coef[:d]
            if std is None:
                loss = 0.5 * reg_param * xp.dot(beta, beta)
                gbeta = reg_param * beta
            else:
                s = xp.asarray(std, dtype=coef.dtype)
                b = beta / s
                loss = 0.5 * reg_param * xp.dot(b, b)
                gbeta = reg_param * beta / (s * s)
            grad = xp.concatenate(
                [gbeta, xp.zeros(coef.shape[0] - d, dtype=coef.dtype)])
            return loss, grad
        return fn

    fn = make(np)
    # jnp twin for inlining inside jitted programs (device line search)
    import jax.numpy as jnp
    fn.traceable = make(jnp)
    # introspection for paths that re-derive the penalty in another layout
    # (the feature-sharded line search applies reg directly to its sharded
    # beta slice — only valid for the standardized, uniform-λ penalty)
    fn.reg_param = float(reg_param)
    fn.is_standardized = std is None
    return fn
