"""Feature-dimension (model-axis) tensor parallelism.

SURVEY §5.7a parity requirement: when the coefficient vector or Gram matrix
outgrows one device's HBM, the ``model`` mesh axis shards the FEATURE
dimension — the TPU-native analog of the reference's 2-D blocking
(``BlockMatrix.multiply``, mllib/linalg/distributed/BlockMatrix.scala:455,
and ALS's in/out blocks, ml/recommendation/ALS.scala:1605). Layout:

- ``x``:    ``P((replica, data), model)`` — rows over the data axes,
            features over the model axis. Each device holds an
            (rows/shard, d/m) block.
- ``beta``: ``P(model)`` — each model group holds its d/m coefficient slice.
- margins:  ``x_blk @ beta_blk`` summed with one psum over ``model`` (the
            only cross-model collective in the forward pass — it rides ICI).
- gradient: ``x_blkᵀ @ mult`` is naturally model-sharded; no collective.
- Gramian:  a ``ppermute`` ring streams d/m-wide feature blocks around the
            model axis so each step multiplies (rows, d/m)ᵀ × (rows, d/m);
            no device ever materializes the full (rows, d) or (d, d) array
            (the scaling-book ring-matmul recipe).

The host optimizer keeps the flat f64 coefficient vector (L-BFGS state is
O(10·d) on the driver — fine to ~10⁷ features); per evaluation only the
d-vector crosses host↔device, exactly the reference's per-iteration
coefficient broadcast (RDDLossFunction.scala:56).
"""

from __future__ import annotations

import functools as _functools
from typing import Optional, Tuple

import numpy as np

from cycloneml_tpu.mesh import DATA_AXIS, MODEL_AXIS, REPLICA_AXIS, MeshRuntime
from cycloneml_tpu.observe import tracing
from cycloneml_tpu.parallel.collectives import (BoundedProgramCache,
                                                dispatch_fused,
                                                psum_over_mesh,
                                                shard_map_compat)

# program-identity cache (see collectives.BoundedProgramCache); the
# gram_ring key varies by (d, rows, dtype), so eviction matters for
# long-lived processes over many datasets
_program_cache = BoundedProgramCache(64)
_cache_put = _program_cache.put
_cache_get = _program_cache.get


@_functools.lru_cache(maxsize=None)
def _upcast_program(dt):
    import jax
    return jax.jit(lambda a: a.astype(dt))


def accumulator_width(x):
    """Upcast a narrow (bf16 data-tier) block to the accumulator dtype at
    the TP boundary. The feature-sharded engine keys its coefficient/
    optimizer dtype off X's dtype and re-materializes X into the
    feature-sharded layout anyway, so the upcast costs no extra sweep
    class; narrowing the TP tier itself is future work. The jitted upcast
    is cached per dtype — a fresh jit per call would retrace every fit."""
    from cycloneml_tpu.dataset.instance import compute_dtype, is_narrow_dtype
    if not is_narrow_dtype(x.dtype):
        return x
    return _upcast_program(np.dtype(compute_dtype()))(x)


def model_parallelism(runtime: MeshRuntime) -> int:
    return int(runtime.mesh.devices.shape[2])


def feature_sharded_put(runtime: MeshRuntime, x):
    """Place (or re-place) a row-block array with features over ``model``.

    ``x`` may be a host array or an already device-resident row-sharded
    array (the RAW dataset's blocks — standardization folds into the TP
    read); resharding happens device-side in the latter case. The feature
    dim must divide the model axis.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    m = model_parallelism(runtime)
    if x.shape[1] % m != 0:
        raise ValueError(
            f"feature dim {x.shape[1]} not divisible by model axis {m}")
    spec = NamedSharding(runtime.mesh, P((REPLICA_AXIS, DATA_AXIS), MODEL_AXIS))
    return jax.device_put(x, spec)


def beta_sharding(runtime: MeshRuntime):
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(runtime.mesh, P(MODEL_AXIS))


def binary_logistic_tp_program(runtime: MeshRuntime):
    """Compiled ``(x, y, w, beta, b0, inv_std, scaled_mean) ->
    (loss, grad_beta, grad_b0, count)`` over RAW feature blocks.

    The feature-sharded twin of ``aggregators.binary_logistic_scaled``
    (ref BinaryLogisticBlockAggregator.scala:41): standardization and
    fitWithMean centering fold INTO the read — ``inv_std`` and
    ``scaled_mean`` are MODEL-SHARDED d-vectors (the same layout as beta),
    so the path that exists precisely for models too big for one chip
    carries X itself, not a standardized copy at 2× the HBM (r4 verdict
    item 3). Margin assembly stays one psum over ``model`` — the scaling
    contributions ride inside the same reduction:

      margin = Σ_shards [x_blk·(inv_std_blk∘β_blk) − scaled_mean_blk·β_blk]
               + β₀
      grad_β_blk = inv_std_blk∘Σrows(x_blkᵀ mult) − scaled_mean_blk·Σmult

    loss / count / grad_b0 are identical on every model shard (computed
    from the full margins), so they reduce over the data axes only;
    grad_beta stays model-sharded — it IS the output layout the optimizer
    wants when d is too big to replicate. Pass inv_std=ones,
    scaled_mean=zeros for the identity read.
    """
    key = ("binlog_tp", runtime.mesh)
    prog = _cache_get(key)
    if prog is not None:
        return prog
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    mesh = runtime.mesh
    rowfeat = P((REPLICA_AXIS, DATA_AXIS), MODEL_AXIS)
    rows = P((REPLICA_AXIS, DATA_AXIS))

    def program(x, y, w, beta, b0, inv_std, scaled_mean):
        def local(xb, yb, wb, bb, b0s, isb, smb):
            sb = isb * bb
            pm = (jnp.dot(xb, sb, precision=jax.lax.Precision.HIGHEST)
                  - jnp.dot(smb, bb, precision=jax.lax.Precision.HIGHEST))
            margin = jax.lax.psum(pm, MODEL_AXIS) + b0s
            loss = jnp.sum(wb * (jax.nn.softplus(margin) - yb * margin))
            mult = wb * (jax.nn.sigmoid(margin) - yb)
            gb_raw = jnp.dot(xb.T, mult, precision=jax.lax.Precision.HIGHEST)
            gb0 = psum_over_mesh(jnp.sum(mult))  # global Σmult: the
            # centering term needs it, and model shards agree on it
            gb = isb * psum_over_mesh(gb_raw) - smb * gb0
            count = jnp.sum(wb)
            # rows are split over (data, replica): sum those axes; model
            # shards already agree on the scalars (full-margin computation)
            return (psum_over_mesh(loss), gb, gb0, psum_over_mesh(count))

        return shard_map_compat(
            local, mesh,
            in_specs=(rowfeat, rows, rows, P(MODEL_AXIS), P(),
                      P(MODEL_AXIS), P(MODEL_AXIS)),
            out_specs=(P(), P(MODEL_AXIS), P(), P()))(
                x, y, w, beta, b0, inv_std, scaled_mean)

    prog = jax.jit(program)
    _cache_put(key, prog)
    return prog


class FeatureShardedLossFunction:
    """(coef) -> (loss, grad) over a feature-sharded dense dataset.

    Drop-in for ``DistributedLossFunction`` on the L-BFGS path when the mesh
    carries a model axis: coefficients live on the driver as flat f64
    ``[beta(d), intercept?]``; beta crosses to the mesh model-sharded each
    evaluation. ``l2_reg_fn`` is the host-side penalty from
    ``l2_regularization`` (same semantics as the replicated path). Also
    provides the fused ``device_line_search`` (one dispatch per L-BFGS
    iteration) — on the large-d path, per-φ host round trips of d-length
    vectors are exactly what must not happen.
    """

    def __init__(self, runtime: MeshRuntime, x_sharded, y, w, d: int,
                 fit_intercept: bool, l2_reg_fn=None,
                 weight_sum: Optional[float] = None, ctx=None,
                 inv_std: Optional[np.ndarray] = None,
                 scaled_mean: Optional[np.ndarray] = None):
        import jax
        import jax.numpy as jnp
        self._rt = runtime
        self._ctx = ctx
        self._x, self._y, self._w = x_sharded, y, w
        self.d = d
        self.fit_intercept = fit_intercept
        self.l2_reg_fn = l2_reg_fn
        self._prog = binary_logistic_tp_program(runtime)
        self._beta_sharding = beta_sharding(runtime)
        # standardization vectors ride MODEL-SHARDED next to beta (folded
        # read over RAW x — no standardized dataset copy on this path)
        cdt = np.dtype(x_sharded.dtype)
        inv_std = (np.ones(d) if inv_std is None
                   else np.asarray(inv_std, dtype=np.float64))
        scaled_mean = (np.zeros(d) if scaled_mean is None
                       else np.asarray(scaled_mean, dtype=np.float64))
        self._inv_std = jax.device_put(inv_std.astype(cdt),
                                       self._beta_sharding)
        self._scaled_mean = jax.device_put(scaled_mean.astype(cdt),
                                           self._beta_sharding)
        if weight_sum is None:
            weight_sum = float(np.asarray(jnp.sum(self._w)))
        self.weight_sum = weight_sum
        self.n_evals = 0
        self.n_dispatches = 0
        self.n_fused_searches = 0

    def _record(self, loss: float, **extra) -> None:
        if self._ctx is not None and hasattr(self._ctx, "record_step"):
            self._ctx.record_step({"loss": loss, **extra})

    def _split(self, coef: np.ndarray, cdt):
        import jax
        beta = jax.device_put(np.asarray(coef[: self.d], dtype=cdt),
                              self._beta_sharding)
        b0 = cdt.type(coef[self.d]) if self.fit_intercept else cdt.type(0.0)
        return beta, b0

    def __call__(self, coef: np.ndarray) -> Tuple[float, np.ndarray]:
        import jax
        self.n_evals += 1
        self.n_dispatches += 1
        cdt = np.dtype(self._x.dtype)
        beta, b0 = self._split(coef, cdt)
        with tracing.span("dispatch", "tp.loss.eval", evals=1):
            out_dev = self._prog(self._x, self._y, self._w, beta, b0,
                                 self._inv_std, self._scaled_mean)
            with tracing.span("transfer", "tp.loss.readback") as tsp:
                loss_t, gb_t, gb0_t, _ = jax.device_get(
                    out_dev)  # one transfer
                tsp.annotate_bytes((loss_t, gb_t, gb0_t))
        loss = float(loss_t) / self.weight_sum
        gb = np.asarray(gb_t, dtype=np.float64) / self.weight_sum
        if self.fit_intercept:
            grad = np.concatenate([gb, [float(gb0_t) / self.weight_sum]])
        else:
            grad = gb
        if self.l2_reg_fn is not None:
            rl, rg = self.l2_reg_fn(coef)
            loss += float(rl)
            grad = grad + np.asarray(rg, dtype=np.float64)
        self._record(loss)
        return loss, grad

    def device_line_search(self, x: np.ndarray, direction: np.ndarray,
                           value: float, grad: np.ndarray, dg0: float,
                           init_alpha: float, c1: float, c2: float,
                           max_evals: int):
        """Whole strong-Wolfe search in one dispatch, beta kept sharded.

        The penalty is re-derived on the sharded beta slice
        (λ/2·βᵀβ, feature coords only), valid only for the standardized
        uniform-λ L2; anything else falls back to the host search.
        """
        if self.l2_reg_fn is not None and \
                not getattr(self.l2_reg_fn, "is_standardized", False):
            return None
        reg = (getattr(self.l2_reg_fn, "reg_param", 0.0)
               if self.l2_reg_fn is not None else 0.0)
        cdt = np.dtype(self._x.dtype)
        key = ("tp_ls", self._rt.mesh, float(c1), float(c2),
               int(max_evals), cdt.str)
        prog, fresh = _program_cache.get_or_build(
            key, lambda: _build_tp_line_search(self._rt, c1, c2, max_evals,
                                               cdt))
        beta0, b0 = self._split(x, cdt)
        dbeta, db0 = self._split(direction, cdt)
        gbeta, gb0 = self._split(grad, cdt)
        args = (self._x, self._y, self._w, beta0, b0, dbeta, db0,
                gbeta, gb0,
                cdt.type(value), cdt.type(dg0), cdt.type(init_alpha),
                cdt.type(self.weight_sum), cdt.type(reg),
                self._inv_std, self._scaled_mean)
        _, (alpha, v, gb, gb0, evals) = dispatch_fused(
            "tp.line_search", key, prog, args, fresh=fresh,
            transfer_name="tp.line_search.readback", evals_at=4)
        self.n_evals += int(evals)
        self.n_dispatches += 1
        self.n_fused_searches += 1
        loss = float(v)
        grad = np.asarray(gb, dtype=np.float64)
        if self.fit_intercept:
            grad = np.concatenate([grad, [float(gb0)]])
        self._record(loss, line_search_evals=int(evals))
        return float(alpha), loss, grad


def _build_tp_line_search(runtime: MeshRuntime, c1: float, c2: float,
                          max_evals: int, cdt: np.dtype):
    """Feature-sharded twin of ``loss._build_line_search``: the same
    ``wolfe_search`` state machine, with φ evaluating the model-axis psum
    aggregation and the gradient pytree (beta_sharded, b0) threaded through
    the loop without ever gathering beta to one device."""
    import jax
    import jax.numpy as jnp
    from cycloneml_tpu.ml.optim.loss import wolfe_search

    tp_prog = binary_logistic_tp_program(runtime)

    def program(x, y, w, beta0, b0, dbeta, db0, gbeta0, gb00,
                value0, dg0, init_alpha, ws, reg, inv_std, scaled_mean):
        def phi(alpha):
            beta = beta0 + alpha * dbeta
            b0a = b0 + alpha * db0
            loss_t, gb, gb0, _ = tp_prog(x, y, w, beta, b0a,
                                         inv_std, scaled_mean)
            loss = (loss_t / ws).astype(cdt)
            gbn = (gb / ws).astype(cdt)
            gb0n = (gb0 / ws).astype(cdt)
            # standardized uniform-λ L2 on the feature coords (sharded dot
            # auto-reduces over the model axis)
            loss = loss + 0.5 * reg * jnp.dot(beta, beta)
            gbn = gbn + reg * beta
            dg = jnp.dot(dbeta, gbn) + db0 * gb0n
            return loss, (gbn, gb0n), dg

        alpha, v, (gb, gb0), evals = wolfe_search(
            phi, (gbeta0, gb00), value0, dg0, init_alpha, c1, c2,
            max_evals, cdt)
        return alpha, v, gb, gb0, evals

    return jax.jit(program)


def gramian_feature_sharded(runtime: MeshRuntime, x_sharded, w=None):
    """XᵀX with X feature-sharded: a ppermute ring over the model axis.

    Each of the m steps multiplies the local (rows, d/m) block against the
    visiting neighbor's block and writes a (d/m, d/m) tile into the local
    (d/m, d) Gram row-band; blocks rotate one hop per step, so after m steps
    every tile is filled without any device holding more than one foreign
    block. Output is the (d, d) Gramian sharded ``P(model, None)``
    (ref computeGramianMatrix:130, whose treeAggregate of spr materializes
    the full packed Gram per executor — impossible at the d this path
    exists for).

    ``w``: optional row weights; rows with w<=0 (mesh padding) are excluded,
    matching the replicated path's mask.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    mesh = runtime.mesh
    m = model_parallelism(runtime)
    d = int(x_sharded.shape[1])
    dm = d // m
    key = ("gram_ring", mesh, d, x_sharded.shape[0], str(x_sharded.dtype),
           w is None)
    prog = _cache_get(key)
    if prog is None:
        rowfeat = P((REPLICA_AXIS, DATA_AXIS), MODEL_AXIS)
        rows = P((REPLICA_AXIS, DATA_AXIS))
        perm = [(i, (i + 1) % m) for i in range(m)]

        def program(x, wv):
            def local(xb, wb):
                xb = xb * (wb > 0)[:, None].astype(xb.dtype)
                my = jax.lax.axis_index(MODEL_AXIS)

                def body(s, carry):
                    blk, acc = carry
                    # after s hops a block has moved +s positions; the one
                    # visiting me started at my - s
                    origin = (my - s) % m
                    tile = jnp.dot(xb.T, blk,
                                   precision=jax.lax.Precision.HIGHEST)
                    acc = jax.lax.dynamic_update_slice(
                        acc, tile,
                        (jnp.zeros((), origin.dtype), origin * dm))
                    blk = jax.lax.ppermute(blk, MODEL_AXIS, perm)
                    return blk, acc

                acc0 = jnp.zeros((xb.shape[1], d), xb.dtype)
                _, acc = jax.lax.fori_loop(0, m, body, (xb, acc0))
                return psum_over_mesh(acc)  # sum row shards (data, replica)

            return shard_map_compat(local, mesh, (rowfeat, rows),
                                    P(MODEL_AXIS, None))(x, wv)

        prog = jax.jit(program)
        _cache_put(key, prog)
    import jax.numpy as jnp
    if w is None:
        w = jnp.ones((x_sharded.shape[0],), x_sharded.dtype)
    return prog(x_sharded, w)
