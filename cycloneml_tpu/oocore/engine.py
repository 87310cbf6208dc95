"""Streaming fit routing + the streamed mini-batch SGD.

Mode selection (``cyclone.oocore.mode``):

- ``auto`` (default): in-core fits run unchanged, but when the PR-5 memory
  budget guard's chunk-halving bottoms out at deviceChunk=1 with the
  program STILL over budget, eligible estimators degrade to the streaming
  epoch engine instead of warn-proceeding (or raising under
  ``budgetAction=raise``) — graceful at any data:memory ratio, the
  capability bar of the reference's spill discipline (PAPER.md layer 3c).
- ``force``: every eligible dense fit streams (each loss/grad evaluation
  is one double-buffered epoch) — the mode for datasets ingested straight
  into a :class:`~cycloneml_tpu.oocore.shards.StreamingDataset`.
- ``off``: pre-oocore behavior everywhere.

The degradation signal is ``observe.costs.OutOfCoreRequired``: raised by
the chunk guard ONLY when the optimizer's owner declared a streaming
fallback (``DeviceLBFGS.oocore_fallback``), caught by the estimator, never
visible to user code.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import numpy as np

from cycloneml_tpu.observe.costs import OutOfCoreRequired  # noqa: F401  (re-export)
from cycloneml_tpu.oocore.objective import StreamingLossFunction
from cycloneml_tpu.oocore.shards import StreamingDataset
from cycloneml_tpu.util.logging import get_logger

logger = get_logger(__name__)


def streaming_mode(conf) -> str:
    from cycloneml_tpu.conf import OOCORE_MODE
    if conf is None:
        return "auto"
    return str(conf.get(OOCORE_MODE))


def degrade_allowed(ctx) -> bool:
    """Whether the budget guard may degrade to streaming (mode=auto|force)."""
    return streaming_mode(getattr(ctx, "conf", None)) != "off"


def shard_dataset(ds, shard_rows: Optional[int] = None,
                  spill_dir: Optional[str] = None) -> StreamingDataset:
    """Spill an in-core dataset to an out-of-core shard set (the degrade
    path's bridge; bounded per-shard staging — see
    :meth:`StreamingDataset.from_dataset`). Routed through the
    content-hash shard-set cache: a CV fold or warm-start re-fit over the
    same dataset ATTACHES to the existing spill — 0 spill-write bytes —
    instead of re-blocking it (``cyclone.oocore.cacheBytes=0`` restores
    the direct build-and-own path)."""
    from cycloneml_tpu.oocore.cache import shard_set_cache
    return shard_set_cache().attach(ds, shard_rows=shard_rows,
                                    spill_dir=spill_dir)


class StreamingGradientDescent:
    """Mini-batch SGD over streamed epochs — the out-of-core twin of
    ``ml/optim/gradient_descent.GradientDescent``.

    Per step, the gradient is the PARTIAL-SWEEP ACCUMULATION: every shard's
    psummed ``{loss, grad, count}`` folded into one host-f64 sum, then one
    Updater step — identical update math to the in-core optimizer, with
    the treeAggregate dispatch replaced by an epoch. ``miniBatchFraction``
    < 1 folds a per-shard Bernoulli row mask into the weights (keyed on
    seed × step × shard × mesh position, so every row samples
    independently and a fixed seed replays exactly); shapes stay static,
    as in-core.
    """

    def __init__(self, step_size: float = 1.0, num_iterations: int = 100,
                 reg_param: float = 0.0, mini_batch_fraction: float = 1.0,
                 updater=None, convergence_tol: float = 0.001, seed: int = 0,
                 shuffle: Optional[bool] = None):
        from cycloneml_tpu.ml.optim.gradient_descent import SimpleUpdater
        self.step_size = step_size
        self.num_iterations = num_iterations
        self.reg_param = reg_param
        self.mini_batch_fraction = mini_batch_fraction
        self.updater = updater or SimpleUpdater()
        self.convergence_tol = convergence_tol
        self.seed = seed
        # per-epoch shard-order shuffling (cyclone.oocore.shuffle when
        # None): a seeded permutation keyed on seed x step — fixed seed
        # replays exactly; the epoch-accumulated gradient is
        # order-invariant up to float summation order (parity-pinned)
        self.shuffle = shuffle

    def optimize(self, sds: StreamingDataset, agg: Callable, x0: np.ndarray
                 ) -> Tuple[np.ndarray, list]:
        """Returns (weights, stochastic loss history), the in-core
        ``GradientDescent.optimize`` contract."""
        import jax
        import jax.numpy as jnp

        from cycloneml_tpu.mesh import DATA_AXIS, REPLICA_AXIS
        from cycloneml_tpu.observe import tracing

        frac = self.mini_batch_fraction
        seed = self.seed
        shuffle = self.shuffle
        if shuffle is None:
            from cycloneml_tpu.conf import OOCORE_SHUFFLE
            conf = getattr(sds.ctx, "conf", None)
            shuffle = bool(conf.get(OOCORE_SHUFFLE)) \
                if conf is not None else False

        def epoch_order(step: int):
            if not shuffle:
                return None
            # keyed on seed x step: every epoch walks its own seeded
            # permutation, and a re-run at the same seed replays it
            return np.random.RandomState(
                (seed * 1000003 + step) % (2 ** 32)).permutation(
                    sds.n_shards)

        if frac < 1.0:
            def fn(x, y, w, coef, step, shard):
                key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
                key = jax.random.fold_in(key, shard)
                key = jax.random.fold_in(key, jax.lax.axis_index(DATA_AXIS))
                key = jax.random.fold_in(key,
                                         jax.lax.axis_index(REPLICA_AXIS))
                w = w * (jax.random.uniform(key, w.shape) < frac)
                return agg(x, y, w, coef)
            loss_fn = StreamingLossFunction(sds, fn)
        else:
            loss_fn = StreamingLossFunction(sds, agg)

        w = np.asarray(x0, dtype=np.float64).copy()
        history: list = []
        _, reg = self.updater.compute(w, np.zeros_like(w), 0.0, 1,
                                      self.reg_param)
        updates = 0
        for t in range(1, self.num_iterations + 1):
            with tracing.span("dispatch", "gd.step", evals=1, streamed=True):
                if frac < 1.0:
                    # step + shard index ride as per-dispatch arguments so
                    # each shard samples its own Bernoulli mask (keyed on
                    # the TRUE shard index — shuffle-invariant)
                    out = loss_fn.sweep(
                        jnp.asarray(w, jnp.float32),
                        jnp.asarray(t, jnp.int32),
                        per_shard=lambda i: (jnp.asarray(i, jnp.int32),),
                        order=epoch_order(t))
                else:
                    out = loss_fn.sweep(jnp.asarray(w, jnp.float32),
                                        order=epoch_order(t))
            count = float(out["count"])
            if count <= 0:
                continue  # empty mini-batch: no update, no history entry
            loss = float(out["loss"]) / count
            grad = np.asarray(out["grad"], dtype=np.float64) / count
            history.append(loss + reg)
            prev_w = w
            w, reg = self.updater.compute(w, grad, self.step_size, t,
                                          self.reg_param)
            updates += 1
            if self.convergence_tol > 0 and updates > 1:
                delta = float(np.linalg.norm(w - prev_w))
                if delta < self.convergence_tol * max(
                        float(np.linalg.norm(prev_w)), 1.0):
                    logger.info(
                        "StreamingGradientDescent converged at iteration %d",
                        t)
                    break
        return w, history

    def optimize_stacked(self, sds: StreamingDataset, agg: Callable,
                         x0: np.ndarray,
                         y_stack: Optional[np.ndarray] = None
                         ) -> Tuple[np.ndarray, list]:
        """Model-axis twin of :meth:`optimize` — the streamed analog of
        ``StackedGradientDescent``: ``x0`` is ``(K, n)``, each step is ONE
        double-buffered epoch whose per-shard program is the vmapped
        aggregator, so K models ride every staged shard. ``y_stack``
        (``(K, n)``, optional) supplies per-model labels (OvR
        relabelings); without it every model sees the shard's own labels
        (grid fits). Per-model convergence masks freeze early-converged
        models exactly where their serial streamed run would stop, while
        the epochs keep serving the rest. Returns ``(weights (K, n),
        histories)``."""
        import jax
        import jax.numpy as jnp

        from cycloneml_tpu.mesh import DATA_AXIS, REPLICA_AXIS
        from cycloneml_tpu.ml.optim import aggregators
        from cycloneml_tpu.observe import tracing
        from cycloneml_tpu.oocore.objective import (
            StackedStreamingLossFunction, _StackedShardView)

        frac = self.mini_batch_fraction
        seed = self.seed
        shuffle = self.shuffle
        if shuffle is None:
            from cycloneml_tpu.conf import OOCORE_SHUFFLE
            conf = getattr(sds.ctx, "conf", None)
            shuffle = bool(conf.get(OOCORE_SHUFFLE)) \
                if conf is not None else False

        def epoch_order(step: int):
            if not shuffle:
                return None
            return np.random.RandomState(
                (seed * 1000003 + step) % (2 ** 32)).permutation(
                    sds.n_shards)

        W = np.asarray(x0, dtype=np.float64).copy()
        n_models = W.shape[0]
        stacked = aggregators.stack_aggregator(agg)

        if frac < 1.0:
            def fn(x, y, w, coef, step, shard):
                # the row mask is drawn ONCE and shared across the model
                # axis (keyed on the TRUE shard index — shuffle- and
                # stack-invariant): each model sees the same sample
                # sequence its serial streamed run would
                key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
                key = jax.random.fold_in(key, shard)
                key = jax.random.fold_in(key, jax.lax.axis_index(DATA_AXIS))
                key = jax.random.fold_in(key,
                                         jax.lax.axis_index(REPLICA_AXIS))
                w = w * (jax.random.uniform(key, w.shape) < frac)
                return stacked(x, y, w, coef)
        else:
            fn = stacked
        # the vmapped aggregator reads a (rows, K) label stack: staged a
        # shard at a time by the view
        view = _StackedShardView.tiled(sds, n_models) if y_stack is None \
            else _StackedShardView.from_stack(sds, y_stack)
        loss_fn = StackedStreamingLossFunction(view, fn, n_models)

        histories: list = [[] for _ in range(n_models)]
        regs = np.zeros(n_models)
        for kk in range(n_models):
            _, regs[kk] = self.updater.compute(
                W[kk], np.zeros_like(W[kk]), 0.0, 1, self.reg_param)
        live = np.ones(n_models, dtype=bool)
        updates = np.zeros(n_models, dtype=np.int64)
        for t in range(1, self.num_iterations + 1):
            if not live.any():
                break
            with tracing.span("dispatch", "gd.step", evals=1, streamed=True,
                              n_models=n_models):
                if frac < 1.0:
                    out = loss_fn.sweep(
                        jnp.asarray(W, jnp.float32),
                        jnp.asarray(t, jnp.int32),
                        per_shard=lambda i: (jnp.asarray(i, jnp.int32),),
                        order=epoch_order(t))
                else:
                    out = loss_fn.sweep(jnp.asarray(W, jnp.float32),
                                        order=epoch_order(t))
            count = np.asarray(out["count"], dtype=np.float64)
            if float(count.max()) <= 0:
                continue  # empty mini-batch: no model updates
            loss = np.asarray(out["loss"], dtype=np.float64) / count
            grad = np.asarray(out["grad"], dtype=np.float64) / count[:, None]
            for kk in np.nonzero(live)[0]:
                histories[kk].append(loss[kk] + regs[kk])
                prev = W[kk].copy()
                W[kk], regs[kk] = self.updater.compute(
                    W[kk], grad[kk], self.step_size, t, self.reg_param)
                updates[kk] += 1
                if self.convergence_tol > 0 and updates[kk] > 1:
                    delta = float(np.linalg.norm(W[kk] - prev))
                    if delta < self.convergence_tol * max(
                            float(np.linalg.norm(prev)), 1.0):
                        live[kk] = False
                        logger.info(
                            "StreamingGradientDescent: model %d converged "
                            "at iteration %d (%d/%d still live)", kk, t,
                            int(live.sum()), n_models)
        return W, histories
