"""Streamed objective: partial-sweep gradient/loss accumulation.

The out-of-core twin of ``ml/optim/loss.DistributedLossFunction``: one
loss/grad evaluation is one EPOCH — every shard staged (double-buffered),
dispatched through the SAME block aggregator the in-core fit uses, its
psummed ``{loss, grad, count}`` partial folded into a host float64
accumulator, and the total normalized by the weight sum exactly like the
in-core path. Because the per-shard math is the identical aggregator over
identically-masked padded blocks, a streamed fit's objective differs from
the in-core fit's only by floating-point summation ORDER (shard partials
vs device partials) — ~1e-15 relative under the f64 test config, the
parity envelope docs/out-of-core.md documents.

There is deliberately NO ``device_line_search`` here: the strong-Wolfe
search runs on the host with each φ(α) evaluation a full streamed epoch —
the line search over streamed objectives the out-of-core regime implies
(evaluations cost I/O, so the optimizer's eval count is the fit's epoch
count; L-BFGS' ~2-3 evals/iteration keeps that civilized).
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import numpy as np

from cycloneml_tpu.observe import costs, tracing
from cycloneml_tpu.oocore.stream import ShardStream
from cycloneml_tpu.util.logging import get_logger

logger = get_logger(__name__)


class StreamingLossFunction:
    """Callable ``(coef) -> (loss, grad)`` in host float64 over a
    :class:`~cycloneml_tpu.oocore.shards.StreamingDataset`.

    - ``agg``: the SAME block aggregator the in-core fit would use
      (``aggregators.*`` — sums, not means; signature
      ``(x, y, w, *extras, coef)``)
    - ``extra_args``: replicated arguments before the coefficients
      (inv_std / scaled_mean / y_pars), identical to the in-core
      ``DistributedLossFunction(extra_args=...)`` contract
    - ``l2_reg_fn``: the driver-side penalty, applied once per epoch
    - the weight sum comes from the shard set's write-pass moments — no
      extra epoch is spent measuring it
    """

    def __init__(self, sds, agg: Callable,
                 l2_reg_fn: Optional[Callable] = None,
                 weight_sum: Optional[float] = None,
                 extra_args: tuple = ()):
        from cycloneml_tpu.parallel import collectives
        self._sds = sds
        self._ctx = sds.ctx
        rt = sds.ctx.mesh_runtime
        # ONE per-shard program for the whole fit: compiled before any
        # shard exists (n_sharded names the row-sharded args), with the
        # staged shard operands DONATED — they are consumed exactly once,
        # and donation frees their HBM for the next in-flight transfer
        self._sds_agg = agg  # kept so reshard() can rebind on a new mesh
        self._prog = collectives.tree_aggregate(agg, rt, n_sharded=3,
                                                donate_rows=True)
        self._extras = tuple(extra_args)
        self.l2_reg_fn = l2_reg_fn
        self.weight_sum = float(weight_sum) if weight_sum is not None \
            else float(sds.weight_sum)
        # each shard's sums are accumulated in this on the device (the fold
        # across shards is float64): the resolution of the loss returned
        from cycloneml_tpu.dataset.instance import compute_dtype
        self.accumulator_dtype = np.dtype(compute_dtype())
        self.n_evals = 0
        self.n_dispatches = 0   # shard dispatches (n_shards per epoch)
        self.epochs = 0

    def reshard(self, runtime=None) -> "StreamingLossFunction":
        """Rebind this streamed objective to the (rebuilt) mesh — the
        out-of-core leg of an elastic reshape: the held per-shard program
        closes over the OLD mesh (the runtime StaleProgramError guard
        would refuse it), so it recompiles against the new runtime while
        every host-side position — epoch/eval/dispatch counters, the
        weight sum, the shard set itself — carries over untouched.
        Shards re-stage lazily on the new topology at the next sweep;
        the fixed ``(padRows, d)`` geometry must divide the new mesh's
        data parallelism (padRows is a multiple of 8× the SPILL-time
        parallelism, so power-of-two scale-downs and moderate scale-ups
        always fit) — an indivisible shape raises before any dispatch."""
        from cycloneml_tpu.parallel import collectives
        rt = runtime if runtime is not None else self._ctx.mesh_runtime
        dp = rt.data_parallelism
        if self._sds.pad_rows % dp:
            raise ValueError(
                f"shard geometry padRows={self._sds.pad_rows} does not "
                f"divide the reshaped mesh's data parallelism {dp}; "
                f"re-spill the shard set for this topology")
        self._prog = collectives.tree_aggregate(
            self._sds_agg, rt, n_sharded=3, donate_rows=True)
        return self

    # -- the streamed sweep ----------------------------------------------------
    def sweep(self, *call_args, per_shard=None, order=None) -> dict:
        """One epoch: stage every shard, dispatch the per-shard program,
        fold the psummed partials into host float64 sums. Returns the raw
        accumulated pytree (sums — the caller normalizes), mirroring what
        one in-core ``tree_aggregate`` dispatch returns. ``per_shard(i)``
        optionally supplies extra replicated arguments appended per shard
        dispatch (the streamed SGD's shard-index mask key — keyed on the
        TRUE shard index, so it is order-invariant). ``order`` optionally
        permutes the staging order for this epoch (streamed-SGD
        shuffling); the accumulated sums differ only by float summation
        order."""
        import jax
        acc: Optional[dict] = None
        self.epochs += 1
        with tracing.span("dispatch", "oocore.sweep",
                          shards=self._sds.n_shards) as sweep_sp:
            with ShardStream(self._sds, order=order) as stream:
                for i, xs, ys, ws in stream:
                    args = call_args if per_shard is None \
                        else (*call_args, *per_shard(i))
                    with tracing.span("dispatch", "oocore.shard", shard=i):
                        out_dev = self._prog(xs, ys, ws, *args)
                        del xs, ys, ws  # donated: dead on dispatch
                        with tracing.span("transfer",
                                          "oocore.readback") as tsp:
                            out = jax.device_get(out_dev)
                            tsp.annotate_bytes(out)
                    self.n_dispatches += 1
                    if acc is None:
                        acc = {k: np.asarray(v, dtype=np.float64)
                               for k, v in out.items()}
                    else:
                        for k, v in out.items():
                            acc[k] = acc[k] + np.asarray(v, dtype=np.float64)
            sweep_sp.annotate(bytes_staged=stream.bytes_staged)
        if acc is None:
            raise RuntimeError("streamed sweep saw zero shards")
        return acc

    def __call__(self, coef: np.ndarray) -> Tuple[float, np.ndarray]:
        self.n_evals += 1
        out = self.sweep(*self._extras, np.asarray(coef))
        loss = float(out["loss"]) / self.weight_sum
        grad = np.asarray(out["grad"], dtype=np.float64) / self.weight_sum
        if self.l2_reg_fn is not None:
            rl, rg = self.l2_reg_fn(coef)
            loss += float(rl)
            grad += np.asarray(rg, dtype=np.float64)
        if hasattr(self._ctx, "record_step"):
            # one streamed epoch ≈ one stage's TaskMetrics
            self._ctx.record_step({"loss": loss,
                                   "oocore_shards": self._sds.n_shards})
        return loss, grad

    # -- accounting ------------------------------------------------------------
    def _shard_avals(self, n_coef: int, concrete: bool = False) -> tuple:
        """Representative per-shard operands at the padded geometry.
        Abstract ``ShapeDtypeStruct``s by default — ``lower()`` only needs
        avals, and a real O(shard) allocation here would compete for the
        very HBM the streamed fit bounds; ``concrete=True`` is the
        fallback for jax versions whose structs cannot carry sharding."""
        import jax
        from cycloneml_tpu.dataset.instance import compute_dtype
        sds = self._sds
        # the ACTUAL stream dtype: fp8 shard sets stage 1-byte codes, and
        # the cost model must bill them at that width (bench-bytes gates
        # the fp8 stream at < 0.55x the bf16 stream)
        xdt = np.dtype(getattr(sds, "x_dtype", np.float64))
        adt = np.dtype(compute_dtype())
        rt = sds.ctx.mesh_runtime
        if concrete:
            x = rt.device_put_sharded_rows(
                np.zeros((sds.pad_rows, sds.n_features), dtype=xdt))
            y = rt.device_put_sharded_rows(np.zeros(sds.pad_rows, dtype=adt))
            w = rt.device_put_sharded_rows(np.zeros(sds.pad_rows, dtype=adt))
        else:
            x = jax.ShapeDtypeStruct((sds.pad_rows, sds.n_features), xdt,
                                     sharding=rt.data_sharding(1))
            y = jax.ShapeDtypeStruct((sds.pad_rows,), adt,
                                     sharding=rt.data_sharding(0))
            w = jax.ShapeDtypeStruct((sds.pad_rows,), adt,
                                     sharding=rt.data_sharding(0))
        return (x, y, w, *self._extras,
                np.zeros(n_coef, dtype=np.float64))

    def sweep_cost(self, n_coef: int) -> costs.ProgramCost:
        """:func:`observe.costs.streamed_sweep_cost` over this fit's
        per-shard program at the padded shard geometry — the whole-epoch
        bytes/FLOPs with the O(shard) per-dispatch memory footprint."""
        cost = costs.streamed_sweep_cost(
            self._prog, self._shard_avals(n_coef), self._sds.n_shards)
        if not cost.cost_available:
            # lower() rejected the abstract operands (older jax): pay the
            # one concrete staging for the measurement
            cost = costs.streamed_sweep_cost(
                self._prog, self._shard_avals(n_coef, concrete=True),
                self._sds.n_shards)
        return cost


class _StackedShardView:
    """StreamingDataset facade carrying a per-shard ``(rows, K)`` label
    stack, built host-side at stage time, for a per-shard program that
    ``vmap``s a one-model aggregator over its label axis (the streamed
    SGD's ``optimize_stacked``): each shard's stack is O(shard · K), staged
    once, donated like every other shard operand — the whole ``(n, K)``
    matrix is never on the device.

    Two label sources:

    - :meth:`tiled` — the shard's own labels broadcast across K models
      (same data, K penalties);
    - :meth:`from_stack` — column slices of a caller ``(K, n)`` stack in
      shard row order (``from_chunks`` preserves row order, so shard
      offsets index the stack directly).
    """

    def __init__(self, sds, n_models: int, y_fn):
        self._sds = sds
        self.n_models = int(n_models)
        self._y_fn = y_fn
        self.y_dtype = self._stack_dtype()

    @staticmethod
    def _stack_dtype() -> np.dtype:
        """The stack is staged at the accumulator's width."""
        from cycloneml_tpu.dataset.instance import compute_dtype
        return np.dtype(compute_dtype())

    @classmethod
    def tiled(cls, sds, n_models: int) -> "_StackedShardView":
        ydt = cls._stack_dtype()

        def y_fn(i, y):
            y = np.asarray(y, dtype=ydt)
            return np.ascontiguousarray(
                np.broadcast_to(y[:, None], (len(y), n_models)))

        return cls(sds, n_models, y_fn)

    @classmethod
    def from_stack(cls, sds, y_stack: np.ndarray) -> "_StackedShardView":
        ydt = cls._stack_dtype()
        offsets = np.cumsum([0] + [s.rows for s in sds._shards])
        if y_stack.shape[1] != sds.n_rows:
            raise ValueError(
                f"y_stack has {y_stack.shape[1]} rows per model; the "
                f"shard set has {sds.n_rows}")

        def y_fn(i, y):
            lo, hi = offsets[i], offsets[i + 1]
            return np.ascontiguousarray(
                np.asarray(y_stack[:, lo:hi]).T.astype(ydt))

        return cls(sds, len(y_stack), y_fn)

    # -- delegated surface (what ShardStream + the objective touch) -----------
    @property
    def ctx(self):
        return self._sds.ctx

    @property
    def n_shards(self) -> int:
        return self._sds.n_shards

    @property
    def n_rows(self) -> int:
        return self._sds.n_rows

    @property
    def n_features(self) -> int:
        return self._sds.n_features

    @property
    def pad_rows(self) -> int:
        return self._sds.pad_rows

    @property
    def weight_sum(self) -> float:
        return self._sds.weight_sum

    @property
    def x_dtype(self):
        return getattr(self._sds, "x_dtype", np.dtype(np.float64))

    @property
    def x_scale(self):
        return getattr(self._sds, "x_scale", None)

    def load_shard(self, i: int):
        x, y, w = self._sds.load_shard(i)
        return x, self._y_fn(i, y), w


class StackedStreamingLossFunction(StreamingLossFunction):
    """Model-axis twin of :class:`StreamingLossFunction` — the streamed
    analog of ``loss.StackedDistributedLossFunction``.

    Callable ``(coef_stack (K, n_coef)) -> (loss (K,), grad (K, n_coef))``
    in host float64; one evaluation is ONE double-buffered epoch whose
    per-shard program is a stacked aggregator — every staged shard serves
    all K models, so a K-model grid/OvR fit over spilled data reads the
    data once per iteration instead of K times. ``sds`` is the shard set
    itself where ``agg`` carries the model axis inside and makes each
    model's label from the shard's own label vector
    (``aggregators.stacked_binary_logistic_*``), or a
    :class:`_StackedShardView` of it where ``agg`` is a ``vmap`` over a
    staged ``(rows, K)`` label stack. Per-model L2 is host-side runtime
    data (``stacked_host_l2`` — shared with the in-core stacked loss, so
    penalties are bit-identical).
    """

    def __init__(self, sds, agg, n_models: int,
                 reg: Optional[np.ndarray] = None,
                 l2_scale: Optional[np.ndarray] = None,
                 weight_sum: Optional[float] = None,
                 extra_args: tuple = ()):
        super().__init__(sds, agg, l2_reg_fn=None, weight_sum=weight_sum,
                         extra_args=extra_args)
        self.n_models = int(n_models)
        self.reg = (np.zeros(self.n_models) if reg is None
                    else np.asarray(reg, dtype=np.float64))
        self.l2_scale = (None if l2_scale is None
                         else np.asarray(l2_scale, dtype=np.float64))

    def __call__(self, coef_stack: np.ndarray):
        from cycloneml_tpu.ml.optim.loss import stacked_host_l2
        self.n_evals += 1
        out = self.sweep(*self._extras, np.asarray(coef_stack))
        loss = np.asarray(out["loss"], dtype=np.float64) / self.weight_sum
        grad = np.asarray(out["grad"], dtype=np.float64) / self.weight_sum
        loss, grad = stacked_host_l2(loss, grad, coef_stack, self.reg,
                                     self.l2_scale)
        if hasattr(self._ctx, "record_step"):
            # one streamed epoch serves all K models
            self._ctx.record_step({"loss": float(np.mean(loss)),
                                   "n_models": self.n_models,
                                   "oocore_shards": self._sds.n_shards})
        return loss, grad

    def _shard_avals(self, n_coef: int, concrete: bool = False) -> tuple:
        """The base class's operands with the model axis on the
        coefficients — and on the labels, where a view stages a stack."""
        import jax
        x, y, w, *rest = super()._shard_avals(n_coef, concrete)
        K = self.n_models
        if isinstance(self._sds, _StackedShardView):
            view = self._sds
            rt = view.ctx.mesh_runtime
            if concrete:
                y = rt.device_put_sharded_rows(
                    np.zeros((view.pad_rows, K), dtype=view.y_dtype))
            else:
                y = jax.ShapeDtypeStruct((view.pad_rows, K), view.y_dtype,
                                         sharding=rt.data_sharding(1))
        return (x, y, w, *rest[:-1], np.zeros((K, n_coef), dtype=np.float64))
