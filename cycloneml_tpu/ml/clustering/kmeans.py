"""K-means clustering.

Re-design of the reference (ref: mllib/clustering/KMeans.scala:41 — Lloyd's
with per-partition center sums then collectAsMap :240-311; the ml wrapper
delegates to it, ml/clustering/KMeans.scala:336; DistanceMeasure.scala:28
with euclidean/cosine). TPU-first formulation:

- one Lloyd step = ONE ``tree_aggregate`` program
  (``jit_tree_aggregate__kmeans_lloyd_step``) returning ``{sums (k, d),
  counts (k,), cost}`` psum'd over the mesh — the per-partition sum + global
  merge of the reference. No ``(n, k)`` value exists on any path: on a TPU,
  for a bfloat16 X whose width is a multiple of 128 (at least 128 rows a
  shard, ``k·d`` within the kernel's VMEM budget, weights that hold one
  live value), the step is the Mosaic kernel ``kmeans_lloyd``
  (``ops/kmeans_lloyd.py``: scores on the MXU with the float32 centres as
  bf16 pieces — every row tile from two of them, and from all three
  wherever a row's two best lie within what the third can move, so the
  decisions are three-piece decisions at two pieces' cost —, argmin with
  the lowest index on a tie, the one-hot update product, X read once at
  storage width); everywhere else its row-blocked XLA twin.
  ``cyclone.ml.usePallasKernels``: ``auto`` takes the kernel where it
  exists, ``false`` forces the twin. ``summary.pieces`` names the width the
  decisions are held to (3), ``summary.recheck_share`` how much of the fit
  the kernel scored again with the third piece; a fit whose near-ties stay
  (more than ``SCREEN_BREAK_EVEN`` of the groups re-checked on two steps
  running) goes on with the unscreened step, the faster one there. The
  reference's per-row ``findClosest`` with triangle-inequality pruning
  (DistanceMeasure.scala:123) exists to avoid flops on a CPU; here the
  dense product on the MXU is the faster search.
- the loop stays on the host: one dispatch and one readback a step, the
  centre update in float64 (``sums / counts``; an empty cluster keeps its
  centre), stop when the largest centre move is under ``tol`` or at
  ``maxIter``.
- start: ``initialModel`` (ref mllib KMeans.setInitialModel: a stated
  starting set, bypassing the random start), else ``initMode`` "random" or
  "k-means||" (Bahmani et al., ref KMeans.scala initKMeansParallel) with a
  distributed cost pass + driver-side weighted k-means++ refinement.
- ``summary.training_cost`` is the cost at the RETURNED centres (one
  assignment-only pass in ``fit.finish``, skipped where the last step moved
  nothing). MLlib reports the last step's cost, taken at the centres BEFORE
  their update: a departure, made so that the reported cost belongs to the
  reported model.

Measured on one TPU v5e at 25,000,000 x 128 bf16, k = 1,000: PERF.md §5
(cell ``kmeans_k1000_lloyd_fit``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional

import numpy as np

from cycloneml_tpu.dataset.dataset import InstanceDataset
from cycloneml_tpu.dataset.frame import MLFrame
from cycloneml_tpu.linalg.matrices import DenseMatrix
from cycloneml_tpu.ml.base import Estimator, Model
from cycloneml_tpu.ml.clustering._util import normalize_rows, pairwise_sq_dists
from cycloneml_tpu.ml.param import ParamValidators as V
from cycloneml_tpu.ml.shared import (
    HasFeaturesCol, HasMaxIter, HasPredictionCol, HasSeed, HasTol, HasWeightCol,
)
from cycloneml_tpu.ml.util_io import MLReadable, MLWritable, load_arrays, save_arrays
from cycloneml_tpu.observe import tracing
from cycloneml_tpu.util.logging import get_logger

logger = get_logger(__name__)


class _KMeansParams(HasFeaturesCol, HasPredictionCol, HasMaxIter, HasSeed,
                    HasTol, HasWeightCol):
    def _declare_kmeans_params(self):
        self._p_features_col()
        self._p_prediction_col()
        self._p_max_iter(20)
        self._p_seed(17)
        self._p_tol(1e-4)
        self._p_weight_col()
        self.k = self._param("k", "number of clusters (> 1)", V.gt(1), default=2)
        self.initMode = self._param(
            "initMode", "initialization: random or k-means||",
            V.in_array(["random", "k-means||"]), default="k-means||")
        self.initSteps = self._param("initSteps", "k-means|| steps (> 0)",
                                     V.gt(0), default=2)
        self.distanceMeasure = self._param(
            "distanceMeasure", "euclidean or cosine",
            V.in_array(["euclidean", "cosine"]), default="euclidean")


@functools.lru_cache(maxsize=None)
def _normalizer():
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda x: normalize_rows(jnp, x))


@functools.lru_cache(maxsize=None)
def lloyd_aggregator(fused: bool, update: bool, screen: bool = True):
    """One Lloyd step over a shard, ``kmeans_lloyd_step(x, y, w, centres) ->
    {sums (k, d), counts (k,), cost, kernel_shards, screened_groups,
    rechecked_groups}`` (``update=False``: ``kmeans_lloyd_cost``, the
    assignment-only pass: ``cost`` and ``kernel_shards``; ``screen=False``:
    ``kmeans_lloyd_step_unscreened``, the kernel's step at three pieces on
    every tile) by ``ops/kmeans_lloyd.lloyd_step``. Cached by VALUE, so
    every fit asks ``tree_aggregate`` for the same function and gets the
    same program (``jit_tree_aggregate__kmeans_lloyd_step`` in a device
    capture): a closure built per fit would be re-traced per fit. ``fused``
    is the caller's word that the kernel exists for its X."""
    def kmeans_lloyd_step(x, y, w, centres):
        from cycloneml_tpu.ops.kmeans_lloyd import lloyd_step
        return lloyd_step(x, w, centres, fused=fused, update=update,
                          screen=screen)

    if not update:
        kmeans_lloyd_step.__name__ = "kmeans_lloyd_cost"
    elif not screen:
        kmeans_lloyd_step.__name__ = "kmeans_lloyd_step_unscreened"
    return kmeans_lloyd_step


@dataclasses.dataclass
class KMeansSummary:
    """What a fit reports (ref ml/clustering/KMeansSummary: ``k``,
    ``num_iter``, ``training_cost``, ``cluster_sizes``), plus what the
    program did: ``total_steps`` (passes over X that updated the centres),
    ``total_dispatches`` (those and the assignment-only pass),
    ``orientation`` (``row_major``: every step ran the Mosaic kernel;
    ``xla``: the row-blocked twin), ``pieces`` (the bf16 pieces of a centre
    the DECISIONS are held to: 3 on a bfloat16 X — float32-faithful scores,
    whatever the kernel's screen paid for them —, None where X is wider and
    the product is ``highest``) and ``recheck_share`` (over the fit's
    screened steps, the share of the 128-row groups the kernel's two-piece
    screen scored that it sent to all three pieces because a row's two best
    centres lay within what the third piece can move: near 0 for
    well-separated data, towards 1 for data full of near-ties — a fit that
    reads more than ``SCREEN_BREAK_EVEN`` on two steps running takes the
    unscreened step from there on, the same ``sums`` and ``counts`` at four
    passes; None where no step screened). ``training_cost`` is the cost AT
    the returned centres
    (MLlib: at the centres before the last update) and ``cluster_sizes``
    the last step's assignment counts (weighted)."""

    k: int
    num_iter: int
    training_cost: float
    cluster_sizes: List[float]
    total_steps: int
    total_dispatches: int
    orientation: str
    pieces: Optional[int]
    recheck_share: Optional[float] = None


class KMeans(Estimator, _KMeansParams, MLWritable, MLReadable):
    def __init__(self, uid=None, **kwargs):
        super().__init__(uid)
        self._declare_kmeans_params()
        # the estimator's alone: a model does not carry its starting set
        self.initialModel = self._param(
            "initialModel", "the starting centres, a (k, d) array or a "
            "KMeansModel (ref mllib KMeans.setInitialModel): bypasses "
            "initMode; its k must match")
        for key, v in kwargs.items():
            self.set(key, v)

    def set(self, param, value):
        name = param if isinstance(param, str) else param.name
        if name == "initialModel" and value is not None:
            # held as a float64 array: what copy, save and load carry
            if isinstance(value, KMeansModel):
                value = value.cluster_centers_matrix().to_array()
            value = np.array(value, dtype=np.float64)
            if value.ndim != 2:
                raise ValueError(f"initialModel wants (k, d) centres, got "
                                 f"an array of shape {value.shape}")
        return super().set(param, value)

    def set_k(self, v):
        return self.set("k", v)

    def set_max_iter(self, v):
        return self.set("maxIter", v)

    def set_seed(self, v):
        return self.set("seed", v)

    def set_initial_model(self, v):
        return self.set("initialModel", v)

    def _fit(self, frame: MLFrame) -> "KMeansModel":
        ds = frame.to_instance_dataset(
            self.get("featuresCol"), label_col=None,
            weight_col=self.get("weightCol") or None)
        return self._fit_dataset(ds)

    def _starting_centers(self, ds: InstanceDataset, k: int) -> np.ndarray:
        if not self.is_set(self.initialModel):
            return self._init_centers(ds, k)
        start = np.asarray(self.get("initialModel"), np.float64)
        if start.shape != (k, ds.n_features):
            raise ValueError(
                f"initialModel holds centres of shape {start.shape}; k is "
                f"{k} and the data's width {ds.n_features}")
        return start

    def _fit_dataset(self, ds: InstanceDataset) -> "KMeansModel":
        """Lloyd's iterations over a device-resident dataset: per step one
        aggregation program (dispatch + readback of ``{sums, counts,
        cost}``) and the float64 centre update on the driver, then — where
        the last step still moved a centre — one assignment-only pass for
        the returned centres' cost."""
        import jax
        import jax.numpy as jnp
        from cycloneml_tpu.dataset.instance import compute_dtype
        from cycloneml_tpu.ops.kernels import use_fused_kernels
        from cycloneml_tpu.ops.kmeans_lloyd import (
            CENTRE_PIECES, SCREEN_BREAK_EVEN, lloyd_tile,
        )

        with tracing.span("phase", "fit.prepare"):
            k = self.get("k")
            cosine = self.get("distanceMeasure") == "cosine"
            if cosine:
                # cosine distance clusters on the unit sphere: normalize once
                ds = ds.derive(x=_normalizer()(ds.x))
            centers = self._starting_centers(ds, k)
            if cosine and self.is_set(self.initialModel):
                centers = normalize_rows(np, centers)
            # centers are a replicated (k, d) set on the ACCUMULATOR tier
            # (f32/f64) even when X stores bf16: the step reads X at
            # storage width and never rounds a centre to it
            dtype = compute_dtype()
            rows = ds.x.sharding.shard_shape(ds.x.shape)[0]
            fused = use_fused_kernels(ds.ctx) and lloyd_tile(
                rows, ds.n_features, k, ds.x.dtype) is not None
            step = ds.tree_aggregate_fn(lloyd_aggregator(fused, True))
            # what a fit full of near-ties goes on with (a program is built
            # by its first dispatch: none, for most fits)
            unscreened = ds.tree_aggregate_fn(
                lloyd_aggregator(fused, True, False))

        n_dispatches = 0
        shards = ds.ctx.mesh_runtime.data_parallelism
        kernel_shards = []
        screened = rechecked = 0.0
        past = 0        # steps running whose re-checks passed break-even

        def dispatch(program, name, at):
            """One launch of ``program`` at the centres ``at`` and its one
            readback."""
            nonlocal n_dispatches
            n_dispatches += 1
            with tracing.span("dispatch", f"kmeans.{name}", passes=1):
                out_dev = program(jnp.asarray(at.astype(dtype)))
                with tracing.span("transfer", "kmeans.readback") as tsp:
                    out = jax.device_get(out_dev)
                    tsp.annotate_bytes(out)
            kernel_shards.append(float(out["kernel_shards"]))
            return out

        tol = self.get("tol")
        cost, moved, counts = float("inf"), float("inf"), np.zeros(k)
        it = 0
        for it in range(1, self.get("maxIter") + 1):
            with tracing.span("phase", "lloyd.iteration",
                              iteration=it) as isp:
                out = dispatch(step, "step", centers)
                groups = {key: float(out[key]) for key in
                          ("screened_groups", "rechecked_groups")}
                screened += groups["screened_groups"]
                rechecked += groups["rechecked_groups"]
                # near-ties that stay (duplicate data under k centres, a
                # lattice): the unscreened step is the faster from here on.
                # Two steps running, because a start with duplicate centres
                # is past break-even once and never again
                past = past + 1 if groups["rechecked_groups"] \
                    > SCREEN_BREAK_EVEN * groups["screened_groups"] else 0
                if past == 2:
                    step = unscreened
                if hasattr(ds.ctx, "record_step"):
                    ds.ctx.record_step({"lloyd_steps": 1.0, **groups})
                counts = np.asarray(out["counts"], dtype=np.float64)
                sums = np.asarray(out["sums"], dtype=np.float64)
                cost = float(out["cost"])
                # empty clusters keep their previous center (ref behavior)
                new_centers = np.where(
                    counts[:, None] > 0,
                    sums / np.maximum(counts[:, None], 1e-300), centers)
                if cosine:
                    norms = np.linalg.norm(new_centers, axis=1, keepdims=True)
                    new_centers = new_centers / np.maximum(norms, 1e-12)
                moved = float(
                    np.linalg.norm(new_centers - centers, axis=1).max())
                centers = new_centers
                isp.annotate(moved=moved, cost=cost, **groups)
            if moved < tol:
                break

        with tracing.span("phase", "fit.finish"):
            if moved > 0.0:
                # the steps report the cost at the centres they were GIVEN:
                # the returned ones get an assignment-only pass of their own
                cost = float(dispatch(
                    ds.tree_aggregate_fn(lloyd_aggregator(fused, False)),
                    "cost", centers)["cost"])
            on_kernel = fused and all(s == shards for s in kernel_shards)
            model = KMeansModel(centers, training_cost=cost, uid=self.uid)
            self._copy_values(model)
            model._set_parent(self)
            model.summary = KMeansSummary(
                k=k, num_iter=it, training_cost=cost,
                cluster_sizes=[float(c) for c in counts],
                total_steps=it, total_dispatches=n_dispatches,
                orientation="row_major" if on_kernel else "xla",
                pieces=CENTRE_PIECES if str(ds.x.dtype) == "bfloat16"
                else None,
                recheck_share=rechecked / screened if screened else None)
            return model

    # -- initialization --------------------------------------------------------
    def _init_centers(self, ds: InstanceDataset, k: int) -> np.ndarray:
        import jax
        import jax.numpy as jnp

        rng = np.random.RandomState(self.get("seed"))
        valid = ds.valid_indices()
        n = len(valid)
        if n <= k:
            x_host = ds.to_numpy()[0]  # tiny by construction
            reps = int(np.ceil(k / max(n, 1)))
            return np.tile(x_host, (reps, 1))[:k]
        if self.get("initMode") == "random":
            idx = rng.choice(valid, size=k, replace=False)
            return ds.gather_rows(idx).astype(np.float64)

        # k-means|| (Bahmani et al.; ref initKMeansParallel): start with one
        # random center; each step samples points w.p. l*d(x)/cost with l=2k,
        # distances computed on device; finish with weighted k-means++ on the
        # (small) candidate set, weights = cluster population. Sampled rows
        # are gathered from the mesh by index — X never lands on the host,
        # so initialization works at out-of-core scale (verdict r2 item 2).
        hi = jax.lax.Precision.HIGHEST

        def min_d2(x, y, w, c):
            d2 = pairwise_sq_dists(jnp, x, c, precision=hi)
            md = jnp.maximum(jnp.min(d2, axis=1), 0.0) * (w > 0)
            return md

        centers = [ds.gather_rows([valid[rng.randint(n)]])[0]]
        l_factor = 2 * k
        # candidate centers ride the accumulator tier (see _fit_dataset)
        from cycloneml_tpu.dataset.instance import compute_dtype
        dtype = np.dtype(compute_dtype())
        for _ in range(self.get("initSteps")):
            c_arr = np.asarray(centers, dtype=dtype)
            d2 = collective_row_values(ds, min_d2, c_arr)  # (n_pad,)
            total = float(d2.sum())  # padding rows contribute 0 via (w > 0)
            if total <= 0:
                break
            probs = np.minimum(l_factor * d2 / total, 1.0)
            picked = np.nonzero(rng.rand(len(d2)) < probs)[0]
            if len(picked):
                centers.extend(ds.gather_rows(picked))
        cand = np.unique(np.asarray(centers, dtype=np.float64), axis=0)
        if cand.shape[0] <= k:
            extra = ds.gather_rows(
                rng.choice(valid, size=k - cand.shape[0], replace=False))
            return np.vstack([cand, extra.astype(np.float64)])[:k]
        # weight candidates by the (weighted) points they attract, computed
        # on device via segment-sum; gated by the (shard x cand) distance
        # buffer each device must hold
        n_pad = int(ds.x.shape[0])
        if n_pad * cand.shape[0] < 5e7:
            m = cand.shape[0]

            def attract_fn(x, y, w, c):
                a = jnp.argmin(pairwise_sq_dists(jnp, x, c, precision=hi), 1)
                return jax.ops.segment_sum(w, a, num_segments=m)

            attract = np.asarray(
                ds.tree_aggregate_fn(attract_fn)(cand.astype(dtype)),
                dtype=np.float64)
            attract = np.maximum(attract, 0.0) + 1e-12
        else:
            attract = np.ones(cand.shape[0])
        return _kmeans_pp(cand, attract, k, rng)


def collective_row_values(ds: InstanceDataset, fn, *extras):
    """Evaluate a per-row fn over the sharded dataset and gather to host."""
    import jax

    @jax.jit
    def run(x, y, w, *e):
        return fn(x, y, w, *e)

    return np.asarray(run(ds.x, ds.y, ds.w, *extras))


def _kmeans_pp(points: np.ndarray, weights: np.ndarray, k: int,
               rng: np.random.RandomState) -> np.ndarray:
    """Weighted k-means++ on a small candidate set (driver-side, ref
    LocalKMeans.kMeansPlusPlus)."""
    n = points.shape[0]
    first = rng.choice(n, p=weights / weights.sum())
    chosen = [first]
    d2 = ((points - points[first]) ** 2).sum(1)
    for _ in range(1, k):
        p = weights * d2
        total = p.sum()
        if total <= 0:
            remaining = [i for i in range(n) if i not in set(chosen)]
            chosen.append(rng.choice(remaining))
        else:
            nxt = rng.choice(n, p=p / total)
            chosen.append(nxt)
            d2 = np.minimum(d2, ((points - points[nxt]) ** 2).sum(1))
    return points[chosen].astype(np.float64)


class KMeansModel(Model, _KMeansParams, MLWritable, MLReadable):
    def __init__(self, centers: Optional[np.ndarray] = None,
                 training_cost: float = 0.0, uid=None):
        super().__init__(uid)
        self._declare_kmeans_params()
        self._centers = np.asarray(centers) if centers is not None else None
        self.training_cost = training_cost
        self.summary: Optional[KMeansSummary] = None

    @property
    def num_iterations(self) -> int:
        """Alias of ``summary.num_iter`` (0 for a model that was loaded)."""
        return self.summary.num_iter if self.summary is not None else 0

    @property
    def cluster_centers(self):
        return [row for row in self._centers]

    def cluster_centers_matrix(self) -> DenseMatrix:
        return DenseMatrix.from_array(self._centers)

    def _assign(self, x: np.ndarray) -> np.ndarray:
        if x.ndim == 1:
            x = x[:, None]
        if self.get("distanceMeasure") == "cosine":
            x = normalize_rows(np, x)
        d2 = pairwise_sq_dists(np, x, self._centers)
        return d2.argmin(1).astype(np.float64)

    def _transform(self, frame: MLFrame) -> MLFrame:
        x = frame[self.get("featuresCol")]
        return frame.with_column(self.get("predictionCol"), self._assign(x))

    def predict(self, features) -> int:
        arr = features.to_array() if hasattr(features, "to_array") else np.asarray(features)
        return int(self._assign(arr[None, :])[0])

    def compute_cost(self, frame: MLFrame) -> float:
        """Sum of squared distances (deprecated in ref in favor of evaluator,
        kept for parity with mllib KMeansModel.computeCost)."""
        x = frame[self.get("featuresCol")]
        if x.ndim == 1:
            x = x[:, None]
        if self.get("distanceMeasure") == "cosine":
            x = normalize_rows(np, x)
        d2 = pairwise_sq_dists(np, x, self._centers)
        return float(np.maximum(d2.min(1), 0.0).sum())

    def _save_data(self, path: str) -> None:
        save_arrays(path, centers=self._centers,
                    training_cost=np.array(self.training_cost))

    def _load_data(self, path: str, meta) -> None:
        arrs = load_arrays(path)
        self._centers = arrs["centers"]
        self.training_cost = float(arrs["training_cost"])
