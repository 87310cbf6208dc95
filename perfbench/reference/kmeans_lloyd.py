"""Plain reference of Lloyd's k-means iterations from a stated starting set,
as Spark MLlib states them (``mllib/clustering/KMeans.scala``: Euclidean
distance, ``maxIter`` steps at most, stop when no centre moves ``tol`` or
more, an empty cluster keeps its centre; ``setInitialModel`` for the start):

    a_i = argmin_c |x_i - c|^2   (the LOWEST index on a tie),
    c  <- mean of the x_i with a_i = c,

a deterministic map with one answer: the ``(k, d)`` centres after the steps.

The points are ``perfbench.kmeans_points`` of the generator's stored rows,
rebuilt block by block (the generator's label is ignored); the starting set
is ``kmeans_points.start``. Distances ``|x|^2 - 2 x.c + |c|^2`` in float32
at ``highest`` in row blocks, the per-block sums, counts and costs added on
the host in float64, the centre update and the stop rule in float64.

The model is one flat vector for ``judge.compare``: ``coef`` = the centres
row-major (k d numbers), ``intercept`` = 0.0; ``objective`` is the cost
``sum_i min_c |x_i - c|^2`` AT the returned centres (one more assignment
pass: MLlib reports the last step's cost, at the centres before their
update — the program reports it as here, and says so).
"""

from __future__ import annotations

import numpy as np

from perfbench import kmeans_points
from perfbench.reference import blocks

CONFIG = "kmeans_synth128_k1000"


def _distances(pf, centres, centre_bits: int):
    """``(b, k)`` float32 squared distances of a block. ``centre_bits`` (a
    planted fault: 0 = none) rounds the centres to that many mantissa bits
    in the product alone, which is what a step does that hands the MXU the
    centres as one bfloat16 piece (7 bits)."""
    import jax
    import jax.numpy as jnp
    used = jax.lax.reduce_precision(centres, 8, centre_bits) \
        if centre_bits else centres
    return jnp.sum(pf * pf, axis=1)[:, None] \
        - 2.0 * jnp.dot(pf, used.T, precision=jax.lax.Precision.HIGHEST) \
        + jnp.sum(centres * centres, axis=1)[None, :]


def _block(quant, centre_bits: int, update: bool):
    def block(xf, yb, scale, mu, centres):
        import jax
        import jax.numpy as jnp
        # the stored row comes back from its float32 copy to the bit
        pts = kmeans_points.block_points(xf.astype(jnp.bfloat16), mu)
        pf = blocks.load_block(pts, quant, scale)
        d2 = _distances(pf, centres, centre_bits)
        out = {"cost": jnp.sum(jnp.maximum(jnp.min(d2, axis=1), 0.0))}
        if update:
            hit = jax.nn.one_hot(jnp.argmin(d2, axis=1), centres.shape[0],
                                 dtype=jnp.float32)
            out.update(
                sums=jnp.dot(hit.T, pf, precision=jax.lax.Precision.HIGHEST),
                counts=jnp.sum(hit, axis=0))
        return out, {}
    return block


_BLOCKS = {(quant, bits, update): _block(quant, bits, update)
           for quant in (None, "fp8") for bits in (0, 7)
           for update in (True, False)}


class Problem:
    """The cost over one dataset, and Lloyd's steps on it. ``kw`` plants
    the control (``quant``) or a fault (``rows_used``, ``shards_used``);
    ``centre_bits`` the one-piece centres."""

    def __init__(self, data, params: dict, centre_bits: int = 0,
                 quant=None, **kw):
        x = data[0]
        self.spec = kmeans_points.spec(CONFIG)
        self.k, self.d = self.spec["k"], x.shape[1]
        self.mu = np.asarray(kmeans_points.centres(
            self.spec["data_seed"], self.k, self.d, self.spec["r"]))
        # the control's per-column scale: a bound on the points' largest
        # magnitude (the stored row's plus the centres'), so nothing clips
        self.scale = (blocks.fp8_scale(data) * blocks.FP8_MAX
                      + np.abs(self.mu).max(axis=0)) / blocks.FP8_MAX \
            if quant is not None else np.ones(self.d)
        self.data, self.kw = data, kw
        self.quant, self.centre_bits = quant, int(centre_bits)
        self.max_iter = int(params["maxIter"])
        self.tol = float(params["tol"])

    def _sweep(self, centres, update: bool):
        block = _BLOCKS[self.quant, self.centre_bits, update]
        small, _, _ = blocks.sweep(
            block, self.data, (self.scale, self.mu, centres), **self.kw)
        return small

    def lloyd(self):
        """``(centres (k, d) float64, steps)`` from the stated start."""
        centres = kmeans_points.start(
            self.spec["data_seed"], self.k, self.d, self.spec["r"])
        steps = 0
        for steps in range(1, self.max_iter + 1):
            s = self._sweep(centres, True)
            counts = s["counts"][:, None]
            new = np.where(counts > 0, s["sums"] / np.maximum(counts, 1e-300),
                           centres)
            moved = float(np.linalg.norm(new - centres, axis=1).max())
            centres = new
            if moved < self.tol:
                break
        return centres, steps

    def objective_of(self, models: np.ndarray, _intercepts=None):
        """The cost of ANY flat centre sets ``(m, k d)`` on the points
        (``judge.compare``'s scalar intercept carries nothing)."""
        models = np.asarray(models, np.float64).reshape(-1, self.k, self.d)
        return np.array([float(self._sweep(c, False)["cost"])
                         for c in models])


def fit(data, params: dict, **kw):
    """``{"coef", "intercept", "objective", "problem", "iterations"}``: the
    centres flattened row-major and the cost they reach."""
    prob = Problem(data, params, **kw)
    centres, steps = prob.lloyd()
    return {"coef": centres.ravel(), "intercept": 0.0,
            "objective": float(prob.objective_of(centres.ravel()[None])[0]),
            "problem": prob, "iterations": steps}
