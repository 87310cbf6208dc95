"""Distributed matrices.

Re-design of ``mllib/linalg/distributed`` (ref: RowMatrix.scala:47 — 868 LoC;
EigenValueDecomposition.scala:87 ARPACK Lanczos): a RowMatrix is an
``InstanceDataset``'s feature block, rows sharded over the mesh.

- ``compute_gramian``: XᵀX as one psum'd MXU matmul — replaces the
  treeAggregate of packed ``spr`` rank-1 updates (ref RowMatrix.scala:130,147).
- ``compute_svd``: for d ≤ max_gram_dim, eigendecomposition of the Gramian
  (the reference's LocalARPACK/LocalLAPACK branch :303); otherwise Lanczos
  with full reorthogonalization where each matvec XᵀXv is a distributed
  psum'd program — the ARPACK-equivalent (``dsaupd`` loop) without JNI.
- ``compute_principal_components``/``compute_covariance``
  (ref :486,523) — covariance from the Gramian + mean, eigh on the driver.
- ``multiply``, ``column_similarities`` (brute-force cosine via the Gramian —
  the DIMSUM sampling path is a CPU-era optimisation; one MXU matmul replaces
  it exactly).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import numpy as np

from cycloneml_tpu.dataset.dataset import InstanceDataset
from cycloneml_tpu.linalg.matrices import DenseMatrix, Matrix
from cycloneml_tpu.linalg.vectors import DenseVector, Vectors
from cycloneml_tpu.ml.stat.summarizer import Summarizer


class SVDResult(NamedTuple):
    U: Optional["RowMatrix"]
    s: DenseVector
    V: DenseMatrix


@functools.lru_cache(maxsize=None)
def _presence_gramian(feature_major: bool):
    """Per-shard ``X'X`` over the rows that exist (w > 0); cached so every
    call reuses one aggregation program."""
    def presence_gramian(x, y, w):
        import jax.numpy as jnp
        from cycloneml_tpu.ops.kernels import moment_sums
        return moment_sums(x, jnp.zeros_like(w), (w > 0).astype(w.dtype),
                           feature_major=feature_major)["aa_sum"]
    return presence_gramian


class RowMatrix:
    """Row-oriented distributed matrix without meaningful row indices
    (ref RowMatrix.scala:47).

    Backed by either the dense device tier (``InstanceDataset``) or the
    sparse ELL tier (``SparseInstanceDataset``) — the reference's RowMatrix
    is likewise storage-agnostic over dense/sparse vectors. Gramian and the
    Lanczos SVD operator dispatch on the tier; the sparse large-d path is
    the NYTimes-class bag-of-words configuration (BASELINE config 5)."""

    def __init__(self, dataset):
        self.dataset = dataset

    @classmethod
    def from_numpy(cls, ctx, x: np.ndarray) -> "RowMatrix":
        return cls(InstanceDataset.from_numpy(ctx, x))

    def num_rows(self) -> int:
        return self.dataset.n_rows

    def num_cols(self) -> int:
        return self.dataset.n_features

    # -- gramian ---------------------------------------------------------------
    def compute_gramian(self) -> DenseMatrix:
        """XᵀX (ref computeGramianMatrix:130 — treeAggregate of spr:147).

        On a mesh with a model axis (model_parallelism > 1) and a divisible
        feature dim, the Gram matrix is computed feature-sharded via the
        ppermute ring (SURVEY §5.7a) — no device materializes the full
        (d, d) — and gathered to the host here. Use
        :meth:`compute_gramian_sharded` to keep it on the mesh when d is too
        large to gather.
        """
        sharded = self.compute_gramian_sharded()
        if sharded is not None:
            return DenseMatrix.from_array(
                np.asarray(sharded, dtype=np.float64))
        import jax
        import jax.numpy as jnp
        from cycloneml_tpu.dataset.sparse import SparseInstanceDataset

        if isinstance(self.dataset, SparseInstanceDataset):
            # small-d sparse Gramian: densify each ELL block on device
            # (scatter into (block, d)) and run the same einsum; for large
            # d use compute_svd's Lanczos operator instead of materializing
            # (d, d)
            d = self.num_cols()
            if self.dataset.is_hybrid:
                def agg(indices, values, coo_row, coo_idx, coo_val, y, w):
                    n_b = indices.shape[0]
                    dense = jnp.zeros((n_b, d), values.dtype)
                    dense = dense.at[
                        jnp.arange(n_b)[:, None], indices].add(values)
                    dense = dense.at[coo_row, coo_idx].add(coo_val)
                    return jnp.einsum(
                        "bi,bj->ij",
                        dense * (w > 0)[:, None].astype(values.dtype),
                        dense, precision=jax.lax.Precision.HIGHEST)
            else:
                def agg(indices, values, y, w):
                    n_b = indices.shape[0]
                    dense = jnp.zeros((n_b, d), values.dtype)
                    dense = dense.at[
                        jnp.arange(n_b)[:, None], indices].add(values)
                    return jnp.einsum(
                        "bi,bj->ij",
                        dense * (w > 0)[:, None].astype(values.dtype),
                        dense, precision=jax.lax.Precision.HIGHEST)
            out = self.dataset.tree_aggregate_fn(agg)()
            return DenseMatrix.from_array(np.asarray(out, dtype=np.float64))

        from cycloneml_tpu.ops.kernels import stored_feature_major
        # the package's one dense Gramian (ops/kernels.moment_sums: the
        # moment pass WeightedLeastSquares aggregates) under the presence
        # mask — one storage-width read of X, tiled the way X is stored
        out = self.dataset.tree_aggregate_fn(_presence_gramian(
            stored_feature_major(self.dataset.x)))()
        return DenseMatrix.from_array(np.asarray(out, dtype=np.float64))

    def compute_gramian_sharded(self):
        """Model-axis-sharded Gramian (``P(model, None)`` device array), or
        None when the mesh has no model axis / d does not divide it."""
        from cycloneml_tpu.dataset.sparse import SparseInstanceDataset
        from cycloneml_tpu.parallel import feature_sharding as fs
        if isinstance(self.dataset, SparseInstanceDataset):
            return None  # the ring is a dense-block pipeline
        rt = self.dataset.ctx.mesh_runtime
        d = self.num_cols()
        m = fs.model_parallelism(rt)
        if m <= 1 or d % m != 0:
            return None
        # the ppermute ring accumulates in X's dtype; narrow data-tier
        # blocks upcast at the TP boundary (fs.accumulator_width)
        x_tp = fs.feature_sharded_put(
            rt, fs.accumulator_width(self.dataset.x))
        return fs.gramian_feature_sharded(rt, x_tp, w=self.dataset.w)

    # -- covariance / pca ------------------------------------------------------
    def compute_covariance(self) -> DenseMatrix:
        """Sample covariance (ref computeCovariance:332): (XᵀX − n·x̄x̄ᵀ)/(n−1)."""
        n = self.num_rows()
        if n < 2:
            raise ValueError("need at least 2 rows for covariance")
        g = self.compute_gramian().to_array()
        mean = Summarizer.summarize(self.dataset).mean
        cov = (g - n * np.outer(mean, mean)) / (n - 1.0)
        return DenseMatrix.from_array(cov)

    def compute_principal_components_and_variance(
            self, k: int) -> Tuple[DenseMatrix, DenseVector]:
        """(ref computePrincipalComponentsAndExplainedVariance:486)."""
        d = self.num_cols()
        if not 1 <= k <= d:
            raise ValueError(f"k must be in [1,{d}]")
        cov = self.compute_covariance().to_array()
        vals, vecs = np.linalg.eigh(cov)  # ascending
        order = np.argsort(vals)[::-1]
        vals, vecs = vals[order], vecs[:, order]
        vecs = _sign_convention(vecs)
        total = max(vals.sum(), 1e-300)
        return (DenseMatrix.from_array(vecs[:, :k]),
                Vectors.dense(vals[:k] / total))

    def compute_principal_components(self, k: int) -> DenseMatrix:
        return self.compute_principal_components_and_variance(k)[0]

    # -- svd -------------------------------------------------------------------
    def compute_svd(self, k: int, compute_u: bool = False,
                    r_cond: float = 1e-9, max_gram_dim: int = 4096,
                    tol: float = 1e-10, max_iter: int = 300) -> SVDResult:
        """Top-k singular value decomposition (ref computeSVD:303).

        Mode selection mirrors the reference: small d → Gramian eigen on the
        driver ("LocalLAPACK"); large d → distributed Lanczos on the operator
        v ↦ XᵀXv ("DistARPACK", EigenValueDecomposition.scala:87).
        """
        d = self.num_cols()
        n = self.num_rows()
        if not 1 <= k <= d:
            raise ValueError(f"k must be in [1,{d}]")
        if d <= max_gram_dim:
            g = self.compute_gramian().to_array()
            vals, vecs = np.linalg.eigh(g)
            order = np.argsort(vals)[::-1]
            vals, vecs = vals[order][:k], vecs[:, order][:, :k]
        else:
            vals, vecs = self._lanczos(k, tol=tol, max_iter=max_iter)
        sigmas = np.sqrt(np.maximum(vals, 0.0))
        # rank by rCond relative to largest (ref :351)
        if sigmas.size == 0 or sigmas[0] <= 0:
            raise ValueError("matrix has rank 0")
        keep = sigmas > r_cond * sigmas[0]
        sigmas = sigmas[keep]
        vecs = _sign_convention(vecs[:, keep])
        s = Vectors.dense(sigmas)
        v = DenseMatrix.from_array(vecs)
        u = None
        if compute_u:
            # U = X V Σ⁻¹, rows stay sharded on device
            import jax
            import jax.numpy as jnp
            from cycloneml_tpu.dataset.sparse import SparseInstanceDataset
            if isinstance(self.dataset, SparseInstanceDataset):
                raise NotImplementedError(
                    "compute_u over the sparse tier: project with "
                    "multiply() after densifying, or request V/σ only")
            vs = jnp.asarray(vecs / sigmas[None, :])
            ux = jax.jit(lambda x, m: jnp.dot(
                x, m, precision=jax.lax.Precision.HIGHEST))(self.dataset.x, vs)
            ds = self.dataset.derive(x=ux, n_features=int(sigmas.size))
            u = RowMatrix(ds)
        return SVDResult(u, s, v)

    def _gram_matvec_fn(self):
        """q ↦ XᵀXq as one jitted psum aggregate — dense blocks use two
        MXU gemvs; sparse (ELL / ELL+COO) blocks use the gather/segment-sum
        pair the sparse training aggregators are built from. The reference
        ships the same product through treeAggregate inside ARPACK's
        reverse-communication loop (EigenValueDecomposition.scala:87)."""
        import jax
        import jax.numpy as jnp
        from cycloneml_tpu.dataset.sparse import SparseInstanceDataset

        d = self.num_cols()
        if isinstance(self.dataset, SparseInstanceDataset):
            from cycloneml_tpu.ml.optim import sparse_aggregators as sa
            if self.dataset.is_hybrid:
                def agg(indices, values, coo_row, coo_idx, coo_val, y, w, q):
                    m = sa._margins_hybrid(indices, values, coo_row,
                                           coo_idx, coo_val, q, 0.0)
                    m = m * (w > 0).astype(values.dtype)
                    return sa._scatter_grad_hybrid(
                        indices, values, coo_row, coo_idx, coo_val, m, d)
            else:
                def agg(indices, values, y, w, q):
                    m = sa._margins(indices, values, q, 0.0)
                    m = m * (w > 0).astype(values.dtype)
                    return sa._scatter_grad(indices, values, m, d)
            return self.dataset.tree_aggregate_fn(agg), \
                self.dataset.values.dtype
        return self.dataset.tree_aggregate_fn(
            lambda x, y, w, q: jnp.dot(
                x.T, jnp.dot(x, q, precision=jax.lax.Precision.HIGHEST)
                * (w > 0).astype(x.dtype),
                precision=jax.lax.Precision.HIGHEST)), self.dataset.x.dtype

    def _lanczos(self, k: int, tol: float, max_iter: int):
        """Lanczos with full reorthogonalization on the driver; the matvec
        is the distributed psum from :meth:`_gram_matvec_fn`."""
        d = self.num_cols()
        matvec_agg, dt = self._gram_matvec_fn()

        def matvec(q: np.ndarray) -> np.ndarray:
            return np.asarray(matvec_agg(q.astype(dt)), dtype=np.float64)

        rng = np.random.RandomState(0)
        m = min(d, max_iter)
        min_steps = min(max(3 * k, 20), m)
        # the Ritz-stability stop cannot resolve below the matvec dtype's
        # noise floor: on the f32 device path converged values still jitter
        # at ~eps relative, so flooring at 32·eps stops when further steps
        # only chase quantization (f64 keeps the user's tol)
        try:
            ritz_tol = max(tol, 32.0 * float(np.finfo(np.dtype(dt)).eps))
        except ValueError:  # non-float dt cannot happen for matvec, but
            ritz_tol = max(tol, 1e-12)
        q = rng.randn(d)
        q /= np.linalg.norm(q)
        qs = [q]
        alphas, betas = [], []
        prev_ritz = None
        for j in range(m):
            z = matvec(qs[j])
            a = float(qs[j] @ z)
            alphas.append(a)
            z = z - a * qs[j] - (betas[-1] * qs[j - 1] if betas else 0.0)
            # full reorthogonalization (twice for stability)
            for _ in range(2):
                for qi in qs:
                    z -= (qi @ z) * qi
            b = float(np.linalg.norm(z))
            if b < tol:
                break
            # grow the subspace past the 3k floor until the wanted Ritz
            # values stop moving — clustered tails need more than 3k steps
            # (ARPACK's restart loop plays this role in the reference)
            if j + 1 >= min_steps and (j + 1) % 5 == 0:
                t = np.diag(alphas)
                for i, bb in enumerate(betas):
                    t[i, i + 1] = t[i + 1, i] = bb
                ritz = np.sort(np.linalg.eigvalsh(t))[::-1][:k]
                if prev_ritz is not None and len(prev_ritz) == len(ritz):
                    denom = np.maximum(np.abs(ritz), 1e-300)
                    if np.max(np.abs(ritz - prev_ritz) / denom) < ritz_tol:
                        betas.append(b)
                        qs.append(z / b)
                        break
                prev_ritz = ritz
            betas.append(b)
            qs.append(z / b)
        t = np.diag(alphas)
        for i, b in enumerate(betas[: len(alphas) - 1]):
            t[i, i + 1] = t[i + 1, i] = b
        evals, evecs = np.linalg.eigh(t)
        order = np.argsort(evals)[::-1][:k]
        basis = np.stack(qs[: t.shape[0]], axis=1)
        return evals[order], basis @ evecs[:, order]

    # -- products --------------------------------------------------------------
    def multiply(self, b: Matrix) -> "RowMatrix":
        """X @ B with rows staying sharded (ref multiply:592)."""
        import jax
        import jax.numpy as jnp
        if b.num_rows != self.num_cols():
            raise ValueError("dimension mismatch")
        barr = jnp.asarray(np.asarray(b.to_array(), dtype=self.dataset.x.dtype))
        out = jax.jit(lambda x, m: jnp.dot(
            x, m, precision=jax.lax.Precision.HIGHEST))(self.dataset.x, barr)
        ds = self.dataset.derive(x=out, n_features=b.num_cols)
        return RowMatrix(ds)

    def column_similarities(self) -> DenseMatrix:
        """Upper-triangular cosine similarities between columns (ref
        columnSimilarities:613 — DIMSUM sampling unnecessary on the MXU)."""
        g = self.compute_gramian().to_array()
        norms = np.sqrt(np.maximum(np.diag(g), 1e-300))
        sim = g / norms[:, None] / norms[None, :]
        return DenseMatrix.from_array(np.triu(sim, 1))

    def compute_column_summary_statistics(self):
        return Summarizer.summarize(self.dataset)

    def to_numpy(self) -> np.ndarray:
        return self.dataset.to_numpy()[0]


def _sign_convention(vecs: np.ndarray) -> np.ndarray:
    """Deterministic sign: largest-|component| positive per column (keeps
    results comparable across runs/backends)."""
    if vecs.size == 0:
        return vecs
    idx = np.argmax(np.abs(vecs), axis=0)
    signs = np.sign(vecs[idx, np.arange(vecs.shape[1])])
    signs[signs == 0] = 1.0
    return vecs * signs[None, :]
