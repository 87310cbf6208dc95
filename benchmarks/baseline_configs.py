"""BASELINE.md harness-config runners (configs 2, 3, 5) at TRUE shape.

Each runner prints one JSON ledger line. Run on the real chip (default
master ``tpu``) — data is generated on device (configs 2/3) or host-built
sparse (config 5, the NYTimes-class ELL payload) to keep the host-to-device
transfer bounded.

  python benchmarks/baseline_configs.py config2   # epsilon-shape elasticNet LinearRegression
  python benchmarks/baseline_configs.py config3   # multi-GB KMeans k=1000
  python benchmarks/baseline_configs.py config5   # NYTimes-shape sparse SVD

Shapes:
- config2: 400,000 x 2,000 dense (the epsilon dataset's exact shape),
  elasticNet OWL-QN (ref BASELINE.json config "LinearRegression elasticNet
  (OWL-QN) on epsilon").
- config3: n x 128 dense, k=1000 (ref "KMeans k=1000 on synthetic
  100M x 128"; n sized to one chip's HBM — the 100M x 128 full run is a
  51 GB dataset that needs the 8-chip pod, see ledger note).
- config5: 300,000 x 102,660 sparse, ~232 nnz/row ≈ the UCI NYTimes
  bag-of-words shape (ref "RowMatrix.computeSVD / PCA on NYTimes";
  RowMatrix.scala:303), Lanczos over the ELL tier, top-20 singular values
  cross-checked against scipy.sparse.linalg.svds on the same matrix.
"""

import json
import resource
import sys
import time

import numpy as np


def _rss_gb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6


def config2(n: int = 400_000, d: int = 2_000) -> dict:
    from cycloneml_tpu.context import CycloneContext
    from cycloneml_tpu.dataset.random import generate_regression
    from cycloneml_tpu.ml.regression import LinearRegression

    ctx = CycloneContext.get_or_create(app_name="baseline-config2")
    t0 = time.perf_counter()
    ds = generate_regression(ctx, n, d, seed=11, noise=0.1)
    gen_s = time.perf_counter() - t0

    lr = LinearRegression(regParam=0.001, elasticNetParam=0.5,
                          maxIter=100, tol=1e-7, solver="l-bfgs")
    t0 = time.perf_counter()
    lr.fit(ds)  # warm-up: compiles
    warm_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    model = lr.fit(ds)
    fit_s = time.perf_counter() - t0
    s = model.summary
    return {"config": 2, "shape": [n, d], "gen_s": round(gen_s, 2),
            "warmup_s": round(warm_s, 2), "fit_s": round(fit_s, 2),
            "iters": s.total_iterations,
            "final_objective": float(s.objective_history[-1]),
            "nnz_coef": int(np.sum(np.abs(
                model.coefficients.to_array()) > 1e-12)),
            "rss_gb": round(_rss_gb(), 2)}


def config3(n: int = 10_000_000, d: int = 128, k: int = 1000) -> dict:
    from cycloneml_tpu.context import CycloneContext
    from cycloneml_tpu.dataset.random import RandomDatasets
    from cycloneml_tpu.ml.clustering import KMeans

    ctx = CycloneContext.get_or_create(app_name="baseline-config3")
    t0 = time.perf_counter()
    ds = RandomDatasets.normal(ctx, n, d, seed=12)
    gen_s = time.perf_counter() - t0

    km = KMeans(k=k, maxIter=10, tol=1e-5, seed=3)
    t0 = time.perf_counter()
    model = km.fit(ds)
    fit_s = time.perf_counter() - t0
    return {"config": 3, "shape": [n, d], "k": k,
            "bytes_gb": round(n * d * 4 / 1e9, 2),
            "gen_s": round(gen_s, 2), "fit_s": round(fit_s, 2),
            "iters": int(model.num_iterations),
            "cost": float(model.training_cost),
            "rss_gb": round(_rss_gb(), 2)}


def _nytimes_like(n_docs: int, vocab: int, nnz_per_doc: int, seed: int = 5):
    """Zipf-marginal bag-of-words at the UCI NYTimes shape: ~300k docs,
    102,660 vocab, ~70M nonzeros. Column draws follow a zipf(1.1) word
    marginal truncated to the vocabulary; counts are 1+poisson."""
    rng = np.random.RandomState(seed)
    # distinct words per doc: draw with replacement then dedupe per ROW —
    # duplicates are summed by the CSR constructor but ELL needs uniqueness
    # per slot to match; simpler: draw and keep duplicates, both paths sum
    idx = (rng.zipf(1.1, size=(n_docs, nnz_per_doc)) - 1) % vocab
    val = (1.0 + rng.poisson(0.6, size=(n_docs, nnz_per_doc))).astype(
        np.float32)
    return idx.astype(np.int32), val


def config5(n_docs: int = 300_000, vocab: int = 102_660,
            nnz_per_doc: int = 232, k: int = 20,
            with_scipy_oracle: bool = True) -> dict:
    from cycloneml_tpu.context import CycloneContext
    from cycloneml_tpu.dataset.sparse import SparseInstanceDataset
    from cycloneml_tpu.linalg.distributed import RowMatrix

    ctx = CycloneContext.get_or_create(app_name="baseline-config5")
    t0 = time.perf_counter()
    idx, val = _nytimes_like(n_docs, vocab, nnz_per_doc)
    gen_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    ds = SparseInstanceDataset.from_ell(ctx, idx, val, n_features=vocab)
    ingest_s = time.perf_counter() - t0

    rm = RowMatrix(ds)
    t0 = time.perf_counter()
    res = rm.compute_svd(k, max_gram_dim=4096, tol=1e-9, max_iter=300)
    svd_s = time.perf_counter() - t0
    sigmas = res.s.to_array()

    out = {"config": 5, "shape": [n_docs, vocab],
           "nnz": int(n_docs * nnz_per_doc), "k": k,
           "gen_s": round(gen_s, 2), "ingest_s": round(ingest_s, 2),
           "svd_s": round(svd_s, 2),
           "sigma_top5": [round(float(s), 4) for s in sigmas[:5]],
           "rss_gb": round(_rss_gb(), 2)}
    if with_scipy_oracle:
        import scipy.sparse as sp
        import scipy.sparse.linalg as spla
        rows = np.repeat(np.arange(n_docs), nnz_per_doc)
        csr = sp.csr_matrix((val.reshape(-1).astype(np.float64),
                             (rows, idx.reshape(-1))),
                            shape=(n_docs, vocab))
        t0 = time.perf_counter()
        ref = np.sort(spla.svds(csr, k=k,
                                return_singular_vectors=False))[::-1]
        out["scipy_s"] = round(time.perf_counter() - t0, 2)
        rel = np.abs(sigmas[:k] - ref) / ref
        out["max_rel_err_vs_scipy"] = float(np.max(rel))
    return out


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "config2"
    fn = {"config2": config2, "config3": config3, "config5": config5}[which]
    kw = {}
    for a in sys.argv[2:]:
        key, v = a.split("=")
        kw[key] = int(v) if v.isdigit() else v == "True"
    print(json.dumps(fn(**kw)))


if __name__ == "__main__":
    main()
