"""Benchmark harness writing a versioned results file.

Mirrors the reference's committed-benchmark discipline (ref:
mllib-local/benchmarks/BLASBenchmark-results.txt and the Benchmark harness
that regenerates them — SURVEY §4 'benchmarks as tests': results are files
in the repo, regressions are reviewed as diffs).

Run on the target hardware:
    PYTHONPATH=. python benchmarks/run_benchmarks.py > benchmarks/results-<hw>.txt

Timing uses data-dependent jit scan chains with a scalar readback — per-call
dispatch latency is amortized and the timed region ends on real completion
(see bench.py).
"""

from __future__ import annotations

import time

import numpy as np


def bench_gemm(dim, iters=100):
    import jax
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.randn(dim, dim), jnp.float32)
    b = jnp.asarray(rng.randn(dim, dim), jnp.float32)

    @jax.jit
    def run(a, b):
        def body(c, _):
            out = jnp.dot(c, b, precision=jax.lax.Precision.HIGHEST)
            return out * (1.0 / dim), None
        c, _ = jax.lax.scan(body, a, None, length=iters)
        return jnp.sum(c)

    float(run(a, b))  # compile
    t0 = time.perf_counter()
    float(run(a, b))
    dt = (time.perf_counter() - t0) / iters
    return 2.0 * dim ** 3 / dt / 1e12, dt


def bench_logistic_eval(n, d, iters=50):
    """Distributed gradient evaluation (the north-star inner loop)."""
    import jax
    import jax.numpy as jnp
    from cycloneml_tpu.ml.optim import aggregators
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(n, d), jnp.float32)
    y = jnp.asarray((rng.rand(n) > 0.5), jnp.float32)
    w = jnp.ones(n, jnp.float32)
    coef0 = jnp.asarray(rng.randn(d + 1), jnp.float32)
    agg = aggregators.binary_logistic(d, True)

    @jax.jit
    def run(x, y, w, c0):
        def body(c, _):
            out = agg(x, y, w, c)
            return c - 1e-6 * out["grad"].astype(c.dtype), out["loss"]
        c, losses = jax.lax.scan(body, c0, None, length=iters)
        return jnp.sum(losses)

    float(run(x, y, w, coef0))
    t0 = time.perf_counter()
    float(run(x, y, w, coef0))
    dt = (time.perf_counter() - t0) / iters
    return dt, n * d * 4 / dt / 1e9


def bench_sparse_eval(n, k, d, iters=20):
    import jax
    import jax.numpy as jnp
    from cycloneml_tpu.ml.optim.sparse_aggregators import binary_logistic_sparse
    rng = np.random.RandomState(0)
    idx = jnp.asarray(rng.randint(0, d, size=(n, k)), jnp.int32)
    val = jnp.asarray(np.abs(rng.randn(n, k)), jnp.float32)
    y = jnp.asarray((rng.rand(n) > 0.5), jnp.float32)
    w = jnp.ones(n, jnp.float32)
    coef0 = jnp.zeros(d, jnp.float32)
    agg = binary_logistic_sparse(d, False)

    @jax.jit
    def run(idx, val, y, w, c0):
        def body(c, _):
            out = agg(idx, val, y, w, c)
            return c - 1e-2 * out["grad"].astype(c.dtype), out["loss"]
        c, losses = jax.lax.scan(body, c0, None, length=iters)
        return jnp.sum(losses)

    float(run(idx, val, y, w, coef0))
    t0 = time.perf_counter()
    float(run(idx, val, y, w, coef0))
    dt = (time.perf_counter() - t0) / iters
    return dt, n * k / dt / 1e9


def bench_kmeans_assign(n, d, kc, iters=50):
    import jax
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(n, d), jnp.float32)
    c0 = jnp.asarray(rng.randn(kc, d), jnp.float32)

    @jax.jit
    def run(x, c0):
        def body(c, _):
            d2 = (jnp.sum(x * x, 1)[:, None] - 2 * x @ c.T
                  + jnp.sum(c * c, 1)[None, :])
            best = jnp.argmin(d2, 1)
            onehot = jax.nn.one_hot(best, kc, dtype=x.dtype)
            sums = onehot.T @ x
            counts = jnp.sum(onehot, 0)[:, None]
            return sums / jnp.maximum(counts, 1.0), jnp.min(d2)
        c, aux = jax.lax.scan(body, c0, None, length=iters)
        return jnp.sum(c) + jnp.sum(aux)

    float(run(x, c0))
    t0 = time.perf_counter()
    float(run(x, c0))
    dt = (time.perf_counter() - t0) / iters
    return dt, n * kc * d * 2 / dt / 1e12


def main():
    import jax
    dev = jax.devices()[0]
    print(f"CycloneML-TPU benchmarks — platform={dev.platform} "
          f"device={getattr(dev, 'device_kind', '?')}")
    print(f"ref baseline: dgemm best-java 2409.7 M ops/s "
          f"(BLASBenchmark-results.txt:158-169)")
    print()
    print("GEMM f32 (HIGHEST precision), square matrices:")
    for dim in (1024, 2048, 4096):
        tflops, dt = bench_gemm(dim)
        vs = tflops * 1e6 / 2409.7
        print(f"  {dim:5d}: {dt*1e3:8.3f} ms  {tflops:8.2f} TFLOP/s  "
              f"({vs:,.0f}x ref java dgemm)")
    print()
    print("Binary-logistic loss+grad evaluation (dense blocks):")
    for n, d in ((131072, 512), (262144, 256), (65536, 2048)):
        dt, gbs = bench_logistic_eval(n, d)
        print(f"  {n:7d}x{d:<5d}: {dt*1e3:8.3f} ms/eval  "
              f"{gbs:6.1f} GB/s effective")
    print()
    print("Sparse (ELL) logistic evaluation:")
    for n, k, d in ((200_000, 39, 1 << 18), (1_000_000, 39, 1 << 20)):
        dt, gnnz = bench_sparse_eval(n, k, d)
        print(f"  n={n:>9,} k={k} d=2^{int(np.log2(d))}: "
              f"{dt*1e3:8.2f} ms/eval  {gnnz:6.3f} Gnnz/s")
    print()
    print("KMeans Lloyd iteration (assign + center update):")
    for n, d, kc in ((500_000, 64, 100), (100_000, 128, 1000)):
        dt, tflops = bench_kmeans_assign(n, d, kc)
        print(f"  n={n:>8,} d={d:<4d} k={kc:<5d}: {dt*1e3:8.2f} ms/iter  "
              f"{tflops:6.2f} TFLOP/s")


if __name__ == "__main__":
    main()
