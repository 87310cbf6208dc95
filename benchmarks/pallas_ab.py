"""A/B microbenchmark: XLA-fused aggregators vs the Pallas kernels.

Run on real TPU hardware (`python benchmarks/pallas_ab.py`). No result of
it is committed: the earlier head-to-heads were builder runs of rounds 3-5
whose record was deleted in PR 21, and it has not been run on the current
machine (ROADMAP S8/D3 decide the ``cyclone.ml.usePallasKernels`` default
from a benchmark cell, not from this script).

Methodology: each variant runs ITERS times inside ONE jitted
``lax.scan`` whose carry depends on the previous output, so one dispatch
covers the whole chain (per-call dispatch cost: not measured), and the
wall clock covers a scalar host readback that ends on real completion.
"""

import sys
import time

import numpy as np

ITERS = 50


def _time_chain(make_step, carry0, data, iters=ITERS):
    """make_step: (carry, *data) -> new carry (data-dependent chain).
    ``data`` rides as jit ARGUMENTS — closure capture would bake it into
    the HLO as multi-hundred-MB constants.
    Returns ms/iter."""
    import jax

    @jax.jit
    def run(c0, *args):
        def body(c, _):
            return make_step(c, *args), None
        out, _ = jax.lax.scan(body, c0, None, length=iters)
        return jax.tree_util.tree_reduce(
            lambda a, b: a + b.sum(), out, 0.0)

    float(run(carry0, *data))  # compile
    t0 = time.perf_counter()
    float(run(carry0, *data))
    return (time.perf_counter() - t0) / iters * 1e3


def main():
    import jax
    import jax.numpy as jnp
    from cycloneml_tpu.ml.optim import aggregators
    from cycloneml_tpu.ops.kernels import (fused_binary_logistic,
                                           fused_kmeans_assign,
                                           pallas_available)
    from cycloneml_tpu.ml.clustering._util import pairwise_sq_dists

    print(f"backend={jax.default_backend()} "
          f"native_pallas={pallas_available()}", file=sys.stderr)
    rng = np.random.RandomState(0)
    rows = []

    # -- binomial logistic loss+grad: (n, d) block, one eval ------------
    for n, d in [(131072, 512), (262144, 128), (32768, 2048)]:
        x = jnp.asarray(rng.randn(n, d), jnp.float32)
        y = jnp.asarray(rng.rand(n) > 0.5, jnp.float32)
        w = jnp.ones(n, jnp.float32)
        coef0 = jnp.asarray(rng.randn(d + 1), jnp.float32)
        agg = aggregators.binary_logistic(d, True)

        def xla_step(coef, xv, yv, wv):
            out = agg(xv, yv, wv, coef)
            return coef - 1e-9 * out["grad"]  # data-dependent chain

        def pal_step(coef, xv, yv, wv):
            out = fused_binary_logistic(xv, yv, wv, coef, d, True)
            return coef - 1e-9 * out["grad"]

        xla = _time_chain(xla_step, coef0, (x, y, w))
        pal = _time_chain(pal_step, coef0, (x, y, w))
        rows.append(("logistic", f"{n}x{d}", xla, pal))

    # -- SCALED binomial logistic (folded standardization, raw X) --------
    from cycloneml_tpu.ops.kernels import fused_binary_logistic_scaled
    for n, d in [(131072, 512), (262144, 128)]:
        x = jnp.asarray(rng.randn(n, d), jnp.float32)
        y = jnp.asarray(rng.rand(n) > 0.5, jnp.float32)
        w = jnp.ones(n, jnp.float32)
        inv_std = jnp.asarray(1.0 / (rng.rand(d) + 0.5), jnp.float32)
        smean = jnp.asarray(rng.randn(d), jnp.float32)
        coef0 = jnp.asarray(rng.randn(d + 1), jnp.float32)
        agg_s = aggregators.binary_logistic_scaled(d, True)

        def xla_step(coef, xv, yv, wv, isv, smv):
            out = agg_s(xv, yv, wv, isv, smv, coef)
            return coef - 1e-9 * out["grad"]

        def pal_step(coef, xv, yv, wv, isv, smv):
            out = fused_binary_logistic_scaled(
                xv, yv, wv, isv, smv, coef, d, True)
            return coef - 1e-9 * out["grad"]

        xla = _time_chain(xla_step, coef0, (x, y, w, inv_std, smean))
        pal = _time_chain(pal_step, coef0, (x, y, w, inv_std, smean))
        rows.append(("logistic_scaled", f"{n}x{d}", xla, pal))

    # -- kmeans assignment: (n, d) x (k, d) ------------------------------
    hi = jax.lax.Precision.HIGHEST
    for n, d, k in [(131072, 128, 100), (65536, 256, 1000)]:
        x = jnp.asarray(rng.randn(n, d), jnp.float32)
        c0 = jnp.asarray(rng.randn(k, d), jnp.float32)

        def xla_step(c, xv):
            d2 = pairwise_sq_dists(jnp, xv, c, precision=hi)
            dist = jnp.maximum(jnp.min(d2, axis=1), 0.0)
            return c + 1e-12 * dist.sum()  # data-dependent chain

        def pal_step(c, xv):
            _, dist = fused_kmeans_assign(xv, c)
            return c + 1e-12 * dist.sum()

        xla = _time_chain(xla_step, c0, (x,))
        pal = _time_chain(pal_step, c0, (x,))
        rows.append(("kmeans_assign", f"{n}x{d},k={k}", xla, pal))

    print(f"{'op':<14} {'shape':<18} {'xla_ms':>8} {'pallas_ms':>10} "
          f"{'pallas/xla':>11}")
    for op, shape, xla, pal in rows:
        print(f"{op:<14} {shape:<18} {xla:8.2f} {pal:10.2f} "
              f"{pal / xla:11.2f}")


if __name__ == "__main__":
    main()
