"""`make serve-demo`: the serving subsystem's acceptance demo.

Registers two fitted models on a ModelServer, fires a storm of
concurrent mixed-size requests, then asserts the serving contract:

1. compile-count == bucket-count — every XLA compile was paid by
   registration warm-up; the request storm compiled NOTHING;
2. p99 request latency stays under the window bound (the batching
   window + a dispatch allowance — the latency price of coalescing is
   bounded by construction);
3. concurrent requests actually coalesced (batches < requests);
4. every prediction bitwise-matches the model's own host predict.

Exits nonzero on any violation.
"""

import os
import sys
import threading

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402

WINDOW_MS = 20.0
# dispatch allowance on top of the window: tiny CPU matvecs dispatch in
# well under this; the bound exists to catch a REcompile (tens of ms per
# bucket) or a stuck batcher, not to benchmark the box
DISPATCH_ALLOWANCE_MS = 150.0
N_REQUESTS = 120
N_THREADS = 8
D = 48


def main() -> int:
    from cycloneml_tpu import CycloneConf, CycloneContext
    from cycloneml_tpu.dataset.frame import MLFrame
    from cycloneml_tpu.ml.classification import LogisticRegression
    from cycloneml_tpu.serving import ModelServer, bucket_sizes

    ctx = CycloneContext.get_or_create(
        CycloneConf().set("cyclone.app.name", "serve-demo")
        # this demo puts itself on the CPU (setdefault above) and says so:
        # the default master 'tpu' means TPU or raise
        .set("cyclone.master", "local-mesh[*]"))
    rng = np.random.RandomState(3)
    x = rng.randn(2048, D).astype(np.float32)
    w = rng.randn(D)
    y = (x @ w > 0).astype(np.float64)
    frame = MLFrame(ctx, {"features": x, "label": y})
    models = {
        "churn": LogisticRegression(maxIter=10, regParam=0.01).fit(frame),
        "fraud": LogisticRegression(maxIter=10, regParam=0.2).fit(frame),
    }

    srv = ModelServer(ctx=ctx, max_batch=32, window_ms=WINDOW_MS)
    for name, model in models.items():
        info = srv.register(name, model)
        print(f"registered {name!r}: buckets={info['buckets']} "
              f"compiles={info['compiles']}")
    n_buckets = len(bucket_sizes(32))
    total_compiles = sum(srv.compile_counts().values())
    # the two models share d=48 shapes, so the SECOND registration reuses
    # the first's executables: total compiles == one bucket set
    assert total_compiles == n_buckets, \
        f"expected {n_buckets} compiles (one per bucket), got {total_compiles}"

    errors = []
    sizes = [1, 2, 4, 7, 9, 16]
    # payloads pre-generated BEFORE the threads start: the shared legacy
    # RandomState is not thread-safe, and the demo's numbers should be
    # reproducible under its seed
    payloads = [rng.randn(sizes[i % len(sizes)], D)
                for i in range(N_REQUESTS)]

    def client(i: int) -> None:
        name = ("churn", "fraud")[i % 2]
        xq = payloads[i]
        try:
            got = srv.predict(name, xq)
            ref = models[name]._predict_batch(xq)
            if not np.array_equal(got, ref):
                errors.append(f"{name}: prediction mismatch")
        except Exception as e:  # noqa: BLE001 — demo reports and fails
            errors.append(f"{name}: {e!r}")

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(N_REQUESTS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    stats = srv.stats()
    srv.stop()

    assert not errors, errors[:5]
    totals = stats["totals"]
    assert totals["requests"] == N_REQUESTS
    after = sum(m["compiles"] for m in stats["models"].values())
    assert after == n_buckets, \
        f"request storm compiled! {after} != {n_buckets}"
    assert totals["batches"] < N_REQUESTS, "no coalescing happened"
    p99 = max(m["latencyMs"]["p99"] for m in stats["models"].values())
    bound = WINDOW_MS + DISPATCH_ALLOWANCE_MS
    assert p99 < bound, f"p99 {p99:.1f} ms over the window bound {bound} ms"
    print(f"serve-demo OK: {N_REQUESTS} requests, "
          f"{totals['batches']} batches ({totals['coalesced']} coalesced), "
          f"p99 {p99:.2f} ms < {bound:.0f} ms bound, "
          f"{after} compiles == {n_buckets} buckets, "
          f"{totals['shed']} shed")
    ctx.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
