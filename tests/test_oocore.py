"""Out-of-core dense tier (round-3 verdict item 2).

Parity: every chunked reader must agree with its whole-file twin up to the
documented row permutation (chunk-round-robin over devices) — compared via
order-insensitive statistics (row multiset hash, Gram matrix, label moments).

Boundedness: the loader's driver-side staging must be O(chunk), not O(file).
On the CPU test mesh "device" memory IS process RAM, so the full-fit check
runs in a subprocess and asserts peak RSS stays under ~2x the dataset bytes
(one device-resident copy + chunk slack) — the whole-file path costs ~4x
(f64 parse + padded blockify copy + device placement), so the bound cleanly
separates the two. On real TPU hardware the same loader is meant to keep
the matrix in HBM only (not measured on the current machine).
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from cycloneml_tpu.dataset.dataset import InstanceDataset
from cycloneml_tpu.dataset.frame import MLFrame
from cycloneml_tpu.dataset.io import (read_csv_chunked, read_libsvm,
                                      read_npy_chunked)


def _row_stats(ds):
    """Order-insensitive fingerprint of the (unpadded, weighted) rows."""
    x, y, w = ds.to_numpy()
    order = np.lexsort(x.T)
    return x[order], y[order], w[order]


def test_from_dense_chunks_matches_from_numpy(ctx):
    rng = np.random.RandomState(0)
    x = rng.randn(1000, 7)
    y = rng.randint(0, 2, 1000).astype(float)

    def chunks():
        for lo in range(0, 1000, 128):
            yield x[lo:lo + 128], y[lo:lo + 128], None

    ds = InstanceDataset.from_dense_chunks(ctx, chunks(), 7)
    ref = InstanceDataset.from_numpy(ctx, x, y)
    assert ds.n_rows == 1000 and ds.n_features == 7
    xs, ys, ws = _row_stats(ds)
    xr, yr, wr = _row_stats(ref)
    np.testing.assert_allclose(xs, xr, rtol=1e-6)
    np.testing.assert_allclose(ys, yr)
    # host label twins attached without a readback
    assert ds._yw_host is not None
    # an aggregate over the mesh agrees (padding stays neutral)
    g1 = ds.tree_aggregate_fn(lambda a, b, c: (a * c[:, None]).T @ a)()
    g2 = ref.tree_aggregate_fn(lambda a, b, c: (a * c[:, None]).T @ a)()
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-5)


def test_from_dense_chunks_rejects_bad_width(ctx):
    with pytest.raises(ValueError, match="expected"):
        InstanceDataset.from_dense_chunks(
            ctx, iter([(np.zeros((4, 3)), None, None)]), n_features=5)


def test_read_libsvm_streamed_matches_whole_file(ctx, tmp_path):
    rng = np.random.RandomState(1)
    p = str(tmp_path / "data.svm")
    n, d = 3000, 12
    with open(p, "w") as fh:
        for i in range(n):
            idx = np.sort(rng.choice(d, 4, replace=False))
            toks = " ".join(f"{j + 1}:{rng.randn():.6f}" for j in idx)
            fh.write(f"{i % 2} {toks}\n")
    whole = read_libsvm(ctx, p, n_features=d, streamed=False)
    chunked = read_libsvm(ctx, p, n_features=d, streamed=True)
    assert chunked.n_rows == whole.n_rows == n
    xs, ys, _ = _row_stats(chunked)
    xr, yr, _ = _row_stats(whole)
    np.testing.assert_allclose(xs, xr, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(ys, yr)
    # streamed path refuses an undersized declared width instead of clipping
    with pytest.raises(ValueError, match="n_features"):
        read_libsvm(ctx, p, n_features=3, streamed=True)


def test_read_npy_chunked_matches_numpy(ctx, tmp_path):
    rng = np.random.RandomState(2)
    data = rng.randn(5000, 9).astype(np.float32)
    data[:, 0] = rng.randint(0, 2, 5000)
    p = str(tmp_path / "data.npy")
    np.save(p, data)
    ds = read_npy_chunked(ctx, p, label_col=0, chunk_rows=700)
    assert ds.shape == (5000, 8)
    xs, ys, _ = _row_stats(ds)
    ref = np.delete(data, 0, axis=1).astype(np.float64)
    order = np.lexsort(ref.T)
    np.testing.assert_allclose(xs, ref[order], rtol=1e-6)
    np.testing.assert_allclose(ys, data[order, 0])


def test_read_csv_chunked_matches_read_csv(ctx, tmp_path):
    from cycloneml_tpu.dataset.io import read_csv
    rng = np.random.RandomState(3)
    data = rng.randn(2000, 5)
    p = str(tmp_path / "data.csv")
    np.savetxt(p, data, delimiter=",", header="y,a,b,c,d", comments="")
    whole = read_csv(ctx, p, label_col=0, skip_header=True)
    chunked = read_csv_chunked(ctx, p, label_col=0, skip_header=True,
                               chunk_rows=300)
    assert chunked.shape == whole.shape
    xs, ys, _ = _row_stats(chunked)
    xr, yr, _ = _row_stats(whole)
    np.testing.assert_allclose(xs, xr, rtol=1e-5, atol=1e-8)
    np.testing.assert_allclose(ys, yr, rtol=1e-5, atol=1e-8)


_RSS_SCRIPT = textwrap.dedent("""
    import os, resource, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    from cycloneml_tpu.conf import CycloneConf
    from cycloneml_tpu.context import CycloneContext
    from cycloneml_tpu.dataset.dataset import InstanceDataset
    from cycloneml_tpu.dataset.io import read_npy_chunked
    from cycloneml_tpu.ml.clustering import KMeans

    mode, path, n, d = sys.argv[1:5]
    n, d = int(n), int(d)
    ctx = CycloneContext(CycloneConf().set("cyclone.master", "local-mesh[8]"))
    if mode == "streamed":
        ds = read_npy_chunked(ctx, path, chunk_rows=32768)
    else:  # whole-file materialization, what the loader replaces
        ds = InstanceDataset.from_numpy(ctx, np.load(path).astype(np.float64))
    assert ds.shape == (n, d), ds.shape
    m = KMeans(k=8, maxIter=2, seed=1).fit(ds)
    assert len(m.cluster_centers) == 8
    print("PEAK_RSS_KB", resource.getrusage(resource.RUSAGE_SELF).ru_maxrss)
""")


def _peak_kb(mode, path, n, d, env):
    out = subprocess.run(
        [sys.executable, "-c", _RSS_SCRIPT, mode, path, str(n), str(d)],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    return int(out.stdout.split("PEAK_RSS_KB")[1])


def _write_big_npy(p, n, d, chunk=32768):
    # write incrementally — the writer must not hold the matrix either
    import numpy.lib.format as npf
    rng = np.random.RandomState(4)
    with open(p, "wb") as fh:
        npf.write_array_header_2_0(
            fh, {"descr": "<f4", "fortran_order": False, "shape": (n, d)})
        for lo in range(0, n, chunk):
            m = min(chunk, n - lo)
            fh.write(rng.randn(m, d).astype(np.float32).tobytes())


def test_npy_reader_staging_is_chunk_bounded(tmp_path):
    """The reader's HOST staging is O(chunk), not O(file): draining the raw
    chunk iterator over a 160 MB file moves peak RSS by less than 30 MB
    (one 16 MB block + buffers). Device placement is excluded — on the CPU
    test platform mesh memory IS process RAM, which is outside the
    loader's control (same methodology as the sparse tier's bounded-RSS
    test)."""
    import resource
    from cycloneml_tpu.dataset import io as dio

    n, d = 320_000, 128  # 160 MB f32
    p = str(tmp_path / "big.npy")
    _write_big_npy(p, n, d)
    ds_bytes = n * d * 4
    assert os.path.getsize(p) > ds_bytes  # sanity

    # reuse read_npy_chunked's own chunk loop via a capturing stub mesh: we
    # drain the identical code path by calling the module-level reader with
    # a fake from_dense_chunks that just iterates
    captured = {"rows": 0}

    class _Probe:
        @staticmethod
        def from_dense_chunks(ctx, chunks, n_features, dtype=None):
            for cx, cy, cw in chunks:
                captured["rows"] += cx.shape[0]
            return None

    orig = dio.InstanceDataset
    rss0 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    dio.InstanceDataset = _Probe
    try:
        dio.read_npy_chunked(None, p, chunk_rows=32768)
    finally:
        dio.InstanceDataset = orig
    rss1 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    assert captured["rows"] == n
    assert (rss1 - rss0) * 1024 < 30e6, (rss0, rss1)


@pytest.mark.slow
def test_kmeans_out_of_core_end_to_end(tmp_path):
    """KMeans trains end-to-end on a chunk-streamed 160 MB dataset in a
    fresh subprocess with a sanity memory cap: < 5x dataset over an
    identical tiny-file baseline (one mesh-resident copy on the CPU test
    platform + concat transient + XLA-CPU unfused elementwise temps; on
    TPU the matrix lives in HBM and host staging is chunk-bounded, proven
    separately above). Anything beyond 5x means the loader regressed to
    holding the file host-side."""
    n, d = 320_000, 128
    p = str(tmp_path / "big.npy")
    _write_big_npy(p, n, d)
    ds_bytes = n * d * 4

    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    tiny = str(tmp_path / "tiny.npy")
    np.save(tiny, np.random.RandomState(0).randn(256, d).astype(np.float32))
    base_kb = _peak_kb("streamed", tiny, 256, d, env)
    peak_kb = _peak_kb("streamed", p, n, d, env)
    extra = (peak_kb - base_kb) * 1024
    assert extra < 5.0 * ds_bytes, (base_kb, peak_kb, ds_bytes)


def test_chunk_split_keeps_shards_balanced(ctx):
    """Few large chunks must not inflate padding: each chunk is split across
    all devices, so per-shard row counts differ by at most the chunk count
    and total padding stays within one sublane multiple per shard."""
    x = np.random.RandomState(5).randn(5 * 65536 // 64, 4)  # ~5120 rows

    def chunks():
        for lo in range(0, len(x), 1024):  # 5 chunks on an 8-device mesh
            yield x[lo:lo + 1024], None, None

    ds = InstanceDataset.from_dense_chunks(ctx, chunks(), 4)
    n_pad = int(ds.x.shape[0])
    assert ds.n_rows == len(x)
    # whole-chunk round-robin would pad to 2x1024x8 = 16384; balanced
    # splitting stays within one sublane multiple (8 rows) per shard
    assert n_pad <= len(x) + 8 * 8 * 2, n_pad


def test_read_csv_chunked_leading_blank_lines(ctx, tmp_path):
    p = str(tmp_path / "gap.csv")
    with open(p, "w") as fh:
        fh.write("y,a\n\n\n1.0,2.0\n\n0.0,4.0\n")
    ds = read_csv_chunked(ctx, p, label_col=0, skip_header=True)
    assert ds.shape == (2, 1)
    x, y, _ = ds.to_numpy()
    np.testing.assert_allclose(sorted(y.tolist()), [0.0, 1.0])


# -- streaming fit mode (oocore/: the out-of-core epoch engine) ---------------


def _binary_problem(n=3000, d=10, seed=11):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, d)
    y = (x @ rng.randn(d) + 0.3 * rng.randn(n) > 0).astype(float)
    return x, y


def _streaming_ds(ctx, x, y, shard_rows=700):
    from cycloneml_tpu.oocore import StreamingDataset

    def chunks():
        for lo in range(0, len(x), 450):  # chunk != shard boundaries
            yield x[lo:lo + 450], y[lo:lo + 450], None

    return StreamingDataset.from_chunks(ctx, chunks(), x.shape[1],
                                        shard_rows=shard_rows)


def test_streaming_dataset_stats_match_summarizer(ctx):
    """The shard WRITE pass harvests the Summarizer moment set: mean/std/
    weight_sum (and the label histogram) must match the in-core psum pass
    over the same rows."""
    from cycloneml_tpu.ml.stat import Summarizer
    x, y = _binary_problem()
    sds = _streaming_ds(ctx, x, y)
    try:
        ref = Summarizer.summarize(InstanceDataset.from_numpy(ctx, x, y))
        got = sds.summary()
        np.testing.assert_allclose(got.mean, ref.mean, rtol=1e-12)
        np.testing.assert_allclose(got.std, ref.std, rtol=1e-12)
        assert got.weight_sum == ref.weight_sum
        assert got.count == ref.count
        np.testing.assert_allclose(got.max, ref.max)
        np.testing.assert_allclose(got.min, ref.min)
        hist = sds.label_histogram()
        np.testing.assert_allclose(
            hist, np.bincount(y.astype(int), minlength=2))
        assert sds.num_classes == 2
    finally:
        sds.close()


def test_streamed_logreg_matches_incore(ctx):
    """Fit-mode acceptance: a streamed LogisticRegression fit (each loss/
    grad evaluation = one double-buffered epoch over shards) lands on the
    in-core coefficients. Under the f64 CPU test config the only
    difference is summation ORDER (shard partials vs device partials), so
    the envelope is ulp-level; under bf16 storage (TPU default tier) the
    documented envelope is the mixed-precision suite's ~1e-3 relative
    (docs/out-of-core.md)."""
    from cycloneml_tpu.ml.classification import LogisticRegression
    x, y = _binary_problem()
    sds = _streaming_ds(ctx, x, y)
    try:
        est = LogisticRegression(maxIter=25, regParam=0.05)
        m_stream = est.fit(sds)
        m_ref = LogisticRegression(maxIter=25, regParam=0.05).fit(
            InstanceDataset.from_numpy(ctx, x, y))
        assert m_stream.summary.streamed
        assert not m_ref.summary.streamed
        np.testing.assert_allclose(np.asarray(m_stream._coef),
                                   np.asarray(m_ref._coef),
                                   rtol=1e-9, atol=1e-12)
        np.testing.assert_allclose(np.asarray(m_stream._icpt),
                                   np.asarray(m_ref._icpt),
                                   rtol=1e-9, atol=1e-12)
        # one sweep dispatches one program per shard; evals count epochs
        assert m_stream.summary.total_dispatches \
            >= m_stream.summary.total_evals * sds.n_shards
    finally:
        sds.close()


def test_streamed_linreg_matches_incore(ctx):
    from cycloneml_tpu.ml.regression import LinearRegression
    rng = np.random.RandomState(12)
    n, d = 2500, 8
    x = rng.randn(n, d)
    y = x @ rng.randn(d) + 0.1 * rng.randn(n)
    sds = _streaming_ds(ctx, x, y)
    try:
        m_stream = LinearRegression(maxIter=25, regParam=0.1,
                                    solver="l-bfgs").fit(sds)
        m_ref = LinearRegression(maxIter=25, regParam=0.1,
                                 solver="l-bfgs").fit(
            InstanceDataset.from_numpy(ctx, x, y))
        assert m_stream.summary.streamed
        np.testing.assert_allclose(np.asarray(m_stream._coef),
                                   np.asarray(m_ref._coef),
                                   rtol=1e-9, atol=1e-12)
        # the normal solver needs the in-core matrix: explicit request fails
        # loudly, auto routes to l-bfgs
        with pytest.raises(ValueError, match="in-core"):
            LinearRegression(solver="normal").fit(sds)
        auto = LinearRegression(maxIter=25, solver="auto").fit(sds)
        assert auto.summary.streamed
    finally:
        sds.close()


def test_streamed_gradient_descent_matches_incore(ctx):
    """Partial-sweep SGD accumulation: the streamed optimizer folds every
    shard's psummed partial into one accumulator-tier gradient per step —
    the same update math as the in-core full-batch GradientDescent."""
    from cycloneml_tpu.ml.optim import aggregators
    from cycloneml_tpu.ml.optim.gradient_descent import (GradientDescent,
                                                         SquaredL2Updater)
    from cycloneml_tpu.oocore import StreamingGradientDescent
    x, y = _binary_problem(n=1500, d=6, seed=13)
    sds = _streaming_ds(ctx, x, y, shard_rows=400)
    try:
        agg = aggregators.binary_logistic(6, fit_intercept=False)
        kw = dict(step_size=1.0, num_iterations=25, reg_param=0.01,
                  updater=SquaredL2Updater(), seed=3)
        w_s, hist_s = StreamingGradientDescent(**kw).optimize(
            sds, agg, np.zeros(6))
        w_r, hist_r = GradientDescent(**kw).optimize(
            InstanceDataset.from_numpy(ctx, x, y), agg, np.zeros(6))
        assert len(hist_s) == len(hist_r)
        np.testing.assert_allclose(w_s, w_r, rtol=1e-9, atol=1e-12)
        np.testing.assert_allclose(hist_s, hist_r, rtol=1e-9)
    finally:
        sds.close()


def test_over_budget_fit_degrades_to_streaming(ctx):
    """The acceptance pin: an in-core fit whose chunk program exceeds the
    memory budget at deviceChunk=1 DEGRADES to the streaming engine and
    completes — even under budgetAction=raise — matching the unbudgeted
    coefficients; cyclone.oocore.mode=off restores the raise."""
    from cycloneml_tpu.ml.classification import LogisticRegression
    from cycloneml_tpu.observe.costs import MemoryBudgetError
    x, y = _binary_problem(n=1200, d=6, seed=14)
    ds = InstanceDataset.from_numpy(ctx, x, y)
    est = lambda: LogisticRegression(maxIter=12, regParam=0.1)  # noqa: E731
    ref = est().fit(ds)
    assert not ref.summary.streamed
    warnings_before = len(ctx.status_store.memory_warnings)
    ctx.conf.set("cyclone.memory.budgetFraction", "1e-12")
    ctx.conf.set("cyclone.memory.budgetAction", "raise")
    try:
        m = est().fit(ds)
        assert m.summary.streamed  # degraded, not OOM'd, not raised
        np.testing.assert_allclose(np.asarray(m._coef),
                                   np.asarray(ref._coef),
                                   rtol=1e-9, atol=1e-12)
        assert ctx.listener_bus.wait_until_empty()
        warns = ctx.status_store.memory_warnings[warnings_before:]
        assert warns  # the exceeded-budget events still posted
        ctx.conf.set("cyclone.oocore.mode", "off")
        with pytest.raises(MemoryBudgetError):
            est().fit(ds)
    finally:
        ctx.conf.remove("cyclone.memory.budgetFraction")
        ctx.conf.remove("cyclone.memory.budgetAction")
        ctx.conf.remove("cyclone.oocore.mode")


def test_oocore_mode_force_streams_eligible_fits(ctx):
    from cycloneml_tpu.ml.classification import LogisticRegression
    x, y = _binary_problem(n=1000, d=5, seed=15)
    ds = InstanceDataset.from_numpy(ctx, x, y)
    ref = LogisticRegression(maxIter=10, regParam=0.1).fit(ds)
    ctx.conf.set("cyclone.oocore.mode", "force")
    try:
        m = LogisticRegression(maxIter=10, regParam=0.1).fit(ds)
        assert m.summary.streamed
        np.testing.assert_allclose(np.asarray(m._coef),
                                   np.asarray(ref._coef),
                                   rtol=1e-9, atol=1e-12)
    finally:
        ctx.conf.remove("cyclone.oocore.mode")


def test_streamed_sweep_cost_is_o_shard(ctx):
    """costs.streamed_sweep_cost: whole-epoch WORK scales with the shard
    count while the per-dispatch MEMORY footprint stays O(shard) — the
    reason the streamed fit cannot OOM."""
    from cycloneml_tpu.ml.optim import aggregators
    from cycloneml_tpu.oocore import StreamingLossFunction
    x, y = _binary_problem(n=2000, d=8, seed=16)
    sds = _streaming_ds(ctx, x, y, shard_rows=500)
    try:
        f = StreamingLossFunction(
            sds, aggregators.binary_logistic(8, fit_intercept=False))
        cost = f.sweep_cost(n_coef=8)
        assert cost.cost_available and cost.memory_available
        per_shard_x_bytes = sds.pad_rows * 8 * np.dtype(np.float64).itemsize
        # epoch bytes cover all shards' X at least once...
        assert cost.bytes_accessed_total >= sds.n_shards * per_shard_x_bytes
        # ...but peak HBM is one padded shard's program, not the epoch
        assert cost.peak_bytes < 3 * per_shard_x_bytes
    finally:
        sds.close()


def test_stream_spans_show_stage_and_compute(ctx):
    """Stream-phase observability: a traced streamed fit records
    ``oocore.stage`` transfer spans (staging thread, bytes annotated),
    ``oocore.shard`` dispatch spans (consumer thread) and the cumulative
    ``oocore.bytes_staged`` counter track — the spans the bench's overlap
    measurement reads."""
    from cycloneml_tpu.ml.classification import LogisticRegression
    from cycloneml_tpu.observe import tracing
    x, y = _binary_problem(n=1200, d=6, seed=17)
    sds = _streaming_ds(ctx, x, y, shard_rows=400)
    tr = tracing.enable()
    mark = tr.mark()
    try:
        LogisticRegression(maxIter=4, regParam=0.1).fit(sds)
        spans = tr.snapshot(since=mark)
        stage = [s for s in spans if s.name == "oocore.stage"]
        shard = [s for s in spans if s.name == "oocore.shard"]
        counters = [s for s in spans if s.name == "oocore.bytes_staged"]
        assert stage and shard and counters
        assert all(s.kind == "transfer" for s in stage)
        assert all(s.attrs.get("bytes", 0) > 0 for s in stage)
        # staging runs on its own thread — the overlap is observable
        assert {s.tid for s in stage} != {s.tid for s in shard}
        per_epoch = sds.n_shards
        assert len(shard) % per_epoch == 0
    finally:
        tracing.disable()
        sds.close()


_STREAM_RSS_SCRIPT = textwrap.dedent("""
    import os, resource, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    from cycloneml_tpu.conf import CycloneConf
    from cycloneml_tpu.context import CycloneContext
    from cycloneml_tpu.ml.classification import LogisticRegression
    from cycloneml_tpu.oocore import StreamingDataset

    n, d, shard_rows = (int(a) for a in sys.argv[1:4])
    ctx = CycloneContext(CycloneConf().set("cyclone.master", "local-mesh[8]"))
    rng = np.random.RandomState(4)
    beta = rng.randn(d)

    def chunks():
        done = 0
        while done < n:
            m = min(32768, n - done)
            xc = rng.randn(m, d).astype(np.float32)
            yc = (xc @ beta > 0).astype(np.float64)
            yield xc, yc, None
            done += m

    sds = StreamingDataset.from_chunks(ctx, chunks(), d,
                                       shard_rows=shard_rows)
    model = LogisticRegression(maxIter=3, regParam=0.1).fit(sds)
    assert model.summary.streamed
    assert sds.n_rows == n
    sds.close()
    print("PEAK_RSS_KB", resource.getrusage(resource.RUSAGE_SELF).ru_maxrss)
""")


def test_streamed_fit_rss_is_shard_bounded(tmp_path):
    """A FULL streamed fit in a fresh subprocess: generate → shard → fit
    without the matrix ever materializing. Peak RSS over an identical
    tiny-problem baseline must stay well under the dataset's own f32
    bytes — the fit's host working set is O(shard), the shards live on
    disk, and on the CPU test platform 'device' memory IS process RAM, so
    this bounds the device residency too (depth+1 padded shards)."""
    n, d, shard_rows = 320_000, 64, 32768
    ds_bytes = n * d * 4
    env = dict(os.environ)

    def run(n_):
        out = subprocess.run(
            [sys.executable, "-c", _STREAM_RSS_SCRIPT, str(n_), str(d),
             str(shard_rows)],
            capture_output=True, text=True, env=env, timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        return int(out.stdout.split("PEAK_RSS_KB")[1])

    base_kb = run(4096)
    peak_kb = run(n)
    extra = (peak_kb - base_kb) * 1024
    assert extra < 0.5 * ds_bytes, (base_kb, peak_kb, ds_bytes)


def test_chunked_dataset_trains_tree_mlp_svc(ctx):
    """Estimators that read labels/features back to host must honor the
    interleaved padding mask (review r3: trees/MLP/SVC sliced [:n_rows])."""
    from cycloneml_tpu.ml.classification import (
        DecisionTreeClassifier, LinearSVC, MultilayerPerceptronClassifier)
    rng = np.random.RandomState(6)
    x = rng.randn(900, 6)
    y = (x[:, 0] + 0.5 * x[:, 1] > 0).astype(float)

    def chunks():
        for lo in range(0, 900, 200):
            yield x[lo:lo + 200], y[lo:lo + 200], None

    ds = InstanceDataset.from_dense_chunks(ctx, chunks(), 6)
    ref = InstanceDataset.from_numpy(ctx, x, y)
    assert ds._valid_mask is not None and not ds._valid_mask.all()
    for est in (DecisionTreeClassifier(maxDepth=4, seed=3),
                MultilayerPerceptronClassifier(layers=[6, 8, 2], maxIter=40, seed=3),
                LinearSVC(maxIter=20, regParam=0.01)):
        m_chunked = est.fit(ds)
        m_ref = est.fit(ref)
        px = np.asarray(m_chunked.transform(
            MLFrame(ctx, {"features": x, "label": y}))["prediction"])
        acc = float((px == y).mean())
        assert acc > 0.85, (type(est).__name__, acc)
        pr = np.asarray(m_ref.transform(
            MLFrame(ctx, {"features": x, "label": y}))["prediction"])
        # chunked row order is a permutation; models need not be identical,
        # but both must learn the same signal
        assert float((pr == y).mean()) > 0.85


def test_shuffled_sgd_matches_fixed_order(ctx):
    """Epoch shard shuffling (ROADMAP 1a): the streamed SGD walks a
    SEEDED permutation of the shard order per epoch. Because the step's
    gradient is the whole-epoch accumulation and the Bernoulli mask keys
    on the TRUE shard index, a shuffled run agrees with the fixed-order
    run at matched seeds up to float summation order — and a shuffled
    re-run at the same seed is bitwise-identical."""
    from cycloneml_tpu.ml.optim import aggregators
    from cycloneml_tpu.ml.optim.gradient_descent import SquaredL2Updater
    from cycloneml_tpu.oocore import StreamingGradientDescent
    x, y = _binary_problem(n=1600, d=6, seed=21)
    sds = _streaming_ds(ctx, x, y, shard_rows=300)
    try:
        agg = aggregators.binary_logistic(6, fit_intercept=False)
        kw = dict(step_size=1.0, num_iterations=12, reg_param=0.01,
                  updater=SquaredL2Updater(), seed=5,
                  mini_batch_fraction=0.6)
        w_fix, hist_fix = StreamingGradientDescent(
            shuffle=False, **kw).optimize(sds, agg, np.zeros(6))
        w_shuf, hist_shuf = StreamingGradientDescent(
            shuffle=True, **kw).optimize(sds, agg, np.zeros(6))
        w_shuf2, _ = StreamingGradientDescent(
            shuffle=True, **kw).optimize(sds, agg, np.zeros(6))
        # parity vs the fixed order at matched seeds (same masks, same
        # per-shard partials — only the fold order differs)
        np.testing.assert_allclose(w_shuf, w_fix, rtol=1e-9, atol=1e-12)
        np.testing.assert_allclose(hist_shuf, hist_fix, rtol=1e-9)
        # seeded determinism: same seed, same permutations, same bits
        np.testing.assert_array_equal(w_shuf, w_shuf2)
    finally:
        sds.close()


def test_shuffle_conf_key_and_order_validation(ctx):
    """cyclone.oocore.shuffle routes the engine default; a bogus order
    passed to the stream is rejected loudly."""
    from cycloneml_tpu.conf import OOCORE_SHUFFLE
    from cycloneml_tpu.oocore import StreamingGradientDescent
    from cycloneml_tpu.oocore.stream import ShardStream
    assert ctx.conf.get(OOCORE_SHUFFLE) is False
    ctx.conf.set("cyclone.oocore.shuffle", "true")
    try:
        assert ctx.conf.get(OOCORE_SHUFFLE) is True
        assert StreamingGradientDescent().shuffle is None  # conf-resolved
    finally:
        ctx.conf.set("cyclone.oocore.shuffle", "false")
    x, y = _binary_problem(n=600, d=4, seed=22)
    sds = _streaming_ds(ctx, x, y, shard_rows=300)
    try:
        with pytest.raises(ValueError, match="permutation"):
            ShardStream(sds, order=[0, 0, 1]).close()
    finally:
        sds.close()


def test_streaming_dataset_close_race_single_unlink(ctx, monkeypatch):
    """Explicit close races ``__del__`` (GC runs finalizers on another
    thread's allocation path): the ``_closed`` latch is taken under a
    lock, so concurrent closers unlink each spill file EXACTLY once —
    never a double-unlink that could tear down a path a new dataset just
    reused. Pinned from a graftlint JX022 check-then-act self-run
    finding."""
    import threading
    from collections import Counter

    x, y = _binary_problem(n=600, d=4)
    sds = _streaming_ds(ctx, x, y, shard_rows=200)
    paths = [s.path for s in sds._shards]
    assert paths and all(os.path.exists(p) for p in paths)

    counts: Counter = Counter()
    count_lock = threading.Lock()
    real_unlink = os.unlink

    def counted(p, *a, **k):
        with count_lock:
            counts[p] += 1
        return real_unlink(p, *a, **k)

    monkeypatch.setattr(os, "unlink", counted)
    barrier = threading.Barrier(4)

    def closer():
        barrier.wait()
        sds.close()

    threads = [threading.Thread(target=closer) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert {counts[p] for p in paths} == {1}
    assert not any(os.path.exists(p) for p in paths)
    sds.close()   # idempotent after the race: the latch stays down
    assert {counts[p] for p in paths} == {1}


# -- fp8 shard stream + stacked streamed epochs + shard-set cache (ISSUE 19) --


def test_fp8_shard_stream_matches_incore_fp8(ctx):
    """Tentpole leg (a): under ``streamDtype=float8`` the spill stores
    e4m3 codes + ONE set-level per-column dequant scale — the identical
    codes and scale an in-core fp8 quantization of the same rows
    produces — so the streamed fit lands ulp-close to the in-core fp8
    fit (the only difference is summation order), and the staged X bytes
    drop to 1 per element."""
    import ml_dtypes
    from cycloneml_tpu.dataset.instance import data_dtype
    from cycloneml_tpu.ml.classification import LogisticRegression
    x, y = _binary_problem(n=1500, d=8, seed=31)
    ctx.conf.set("cyclone.oocore.streamDtype", "float8")
    ctx.conf.set("cyclone.data.dtype", "float8")
    try:
        sds = _streaming_ds(ctx, x, y)
        try:
            assert sds.x_dtype == np.dtype(ml_dtypes.float8_e4m3fn)
            assert sds.x_scale is not None and sds.x_scale.shape == (8,)
            est = lambda: LogisticRegression(maxIter=30,  # noqa: E731
                                             regParam=0.01, tol=1e-10)
            m_st = est().fit(sds)
            assert m_st.summary.streamed
            ds8 = InstanceDataset.from_numpy(
                ctx, x, y, dtype=data_dtype(ctx.conf, fp8_capable=True))
            # the finalize pass and the in-core quantizer agree bitwise
            # on the set-level scale
            np.testing.assert_array_equal(sds.x_scale,
                                          np.asarray(ds8.x_scale))
            m_in = est().fit(ds8)
            np.testing.assert_allclose(np.asarray(m_st._coef),
                                       np.asarray(m_in._coef),
                                       rtol=1e-9, atol=1e-12)
            np.testing.assert_allclose(np.asarray(m_st._icpt),
                                       np.asarray(m_in._icpt),
                                       rtol=1e-9, atol=1e-12)
            # the staged stream really is 1-byte codes
            x0, _, _ = sds.load_shard(0)
            assert x0.dtype == np.dtype(ml_dtypes.float8_e4m3fn)
            assert x0.itemsize == 1
        finally:
            sds.close()
    finally:
        ctx.conf.remove("cyclone.oocore.streamDtype")
        ctx.conf.set("cyclone.data.dtype", "auto")


def test_fp8_stream_probe_refusal_stays_wide_and_visible(ctx):
    """The fp8 stream's safety rail: an ill-conditioned column (absmax
    >> std) makes the materialization-time envelope probe refuse the fp8
    rung for the shard SET — the spill stays at the write rung, the fit
    completes, and the decision surfaces as a PrecisionFallback event
    (automatic and visible, never silent)."""
    from cycloneml_tpu.ml.classification import LogisticRegression
    from cycloneml_tpu.util.events import PrecisionFallback
    x, y = _binary_problem(n=900, d=6, seed=32)
    x[:, 2] = 1000.0 + 0.01 * np.random.RandomState(1).randn(900)
    events = []
    ctx.listener_bus.add_listener(events.append)
    ctx.conf.set("cyclone.oocore.streamDtype", "float8")
    try:
        sds = _streaming_ds(ctx, x, y)
        try:
            ctx.listener_bus.wait_until_empty()
            assert sds.x_scale is None  # the requantize was refused
            assert sds.x_dtype.itemsize > 1
            falls = [e for e in events if isinstance(e, PrecisionFallback)]
            assert len(falls) == 1
            assert falls[0].from_dtype == "float8_e4m3fn"
            assert "absmax/std" in falls[0].reason
            m = LogisticRegression(maxIter=8, regParam=0.1).fit(sds)
            assert m.summary.streamed
            assert np.all(np.isfinite(np.asarray(m._coef)))
        finally:
            sds.close()
    finally:
        ctx.conf.remove("cyclone.oocore.streamDtype")
        ctx.listener_bus.remove_listener(events.append)


def test_streamed_stacked_fit_matches_serial_streamed(ctx):
    """Tentpole leg (b): ``fit_stacked`` over a StreamingDataset drives K
    models through ONE double-buffered epoch per optimizer round (vmap
    over the per-shard partials, per-model convergence masks on the host
    fold). Coefficient parity with K serial streamed fits at matched
    regs is 1e-9, and the stacked run's epoch count is the MAX of the
    serial counts, not their sum."""
    from cycloneml_tpu.ml.classification import LogisticRegression
    x, y = _binary_problem(n=2000, d=8, seed=33)
    sds = _streaming_ds(ctx, x, y)
    regs = [0.0, 0.01, 0.1, 1.0]
    try:
        models = LogisticRegression(maxIter=40, tol=1e-9).fit_stacked(
            sds, reg_params=regs)
        assert len(models) == len(regs)
        serial_evals = []
        for kk, r in enumerate(regs):
            m_ref = LogisticRegression(maxIter=40, tol=1e-9,
                                       regParam=r).fit(sds)
            np.testing.assert_allclose(np.asarray(models[kk]._coef),
                                       np.asarray(m_ref._coef),
                                       rtol=1e-9, atol=1e-12)
            np.testing.assert_allclose(np.asarray(models[kk]._icpt),
                                       np.asarray(m_ref._icpt),
                                       rtol=1e-9, atol=1e-12)
            serial_evals.append(m_ref.summary.total_evals)
        s = models[0].summary
        assert s.streamed and s.n_models == len(regs)
        # ONE streamed epoch serves all K models per round
        assert s.total_evals <= max(serial_evals)
        assert s.total_evals < sum(serial_evals)
    finally:
        sds.close()


def test_streamed_ovr_fit_stages_no_label_stack(ctx):
    """``fit_stacked(sds, num_classes=K)`` — OneVsRest's relabelling over a
    shard set: model j's label is made inside the per-shard program from
    the shard's own label vector, so nothing of shape ``(rows, K)`` is ever
    staged; each model matches its serial streamed fit on ``1[y == j]`` to
    1e-9, and ``OneVsRest`` under ``cyclone.oocore.mode=force`` takes this
    leg and lands on the in-core stacked fit's models."""
    from cycloneml_tpu.dataset.frame import MLFrame
    from cycloneml_tpu.ml.classification import LogisticRegression, OneVsRest
    rng = np.random.RandomState(44)
    k, n, d = 3, 2000, 8
    centers = rng.randn(k, d) * 1.5
    y = rng.randint(0, k, n).astype(float)
    x = centers[y.astype(int)] + rng.randn(n, d)
    lr = LogisticRegression(maxIter=40, tol=1e-9, regParam=0.05)
    sds = _streaming_ds(ctx, x, y)
    rt = ctx.mesh_runtime
    staged, real_put = [], rt.device_put_sharded_rows
    rt.device_put_sharded_rows = \
        lambda a, *p, **kw: staged.append(np.shape(a)) or real_put(a, *p, **kw)
    try:
        models = lr.fit_stacked(sds, num_classes=k)
    finally:
        rt.device_put_sharded_rows = real_put
    try:
        assert staged and all(len(shape) == 1 or shape[1] == d
                              for shape in staged), set(staged)
        serial_evals = []
        for j in range(k):
            one = _streaming_ds(ctx, x, (y == j).astype(float))
            try:
                ref = lr.fit(one)
            finally:
                one.close()
            np.testing.assert_allclose(models[j]._coef, ref._coef,
                                       rtol=1e-9, atol=1e-12)
            np.testing.assert_allclose(models[j]._icpt, ref._icpt,
                                       rtol=1e-9, atol=1e-12)
            serial_evals.append(ref.summary.total_evals)
        s = models[0].summary
        assert s.streamed and s.n_models == k
        assert s.stacked_evals <= max(serial_evals) < sum(serial_evals)
        with pytest.raises(ValueError, match="class-index labels below 2"):
            lr.fit_stacked(sds, num_classes=2)
    finally:
        sds.close()

    frame = MLFrame(ctx, {"features": x, "label": y})
    ovr = OneVsRest(classifier=lr, parallelism=k)
    incore = ovr.fit(frame)
    ctx.conf.set("cyclone.oocore.mode", "force")
    try:
        forced = ovr.fit(MLFrame(ctx, {"features": x, "label": y}))
    finally:
        ctx.conf.remove("cyclone.oocore.mode")
    assert all(m.summary.streamed for m in forced.models)
    assert forced.summary.total_evals == forced.models[0].summary.stacked_evals
    for a, b in zip(forced.models, incore.models):
        np.testing.assert_allclose(a._coef, b._coef, rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(a._icpt, b._icpt, rtol=1e-5, atol=1e-7)


def test_streamed_stacked_sgd_matches_serial(ctx):
    """``optimize_stacked`` is the model-axis twin of the streamed SGD:
    per-model labels via ``y_stack`` (OvR relabelings), a shared
    mini-batch mask keyed on the true shard index, and per-model
    convergence — each model's trajectory matches its serial streamed
    run at matched seeds."""
    from cycloneml_tpu.ml.optim import aggregators
    from cycloneml_tpu.ml.optim.gradient_descent import SquaredL2Updater
    from cycloneml_tpu.oocore import StreamingGradientDescent
    x, y = _binary_problem(n=1200, d=6, seed=34)
    sds = _streaming_ds(ctx, x, y, shard_rows=400)
    sds_flip = _streaming_ds(ctx, x, 1.0 - y, shard_rows=400)
    try:
        agg = aggregators.binary_logistic(6, fit_intercept=False)
        kw = dict(step_size=1.0, num_iterations=15, reg_param=0.01,
                  updater=SquaredL2Updater(), seed=7,
                  mini_batch_fraction=0.6)
        y_stack = np.stack([y, 1.0 - y])
        W, hists = StreamingGradientDescent(**kw).optimize_stacked(
            sds, agg, np.zeros((2, 6)), y_stack=y_stack)
        w0, h0 = StreamingGradientDescent(**kw).optimize(
            sds, agg, np.zeros(6))
        w1, h1 = StreamingGradientDescent(**kw).optimize(
            sds_flip, agg, np.zeros(6))
        np.testing.assert_allclose(W[0], w0, rtol=1e-9, atol=1e-12)
        np.testing.assert_allclose(W[1], w1, rtol=1e-9, atol=1e-12)
        np.testing.assert_allclose(hists[0], h0, rtol=1e-9)
        np.testing.assert_allclose(hists[1], h1, rtol=1e-9)
    finally:
        sds.close()
        sds_flip.close()


def test_shard_set_cache_attach_hit_zero_respill(ctx):
    """Tentpole leg (c): the second attach over the same dataset is a
    HIT — a shared view onto the existing spill files, ZERO spill-write
    bytes — and closing one handle releases its refcount without tearing
    the cached files down from under the other."""
    from cycloneml_tpu.ml.classification import LogisticRegression
    from cycloneml_tpu.oocore import shard_dataset, shard_set_cache
    cache = shard_set_cache()
    cache.clear()
    x, y = _binary_problem(n=900, d=5, seed=35)
    ds = InstanceDataset.from_numpy(ctx, x, y)
    st0 = cache.stats()
    s1 = shard_dataset(ds, shard_rows=300)
    st1 = cache.stats()
    assert st1["misses"] == st0["misses"] + 1
    wrote = st1["spillWriteBytes"] - st0["spillWriteBytes"]
    assert wrote > 0
    try:
        s2 = shard_dataset(ds, shard_rows=300)
        st2 = cache.stats()
        assert st2["hits"] == st1["hits"] + 1
        assert st2["spillWriteBytes"] == st1["spillWriteBytes"]  # 0 re-spill
        assert [a.path for a in s2._shards] == [a.path for a in s1._shards]
        m = LogisticRegression(maxIter=6, regParam=0.1).fit(s2)
        assert m.summary.streamed
        s2.close()
        # s1 still holds a ref: the files survive s2's close
        assert all(os.path.exists(a.path) for a in s1._shards)
        m2 = LogisticRegression(maxIter=6, regParam=0.1).fit(s1)
        np.testing.assert_array_equal(np.asarray(m2._coef),
                                      np.asarray(m._coef))
    finally:
        s1.close()
        cache.clear()


def test_shard_set_cache_keying_negatives(ctx):
    """The content key covers everything that changes the spilled bytes:
    different data, different shard geometry, and a different stream
    tier each MISS — attaching never serves a spill built for other
    bytes."""
    from cycloneml_tpu.oocore import shard_dataset, shard_set_cache
    cache = shard_set_cache()
    cache.clear()
    x, y = _binary_problem(n=800, d=5, seed=36)
    x2 = x.copy()
    x2[0, 0] += 1.0
    ds = InstanceDataset.from_numpy(ctx, x, y)
    ds2 = InstanceDataset.from_numpy(ctx, x2, y)
    st0 = cache.stats()
    handles = [shard_dataset(ds, shard_rows=300)]
    try:
        handles.append(shard_dataset(ds, shard_rows=128))  # geometry
        handles.append(shard_dataset(ds2, shard_rows=300))  # content
        ctx.conf.set("cyclone.oocore.streamDtype", "float8")
        try:
            handles.append(shard_dataset(ds, shard_rows=300))  # tier
        finally:
            ctx.conf.remove("cyclone.oocore.streamDtype")
        st = cache.stats()
        assert st["hits"] == st0["hits"]
        assert st["misses"] == st0["misses"] + 4
    finally:
        for h in handles:
            h.close()
        cache.clear()


def test_shard_set_cache_eviction_pins_live_streams(ctx):
    """The byte bound LRU-evicts — but NEVER an entry with a live handle:
    under a bound that fits one entry, the pinned set survives two
    further builds (the released one is the victim) and still serves a
    fit afterwards."""
    from cycloneml_tpu.ml.classification import LogisticRegression
    from cycloneml_tpu.oocore import shard_dataset, shard_set_cache
    cache = shard_set_cache()
    cache.clear()
    probs = [_binary_problem(n=900, d=6, seed=s) for s in (37, 38, 39)]
    dss = [InstanceDataset.from_numpy(ctx, x, y) for x, y in probs]
    st0 = cache.stats()
    live = shard_dataset(dss[0], shard_rows=300)
    nb = cache.stats()["bytes"]
    assert nb > 0
    ctx.conf.set("cyclone.oocore.cacheBytes", str(nb))  # one entry fits
    try:
        other = shard_dataset(dss[1], shard_rows=300)
        other_paths = [s.path for s in other._shards]
        other.close()   # refs 0 → evictable; live stays pinned
        third = shard_dataset(dss[2], shard_rows=300)
        third.close()
        st = cache.stats()
        assert st["evictionsLru"] >= st0["evictionsLru"] + 1
        # the released entry's files are gone, the pinned one's remain
        assert not any(os.path.exists(p) for p in other_paths)
        assert all(os.path.exists(s.path) for s in live._shards)
        m = LogisticRegression(maxIter=5, regParam=0.1).fit(live)
        assert m.summary.streamed
    finally:
        ctx.conf.remove("cyclone.oocore.cacheBytes")
        live.close()
        cache.clear()


def test_shard_set_cache_bypass_modes(ctx):
    """cacheBytes=0 and an explicit spill_dir both restore the pre-cache
    contract: a direct build that OWNS its files (closed → unlinked)."""
    from cycloneml_tpu.oocore import shard_dataset, shard_set_cache
    cache = shard_set_cache()
    cache.clear()
    x, y = _binary_problem(n=600, d=4, seed=40)
    ds = InstanceDataset.from_numpy(ctx, x, y)
    ctx.conf.set("cyclone.oocore.cacheBytes", "0")
    try:
        st0 = cache.stats()
        sds = shard_dataset(ds, shard_rows=200)
        assert cache.stats() == st0    # the cache never saw it
        paths = [s.path for s in sds._shards]
        sds.close()
        assert not any(os.path.exists(p) for p in paths)  # owned + removed
    finally:
        ctx.conf.remove("cyclone.oocore.cacheBytes")
        cache.clear()


def test_fp8_stream_attribution_bytes_and_cache_hits(ctx):
    """Usage attribution across the new planes: staged h2dBytes bill at
    the staged arrays' ACTUAL itemsize — an fp8 epoch's X stream bills 1
    byte/element where the bf16 rung bills 2 — and shard-set cache hits
    land on the calling scope's ``cacheHits`` ledger field."""
    import jax.numpy as jnp
    from cycloneml_tpu.ml.optim import aggregators
    from cycloneml_tpu.observe import attribution
    from cycloneml_tpu.oocore import (StreamingDataset, StreamingLossFunction,
                                      shard_dataset, shard_set_cache)
    d = 64
    x, y = _binary_problem(n=1600, d=d, seed=41)

    def chunks():
        for lo in range(0, len(x), 400):
            yield x[lo:lo + 400], y[lo:lo + 400], None

    attribution.disable()
    led = attribution.enable()
    cache = shard_set_cache()
    cache.clear()
    try:
        staged = {}
        for tier in ("bfloat16", "float8"):
            sds = StreamingDataset.from_chunks(ctx, chunks(), d,
                                               shard_rows=400,
                                               stream_dtype=tier)
            try:
                agg = aggregators.binary_logistic(d, fit_intercept=False)
                f = StreamingLossFunction(sds, agg)
                with attribution.scope(f"epoch-{tier}"):
                    f.sweep(jnp.zeros(d, jnp.float32))
                staged[tier] = led.row(f"epoch-{tier}")["h2dBytes"]
                geom = (sds.n_shards, sds.pad_rows)
            finally:
                sds.close()
        assert staged["float8"] > 0
        assert staged["float8"] < staged["bfloat16"]
        # exact byte math: X bytes halve (1 vs 2 per element) while y/w
        # ride the accumulator tier in both, so the delta is EXACTLY one
        # epoch of X at one byte per element over the padded geometry —
        # the ledger bills the staged arrays' actual itemsize, not an
        # assumed bf16 width
        n_shards, pad_rows = geom
        assert staged["bfloat16"] - staged["float8"] \
            == n_shards * pad_rows * d
        ds = InstanceDataset.from_numpy(ctx, *_binary_problem(
            n=600, d=4, seed=42))
        with attribution.scope("cache-job"):
            a = shard_dataset(ds, shard_rows=200)
            b = shard_dataset(ds, shard_rows=200)
        assert led.row("cache-job")["cacheHits"] == 1
        a.close()
        b.close()
    finally:
        cache.clear()
        attribution.disable()
