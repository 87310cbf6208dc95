"""ALS tests (BASELINE config 4 family): explicit low-rank recovery, implicit
ranking, ALS-WR regularization behavior, NNLS mode, cold start, persistence."""

import numpy as np
import pytest

from cycloneml_tpu.dataset.frame import MLFrame
from cycloneml_tpu.ml.recommendation import ALS, ALSModel


def _ratings(seed=51, n_users=40, n_items=30, rank=3, frac=0.5):
    rng = np.random.RandomState(seed)
    u = rng.randn(n_users, rank)
    v = rng.randn(n_items, rank)
    full = u @ v.T
    mask = rng.rand(n_users, n_items) < frac
    users, items = np.nonzero(mask)
    return users, items, full[users, items], full, mask


def test_explicit_recovers_low_rank(ctx):
    users, items, r, full, mask = _ratings()
    frame = MLFrame(ctx, {"user": users, "item": items, "rating": r})
    model = ALS(rank=3, maxIter=15, regParam=0.01, seed=1).fit(frame)
    out = model.transform(frame)
    rmse = float(np.sqrt(np.mean((out["prediction"] - r) ** 2)))
    assert rmse < 0.05
    # held-out entries also predicted well (low-rank generalization)
    hu, hi = np.nonzero(~mask)
    hold = MLFrame(ctx, {"user": hu, "item": hi, "rating": full[hu, hi]})
    out_h = model.transform(hold)
    rmse_h = float(np.sqrt(np.nanmean((out_h["prediction"] - full[hu, hi]) ** 2)))
    assert rmse_h < 0.5


def test_regularization_shrinks_factors(ctx):
    users, items, r, _, _ = _ratings(seed=52)
    frame = MLFrame(ctx, {"user": users, "item": items, "rating": r})
    small = ALS(rank=3, maxIter=10, regParam=0.01, seed=2).fit(frame)
    big = ALS(rank=3, maxIter=10, regParam=10.0, seed=2).fit(frame)
    assert np.linalg.norm(big.user_factors) < np.linalg.norm(small.user_factors)


def test_implicit_ranks_observed_higher(ctx):
    rng = np.random.RandomState(53)
    n_users, n_items = 30, 25
    # block structure: users < 15 like items < 12
    users, items, counts = [], [], []
    for u in range(n_users):
        liked = range(0, 12) if u < 15 else range(12, 25)
        for i in liked:
            if rng.rand() < 0.6:
                users.append(u)
                items.append(i)
                counts.append(rng.randint(1, 5))
    frame = MLFrame(ctx, {"user": np.array(users), "item": np.array(items),
                          "rating": np.array(counts, dtype=float)})
    model = ALS(rank=4, maxIter=10, regParam=0.05, implicitPrefs=True,
                alpha=10.0, seed=3).fit(frame)
    scores = model.user_factors @ model.item_factors.T
    # group-0 users should prefer group-0 items on average
    assert scores[:15, :12].mean() > scores[:15, 12:].mean() + 0.1
    assert scores[15:, 12:].mean() > scores[15:, :12].mean() + 0.1


def test_nonnegative_factors(ctx):
    users, items, r, _, _ = _ratings(seed=54)
    r = np.abs(r) + 0.1
    frame = MLFrame(ctx, {"user": users, "item": items, "rating": r})
    model = ALS(rank=3, maxIter=8, regParam=0.1, nonnegative=True, seed=4).fit(frame)
    assert model.user_factors.min() >= 0.0
    assert model.item_factors.min() >= 0.0
    out = model.transform(frame)
    rmse = float(np.sqrt(np.mean((out["prediction"] - r) ** 2)))
    assert rmse < 1.0


def test_cold_start_nan_and_drop(ctx):
    users, items, r, _, _ = _ratings(seed=55)
    frame = MLFrame(ctx, {"user": users, "item": items, "rating": r})
    model = ALS(rank=3, maxIter=5, seed=5).fit(frame)
    probe = MLFrame(ctx, {"user": np.array([users[0], 9999]),
                          "item": np.array([items[0], 0]),
                          "rating": np.array([1.0, 1.0])})
    out = model.transform(probe)
    assert np.isfinite(out["prediction"][0])
    assert np.isnan(out["prediction"][1])
    model.set("coldStartStrategy", "drop")
    out2 = model.transform(probe)
    assert out2.n_rows == 1


def test_recommend_for_all_users(ctx):
    users, items, r, full, _ = _ratings(seed=56)
    frame = MLFrame(ctx, {"user": users, "item": items, "rating": r})
    model = ALS(rank=3, maxIter=10, regParam=0.01, seed=6).fit(frame)
    recs = model.recommend_for_all_users(5)
    assert recs.n_rows == 40 * 5
    # top recommendation for user 0 should be among its true top items
    u0 = recs.filter_rows(np.asarray(recs["user"]) == model.user_ids[0])
    top_true = set(np.argsort(-full[0])[:8])
    assert int(u0["item"][0]) in top_true


def test_save_load(ctx, tmp_path):
    users, items, r, _, _ = _ratings(seed=57)
    frame = MLFrame(ctx, {"user": users, "item": items, "rating": r})
    model = ALS(rank=3, maxIter=5, seed=7).fit(frame)
    p = str(tmp_path / "als")
    model.save(p)
    back = ALSModel.load(p)
    np.testing.assert_allclose(back.user_factors, model.user_factors)
    o1 = model.transform(frame)["prediction"]
    o2 = back.transform(frame)["prediction"]
    np.testing.assert_allclose(o1, o2)


def test_checkpoint_resume_matches_uninterrupted(ctx, tmp_path):
    """checkpointDir lets a killed fit resume mid-training and land on the
    uninterrupted run's factors (deterministic seeded solves)."""
    users, items, r, _, _ = _ratings(seed=3)
    frame = MLFrame(ctx, {"user": users, "item": items, "rating": r})
    full = ALS(rank=3, maxIter=6, seed=9).fit(frame)

    ck = str(tmp_path / "als-ck")
    ALS(rank=3, maxIter=2, seed=9, checkpointDir=ck,
        checkpointInterval=1).fit(frame)
    resumed = ALS(rank=3, maxIter=6, seed=9, checkpointDir=ck,
                  checkpointInterval=1).fit(frame)
    np.testing.assert_allclose(resumed.user_factors, full.user_factors,
                               rtol=1e-6, atol=1e-8)
    np.testing.assert_allclose(resumed.item_factors, full.item_factors,
                               rtol=1e-6, atol=1e-8)


def test_checkpoint_fingerprint_guards_foreign_resume(ctx, tmp_path):
    users, items, r, _, _ = _ratings(seed=3)
    frame = MLFrame(ctx, {"user": users, "item": items, "rating": r})
    ck = str(tmp_path / "ck")
    ALS(rank=3, maxIter=3, seed=9, checkpointDir=ck,
        checkpointInterval=1).fit(frame)
    # different rank on the same dir must refuse, not crash on shapes
    with pytest.raises(ValueError, match="DIFFERENT ALS run"):
        ALS(rank=4, maxIter=3, seed=9, checkpointDir=ck,
            checkpointInterval=1).fit(frame)
    # different ratings likewise
    frame2 = MLFrame(ctx, {"user": users, "item": items, "rating": r + 1.0})
    with pytest.raises(ValueError, match="DIFFERENT ALS run"):
        ALS(rank=3, maxIter=3, seed=9, checkpointDir=ck,
            checkpointInterval=1).fit(frame2)


def test_chunked_aggregation_matches_unchunked(ctx):
    """A tiny chunk budget (forcing many scan chunks) must produce the same
    factors as the single-chunk path — chunking is a memory layout, not a
    math change."""
    users, items, r, _, _ = _ratings(seed=5)
    frame = MLFrame(ctx, {"user": users, "item": items, "rating": r})
    big = ALS(rank=3, maxIter=5, seed=2).fit(frame)
    small = ALS(rank=3, maxIter=5, seed=2,
                aggregationChunkBytes=4096).fit(frame)
    np.testing.assert_allclose(small.user_factors, big.user_factors,
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(small.item_factors, big.item_factors,
                               rtol=1e-5, atol=1e-7)


def test_normal_eq_memory_proportional_to_entities(ctx):
    """MovieLens-25M shape (25M ratings, rank 64): compile the user-side
    normal-equation aggregation and assert XLA's planned temp memory is
    entities-proportional, NOT nnz-proportional (VERDICT r1 item 5).

    The un-chunked build materializes (nnz/shard, r, r) ≈ 48 GB per shard;
    the chunked scan needs the (n_users, r, r) accumulator (~2.7 GB) plus
    one chunk. Compile-only: no 25M-row run on the CPU mesh."""
    import jax
    from cycloneml_tpu.ml.recommendation.als import _normal_eq_local
    from cycloneml_tpu.parallel import collectives

    rt = ctx.mesh_runtime
    n_users, rank = 162_541, 64
    shards = rt.data_parallelism
    nnz = 25_000_000
    budget = 256 << 20
    shard0 = -(-nnz // shards)
    n_chunks = max(1, -(-shard0 * rank * rank * 4 // budget))
    chunk = -(-shard0 // n_chunks)
    chunk += (-chunk) % 8
    total = chunk * n_chunks * shards

    local = _normal_eq_local(n_users, rank, n_chunks, False, 1.0)
    prog = collectives.tree_aggregate(
        local, rt, np.zeros(0, np.int32), np.zeros(0, np.int32),
        np.zeros(0, np.float32), np.zeros(0, np.float32))

    S = jax.ShapeDtypeStruct
    row_sharding = rt.data_sharding(extra_axes=0)
    args = (S((total,), np.int32, sharding=row_sharding),
            S((total,), np.int32, sharding=row_sharding),
            S((total,), np.float32, sharding=row_sharding),
            S((total,), np.float32, sharding=row_sharding),
            S((n_users, rank), np.float32, sharding=rt.replicated()),
            S((rank, rank), np.float32, sharding=rt.replicated()))
    # the program cache hands back the _instrument_dispatch wrapper; the
    # raw jitted program (the thing with .lower) rides its __wrapped__
    compiled = prog.__wrapped__.lower(*args).compile()
    ma = compiled.memory_analysis()
    if ma is None or not hasattr(ma, "temp_size_in_bytes"):
        pytest.skip("memory_analysis unavailable on this backend")
    temp = int(ma.temp_size_in_bytes)
    entities_bytes = n_users * rank * rank * 4          # the accumulator
    nnz_bytes_per_shard = shard0 * rank * rank * 4      # the un-chunked blob
    assert temp < 4 * entities_bytes, (temp, entities_bytes)
    assert temp < nnz_bytes_per_shard / 3, (temp, nnz_bytes_per_shard)


@pytest.mark.parametrize("implicit", [False, True])
def test_blocked_matches_replicated(ctx, implicit):
    """Factor-sharded (blocked) ALS must match the replicated path: same
    init, same normal equations, different partitioning — the dst-sharded
    accumulator plus one src all-gather is algebraically identical to the
    replicated psum (ref ALS.scala:1605 block structure)."""
    users, items, r, _, _ = _ratings(seed=53)
    if implicit:
        r = np.abs(r)
    frame = MLFrame(ctx, {"user": users, "item": items, "rating": r})
    kw = dict(rank=3, maxIter=5, regParam=0.05, seed=4,
              implicitPrefs=implicit, alpha=0.5)
    rep = ALS(shardFactors="never", **kw).fit(frame)
    blk = ALS(shardFactors="always", **kw).fit(frame)
    np.testing.assert_allclose(blk.user_factors, rep.user_factors,
                               rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(blk.item_factors, rep.item_factors,
                               rtol=2e-3, atol=2e-4)


def test_blocked_nonnegative_and_checkpoint(ctx, tmp_path):
    users, items, r, _, _ = _ratings(seed=54)
    frame = MLFrame(ctx, {"user": users, "item": items, "rating": np.abs(r)})
    m = ALS(rank=3, maxIter=4, regParam=0.05, seed=5, nonnegative=True,
            shardFactors="always").fit(frame)
    assert (m.user_factors >= 0).all() and (m.item_factors >= 0).all()
    # checkpointed blocked run resumes to the same factors
    ckdir = str(tmp_path / "ck")
    kw = dict(rank=3, maxIter=6, regParam=0.05, seed=6,
              shardFactors="always")
    full = ALS(**kw).fit(frame)
    ALS(maxIter=4, checkpointDir=ckdir, checkpointInterval=2,
        **{k: v for k, v in kw.items() if k != "maxIter"}
        ).set("maxIter", 4).fit(frame)
    resumed = ALS(checkpointDir=ckdir, checkpointInterval=2, **kw).fit(frame)
    np.testing.assert_allclose(resumed.user_factors, full.user_factors,
                               rtol=1e-4, atol=1e-5)


def test_auto_mode_switches_on_threshold(ctx):
    users, items, r, _, _ = _ratings(seed=55)
    frame = MLFrame(ctx, {"user": users, "item": items, "rating": r})
    # tiny threshold forces the blocked path through "auto"
    m = ALS(rank=3, maxIter=3, regParam=0.05, seed=7,
            factorShardingThresholdBytes=64).fit(frame)
    rep = ALS(rank=3, maxIter=3, regParam=0.05, seed=7,
              shardFactors="never").fit(frame)
    np.testing.assert_allclose(m.user_factors, rep.user_factors,
                               rtol=2e-3, atol=2e-4)


@pytest.mark.slow
def test_blocked_als_movielens_scale(ctx):
    """Scaled-down MovieLens-25M-shape run of the factor-sharded trainer:
    2M ratings over the full entity space at rank 16, one iteration, on the
    8-device mesh. The full-shape run (25M ratings x rank 64) has no
    record on the current machine."""
    n_users, n_items, nnz, rank = 162_541, 62_423, 2_000_000, 16
    rng = np.random.default_rng(1)
    users = rng.integers(0, n_users, nnz)
    items = rng.integers(0, n_items, nnz)
    r = rng.random(nnz) * 4 + 1
    frame = MLFrame(ctx, {"user": users, "item": items, "rating": r})
    m = ALS(rank=rank, maxIter=1, regParam=0.1, seed=2,
            shardFactors="always").fit(frame)
    assert m.user_factors.shape[0] == len(np.unique(users))
    assert np.isfinite(m.user_factors).all()
    assert np.isfinite(m.item_factors).all()
    # predictions on observed entries are finite and in a sane range
    pred = m.transform(frame.limit(10_000))["prediction"]
    assert np.isfinite(pred).all() and abs(float(np.mean(pred))) < 10
