"""PySpark-compat surface + binary summary metrics + profiler hook."""

import os

import numpy as np
import pytest

from cycloneml_tpu.dataset.frame import MLFrame
from cycloneml_tpu.ml.classification import LogisticRegression
from cycloneml_tpu.ml.classification.logistic_regression import (
    BinaryLogisticRegressionSummary)


def test_spark_session_builder(ctx):
    from cycloneml_tpu.compat import SparkSession, getActiveSession
    spark = (SparkSession.builder.master("local-mesh[8]")
             .appName("compat-app").config("cyclone.custom.flag", "1")
             .getOrCreate())
    assert spark.sparkContext is ctx  # reuses the active context
    df = spark.createDataFrame({"x": [1.0, 2.0, 3.0]})
    assert df.count() == 3
    assert spark.sql is not None
    active = getActiveSession()
    assert active is not None and active.sparkContext is ctx
    # fresh builder per access (no shared mutable conf)
    b1, b2 = SparkSession.builder, SparkSession.builder
    assert b1 is not b2
    # getOrCreate returns the SAME session: temp views carry across calls
    spark.register_temp_view("compat_t", df)
    again = SparkSession.builder.getOrCreate()
    assert again is spark
    assert again.table("compat_t").count() == 3
    assert getActiveSession() is spark


def test_compat_functions_and_window(ctx):
    # ``ctx``: getOrCreate reuses the active context; with none (this test
    # first on its xdist worker) it would build the default master, ``tpu``
    from cycloneml_tpu.compat import SparkSession, Window, col, functions as F
    spark = SparkSession.builder.getOrCreate()
    df = spark.createDataFrame({"k": ["a", "a", "b"], "v": [1.0, 2.0, 3.0]})
    out = df.withColumn(
        "rn", __import__("cycloneml_tpu.sql.window", fromlist=["row_number"])
        .row_number().over(Window.partition_by("k").order_by("v"))).to_dict()
    np.testing.assert_array_equal(out["rn"], [1, 2, 1])
    agg = df.groupBy("k").agg(F.sum("v").alias("s")).order_by("k").collect()
    assert [r.s for r in agg] == [3.0, 3.0]


def test_binary_summary_against_sklearn(ctx):
    from sklearn.metrics import roc_auc_score
    rng = np.random.RandomState(0)
    x = rng.randn(400, 6)
    y = (x @ rng.randn(6) + 0.3 * rng.randn(400) > 0).astype(float)
    frame = MLFrame(ctx, {"features": x, "label": y})
    model = LogisticRegression(maxIter=30).fit(frame)
    summary = model.evaluate(frame)
    probs = np.asarray(model.transform(frame)["probability"])[:, 1]
    want_auc = roc_auc_score(y, probs)
    assert summary.area_under_roc == pytest.approx(want_auc, abs=1e-9)
    roc = summary.roc
    assert roc[0].tolist() == [0.0, 0.0] and roc[-1].tolist() == [1.0, 1.0]
    assert np.all(np.diff(roc[:, 0]) >= 0)
    pr = summary.pr
    assert pr[0, 0] == 0.0 and pr[-1, 0] == 1.0
    f1 = summary.f_measure_by_threshold()
    best_t = f1[np.argmax(f1[:, 1]), 0]
    assert 0.0 < best_t < 1.0
    assert summary.accuracy > 0.8


def test_binary_summary_known_values():
    scores = np.array([0.9, 0.8, 0.3, 0.2])
    labels = np.array([1.0, 0.0, 1.0, 0.0])
    s = BinaryLogisticRegressionSummary(scores, labels)
    # perfect ordering would be auc=1; this ordering gives 0.75
    assert s.area_under_roc == pytest.approx(0.75)
    np.testing.assert_allclose(s.recall_by_threshold()[:, 1],
                               [0.5, 0.5, 1.0, 1.0])
    assert s.accuracy == pytest.approx(0.5)


def test_evaluate_respects_custom_label_col(ctx):
    rng = np.random.RandomState(4)
    x = rng.randn(150, 3)
    y = (x @ rng.randn(3) > 0).astype(float)
    frame = MLFrame(ctx, {"features": x, "target": y,
                          "label": np.zeros(150)})  # decoy column
    model = LogisticRegression(maxIter=10, labelCol="target").fit(frame)
    s = model.evaluate(frame)
    assert s.accuracy > 0.9  # scored against 'target', not the decoy


def test_summary_accuracy_respects_threshold(ctx):
    rng = np.random.RandomState(2)
    x = rng.randn(200, 4)
    y = (x @ rng.randn(4) > 0).astype(float)
    frame = MLFrame(ctx, {"features": x, "label": y})
    model = LogisticRegression(maxIter=20).fit(frame)
    model.set("threshold", 0.95)  # prediction col shifts; accuracy follows
    s = model.evaluate(frame)
    pred = np.asarray(model.transform(frame)["prediction"])
    assert s.accuracy == pytest.approx(float((pred == y).mean()))
    with pytest.raises(ValueError, match="empty"):
        BinaryLogisticRegressionSummary(np.array([]), np.array([]))


def test_count_over_ordered_string_window():
    from cycloneml_tpu.sql import functions as F
    from cycloneml_tpu.sql.session import CycloneSession
    from cycloneml_tpu.sql.window import Window
    s = CycloneSession()
    df = s.create_data_frame({"k": ["a", "a"], "name": ["x", "y"],
                              "t": [1.0, 2.0]})
    out = df.with_column(
        "c", F.count("name").over(Window.partition_by("k").order_by("t")))
    np.testing.assert_array_equal(out.to_dict()["c"], [1, 2])


def test_als_resume_with_smaller_max_iter_rejected(ctx, tmp_path):
    from cycloneml_tpu.ml.recommendation.als import ALS
    rng = np.random.RandomState(0)
    u, i = np.where(rng.rand(20, 15) < 0.6)
    frame = MLFrame(ctx, {"user": u, "item": i,
                          "rating": rng.randn(len(u))})
    ck = str(tmp_path / "ck")
    ALS(rank=2, maxIter=5, seed=1, checkpointDir=ck,
        checkpointInterval=1).fit(frame)
    with pytest.raises(ValueError, match="over-trained"):
        ALS(rank=2, maxIter=3, seed=1, checkpointDir=ck,
            checkpointInterval=1).fit(frame)


def test_multinomial_evaluate_rejected(ctx):
    rng = np.random.RandomState(0)
    x = rng.randn(90, 4)
    y = rng.randint(0, 3, 90).astype(float)
    model = LogisticRegression(maxIter=5, family="multinomial").fit(
        MLFrame(ctx, {"features": x, "label": y}))
    with pytest.raises(ValueError, match="binary-only"):
        model.evaluate(MLFrame(ctx, {"features": x, "label": y}))


def test_profiler_hook(ctx, tmp_path):
    import jax.numpy as jnp
    d = str(tmp_path / "trace")
    with ctx.profile(d):
        float(jnp.sum(jnp.arange(16.0)))
    # a trace directory with at least one artifact was produced
    found = [os.path.join(r, f) for r, _, fs in os.walk(d) for f in fs]
    assert found
