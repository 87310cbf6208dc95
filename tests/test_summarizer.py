"""The label's side of the one-pass summary.

``SummaryStats.label_sum`` / ``label_sq_sum`` / ``weight_sq_sum`` (Σ w·y,
Σ w·y², Σ w²) ride the Summarizer's single pass in core and the shard write
pass for a streamed dataset; ``LinearRegression`` standardises the label by
them and runs no pass of its own.
"""

import numpy as np
import pytest

from cycloneml_tpu.dataset.dataset import InstanceDataset
from cycloneml_tpu.ml.regression import LinearRegression
from cycloneml_tpu.ml.stat import Summarizer


def _weights(kind, n, rng):
    if kind == "unit":
        return None
    w = rng.uniform(0.25, 3.0, n)
    if kind == "padded":
        # zero-weight rows with loud labels: they must count for nothing
        w[rng.choice(n, n // 5, replace=False)] = 0.0
    return w


def _summarised(ctx, where, x, y, w):
    """``(summary, dataset, close)`` of the rows in core or streamed."""
    if where == "in_core":
        ds = InstanceDataset.from_numpy(ctx, x, y, w)
        return Summarizer.summarize(ds), ds, lambda: None
    from cycloneml_tpu.oocore import StreamingDataset

    def chunks():
        for lo in range(0, len(x), 450):  # chunk != shard boundaries
            yield (x[lo:lo + 450], y[lo:lo + 450],
                   None if w is None else w[lo:lo + 450])

    sds = StreamingDataset.from_chunks(ctx, chunks(), x.shape[1],
                                       shard_rows=700)
    return sds.summary(), sds, sds.close


@pytest.mark.parametrize("where", ["in_core", "streamed"])
@pytest.mark.parametrize("weights", ["unit", "weighted", "padded"])
def test_label_moments_match_numpy(ctx, where, weights):
    rng = np.random.RandomState(31)
    n, d = 2501, 6                      # no multiple of the 8 shards: padded
    x = rng.randn(n, d)
    y = 3.0 + x @ rng.randn(d) + 0.3 * rng.randn(n)
    w = _weights(weights, n, rng)
    if weights == "padded":
        y[w == 0.0] = 1e6
    stats, _, close = _summarised(ctx, where, x, y, w)
    try:
        wn = np.ones(n) if w is None else w
        assert stats.label_sum == pytest.approx(float(wn @ y), rel=1e-12)
        assert stats.label_sq_sum == pytest.approx(float(wn @ (y * y)),
                                                   rel=1e-12)
        assert stats.weight_sq_sum == pytest.approx(float(wn @ wn),
                                                    rel=1e-12)
        assert stats.weight_sum == pytest.approx(float(wn.sum()), rel=1e-12)
        assert all(isinstance(v, float) for v in (
            stats.label_sum, stats.label_sq_sum, stats.weight_sq_sum))
    finally:
        close()


@pytest.mark.parametrize("where", ["in_core", "streamed"])
def test_constant_label_still_returns_early(ctx, where):
    """σ_y = 0 read from the summary takes the constant-label shortcut:
    zero coefficients, the label as intercept, no optimiser."""
    rng = np.random.RandomState(32)
    n = 1500
    x, y = rng.randn(n, 3), np.full(n, 7.5)
    stats, ds, close = _summarised(ctx, where, x, y, None)
    try:
        assert stats.label_sum == pytest.approx(7.5 * n)
        assert stats.label_sq_sum == pytest.approx(7.5 ** 2 * n)
        m = LinearRegression(regParam=0.1, elasticNetParam=0.5).fit(ds)
        np.testing.assert_array_equal(m.coefficients.to_array(), 0.0)
        assert m.intercept == pytest.approx(7.5)
        assert m.summary.objective_history == [0.0]
        assert m.summary.total_evals is None
    finally:
        close()
