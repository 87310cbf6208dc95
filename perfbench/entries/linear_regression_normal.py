"""Entry: ``cycloneml_tpu.ml.regression.LinearRegression.fit`` on an in-core
dense dataset with no L1 share and at most 4,096 features: the normal
equations — one moment pass over X (``WeightedLeastSquares``), then a
Cholesky solve on the host."""

from __future__ import annotations

import re

import numpy as np

from perfbench.entries import glm


def work_per_eval(n_rows: int, n_cols: int, x_itemsize: int) -> dict:
    """What one moment pass must do whatever implements it: read the stored
    X once, and multiply-add the symmetric half of ``X'X`` (``d (d + 1) / 2``
    entries a row, so a later triangle-only kernel cannot read over 100 %)
    and the two vectors ``X'1`` and ``X'y``."""
    n, d = float(n_rows), float(n_cols)
    return {"bytes": n * d * x_itemsize, "flops": n * d * (d + 1) + 2 * n * d}


def dataset(ctx, x, y):
    return glm.instance_dataset(ctx, x, y, host_labels=False)


def estimator(params: dict):
    from cycloneml_tpu.ml.regression import LinearRegression
    return LinearRegression(**params)


def fit(est, ds, ctx) -> dict:
    """One timed fit; every counter is the model's own summary's. ``evals``
    counts passes over X (what the shares of a peak multiply the required
    work by): the solver evaluates no loss function."""
    model = est.fit(ds)
    s = model.summary
    return {"coef": np.asarray(model.coefficients, np.float64),
            "intercept": float(model.intercept),
            "objective": float(s.objective_history[-1]),
            "iterations": int(s.total_iterations),
            "solver": s.solver,
            "evals": int(s.total_passes),
            "dispatches": int(s.total_dispatches),
            "streamed": bool(s.streamed)}


def assert_path(ctx, ds, answer: dict, x_dtype: str, native: bool) -> None:
    glm.assert_stored(ds, ctx.mesh_runtime.n_devices, x_dtype)
    if answer["streamed"]:
        raise AssertionError("the fit was re-routed out of core")
    if (answer["solver"], answer["evals"], answer["dispatches"]) != \
            ("normal", 1, 1):
        raise AssertionError(
            f"solver {answer['solver']!r}, {answer['evals']} passes, "
            f"{answer['dispatches']} dispatches: not one moment pass of "
            f"the normal equations")
    if native:
        assert_no_copy_of_x(ds)


def assert_no_copy_of_x(ds) -> None:
    """The fit's own aggregation program (the factory and the program cache
    are keyed by identity, so asking again returns it), compiled, holds no
    f32 value of X's shape and no pad or copy of a bf16 array with X's
    rows."""
    from cycloneml_tpu.ml.optim import wls
    from cycloneml_tpu.ops import kernels
    from cycloneml_tpu.parallel import collectives
    size = len(collectives._program_cache)
    call = ds.tree_aggregate_fn(
        wls.moments_aggregator(kernels.stored_feature_major(ds.x)))
    if len(collectives._program_cache) != size:
        raise AssertionError("the fit did not build the moment program "
                             "this proof asks for")
    text = call.compiled.__wrapped__.lower(*call.arrays()).compile().as_text()
    rows, d = ds.x.sharding.shard_shape(ds.x.shape)
    wide = re.findall(rf"= f32\[{rows},{d}\]", text)
    moved = re.findall(rf"= bf16\[{rows},\d+\]\S* (?:pad|copy)\(", text)
    if wide or moved:
        raise AssertionError(f"the moment program widens or copies X: "
                             f"{(wide + moved)[:3]}")
