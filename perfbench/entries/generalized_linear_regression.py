"""Entry: ``cycloneml_tpu.ml.regression.GeneralizedLinearRegression.fit`` on
an in-core dense dataset: iteratively reweighted least squares — per
iteration one aggregation program over X (the working point and the
weighted moment Gramian) and a Cholesky solve on the host — read for what
its user came for: the coefficient table (estimates, standard errors,
deviance)."""

from __future__ import annotations

import re

import numpy as np

from perfbench.entries import glm


def work_per_eval(n_rows: int, n_cols: int, x_itemsize: int) -> dict:
    """What one IRLS pass must do whatever implements it: read the stored X
    once (the margins and the Gramian can share the read), multiply-add the
    symmetric half of ``X'WX`` (``d (d + 1) / 2`` entries a row), the two
    vectors ``X'W1`` and ``X'Wz``, and the margins ``X b``."""
    n, d = float(n_rows), float(n_cols)
    return {"bytes": n * d * x_itemsize, "flops": n * d * (d + 1) + 4 * n * d}


def dataset(ctx, x, y):
    from cycloneml_tpu.ml.regression import GeneralizedLinearRegression
    if not hasattr(GeneralizedLinearRegression, "_fit_dataset"):
        # a program without the device-dataset path would pull all of X to
        # the host as float64: refuse before any fit
        raise SystemExit("GeneralizedLinearRegression has no _fit_dataset: "
                         "this program cannot fit a device-resident dataset")
    return glm.instance_dataset(ctx, x, y, host_labels=False)


def estimator(params: dict):
    from cycloneml_tpu.ml.regression import GeneralizedLinearRegression
    return GeneralizedLinearRegression(**params)


def fit(est, ds, ctx) -> dict:
    """One timed fit, ended by the host copy of the model AND of its
    standard errors (the ``potri`` over the last solve's factor is paid
    inside every fit). ``evals`` counts weighted-Gramian passes over X."""
    model = est.fit(ds)
    s = model.summary
    return {"coef": np.asarray(model.coefficients, np.float64),
            "intercept": float(model.intercept),
            "objective": float(s.deviance),
            "standard_errors": np.asarray(s.coefficient_standard_errors,
                                          np.float64),
            "iterations": int(s.num_iterations),
            "solver": est.get("solver"),
            "evals": int(s.total_passes),
            "dispatches": int(s.total_dispatches)}


def assert_path(ctx, ds, answer: dict, x_dtype: str, native: bool) -> None:
    glm.assert_stored(ds, ctx.mesh_runtime.n_devices, x_dtype)
    passes, dispatches = answer["evals"], answer["dispatches"]
    if answer["solver"] != "irls" or passes != answer["iterations"] \
            or passes < 2 or dispatches != passes + 1:
        raise AssertionError(
            f"solver {answer['solver']!r}, {passes} passes for "
            f"{answer['iterations']} iterations, {dispatches} dispatches: "
            f"not one weighted-Gramian pass an iteration and one deviance "
            f"pass")
    if not np.all(np.isfinite(answer["standard_errors"])) or \
            answer["standard_errors"].shape != (ds.n_features + 1,):
        raise AssertionError("the fit returned no standard errors")
    if native:
        assert_irls_program(ds)


def assert_irls_program(ds) -> None:
    """The fit's own IRLS program (the factory and the program cache are
    keyed by value and identity, so asking again returns it), compiled,
    holds a Mosaic call, no f32 value of X's shape and no pad or copy of a
    bf16 array with X's rows."""
    import jax.numpy as jnp
    from cycloneml_tpu.ml.regression import glm as program
    from cycloneml_tpu.ops import kernels
    from cycloneml_tpu.parallel import collectives
    size = len(collectives._program_cache)
    call = ds.tree_aggregate_fn(program.irls_aggregator(
        program.Binomial(), program.Logit(),
        kernels.stored_feature_major(ds.x), False))
    if len(collectives._program_cache) != size:
        raise AssertionError("the fit did not build the IRLS program this "
                             "proof asks for")
    params = jnp.zeros(ds.n_features + 2, jnp.float32)
    text = call.compiled.__wrapped__.lower(
        *call.arrays(), params).compile().as_text()
    if "tpu_custom_call" not in text:
        raise AssertionError("no Mosaic custom call in the IRLS program: "
                             "the moment Gramian was replaced")
    rows, d = ds.x.sharding.shard_shape(ds.x.shape)
    wide = re.findall(rf"= f32\[{rows},{d}\]", text)
    moved = re.findall(rf"= bf16\[{rows},\d+\]\S* (?:pad|copy)\(", text)
    if wide or moved:
        raise AssertionError(f"the IRLS program widens or copies X: "
                             f"{(wide + moved)[:3]}")
