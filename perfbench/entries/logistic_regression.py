"""Entry: ``cycloneml_tpu.ml.classification.LogisticRegression.fit`` on an
in-core dense dataset."""

from __future__ import annotations

import numpy as np

from perfbench.entries import glm

work_per_eval = glm.work_per_eval


def dataset(ctx, x, y):
    return glm.instance_dataset(ctx, x, y, host_labels=True)


def estimator(params: dict):
    from cycloneml_tpu.ml.classification import LogisticRegression
    return LogisticRegression(**params)


def fit(est, ds, ctx) -> dict:
    """One timed fit, ended by the host copy of the model; the counters are
    the fit's own summary."""
    model = est.fit(ds)
    s = model.summary
    return {"coef": np.asarray(model.coefficients, np.float64),
            "intercept": float(model.intercept),
            "objective": float(s.objective_history[-1]),
            "iterations": int(s.total_iterations),
            "evals": int(s.total_evals),
            "dispatches": int(s.total_dispatches),
            "streamed": bool(s.streamed)}


def assert_path(ctx, ds, answer: dict, x_dtype: str, native: bool) -> None:
    glm.assert_stored(ds, ctx.mesh_runtime.n_devices, x_dtype)
    if answer["streamed"]:
        raise AssertionError("the fit was re-routed out of core")
    if not answer["dispatches"] < answer["evals"]:
        raise AssertionError(
            f"{answer['dispatches']} dispatches for {answer['evals']} "
            f"evaluations: the fit left the device-resident optimiser")
    if native:
        import jax.numpy as jnp
        from cycloneml_tpu.ml.optim import aggregators
        d = ds.n_features
        v = jnp.zeros(d, jnp.float32)
        glm.assert_mosaic(ds, aggregators.binary_logistic_pallas_scaled(
            d, True), (v, v, jnp.zeros(d + 1, jnp.float32)))
