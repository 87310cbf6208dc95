"""Entry: ``cycloneml_tpu.ml.regression.LinearRegression.fit`` on an in-core
dense dataset (with an L1 share: the host-side OWL-QN path)."""

from __future__ import annotations

import numpy as np

from perfbench.entries import glm

work_per_eval = glm.work_per_eval


def dataset(ctx, x, y):
    return glm.instance_dataset(ctx, x, y, host_labels=False)


def estimator(params: dict):
    from cycloneml_tpu.ml.regression import LinearRegression
    return LinearRegression(**params)


def _steps(ctx) -> int:
    return int(ctx.metrics.registry.counter("steps.completed").count)


def fit(est, ds, ctx) -> dict:
    """One timed fit. The model's summary carries no evaluation count, so
    the counters are the context's own: every evaluation of the host-side
    optimiser is one dispatch and posts one completed step."""
    before = _steps(ctx)
    model = est.fit(ds)
    evals = _steps(ctx) - before
    s = model.summary
    return {"coef": np.asarray(model.coefficients, np.float64),
            "intercept": float(model.intercept),
            "objective": float(s.objective_history[-1]),
            "iterations": int(s.total_iterations),
            "evals": evals, "dispatches": evals,
            "streamed": bool(s.streamed)}


def assert_path(ctx, ds, answer: dict, x_dtype: str, native: bool) -> None:
    glm.assert_stored(ds, ctx.mesh_runtime.n_devices, x_dtype)
    if answer["streamed"]:
        raise AssertionError("the fit was re-routed out of core")
    if answer["evals"] <= answer["iterations"]:
        raise AssertionError(
            f"{answer['evals']} evaluations for {answer['iterations']} "
            f"iterations: the step counter did not follow the fit")
    if native:
        import jax.numpy as jnp
        from cycloneml_tpu.ml.optim import aggregators
        d = ds.n_features
        v = jnp.zeros(d, jnp.float32)
        glm.assert_mosaic(ds, aggregators.least_squares_pallas_scaled(d),
                          (v, v, jnp.zeros(2, jnp.float32), v))
