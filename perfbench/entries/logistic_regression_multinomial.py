"""Entry: ``cycloneml_tpu.ml.classification.LogisticRegression.fit`` on an
in-core dense dataset with more than two label classes: ``family="auto"``
turns multinomial (softmax regression, all K coefficient vectors kept), the
fused K-class sweep under the device-resident L-BFGS. The labels are
``perfbench.class_labels`` of the stored X; the model goes to
``judge.compare`` as one flat vector ``[W.ravel(), b]``."""

from __future__ import annotations

import re

import numpy as np

from perfbench import class_labels
from perfbench.entries import glm

CONFIG = "lr_mnist8m_multinomial"
#: the mesh axes the rows are sharded over (``perfbench.run.ROW_AXES``)
ROW_AXES = ("replica", "data")


def work_per_eval(n_rows: int, n_cols: int, x_itemsize: int) -> dict:
    """What one loss-and-gradient evaluation must do whatever implements it:
    read the stored X once (margins and gradient can share the read), and
    multiply-add every element twice for each of the K classes (``X W'``
    and ``M' X``)."""
    k = class_labels.spec(CONFIG)["classes"]
    n, d = float(n_rows), float(n_cols)
    return {"bytes": n * d * x_itemsize, "flops": 4.0 * n * d * k}


def dataset(ctx, x, y):
    from cycloneml_tpu.ml.optim import aggregators
    if not hasattr(aggregators, "multinomial_logistic_pallas_scaled"):
        # a program without the K-class kernel would fit through XLA's two
        # passes with the coefficients rounded to bf16: refuse before any fit
        raise SystemExit("no multinomial Pallas aggregator: this program "
                         "has no fused K-class sweep")
    labels = class_labels.of(x, ctx.mesh_runtime.mesh, ROW_AXES,
                             **class_labels.spec(CONFIG))
    return glm.instance_dataset(ctx, x, labels, host_labels=True)


def estimator(params: dict):
    from cycloneml_tpu.ml.classification import LogisticRegression
    return LogisticRegression(**params)


def fit(est, ds, ctx) -> dict:
    """One timed fit, ended by the host copy of the coefficient matrix and
    the intercept vector; the counters are the fit's own summary."""
    model = est.fit(ds)
    s = model.summary
    wmat = np.asarray(model.coefficient_matrix.to_array(), np.float64)
    icpt = np.asarray(model.intercept_vector.to_array(), np.float64)
    return {"coef": np.concatenate([wmat.ravel(), icpt]),
            "intercept": 0.0,
            "objective": float(s.objective_history[-1]),
            "iterations": int(s.total_iterations),
            "evals": int(s.total_evals),
            "evals_per_iteration": s.total_evals / max(s.total_iterations, 1),
            "dispatches": int(s.total_dispatches),
            "classes": int(model.num_classes),
            "orientation": s.orientation,
            "streamed": bool(s.streamed)}


def assert_path(ctx, ds, answer: dict, x_dtype: str, native: bool) -> None:
    glm.assert_stored(ds, ctx.mesh_runtime.n_devices, x_dtype)
    k = class_labels.spec(CONFIG)["classes"]
    if answer["classes"] != k:
        raise AssertionError(f"the fit saw {answer['classes']} classes, the "
                             f"configuration states {k}")
    if answer["streamed"]:
        raise AssertionError("the fit was re-routed out of core")
    if not answer["dispatches"] < answer["evals"]:
        raise AssertionError(
            f"{answer['dispatches']} dispatches for {answer['evals']} "
            f"evaluations: the fit left the device-resident optimiser")
    if native:
        assert_sweep_program(ds, k, answer["orientation"])


def assert_sweep_program(ds, k: int, orientation) -> None:
    """The fit's own aggregation program (the factory is cached by value
    and the program cache by identity, so asking again returns it),
    compiled, holds a Mosaic call, no f32 value of X's shape and no pad or
    copy of a bf16 array with X's rows."""
    import jax.numpy as jnp
    from cycloneml_tpu.ml.optim import aggregators
    from cycloneml_tpu.parallel import collectives
    if orientation not in ("feature_major", "row_major"):
        raise AssertionError(f"the fit ran no fused sweep (orientation "
                             f"{orientation!r})")
    d = ds.n_features
    size = len(collectives._program_cache)
    call = ds.tree_aggregate_fn(aggregators.multinomial_logistic_pallas_scaled(
        d, k, True, feature_major=orientation == "feature_major"))
    if len(collectives._program_cache) != size:
        raise AssertionError("the fit did not build the multinomial Pallas "
                             "aggregation program")
    v = jnp.zeros(d, jnp.float32)
    text = call.compiled.__wrapped__.lower(
        *call.arrays(), v, v, jnp.zeros(d * k + k, jnp.float32)
    ).compile().as_text()
    if "tpu_custom_call" not in text:
        raise AssertionError("no Mosaic custom call in the aggregation "
                             "program: the kernel was replaced")
    rows = ds.x.sharding.shard_shape(ds.x.shape)[0]
    wide = re.findall(rf"= f32\[{rows},{d}\]", text)
    moved = re.findall(rf"= bf16\[{rows},\d+\]\S* (?:pad|copy)\(", text)
    if wide or moved:
        raise AssertionError(f"the aggregation program widens or copies X: "
                             f"{(wide + moved)[:3]}")
