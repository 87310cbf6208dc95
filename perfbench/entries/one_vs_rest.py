"""Entry: ``cycloneml_tpu.ml.classification.OneVsRest.fit`` over a
``LogisticRegression`` base classifier on an in-core dense dataset whose
labels are class indices: K binary fits as ONE stacked program — one read of
X an evaluation for all K models, K device-resident L-BFGS lanes. The labels
are ``perfbench.class_labels`` of the stored X; the K models go to
``judge.compare`` as one flat vector ``[W.ravel(), b]``, row k the k-th
binary model."""

from __future__ import annotations

import re

import numpy as np

from perfbench import class_labels
from perfbench.entries import glm

CONFIG = "ovr_lr_mnist8m"
#: the mesh axes the rows are sharded over (``perfbench.run.ROW_AXES``)
ROW_AXES = ("replica", "data")
#: the estimator's own Params; every other one is the base classifier's
OVR_PARAMS = ("parallelism",)


def require_program() -> None:
    """Refuse a program that cannot run this cell: one whose ``OneVsRest``
    takes host frames only, or whose stacked fit has no sweep that reads X
    once for all K models (it would pad and copy X, or fit through K serial
    sweeps). ``manifest.Cell`` imports this module before the harness makes
    any data, and the module asks at once: the refusal costs no allocation."""
    from cycloneml_tpu.ml.classification import one_vs_rest
    from cycloneml_tpu.ml.optim import aggregators
    if not hasattr(one_vs_rest, "OneVsRestSummary"):
        raise SystemExit("OneVsRest has no dataset path: this program fits "
                         "one-vs-rest from host frames only")
    if not hasattr(aggregators, "stacked_binary_logistic_pallas_scaled"):
        raise SystemExit("no stacked binomial Pallas aggregator: this "
                         "program has no sweep that serves K models a read")


require_program()


def work_per_eval(n_rows: int, n_cols: int, x_itemsize: int) -> dict:
    """What one evaluation of the K objectives must do whatever implements
    it: read the stored X ONCE (every model's margins and gradient can share
    the read), and multiply-add every element twice for each of the K models
    (``X W'`` and ``M' X``)."""
    k = class_labels.spec(CONFIG)["classes"]
    n, d = float(n_rows), float(n_cols)
    return {"bytes": n * d * x_itemsize, "flops": 4.0 * n * d * k}


def dataset(ctx, x, y):
    require_program()
    labels = class_labels.of(x, ctx.mesh_runtime.mesh, ROW_AXES,
                             **class_labels.spec(CONFIG))
    return glm.instance_dataset(ctx, x, labels, host_labels=True)


def estimator(params: dict):
    from cycloneml_tpu.ml.classification import LogisticRegression, OneVsRest
    base = {k: v for k, v in params.items() if k not in OVR_PARAMS}
    own = {k: params[k] for k in OVR_PARAMS if k in params}
    return OneVsRest(classifier=LogisticRegression(**base), **own)


def frozen_lane_evals_pct(classes: int, evals: int, lane_evals: int) -> float:
    """The share of lane-evaluations a shared sweep computed for models that
    no longer asked for one: ``classes * evals`` were computed (every sweep
    serves every lane), ``lane_evals`` were wanted."""
    computed = classes * evals
    return 100.0 * (computed - lane_evals) / computed if computed else 0.0


def fit(est, ds, ctx) -> dict:
    """One timed fit, ended by the host copy of the K coefficient vectors
    and intercepts; the counters are the fit's own summary."""
    model = est.fit(ds)
    s = model.summary
    wmat = np.stack([m.coefficients.to_array() for m in model.models])
    icpt = np.array([float(m.intercept) for m in model.models])
    iterations = max(s.iterations)
    lane_evals = sum(s.evals)
    return {"coef": np.concatenate([wmat.ravel(), icpt]),
            "intercept": 0.0,
            "objective": float(sum(s.objectives)),
            "iterations": int(iterations),
            "evals": int(s.total_evals),
            "lane_evals": int(lane_evals),
            "evals_per_iteration": s.total_evals / max(iterations, 1),
            "dispatches": int(s.total_dispatches),
            "classes": int(s.num_classes),
            "orientation": s.orientation,
            "pieces": s.pieces,
            "frozen_lane_evals_pct": frozen_lane_evals_pct(
                s.num_classes, s.total_evals, lane_evals)}


def assert_path(ctx, ds, answer: dict, x_dtype: str, native: bool) -> None:
    glm.assert_stored(ds, ctx.mesh_runtime.n_devices, x_dtype)
    k = class_labels.spec(CONFIG)["classes"]
    if answer["classes"] != k:
        raise AssertionError(f"the fit made {answer['classes']} models, the "
                             f"configuration states {k}")
    if not answer["dispatches"] < answer["evals"]:
        raise AssertionError(
            f"{answer['dispatches']} dispatches for {answer['evals']} "
            f"evaluations: the fit left the device-resident optimiser")
    if native:
        if answer["pieces"] != 3:
            raise AssertionError(f"the sweep's products ran in "
                                 f"{answer['pieces']} pieces, not 3")
        assert_sweep_program(ds, k, answer["orientation"])


def assert_sweep_program(ds, k: int, orientation) -> None:
    """The fit's own evaluation program (the factory is cached by value and
    the program cache by identity, so asking again returns it), compiled,
    holds ONE Mosaic call — X is read once for all K models — no f32 value
    of X's shape, no pad or copy of a bf16 array with X's rows, and no value
    of one entry a (row, model) pair in a storage type: the label matrix."""
    import jax.numpy as jnp
    from cycloneml_tpu.ml.optim import aggregators
    from cycloneml_tpu.parallel import collectives
    if orientation not in ("feature_major", "row_major"):
        raise AssertionError(f"the fit ran no fused sweep (orientation "
                             f"{orientation!r})")
    d = ds.n_features
    size = len(collectives._program_cache)
    call = ds.tree_aggregate_fn(
        aggregators.stacked_binary_logistic_pallas_scaled(
            d, k, True, feature_major=orientation == "feature_major"))
    if len(collectives._program_cache) != size:
        raise AssertionError("the fit did not build the stacked Pallas "
                             "aggregation program")
    v = jnp.zeros(d, jnp.float32)
    text = call.compiled.__wrapped__.lower(
        *call.arrays(), v, v, jnp.zeros((k, d + 1), jnp.float32)
    ).compile().as_text()
    if text.count("tpu_custom_call") != 1:
        raise AssertionError(
            f"{text.count('tpu_custom_call')} Mosaic custom calls in the "
            f"evaluation program: X is not read once for all {k} models")
    rows = ds.x.sharding.shard_shape(ds.x.shape)[0]
    k_pad = -(-k // 16) * 16
    pairs = "|".join(f"{a},{b}" for a, b in (
        (rows, k), (rows, k_pad), (k, rows), (k_pad, rows)))
    wide = re.findall(rf"= f32\[{rows},{d}\]", text)
    moved = re.findall(rf"= bf16\[{rows},\d+\]\S* (?:pad|copy)\(", text)
    labels = re.findall(rf"= (?:bf16|f16|f32|s8|u8|s32|pred)\[(?:{pairs})\]",
                        text)
    if wide or moved or labels:
        raise AssertionError(
            f"the evaluation program widens or copies X, or holds a label "
            f"matrix: {(wide + moved + labels)[:3]}")
