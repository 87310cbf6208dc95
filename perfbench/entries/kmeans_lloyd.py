"""Entry: ``cycloneml_tpu.ml.clustering.KMeans.fit`` from a stated starting
set (``initialModel``) on an in-core dense dataset: Lloyd's steps, each one
``tree_aggregate`` program over the fused assign-and-update kernel, the
centre update on the host. The clustered points are
``perfbench.kmeans_points`` of the generator's stored rows; the model goes
to ``judge.compare`` as one flat vector, the centres row-major."""

from __future__ import annotations

import importlib.util
import re

import numpy as np

from perfbench import kmeans_points, manifest
from perfbench.entries import glm

CONFIG = "kmeans_synth128_k1000"
#: the mesh axes the rows are sharded over (``perfbench.run.ROW_AXES``)
ROW_AXES = ("replica", "data")
KERNEL_MODULE = "cycloneml_tpu.ops.kmeans_lloyd"


def work_per_eval(n_rows: int, n_cols: int, x_itemsize: int) -> dict:
    """What one Lloyd step must do whatever implements it: read the stored
    X once, and one multiply-add for every row, centre and feature (the
    distances alone, at ONE pass: the update is O(n d) as a reduction)."""
    k = kmeans_points.spec(CONFIG)["k"]
    n, d = float(n_rows), float(n_cols)
    return {"bytes": n * d * x_itemsize, "flops": 2.0 * n * k * d}


def dataset(ctx, x, y):
    from cycloneml_tpu.ml.clustering import KMeans
    # refused BEFORE any allocation: a program without the starting set
    # cannot make the reference's iteration, and one without the fused step
    # would build the (rows, k) distance matrix
    if "initialModel" not in KMeans()._params:
        raise SystemExit("KMeans has no initialModel param: this program "
                         "cannot start Lloyd's steps from a stated set")
    if importlib.util.find_spec(KERNEL_MODULE) is None:
        raise SystemExit(f"no {KERNEL_MODULE}: this program has no fused "
                         f"Lloyd step")
    pts = kmeans_points.points(x, ctx.mesh_runtime.mesh, ROW_AXES,
                               **kmeans_points.spec(CONFIG))
    return glm.instance_dataset(ctx, pts, y, host_labels=False)


def estimator(params: dict):
    from cycloneml_tpu.ml.clustering import KMeans
    cfg = manifest.load_json(manifest.HERE, "configs", CONFIG + ".json")
    spec = kmeans_points.spec(CONFIG)
    return KMeans(**params, initialModel=kmeans_points.start(
        spec["data_seed"], spec["k"], int(cfg["n_features"]), spec["r"]))


def fit(est, ds, ctx) -> dict:
    """One timed fit, ended by the host copy of the centres; the counters
    are the fit's own summary."""
    model = est.fit(ds)
    s = model.summary
    centres = np.asarray(model.cluster_centers_matrix().to_array(),
                         np.float64)
    return {"coef": centres.ravel(), "intercept": 0.0,
            "objective": float(s.training_cost),
            "iterations": int(s.num_iter), "evals": int(s.total_steps),
            "dispatches": int(s.total_dispatches),
            "orientation": s.orientation, "pieces": s.pieces}


def assert_path(ctx, ds, answer: dict, x_dtype: str, native: bool) -> None:
    glm.assert_stored(ds, ctx.mesh_runtime.n_devices, x_dtype)
    if not answer["dispatches"] <= answer["evals"] + 2:
        raise AssertionError(
            f"{answer['dispatches']} dispatches for {answer['evals']} "
            f"steps: more than one launch a step and the cost pass")
    if native:
        if answer["pieces"] != 3:
            raise AssertionError(f"the scores took {answer['pieces']} bf16 "
                                 f"pieces of a centre, the configuration "
                                 f"states float32 centres (3)")
        assert_step_program(ds, kmeans_points.spec(CONFIG)["k"],
                            answer["orientation"])


def assert_step_program(ds, k: int, orientation) -> None:
    """The fit's own step program (the factory is cached by value and the
    program cache by identity, so asking again returns it), compiled, holds
    a Mosaic call, no f32 value of X's shape, no value of any type with X's
    rows and k (or the padded k) columns, and no pad or copy of a bf16 array
    with X's rows."""
    import jax.numpy as jnp
    from cycloneml_tpu.ml.clustering import kmeans
    from cycloneml_tpu.parallel import collectives
    if orientation != "row_major":
        raise AssertionError(f"the fit ran no fused Lloyd step (orientation "
                             f"{orientation!r})")
    d = ds.n_features
    size = len(collectives._program_cache)
    call = ds.tree_aggregate_fn(kmeans.lloyd_aggregator(True, True))
    if len(collectives._program_cache) != size:
        raise AssertionError("the fit did not build the fused Lloyd step "
                             "program")
    text = call.compiled.__wrapped__.lower(
        *call.arrays(), jnp.zeros((k, d), jnp.float32)).compile().as_text()
    if "tpu_custom_call" not in text or "kmeans_lloyd" not in text:
        raise AssertionError("no Mosaic custom call kmeans_lloyd in the "
                             "step program: the kernel was replaced")
    rows = ds.x.sharding.shard_shape(ds.x.shape)[0]
    wide = re.findall(rf"= f32\[{rows},{d}\]", text)
    scores = re.findall(rf"= \w+\[{rows},(?:{k}|{-(-k // 16) * 16}|"
                        rf"{-(-k // 128) * 128})\]", text)
    moved = re.findall(rf"= bf16\[{rows},\d+\]\S* (?:pad|copy)\(", text)
    if wide or scores or moved:
        raise AssertionError(f"the step program widens or copies X, or "
                             f"builds the distance matrix: "
                             f"{(wide + scores + moved)[:3]}")
