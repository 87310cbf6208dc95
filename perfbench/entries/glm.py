"""What the dense GLM entries share: the dataset the program is handed, the
required work of one evaluation, and the proof of the path a fit took."""

from __future__ import annotations

import numpy as np


def instance_dataset(ctx, x, y, host_labels: bool):
    """The benchmark's arrays as the program's ``InstanceDataset`` (unit
    weights; the row count divides the shards, so no row is padding)."""
    from cycloneml_tpu.dataset.dataset import InstanceDataset
    n, d = x.shape
    w = np.ones(n, np.float32)
    ds = InstanceDataset(ctx, x, y, ctx.mesh_runtime.device_put_sharded_rows(w),
                         n, d)
    if host_labels:
        ds.attach_host_labels(np.asarray(y).astype(np.float64),
                              w.astype(np.float64))
    return ds


def work_per_eval(n_rows: int, n_cols: int, x_itemsize: int) -> dict:
    """What one loss-and-gradient evaluation must do whatever implements it:
    read the stored X once (the margins and the gradient can share the
    read), and multiply-add every element twice (``X b`` and ``X' r``)."""
    return {"bytes": float(n_rows) * n_cols * x_itemsize,
            "flops": 4.0 * n_rows * n_cols}


def assert_stored(ds, n_devices: int, x_dtype: str) -> None:
    if str(ds.x.dtype) != x_dtype:
        raise AssertionError(f"data tier is {ds.x.dtype}, the configuration "
                             f"states {x_dtype}")
    if len(ds.x.sharding.device_set) != n_devices:
        raise AssertionError(f"X sits on {len(ds.x.sharding.device_set)} of "
                             f"{n_devices} devices")
    if ds.x_scale is not None:
        raise AssertionError("an fp8 tier on a default-conf fit")


def assert_mosaic(ds, agg, extras) -> None:
    """The aggregation program a default-conf fit of ``ds`` built lowers to
    a Mosaic custom call (the factory and the program cache are keyed by
    identity, so asking again returns the fit's own program)."""
    from cycloneml_tpu.parallel import collectives
    size = len(collectives._program_cache)
    call = ds.tree_aggregate_fn(agg)
    if len(collectives._program_cache) != size:
        raise AssertionError("the fit did not build the Pallas aggregation "
                             "program")
    text = call.compiled.__wrapped__.lower(*call.arrays(), *extras).as_text()
    if "tpu_custom_call" not in text:
        raise AssertionError("no Mosaic custom call in the aggregation "
                             "program: the kernel was replaced")
