"""K-class labels for a dense design the generator made: a deterministic
function of each row's STORED values,

    label_i = argmax_k  x_i.B_k + s ((x_i.C_k)^2 - 1) / sqrt(2),

with ``B``, ``C`` drawn from the traffic's ``data_seed`` (unit columns, so
for standard-normal rows both terms have unit variance). The linear term is
what a softmax regression can learn; the quadratic one is what it cannot,
so it is the label noise, and ``s`` sets the accuracy of the best linear
model. Being a function of the row, the same rows give the same labels in
any order and on any shard: ``--seed`` still only orders them.

``datagen.generate`` knows a binary and a regression label and is not this
PR's to edit; a configuration that wants classes states ``data.class_labels``
(``classes``, ``noise`` = s, and the ``traffic`` whose ``data_seed`` draws
``B`` and ``C``) and both its entry and its reference call :func:`of` —
ONE jitted program on the same array, so no near-tie flips between them.
"""

from __future__ import annotations

import functools

import numpy as np

from perfbench import datagen, manifest
from perfbench.reference import blocks


def spec(config_name: str) -> dict:
    """``{"classes", "noise", "data_seed"}`` of a configuration."""
    cfg = manifest.load_json(manifest.HERE, "configs", config_name + ".json")
    labels = cfg["data"]["class_labels"]
    traffic = manifest.load_json(manifest.HERE, "traffic",
                                 labels["traffic"] + ".json")
    return {"classes": int(labels["classes"]),
            "noise": float(labels["noise"]),
            "data_seed": int(traffic["data_seed"])}


@functools.lru_cache(maxsize=8)
def _program(mesh, row_axes, rows, n_cols, classes):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    b = blocks.block_rows(rows)
    row = P(tuple(row_axes))
    hi = jax.lax.Precision.HIGHEST

    def unit_columns(key):
        m = jax.random.normal(key, (n_cols, classes), jnp.float32)
        return m / jnp.linalg.norm(m, axis=0, keepdims=True)

    def local(x, key, noise):
        lin = unit_columns(jax.random.fold_in(key, 1))
        quad = unit_columns(jax.random.fold_in(key, 2))

        def block(_, i):
            xf = jax.lax.dynamic_slice(
                x, (i * b, 0), (b, n_cols)).astype(jnp.float32)
            z = jnp.dot(xf, quad, precision=hi)
            score = jnp.dot(xf, lin, precision=hi) \
                + noise * (z * z - 1.0) / np.sqrt(2.0)
            return None, jnp.argmax(score, axis=1).astype(jnp.float32)

        _, labels = jax.lax.scan(block, None, jnp.arange(rows // b))
        return labels.reshape(rows)

    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(row, P(), P()),
                                 out_specs=row, check_vma=False))


def of(x, mesh, row_axes, *, classes: int, noise: float, data_seed: int):
    """``(n,)`` float32 class indices of ``x``'s rows, sharded as they are."""
    import jax.numpy as jnp
    n_shards = int(np.prod([mesh.shape[a] for a in row_axes]))
    fn = _program(mesh, tuple(row_axes), x.shape[0] // n_shards, x.shape[1],
                  int(classes))
    return fn(x, datagen.base_key(data_seed), jnp.float32(noise))
