"""Live-state motion for elastic reshapes.

The whole point of a reshape (vs. the crash-recovery rebuild) is that the
OLD mesh is still alive when the decision lands, so state moves through
memory instead of through a checkpoint file:

- :func:`host_bounce` pulls every device leaf of a pytree to host numpy
  in ONE batched ``jax.device_get`` (the JX001 discipline — no piecemeal
  per-leaf pulls, each its own blocking transfer). Host leaves pass through
  untouched, so bouncing an already-host-resident L-BFGS state is free.
- :func:`host_bounce_state` is the OptimState form: coefficients,
  gradient and the S/Y curvature rings come back as host float64 —
  exactly what ``optimizer.iterations(..., resume=state)`` re-places onto
  whatever mesh is active when it restarts. GSPMD resharding (Xu et al.,
  PAPERS.md) is why the re-place needs no per-shape surgery: the resumed
  program's sharding annotations re-distribute the replicated state onto
  the new topology at dispatch.

Dataset motion rides the existing decommission hop
(``StorageManager.migrate_device_to_host`` + lazy re-place): see
``MeshSupervisor.reshape``.
"""

from __future__ import annotations

from typing import Any, Optional

import numpy as np


def _is_device_leaf(leaf: Any) -> bool:
    import jax
    return isinstance(leaf, jax.Array)


def host_bounce(tree: Any) -> Any:
    """Pytree with every ``jax.Array`` leaf replaced by its host numpy
    value; one batched transfer for all device leaves, host leaves (and
    non-array leaves) returned as-is."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    device_idx = [i for i, lf in enumerate(leaves) if _is_device_leaf(lf)]
    if device_idx:
        pulled = jax.device_get([leaves[i] for i in device_idx])
        for i, v in zip(device_idx, pulled):
            leaves[i] = np.asarray(v)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def host_bounce_state(state: Optional[Any]) -> Optional[Any]:
    """OptimState (or None) with all device leaves bounced to host — the
    in-memory handoff captured BEFORE a reshape/drain tears the old mesh
    down. A pure-host state round-trips bitwise."""
    if state is None:
        return None
    from cycloneml_tpu.ml.optim.lbfgs import OptimState
    if isinstance(state, OptimState):
        return OptimState.from_pytree(host_bounce(state.to_pytree()))
    return host_bounce(state)
