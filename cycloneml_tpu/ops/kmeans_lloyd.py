"""One Lloyd step of k-means over a shard: nearest centre of every row and
the per-cluster sums, counts and cost, with no ``(rows, k)`` value anywhere.

Two forms of one map ``(x, w, centres) -> {sums (k, d), counts (k,), cost}``:

- :func:`fused_lloyd_step`, the Mosaic kernel ``kmeans_lloyd``: row-major
  ``(row_tile, d)`` blocks of a bfloat16 X as it is stored (nothing padded or
  copied; the rows of the last tile past n are selected out in that grid
  step alone), the centres resident in VMEM. Per tile the scores
  ``‖c‖² − 2 x·c`` of all centres on the MXU — the float32 centres ride as
  three bfloat16 pieces whose sum is the centre to its last bit, side by side
  on the contraction axis, so bf16 x bf16 products are exact and the MXU's
  own f32 accumulator adds the pieces — the argmin down the sublanes on the
  VPU (centres on the sublanes, rows on the lanes: the LOWEST index wins a
  tie, MLlib's ``findClosest``; a padded centre carries ``‖c‖² = inf`` and
  never wins), then ``onehot · x_tile`` on the MXU (0/1 times bf16: exact),
  the counts and ``Σ min d²``, Kahan-added into f32 accumulators across the
  sequential grid as the GLM sweeps' sums are. The ``(k_pad, row_tile)``
  score tile never leaves VMEM and X is read once a step at storage width.
  The STEP pays for the third piece only near a tie: every tile is scored
  from the first two pieces (contraction depth ``2·d`` for ``3·d``), and
  the rows whose two best centres lie within what the third piece can move
  are scored again from all three, 128 rows at a time — three MXU passes a
  step (two for the screen, one for the update) plus the re-checks, for the
  unscreened step's four, and the same ``sums`` and ``counts`` to the bit
  (the ``cost`` to two float32 roundings: it is summed in another order).
  The assignment-only pass needs values, not an argmin: all three pieces.
- :func:`blocked_lloyd_step`, the row-blocked XLA twin: a ``lax.scan`` over
  row chunks with the same scores (three pieces on a bfloat16 X, ``highest``
  at the accumulator's width on every other storage) — the host platform's
  path, and the path of every shape the kernel refuses.

:func:`lloyd_step` picks: the kernel where :func:`lloyd_tile` finds a tile
and ``fused`` says the backend lowers Mosaic, and INSIDE the program the
weights decide — the kernel's one-hot product is exact only under a 0/1
mask, so a shard whose weights hold ONE live value ``c`` (every weight 0 or
c: unit weights, padding rows, a constant weight column) takes the kernel
under ``w > 0`` and scales by c; a second live value takes the twin.

The stated precision of both: the assignments are those of float32 scores
from the stored rows to float32 centres. Rounding the centres to one bf16
piece is a different result (``pieces=1`` exists for the tests that show it).
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cycloneml_tpu.ops.kernels import (
    _NN, _NT, _VMEM_BUDGET, LANE, _compiler_params, _kahan_add, _lane_sums,
    _pad_to, _split3_rounded,
)

#: centres ride the sublanes in whole packed bf16 groups
CENTRE_GROUP = 16
#: bf16 pieces a float32 centre is split into (8 + 8 + 8 mantissa bits)
CENTRE_PIECES = 3
#: pieces the step's screen scores every tile with
SCREEN_PIECES = 2
#: float32 ulps (2⁻²³) of ``max(‖x‖², |best|)`` the screen leaves, PER 128
#: COLUMNS OF X, for the rounding of the two products' f32 accumulation.
#: Read on the v5e at d = 128 alone (contraction depths 256 and 384), from
#: the cell's rows (PR 40; 3 x 16,384 rows x 1,000 centres at the centres of
#: steps 0, 2 and 19, two row orders): the largest |kernel score − float64
#: score| read 2.65 such ulps (8.7e-5 at ‖x‖² 146–425) for three pieces and
#: 2.58 for two, and the largest |screen − full + x·lo| 1.59; 48 is 16 x
#: the first, rounded up — the argument needs 4 x (:func:`fused_lloyd_step`).
#: An accumulation's rounding grows at most with its depth, so the kernel
#: takes ``SCREEN_ULPS · d / 128``: wider rows were not measured, and the
#: screen's bit-equality with the unscreened step is shown at d = 128 only
SCREEN_ULPS = 48
#: share of a step's screened 128-row groups past which the unscreened step
#: is the faster: on the v5e, on the cell's rows, the screened step reads
#: 131 ms + 2.0 ms a per cent of its groups re-checked (3 % to 100 %) and
#: the unscreened one 156.3 (PR 40: PERF.md §6). A fit that reads more on
#: two steps running goes on unscreened (``KMeans._fit_dataset``)
SCREEN_BREAK_EVEN = 0.12
#: f32 bytes of scores one chunk of the XLA twin may hold
TWIN_CHUNK_BYTES = 64 << 20


def lloyd_tile(rows: int, d: int, k: int, dtype) -> Optional[int]:
    """Rows of X one grid step of :func:`fused_lloyd_step` takes, or None
    where the kernel cannot be built and the row-blocked twin is the path:
    storage other than bfloat16 (an f32 X would need pieces of its own, fp8
    its scale), a width that does not end on a lane (d % 128: a row-major
    block would be padded), fewer than 128 rows, or a ``(k, d)`` whose
    resident pieces, accumulators and score tile pass the VMEM budget
    (k = 1,000 at d = 128 takes a 1,024-row tile, k = 2,048 a 256-row one,
    k = 4,096 none)."""
    if np.dtype(dtype) != np.dtype(jnp.bfloat16) or rows < LANE or k < 1 \
            or d % LANE:
        return None
    kp = _pad_to(k, CENTRE_GROUP)
    # resident: the pieces (double-buffered), the sums, their compensation
    # and the pipeline's second output buffer, the lane-wise counts likewise
    fixed = kp * d * (2 * CENTRE_PIECES * 2 + 3 * 4) + kp * LANE * 3 * 4
    # per row of X: the double-buffered storage block, its three-fold copy
    # on the contraction axis, the f32 squares and their transpose, the
    # re-check's best and winner; per centre the screen's scores, the select
    # of the classes within the slack, the f32 and bf16 one-hot (a group's
    # re-check holds its full scores and their select, an eighth of a
    # tile's, where the one-hot's tiles will be: the screen's scores stay
    # for the cost)
    per_row = d * (2 * 2 + CENTRE_PIECES * 2 + 3 * 4) + 2 * 4 \
        + kp * (4 + 4 + 4 + 2)
    for t in (1024, 512, 256, 128):
        if t <= rows and fixed + t * per_row <= _VMEM_BUDGET:
            return t
    return None


def _note(k: int, **attrs) -> None:
    """``kernel.kmeans_lloyd`` instant, one per program BUILT (this runs
    while the aggregation program is traced, not per dispatch)."""
    from cycloneml_tpu.observe import tracing
    tracing.instant("kernel.kmeans_lloyd", k=k, **attrs)


def _centre_operands(centres, kp: int, pieces: int):
    """``(pieces (kp, pieces·d) bf16 of −2c, ‖c‖² (kp, 1) f32, L (1, 1)
    f32)`` of float32 centres padded to ``kp`` rows; a padded centre's norm
    is inf. ``L = max_j ‖lo_j‖₂`` over the THIRD pieces of ``−2c``: by
    Cauchy–Schwarz ``|x·lo_j| ≤ ‖x‖·L`` for every row and centre, which is
    what a score that leaves the third piece out can be off by (0 where
    the centres are bf16-exact)."""
    c = jnp.asarray(centres, jnp.float32)
    k, _ = c.shape
    cn = jnp.concatenate([jnp.sum(c * c, axis=1),
                          jnp.full((kp - k,), jnp.inf, jnp.float32)])
    # −2c is c's own bits with another exponent: its pieces are exact too
    split = _split3_rounded(jnp.pad(-2.0 * c, ((0, kp - k), (0, 0))))
    lo = split[2].astype(jnp.float32)
    bound = jnp.sqrt(jnp.max(jnp.sum(lo * lo, axis=1)))
    return (jnp.concatenate(split[:pieces], axis=1), cn.reshape(kp, 1),
            bound.reshape(1, 1))


def fused_lloyd_step(x, w, centres, *, update: bool = True,
                     interpret: bool = False, tile: Optional[int] = None,
                     pieces: int = CENTRE_PIECES,
                     screen: bool = True) -> Dict[str, jnp.ndarray]:
    """``{sums (k, d), counts (k,), cost, screened_groups,
    rechecked_groups}`` of the rows with ``w > 0`` — each counted ONCE,
    whatever its weight: the caller scales by the one live value
    (:func:`lloyd_step`). ``update=False`` is
    the assignment-only pass (``cost`` alone: no one-hot, no second product,
    all three pieces; the kernel is then named ``kmeans_lloyd_cost``).
    ``tile`` overrides the rows a grid step takes, ``pieces`` the bf16
    pieces of a centre and ``screen=False`` gives the step that scores
    every tile with all of them (tests, ``chip_smoke.py``, and a fit whose
    re-checks pass :data:`SCREEN_BREAK_EVEN`).

    The screen (the step at three pieces). A tile's scores are first taken
    from the hi and mid pieces alone: the first ``2·d`` columns of the
    operands. Row i is DECIDED where its best screen score beats every
    other centre's by more than

        t_i = 2·sqrt(‖x_i‖²)·L + (SCREEN_ULPS·d/128)·2⁻²³·max(‖x_i‖², |best_i|).

    The dropped term moves no score by more than ``‖x_i‖·L``
    (:func:`_centre_operands`), so the first part is what it can move a gap
    of two; the second is the room for rounding: with every computed score
    within e of its exact value, a decided row's best computed FULL score
    still lies under every other by ``t_i − 2‖x_i‖L − 4e`` (:data:`SCREEN_ULPS`
    leaves 16 e at d = 128, the one width e was read at), so its argmin is
    the unscreened step's. Undecided rows are scored again from all three
    pieces by the unscreened step's own product, in 128-row lane groups (a
    column of the MXU product is that row's and no other's), and take their
    best and lowest-index argmin from those: ``sums`` and ``counts`` are the
    unscreened step's bits (on the v5e, PR 40: at every step of the cell's
    fit on three row orders). ``cost`` stays float32-faithful but is not
    the unscreened step's bits: every winner enters with its SCREEN score
    and the tile adds the dropped term of them all,
    ``Σ_j lo_j · (the tile's sums)_j`` — another order of the same float32
    sum, so the two costs differ by up to two roundings of the total
    (2.4e-7 of it; both lie as near the float64 cost). ``screened_groups``
    counts the 128-row groups the screen scored, ``rechecked_groups`` those
    of them scored again (both 0 where nothing screens). The one tile that
    holds rows past n is scored unscreened and counts in neither.

    What it costs follows the data: well-separated rows re-check nothing
    (the cell: 0.6–3.3 % of the groups a step, a step of 132 ms for the
    unscreened 154); a re-checked group costs a quarter of a tile's full
    scores, so rows full of near-ties — duplicate centres, data on a
    lattice scored from centres that are data points — lose: past
    :data:`SCREEN_BREAK_EVEN` of the groups the unscreened step is the
    faster, and where every group is re-checked the step reads 330 ms for
    156 (PERF.md §6). The kernel has one re-check form and no guard of its
    own; the fit, which reads the share every step, has the guard."""
    n, d = x.shape
    k = centres.shape[0]
    if tile is None:
        tile = lloyd_tile(n, d, k, x.dtype)
    if tile is None:
        raise ValueError(
            f"no Lloyd kernel for a {x.dtype} X of {n} x {d}, {k} centres: "
            f"ask lloyd_tile first and take blocked_lloyd_step")
    kp = _pad_to(k, CENTRE_GROUP)
    steps, tail = pl.cdiv(n, tile), n % tile
    # the flag reads a sum of class indices: exact in f32 while kp² ≤ 2²⁴
    screen = screen and update and pieces == CENTRE_PIECES \
        and kp * kp <= 1 << 24
    p, cn, bound = _centre_operands(centres, kp, pieces)
    ulps = SCREEN_ULPS * (d // LANE)
    _note(k, k_pad=kp, pieces=pieces, row_tile=tile, tail_rows=tail,
          orientation="row_major", update="onehot" if update else "none",
          screen_pieces=SCREEN_PIECES if screen else None)

    shapes = [(1, LANE)] + ([(kp, d), (kp, LANE)] if update else [])

    def kmeans_lloyd(x_ref, w_ref, p_ref, cn_ref, *refs):
        if screen:
            # the bound after the inputs, the re-checks' count after the
            # sums, their best and winner after the sums' compensations
            bound_ref, *refs, best_ref, first_ref = refs
            redone_ref = refs.pop(len(refs) // 2)
        i = pl.program_id(0)
        half = len(refs) // 2
        sums = tuple(zip(refs[:half], refs[half:]))

        @pl.when(i == 0)
        def _():
            for ref in (*refs, *([redone_ref] if screen else [])):
                ref[:] = jnp.zeros_like(ref)

        def tile_sums(rows_left=None):
            # the one tile with rows past n takes the unscreened form: a
            # second copy of the screen's branches made the step's code
            # 3.5 MB for 2.6, and half of all processes then ran it with
            # 3–4 ms more between two steps (PERF.md §6, PR 40)
            screening = screen and rows_left is None
            xv = x_ref[:]
            live = w_ref[:] > 0                               # (1, tile)
            if rows_left is not None:
                # Pallas leaves the rows past n undefined, and 0 · NaN is
                # NaN: they go out of X itself and out of every sum
                rows = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
                xv = jnp.where(rows < rows_left, xv,
                               jnp.zeros((), xv.dtype))
                live &= jax.lax.broadcasted_iota(
                    jnp.int32, (1, tile), 1) < rows_left
            xs = jnp.concatenate([xv] * pieces, axis=1)
            xf = xv.astype(jnp.float32)
            x2 = jnp.sum((xf * xf).T, axis=0, keepdims=True)  # (1, tile)

            # every column the class indices
            klass = jax.lax.broadcasted_iota(
                jnp.int32, (kp, tile), 0).astype(jnp.float32) \
                if update else None

            def classes(rows):
                """``klass`` at ``rows`` columns (made, not sliced: Mosaic
                refuses a lane slice of an iota)."""
                return klass if rows == tile else jax.lax.broadcasted_iota(
                    jnp.int32, (kp, rows), 0).astype(jnp.float32)

            def scores(depth, rows=slice(None)):
                """``(kp, rows)`` scores from the first ``depth`` columns of
                the operands."""
                return cn_ref[:] + jax.lax.dot_general(
                    p_ref[:, :depth], xs[rows, :depth], _NT,
                    preferred_element_type=jnp.float32)

            def lowest(mask):
                """The lowest class ``mask`` holds, ``(1, rows)``, and the
                masked classes themselves (``kp`` outside the mask)."""
                held = jnp.where(mask, classes(mask.shape[1]), float(kp))
                return jnp.min(held, axis=0, keepdims=True), held

            if screening:
                score = scores(SCREEN_PIECES * d)
                best = jnp.min(score, axis=0, keepdims=True)
                slack = 2.0 * jnp.sqrt(x2) * bound_ref[0, 0] \
                    + ulps * 2.0 ** -23 * jnp.maximum(x2, jnp.abs(best))
                first, held = lowest(score <= best + slack)
                # one class within the slack: its sum is first + (kp − 1)·kp
                undecided = jnp.where(
                    live & (jnp.sum(held, axis=0, keepdims=True)
                            != first + float((kp - 1) * kp)), 1.0, 0.0)

                def recheck(rows):
                    """The three-piece winners of ``rows`` and THEIR screen
                    scores (the cost adds the dropped term of every winner
                    alike)."""
                    full = scores(pieces * d, rows)
                    winner, _ = lowest(full == jnp.min(
                        full, axis=0, keepdims=True))
                    best_ref[:, rows] = jnp.min(
                        jnp.where(classes(LANE) == winner, score[:, rows],
                                  jnp.inf), axis=0, keepdims=True)
                    first_ref[:, rows] = winner
                    redone_ref[:] += 1.0

                best_ref[:] = best
                first_ref[:] = first

                @pl.when(jnp.max(undecided) > 0.0)
                def _():
                    # every group's flag before the first branch: read one
                    # by one between the branches they cost 1–5 ms a step
                    groups = [slice(g, g + LANE)
                              for g in range(0, tile, LANE)]
                    asked = [jnp.max(undecided[:, rows]) > 0.0
                             for rows in groups]
                    for rows, a in zip(groups, asked):
                        pl.when(a)(lambda rows=rows: recheck(rows))

                best, first = best_ref[:], first_ref[:]
            else:
                score = scores(pieces * d)
                best = jnp.min(score, axis=0, keepdims=True)
                if update:
                    first, _ = lowest(score == best)
            v_cost = jnp.where(live, jnp.maximum(x2 + best, 0.0), 0.0)
            values = [_lane_sums(v_cost, tile)]
            if update:
                hit = jnp.where(klass == jnp.where(live, first, float(kp)),
                                1.0, 0.0)
                values += [jax.lax.dot_general(
                    hit.astype(jnp.bfloat16), xv, _NN,
                    preferred_element_type=jnp.float32),
                    _lane_sums(hit, tile)]
            if screening:
                # a screen score lacks x·lo of its centre: over the tile's
                # winners that is Σ_j lo_j · (the tile's sums)_j
                lo = p_ref[:, SCREEN_PIECES * d:].astype(jnp.float32)
                values[0] += _lane_sums(jnp.sum(
                    lo * values[1], axis=0, keepdims=True), d)
            for (acc, comp), v in zip(sums, values):
                _kahan_add(acc, comp, v)

        if tail == 0:
            tile_sums()
        else:
            pl.when(i < steps - 1)(tile_sums)
            pl.when(i == steps - 1)(lambda: tile_sums(tail))

    outs = shapes + ([(1, LANE)] if screen else [])
    out = pl.pallas_call(
        kmeans_lloyd,
        name="kmeans_lloyd" if update else "kmeans_lloyd_cost",
        grid=(steps,),
        in_specs=[pl.BlockSpec((tile, d), lambda i: (i, 0)),
                  pl.BlockSpec((1, tile), lambda i: (0, i)),
                  pl.BlockSpec(p.shape, lambda i: (0, 0)),
                  pl.BlockSpec((kp, 1), lambda i: (0, 0))]
        + ([pl.BlockSpec(memory_space=pltpu.SMEM)] if screen else []),
        out_specs=[pl.BlockSpec(s, lambda i: (0, 0)) for s in outs],
        out_shape=[jax.ShapeDtypeStruct(s, jnp.float32) for s in outs],
        scratch_shapes=[pltpu.VMEM(s, jnp.float32) for s in shapes]
        + ([pltpu.VMEM((1, tile), jnp.float32)] * 2 if screen else []),
        compiler_params=_compiler_params("arbitrary"),
        interpret=interpret,
    )(x, jnp.asarray(w, jnp.float32).reshape(1, n), p, cn,
      *([bound] if screen else []))
    result = {"cost": jnp.sum(out[0])}
    if update:
        # the tile with rows past n never screens
        ran = (n // tile) * (tile // LANE) if screen else 0
        result.update(sums=out[1][:k], counts=jnp.sum(out[2][:k], axis=1),
                      screened_groups=jnp.full((), ran, jnp.float32),
                      rechecked_groups=out[3][0, 0] if screen
                      else jnp.zeros((), jnp.float32))
    return result


def _scores(xb, centres, operands):
    """``‖c‖² − 2 x·c`` of a row chunk, ``(b, k)`` at the accumulator's
    width: the kernel's own product of bf16 pieces on a bfloat16 chunk
    (``operands``: :func:`_centre_operands`, made once a step), ``highest``
    on every other storage."""
    if operands is not None:
        p, cn, _ = operands
        return cn.T + jax.lax.dot_general(
            jnp.concatenate([xb] * (p.shape[1] // xb.shape[1]), axis=1), p,
            _NT, preferred_element_type=jnp.float32)
    return jnp.sum(centres * centres, axis=1)[None, :] - 2.0 * jnp.dot(
        xb.astype(centres.dtype), centres.T,
        precision=jax.lax.Precision.HIGHEST)


def blocked_lloyd_step(x, w, centres, *, update: bool = True,
                       pieces: int = CENTRE_PIECES,
                       chunk: Optional[int] = None) -> Dict[str, jnp.ndarray]:
    """The weighted ``{sums, counts, cost}`` by a scan over row chunks of at
    most ``chunk`` rows (default: :data:`TWIN_CHUNK_BYTES` of scores): the
    only ``(·, k)`` value is one chunk's. Any weights, any storage."""
    n, d = x.shape
    k = centres.shape[0]
    narrow = x.dtype == jnp.bfloat16
    acc = jnp.float32 if narrow else centres.dtype
    centres = centres.astype(acc)
    w = jnp.asarray(w, acc)
    if chunk is None:
        chunk = max(8, TWIN_CHUNK_BYTES // (4 * k) // 8 * 8)
    chunk = min(chunk, n)
    hi = jax.lax.Precision.HIGHEST
    operands = _centre_operands(centres, k, pieces) if narrow else None

    def chunk_sums(xb, wb):
        score = _scores(xb, centres, operands)
        xf = xb.astype(acc)
        best = jnp.min(score, axis=1)
        out = {"cost": jnp.sum(wb * jnp.maximum(
            jnp.sum(xf * xf, axis=1) + best, 0.0))}
        if update:
            # argmin takes the lowest index on a tie
            hit = jax.nn.one_hot(jnp.argmin(score, axis=1), k, dtype=acc) \
                * wb[:, None]
            out.update(sums=jnp.dot(hit.T, xf, precision=hi),
                       counts=jnp.sum(hit, axis=0))
        return out

    def body(carry, i):
        part = chunk_sums(
            jax.lax.dynamic_slice(x, (i * chunk, 0), (chunk, d)),
            jax.lax.dynamic_slice(w, (i * chunk,), (chunk,)))
        return jax.tree.map(jnp.add, carry, part), None

    # chunk <= n: at least one whole chunk, then the rows left over
    n_full = n // chunk
    zero = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(chunk_sums, x[:chunk], w[:chunk]))
    total, _ = jax.lax.scan(body, zero, jnp.arange(n_full))
    rest = chunk_sums(x[n_full * chunk:], w[n_full * chunk:]) \
        if n % chunk else None
    return total if rest is None else jax.tree.map(jnp.add, total, rest)


def lloyd_step(x, w, centres, *, fused: bool, update: bool = True,
               screen: bool = True,
               interpret: bool = False) -> Dict[str, jnp.ndarray]:
    """One shard's ``{sums, counts, cost, kernel_shards, screened_groups,
    rechecked_groups}`` (``update=False``: ``cost`` and ``kernel_shards``).
    ``fused`` (static) is the caller's word that the backend lowers Mosaic,
    ``screen`` (static) its choice of the kernel's step
    (:func:`fused_lloyd_step`); ``kernel_shards`` is 1.0 where this shard
    took the kernel, ``screened_groups`` the 128-row groups of the whole row
    tiles its screen then scored and ``rechecked_groups`` those of them it
    sent to all three pieces — the psum over shards adds each up."""
    n, d = x.shape
    k = centres.shape[0]
    tile = lloyd_tile(n, d, k, x.dtype) if fused else None
    none = jnp.zeros((), jnp.float32)
    counted = {"screened_groups": none, "rechecked_groups": none} \
        if update else {}
    if tile is None:
        _note(k, k_pad=k, row_tile=0, tail_rows=0, orientation="xla",
              pieces=CENTRE_PIECES if x.dtype == jnp.bfloat16 else None,
              update="onehot" if update else "none", screen_pieces=None)
        out = blocked_lloyd_step(x, w, centres, update=update)
        return {**out, **counted,
                "kernel_shards": jnp.zeros((), out["cost"].dtype)}
    w = jnp.asarray(w, jnp.float32)
    c = jnp.max(w)
    one_value = jnp.isfinite(c) & (c >= 0) & jnp.all((w == 0) | (w == c))

    def kernel():
        out = fused_lloyd_step(x, w, centres, update=update, tile=tile,
                               screen=screen, interpret=interpret)
        groups = {key: out.pop(key) for key in counted}
        # the mask form's sums times the one live value (1.0 · v is v)
        return {**jax.tree.map(lambda v: c * v, out), **groups}

    out = jax.lax.cond(
        one_value, kernel,
        lambda: {**blocked_lloyd_step(x, w, centres.astype(jnp.float32),
                                      update=update), **counted})
    return {**out, "kernel_shards": jnp.where(one_value, 1.0, 0.0)}
