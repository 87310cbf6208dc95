"""The normal-equation path of ``LinearRegression``: the moment pass
(``ops/kernels.moment_sums`` — the Pallas kernel in the interpreter here,
XLA's contraction on the host platform) against float64, and whole fits
through ``fit(InstanceDataset)`` against the benchmark's plain reference
(``perfbench/reference/linreg_ridge.py``, which imports nothing of the
program).

Tolerances. The products of stored-bf16 operands are exact and every sum
is f32 (the reference's too: f32 ``highest`` blocks, float64 from there),
so a model of 4,096 rows agrees to a few 1e-6 of its norm; 2e-5 leaves
room for the f32 sums' order and would still fail a bf16 accumulator or a
Gramian of rounded X (1e-3 and up).
"""

from unittest.mock import patch

import numpy as np
import pytest

from cycloneml_tpu.ops import kernels

COEF_RTOL = 2e-5


def _bf16(a):
    import jax.numpy as jnp
    return jnp.asarray(np.asarray(a, np.float32), jnp.bfloat16)


# -- the moment pass against float64 ------------------------------------------

def _moment_reference(x, y, w):
    x, y, w = (np.asarray(a, np.float64) for a in (x, y, w))
    return {"w_sum": w.sum(), "b_sum": (w * y).sum(),
            "bb_sum": (w * y * y).sum(), "a_sum": w @ x,
            "ab_sum": (w * y) @ x, "aa_sum": (x * w[:, None]).T @ x}


def _assert_moments(got, want, rtol):
    for k, v in want.items():
        scale = max(np.max(np.abs(v)), 1.0)
        np.testing.assert_allclose(np.asarray(got[k], np.float64), v,
                                   rtol=0, atol=rtol * scale, err_msg=k)


def _kernel_moments(x, y, w, feature_major, tile):
    with patch.object(kernels, "moment_gramian_tile", lambda *a: tile):
        return kernels.moment_sums(x, y, w, feature_major=feature_major,
                                   interpret=True)


@pytest.mark.parametrize("n,d,feature_major,tile", [
    (400, 32, True, 128),      # a tail of 16 rows, one block
    (1000, 528, True, 256),    # 16 + 528 rows: several blocks of the triangle
    (384, 128, False, 128),    # row-major tile, transposed in VMEM
    (900, 256, False, 256),    # row-major with a tail
])
def test_fused_moment_gramian(ctx, n, d, feature_major, tile):
    """The augmented Gramian [1|y|X]'W[1|y|X] of a bf16 X under a presence
    mask: every moment WeightedLeastSquares wants, against float64 over
    the stored values (products are exact; 3e-6 is the f32 sums' room)."""
    rng = np.random.RandomState(3)
    x = _bf16(rng.randn(n, d))
    y = rng.randn(n).astype(np.float32)
    w = (rng.rand(n) > 0.2).astype(np.float32)
    got = _kernel_moments(x, y, w, feature_major, tile)
    _assert_moments(got, _moment_reference(x, y, w), 3e-6)
    # symmetry is exact, not approximate: the lower half is the upper's
    g = np.asarray(got["aa_sum"])
    np.testing.assert_array_equal(g, g.T)


@pytest.mark.parametrize("feature_major", [True, False])
def test_fused_moment_gramian_weights_not_binary(ctx, feature_major):
    """Weights that are not 0/1 take the three-pass branch INSIDE the same
    program (the f32 product x·w as three bf16 pieces per VMEM block) and
    agree with float64 as the one-pass branch does; zero weights still
    drop their rows."""
    rng = np.random.RandomState(4)
    n, d = 640, 128
    x = _bf16(rng.randn(n, d))
    y = rng.randn(n).astype(np.float32)
    w = (0.25 + rng.rand(n)).astype(np.float32)
    w[::7] = 0.0
    got = _kernel_moments(x, y, w, feature_major, 128)
    _assert_moments(got, _moment_reference(x, y, w), 3e-6)


@pytest.mark.parametrize("feature_major", [True, False])
def test_moment_gramian_tail_rows_carry_their_weight(ctx, feature_major):
    """Rows that do not fill the last tile are counted (once) and the
    lanes past n are selected out: the tail rows carry 1000x the weight,
    so a dropped or a garbage row moves every sum."""
    rng = np.random.RandomState(5)
    n, d, tile = 128 * 3 + 37, 128, 128
    x = _bf16(rng.randn(n, d))
    y = rng.randn(n).astype(np.float32)
    w = np.ones(n, np.float32)
    w[-37:] = 1000.0
    got = _kernel_moments(x, y, w, feature_major, tile)
    _assert_moments(got, _moment_reference(x, y, w), 3e-6)


def test_moment_gramian_accumulation_bound(ctx):
    """The stated bound against float64, |err_ij| <= (T + 4) 2^-24
    sum_r w_r |z_ri z_rj|, over many grid steps (157 tiles of 128 rows) of
    same-sign products: the compensated sum stays at the in-tile error
    whatever the step count; the test holds a twentieth of the worst
    case."""
    rng = np.random.RandomState(6)
    n, d, tile = 20000, 16, 128
    x = _bf16(np.abs(rng.randn(n, d)) + 0.5)
    y = np.abs(rng.randn(n)).astype(np.float32)
    w = np.ones(n, np.float32)
    got = _kernel_moments(x, y, w, True, tile)
    want = _moment_reference(x, y, w)
    x64 = np.abs(np.asarray(x, np.float64))
    bound = (tile + 4) * 2.0 ** -24 * (x64.T @ x64)
    err = np.abs(np.asarray(got["aa_sum"], np.float64) - want["aa_sum"])
    assert np.all(err <= bound / 20), float(np.max(err / bound))
    assert abs(float(got["bb_sum"]) - want["bb_sum"]) <= \
        (tile + 4) * 2.0 ** -24 * want["bb_sum"] / 20


def test_moment_sums_reads_the_array_not_a_conf_key(ctx):
    """Where no kernel can be built — the host platform, f32 storage, a
    width that ends inside a packed sublane group, too few rows, a width
    past the VMEM the kernel declares — the moments are XLA's
    contraction; where one can, a tile comes back."""
    import jax.numpy as jnp
    tile = kernels.moment_gramian_tile
    assert tile(2_000_000, 2000, jnp.bfloat16, True) is not None
    assert tile(2_000_000, 1280, jnp.bfloat16, False) is not None
    assert tile(2_000_000, 2000, np.float32, True) is None
    assert tile(2_000_000, 200, jnp.bfloat16, True) is None    # 200 % 16
    assert tile(2_000_000, 2000, jnp.bfloat16, False) is None  # 2000 % 128
    assert tile(100, 2000, jnp.bfloat16, True) is None
    assert tile(2_000_000, 4096, jnp.bfloat16, False) is None  # VMEM
    rng = np.random.RandomState(7)
    x = _bf16(rng.randn(300, 28))
    y = rng.randn(300).astype(np.float32)
    w = np.ones(300, np.float32)
    got = kernels.moment_sums(x, y, w)          # no TPU here: XLA
    _assert_moments(got, _moment_reference(x, y, w), 3e-6)


# -- the pass count the weights ask for ---------------------------------------

def _block(x, y, w, feature_major, tile, weighted):
    """One form of the kernel, called directly: the packed upper block."""
    return np.asarray(kernels.fused_moment_gramian(
        x, y, w, feature_major=feature_major, lane_tile=tile,
        weighted=weighted, interpret=True))


def _entries(upper, d):
    """The moments that ARE entries of the block (the label's are sums of
    three): what ``moment_sums`` returns of them."""
    k = kernels.MOMENT_ROWS
    gram = upper[k:, k:]
    return {"w_sum": upper[0, 0], "a_sum": upper[0, k:],
            "aa_sum": np.triu(gram) + np.triu(gram, 1).T}


def _same_bits(got, want):
    for name, v in want.items():
        np.testing.assert_array_equal(
            np.asarray(got[name]).view(np.uint32),
            np.asarray(v, np.float32).view(np.uint32), err_msg=name)


def _one_value_case(feature_major, c, seed=8):
    """A ragged last tile (n % tile = 52), every seventh row weightless."""
    rng = np.random.RandomState(seed)
    d, tile = (48, 128) if feature_major else (128, 128)
    n = 3 * tile + 52
    x = _bf16(rng.randn(n, d))
    y = rng.randn(n).astype(np.float32)
    w = np.full(n, c, np.float32)
    w[::7] = 0.0
    return x, y, w, tile


@pytest.mark.parametrize("c", [0.1875, 0.3, 2.5, 1.0])
@pytest.mark.parametrize("feature_major", [True, False])
def test_one_value_weights_take_one_mxu_pass(ctx, feature_major, c):
    """Weights that are all 0 or ONE value c (a constant weight column, the
    first IRLS pass of a binomial-logit fit: 0.1875) take the presence-mask
    form and scale its block by c — bit for bit ``c x`` what the mask form
    returns, so one rounding an entry on top of its exact-product sums:
    inside the stated bound against float64 — and say so: ``mxu_passes``
    1. At c = 1 that is today's 0/1 branch to the bit."""
    x, y, w, tile = _one_value_case(feature_major, c)
    got = _kernel_moments(x, y, w, feature_major, tile)
    assert float(got["mxu_passes"]) == 1.0
    mask = _block(x, y, (w > 0).astype(np.float32), feature_major, tile,
                  weighted=False)
    _same_bits(got, _entries(np.float32(c) * mask, x.shape[1]))
    want = _moment_reference(x, y, w)
    _assert_moments(got, want, 3e-6)
    x64 = np.abs(np.asarray(x, np.float64)) * np.sqrt(np.asarray(w))[:, None]
    bound = (tile + 5) * 2.0 ** -24 * (x64.T @ x64)
    err = np.abs(np.asarray(got["aa_sum"], np.float64) - want["aa_sum"])
    assert np.all(err <= bound), float(np.max(err / bound))


@pytest.mark.parametrize("second", [0.25, float("nan"), float("inf"), -0.5])
@pytest.mark.parametrize("feature_major", [True, False])
def test_a_second_live_value_takes_three_mxu_passes(ctx, feature_major,
                                                    second):
    """One row with another weight — a second live value, a NaN, an
    infinity, a negative weight — and the shard takes the three-piece form:
    the bits ``fused_moment_gramian(weighted=True)`` returns, and
    ``mxu_passes`` 3."""
    x, y, w, tile = _one_value_case(feature_major, 0.1875, seed=9)
    w[5] = second
    got = _kernel_moments(x, y, w, feature_major, tile)
    assert float(got["mxu_passes"]) == 3.0
    _same_bits(got, _entries(
        _block(x, y, w, feature_major, tile, weighted=True), x.shape[1]))


def test_weightless_and_all_infinite_shards(ctx):
    """The rule's edges: a shard of padding alone (every weight 0: c = 0)
    is a mask and returns zeros in one pass; a shard whose every weight is
    infinite has one value and is NOT one pass (c must be finite)."""
    x, y, w, tile = _one_value_case(True, 1.0, seed=10)
    got = _kernel_moments(x, y, np.zeros_like(w), True, tile)
    assert float(got["mxu_passes"]) == 1.0
    assert float(got["w_sum"]) == 0.0 and not np.any(np.asarray(got["aa_sum"]))
    got = _kernel_moments(x, y, np.full_like(w, np.inf), True, tile)
    assert float(got["mxu_passes"]) == 3.0


def test_mean_mxu_passes_over_shards():
    """The host's reading of the psum'd count: 1 or 3 where the shards
    agree, their mean where they do not, None for XLA's contraction."""
    assert kernels.mean_mxu_passes({"mxu_passes": np.float32(8.0)}, 8) == 1
    assert kernels.mean_mxu_passes({"mxu_passes": np.float32(24.0)}, 8) == 3
    assert kernels.mean_mxu_passes({"mxu_passes": np.float32(10.0)}, 4) == 2.5
    assert kernels.mean_mxu_passes({"w_sum": 1.0}, 8) is None


# -- the weighted form against the body it replaced ---------------------------

def _parent_weighted_gramian(x, y, w, *, feature_major, lane_tile):
    """A straight transcription of the weighted branch as it stood before
    PR 38 (each row block split at the head of its own products, the
    pieces compiler temporaries): kept HERE, not in the package, as the
    proof that the package's body moved the schedule and nothing else."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    n, d = x.shape
    rows, block = kernels.MOMENT_ROWS, kernels.GRAM_BLOCK
    big = rows + d
    edges = list(range(0, big, block)) + [big]
    spans = list(zip(edges[:-1], edges[1:]))
    tile = lane_tile
    nt = (((1,), (1,)), ((), ()))

    def split3(v):
        hi = v.astype(jnp.bfloat16)
        rest = v - hi.astype(jnp.float32)
        mid = rest.astype(jnp.bfloat16)
        lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
        return hi, mid, lo

    def moment_gramian(x_ref, y_ref, w_ref, acc_ref, comp_ref, z_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            acc_ref[:] = jnp.zeros_like(acc_ref)
            comp_ref[:] = jnp.zeros_like(comp_ref)

        lane = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1) + i * tile
        valid = lane < n
        wv = jnp.where(valid, w_ref[:], 0.0)
        y_hi, y_mid, y_lo = (p.astype(jnp.float32) for p in split3(
            jnp.where(valid, y_ref[:], 0.0)))
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, tile), 0)
        moments = jnp.where(
            row == 0, valid.astype(jnp.float32),
            jnp.where(row == 1, y_hi, jnp.where(
                row == 2, y_mid, jnp.where(row == 3, y_lo, 0.0))))
        z_ref[0:rows, :] = moments.astype(jnp.bfloat16)
        xv = x_ref[:] if feature_major else x_ref[:].T
        z_ref[rows:big, :] = jnp.where(valid, xv, jnp.zeros((), xv.dtype))
        for a, (i0, i1) in enumerate(spans):
            left = split3(z_ref[i0:i1, :].astype(jnp.float32) * wv)
            for j0, j1 in spans[a:]:
                right = z_ref[j0:j1, :]
                v = sum(jax.lax.dot_general(
                    piece, right, nt, preferred_element_type=jnp.float32)
                    for piece in left)
                yk = v - comp_ref[i0:i1, j0:j1]
                t = acc_ref[i0:i1, j0:j1] + yk
                comp_ref[i0:i1, j0:j1] = (t - acc_ref[i0:i1, j0:j1]) - yk
                acc_ref[i0:i1, j0:j1] = t

    if feature_major:
        x_arg, x_spec = x.T, pl.BlockSpec((d, tile), lambda i: (0, i))
    else:
        x_arg, x_spec = x, pl.BlockSpec((tile, d), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, tile), lambda i: (0, i))
    return np.asarray(pl.pallas_call(
        moment_gramian, grid=(pl.cdiv(n, tile),),
        in_specs=[x_spec, vec_spec, vec_spec],
        out_specs=pl.BlockSpec((big, big), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((big, big), jnp.float32),
        scratch_shapes=[pltpu.VMEM((big, big), jnp.float32),
                        pltpu.VMEM((big, tile), jnp.bfloat16)],
        interpret=True,
    )(x_arg, jnp.asarray(y, jnp.float32).reshape(1, n),
      jnp.asarray(w, jnp.float32).reshape(1, n)))


def _weights(kind, rng, n):
    if kind == "logistic":      # mu (1 - mu) of a fit under way
        mu = 1.0 / (1.0 + np.exp(-1.5 * rng.randn(n)))
        return mu * (1.0 - mu)
    if kind == "signed":
        return rng.randn(n)
    if kind == "tiny":          # down to where the low pieces go subnormal
        return rng.rand(n) * 10.0 ** rng.uniform(-36, -20, n)
    return np.asarray(_bf16(0.25 + rng.rand(n)), np.float64)   # exact bf16


@pytest.mark.parametrize("kind", ["logistic", "signed", "tiny", "bf16"])
@pytest.mark.parametrize("feature_major", [True, False])
def test_weighted_form_is_the_parents_to_the_bit(ctx, feature_major, kind):
    """At one lane tile the three-piece form returns the parent's block
    bit for bit — the same pieces, products and order of accumulation:
    PR 38 moved the tile the form is GIVEN (``GRAM_WEIGHTED_TILE``), not
    what it does with one. Several blocks of the triangle, a ragged last
    tile, a last row block that is not whole."""
    rng = np.random.RandomState(11)
    d, tile = (272, 256) if feature_major else (256, 128)
    n = 2 * tile + 77
    x = _bf16(rng.randn(n, d))
    y = rng.randn(n).astype(np.float32)
    w = _weights(kind, rng, n).astype(np.float32)
    got = _block(x, y, w, feature_major, tile, weighted=True)
    want = _parent_weighted_gramian(x, y, w, feature_major=feature_major,
                                    lane_tile=tile)
    assert np.any(np.triu(want)[kernels.MOMENT_ROWS:] != 0)
    np.testing.assert_array_equal(np.triu(got).view(np.uint32),
                                  np.triu(want).view(np.uint32))


def test_each_form_gets_its_lane_tile(ctx):
    """The three-piece form runs at ``GRAM_WEIGHTED_TILE`` lanes (its
    three pieces of a row block then stay in the vector registers), the
    mask form at the tile the rule finds; a shard with fewer rows than
    either runs both at what it has."""
    seen = []
    native = kernels.fused_moment_gramian

    def spy(*a, **kw):
        seen.append((kw["weighted"], kw["lane_tile"]))
        return native(*a, **kw)

    rng = np.random.RandomState(12)
    n, d = 1100, 32
    x = _bf16(rng.randn(n, d))
    y = rng.randn(n).astype(np.float32)
    w = (0.25 + rng.rand(n)).astype(np.float32)
    with patch.object(kernels, "fused_moment_gramian", spy):
        got = _kernel_moments(x, y, w, True, 1024)
        _kernel_moments(x[:300], y[:300], w[:300], True, 128)
    assert sorted(seen) == [(False, 128), (False, 1024), (True, 128),
                            (True, kernels.GRAM_WEIGHTED_TILE)]
    assert kernels.GRAM_WEIGHTED_TILE == 256
    _assert_moments(got, _moment_reference(x, y, w), 3e-6)


def test_weighted_form_accumulation_bound(ctx):
    """The stated bound on the three-piece form at its own tile: ``|err_ij|
    <= (T + 4) 2^-24 sum_r w_r |z_ri z_rj|`` with T = 256, over 79 grid
    steps of same-sign products and weights like a logistic fit's; the
    test holds a twentieth of the worst case, as the mask form's does."""
    rng = np.random.RandomState(13)
    n, d, tile = 20000, 16, kernels.GRAM_WEIGHTED_TILE
    x = _bf16(np.abs(rng.randn(n, d)) + 0.5)
    y = np.abs(rng.randn(n)).astype(np.float32)
    mu = 1.0 / (1.0 + np.exp(-1.5 * rng.randn(n)))
    w = (mu * (1.0 - mu)).astype(np.float32)
    got = _kernel_moments(x, y, w, True, 1024)
    assert float(got["mxu_passes"]) == 3.0
    want = _moment_reference(x, y, w)
    x64 = np.abs(np.asarray(x, np.float64)) * np.sqrt(
        np.asarray(w, np.float64))[:, None]
    bound = (tile + 4) * 2.0 ** -24 * (x64.T @ x64)
    err = np.abs(np.asarray(got["aa_sum"], np.float64) - want["aa_sum"])
    assert np.all(err <= bound / 20), float(np.max(err / bound))


# -- whole fits against the plain reference ------------------------------------

ROW_AXES = ("replica", "data")


def _case(seed, n, d, constant_col=None):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, d)
    if constant_col is not None:
        x[:, constant_col] = 1.5
    beta = rng.randn(d) / np.sqrt(d)
    y = x @ beta + 0.5 * rng.randn(n) + 0.3
    return x, y


def _stored(x, dtype):
    """X rounded to its storage type, as float64: what both sides see."""
    import jax.numpy as jnp
    return np.asarray(jnp.asarray(x, jnp.dtype(dtype)), np.float64)


def _reference(ctx, x, y, params):
    """The plain reference on (replicated) rows placed on the mesh."""
    from perfbench.reference import linreg_ridge
    rt = ctx.mesh_runtime
    data = (rt.device_put_sharded_rows(x.astype(np.float32)),
            rt.device_put_sharded_rows(y.astype(np.float32)),
            rt.mesh, ROW_AXES)
    return linreg_ridge.fit(data, params)


def _fit(ctx, x, y, w, dtype, params):
    from cycloneml_tpu.dataset.dataset import InstanceDataset
    from cycloneml_tpu.ml.regression import LinearRegression
    import jax.numpy as jnp
    ds = InstanceDataset.from_numpy(ctx, x, y, w, dtype=jnp.dtype(dtype))
    return LinearRegression(**params).fit(ds), ds


def _gap(model, ref):
    got = np.append(np.asarray(model.coefficients, np.float64),
                    model.intercept)
    want = np.append(ref["coef"], ref["intercept"])
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


PARAMS = {"maxIter": 100, "regParam": 0.01, "elasticNetParam": 0.0}


@pytest.mark.parametrize("dtype,d", [("float32", 200), ("bfloat16", 200),
                                     ("float32", 28), ("bfloat16", 28),
                                     ("bfloat16", 256)])
def test_ridge_fit_equals_the_plain_reference(ctx, dtype, d):
    """``LinearRegression(regParam=0.01).fit(InstanceDataset)`` takes the
    normal equations and lands on the reference's closed-form optimum, at
    f32 and bf16 storage, at widths that are no multiple of 128 (200, 28)
    and one that is (256); the objective it reports is the reference's
    objective at the fit's own model."""
    x, y = _case(11, 4096, d)
    xs = _stored(x, dtype)
    y32 = y.astype(np.float32).astype(np.float64)
    model, _ = _fit(ctx, xs, y32, None, dtype, PARAMS)
    s = model.summary
    assert (s.solver, s.total_passes, s.total_dispatches) == ("normal", 1, 1)
    ref = _reference(ctx, xs, y32, PARAMS)
    assert _gap(model, ref) <= COEF_RTOL
    ours = ref["problem"].objective_of(
        np.asarray(model.coefficients)[None], np.array([model.intercept]))[0]
    assert s.objective_history[-1] == pytest.approx(ours, rel=1e-6)
    assert s.objective_history[-1] == pytest.approx(ref["objective"],
                                                    rel=1e-6)


@pytest.mark.parametrize("path", ["feature_major", "row_major"])
def test_ridge_fit_on_the_kernel_equals_the_plain_reference(ctx, monkeypatch,
                                                            path):
    """The same fit with the moment pass on the Pallas kernel (the test
    routes its pallas_call through the interpreter and says what the chip
    would: Mosaic lowers, X is stored this way): both tilings, rows that
    do not fill the last tile (4,096 rows over 8 shards = 512 a shard, the
    tile 384), one dispatch a fit."""
    from cycloneml_tpu.parallel import collectives
    d = 48 if path == "feature_major" else 128
    x, y = _case(12, 4096, d)
    xs = _stored(x, "bfloat16")
    y32 = y.astype(np.float32).astype(np.float64)
    native_call = kernels.pl.pallas_call
    monkeypatch.setattr(
        kernels.pl, "pallas_call",
        lambda *a, **kw: native_call(*a, **{**kw, "interpret": True}))
    monkeypatch.setattr(kernels, "pallas_available", lambda: True)
    monkeypatch.setattr(kernels, "stored_feature_major",
                        lambda a: path == "feature_major")
    monkeypatch.setattr(kernels, "moment_gramian_tile", lambda *a: 384)
    collectives.clear_program_cache()
    try:
        model, _ = _fit(ctx, xs, y32, None, "bfloat16", PARAMS)
    finally:
        collectives.clear_program_cache()
    ref = _reference(ctx, xs, y32, PARAMS)
    assert _gap(model, ref) <= COEF_RTOL
    assert model.summary.total_dispatches == 1


def test_ridge_fit_with_weights_equals_the_reference_on_repeated_rows(ctx):
    """Weights that are not 0/1, and zero-weight rows among them: a row of
    weight k is the reference's row k times, a row of weight 0 is not in
    the reference's data at all (the dataset's own padding rows are such
    rows too: 4,090 rows over 8 shards)."""
    x, y = _case(13, 4090, 40)
    rng = np.random.RandomState(14)
    w = rng.randint(0, 4, size=4090).astype(np.float64)
    w[-2:] = 3.0
    w[: (8 - int(w.sum()) % 8) % 8] += 1.0     # rows the shards divide
    xs = _stored(x, "bfloat16")
    y32 = y.astype(np.float32).astype(np.float64)
    model, _ = _fit(ctx, xs, y32, w, "bfloat16", PARAMS)
    times = w.astype(int)
    ref = _reference(ctx, np.repeat(xs, times, axis=0),
                     np.repeat(y32, times), PARAMS)
    assert _gap(model, ref) <= COEF_RTOL


def test_ridge_fit_with_a_constant_column(ctx):
    """A column that does not vary gets the coefficient 0 on both sides
    (its standardised column is zero) and the rest agree."""
    x, y = _case(15, 4096, 24, constant_col=5)
    xs = _stored(x, "bfloat16")
    y32 = y.astype(np.float32).astype(np.float64)
    model, _ = _fit(ctx, xs, y32, None, "bfloat16", PARAMS)
    ref = _reference(ctx, xs, y32, PARAMS)
    assert model.coefficients[5] == 0.0 and ref["coef"][5] == 0.0
    assert _gap(model, ref) <= COEF_RTOL


def test_every_fit_pays_its_pass(ctx):
    """Three fits of one dataset in a row: each dispatches the moment
    program once (the context's step counter and the summary agree), no
    fit is handed another's Gramian, and the program is built once."""
    from cycloneml_tpu.ml.regression import LinearRegression
    from cycloneml_tpu.parallel import collectives
    x, y = _case(16, 2048, 20)
    model, ds = _fit(ctx, x, y, None, "float32", PARAMS)
    counter = ctx.metrics.registry.counter("steps.completed")
    programs = len(collectives._program_cache)
    for reg in (0.01, 0.01, 0.5):
        before = counter.count
        m = LinearRegression(regParam=reg).fit(ds)
        assert counter.count - before == 1
        s = m.summary
        assert (s.solver, s.total_passes, s.total_dispatches,
                s.total_evals) == ("normal", 1, 1, None)
    assert len(collectives._program_cache) == programs
    # another regParam moved the model: the moments were used, not a model
    assert np.linalg.norm(np.asarray(m.coefficients)
                          - np.asarray(model.coefficients)) > 1e-3


def test_arrays_and_dataset_take_the_same_pass(ctx):
    """``WeightedLeastSquares.fit(x, y, w)`` on bare arrays (the small
    callers) and ``fit(dataset)`` solve the same system."""
    from cycloneml_tpu.dataset.dataset import InstanceDataset
    from cycloneml_tpu.ml.optim.wls import WeightedLeastSquares
    x, y = _case(17, 1000, 12)
    w = np.random.RandomState(18).rand(1000) + 0.5
    ds = InstanceDataset.from_numpy(ctx, x, y, w, dtype=np.float64)
    a = WeightedLeastSquares(True, reg_param=0.1).fit(x, y, w)
    b = WeightedLeastSquares(True, reg_param=0.1).fit(ds)
    np.testing.assert_allclose(a.coefficients, b.coefficients, rtol=1e-9)
    assert a.intercept == pytest.approx(b.intercept, rel=1e-9)
    assert a.objective_history == pytest.approx(b.objective_history)
    np.testing.assert_allclose(a.diag_inv_atwa, b.diag_inv_atwa, rtol=1e-7)


# -- which system the driver factors -------------------------------------------

def _f32_moments(x, y, w):
    """The six sums as the device delivers them: float32 arrays."""
    return {k: np.asarray(v, np.float32)
            for k, v in _moment_reference(x, y, w).items()}


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want) / np.abs(want)))


@pytest.mark.parametrize("unit_weights", [True, False])
@pytest.mark.parametrize("reg_param", [0.0, 0.01])
@pytest.mark.parametrize("standardize_label", [True, False])
@pytest.mark.parametrize("standardize_features", [True, False])
@pytest.mark.parametrize("fit_intercept", [True, False])
def test_the_moment_block_solves_the_standardised_system(
        fit_intercept, standardize_features, standardize_label, reg_param,
        unit_weights):
    """``solve`` factors the moment block it was handed (one widening
    copy, the standardisation on O(d) vectors) and returns what the
    standardised (d+1)² block returns — coefficients, intercept, the
    standardised quadratic it reports, and the inverse's diagonal that a
    GLR fit's standard errors are (``standardize_* = False`` is IRLS's
    setting) — on the same float32 moments: the congruence is exact, so
    1e-10 is the float64 solves' room (1e-13 observed), not a tolerance
    of the method. Columns of unlike scale and offset, so a dropped
    ``a_std`` or a misplaced ``w_sum`` shows."""
    from cycloneml_tpu.ml.optim.wls import WeightedLeastSquares
    rng = np.random.RandomState(21)
    n, d = 3000, 12
    x = rng.randn(n, d) * rng.uniform(0.2, 5.0, d) + rng.randn(d)
    y = x @ rng.randn(d) / np.sqrt(d) + 0.5 * rng.randn(n) + 0.3
    w = np.ones(n) if unit_weights else rng.uniform(0.5, 2.0, n)
    m = _f32_moments(x, y, w)
    wls = WeightedLeastSquares(
        fit_intercept, reg_param=reg_param,
        standardize_features=standardize_features,
        standardize_label=standardize_label)
    got, want = wls.solve(m, d), wls.solve(m, d, _standardise=True)
    assert (got.system, want.system) == ("moments", "standardised")
    assert _rel(got.coefficients, want.coefficients) <= 1e-10
    assert got.intercept == pytest.approx(want.intercept, rel=1e-10)
    assert (got.intercept != 0.0) == fit_intercept
    assert _rel(got.objective_history, want.objective_history) <= 1e-10
    assert got.diag_inv_atwa.shape == (d + fit_intercept,)
    assert _rel(got.diag_inv_atwa, want.diag_inv_atwa) <= 1e-10


def _singular_case():
    """Two identical ±1 columns over 64 unit-weight rows: every entry of
    the moment block is a small integer and its first pivot a perfect
    square (64), so the second pivot is 0 EXACTLY and ``potrf`` fails on
    either form, whatever LAPACK's blocking."""
    rng = np.random.RandomState(22)
    x = np.where(rng.rand(64, 3) > 0.5, 1.0, -1.0)
    x[:, 0] = np.tile([1.0, -1.0], 32)
    x[:, 1] = x[:, 0]
    y = x[:, 0] + 0.5 * x[:, 2] + 0.25 * rng.randn(64)
    return x, y, np.ones(64)


@pytest.mark.parametrize("form", ["constant_column", "elastic_net",
                                  "singular_fallback"])
def test_the_forms_that_keep_the_standardised_block(form):
    """Where standardised coordinates are needed the O(d²) rescaling is
    still built, and the answer is the one the standardised block gives:
    a column that does not vary (``E`` is singular; coefficient exactly
    0), the quasi-Newton solver (L1 is not scale-invariant), and auto's
    fallback from a ``potrf`` that failed on the moment block."""
    from cycloneml_tpu.ml.optim.wls import AUTO, WeightedLeastSquares
    kw = dict(fit_intercept=True, reg_param=0.01, solver_type=AUTO,
              max_iter=200, tol=1e-10)
    if form == "singular_fallback":
        x, y, w = _singular_case()
        kw["reg_param"] = 0.0
    else:
        x, y = _case(23, 2000, 8,
                     constant_col=5 if form == "constant_column" else None)
        w = np.random.RandomState(24).uniform(0.5, 2.0, 2000)
    if form == "elastic_net":
        kw["elastic_net_param"] = 0.5
    m = _moment_reference(x, y, w)
    wls = WeightedLeastSquares(**kw)
    got, want = wls.solve(m, x.shape[1]), \
        wls.solve(m, x.shape[1], _standardise=True)
    assert got.system == want.system == "standardised"
    # the same block through the same solver: not close, equal
    np.testing.assert_array_equal(got.coefficients, want.coefficients)
    assert got.intercept == want.intercept
    assert got.objective_history == want.objective_history
    if form == "constant_column":
        assert got.coefficients[5] == 0.0
        assert np.isinf(got.diag_inv_atwa[5])
    elif form == "elastic_net":
        assert len(got.objective_history) > 1        # OWL-QN iterated
    else:
        # quasi-Newton from zero splits the twin columns' weight evenly
        assert len(got.objective_history) > 1
        assert got.coefficients[0] == pytest.approx(got.coefficients[1],
                                                    rel=1e-8)
        fitted = x @ got.coefficients + got.intercept
        ref = np.linalg.lstsq(np.c_[x[:, [0, 2]], np.ones(64)], y,
                              rcond=None)[0]
        np.testing.assert_allclose(
            fitted, np.c_[x[:, [0, 2]], np.ones(64)] @ ref, atol=1e-6)


def test_a_failed_potrf_restores_the_block():
    """``_cholesky`` works in place; when ``potrf`` stops (here at the
    second pivot, on the moment block of the singular case) it puts the
    matrix back before raising, and a forced Cholesky solver raises where
    auto falls back."""
    from cycloneml_tpu.ml.optim.wls import (CHOLESKY, WeightedLeastSquares,
                                            _normal_block)
    x, y, w = _singular_case()
    m = _moment_reference(x, y, w)
    block = _normal_block(m["aa_sum"], np.zeros(3), m["a_sum"], m["w_sum"])
    assert block.flags.f_contiguous and block.dtype == np.float64
    before = block.copy()
    wls = WeightedLeastSquares(True, solver_type=CHOLESKY)
    with pytest.raises(np.linalg.LinAlgError, match="potrf info=2"):
        wls._cholesky(block, np.append(m["ab_sum"], m["b_sum"]), 1.0)
    np.testing.assert_array_equal(block, before)
    with pytest.raises(np.linalg.LinAlgError):
        wls.solve(m, 3)
