"""The GLM sweep's tiling against the layout XLA:TPU gives X, checked by
compiling for a described v5e (no chip attached: counts and sizes, never
times). All such compiles live in THIS file and describe the topology inside
a fixture: one pytest worker loads libtpu, and only when it runs these tests.
"""

import re

import numpy as np
import pytest


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


#: classes of the multinomial programs compiled here (the mnist8m cell's)
N_CLASSES = 10


def _aggregator(kind, d, feature_major):
    """(the scaled Pallas aggregator the estimators use, its extras)"""
    import jax.numpy as jnp
    from cycloneml_tpu.ml.optim import aggregators
    v = (d,), jnp.float32
    if kind == "logistic":
        return (aggregators.binary_logistic_pallas_scaled(
            d, True, feature_major=feature_major),
            [v, v, ((d + 1,), jnp.float32)])
    if kind == "multinomial":
        return (aggregators.multinomial_logistic_pallas_scaled(
            d, N_CLASSES, True, feature_major=feature_major),
            [v, v, ((d * N_CLASSES + N_CLASSES,), jnp.float32)])
    if kind == "stacked":
        return (aggregators.stacked_binary_logistic_pallas_scaled(
            d, N_CLASSES, True, feature_major=feature_major),
            [v, v, ((N_CLASSES, d + 1), jnp.float32)])
    return (aggregators.least_squares_pallas_scaled(
        d, feature_major=feature_major), [v, v, ((2,), jnp.float32), v])


def _compile(topo, kind, n, d, feature_major, n_chips=1):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    agg, extras = _aggregator(kind, d, feature_major)
    mesh = Mesh(np.array(topo.devices[:n_chips]), ("data",))
    rows, rep = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    args = [jax.ShapeDtypeStruct((n, d), jnp.bfloat16, sharding=rows),
            jax.ShapeDtypeStruct((n,), jnp.float32, sharding=rows),
            jax.ShapeDtypeStruct((n,), jnp.float32, sharding=rows)]
    args += [jax.ShapeDtypeStruct(s, t, sharding=rep) for s, t in extras]

    def program(*a):
        local = lambda *b: jax.tree_util.tree_map(
            lambda t: jax.lax.psum(t, "data"), agg(*b))
        return jax.shard_map(
            local, mesh=mesh, in_specs=(P("data"),) * 3 + (P(),) * len(extras),
            out_specs=P(), check_vma=False)(*a)

    # the chip runs without x64 (tests/conftest.py turns it on for the CPU
    # parity suites; Mosaic refuses the i64 block indices it would bring)
    with jax.enable_x64(False):
        return jax.jit(program).lower(*args).compile()


def _x_ops(text, n):
    """Instructions that write a bf16 array with X's row count: the lane
    pad and the layout copy are such."""
    return [line.strip() for line in text.splitlines()
            if re.search(rf"= bf16\[{n},\d+\]\S* (pad|copy)\(", line)]


def _entry_layout_of_x(text):
    return re.search(r"entry_computation_layout=\{\(bf16\[\d+,\d+\]\{([\d,]+)",
                     text).group(1)


@pytest.mark.parametrize("kind", ["logistic", "squared"])
def test_feature_major_program_makes_no_copy_of_x(topo, kind):
    """epsilon's width: X arrives ``{0,1}`` (rows on the lanes), and the
    feature-major program holds the Mosaic call and no pad or copy of X —
    the last tile's 64 rows are masked in the kernel, not padded."""
    n, d = 8192 * 4 + 64, 2000
    compiled = _compile(topo, kind, n, d, feature_major=True)
    text = compiled.as_text()
    assert _entry_layout_of_x(text) == "0,1"
    assert "tpu_custom_call" in text
    assert _x_ops(text, n) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_row_major_program_at_epsilon_width_is_what_the_selector_avoids(topo):
    """Same X, row-major tiling: the layout copy and the lane pad the
    feature-major tiling exists to avoid are both there."""
    n, d = 8192 * 4 + 64, 2000
    ops = _x_ops(_compile(topo, "logistic", n, d, False).as_text(), n)
    assert any(" pad(" in op for op in ops)
    assert any(" copy(" in op for op in ops)


@pytest.mark.parametrize("kind", ["logistic", "squared"])
def test_lane_aligned_width_stays_row_major(topo, kind):
    """d = 1,280: X arrives ``{1,0}``, the default orientation is row-major
    and that program has the Mosaic call and still no pad or copy of X."""
    from cycloneml_tpu.ops import kernels
    n, d = 8192 * 4, 1280
    assert d % kernels.LANE == 0      # default_feature_major's rule
    text = _compile(topo, kind, n, d, feature_major=False).as_text()
    assert _entry_layout_of_x(text) == "1,0"
    assert "tpu_custom_call" in text
    assert _x_ops(text, n) == []


def test_feature_major_program_on_four_chips(topo):
    """Under shard_map on the 2x2 host every chip's (n/4, 2000) shard is
    still read as it lies: the held-back x4 cell's program."""
    n, d = 4 * (8192 * 2 + 64), 2000
    compiled = _compile(topo, "logistic", n, d, True, n_chips=4)
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text
    assert _x_ops(text, n // 4) == [] and _x_ops(text, n) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("n,d,feature_major,layout", [
    (8_100_000, 784, True, "0,1"), (2_000_000, 1280, False, "1,0")])
def test_multinomial_program_reads_x_as_stored(topo, n, d, feature_major,
                                               layout):
    """``lr_multinomial_mnist8m_fit``'s shard — 8,100,000 x 784 bf16 on one
    chip, stored ``{0,1}``, 12.7 GB of arguments — and the row-major side at
    a lane-aligned width: the aggregation program is ONE Mosaic call
    (``glm_sweep_multinomial``; the last tile's rows past n are masked in
    the kernel) with no f32 value of X's shape, no pad or copy of X, and
    temporaries (the ``(1, n)`` rows of y and w) far under 1 GB."""
    compiled = _compile(topo, "multinomial", n, d, feature_major)
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    assert _entry_layout_of_x(text) == layout
    assert text.count("tpu_custom_call") == 1
    assert "glm_sweep_multinomial" in text
    assert n * d * 2 <= mem.argument_size_in_bytes <= n * d * 2 + (1 << 27)
    assert mem.temp_size_in_bytes < 1 << 30
    assert _x_ops(text, n) == [] and _wide_x(text, n, d) == []


def _label_matrices(text, n, k):
    """Instructions that hold a value of one entry a (row, model) pair in a
    storage type: the OneVsRest label matrix in any of its shapes."""
    shapes = "|".join(f"{a},{b}" for a, b in (
        (n, k), (n, 16), (k, n), (16, n)))
    return [line.strip() for line in text.splitlines()
            if re.search(rf"= (?:bf16|f16|f32|s8|u8|pred|s32)\[(?:{shapes})\]",
                         line)]


@pytest.mark.parametrize("n,d,feature_major,layout", [
    (8_100_000, 784, True, "0,1"), (2_000_000, 1280, False, "1,0")])
def test_stacked_binomial_program_reads_x_once_as_stored(topo, n, d,
                                                         feature_major,
                                                         layout):
    """``ovr_lr_mnist8m_fit``'s shard and the row-major side at a
    lane-aligned width: the K = 10 binary models' evaluation is ONE Mosaic
    call (``glm_sweep_stacked_binomial``: X is read once for all ten) with
    no f32 value of X's shape, no pad or copy of X, and no ``(rows, K)``
    label matrix in any orientation — the labels are made in the kernel
    from the class-index row."""
    compiled = _compile(topo, "stacked", n, d, feature_major)
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    assert _entry_layout_of_x(text) == layout
    assert text.count("tpu_custom_call") == 1
    assert "glm_sweep_stacked_binomial" in text
    assert n * d * 2 <= mem.argument_size_in_bytes <= n * d * 2 + (1 << 27)
    assert mem.temp_size_in_bytes < 1 << 30
    assert _x_ops(text, n) == [] and _wide_x(text, n, d) == []
    assert _label_matrices(text, n, N_CLASSES) == []


# -- the normal equations' moment program (WeightedLeastSquares) ---------------

def _compile_moments(topo, n, d, feature_major, n_chips=1):
    """The aggregation program ``LinearRegression``'s normal solver
    dispatches — ``wls.moments_aggregator`` under psum — for ``n_chips``
    described v5e chips. The aggregator asks ``pallas_available()``, which
    sees this sandbox's CPU: the test answers for the chip."""
    import jax
    import jax.numpy as jnp
    from unittest.mock import patch
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from cycloneml_tpu.ml.optim import wls
    from cycloneml_tpu.ops import kernels
    agg = wls.moments_aggregator(feature_major)
    mesh = Mesh(np.array(topo.devices[:n_chips]), ("data",))
    rows = NamedSharding(mesh, P("data"))
    args = [jax.ShapeDtypeStruct((n, d), jnp.bfloat16, sharding=rows),
            jax.ShapeDtypeStruct((n,), jnp.float32, sharding=rows),
            jax.ShapeDtypeStruct((n,), jnp.float32, sharding=rows)]

    def program(*a):
        local = lambda *b: jax.tree_util.tree_map(
            lambda t: jax.lax.psum(t, "data"), agg(*b))
        return jax.shard_map(local, mesh=mesh, in_specs=(P("data"),) * 3,
                             out_specs=P(), check_vma=False)(*a)

    with jax.enable_x64(False), \
            patch.object(kernels, "pallas_available", lambda: True):
        return jax.jit(program).lower(*args).compile()


def _wide_x(text, n, d):
    """Instructions that hold an f32 value of X's shape."""
    return [line.strip() for line in text.splitlines()
            if re.search(rf"= f32\[{n},{d}\]", line)]


def test_moment_program_at_the_cells_shape_reads_x_as_stored(topo):
    """``linreg_ridge_normal_fit``: 2,000,000 x 2,000 bf16 on one chip.
    X arrives ``{0,1}``, the program is the Mosaic call (one a branch of
    the weights' cond) over 8.0 GB of arguments with temporaries of a few
    (d, d) blocks, and holds no f32 copy, pad or layout copy of X."""
    n, d = 2_000_000, 2000
    compiled = _compile_moments(topo, n, d, feature_major=True)
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    assert _entry_layout_of_x(text) == "0,1"
    assert text.count("tpu_custom_call") >= 2
    assert 8.0e9 <= mem.argument_size_in_bytes <= 8.1e9
    assert mem.temp_size_in_bytes < 64 << 20
    assert _x_ops(text, n) == [] and _wide_x(text, n, d) == []


def test_moment_program_on_four_chips(topo):
    """Under shard_map on the 2x2 host every chip's 2,000,000-row shard is
    read as it lies and the (d, d) moments are all-reduced."""
    n, d = 8_000_000, 2000
    compiled = _compile_moments(topo, n, d, feature_major=True, n_chips=4)
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    assert "tpu_custom_call" in text and "all-reduce" in text
    assert 8.0e9 <= mem.argument_size_in_bytes <= 8.1e9
    assert mem.temp_size_in_bytes < 64 << 20
    for rows in (n, n // 4):
        assert _x_ops(text, rows) == [] and _wide_x(text, rows, d) == []


def test_moment_program_at_a_lane_aligned_width_stays_row_major(topo):
    """d = 1,280: X arrives ``{1,0}``; the row-major tiling transposes its
    tile in VMEM, so the program still holds no pad or copy of X."""
    n, d = 2_000_000, 1280
    compiled = _compile_moments(topo, n, d, feature_major=False)
    text = compiled.as_text()
    assert _entry_layout_of_x(text) == "1,0"
    assert "tpu_custom_call" in text
    assert _x_ops(text, n) == [] and _wide_x(text, n, d) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_moment_program_where_no_kernel_fits_widens_no_copy_of_x(topo):
    """d = 200 (200 % 16 = 8: no kernel tile): XLA's contraction with the
    weight folded into the convolution's operand — no f32 or weighted copy
    of X in HBM either (temporaries stay under 64 MiB at 1.6 GB of X)."""
    n, d = 4_000_000, 200
    compiled = _compile_moments(topo, n, d, feature_major=True)
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


# -- GeneralizedLinearRegression's IRLS programs ------------------------------------

def _compile_irls(topo, n, d, which):
    """The two programs a binomial ``GeneralizedLinearRegression`` fit
    dispatches — the IRLS pass and the margin-only deviance pass — under
    psum for one described v5e chip, with the replicated ``[beta |
    intercept | first]`` vector as their one extra argument."""
    import jax
    import jax.numpy as jnp
    from unittest.mock import patch
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from cycloneml_tpu.ml.regression import glm
    from cycloneml_tpu.ops import kernels
    fam, link = glm.Binomial(), glm.Logit()
    agg = glm.irls_aggregator(fam, link, True, False) if which == "pass" \
        else glm.deviance_aggregator(fam, link, False)
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    rows, rep = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    args = [jax.ShapeDtypeStruct((n, d), jnp.bfloat16, sharding=rows),
            jax.ShapeDtypeStruct((n,), jnp.float32, sharding=rows),
            jax.ShapeDtypeStruct((n,), jnp.float32, sharding=rows),
            jax.ShapeDtypeStruct((d + 2,), jnp.float32, sharding=rep)]

    def program(*a):
        local = lambda *b: jax.tree_util.tree_map(
            lambda t: jax.lax.psum(t, "data"), agg(*b))
        return jax.shard_map(local, mesh=mesh,
                             in_specs=(P("data"),) * 3 + (P(),),
                             out_specs=P(), check_vma=False)(*a)

    with jax.enable_x64(False), \
            patch.object(kernels, "pallas_available", lambda: True):
        return jax.jit(program).lower(*args).compile()


@pytest.mark.parametrize("which", ["pass", "deviance"])
def test_irls_programs_at_the_cells_shape_read_x_as_stored(topo, which):
    """``glr_binomial_irls_fit``: 2,000,000 x 2,000 bf16 on one chip. X
    arrives ``{0,1}``; the pass is the margins' contraction over the
    stored X (bf16 x the three bf16 pieces of beta) and the Mosaic moment
    Gramian (one a branch of the weights' cond) over 8.0 GB of arguments,
    the deviance pass the contraction alone; neither holds an f32 value of
    X's shape, a pad or a layout copy of X, and their temporaries are the
    row vectors (eta, z, omega) and a few (d, d) blocks."""
    n, d = 2_000_000, 2000
    compiled = _compile_irls(topo, n, d, which)
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    assert _entry_layout_of_x(text) == "0,1"
    assert text.count("tpu_custom_call") >= (2 if which == "pass" else 0)
    assert 8.0e9 <= mem.argument_size_in_bytes <= 8.1e9
    assert mem.temp_size_in_bytes < 96 << 20
    assert _x_ops(text, n) == [] and _wide_x(text, n, d) == []
    # the margins: ONE contraction of the stored X with beta's three pieces
    assert len(re.findall(rf"= f32\[{n},3\]\S* convolution\(", text)) == 1


# -- KMeans' Lloyd step ---------------------------------------------------------

def _compile_lloyd(topo, n, d, k, update, n_chips=1, screen=True):
    """The program one Lloyd step of ``KMeans`` dispatches —
    ``kmeans.lloyd_aggregator(fused=True, update)`` under psum — with the
    replicated float32 centres as its one extra argument (``screen=False``:
    the program a fit full of near-ties goes on with)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from cycloneml_tpu.ml.clustering import kmeans
    agg = kmeans.lloyd_aggregator(True, update, screen)
    mesh = Mesh(np.array(topo.devices[:n_chips]), ("data",))
    rows, rep = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    args = [jax.ShapeDtypeStruct((n, d), jnp.bfloat16, sharding=rows),
            jax.ShapeDtypeStruct((n,), jnp.float32, sharding=rows),
            jax.ShapeDtypeStruct((n,), jnp.float32, sharding=rows),
            jax.ShapeDtypeStruct((k, d), jnp.float32, sharding=rep)]

    def program(*a):
        local = lambda *b: jax.tree_util.tree_map(
            lambda t: jax.lax.psum(t, "data"), agg(*b))
        return jax.shard_map(local, mesh=mesh,
                             in_specs=(P("data"),) * 3 + (P(),),
                             out_specs=P(), check_vma=False)(*a)

    with jax.enable_x64(False):
        return jax.jit(program).lower(*args).compile()


def _score_values(text, rows, k):
    """Instructions that hold a value of any type with X's rows and k (or
    the padded k) columns: a distance or one-hot matrix."""
    pads = {k, -(-k // 16) * 16, -(-k // 128) * 128}
    return [line.strip() for line in text.splitlines()
            if re.search(rf"= \w+\[{rows},(?:{'|'.join(map(str, pads))})\]",
                         line)]


@pytest.mark.parametrize("update,name,screen", [
    (True, "kmeans_lloyd", True), (True, "kmeans_lloyd", False),
    (False, "kmeans_lloyd_cost", True)])
def test_lloyd_step_program_at_the_cells_shape(topo, update, name, screen):
    """``kmeans_k1000_lloyd_fit``: 25,000,000 x 128 bf16 on one chip, k =
    1,000. X arrives ``{1,0}`` (a width of 128: the first row-major cell);
    the step is ONE Mosaic call (the kernel branch of the weights' cond;
    the other branch is the row-blocked twin) over 6.4 GB of arguments, and
    holds no ``(rows, k)`` value of any type, no f32 value of X's shape and
    no pad or copy of X; its temporaries are the ``(1, n)`` row of w and
    one chunk of the twin."""
    n, d, k = 25_000_000, 128, 1000
    compiled = _compile_lloyd(topo, n, d, k, update, screen=screen)
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    assert _entry_layout_of_x(text) == "1,0"
    assert text.count("tpu_custom_call") == 1 and name in text
    assert n * d * 2 <= mem.argument_size_in_bytes <= n * d * 2 + (1 << 28)
    assert mem.temp_size_in_bytes < 1 << 30
    assert _x_ops(text, n) == [] and _wide_x(text, n, d) == []
    assert _score_values(text, n, k) == []


def test_lloyd_step_program_on_four_chips(topo):
    """The stated deployment: 100,000,000 x 128 over the 2x2 host, every
    chip's 25,000,000-row shard read as it lies, the ``(k, d)`` sums
    all-reduced."""
    n, d, k = 100_000_000, 128, 1000
    text = _compile_lloyd(topo, n, d, k, True, n_chips=4).as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text
    for rows in (n, n // 4):
        assert _x_ops(text, rows) == [] and _wide_x(text, rows, d) == []
        assert _score_values(text, rows, k) == []
