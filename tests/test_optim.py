"""Optimizer + aggregator tests.

Parity models (SURVEY §4 takeaway): hand-derived aggregator gradients are
checked against jax.grad; L-BFGS/OWL-QN are checked against scipy and
sklearn closed-form/iterative references with tight tolerances.
"""

import numpy as np
import pytest

from cycloneml_tpu.ml.optim import LBFGS, OWLQN, aggregators
from cycloneml_tpu.ml.optim.loss import DistributedLossFunction, l2_regularization


# -- L-BFGS core --------------------------------------------------------------

def test_lbfgs_quadratic_exact():
    rng = np.random.RandomState(0)
    a = rng.randn(10, 10)
    h = a @ a.T + 10 * np.eye(10)
    b = rng.randn(10)

    def f(x):
        return 0.5 * x @ h @ x - b @ x, h @ x - b

    st = LBFGS(max_iter=100, tol=1e-12).minimize(f, np.zeros(10))
    np.testing.assert_allclose(st.x, np.linalg.solve(h, b), rtol=1e-6)
    assert st.converged


def test_lbfgs_rosenbrock_vs_scipy():
    from scipy.optimize import rosen, rosen_der

    def f(x):
        return rosen(x), rosen_der(x)

    x0 = np.array([-1.2, 1.0, -0.5, 0.8])
    st = LBFGS(max_iter=500, tol=1e-14).minimize(f, x0)
    np.testing.assert_allclose(st.x, np.ones(4), atol=1e-5)


def test_lbfgs_loss_history_monotone():
    rng = np.random.RandomState(1)
    h = np.diag(rng.uniform(1, 5, 6))
    b = rng.randn(6)

    def f(x):
        return 0.5 * x @ h @ x - b @ x, h @ x - b

    st = LBFGS(max_iter=50).minimize(f, np.zeros(6))
    diffs = np.diff(st.loss_history)
    assert np.all(diffs <= 1e-12)


def test_owlqn_lasso_vs_sklearn():
    from sklearn.linear_model import Lasso
    rng = np.random.RandomState(2)
    n, d = 200, 8
    x = rng.randn(n, d)
    true = np.array([1.5, -2.0, 0, 0, 3.0, 0, 0, 0.5])
    y = x @ true + 0.01 * rng.randn(n)
    alpha = 0.1

    def f(beta):
        err = x @ beta - y
        return float(0.5 / n * err @ err), x.T @ err / n

    st = OWLQN(max_iter=500, tol=1e-12, l1_reg=alpha).minimize(f, np.zeros(d))
    sk = Lasso(alpha=alpha, tol=1e-12, max_iter=100000).fit(x, y)
    np.testing.assert_allclose(st.x, sk.coef_, atol=2e-4)
    # sparsity pattern must match
    assert set(np.nonzero(np.abs(st.x) > 1e-8)[0]) == set(np.nonzero(np.abs(sk.coef_) > 1e-8)[0])


def _quad1(x):
    return 0.5 * float(x @ x), x.copy()


def _line1(x):
    return -float(x[0]), np.array([-1.0])


def _kink1(x):
    return float(np.abs(x).sum()), np.sign(x)


def _rosen_fg(x):
    a, b = x
    return (float((1 - a) ** 2 + 100 * (b - a * a) ** 2),
            np.array([-2 * (1 - a) - 400 * a * (b - a * a),
                      200 * (b - a * a)]))


@pytest.mark.parametrize("f, x, d, init_alpha, cap, alpha, evals", [
    # (alpha, evals) as the pre-coroutine _strong_wolfe returned them
    pytest.param(_quad1, [1.0], [-1.0], 1.0, 30, 1.0, 1,
                 id="first_trial_accepted"),
    pytest.param(_quad1, [1.0], [-1.0], 4.0, 30, 1.0, 3,
                 id="armijo_fails_then_zoom"),
    pytest.param(_quad1, [1.0], [-1.0], 1.95, 30, 0.975, 2,
                 id="positive_slope_then_zoom"),
    pytest.param(_rosen_fg, [-1.2, 1.0], None, 1.0, 30, 0.0009765625, 11,
                 id="rosenbrock_long_zoom"),
    pytest.param(_line1, [0.0], [1.0], 1.0, 5, 32.0, 6,
                 id="bracket_cap_exhausted"),
    pytest.param(_kink1, [1.0], [-1.0], 3.0, 8, 0.99609375, 9,
                 id="zoom_cap_exhausted"),
    pytest.param(_quad1, [1.0], [1.0], 1.0, 30, None, 0,
                 id="non_descent_raises"),
])
def test_strong_wolfe_search_branches(f, x, d, init_alpha, cap, alpha, evals):
    """The one host search (the coroutine, driven here by a callable) on a
    case for each of its branches."""
    from cycloneml_tpu.ml.optim.lbfgs import _strong_wolfe
    x = np.array(x)
    value, grad = f(x)
    d = -grad if d is None else np.array(d)
    seen = []

    def counted(z):
        seen.append(z)
        return f(z)

    if alpha is None:
        with pytest.raises(ValueError, match="not a descent direction"):
            _strong_wolfe(counted, x, value, grad, d, init_alpha)
        assert not seen
        return
    a, v, g = _strong_wolfe(counted, x, value, grad, d, init_alpha,
                            max_evals=cap)
    assert (a, len(seen)) == (alpha, evals)
    # what comes back is the last point evaluated
    v_ref, g_ref = f(x + a * d)
    assert v == v_ref
    np.testing.assert_array_equal(g, g_ref)


def _host_logistic(seed=0, n=60, d=5, reg=0.05):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d)
    y = (X @ rng.randn(d) + 0.3 * rng.randn(n) > 0).astype(np.float64)

    def f(w):
        z = X @ w
        loss = float(np.mean(np.logaddexp(0.0, z) - y * z)
                     + 0.5 * reg * (w @ w))
        return loss, X.T @ (1.0 / (1.0 + np.exp(-z)) - y) / n + reg * w
    return f, d


@pytest.mark.parametrize("objective", ["quadratic", "logistic"])
def test_stacked_host_lbfgs_of_one_model_is_lbfgs(objective):
    """StackedHostLBFGS drives the SAME turn decisions and the same search
    as LBFGS.iterations: one model, same replies, the same floats."""
    from cycloneml_tpu.ml.optim.device_lbfgs import StackedHostLBFGS
    if objective == "quadratic":
        rng = np.random.RandomState(3)
        A = rng.randn(8, 8)
        A = A @ A.T + np.eye(8)
        b = rng.randn(8)
        d = 8

        def f(x):
            return 0.5 * float(x @ A @ x) - float(b @ x), A @ x - b
    else:
        f, d = _host_logistic()
    x0 = np.full(d, 0.5)
    calls = []

    def counted(x):
        calls.append(x)
        return f(x)

    serial = LBFGS(max_iter=40, tol=1e-9).minimize(counted, x0)

    def stacked_f(xs):
        out = [f(x) for x in xs]
        return (np.array([v for v, _ in out]),
                np.stack([g for _, g in out]))

    res = StackedHostLBFGS(max_iter=40, tol=1e-9).minimize(
        stacked_f, x0[None, :])
    assert serial.iteration > 3
    np.testing.assert_array_equal(res.x[0], serial.x)
    assert res.values[0] == serial.value
    assert int(res.iterations[0]) == serial.iteration
    assert res.converged_reasons[0] == serial.converged_reason
    assert res.loss_histories[0] == serial.loss_history
    assert int(res.evals[0]) == len(calls)


def test_owlqn_zero_l1_equals_lbfgs():
    rng = np.random.RandomState(3)
    h = np.diag(rng.uniform(1, 3, 5))
    b = rng.randn(5)

    def f(x):
        return 0.5 * x @ h @ x - b @ x, h @ x - b

    a = LBFGS(max_iter=200, tol=1e-12).minimize(f, np.zeros(5))
    o = OWLQN(max_iter=200, tol=1e-12, l1_reg=0.0).minimize(f, np.zeros(5))
    np.testing.assert_allclose(a.x, o.x, atol=1e-8)


# -- OWL-QN's line search ------------------------------------------------------

@pytest.fixture
def tracer():
    from cycloneml_tpu.observe import tracing
    tracing.disable()  # defend against a leak from a dirty test
    t = tracing.enable(max_spans=10_000)
    yield t
    tracing.disable()


def _enet_quadratic(d=200, n=100_000, l1=0.005, l2=0.005, seed=7,
                    dtype=np.float64):
    """A seeded elastic-net quadratic shaped like a standardised regression
    fit: Gramian I + O(n^-1/2) noise, ||beta|| = 1, noise 0.5, unit label
    variance. Returns a counting loss/grad callable that rounds what it
    returns to ``dtype`` (and says so, as the distributed loss functions
    do), and a long proximal-gradient solve's point and objective."""
    rng = np.random.RandomState(seed)
    e = rng.randn(d, d) / np.sqrt(n)
    h = np.eye(d) + (e + e.T) / 2
    beta = rng.randn(d)
    beta /= np.linalg.norm(beta)
    b = (h @ beta + 0.5 * rng.randn(d) / np.sqrt(n)) / np.sqrt(1.25)

    def smooth(x):
        hx = h @ x
        return (0.5 - b @ x + 0.5 * x @ hx + 0.5 * l2 * x @ x,
                hx - b + l2 * x)

    class Counted:
        accumulator_dtype = np.dtype(dtype)
        n_evals = 0

        def __call__(self, x):
            self.n_evals += 1
            v, g = smooth(x)
            return float(dtype(v)), g.astype(dtype).astype(np.float64)

    x = np.zeros(d)
    step = 1.0 / (np.linalg.eigvalsh(h)[-1] + l2)
    for _ in range(2000):
        z = x - step * smooth(x)[1]
        x = np.sign(z) * np.maximum(np.abs(z) - step * l1, 0.0)
    return Counted(), np.full(d, l1), x, smooth(x)[0] + l1 * np.abs(x).sum()


@pytest.mark.parametrize("dtype", [np.float64, np.float32])
def test_owlqn_search_takes_one_evaluation_an_iteration(dtype):
    """The regression this guards: with the strong-Wolfe zoom in OWL-QN's
    place (curvature tested on the smooth part's slope, which the L1 term
    keeps from vanishing) this case counted 67 (float64) and 66 (float32)
    evaluations for its 4 iterations, two searches running out their
    30-evaluation cap; the Armijo backtracking search takes the first trial
    step: 5."""
    f, l1, x_ref, f_ref = _enet_quadratic(dtype=dtype)
    st = OWLQN(tol=1e-6, l1_reg=l1).minimize(f, np.zeros(len(l1)))
    assert st.converged and st.iteration >= 2
    assert f.n_evals <= st.iteration + 3, st.search_evals
    assert abs(st.value - f_ref) <= 1e-7 * f_ref
    assert np.linalg.norm(st.x - x_ref) <= 1e-4 * np.linalg.norm(x_ref)


def test_owlqn_search_never_climbs_and_says_unresolved(tracer):
    """A loss that carries +-1 ulp of float32 noise: once the decrease on
    offer is under that resolution the search stops instead of halving to
    its cap, says so, and hands back nothing that lies above F(x) by more
    than the resolution."""
    f, l1, _, _ = _enet_quadratic(dtype=np.float32)
    rng = np.random.RandomState(0)

    def noisy(x):
        v, g = f(x)
        return float(np.nextafter(np.float32(v), np.float32(
            rng.choice([-np.inf, np.inf])))), g
    noisy.accumulator_dtype = np.dtype(np.float32)

    eps = float(np.finfo(np.float32).eps)
    states = list(OWLQN(tol=1e-12, max_iter=40, l1_reg=l1).iterations(
        noisy, np.zeros(len(l1))))
    for before, after in zip(states, states[1:]):
        assert after.value <= before.value + eps * abs(before.value)
    turns = [s.attrs for s in tracer.snapshot()
             if s.name == "optim.iteration" and s.attrs["iteration"] > 0]
    assert [t["search_evals"] for t in turns] == states[-1].search_evals[1:]
    assert {t["search"] for t in turns} <= {
        "first_trial", "backtracked", "unresolved"}
    assert turns[-1]["search"] == "unresolved"
    # a search that cannot be resolved is short: far under the 30-trial cap
    assert max(states[-1].search_evals) <= 12, states[-1].search_evals
    assert states[-1].converged_reason == "function value converged"


def test_owlqn_search_evals_sum_to_the_evaluations():
    f, l1, _, _ = _enet_quadratic(d=50, n=2_000, seed=3)
    opt = OWLQN(tol=1e-10, l1_reg=l1)
    states = list(opt.iterations(f, np.zeros(len(l1))))
    last = states[-1]
    assert last.search_evals[0] == 1
    assert len(last.search_evals) == last.iteration + 1
    assert sum(last.search_evals) == f.n_evals
    # carried through a checkpoint, and continued from it on resume
    from cycloneml_tpu.ml.optim import OptimState
    mid = OptimState.from_pytree(states[2].to_pytree())
    assert mid.search_evals == states[2].search_evals
    resumed = opt.minimize(f, None, resume=mid)
    assert resumed.search_evals == last.search_evals


# -- aggregator gradients vs jax.grad ----------------------------------------

def _check_grad(agg, coef_len, k_classes=None, extra_tail=0):
    import jax
    import jax.numpy as jnp
    rng = np.random.RandomState(4)
    b, d = 16, 5
    x = jnp.asarray(rng.randn(b, d))
    if k_classes:
        y = jnp.asarray(rng.randint(0, k_classes, b).astype(np.float64))
    else:
        y = jnp.asarray(rng.randint(0, 2, b).astype(np.float64))
    w = jnp.asarray(rng.uniform(0.5, 2.0, b))
    coef = jnp.asarray(rng.randn(coef_len) + (1.0 if extra_tail else 0.0))

    out = agg(x, y, w, coef)
    auto = jax.grad(lambda c: agg(x, y, w, c)["loss"])(coef)
    np.testing.assert_allclose(np.asarray(out["grad"]), np.asarray(auto),
                               rtol=1e-8, atol=1e-8)
    assert float(out["count"]) == pytest.approx(float(jnp.sum(w)))


def test_binary_logistic_grad_matches_autodiff():
    _check_grad(aggregators.binary_logistic(5, fit_intercept=True), 6)
    _check_grad(aggregators.binary_logistic(5, fit_intercept=False), 5)


def test_multinomial_grad_matches_autodiff():
    _check_grad(aggregators.multinomial_logistic(5, 3, fit_intercept=True),
                5 * 3 + 3, k_classes=3)
    _check_grad(aggregators.multinomial_logistic(5, 3, fit_intercept=False),
                5 * 3, k_classes=3)


def test_least_squares_grad_matches_autodiff():
    _check_grad(aggregators.least_squares(5, fit_intercept=True), 6)


def test_huber_grad_matches_autodiff():
    # sigma (last coef) shifted positive by extra_tail offset
    _check_grad(aggregators.huber(5, fit_intercept=True), 7, extra_tail=1)


def test_hinge_loss_value():
    import jax.numpy as jnp
    x = jnp.asarray([[1.0, 0.0], [0.0, 1.0]])
    y = jnp.asarray([1.0, 0.0])
    w = jnp.asarray([1.0, 1.0])
    agg = aggregators.hinge(2, fit_intercept=False)
    out = agg(x, y, w, jnp.asarray([0.0, 0.0]))
    assert float(out["loss"]) == pytest.approx(2.0)  # both at margin 0 -> hinge 1


# -- distributed loss over the mesh -------------------------------------------

def test_distributed_loss_matches_local(ctx):
    from cycloneml_tpu.dataset.dataset import InstanceDataset
    rng = np.random.RandomState(5)
    n, d = 300, 6
    x = rng.randn(n, d)
    y = (rng.rand(n) > 0.5).astype(np.float64)
    ds = InstanceDataset.from_numpy(ctx, x, y, dtype=np.float64)
    agg = aggregators.binary_logistic(d, fit_intercept=True)
    lf = DistributedLossFunction(ds, agg)
    assert lf.weight_sum == n
    coef = rng.randn(d + 1)
    loss, grad = lf(coef)

    # local reference in numpy
    beta, b0 = coef[:d], coef[d]
    m = x @ beta + b0
    ref_loss = np.sum(np.logaddexp(0, m) - y * m) / n
    mult = (1 / (1 + np.exp(-m)) - y) / n
    ref_grad = np.concatenate([x.T @ mult, [mult.sum()]])
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-10)
    np.testing.assert_allclose(grad, ref_grad, rtol=1e-8, atol=1e-12)


def test_l2_regularization_modes():
    d = 3
    coef = np.array([1.0, -2.0, 3.0, 0.5])  # last = intercept
    fn = l2_regularization(0.1, d, True, standardize=True)
    loss, grad = fn(coef)
    assert loss == pytest.approx(0.05 * (1 + 4 + 9))
    np.testing.assert_allclose(grad, [0.1, -0.2, 0.3, 0.0])
    std = np.array([1.0, 2.0, 0.5])
    fn2 = l2_regularization(0.1, d, True, features_std=std, standardize=False)
    loss2, grad2 = fn2(coef)
    assert loss2 == pytest.approx(0.05 * (1 + 1 + 36))
    np.testing.assert_allclose(grad2, [0.1, -0.05, 1.2, 0.0])


def test_distributed_logistic_end_to_end_lbfgs(ctx):
    """Mini end-to-end: distributed loss + L-BFGS equals sklearn."""
    from sklearn.linear_model import LogisticRegression as SkLR
    from cycloneml_tpu.dataset.dataset import InstanceDataset
    rng = np.random.RandomState(6)
    n, d = 400, 5
    x = rng.randn(n, d)
    true = rng.randn(d)
    y = (x @ true + 0.3 * rng.randn(n) > 0).astype(np.float64)
    ds = InstanceDataset.from_numpy(ctx, x, y, dtype=np.float64)
    reg = 0.01
    lf = DistributedLossFunction(
        ds, aggregators.binary_logistic(d, True),
        l2_reg_fn=l2_regularization(reg, d, True, standardize=True))
    st = LBFGS(max_iter=200, tol=1e-12).minimize(lf, np.zeros(d + 1))
    # sklearn: minimizes sum(logloss) + 1/(2C)||b||^2; ours: mean + reg/2||b||^2
    sk = SkLR(C=1.0 / (reg * n), tol=1e-10, max_iter=10000).fit(x, y)
    np.testing.assert_allclose(st.x[:d], sk.coef_[0], atol=1e-4)
    np.testing.assert_allclose(st.x[d], sk.intercept_[0], atol=1e-4)


def test_matmul_precision_config(ctx):
    """'cyclone.compute.matmulPrecision' steers the aggregator hot path at
    build time; invalid values are rejected by the typed registry."""
    import jax
    import pytest
    from cycloneml_tpu.conf import MATMUL_PRECISION
    from cycloneml_tpu.ml.optim.aggregators import matmul_precision

    assert matmul_precision() == jax.lax.Precision.HIGHEST  # default
    ctx.conf.set(MATMUL_PRECISION, "default")
    try:
        assert matmul_precision() == jax.lax.Precision.DEFAULT
    finally:
        ctx.conf.set(MATMUL_PRECISION, "highest")
    assert matmul_precision() == jax.lax.Precision.HIGHEST
    ctx.conf.set(MATMUL_PRECISION, "bogus")
    try:
        with pytest.raises(ValueError):
            matmul_precision()  # misconfiguration surfaces at build time
    finally:
        ctx.conf.set(MATMUL_PRECISION, "highest")


# -- device-resident (fused) line search --------------------------------------

class _HostPathOnly:
    """Strips device_line_search so _strong_wolfe takes the per-eval path."""

    def __init__(self, f):
        self._f = f

    def __call__(self, coef):
        return self._f(coef)


def test_fused_line_search_matches_host_trajectory(ctx):
    """The one-dispatch bracket+zoom while_loop must reproduce the host
    Nocedal-Wright search decision-for-decision (dense path, f64 on the test
    mesh, so trajectories are bitwise-comparable)."""
    from cycloneml_tpu.dataset.dataset import InstanceDataset

    rng = np.random.RandomState(5)
    n, d = 400, 24
    x = rng.randn(n, d)
    y = (rng.rand(n) > 0.5).astype(np.float64)
    ds = InstanceDataset.from_numpy(ctx, x, y)
    l2 = l2_regularization(0.1, d, True, standardize=True)
    agg = aggregators.binary_logistic(d, fit_intercept=True)
    fused_loss = DistributedLossFunction(ds, agg, l2)
    host_loss = _HostPathOnly(DistributedLossFunction(ds, agg, l2))

    fused = list(LBFGS(max_iter=15, tol=1e-12).iterations(fused_loss, np.zeros(d + 1)))
    host = list(LBFGS(max_iter=15, tol=1e-12).iterations(host_loss, np.zeros(d + 1)))
    assert len(fused) == len(host)
    for a, b in zip(fused, host):
        np.testing.assert_allclose(a.x, b.x, rtol=1e-12, atol=1e-14)
        assert abs(a.value - b.value) < 1e-12


def test_fused_line_search_dispatch_count(ctx):
    """The point of the fusion: host->device round trips per iteration must
    be ~1 (one line-search dispatch), NOT one per phi evaluation."""
    from cycloneml_tpu.dataset.dataset import InstanceDataset

    rng = np.random.RandomState(2)
    n, d = 600, 32
    x = rng.randn(n, d)
    true = rng.randn(d)
    y = (x @ true + rng.randn(n) > 0).astype(np.float64)
    ds = InstanceDataset.from_numpy(ctx, x, y)
    loss = DistributedLossFunction(
        ds, aggregators.binary_logistic(d, fit_intercept=True),
        l2_regularization(0.01, d, True, standardize=True))
    # a start far from the optimum: the first searches must double their
    # first trial before the curvature condition holds (from zeros every
    # search of this problem accepts its first trial, and at the float64
    # floor `wolfe_search` ends a search at once since PR 41)
    st = LBFGS(max_iter=20, tol=0.0).minimize(loss, 3.0 * rng.randn(d + 1))
    assert st.iteration >= 5
    # initial eval = 1 dispatch; each iteration = 1 fused line-search dispatch
    assert loss.n_dispatches <= st.iteration + 2, \
        (loss.n_dispatches, st.iteration, loss.n_evals)
    assert loss.n_evals > loss.n_dispatches  # multiple evals rode each dispatch


def test_fused_line_search_sparse_tier(ctx):
    """The sparse (Criteo-path) aggregation also fuses: same dispatch bound."""
    from cycloneml_tpu.dataset.sparse import SparseInstanceDataset
    from cycloneml_tpu.ml.optim.sparse_aggregators import binary_logistic_sparse

    rng = np.random.RandomState(3)
    n, k, D = 512, 6, 100
    idx = rng.randint(0, D, size=(n, k)).astype(np.int32)
    val = np.abs(rng.randn(n, k))
    y = (rng.rand(n) > 0.5).astype(np.float64)
    sds = SparseInstanceDataset.from_ell(ctx, idx, val, y=y, n_features=D)
    loss = DistributedLossFunction(sds, binary_logistic_sparse(D, False))
    st = LBFGS(max_iter=10, tol=0.0).minimize(loss, np.zeros(D))
    assert loss.n_dispatches <= st.iteration + 2
    assert np.all(np.isfinite(st.x))


# -- LBFGS-B (box constraints) -------------------------------------------------

def _quad_problem(d=6, seed=0):
    """Convex quadratic ½(x−c)ᵀQ(x−c) with known unconstrained optimum c."""
    rng = np.random.RandomState(seed)
    a = rng.randn(d, d)
    q = a @ a.T + d * np.eye(d)
    c = rng.randn(d) * 2.0

    def f(x):
        diff = x - c
        return 0.5 * float(diff @ q @ diff), q @ diff
    return f, q, c


def test_lbfgsb_matches_scipy():
    """Parity against scipy's L-BFGS-B on the same bounded problem
    (VERDICT r1 item 8's oracle)."""
    from scipy.optimize import fmin_l_bfgs_b
    from cycloneml_tpu.ml.optim.lbfgs import LBFGSB

    f, q, c = _quad_problem()
    lo = np.full(6, -0.5)
    hi = np.full(6, 0.75)
    state = LBFGSB(lo, hi, max_iter=200, tol=1e-12).minimize(f, np.zeros(6))
    ref_x, ref_v, info = fmin_l_bfgs_b(
        lambda x: f(x), np.zeros(6), bounds=list(zip(lo, hi)),
        pgtol=1e-12, factr=10.0)
    np.testing.assert_allclose(state.x, ref_x, rtol=1e-6, atol=1e-8)
    np.testing.assert_allclose(state.value, ref_v, rtol=1e-9)
    # solution respects the box and actually binds some constraints
    assert np.all(state.x >= lo - 1e-12) and np.all(state.x <= hi + 1e-12)
    assert np.any(np.isclose(state.x, lo) | np.isclose(state.x, hi))


def test_lbfgsb_inactive_bounds_match_lbfgs():
    """Wide-open bounds must reproduce the unconstrained optimizer."""
    from cycloneml_tpu.ml.optim.lbfgs import LBFGS, LBFGSB

    f, q, c = _quad_problem(seed=3)
    free = LBFGS(max_iter=200, tol=1e-12).minimize(f, np.zeros(6))
    boxed = LBFGSB(np.full(6, -1e6), np.full(6, 1e6),
                   max_iter=200, tol=1e-12).minimize(f, np.zeros(6))
    np.testing.assert_allclose(boxed.x, free.x, rtol=1e-8, atol=1e-10)
    np.testing.assert_allclose(boxed.x, c, rtol=1e-6, atol=1e-8)


def test_lbfgsb_rejects_crossed_bounds():
    from cycloneml_tpu.ml.optim.lbfgs import LBFGSB
    with pytest.raises(ValueError, match="lower bound"):
        LBFGSB(np.ones(3), np.zeros(3))


def test_lbfgsb_resume_exact(tmp_path):
    """Checkpoint/resume continuity holds for the bounded optimizer too."""
    from cycloneml_tpu.ml.optim.lbfgs import LBFGSB

    f, q, c = _quad_problem(seed=5)
    lo, hi = np.full(6, -0.4), np.full(6, 0.6)
    opt = LBFGSB(lo, hi, max_iter=40, tol=1e-13)
    full = opt.minimize(f, np.zeros(6))
    # stop after 3 iterations, resume from that state
    states = []
    for s in opt.iterations(f, np.zeros(6)):
        states.append(s)
        if s.iteration == 3:
            break
    resumed = opt.minimize(f, np.zeros(6), resume=states[-1])
    np.testing.assert_allclose(resumed.x, full.x, rtol=1e-10, atol=1e-12)


def test_lbfgsb_degenerate_and_corner_cases():
    """lower == upper (pinned coordinates) and a start clipped onto the
    optimal corner must CONVERGE, not crash on a zero direction."""
    from cycloneml_tpu.ml.optim.lbfgs import LBFGSB

    def f(x):
        return 0.5 * float(x @ x), x.copy()

    pinned = LBFGSB(np.ones(3), np.ones(3)).minimize(f, np.zeros(3))
    assert pinned.converged and np.allclose(pinned.x, 1.0)

    corner = LBFGSB(np.full(3, 1.0), np.full(3, 2.0)).minimize(f, np.zeros(3))
    assert corner.converged and np.allclose(corner.x, 1.0)

    # partial pin: one coordinate fixed, others free
    lo = np.array([-5.0, 2.0, -5.0])
    hi = np.array([5.0, 2.0, 5.0])
    mixed = LBFGSB(lo, hi, max_iter=100, tol=1e-12).minimize(f, np.zeros(3))
    np.testing.assert_allclose(mixed.x, [0.0, 2.0, 0.0], atol=1e-8)


def test_scaled_aggregators_grad_matches_autodiff():
    """The fold-standardization-into-the-read aggregators: hand-derived
    gradients (inv_std unscaling + scaled_mean offset terms) against
    autodiff, and equality with the plain aggregator on pre-standardized
    data."""
    import jax
    import jax.numpy as jnp
    rng = np.random.RandomState(11)
    b, d, k = 16, 5, 3
    x = jnp.asarray(rng.randn(b, d) * 2.0 + 1.0)
    w = jnp.asarray(rng.uniform(0.5, 2.0, b))
    inv_std = jnp.asarray(rng.uniform(0.5, 2.0, d))
    mu = jnp.asarray(rng.randn(d))

    for agg, coef_len, y in (
            (aggregators.binary_logistic_scaled(d, True), d + 1,
             jnp.asarray((rng.rand(b) > 0.5).astype(np.float64))),
            (aggregators.multinomial_logistic_scaled(d, k, True),
             d * k + k, jnp.asarray(rng.randint(0, k, b).astype(float))),
            (aggregators.multinomial_logistic_scaled(d, k, False),
             d * k, jnp.asarray(rng.randint(0, k, b).astype(float)))):
        coef = jnp.asarray(rng.randn(coef_len))
        out = agg(x, y, w, inv_std, mu, coef)
        auto = jax.grad(lambda c: agg(x, y, w, inv_std, mu, c)["loss"])(coef)
        np.testing.assert_allclose(np.asarray(out["grad"]),
                                   np.asarray(auto), rtol=1e-8, atol=1e-8)

    # scaled agg on raw x == plain agg on standardized x
    y2 = jnp.asarray(rng.randint(0, k, b).astype(float))
    coef = jnp.asarray(rng.randn(d * k + k))
    # the scaled agg's contract: x̂ = x·inv_std − scaled_mean
    x_hat = x * inv_std[None, :] - mu[None, :]
    got = aggregators.multinomial_logistic_scaled(d, k, True)(
        x, y2, w, inv_std, mu, coef)
    want = aggregators.multinomial_logistic(d, k, True)(x_hat, y2, w, coef)
    np.testing.assert_allclose(float(got["loss"]), float(want["loss"]),
                               rtol=1e-10)
