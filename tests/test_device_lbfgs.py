"""Chunked device L-BFGS: trajectory parity with the host optimizer."""

import dataclasses

import numpy as np
import pytest

from cycloneml_tpu.dataset.dataset import InstanceDataset
from cycloneml_tpu.ml.optim import LBFGS, aggregators
from cycloneml_tpu.ml.optim.device_lbfgs import DeviceLBFGS
from cycloneml_tpu.ml.optim.loss import (DistributedLossFunction,
                                         l2_regularization)
from cycloneml_tpu.observe import tracing


def _loss(ctx, n=400, d=12, seed=0, reg=0.0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, d)
    y = (x @ rng.randn(d) > 0).astype(np.float64)
    ds = InstanceDataset.from_numpy(ctx, x, y)
    l2 = l2_regularization(reg, d, True, standardize=True) if reg else None
    return DistributedLossFunction(
        ds, aggregators.binary_logistic(d, fit_intercept=True), l2), d


def test_device_chunk_matches_host_trajectory(ctx):
    """Under the f64 CPU config the chunked program runs the SAME two-loop
    + Wolfe machine as the host path — final states must agree tightly."""
    for reg in (0.0, 0.1):
        host_f, d = _loss(ctx, seed=3, reg=reg)
        host = LBFGS(max_iter=30, tol=1e-10).minimize(host_f, np.zeros(d + 1))
        dev_f, _ = _loss(ctx, seed=3, reg=reg)
        dev = DeviceLBFGS(max_iter=30, tol=1e-10, chunk=8).minimize(
            dev_f, np.zeros(d + 1))
        np.testing.assert_allclose(dev.x, host.x, rtol=1e-7, atol=1e-9)
        np.testing.assert_allclose(dev.value, host.value, rtol=1e-10)
        assert dev.converged_reason == host.converged_reason
        # the whole point: far fewer dispatches than evaluations
        assert dev_f.n_dispatches < dev_f.n_evals
        assert dev_f.n_dispatches <= (dev.iteration // 8 + 2)


def test_device_chunk_loss_history_per_iteration(ctx):
    f, d = _loss(ctx, seed=5, reg=0.05)
    state = DeviceLBFGS(max_iter=12, tol=0.0, chunk=4).minimize(
        f, np.zeros(d + 1))
    # initial loss + one entry per iteration, monotone-ish decreasing
    assert len(state.loss_history) == state.iteration + 1
    assert state.loss_history[-1] < state.loss_history[0]


def test_device_chunk_respects_max_iter(ctx):
    f, d = _loss(ctx, seed=7)
    state = DeviceLBFGS(max_iter=5, tol=0.0, chunk=8).minimize(
        f, np.zeros(d + 1))
    assert state.iteration == 5
    assert state.converged_reason == "max iterations reached"


def test_device_chunk_resume_exact(ctx):
    """Chunk-boundary states carry the full curvature ring: resuming from
    one reproduces the uninterrupted trajectory."""
    f, d = _loss(ctx, seed=9, reg=0.02)
    opt = DeviceLBFGS(max_iter=24, tol=1e-12, chunk=4)
    full = opt.minimize(f, np.zeros(d + 1))
    f2, _ = _loss(ctx, seed=9, reg=0.02)
    it = opt.iterations(f2, np.zeros(d + 1))
    next(it)           # initial state
    mid = next(it)     # after one chunk
    f3, _ = _loss(ctx, seed=9, reg=0.02)
    resumed = opt.minimize(f3, np.zeros(d + 1), resume=mid)
    np.testing.assert_allclose(resumed.x, full.x, rtol=1e-8, atol=1e-10)


@pytest.fixture
def tracer():
    tracing.disable()
    t = tracing.enable(max_spans=50_000)
    yield t
    tracing.disable()


def _history_reads(tracer):
    return [s for s in tracer.snapshot()
            if s.kind == "instant" and s.name == "optim.history.read"]


def _count_device_indexing(monkeypatch):
    """Every ``device_array[...]`` from Python is a launch of its own
    (``jit_dynamic_slice`` / ``jit_squeeze`` on the chip): count them."""
    from jax._src.array import ArrayImpl
    calls = []
    index = ArrayImpl.__getitem__

    def counted(self, idx):
        calls.append((self.shape, idx))
        return index(self, idx)

    monkeypatch.setattr(ArrayImpl, "__getitem__", counted)
    return calls


def _buffers(state):
    """Host copies of what a device state's history view stands on, taken
    without reading the view: the rows it must yield, oldest first."""
    hist = state.hist_s._hist
    S, Y = hist._bufs
    return S.shape, np.asarray(S)[hist._lo:], np.asarray(Y)[hist._lo:]


@pytest.mark.parametrize("chunk", [2, 8])
def test_history_leaves_the_turn_as_a_view(ctx, tracer, monkeypatch, chunk):
    """A turn launches nothing for the L-BFGS history: its state carries a
    view of the chunk's ring buffers, cut into rows only when read. chunk=8
    is the benchmark's fit (one terminal turn: the view IS the buffers);
    chunk=2 has turns that another dispatch follows — those DONATE S/Y, so
    each parts with them by one slice a buffer, and the state it yielded
    stays readable afterwards."""
    m, iters = 10, 6
    f, d = _loss(ctx, seed=11, reg=0.02)
    indexed = _count_device_indexing(monkeypatch)
    states = list(DeviceLBFGS(max_iter=iters, tol=0.0, chunk=chunk)
                  .iterations(f, np.zeros(d + 1)))
    turns = [s.attrs["history_launches"] for s in tracer.snapshot()
             if s.kind == "phase" and s.name == "optim.iteration"]
    # (a) nobody read the history: no read instant, and the only device
    # indexing of the run is what the non-terminal turns reported
    assert turns == ([0] if chunk == 8 else [2, 2, 0])
    assert len(indexed) == sum(turns)
    assert not _history_reads(tracer)
    assert states[-1].converged and states[0].hist_s == []

    host_f, _ = _loss(ctx, seed=11, reg=0.02)
    host = {s.iteration: s for s in LBFGS(max_iter=iters, tol=0.0)
            .iterations(host_f, np.zeros(d + 1))}
    for k, state in enumerate(states[1:], 1):
        hk = min(state.iteration, m)
        shape, want_s, want_y = _buffers(state)
        # a terminal turn's view stands on the (m, n) ring buffers
        # themselves, an earlier one's on its own slice of the live rows
        assert shape == ((m, d + 1) if state.converged else (hk, d + 1))
        assert len(state.hist_s) == len(state.hist_y) == hk
        assert not _history_reads(tracer)[k - 1:]   # len() reads nothing
        # (b) one read instant a state, whatever is read after the first
        tree = state.to_pytree()
        assert state.hist_s[-1] is tree["hist_s"][-1]
        assert all(a is b for a, b in zip(state.hist_y[-m:],
                                          tree["hist_y"], strict=True))
        reads = _history_reads(tracer)
        assert len(reads) == k and reads[-1].attrs["rows"] == hk
        # (c) the rows of the buffers, bit for bit, oldest first — and the
        # host optimizer's pairs at the same iteration (f64: same machine)
        for got, want, ref in ((tree["hist_s"], want_s, host[state.iteration].hist_s),
                               (tree["hist_y"], want_y, host[state.iteration].hist_y)):
            got = np.stack([np.asarray(r) for r in got])
            assert got.dtype == want.dtype
            np.testing.assert_array_equal(got, want)
            np.testing.assert_allclose(got, np.stack(ref), rtol=1e-6,
                                       atol=1e-12)


@pytest.mark.parametrize("onto", ["device", "host"])
def test_resume_from_the_view_equals_resume_from_lists(ctx, onto):
    """A state whose history is the view resumes — on the device chunk, or
    on the host L-BFGS a fit falls back to — exactly as one that carries
    the materialised lists: same iterations, same coefficients."""
    def fresh():
        return _loss(ctx, seed=9, reg=0.02)[0]
    f, d = _loss(ctx, seed=9, reg=0.02)
    opt = DeviceLBFGS(max_iter=24, tol=1e-12, chunk=4)
    full = opt.minimize(f, np.zeros(d + 1))
    it = opt.iterations(fresh(), np.zeros(d + 1))
    next(it)
    mid = next(it)                       # not terminal: 4 of 24 iterations
    assert not mid.converged and not isinstance(mid.hist_s, list)
    _, rows_s, rows_y = _buffers(mid)
    listed = dataclasses.replace(mid, hist_s=list(rows_s),
                                 hist_y=list(rows_y))
    next(it)                             # the view outlives a dispatch
    to = opt if onto == "device" else LBFGS(max_iter=24, tol=1e-12)
    from_view = to.minimize(fresh(), np.zeros(d + 1), resume=mid)
    from_lists = to.minimize(fresh(), np.zeros(d + 1), resume=listed)
    assert from_view.iteration == from_lists.iteration
    assert from_view.loss_history == from_lists.loss_history
    np.testing.assert_array_equal(from_view.x, from_lists.x)
    np.testing.assert_allclose(from_view.x, full.x, rtol=1e-8, atol=1e-10)


def test_lr_estimator_uses_device_chunk(ctx):
    from cycloneml_tpu.conf import LBFGS_DEVICE_CHUNK
    from cycloneml_tpu.dataset.frame import MLFrame
    from cycloneml_tpu.ml.classification import LogisticRegression
    rng = np.random.RandomState(11)
    x = rng.randn(300, 8)
    y = (x @ rng.randn(8) > 0).astype(np.float64)
    frame = MLFrame(ctx, {"features": x, "label": y})
    m1 = LogisticRegression(maxIter=40, regParam=0.05, tol=1e-9).fit(frame)
    assert m1.summary.total_dispatches < m1.summary.total_evals
    # disabling the chunk reproduces the same model via the host loop
    old = ctx.conf.get(LBFGS_DEVICE_CHUNK)
    ctx.conf.set(LBFGS_DEVICE_CHUNK, 0)
    try:
        m0 = LogisticRegression(maxIter=40, regParam=0.05, tol=1e-9).fit(frame)
    finally:
        ctx.conf.set(LBFGS_DEVICE_CHUNK, old)
    np.testing.assert_allclose(m1.coefficients.to_array(),
                               m0.coefficients.to_array(),
                               rtol=1e-6, atol=1e-9)


class TestResolutionRule:
    """``loss.wolfe_search`` ends where float32 cannot resolve the decrease
    on offer (``lbfgs.OWLQN._search``'s rule, PR 30) instead of bisecting
    its whole budget away."""

    @staticmethod
    def _search(phi, value0, dg0, dtype, active=None):
        import jax.numpy as jnp
        from cycloneml_tpu.ml.optim.loss import wolfe_search
        cdt = np.dtype(dtype)
        value0 = jnp.asarray(value0, cdt)
        return wolfe_search(phi, jnp.zeros(jnp.shape(value0) + (2,), cdt),
                            value0, jnp.asarray(dg0, cdt),
                            jnp.ones(jnp.shape(value0), cdt), 1e-4, 0.9, 30,
                            cdt, active=active)

    @staticmethod
    def _flat(slope):
        """φ at the bottom of a float32 objective: every trial reads an ulp
        ABOVE the start."""
        import jax.numpy as jnp

        def phi(alpha):
            return (jnp.float32(1.0) + jnp.float32(2.0 ** -23),
                    jnp.full((2,), alpha, jnp.float32), jnp.float32(slope))
        return phi

    def test_a_trial_that_fails_armijo_by_rounding_ends_the_search(self):
        """Armijo fails by an ulp, and would for every smaller step: the
        search ends after that one trial. The trial read ABOVE the start,
        so what comes back is the empty step — α = 0, the start's own value
        and the start's gradient (``g0``, zeros here; φ's is ones)."""
        alpha, v, g, evals = self._search(self._flat(-1e-9), 1.0, -1e-9,
                                          np.float32)
        assert int(evals) == 1 and float(alpha) == 0.0
        assert float(v) == 1.0
        assert g.tolist() == [0.0, 0.0]

    def test_a_trial_that_lowers_the_value_is_kept(self):
        """The resolution exit on a trial BELOW the start (it fails the
        curvature condition with a positive slope, and a zoom would only go
        to smaller steps): that trial is the result, gradient and all."""
        import jax.numpy as jnp

        def phi(alpha):
            return (jnp.float32(1.0) - jnp.float32(2.0 ** -23),
                    jnp.full((2,), 7.0, jnp.float32), jnp.float32(1.0))

        alpha, v, g, evals = self._search(phi, 1.0, -1e-9, np.float32)
        assert int(evals) == 1 and float(alpha) == 1.0
        assert float(v) == np.float32(1.0) - np.float32(2.0 ** -23)
        assert g.tolist() == [7.0, 7.0]

    def test_the_zoom_stops_at_the_resolution_not_at_its_budget(self):
        """A slope the accumulator resolves at α = 1 but not a few
        bisections later: the zoom ends there."""
        _, _, _, evals = self._search(self._flat(-1e-6), 1.0, -1e-6,
                                      np.float32)
        # eps·|F| = 1.2e-7: α = 1, 1/2, 1/4, 1/8, then 1/16 offers 6e-8
        assert int(evals) == 5

    def test_lanes_keep_their_own_searches(self):
        """Batched: the unresolved lane stops at once, the sound lane runs
        its own bracket to the end and neither holds the other."""
        import jax.numpy as jnp

        def phi(alpha):
            # lane 0: flat to rounding; lane 1: (α - 30)^2 / 2
            v = jnp.stack([jnp.float32(1.0) + jnp.float32(2.0 ** -23),
                           0.5 * (alpha[1] - 30.0) ** 2])
            dg = jnp.stack([jnp.float32(-1e-9), alpha[1] - 30.0])
            return v.astype(jnp.float32), jnp.zeros((2, 2), jnp.float32), \
                dg.astype(jnp.float32)

        alpha, v, _, evals = self._search(
            phi, np.array([1.0, 450.0]), np.array([-1e-9, -30.0]),
            np.float32)
        # lane 0 takes the empty step; lane 1 doubles its step until the
        # curvature condition holds
        assert evals.tolist() == [1, 3] and alpha.tolist() == [0.0, 4.0]
        assert v.tolist() == [1.0, 338.0]

    @pytest.mark.parametrize("stacked", [False, True])
    def test_a_chunk_drops_the_step_that_raised_its_objective(self, stacked):
        """A float32 chunk over an objective whose value stops resolving
        (a quadratic riding on 1e4): the run ends on the value test after a
        handful of evaluations, and its history never rises — a search that
        ended on rounding above its start hands back the empty step."""
        import jax.numpy as jnp
        from cycloneml_tpu.ml.optim import device_lbfgs
        cdt = np.dtype(np.float32)
        target = jnp.asarray([0.3, -0.2, 0.1, 0.7], jnp.float32)
        curv = jnp.asarray([1.0, 7.0, 30.0, 100.0], jnp.float32)

        def compiled(coef):
            r = coef - target
            return {"loss": 1e4 + 0.5 * jnp.sum(curv * r * r, axis=-1),
                    "grad": curv * r}

        n, m, iters = 4, 10, 40
        if stacked:
            prog = device_lbfgs._build_stacked_chunk(
                compiled, m, iters, 1e-4, 0.9, 30, cdt, n_arrays=0)
            out = prog(np.zeros((2, n), cdt), np.zeros((2, m, n), cdt),
                       np.zeros((2, m, n), cdt), np.zeros(2, np.int32),
                       np.zeros(2, cdt), np.zeros((2, n), cdt),
                       np.bool_(True), cdt.type(1.0), np.zeros(2, cdt),
                       np.zeros(n, cdt), cdt.type(0.0), cdt.type(0.0),
                       np.int32(iters), np.bool_(True),
                       np.zeros(2, np.int32))
            losses, steps, evals = out[6][0], int(out[7]), int(out[10])
            codes = out[11].tolist()
        else:
            prog = device_lbfgs._build_chunk(
                compiled, None, m, iters, 1e-4, 0.9, 30, cdt, n_arrays=0)
            out = prog(np.zeros(n, cdt), np.zeros((m, n), cdt),
                       np.zeros((m, n), cdt), np.int32(0), cdt.type(0.0),
                       np.zeros(n, cdt), np.bool_(True), cdt.type(1.0),
                       cdt.type(0.0), cdt.type(0.0), np.int32(iters),
                       np.bool_(True))
            losses, steps, evals = out[6], int(out[7]), int(out[8])
            codes = [int(out[9])]
        hist = [float(v) for v in np.asarray(losses)[:steps]]
        assert all(b <= a for a, b in zip(hist, hist[1:])), hist
        # tol = 0: what stops the run is a step that no longer moves the
        # float32 value (or a gradient of exactly zero) — reached without
        # bisecting a budget away
        assert all(c != 0 for c in codes) and steps < iters, (codes, steps)
        assert evals <= 2 * steps + 6, (evals, steps)

    def test_float64_searches_are_what_they_were(self):
        """The rule is ``eps`` of the accumulator: in float64 a slope of
        1e-9 is far above it, and the search bisects on as before."""
        import jax.numpy as jnp

        def phi(alpha):
            return (jnp.float64(1.0) + 1e-12, jnp.zeros((2,), jnp.float64),
                    jnp.float64(-1e-9))

        _, _, _, evals = self._search(phi, 1.0, -1e-9, np.float64)
        assert int(evals) > 10

    def test_the_host_driver_never_takes_a_raised_step_in_float32(
            self, ctx, monkeypatch):
        """``DistributedLossFunction.device_line_search`` under the host
        ``LBFGS`` with a float32 accumulator (the chip's tier): run to the
        floor (tol = 0), the history never rises, the run ends on the empty
        step's |Δf| = 0 and no search bisects a budget away."""
        from cycloneml_tpu.dataset import instance
        from cycloneml_tpu.dataset.dataset import InstanceDataset
        from cycloneml_tpu.ml.optim import aggregators
        from cycloneml_tpu.ml.optim.lbfgs import LBFGS
        from cycloneml_tpu.ml.optim.loss import (DistributedLossFunction,
                                                 l2_regularization)
        monkeypatch.setattr(instance, "compute_dtype", lambda: np.float32)
        rng = np.random.RandomState(2)
        n, d = 600, 32
        x = rng.randn(n, d)
        y = (x @ rng.randn(d) + rng.randn(n) > 0).astype(np.float64)
        ds = InstanceDataset.from_numpy(ctx, x, y)
        loss = DistributedLossFunction(
            ds, aggregators.binary_logistic(d, fit_intercept=True),
            l2_regularization(0.01, d, True, standardize=True))
        assert loss.accumulator_dtype == np.float32
        st = LBFGS(max_iter=100, tol=0.0).minimize(loss, np.zeros(d + 1))
        hist = st.loss_history
        assert all(b <= a for a, b in zip(hist, hist[1:])), hist
        assert st.converged_reason == "function value converged", st
        assert st.iteration < 100
        assert loss.n_evals <= 2 * st.iteration + 6, \
            (loss.n_evals, st.iteration)
