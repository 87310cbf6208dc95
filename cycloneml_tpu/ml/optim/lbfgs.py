"""Driver-side quasi-Newton optimizers.

Re-implements the semantics of Breeze's ``LBFGS`` / ``OWLQN`` as used by the
reference's estimators (ref: ml/classification/LogisticRegression.scala:25
imports breeze LBFGS/OWLQN; createOptimizer:777-814; mllib/optimization/
LBFGS.scala:37 runLBFGS:183) — NOT a port of Breeze: a clean
Nocedal–Wright L-BFGS with strong-Wolfe line search (what Breeze's
``StrongWolfeLineSearch`` implements), two-loop recursion with history
m=10 (Spark's default ``aggregationDepth``-independent corrections), initial
Hessian scaling γ = sᵀy/yᵀy, and Breeze-compatible convergence tests
(max iterations; relative function-value improvement ≤ tol; gradient-norm
ratio). OWL-QN adds the L1 pseudo-gradient and orthant projection, and
searches the line as Andrew & Gao's OWL-QN and Breeze's
``OWLQN.determineStepSize`` do: Armijo backtracking on the penalised,
orthant-projected objective (``OWLQN._search``). Strong Wolfe is for the
smooth objectives of ``LBFGS`` / ``LBFGSB`` only: under an L1 term the slope
of the smooth part along the ray is not the slope of the objective, so a
curvature test on it cannot be met near the optimum.

The loss/grad callable is typically the jit-compiled mesh aggregation
(psum over ICI); optimizer state stays on the host in float64 — exactly the
reference's driver-side Breeze arrangement (SURVEY §3.3).

Written once, here: the strong-Wolfe search is the coroutine
``_wolfe_search`` (``_strong_wolfe`` drives it with a callable, after
trying the objective's fused ``device_line_search``); the decisions of one
L-BFGS turn are ``LBFGS._direction`` / ``_advance`` / ``_converged``, which
both ``LBFGS.iterations`` and the coroutine ``LBFGS._minimize_co``
(``device_lbfgs.StackedHostLBFGS`` runs K of them on one batched objective)
use; and which optimizer serves an objective is ``optimizer_for``'s to say.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, List, Optional, Tuple

import numpy as np

from cycloneml_tpu.observe import tracing
from cycloneml_tpu.util.logging import get_logger

logger = get_logger(__name__)

LossGrad = Callable[[np.ndarray], Tuple[float, np.ndarray]]


def _turn(iteration: int):
    """The ``optim.iteration`` phase span of one turn of a host loop: the
    initial evaluation (``iteration`` 0) or one quasi-Newton iteration —
    direction, line search (its ``dispatch`` spans nest here), update and
    convergence test. Its self time is the host optimizer's own work. A
    turn opens and closes its span between two yields, never across one:
    the consumer's time is not the optimizer's, and an abandoned generator
    must leave the thread's span stack as it found it."""
    return tracing.span("phase", "optim.iteration", iteration=iteration)


@dataclass
class OptimState:
    x: np.ndarray
    value: float
    grad: np.ndarray
    iteration: int = 0
    converged: bool = False
    converged_reason: str = ""
    loss_history: List[float] = field(default_factory=list)
    # curvature memory, carried so training can checkpoint/resume EXACTLY
    # (the reference has no mid-training checkpointing at all — SURVEY §5.4
    # flags step-level checkpoint as the required improvement). Lists, oldest
    # pair first; a DeviceLBFGS state carries a read-on-demand Sequence that
    # reads like one (device_lbfgs._HistoryView)
    hist_s: List[np.ndarray] = field(default_factory=list)
    hist_y: List[np.ndarray] = field(default_factory=list)
    raw_grad: Optional[np.ndarray] = None  # OWLQN: grad before pseudo-grad
    # OWLQN: evaluations per turn — [1] for the initial evaluation, then one
    # entry per iteration's line search ("4 iterations, 1+1+1+1+1"); sums to
    # the evaluations the optimizer asked of the loss function
    search_evals: List[int] = field(default_factory=list)

    def to_pytree(self) -> dict:
        return {"x": self.x, "value": self.value, "grad": self.grad,
                "iteration": self.iteration,
                "converged": self.converged,
                "converged_reason": self.converged_reason,
                "loss_history": list(self.loss_history),
                "hist_s": list(self.hist_s), "hist_y": list(self.hist_y),
                "raw_grad": self.raw_grad,
                "search_evals": list(self.search_evals)}

    @classmethod
    def from_pytree(cls, t: dict) -> "OptimState":
        return cls(x=np.asarray(t["x"]), value=float(t["value"]),
                   grad=np.asarray(t["grad"]), iteration=int(t["iteration"]),
                   converged=bool(t.get("converged", False)),
                   converged_reason=str(t.get("converged_reason", "")),
                   loss_history=[float(v) for v in t["loss_history"]],
                   hist_s=[np.asarray(s) for s in t["hist_s"]],
                   hist_y=[np.asarray(y) for y in t["hist_y"]],
                   raw_grad=(np.asarray(t["raw_grad"])
                             if t.get("raw_grad") is not None else None),
                   search_evals=[int(n) for n in t.get("search_evals", [])])


class _History:
    """L-BFGS curvature-pair memory (two-loop recursion)."""

    def __init__(self, m: int):
        self.m = m
        self.s: List[np.ndarray] = []
        self.y: List[np.ndarray] = []

    def update(self, s: np.ndarray, y: np.ndarray) -> None:
        # curvature condition: keep the pair only if sᵀy is safely positive
        if float(np.dot(s, y)) > 1e-10 * float(np.dot(y, y)):
            self.s.append(s)
            self.y.append(y)
            if len(self.s) > self.m:
                self.s.pop(0)
                self.y.pop(0)

    def reset(self) -> None:
        self.s, self.y = [], []

    def direction(self, grad: np.ndarray) -> np.ndarray:
        q = grad.copy()
        k = len(self.s)
        alpha = np.empty(k)
        rho = np.empty(k)
        for i in range(k - 1, -1, -1):
            rho[i] = 1.0 / np.dot(self.y[i], self.s[i])
            alpha[i] = rho[i] * np.dot(self.s[i], q)
            q -= alpha[i] * self.y[i]
        if k > 0:
            gamma = np.dot(self.s[-1], self.y[-1]) / np.dot(self.y[-1], self.y[-1])
            q *= gamma
        for i in range(k):
            beta = rho[i] * np.dot(self.y[i], q)
            q += (alpha[i] - beta) * self.s[i]
        return -q


def _descent_slope(direction: np.ndarray, grad: np.ndarray) -> float:
    """φ'(0) = d·g of a search along ``direction``; a search needs it
    negative."""
    slope = float(np.dot(direction, grad))
    if slope >= 0:
        raise ValueError("direction is not a descent direction")
    return slope


def _phi_eval(x, direction, alpha):
    """One φ(α) evaluation of a search coroutine: yields the trial point,
    receives ``(value, grad)`` from whoever drives it."""
    v, g = yield x + alpha * direction
    g = np.asarray(g, dtype=np.float64)
    return float(v), g, float(np.dot(direction, g))


def _zoom(x, direction, value, slope, lo, hi, v_lo, c1, c2, max_evals):
    """Nocedal & Wright alg. 3.6 by bisection (Breeze interpolates;
    bisection keeps the same Wolfe guarantees and is deterministic).
    Returns the last point evaluated when the budget or the bracket runs
    out before a Wolfe point is found."""
    best = None
    for _ in range(max_evals):
        alpha = 0.5 * (lo + hi)
        v, g, dg = yield from _phi_eval(x, direction, alpha)
        if v > value + c1 * alpha * slope or v >= v_lo:
            hi = alpha
        else:
            if abs(dg) <= -c2 * slope:
                return alpha, v, g
            if dg * (hi - lo) >= 0:
                hi = lo
            lo, v_lo = alpha, v
        best = (alpha, v, g)
        if abs(hi - lo) < 1e-12:
            break
    return best


def _wolfe_search(x, value, slope, direction, init_alpha,
                  c1=1e-4, c2=0.9, max_evals=30):
    """THE host strong-Wolfe search (Nocedal & Wright alg. 3.5/3.6 — the
    scheme Breeze's StrongWolfeLineSearch follows) as a coroutine: every
    φ(α) is a ``yield`` of the trial point, answered with
    ``send((value, grad))``. ``_strong_wolfe`` drives one with a callable;
    ``StackedHostLBFGS`` drives K of them with one batched evaluation a
    round. Returns ``(alpha, f(x+αd), g)`` via StopIteration."""
    alpha_prev, v_prev = 0.0, value
    alpha = init_alpha
    for i in range(max_evals):
        v, g, dg = yield from _phi_eval(x, direction, alpha)
        if v > value + c1 * alpha * slope or (i > 0 and v >= v_prev):
            return (yield from _zoom(x, direction, value, slope,
                                     alpha_prev, alpha, v_prev,
                                     c1, c2, max_evals))
        if abs(dg) <= -c2 * slope:
            return alpha, v, g
        if dg >= 0:
            return (yield from _zoom(x, direction, value, slope,
                                     alpha, alpha_prev, v,
                                     c1, c2, max_evals))
        alpha_prev, v_prev = alpha, v
        alpha *= 2.0
    # fall back to the last evaluated point if Wolfe could not be satisfied
    v, g, _ = yield from _phi_eval(x, direction, alpha)
    return alpha, v, g


def _strong_wolfe(f: LossGrad, x: np.ndarray, value: float, grad: np.ndarray,
                  direction: np.ndarray, init_alpha: float = 1.0,
                  c1: float = 1e-4, c2: float = 0.9,
                  max_evals: int = 30) -> Tuple[float, float, np.ndarray]:
    """Strong-Wolfe line search along ``direction`` with a callable
    objective. Returns (alpha, f(x+αd), g)."""
    slope = _descent_slope(direction, grad)

    # fused path: a DistributedLossFunction runs the whole bracket+zoom
    # search in ONE device dispatch (vs one dispatch per phi eval here)
    fused = getattr(f, "device_line_search", None)
    if fused is not None:
        out = fused(x, direction, value, grad, slope, init_alpha,
                    c1, c2, max_evals)
        if out is not None:
            return out

    search = _wolfe_search(x, value, slope, direction, init_alpha,
                           c1, c2, max_evals)
    try:
        trial = next(search)
        while True:
            trial = search.send(f(trial))
    except StopIteration as fin:
        return fin.value


def _steepest_alpha(grad: np.ndarray) -> float:
    """First trial step along ``-grad``: min(1, 1/‖g‖)."""
    return min(1.0, 1.0 / max(float(np.linalg.norm(grad)), 1e-12))


def _reopen(resume: OptimState, max_iter: int) -> OptimState:
    """'max iterations reached' is a budget stop, not convergence: a resumed
    run with a larger budget continues (real convergence reasons hold)."""
    import dataclasses
    if (resume.converged
            and resume.converged_reason == "max iterations reached"
            and resume.iteration < max_iter):
        return dataclasses.replace(resume, converged=False,
                                   converged_reason="")
    return resume


class LBFGS:
    """Limited-memory BFGS (Breeze-LBFGS semantics).

    Convergence mirrors Breeze's FirstOrderMinimizer checks used by the
    reference: maxIter; |Δf| ≤ tol·max(|f|,|f'|,1e-6) (relative improvement);
    ‖g‖/max(‖x‖,1) ≤ tol-ish gradient test.
    """

    def __init__(self, max_iter: int = 100, m: int = 10, tol: float = 1e-6,
                 grad_tol: Optional[float] = None):
        self.max_iter = max_iter
        self.m = m
        self.tol = tol
        self.grad_tol = grad_tol if grad_tol is not None else tol

    def _converged(self, state: OptimState, f_old: float) -> Optional[str]:
        if state.iteration >= self.max_iter:
            return "max iterations reached"
        denom = max(abs(state.value), abs(f_old), 1e-6)
        if abs(f_old - state.value) <= self.tol * denom:
            return "function value converged"
        gnorm = float(np.linalg.norm(state.grad))
        if gnorm <= self.grad_tol * max(float(np.linalg.norm(state.x)), 1.0):
            return "gradient converged"
        return None

    # -- the decisions of one turn, shared by both loops below ---------------
    @staticmethod
    def _start(x0: np.ndarray, value: float, grad: np.ndarray) -> OptimState:
        value = float(value)
        return OptimState(x=x0, value=value,
                          grad=np.asarray(grad, dtype=np.float64),
                          loss_history=[value])

    @staticmethod
    def _direction(hist: _History, state: OptimState
                   ) -> Tuple[np.ndarray, float]:
        """Search direction and first trial step of the turn after
        ``state``. The two-loop direction starts at α = 1; steepest descent
        — the very first turn, and the retry after a non-descent direction
        reset the curvature memory (Breeze retries) — at min(1, 1/‖g‖)."""
        grad = state.grad
        d = hist.direction(grad)
        if float(np.dot(d, grad)) >= 0:
            hist.reset()
            return -grad, _steepest_alpha(grad)
        return d, (1.0 if state.iteration > 0 else _steepest_alpha(grad))

    def _advance(self, hist: _History, state: OptimState, d: np.ndarray,
                 alpha: float, v_new: float, g_new: np.ndarray) -> OptimState:
        """The state after the step ``alpha·d``: curvature pair, history,
        convergence test."""
        x_new = state.x + alpha * d
        g_new = np.asarray(g_new, dtype=np.float64)
        hist.update(x_new - state.x, g_new - state.grad)
        new = OptimState(
            x=x_new, value=float(v_new), grad=g_new,
            iteration=state.iteration + 1,
            loss_history=state.loss_history + [float(v_new)],
            hist_s=list(hist.s), hist_y=list(hist.y))
        reason = self._converged(new, state.value)
        if reason is not None:
            new.converged = True
            new.converged_reason = reason
        return new

    def iterations(self, f: LossGrad, x0: np.ndarray,
                   resume: Optional[OptimState] = None):
        """Generator of OptimState per iteration (like Breeze .iterations).
        Pass a checkpointed ``resume`` state to continue exactly where a
        previous run stopped (same curvature memory → identical trajectory)."""
        hist = _History(self.m)
        if resume is not None:
            state = _reopen(resume, self.max_iter)
            hist.s = [np.asarray(s) for s in resume.hist_s]
            hist.y = [np.asarray(y) for y in resume.hist_y]
        else:
            with _turn(0):
                x = np.asarray(x0, dtype=np.float64).copy()
                state = self._start(x, *f(x))
        yield state
        if state.converged:
            return  # resumed from a finished checkpoint: nothing to do
        while True:
            with _turn(state.iteration + 1):
                d, init_alpha = self._direction(hist, state)
                alpha, v_new, g_new = _strong_wolfe(
                    f, state.x, state.value, state.grad, d, init_alpha)
                state = self._advance(hist, state, d, alpha, v_new, g_new)
            yield state
            if state.converged:
                return

    def _minimize_co(self, x0: np.ndarray, c1: float = 1e-4,
                     c2: float = 0.9, max_ls: int = 30):
        """:meth:`minimize` as a coroutine: every loss/grad evaluation is a
        ``yield x`` answered by ``send((value, grad))``, so K of these can
        share one batched evaluation a round (``StackedHostLBFGS``). Same
        decisions as :meth:`iterations`, so identical replies give the
        identical trajectory. Returns the terminal state via StopIteration."""
        hist = _History(self.m)
        x = np.asarray(x0, dtype=np.float64).copy()
        state = self._start(x, *(yield x))
        while not state.converged:
            d, init_alpha = self._direction(hist, state)
            alpha, v_new, g_new = yield from _wolfe_search(
                state.x, state.value, _descent_slope(d, state.grad), d,
                init_alpha, c1, c2, max_ls)
            state = self._advance(hist, state, d, alpha, v_new, g_new)
        return state

    def minimize(self, f: LossGrad, x0: np.ndarray,
                 resume: Optional[OptimState] = None) -> OptimState:
        state = None
        for state in self.iterations(f, x0, resume=resume):
            pass
        return state


class LBFGSB(LBFGS):
    """Box-constrained L-BFGS (Breeze-LBFGSB semantics — the optimizer the
    reference selects whenever coefficient bounds are set,
    ref LogisticRegression.scala:788 ``new BreezeLBFGSB(lowerBounds,
    upperBounds, ...)``).

    Projected-gradient formulation: the quasi-Newton direction is built from
    the PROJECTED gradient (components at an active bound pointing outward
    are clipped to zero), every line-search trial point is projected into
    the box, and convergence tests use the projected gradient — the same
    fixed points as Byrd-Lu-Nocedal-Zhu without its generalized-Cauchy
    subspace machinery (scipy's L-BFGS-B is the parity oracle in tests).

    Line searches run on the HOST (one device dispatch per φ evaluation):
    the box projection sits between the optimizer and the loss, so the fused
    device-resident search does not apply. That still beats the reference's
    structure — Breeze LBFGSB is host-driven with one Spark job per
    evaluation — but bounded fits cost more dispatches per iteration than
    unbounded ones.
    """

    def __init__(self, lower: np.ndarray, upper: np.ndarray,
                 max_iter: int = 100, m: int = 10, tol: float = 1e-6,
                 grad_tol: Optional[float] = None):
        super().__init__(max_iter, m, tol, grad_tol)
        self.lower = np.asarray(lower, dtype=np.float64)
        self.upper = np.asarray(upper, dtype=np.float64)
        if np.any(self.lower > self.upper):
            raise ValueError("lower bound exceeds upper bound")

    def _clip(self, x: np.ndarray) -> np.ndarray:
        return np.clip(x, self.lower, self.upper)

    def _projected_grad(self, x: np.ndarray, grad: np.ndarray) -> np.ndarray:
        """Gradient with active-bound components pointing outward zeroed
        (the 'gradient clipping at active bounds' of the reference's
        bound-constrained path)."""
        at_lo = (x <= self.lower) & (grad > 0)
        at_hi = (x >= self.upper) & (grad < 0)
        return np.where(at_lo | at_hi, 0.0, grad)

    def iterations(self, f: LossGrad, x0: np.ndarray,
                   resume: Optional[OptimState] = None):
        hist = _History(self.m)
        if resume is not None:
            state = _reopen(resume, self.max_iter)
            hist.s = [np.asarray(s) for s in resume.hist_s]
            hist.y = [np.asarray(y) for y in resume.hist_y]
            raw_grad = (np.asarray(resume.raw_grad)
                        if resume.raw_grad is not None else resume.grad)
        else:
            with _turn(0):
                x = self._clip(np.asarray(x0, dtype=np.float64))
                value, grad = f(x)
                raw_grad = np.asarray(grad, dtype=np.float64)
                state = OptimState(x=x, value=float(value),
                                   grad=self._projected_grad(x, raw_grad),
                                   raw_grad=raw_grad)
                state.loss_history.append(state.value)
            if not np.any(state.grad):
                # the (clipped) start is already a KKT point of the box —
                # degenerate bounds (lower == upper) land here too
                state.converged = True
                state.converged_reason = "gradient converged"
        yield state
        if state.converged:
            return
        while True:
            if not np.any(state.grad):
                import dataclasses
                state = dataclasses.replace(
                    state, converged=True,
                    converged_reason="gradient converged")
                yield state
                return
            with _turn(state.iteration + 1):
                d = hist.direction(state.grad)
                # zero direction components that would immediately leave the box
                at_lo = (state.x <= self.lower) & (d < 0)
                at_hi = (state.x >= self.upper) & (d > 0)
                d = np.where(at_lo | at_hi, 0.0, d)
                if not np.any(d):
                    d = -state.grad

                def f_boxed(xt: np.ndarray):
                    xt = self._clip(xt)
                    v, g = f(xt)
                    return float(v), np.asarray(g, dtype=np.float64)

                init_alpha = 1.0 if state.iteration > 0 else \
                    _steepest_alpha(state.grad)
                if float(np.dot(d, state.grad)) >= 0:
                    # non-descent: reset and retry along steepest descent
                    hist.reset()
                    d, init_alpha = -state.grad, _steepest_alpha(state.grad)
                alpha, v_new, g_new = _strong_wolfe(
                    f_boxed, state.x, state.value, state.grad, d, init_alpha)
                x_new = self._clip(state.x + alpha * d)
                raw_grad_new = np.asarray(g_new, dtype=np.float64)
                pg_new = self._projected_grad(x_new, raw_grad_new)
                # reduced-space curvature: pairs are only meaningful within one
                # face of the box. When the active set changes, old pairs
                # describe a different subspace — drop them (the classic
                # active-set restart); within a face, mask y to the free
                # coordinates so the two-loop recursion models the reduced
                # Hessian (s is already zero at active coordinates).
                active_new = (x_new <= self.lower) | (x_new >= self.upper)
                active_old = (state.x <= self.lower) | (state.x >= self.upper)
                if not np.array_equal(active_new, active_old):
                    hist = _History(self.m)
                else:
                    free = ~active_new
                    hist.update((x_new - state.x) * free,
                                (raw_grad_new - raw_grad) * free)
                f_old = state.value
                raw_grad = raw_grad_new
                state = OptimState(
                    x=x_new, value=float(v_new), grad=pg_new,
                    iteration=state.iteration + 1,
                    loss_history=state.loss_history + [float(v_new)],
                    hist_s=list(hist.s), hist_y=list(hist.y),
                    raw_grad=raw_grad_new)
                reason = self._converged(state, f_old)
                if reason is not None:
                    state.converged = True
                    state.converged_reason = reason
            yield state
            if state.converged:
                return


class OWLQN(LBFGS):
    """Orthant-wise limited-memory quasi-Newton for L1 regularization
    (Breeze-OWLQN semantics; selected by the reference when elasticNet has an
    L1 component, ref LogisticRegression.scala:814).

    ``l1_reg`` may be a scalar or per-coordinate array (the reference passes
    0 for the intercept and per-feature values under standardization).

    Line searches run on the HOST, one evaluation per trial step (for a
    streamed fit: one epoch), and usually one trial: see :meth:`_search`.
    """

    def __init__(self, max_iter: int = 100, m: int = 10, tol: float = 1e-6,
                 l1_reg=0.0):
        super().__init__(max_iter, m, tol)
        self.l1_reg = l1_reg

    def _l1(self, x: np.ndarray) -> float:
        return float(np.sum(np.abs(x) * self.l1_reg))

    def _pseudo_grad(self, x: np.ndarray, grad: np.ndarray) -> np.ndarray:
        """Sub-gradient of f + λ‖x‖₁ choosing the steepest-descent element."""
        lam = np.broadcast_to(np.asarray(self.l1_reg, dtype=np.float64), x.shape)
        pg = np.where(x > 0, grad + lam, np.where(x < 0, grad - lam, 0.0))
        at_zero = (x == 0)
        pg = np.where(at_zero & (grad + lam < 0), grad + lam, pg)
        pg = np.where(at_zero & (grad - lam > 0), grad - lam, pg)
        return pg

    def _search(self, f: LossGrad, state: OptimState, raw_grad: np.ndarray,
                d: np.ndarray, orthant: np.ndarray, init_alpha: float):
        """OWL-QN's line search: backtrack from ``init_alpha`` by halves to
        the first α with sufficient decrease of the PENALISED objective at
        the orthant-projected point, ``F(π(x + αd)) ≤ F(x) + c1·α·d·pg``,
        and nothing else. The slope is Breeze's (``OWLQN.determineStepSize``
        backtracks on ``dir·adjustedGradient``) rather than Andrew & Gao's
        per-trial ``pg·(π(x + αd) − x)``: it is one number a search,
        negative whatever ``l1_reg`` is (with no L1 share ``d`` is not
        projected, and a slope over the clipped step need not be), and at
        c1 = 1e-4 the two differ by a ten-thousandth of what the clipped
        coordinates were predicted to give. No curvature condition:
        ``d·∇f`` along the ray is the slope of the smooth part only (it
        differs from φ' by ``d·(λ·sign x)``, which does not vanish at the
        optimum), and ``_History.update`` keeps a pair only when ``sᵀy`` is
        safely positive, which is all a Wolfe condition would buy.

        The search also ends when the first-order decrease still on offer,
        ``α·|d·pg|``, is below what the objective resolves: ``eps·|F(x)|``,
        ``eps`` of the dtype the loss function accumulates in
        (``f.accumulator_dtype``; float64 for a plain callable). A trial
        that fails Armijo there fails by rounding, and every smaller α
        offers less. It then keeps the lowest point seen if that lies no
        more than the resolution above ``F(x)``; otherwise (as when all 30
        trials fail, the backstop) the step is empty and the caller's
        ``|Δf|`` test ends the run.

        Returns ``(alpha, x_new, value, raw_grad, evals, outcome)``,
        outcome ``first_trial`` / ``backtracked`` (Armijo held) or
        ``unresolved`` (no α could be certified)."""
        c1, max_evals = 1e-4, 30
        slope = float(np.dot(d, state.grad))
        if slope >= 0:
            raise ValueError("direction is not a descent direction")
        resolution = float(np.finfo(
            getattr(f, "accumulator_dtype", np.float64)).eps) * abs(state.value)
        best = (0.0, state.x, state.value, raw_grad)  # the empty step
        lowest = state.value + resolution
        alpha = init_alpha
        for evals in range(1, max_evals + 1):
            xt = state.x + alpha * d
            xt = np.where(xt * orthant >= 0, xt, 0.0)  # orthant projection
            v, g = f(xt)
            v = float(v) + self._l1(xt)
            g = np.asarray(g, dtype=np.float64)
            if v <= state.value + c1 * alpha * slope:
                return (alpha, xt, v, g, evals,
                        "first_trial" if evals == 1 else "backtracked")
            if v <= lowest:
                best, lowest = (alpha, xt, v, g), v
            if -alpha * slope <= resolution:
                break
            alpha *= 0.5
        return best + (evals, "unresolved")

    def iterations(self, f: LossGrad, x0: np.ndarray,
                   resume: Optional[OptimState] = None):
        hist = _History(self.m)
        if resume is not None:
            state = _reopen(resume, self.max_iter)
            x = np.asarray(resume.x, dtype=np.float64)
            hist.s = [np.asarray(s) for s in resume.hist_s]
            hist.y = [np.asarray(y) for y in resume.hist_y]
            raw_grad = (np.asarray(resume.raw_grad)
                        if resume.raw_grad is not None else resume.grad)
        else:
            with _turn(0):
                x = np.asarray(x0, dtype=np.float64).copy()
                value, grad = f(x)
                value = float(value) + self._l1(x)
                grad = np.asarray(grad, dtype=np.float64)
                state = OptimState(x=x, value=value,
                                   grad=self._pseudo_grad(x, grad),
                                   raw_grad=grad, search_evals=[1])
                state.loss_history.append(state.value)
                raw_grad = grad
        yield state
        if state.converged:
            return  # resumed from a finished checkpoint: nothing to do
        while True:
            with _turn(state.iteration + 1) as turn:
                d = hist.direction(state.grad)
                # project direction onto the pseudo-gradient descent orthant
                d = np.where(d * state.grad >= 0, 0.0, d) if self._has_l1() else d
                if not np.any(d):
                    d = -state.grad
                orthant = np.where(x != 0, np.sign(x), -np.sign(state.grad))
                steepest_alpha = _steepest_alpha(state.grad)
                try:
                    alpha, x_new, v_new, raw_grad_new, evals, outcome = \
                        self._search(
                            f, state, raw_grad, d, orthant,
                            1.0 if state.iteration > 0 else steepest_alpha)
                except ValueError:
                    alpha, x_new, v_new, raw_grad_new, evals, outcome = \
                        self._search(f, state, raw_grad, -state.grad, orthant,
                                     steepest_alpha)
                turn.annotate(search_evals=evals, alpha=alpha, search=outcome)
                pg_new = self._pseudo_grad(x_new, raw_grad_new)
                hist.update(x_new - state.x, raw_grad_new - raw_grad)
                f_old = state.value
                x = x_new
                raw_grad = raw_grad_new
                state = OptimState(
                    x=x_new, value=float(v_new), grad=pg_new,
                    iteration=state.iteration + 1,
                    loss_history=state.loss_history + [float(v_new)],
                    hist_s=list(hist.s), hist_y=list(hist.y),
                    raw_grad=raw_grad_new,
                    search_evals=state.search_evals + [evals])
                reason = self._converged(state, f_old)
                if reason is not None:
                    state.converged = True
                    state.converged_reason = reason
            yield state
            if state.converged:
                return

    def _has_l1(self) -> bool:
        return bool(np.any(np.asarray(self.l1_reg) > 0))


def optimizer_for(max_iter: int, tol: float, n_coef: int, *,
                  bounds: Optional[Tuple[np.ndarray, np.ndarray]] = None,
                  l1: float = 0.0, n_penalized: int = 0,
                  penalty_std: Optional[np.ndarray] = None) -> LBFGS:
    """The optimizer that serves an objective over ``n_coef`` coordinates
    (ref createOptimizer, LogisticRegression.scala:777-814): ``(lower,
    upper)`` bounds → :class:`LBFGSB`; an L1 share → :class:`OWLQN`; else
    :class:`LBFGS`.

    The L1 penalty is ``l1`` on the first ``n_penalized`` coordinates and 0
    on the rest (intercepts are never penalised). ``penalty_std`` — σ per
    penalised coordinate — asks for the penalty in the ORIGINAL feature
    space (``standardization=False``) while the coordinates live in the
    standardized one: ``l1/σ``, and 0 where σ = 0."""
    if bounds is not None:
        return LBFGSB(*bounds, max_iter=max_iter, tol=tol)
    if l1 > 0:
        l1_vec = np.zeros(n_coef)
        if penalty_std is None:
            l1_vec[:n_penalized] = l1
        else:
            std = np.asarray(penalty_std, dtype=np.float64)
            l1_vec[:n_penalized] = np.where(
                std > 0, l1 / np.where(std > 0, std, 1.0), 0.0)
        return OWLQN(max_iter=max_iter, tol=tol, l1_reg=l1_vec)
    return LBFGS(max_iter=max_iter, tol=tol)
