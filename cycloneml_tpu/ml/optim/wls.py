"""WeightedLeastSquares — the reference's normal-equation solver component.

Semantics port of ml/optim/WeightedLeastSquares.scala:101-326 and
NormalEquationSolver.scala:59-153 (CholeskySolver + QuasiNewtonSolver),
TPU-shaped: the moment aggregation (the reference's ``treeAggregate(new
Aggregator)``) is ONE ``tree_aggregate`` program over the dataset's
row-sharded arrays (``moments_aggregator``: one read of X at storage width,
psum over the mesh) producing {wSum, bSum, bbSum, aSum, abSum, aaSum}; the
(d+1)-sized standardized normal-equation solve then runs on the driver in
f64, exactly where the reference solves after its aggregate.

What the driver factors. The standardised system is a diagonal congruence
of the moments: with ``S = [[aaSum, aSum], [aSum', wSum]]`` and ``E =
diag(1/aStd, 1)`` it is ``E (S / wSum) E + diag(lam, 0)``, and where every
feature varies (``aStd > 0``) that is ``E (S' / wSum) E`` for ``S' = S +
wSum · diag(lam · aVar, 0)``. Cholesky's backward error does not depend
on a diagonal scaling of the matrix (Higham, *Accuracy and Stability of
Numerical Algorithms*, §10.1), so the Cholesky solver factors ``S'`` — the
block as the device delivered it, widened ONCE into the column-major
float64 array LAPACK works in — and the standardisation and its inverse
act on O(d) vectors, where they cancel: ``coef = bStd · t[:d]`` for ``S' t
= wSum · [abBar / bStd ; bBar]``. The O(d²) rescaling is built only
where it is needed: a feature that does not vary (its raw column is
collinear with the intercept, its standardised one is zero), the
quasi-Newton solver (the L1 penalty lives in standardised coordinates and
OWL-QN is not scale-invariant), and auto's fallback to it.
``WeightedLeastSquaresModel.system`` says which was solved.

Distinctions that matter for golden parity (and differ from the
LinearRegression l-bfgs path):

- moments are POPULATION-weighted (aVar = aaBar − aBar², divided by wSum)
  — glmnet's convention, NOT the Summarizer's unbiased denominator;
- the intercept is an APPENDED column of the standardized system (getAtA
  at :312), not a centering trick, and the quasi-Newton cost function
  pins it to bBar − aBar·β every evaluation (NormalEquationSolver.scala:
  134-144);
- zero-variance features get zero coefficients via the bStd/aStd=0
  mapping (:290);
- a constant label short-circuits with fitIntercept (or an all-zero
  label), refuses regularization when the label is standardized, and
  otherwise trains with bStd = |bBar| (:117-141).

GLM's IRLS and LinearRegression's 'normal' solver are this component's
estimator-level callers in the reference (SURVEY §2.3 optimizers row).
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

AUTO = "auto"
CHOLESKY = "cholesky"
QUASI_NEWTON = "quasi-newton"

MAX_NUM_FEATURES = 4096  # ref WeightedLeastSquares.MAX_NUM_FEATURES:335


class WeightedLeastSquaresModel:
    """``diag_inv_atwa`` — the diagonal of ``(AᵀWA)⁻¹`` the reference's
    summaries take standard errors from — may be handed over as a
    callable: the Cholesky solver's costs a second pass over its factor
    (LAPACK ``potri``), which a fit that never reads it does not pay.
    ``system`` names the matrix the solver was handed: ``"moments"`` (the
    moment block itself), ``"standardised"`` (its O(d²) rescaling), or
    None where a constant label needed no solve."""

    def __init__(self, coefficients: np.ndarray, intercept: float,
                 diag_inv_atwa, objective_history,
                 system: Optional[str] = None):
        self.coefficients = coefficients
        self.intercept = intercept
        self._diag_inv_atwa = diag_inv_atwa
        self.objective_history = list(objective_history)
        self.system = system

    @property
    def diag_inv_atwa(self) -> np.ndarray:
        if callable(self._diag_inv_atwa):
            self._diag_inv_atwa = self._diag_inv_atwa()
        return self._diag_inv_atwa

    def predict(self, x: np.ndarray) -> np.ndarray:
        return np.asarray(x) @ self.coefficients + self.intercept


@functools.lru_cache(maxsize=None)
def moments_aggregator(feature_major: bool = False):
    """The per-shard moment pass (ref Aggregator.add; the psum over shards
    is ``tree_aggregate``'s, replacing treeAggregate's merge): ``{w_sum,
    b_sum, bb_sum, a_sum, ab_sum, aa_sum}`` from ONE read of X at storage
    width (``ops/kernels.moment_sums``). Cached, so every fit of every
    dataset asks ``tree_aggregate`` for the same function and gets the same
    program (``jit_tree_aggregate__wls_moments`` in a device capture);
    ``feature_major`` is the caller's observation of how X is stored."""
    def wls_moments(x, y, w):
        from cycloneml_tpu.ops.kernels import moment_sums
        return moment_sums(x, y, w, feature_major=feature_major)
    return wls_moments


@functools.lru_cache(maxsize=None)
def _array_moments():
    """The same pass for bare arrays (the small callers: golden parity,
    IRLS-sized systems handed numpy): one jitted call, no mesh."""
    import jax
    return jax.jit(moments_aggregator(False))


def _normal_block(aa_sum, ridge, border, corner, scale=None):
    """The ONE column-major ``(k, k)`` float64 block LAPACK factors in
    place: ``[[aa_sum + diag(ridge), border], [border', corner]]`` (no
    ``border``: the core alone). ``aa_sum`` is symmetric, so its
    transpose is the same matrix in the layout of the target and the
    widening copy is contiguous; nothing rides in that copy (a ``multiply``
    of mixed widths goes through the ufunc's casting buffer, the slow
    way to widen). ``scale = (rows, columns)`` is the standardisation, two
    more passes over the core: ``aa_sum ⊙ rows columns'``."""
    d = aa_sum.shape[0]
    k = d if border is None else d + 1
    ata = np.empty((k, k), order="F")
    core = ata[:d, :d]
    if scale is None:
        core[...] = aa_sum.T
    else:
        np.multiply(aa_sum.T, scale[0][:, None], out=core)
        core *= scale[1][None, :]
    core[np.arange(d), np.arange(d)] += ridge
    if border is not None:
        ata[:d, d] = ata[d, :d] = border
        ata[d, d] = corner
    return ata


class WeightedLeastSquares:
    """Normal-equation WLS with the reference's exact solver semantics."""

    def __init__(self, fit_intercept: bool, reg_param: float = 0.0,
                 elastic_net_param: float = 0.0,
                 standardize_features: bool = True,
                 standardize_label: bool = True,
                 solver_type: str = AUTO,
                 max_iter: int = 100, tol: float = 1e-6):
        if reg_param < 0:
            raise ValueError("regParam must be >= 0")
        if not 0.0 <= elastic_net_param <= 1.0:
            raise ValueError("elasticNetParam must be in [0, 1]")
        if solver_type not in (AUTO, CHOLESKY, QUASI_NEWTON):
            raise ValueError(f"unknown solver {solver_type!r}")
        self.fit_intercept = fit_intercept
        self.reg_param = float(reg_param)
        self.elastic_net_param = float(elastic_net_param)
        self.standardize_features = standardize_features
        self.standardize_label = standardize_label
        self.solver_type = solver_type
        self.max_iter = max_iter
        self.tol = tol
        # passes over the data so far, each one dispatch: the count the
        # estimator's training summary states
        self.n_passes = 0

    # -- public ----------------------------------------------------------
    def fit(self, x, y=None, w: Optional[np.ndarray] = None
            ) -> WeightedLeastSquaresModel:
        """Fit an in-core ``InstanceDataset`` (``fit(ds)``: the moment pass
        is one ``tree_aggregate`` program over its row-sharded arrays) or
        bare ``x``/``y``/``w`` arrays. Only the O(d²) moments come back to
        the driver either way."""
        d = x.n_features if y is None else x.shape[1]
        return self.solve(self.moments(x, y, w), d)

    def moments(self, x, y=None, w=None) -> dict:
        """The one pass over the data: its six weighted sums as host
        arrays at the accumulator's width (:meth:`solve` widens them).
        Every call pays the pass — nothing here remembers a dataset's
        Gramian. For a dataset the dispatch and the readback
        are spans (``cyclone.dispatch.wls.moments``, ``cyclone.transfer.
        wls.readback``) and one completed step of its context."""
        import jax
        from cycloneml_tpu.observe import tracing
        n, d = x.shape
        if d > MAX_NUM_FEATURES:
            raise ValueError(
                f"WeightedLeastSquares supports at most {MAX_NUM_FEATURES} "
                f"features, got {d}")
        if y is None:
            from cycloneml_tpu.ops.kernels import (mean_mxu_passes,
                                                   stored_feature_major)
            ds = x
            call = ds.tree_aggregate_fn(
                moments_aggregator(stored_feature_major(ds.x)))
            with tracing.span("dispatch", "wls.moments", passes=1) as dsp:
                out_dev = call()            # 'collective' span inside
                with tracing.span("transfer", "wls.readback") as tsp:
                    out = jax.device_get(out_dev)
                    tsp.annotate_bytes(out)
                # which form of the Gramian the weights took: chosen on
                # the device, so known with the moments (None: XLA's)
                dsp.annotate(mxu_passes=mean_mxu_passes(
                    out, ds.ctx.mesh_runtime.data_parallelism))
            if hasattr(ds.ctx, "record_step"):
                ds.ctx.record_step({"wls_passes": 1.0})
        else:
            import jax.numpy as jnp
            if w is None:
                w = np.ones(n)
            out = jax.device_get(_array_moments()(
                jnp.asarray(x), jnp.asarray(y), jnp.asarray(w)))
        self.n_passes += 1
        return out

    # -- the reference algorithm -----------------------------------------
    def solve(self, m: dict, d: int, *, _standardise: bool = False
              ) -> WeightedLeastSquaresModel:
        """The driver's half (ref WeightedLeastSquares.fit after its
        treeAggregate): the (d+1)-sized system in float64, and the model
        in the data's own coordinates.

        The Cholesky solver factors the moment block it was handed, ``S'
        = [[aa_sum + w_sum·diag(lam·a_var), a_sum], [a_sum', w_sum]]``:
        the standardised system is ``E (S' / w_sum) E`` with ``E =
        diag(1/a_std, 1)``, a diagonal congruence that moves neither the
        solution nor Cholesky's backward error (module docstring), so for
        ``S' t = w_sum · [ab_bar / b_std ; b_bar]`` the standardised
        solution is ``E⁻¹ t`` and mapping it back cancels ``E⁻¹``: ``coef
        = b_std · t[:d]``; ``diag_inv_atwa`` is ``diag(S'⁻¹)`` as it
        stands; the objective is the standardised quadratic with ONE
        ``1 / w_sum`` (``_cholesky``'s ``scale``). The block is filled by
        one widening copy: at d = 2,000 every pass over a (d, d) float64
        array is 32 MB of host memory traffic, and the chip idles through
        each. The O(d²) rescaling is built where standardised coordinates
        are needed: a feature with ``a_std == 0`` (``E`` is singular:
        the column gets coefficient 0 through its zero standardised
        column), the quasi-Newton solver, and auto's fallback to it after
        a failed ``potrf``, which rebuilds from ``m``. The returned
        model's ``system`` names the form; ``_standardise`` forces the
        rescaled one (the tests' twin of the moment form, no option of
        the component)."""
        w_sum = float(m["w_sum"])
        if w_sum <= 0:
            raise ValueError("sum of weights must be positive")
        b_sum = float(m["b_sum"])
        raw_b_bar = b_sum / w_sum
        raw_bb_bar = float(m["bb_sum"]) / w_sum
        raw_b_std = float(np.sqrt(max(raw_bb_bar - raw_b_bar ** 2, 0.0)))

        if raw_b_std == 0.0:
            if self.fit_intercept or raw_b_bar == 0.0:
                # ref :121-136: constant label needs no training
                return WeightedLeastSquaresModel(
                    np.zeros(d), float(raw_b_bar) if self.fit_intercept
                    else 0.0, np.zeros(1), [0.0])
            if self.reg_param > 0.0 and self.standardize_label:
                raise ValueError(
                    "The standard deviation of the label is zero. Model "
                    "cannot be regularized when labels are standardized")
        b_std = abs(float(raw_b_bar)) if raw_b_std == 0.0 else raw_b_std
        b_bar = float(raw_b_bar) / b_std
        bb_bar = float(raw_bb_bar) / (b_std * b_std)

        a_sum = np.asarray(m["a_sum"], np.float64)
        ab_sum = np.asarray(m["ab_sum"], np.float64)
        raw_a_bar = a_sum / w_sum
        raw_ab_bar = ab_sum / w_sum
        aa_sum = np.asarray(m["aa_sum"])
        a_var = np.maximum(
            np.diagonal(aa_sum).astype(np.float64) / w_sum - raw_a_bar ** 2,
            0.0)
        a_std = np.sqrt(a_var)
        live = a_std > 0
        inv_std = np.where(live, 1.0 / np.where(live, a_std, 1.0), 0.0)

        a_bar = raw_a_bar * inv_std
        ab_bar = raw_ab_bar * inv_std / b_std

        eff_reg = self.reg_param / b_std
        eff_l1 = self.elastic_net_param * eff_reg
        eff_l2 = (1.0 - self.elastic_net_param) * eff_reg

        # L2 onto the standardized diagonal (ref :213-231)
        lam = np.full(d, eff_l2)
        if not self.standardize_features:
            lam = np.where(live, lam * inv_std * inv_std, 0.0)
        if not self.standardize_label:
            lam = lam * b_std

        use_qn = (self.solver_type == QUASI_NEWTON
                  or (self.solver_type == AUTO
                      and self.elastic_net_param != 0.0
                      and self.reg_param != 0.0))
        icpt = self.fit_intercept
        k = d + 1 if icpt else d

        def system(moments: bool):
            """Block and right-hand side of either form; the intercept
            rides as an appended bias column (getAtA, ref :312)."""
            if moments:
                ata = _normal_block(aa_sum, w_sum * lam * a_var,
                                    a_sum if icpt else None, w_sum)
                return ata, np.append(ab_sum, b_sum)[:k] / b_std
            ata = _normal_block(aa_sum, lam, a_bar if icpt else None, 1.0,
                                scale=(inv_std / w_sum, inv_std))
            return ata, np.append(ab_bar, b_bar)[:k]

        moments = not (use_qn or _standardise) and bool(live.all())
        ata, atb = system(moments)
        if use_qn:
            sol, history, aa_inv = self._quasi_newton(
                ata, atb, a_bar, b_bar, bb_bar, a_std, eff_l1, d)
        else:
            try:
                sol, history, aa_inv = self._cholesky(
                    ata, atb, bb_bar, 1.0 / w_sum if moments else 1.0)
            except np.linalg.LinAlgError:
                if self.solver_type != AUTO:
                    raise
                # ref :266-273: auto falls back to QN on singular AtA —
                # in standardised coordinates, rebuilt from the moments
                if moments:
                    moments = False
                    ata, atb = system(False)
                sol, history, aa_inv = self._quasi_newton(
                    ata, atb, a_bar, b_bar, bb_bar, a_std, None, d)

        # back to the data's coordinates: E⁻¹ has cancelled in the moment
        # form; a dead feature's inv_std is 0, and so is its coefficient
        coef = sol[:d] * (b_std if moments else b_std * inv_std)
        intercept = float(sol[d]) * b_std if icpt else 0.0

        if aa_inv is None:
            diag = np.zeros(1)
        elif moments:
            diag = aa_inv               # diag(S'⁻¹): nothing to rescale
        else:
            mult = np.append(a_var, 1.0)[:k]

            def diag():
                with np.errstate(divide="ignore"):
                    return np.where(mult > 0, aa_inv() / (w_sum * mult),
                                    np.inf)
        return WeightedLeastSquaresModel(
            coef, intercept, diag, history,
            system="moments" if moments else "standardised")

    def _cholesky(self, ata, atb, bb_bar, scale=1.0):
        """LAPACK's symmetric positive-definite routines on the ONE
        column-major block (``potrf`` / ``potrs``, and ``potri`` when the
        inverse's diagonal is asked for; ref CholeskySolver's ``dppsv`` +
        ``dpptri``), in place: the factor overwrites the lower triangle,
        the strict upper triangle keeps the matrix, so the objective's
        ``sol'A sol`` is a ``symv`` over the upper half with the saved
        diagonal put back — no second (d, d) array. ``ata`` is whichever
        block ``solve`` built; ``scale`` is what takes its quadratic to
        the standardised one (``1 / w_sum`` for the moment block, whose
        solution and right-hand side carry the rest of the congruence;
        1 for the standardised block). A matrix that is not positive
        definite raises LinAlgError (the reference's
        SingularMatrixException analog) with ``ata`` restored."""
        from scipy.linalg import blas, lapack
        diag = ata.diagonal().copy()
        chol, info = lapack.dpotrf(ata, lower=1, clean=0, overwrite_a=1)
        if info != 0:
            upper = np.triu(chol, 1)
            ata[...] = upper + upper.T
            ata[np.arange(len(diag)), np.arange(len(diag))] = diag
            raise np.linalg.LinAlgError(
                f"normal-equation matrix is not positive definite "
                f"(potrf info={info})")
        sol, _ = lapack.dpotrs(chol, atb, lower=1)
        # the objective the solution reaches: the standardised quadratic
        # _quasi_newton's cost function evaluates, penalty included
        a_sol = blas.dsymv(1.0, chol, sol, lower=0) \
            + (diag - chol.diagonal()) * sol
        loss = 0.5 * bb_bar - scale * float(atb @ sol) \
            + 0.5 * scale * float(sol @ a_sol)

        def inv_diag():
            return lapack.dpotri(chol, lower=1, overwrite_c=1)[0] \
                .diagonal().copy()
        return sol, [loss], inv_diag

    def _quasi_newton(self, ata, atb, a_bar, b_bar, bb_bar, a_std,
                      eff_l1, d: int):
        from cycloneml_tpu.ml.optim.lbfgs import optimizer_for

        k = ata.shape[0]

        def f(coef):
            coef = np.asarray(coef, dtype=np.float64).copy()
            if self.fit_intercept:
                # ref NormalEquationCostFun:134-144 — the bias coordinate
                # is pinned to its optimum given the features
                coef[d] = b_bar - float(coef[:d] @ a_bar)
            aax = ata @ coef
            loss = 0.5 * bb_bar - float(atb @ coef) + 0.5 * float(coef @ aax)
            return loss, aax - atb

        x0 = np.zeros(k)
        if self.fit_intercept:
            x0[d] = b_bar
        opt = optimizer_for(
            self.max_iter, self.tol, k, l1=eff_l1 or 0.0, n_penalized=d,
            penalty_std=None if self.standardize_features else a_std)
        state = None
        for state in opt.iterations(f, x0):
            pass
        sol = np.asarray(state.x, dtype=np.float64).copy()
        if self.fit_intercept:
            sol[d] = b_bar - float(sol[:d] @ a_bar)
        return sol, list(state.loss_history), None
