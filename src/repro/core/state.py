"""Persistent, incrementally-updatable posterior state (the serving core).

The paper's complexity story — O(N^2 D + N^3) in the low-data regime
N < D (Sec. 4) — only pays off in the workloads it motivates (optimizer
loops, GPG-HMC, online BO) if observations can be **appended one at a time
without refactoring from scratch** and many posterior queries can be
served against one cached solve.  This module is that state machine:

  ``GPGData``   — a fixed-capacity, jit-compatible pytree holding the
                  zero-padded ``GramFactors`` strips, the bordered Cholesky
                  ``L`` of the N x N fast-case matrix K1n = K1e + (s^2/lam) I,
                  and the solved representers ``Z``.
  ``gpg_extend``— appends one (x, grad) observation: O(ND) border of the
                  factor strips, an O(N^2) **bordered Cholesky update** of
                  L (DESIGN.md sec. 10), and a warm-started preconditioned
                  CG re-solve.  The O(N^6) dense inner refactorization of
                  ``woodbury_solve`` never runs: no intermediate with an
                  N^2-sized axis is ever created (asserted structurally in
                  tests/test_core_state.py).
  ``gpg_evict`` — drops the oldest observation for bounded-N sliding-window
                  serving: a rank-1 Cholesky update restores L in O(N^2)
                  (no downdate is ever needed — deleting the first row of a
                  Cholesky is a rank-1 *up*date of the trailing block).
  fallback      — when the bordered pivot degenerates (observations nearly
                  collinear in kernel space), the update falls back to a
                  full O(N^3) refactorization of L (``n_refactor`` counts
                  these; still never O(N^6)).

All pure functions are traceable: ``optim/gp_precond.py`` runs them inside
a jitted, sharded training step.  The host-facing :class:`GPGState` wraps
them with auto-evict / auto-grow policy and python-side bookkeeping; the
batched query layer on top lives in ``core/query.py``.

Masking convention: arrays are padded to ``capacity`` rows; rows >= count
are zero (L carries an identity tail) so every contraction below is exact
on the padded arrays — see DESIGN.md sec. 10.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.scipy.linalg import cho_solve, solve_triangular

from repro.obs import injit as _obs_tap
from repro.obs import trace as _obs
# host-side guardrails + typed errors (repro.resilience imports nothing
# from repro.core at module level, so this is cycle-free)
from repro.resilience import guardrails as _guard
from repro.resilience.errors import UnsupportedQueryError

from . import backend
from .gram import GramFactors
from .kernels import KernelSpec, get_kernel
from .mvm import gram_matvec
from .solvers import cg

Array = jnp.ndarray

_TINY = 1e-30


class GPGData(NamedTuple):
    """Jit-compatible posterior state (fixed capacity, zero-padded).

    X/G:    (cap, D) raw inputs / observed gradients (rows >= count are 0).
    Xt:     (cap, D) centered inputs (X - c for dot kernels, X stationary).
    K1e/K2e:(cap, cap) effective kernel-derivative strips, zero-padded.
    L:      (cap, cap) lower Cholesky of K1n = K1e + (noise/lam + jitter) I
            on the valid block, identity on the tail rows/cols.
    Z:      (cap, D) representers solving (grad K grad') vec(Z) = vec(rhs);
            rhs is G unless overridden (flipped GP-X inference).
    lam:    scalar or (D,) Lambda diagonal.
    count:  valid row count; n_refactor/n_solve: lifetime op counters;
    cg_iters/resnorm: stats of the most recent solve.
    """

    X: Array
    G: Array
    Xt: Array
    K1e: Array
    K2e: Array
    L: Array
    Z: Array
    lam: Array
    count: Array
    n_refactor: Array
    n_solve: Array
    cg_iters: Array
    resnorm: Array
    c: Optional[Array] = None

    @property
    def capacity(self) -> int:
        return self.X.shape[0]

    @property
    def d(self) -> int:
        return self.X.shape[1]


def _row_mask(data: GPGData) -> Array:
    return jnp.arange(data.capacity) < data.count


def _static_noise(noise) -> bool:
    """True when ``noise`` is a host python number (the single-state path).

    The fleet path (``core/fleet.py``) vmaps these functions with the
    per-tenant noise riding as a TRACED scalar so one compiled program
    serves heterogeneous tenants; every host-side branch on noise below
    is gated on this predicate (a tracer always takes the traced form).
    """
    return isinstance(noise, (int, float))


def _diag_shift(lam: Array, noise, jitter: float):
    """(noise/lam + jitter) — the scalar added to K1e's valid diagonal."""
    lam = jnp.asarray(lam)
    if _static_noise(noise):
        if noise and lam.ndim != 0:
            raise ValueError(
                "noise > 0 requires scalar Lambda (as in woodbury)")
        return (noise / lam if noise else 0.0) + jitter
    # traced per-tenant noise (fleet): scalar Lambda by construction
    return noise / lam + jitter


def gpg_init(
    spec: KernelSpec,
    d: int,
    capacity: int,
    *,
    lam=1.0,
    c: Optional[Array] = None,
    dtype=None,
) -> GPGData:
    """Empty state with room for ``capacity`` gradient observations."""
    if dtype is None:
        dtype = jnp.asarray(0.0).dtype
    cap = int(capacity)
    zmat = jnp.zeros((cap, d), dtype)
    znn = jnp.zeros((cap, cap), dtype)
    return GPGData(
        X=zmat, G=zmat, Xt=zmat, K1e=znn, K2e=znn,
        L=jnp.eye(cap, dtype=dtype), Z=zmat,
        lam=jnp.asarray(lam, dtype),
        count=jnp.zeros((), jnp.int32),
        n_refactor=jnp.zeros((), jnp.int32),
        n_solve=jnp.zeros((), jnp.int32),
        cg_iters=jnp.zeros((), jnp.int32),
        resnorm=jnp.zeros((), dtype),
        c=None if (spec.is_stationary or c is None) else jnp.asarray(c, dtype),
    )


# ---------------------------------------------------------------------------
# Internals: border rows, Cholesky surgery, the masked solve
# ---------------------------------------------------------------------------

def _border(spec: KernelSpec, data: GPGData, x: Array):
    """New factor border: (xt_new, k1_col, k2_col, r_self) — O(ND).

    ONE ``backend.fused_factor_build`` sweep of the stored (cap, D) strip
    emits the border gram column AND both norm strips (stationary r) AND
    the new point's self-dot (dot-kernel r_self) — the pre-fusion
    scaled_gram/gram_norms/row_dots launches are gone (DESIGN.md sec. 12).
    """
    mask = _row_mask(data)
    xt_new = x if (spec.is_stationary or data.c is None) else x - data.c
    P, na, nb, _, _ = backend.fused_factor_build(data.Xt, xt_new[None], None,
                                                 data.lam)
    if spec.is_stationary:
        r_col = jnp.maximum(na + nb[0] - 2.0 * P[:, 0], 0.0)
        r_self = jnp.zeros((), x.dtype)
    else:
        r_col = P[:, 0]
        r_self = nb[0]
    k1_col = jnp.where(mask, spec.k1e(r_col), 0.0)
    k2_col = jnp.where(mask, spec.k2e(r_col), 0.0)
    return xt_new, k1_col, k2_col, r_self


def _full_chol(data: GPGData, noise: float, jitter: float) -> Array:
    """O(N^3) Cholesky of the masked K1n (identity tail); the fallback."""
    mask = _row_mask(data)
    shift = _diag_shift(data.lam, noise, jitter)
    K1n = data.K1e + jnp.diag(jnp.where(mask, shift, 1.0))
    L = jnp.linalg.cholesky(K1n)
    # last-resort regularization if K1n lost positive-definiteness to
    # roundoff (near-duplicate observations): retry with a scaled jitter
    bad = ~jnp.all(jnp.isfinite(L))
    tr = jnp.trace(K1n) / jnp.maximum(data.count, 1)
    K1r = K1n + jnp.diag(jnp.where(mask, 1e-6 * tr, 0.0))
    return jnp.where(bad, jnp.linalg.cholesky(K1r), L)


def _chol_append(L: Array, k_col: Array, kappa, n: Array, deg_thresh: float):
    """Bordered Cholesky: O(N^2) append of row n.
    Returns (L', degraded, pivot2).

    k_col must be zero at rows >= n (and L identity there), so the
    triangular solve is exact on the padded arrays.  ``pivot2`` is the
    squared new pivot — the numerical-health signal the obs taps record
    (it collapsing toward ``deg_thresh * kappa`` is the early warning for
    the O(N^3) fallback).
    """
    l = solve_triangular(L, k_col, lower=True)
    pivot2 = kappa - jnp.vdot(l, l)
    degraded = pivot2 <= deg_thresh * jnp.maximum(kappa, _TINY)
    row = jnp.where(jnp.arange(L.shape[0]) < n, l, 0.0)
    row = row.at[n].set(jnp.sqrt(jnp.maximum(pivot2, _TINY)))
    return L.at[n].set(row), degraded, pivot2


def _chol_rank1_update(L: Array, v: Array) -> Array:
    """chol(L L^T + v v^T) in O(N^2); identity-tail/zero-v rows are no-ops."""
    cap = L.shape[0]
    idx = jnp.arange(cap)

    def body(k, carry):
        L, v = carry
        Lkk, vk = L[k, k], v[k]
        r = jnp.sqrt(Lkk * Lkk + vk * vk)
        cos = r / jnp.maximum(Lkk, _TINY)
        sin = vk / jnp.maximum(Lkk, _TINY)
        below = idx > k
        col = L[:, k]
        new_col = jnp.where(below, (col + sin * v) / cos, col).at[k].set(r)
        v = jnp.where(below, cos * v - sin * new_col, v)
        return L.at[:, k].set(new_col), v

    L, _ = jax.lax.fori_loop(0, cap, body, (L, v))
    return L


def _solve(spec: KernelSpec, data: GPGData, rhs: Array, z0: Array, *,
           noise: float, tol: float, maxiter: int) -> GPGData:
    """Warm-started preconditioned CG on the masked padded Gram system.

    The preconditioner is the free Kronecker factor B = K1n x Lam applied
    through the cached Cholesky — two O(N^2) triangular sweeps per
    iteration plus ONE fused Gram MVM (O(N^2 D)); nothing here ever has an
    N^2-sized axis.
    """
    mask = _row_mask(data)[:, None]
    f = GramFactors(K1e=data.K1e, K2e=data.K2e,
                    Xt=jnp.where(mask, data.Xt, 0.0), lam=data.lam,
                    noise=float(noise) if _static_noise(noise) else 0.0,
                    c=data.c)
    if _static_noise(noise):
        mv = lambda V: gram_matvec(f, V, stationary=spec.is_stationary)
    else:
        # traced noise rides OUTSIDE the factors (the backend kernels take
        # static noise); one extra fused axpy per MVM, identical math
        mv = lambda V: gram_matvec(
            f, V, stationary=spec.is_stationary) + noise * V
    M_inv = lambda V: cho_solve((data.L, True), V) / data.lam
    res = cg(mv, jnp.where(mask, rhs, 0.0), x0=jnp.where(mask, z0, 0.0),
             tol=tol, maxiter=maxiter, M_inv=M_inv)
    _obs_tap.tap("state.cg_iters", res.iters, kind="hist")
    _obs_tap.tap("state.cg_resnorm", res.resnorm)
    Z = jnp.where(mask & jnp.isfinite(res.x), res.x, 0.0)
    return data._replace(Z=Z, n_solve=data.n_solve + 1, cg_iters=res.iters,
                         resnorm=jnp.asarray(res.resnorm, data.resnorm.dtype))


def _default_maxiter(data: GPGData, maxiter: Optional[int], *,
                     cond: Optional[float] = None,
                     tol: float = 1e-10) -> int:
    """Iteration budget for the warm-started CG re-solve.

    An explicit ``maxiter`` always wins.  Otherwise the budget is the
    classic CG bound  iters ~ sqrt(kappa) * log(2/tol) / 2  evaluated at
    the health monitor's condition proxy (``obs.health.condition_proxy``
    — a free lower bound on cond(K1n), the operator the preconditioner
    has to equalize), clamped between a warm-start floor and the legacy
    ``10 * capacity + 50`` ceiling so a wild proxy can neither starve nor
    blow up the solve.  Without a condition sample (no monitor attached,
    or jitted consumers where ``maxiter`` must stay static) the ceiling
    is the budget — exactly the pre-regime behavior.
    """
    if maxiter is not None:
        return int(maxiter)
    cap = data.capacity
    ceiling = 10 * cap + 50
    if cond is None:
        return ceiling
    import math

    if not math.isfinite(cond) or cond <= 1.0:
        return ceiling
    need = 0.5 * math.sqrt(cond) * math.log(2.0 / max(float(tol), 1e-300))
    return int(min(ceiling, max(cap // 2 + 16, math.ceil(need))))


# ---------------------------------------------------------------------------
# The public pure-functional API (jit/shard_map-safe; spec & floats static)
# ---------------------------------------------------------------------------

def gpg_extend(
    spec: KernelSpec,
    data: GPGData,
    x: Array,
    g: Array,
    *,
    noise: float = 0.0,
    jitter: float = 1e-10,
    deg_thresh: float = 1e-8,
    tol: float = 1e-10,
    maxiter: Optional[int] = None,
    solve: bool = True,
    rhs: Optional[Array] = None,
) -> GPGData:
    """Append one (x, grad) observation with a bordered factor update.

    Requires count < capacity (the host wrapper evicts/grows first; the
    jitted consumers guarantee it by construction).  ``rhs`` overrides the
    right-hand side of the re-solve (flipped GP-X inference); default G.
    """
    x = jnp.asarray(x, data.X.dtype)
    g = jnp.asarray(g, data.X.dtype)
    n = data.count
    xt_new, k1_col, k2_col, r_self = _border(spec, data, x)
    k1_diag = spec.k1e(r_self)
    shift = _diag_shift(data.lam, noise, jitter)

    K1e = data.K1e.at[n, :].set(k1_col).at[:, n].set(k1_col)
    K1e = K1e.at[n, n].set(k1_diag)
    K2e = data.K2e.at[n, :].set(k2_col).at[:, n].set(k2_col)
    K2e = K2e.at[n, n].set(spec.k2e(r_self))
    data = data._replace(
        X=data.X.at[n].set(x), G=data.G.at[n].set(g),
        Xt=data.Xt.at[n].set(xt_new), K1e=K1e, K2e=K2e,
        count=n + 1,
    )

    L_new, degraded, pivot2 = _chol_append(data.L, k1_col, k1_diag + shift,
                                           n, deg_thresh)
    _obs_tap.tap("state.pivot2", pivot2)
    _obs_tap.tap("state.degenerate_fallback", degraded, kind="counter")
    data = jax.lax.cond(
        degraded,
        lambda d: d._replace(L=_full_chol(d, noise, jitter),
                             n_refactor=d.n_refactor + 1),
        lambda d: d._replace(L=L_new),
        data,
    )
    if solve:
        data = _solve(spec, data, data.G if rhs is None else rhs, data.Z,
                      noise=noise, tol=tol,
                      maxiter=_default_maxiter(data, maxiter))
    return data


def gpg_evict(
    spec: KernelSpec,
    data: GPGData,
    *,
    noise: float = 0.0,
    tol: float = 1e-10,
    maxiter: Optional[int] = None,
    solve: bool = True,
) -> GPGData:
    """Drop the OLDEST observation (sliding window) in O(N^2 + N D).

    Removing row 0 of K1n = L L^T leaves the trailing block
    L21 L21^T + L22 L22^T, whose Cholesky is a rank-1 *update* of L22 —
    no downdate (and hence no loss of positive definiteness) ever occurs.
    """
    n = data.count
    cap = data.capacity
    keep = jnp.arange(cap) < jnp.maximum(n - 1, 0)
    km = keep[:, None]
    kmm = keep[:, None] & keep[None, :]

    def up(A):  # shift rows up by one, zeroing the vacated tail
        return jnp.where(km, jnp.roll(A, -1, axis=0), 0.0)

    def upleft(A):
        return jnp.where(kmm, jnp.roll(jnp.roll(A, -1, 0), -1, 1), 0.0)

    Ls = upleft(data.L) + jnp.diag(jnp.where(keep, 0.0, 1.0))
    v = jnp.where(keep, jnp.roll(data.L[:, 0], -1), 0.0)
    data = data._replace(
        X=up(data.X), G=up(data.G), Xt=up(data.Xt), Z=up(data.Z),
        K1e=upleft(data.K1e), K2e=upleft(data.K2e),
        L=_chol_rank1_update(Ls, v),
        count=jnp.maximum(n - 1, 0),
    )
    if solve:
        data = _solve(spec, data, data.G, data.Z, noise=noise, tol=tol,
                      maxiter=_default_maxiter(data, maxiter))
    return data


def gpg_refactor(
    spec: KernelSpec,
    data: GPGData,
    lam: Optional[Array] = None,
    *,
    noise: float = 0.0,
    jitter: float = 1e-10,
    tol: float = 1e-10,
    maxiter: Optional[int] = None,
    solve: bool = True,
    rhs: Optional[Array] = None,
) -> GPGData:
    """Full O(N^2 D + N^3) rebuild of factors + Cholesky (+ solve).

    The explicit refactorization entry point: hyperparameter (Lambda)
    refresh, bulk conditioning (``GPGState.from_data``), and the
    degradation fallback all land here.  Still never O(N^6).
    """
    if lam is not None:
        data = data._replace(lam=jnp.asarray(lam, data.X.dtype))
    mask = _row_mask(data)
    mm = mask[:, None] & mask[None, :]
    if spec.is_stationary:
        Xt = jnp.where(mask[:, None], data.X, 0.0)
    else:
        Xt = data.X if data.c is None else data.X - data.c
        Xt = jnp.where(mask[:, None], Xt, 0.0)
    r = backend.pairwise_r(spec, Xt, Xt, data.lam)
    data = data._replace(
        Xt=Xt,
        K1e=jnp.where(mm, spec.k1e(r), 0.0),
        K2e=jnp.where(mm, spec.k2e(r), 0.0),
        n_refactor=data.n_refactor + 1,
    )
    data = data._replace(L=_full_chol(data, noise, jitter))
    if solve:
        data = _solve(spec, data, data.G if rhs is None else rhs, data.Z,
                      noise=noise, tol=tol,
                      maxiter=_default_maxiter(data, maxiter))
    return data


def gpg_resolve(
    spec: KernelSpec,
    data: GPGData,
    rhs: Array,
    *,
    noise: float = 0.0,
    tol: float = 1e-10,
    maxiter: Optional[int] = None,
) -> GPGData:
    """Re-solve against a NEW right-hand side, reusing factors + Cholesky.

    Zero refactorization — this is the GP-X path, where the observations
    (displacements X - x_t) change wholesale every step while the Gram
    factors (built on the gradient inputs) only grow by borders.
    """
    return _solve(spec, data, rhs, data.Z, noise=noise, tol=tol,
                  maxiter=_default_maxiter(data, maxiter))


# ---------------------------------------------------------------------------
# Host-facing wrapper: policy (auto-evict / auto-grow) + bookkeeping
# ---------------------------------------------------------------------------

class GPGState:
    """A conditioned gradient-GP posterior you can stream observations into.

    >>> st = GPGState("rbf", d=32, window=8, lam=1.0 / 32, noise=1e-8)
    >>> st.extend(x, g)          # O(N^2 D) bordered update, warm CG re-solve
    >>> pb = st.posterior(Xq)    # batched queries, zero re-solves

    ``window=m`` serves from a bounded sliding window (extend auto-evicts
    the oldest observation); ``window=None`` grows capacity geometrically
    (a pure zero-pad — padding is exact, so growth needs no refactor).
    """

    def __init__(
        self,
        kernel: str | KernelSpec = "rbf",
        d: int | None = None,
        *,
        capacity: int = 8,
        window: int | None = None,
        lam=1.0,
        noise: float = 0.0,
        signal: float = 1.0,
        c=None,
        jitter: float = 1e-10,
        deg_thresh: float = 1e-8,
        tol: float = 1e-10,
        maxiter: int | None = None,
        dtype=None,
        precision: str | None = None,
        policy=None,
    ):
        if d is None:
            raise TypeError("GPGState needs the input dimension d")
        self.spec = get_kernel(kernel) if isinstance(kernel, str) else kernel
        # Stream storage precision (DESIGN.md sec. 12): 'f32' | 'bf16'.
        # bf16 keeps the f32 masters in ``data`` for every solve/factor and
        # maintains bf16 COPIES of the (cap, D) stream operands for the
        # query path — cast once per state revision, not once per query.
        self.precision = (backend.resolve_precision() if precision is None
                          else precision)
        backend.stream_dtype(self.precision)  # validate early
        self._stream_cache = None
        self.noise = float(noise)
        self.signal = float(signal)
        self.jitter = float(jitter)
        self.deg_thresh = float(deg_thresh)
        self.tol = float(tol)
        self.maxiter = maxiter
        self.window = int(window) if window else None
        cap = self.window if self.window else int(capacity)
        self.data = gpg_init(self.spec, int(d), cap, lam=lam, c=c,
                             dtype=dtype)
        # Regime policy (repro.regime): which solve/evidence path the
        # state's size warrants, and what a full window does — 'evict'
        # (the PR-3 default), 'compress' (exact gradient reduction onto
        # the observed subspace), 'iterate' (grow past the window and let
        # the iterative regime absorb it), or 'auto'.  Deferred import:
        # repro.regime imports core submodules at load time.
        from repro.regime.policy import resolve_policy

        self.policy = resolve_policy(policy, window=self.window)
        self._last_regime: str | None = None
        self._reduction = None      # set when 'compress' has fired
        self._raw_X = None          # original-frame copies (compress only)
        self._raw_G = None
        # Monotonic revision counters (repro.obs): ``revision`` bumps on
        # EVERY data mutation, ``factor_revision`` only when the factor
        # strips / Cholesky / lam / count change — it is the exact cache
        # key the serve layer's variance-solver LRU needs (a resolve()
        # against a new RHS changes Z but not the factorization).
        self.revision = 0
        self.factor_revision = 0
        self._health = None
        if _obs.enabled():
            # pre-register so a run that never trips them still exports
            # the keys (tools/check_telemetry.py self-consistency gate)
            _obs.REGISTRY.inc("state.extend_calls", 0)
            _obs.REGISTRY.inc("state.refactor_fallback", 0)

    # -- construction ------------------------------------------------------

    @classmethod
    def from_data(cls, kernel, X: Array, G: Array, **kw) -> "GPGState":
        """Bulk-condition on (X, G) with ONE solve (then stream via extend)."""
        X = jnp.atleast_2d(X)
        n, d = X.shape
        kw.setdefault("capacity", max(n, 1))
        st = cls(kernel, d, **kw)
        if st.window and n > st.window:
            raise ValueError(f"{n} observations exceed window={st.window}")
        if n > st.data.capacity:
            raise ValueError(f"{n} observations exceed "
                             f"capacity={st.data.capacity}")
        cap = st.data.capacity
        pad = cap - n
        Xp = jnp.pad(jnp.asarray(X, st.data.X.dtype), ((0, pad), (0, 0)))
        Gp = jnp.pad(jnp.asarray(G, st.data.X.dtype), ((0, pad), (0, 0)))
        st.data = st.data._replace(X=Xp, G=Gp,
                                   count=jnp.asarray(n, jnp.int32))
        st.data = gpg_refactor(st.spec, st.data, noise=st._noise_eff,
                               jitter=st.jitter, tol=st.tol,
                               maxiter=st.maxiter)
        return st

    # -- streaming updates -------------------------------------------------

    def _bump(self, factors: bool = True) -> None:
        """Advance the revision counters after a data mutation."""
        self.revision += 1
        if factors:
            self.factor_revision += 1

    def attach_health(self, monitor=None) -> "GPGState":
        """Attach a ``repro.obs.HealthMonitor`` (ticked on every extend)."""
        from repro.obs import HealthMonitor

        self._health = HealthMonitor() if monitor is None else monitor
        return self

    # -- regime selection (repro.regime) ------------------------------------

    @property
    def regime(self) -> str:
        """'exact' | 'iterative' — the solve/evidence path the policy's
        cost model picks for the CURRENT (n, d)."""
        return self.policy.regime_for(self.n, self.d)

    def _publish_regime(self) -> None:
        """Export regime gauges; emit a switch event on a boundary cross."""
        cur = self.regime
        self.policy.publish(self.n, self.d, cur, prev=self._last_regime)
        self._last_regime = cur

    def _cond_hint(self) -> Optional[float]:
        """Condition proxy for the maxiter budget, from the attached
        health monitor's last sample, bucketed to powers of 4 so the
        derived static maxiter only takes a handful of distinct values."""
        if self._health is None:
            return None
        last = getattr(self._health, "last", None)
        if not last:
            return None
        cond = last.get("cond_k1n")
        if cond is None or cond <= 1.0:
            return None
        import math

        if not math.isfinite(cond):
            return cond
        return 4.0 ** math.ceil(math.log(cond, 4.0))

    def _maxiter_eff(self) -> Optional[int]:
        """The per-solve iteration budget (None = legacy ceiling) —
        condition-scaled when a health monitor has sampled the state."""
        if self.maxiter is not None:
            return int(self.maxiter)
        cond = self._cond_hint()
        if cond is None:
            return None
        return _default_maxiter(self.data, None, cond=cond, tol=self.tol)

    def _capacity_action(self) -> str:
        """Resolve what a full window does, feeding the policy the data's
        affine rank only when compression is actually on the table.
        Non-scalar Lambda never compresses: the exact-reduction theorem
        (regime/reduction.py) is an isotropic-metric statement."""
        rank = None
        if self.policy.capacity in ("compress", "auto") \
                and self._reduction is None \
                and jnp.asarray(self.data.lam).ndim == 0:
            from repro.regime.reduction import affine_rank

            base = None if self.spec.is_stationary else \
                (self.data.c if self.data.c is not None else 0.0 * self.X[0])
            rank = affine_rank(self.X, base=base)
        return self.policy.capacity_action(self.n, self.d, rank)

    def _rebuild_reduced(self, Xr: Array, Gr: Array) -> None:
        """Replace ``data`` with a freshly-conditioned state over the
        reduced observations (one O(N^2 k + N^3) refactor)."""
        n = Xr.shape[0]
        cap = max(self.data.capacity, n + 1)
        data = gpg_init(self.spec, Xr.shape[1], cap, lam=self.data.lam,
                        c=None, dtype=self.data.X.dtype)
        pad = cap - n
        data = data._replace(
            X=jnp.pad(jnp.asarray(Xr, data.X.dtype), ((0, pad), (0, 0))),
            G=jnp.pad(jnp.asarray(Gr, data.X.dtype), ((0, pad), (0, 0))),
            count=jnp.asarray(n, jnp.int32))
        self.data = gpg_refactor(self.spec, data, noise=self._noise_eff,
                                 jitter=self.jitter, tol=self.tol,
                                 maxiter=self.maxiter)
        self._stream_cache = None

    def _compress(self) -> None:
        """Exact gradient reduction of the stored window onto its affine
        span (``regime/reduction.py``): the D axis collapses to the data's
        rank k, and the window cap is re-expressed at the reduced per-row
        flops — the state gains O(D/k) rows of headroom instead of
        evicting.  In-span posterior queries are EXACTLY unchanged; the
        dropped orthogonal gradient mass is published as
        ``regime.compress_residual``."""
        from repro.regime.reduction import reduce_gradients

        X, G = self.X, self.G
        red = reduce_gradients(self.spec, X, G, c=self.data.c)
        d_full, k, n = self.d, red.rank, self.n
        if self.window:
            self.window = max(self.window + 1,
                              int(self.window * d_full / max(k, 1)))
        # raw copies in the original frame: what basis growth rebuilds from
        self._raw_X = [row for row in X]
        self._raw_G = [row for row in G]
        self._reduction = red
        self._rebuild_reduced(red.Xr, red.Gr)
        if _obs.enabled():
            _obs.REGISTRY.inc("regime.compressions")
            _obs.REGISTRY.set_gauge("regime.compress_rank", float(k))
            _obs.REGISTRY.set_gauge("regime.compress_residual",
                                    float(red.residual))
            _obs.emit({"type": "regime", "event": "compress", "n": n,
                       "d": d_full, "rank": k,
                       "residual": float(red.residual)})

    def _grow_basis(self, x: Array) -> None:
        """Append the out-of-span direction of ``x`` to the reduction
        basis and rebuild the reduced state from the raw copies — rare
        (once per genuinely new direction), after which the grown span
        covers the newcomer exactly."""
        from repro.regime.reduction import Reduction

        red = self._reduction
        xc = jnp.asarray(x, red.base.dtype) - red.base
        resid = xc - red.basis @ (red.basis.T @ xc)
        w = resid / jnp.maximum(jnp.linalg.norm(resid), _TINY)
        basis = jnp.concatenate([red.basis, w[:, None]], axis=1)
        Xraw = jnp.stack(self._raw_X)
        Graw = jnp.stack(self._raw_G)
        Xr = (Xraw - red.base) @ basis
        Gr = Graw @ basis
        residual = jnp.linalg.norm(Graw - Gr @ basis.T)
        self._reduction = Reduction(basis=basis, base=red.base, Xr=Xr,
                                    Gr=Gr, residual=residual)
        self._rebuild_reduced(Xr, Gr)
        if _obs.enabled():
            _obs.REGISTRY.inc("regime.basis_growths")
            _obs.REGISTRY.set_gauge("regime.compress_rank",
                                    float(basis.shape[1]))

    def _project_in(self, x: Array) -> Array:
        """Map an incoming D-dim input into the reduced frame, growing
        the basis first when x leaves the observed span."""
        from repro.regime.reduction import project_points

        x = jnp.asarray(x)
        y, out = project_points(self._reduction, x[None])
        rn = float(out[0])
        if _obs.enabled():
            _obs.REGISTRY.set_gauge("regime.out_of_span", rn)
        scale = max(float(jnp.linalg.norm(x - self._reduction.base)), 1.0)
        if rn > 1e-7 * scale:
            self._grow_basis(x)
            y, _ = project_points(self._reduction, x[None])
        return y[0]

    def extend(self, x: Array, g: Array, *, solve: bool = True) -> "GPGState":
        """Append one observation.  A full window applies the policy's
        capacity action ({evict, compress, iterate}); a full capacity
        without a window zero-pad-grows, as ever."""
        obs_on = _obs.enabled()
        # admission guardrail: a NaN/inf observation raises a typed error
        # HERE, before any factor strip sees it (host-side: the jitted
        # extend program is byte-identical with guardrails on or off)
        with _obs.span("guard.check_finite"):
            _guard.check_finite(x, g, what="observation")
        with _obs.span("state.extend"):
            # the in-jit tap counts degenerate pivots as they happen; the
            # host-side counter below is the device-synced ground truth
            # (the capacity actions above never border-refactor, so any
            # n_refactor delta across gpg_extend IS the degenerate-pivot
            # fallback)
            before = int(self.data.n_refactor) if obs_on else 0
            x = jnp.asarray(x)
            g = jnp.asarray(g)
            if self.window and self.n >= self.window:
                action = self._capacity_action()
                if action == "compress":
                    self._compress()
                elif action == "iterate":
                    # lift the window cap: growth is absorbed by the
                    # iterative regime from here on
                    self.window = None
                else:
                    with _obs.span("state.evict"):
                        self.data = gpg_evict(self.spec, self.data,
                                              noise=self._noise_eff,
                                              solve=False)
                    if self._raw_X is not None:
                        self._raw_X.pop(0)
                        self._raw_G.pop(0)
            if self.n >= self.data.capacity:
                self._grow()
            if self._reduction is not None:
                xr = self._project_in(x)      # may grow the basis
                gr = g @ self._reduction.basis
                self._raw_X.append(x)
                self._raw_G.append(g)
                x, g = xr, gr
            before_refac = int(self.data.n_refactor) if obs_on else before
            with _obs.span(f"state.solve.{self.regime}"):
                self.data = gpg_extend(
                    self.spec, self.data, x, g, noise=self._noise_eff,
                    jitter=self.jitter, deg_thresh=self.deg_thresh,
                    tol=self.tol, maxiter=self._maxiter_eff(), solve=solve)
            if obs_on:
                _obs.REGISTRY.inc("state.extend_calls")
                fallbacks = int(self.data.n_refactor) - before_refac
                if fallbacks:
                    _obs.REGISTRY.inc("state.refactor_fallback", fallbacks)
                _obs.REGISTRY.set_gauge("state.n", self.n)
                if self._health is not None:
                    self._health.tick(self)
            self._publish_regime()
        self._bump()
        # post-mutation watchdog: one scalar read of the fresh pivot +
        # solve residual; non-finite factors climb the jitter ladder
        # (repro.resilience.guardrails) — triggers on NON-finite only,
        # so healthy-trajectory bits are untouched
        if _guard.enabled():
            with _obs.span("guard.after_mutation"):
                _guard.after_mutation(self)
        return self

    def evict(self, k: int = 1) -> "GPGState":
        """Drop the k oldest observations (one re-solve at the end)."""
        with _obs.span("state.evict", k=k):
            for i in range(k):
                self.data = gpg_evict(self.spec, self.data,
                                      noise=self._noise_eff, tol=self.tol,
                                      maxiter=self.maxiter,
                                      solve=(i == k - 1))
                if self._raw_X is not None and self._raw_X:
                    self._raw_X.pop(0)
                    self._raw_G.pop(0)
            if _obs.enabled():
                _obs.REGISTRY.inc("state.evict_calls")
                _obs.REGISTRY.set_gauge("state.n", self.n)
        self._bump()
        return self

    def refactor(self, lam=None) -> "GPGState":
        """Explicit full refactorization (e.g. after a Lambda refresh)."""
        with _obs.span("state.refactor"):
            self.data = gpg_refactor(self.spec, self.data, lam,
                                     noise=self._noise_eff,
                                     jitter=self.jitter, tol=self.tol,
                                     maxiter=self.maxiter)
            if _obs.enabled():
                _obs.REGISTRY.inc("state.refactor_calls")
        self._bump()
        return self

    def resolve(self, rhs: Array) -> Array:
        """Solve for a new RHS with cached factors; returns trimmed Z."""
        with _obs.span("state.resolve"):
            full = jnp.zeros_like(self.data.G).at[: rhs.shape[0]].set(
                jnp.asarray(rhs, self.data.G.dtype))
            self.data = gpg_resolve(self.spec, self.data, full,
                                    noise=self._noise_eff, tol=self.tol,
                                    maxiter=self.maxiter)
            if _obs.enabled():
                _obs.REGISTRY.inc("state.resolve_calls")
        # factors/Cholesky untouched: the variance-solver LRU stays valid
        self._bump(factors=False)
        return self.Z

    def _grow(self):
        """Double capacity by zero-padding — exact, no refactorization."""
        d0 = self.data
        cap = d0.capacity
        pr = ((0, cap), (0, 0))
        pnn = ((0, cap), (0, cap))
        L = jnp.pad(d0.L, pnn)
        L = L.at[jnp.arange(cap, 2 * cap), jnp.arange(cap, 2 * cap)].set(1.0)
        self.data = d0._replace(
            X=jnp.pad(d0.X, pr), G=jnp.pad(d0.G, pr), Xt=jnp.pad(d0.Xt, pr),
            Z=jnp.pad(d0.Z, pr), K1e=jnp.pad(d0.K1e, pnn),
            K2e=jnp.pad(d0.K2e, pnn), L=L)

    # -- model selection (repro.hyper) -------------------------------------

    @property
    def _noise_eff(self) -> float:
        """sigma^2 / s^2 — the noise the UNSCALED Gram solves see.

        Posterior means only depend on noise through this ratio
        (s^2 k_q (s^2 K + sigma^2 I)^{-1} = k_q (K + sigma^2/s^2 I)^{-1}),
        so the representer state is signal-invariant; the signal variance
        re-enters multiplicatively in the posterior variance paths.
        """
        return self.noise / self.signal

    @property
    def hypers(self):
        """Current hyperparameters as a ``repro.hyper.HyperParams``."""
        from repro.hyper import HyperParams

        lam = jnp.asarray(self.data.lam)
        if lam.ndim != 0:
            raise ValueError("HyperParams requires scalar (isotropic) Lambda")
        # floor a noise-free state at a float32-representable tiny so the
        # log-reparameterization stays finite even without x64
        return HyperParams.create(
            lengthscale2=1.0 / lam, signal=self.signal,
            noise=max(self.noise, 1e-30))

    def _evidence_method(self, method: str) -> str:
        """Normalize an evidence ``method`` knob: 'auto' follows the
        regime policy (SLQ past the crossover — the exact determinant-
        lemma inner matrix is (N^2, N^2))."""
        if method == "auto":
            return "slq" if self.regime == "iterative" else "exact"
        if method not in ("exact", "slq"):
            raise ValueError(
                f"method must be 'auto', 'exact' or 'slq': {method!r}")
        return method

    def mll(self, *, method: str = "auto", **slq_kw):
        """Log marginal likelihood of the CURRENT window at the current
        hypers.  ``method='exact'`` is the structured determinant-lemma
        path (never the (ND, ND) Gram); ``'slq'`` the stochastic Lanczos
        quadrature estimator (``regime/slq.py``) whose cost stays
        O(P m N^2 D) past the crossover; ``'auto'`` follows the regime.
        ``slq_kw`` (key/probes/lanczos_iters/cg_tol/cg_maxiter) pass
        through to :func:`repro.regime.slq.slq_mll`."""
        if self.n < 1:
            raise ValueError("mll() needs at least one observation")
        if self._evidence_method(method) == "slq":
            from repro.regime.slq import slq_mll

            return slq_mll(self.spec, self.X, self.G, self.hypers,
                           c=self.data.c, **slq_kw)
        from repro.hyper import mll as _mll

        return _mll(self.spec, self.X, self.G, self.hypers, c=self.data.c)

    def refit(self, *, mask=None, steps: int = 150, lr: float = 0.08,
              method: str = "auto", **fit_kw):
        """Refit the hypers by MLL ascent on the current window, then do the
        one legitimate full refactorization with the fitted lengthscale.

        ``method`` picks the evidence estimator the ascent runs on (see
        :meth:`mll`); 'auto' uses SLQ + Hutchinson hyper-gradients past
        the regime crossover, where the exact evidence is unaffordable.
        SLQ knobs (key/probes/lanczos_iters/cg_tol/cg_maxiter) ride in
        ``fit_kw``.  Updates ``noise``/``signal``/``lam`` in place and
        re-solves; returns the ``repro.hyper.FitResult`` (``.improvement``
        = MLL gain over the current hypers, which seed the fit).
        """
        if self.n < 2:
            raise ValueError("refit() needs at least two observations")
        method = self._evidence_method(method)
        with _obs.span("state.refit", method=method):
            if method == "slq":
                from repro.hyper import fit_fn as _fit_fn
                from repro.regime.slq import make_slq_mll_fn

                slq_kw = {k: fit_kw.pop(k)
                          for k in ("key", "probes", "lanczos_iters",
                                    "cg_tol", "cg_maxiter")
                          if k in fit_kw}
                fn = make_slq_mll_fn(self.spec, self.X, self.G,
                                     c=self.data.c, **slq_kw)
                res = _fit_fn(fn, self.hypers, steps=steps, lr=lr,
                              mask=mask, **fit_kw)
            else:
                from repro.hyper import fit as _fit

                res = _fit(self.spec, self.X, self.G, init=self.hypers,
                           c=self.data.c, mask=mask, steps=steps, lr=lr,
                           **fit_kw)
            self.noise = float(res.hypers.noise)
            self.signal = float(res.hypers.signal)
            self.refactor(lam=res.hypers.lam)
        return res

    # -- views -------------------------------------------------------------

    @property
    def n(self) -> int:
        return int(self.data.count)

    @property
    def d(self) -> int:
        return self.data.d

    @property
    def X(self) -> Array:
        return self.data.X[: self.n]

    @property
    def G(self) -> Array:
        return self.data.G[: self.n]

    @property
    def Z(self) -> Array:
        return self.data.Z[: self.n]

    @property
    def factors(self) -> GramFactors:
        """GramFactors trimmed to the valid rows (for core/ entry points)."""
        k = self.n
        return GramFactors(K1e=self.data.K1e[:k, :k],
                           K2e=self.data.K2e[:k, :k],
                           Xt=self.data.Xt[:k], lam=self.data.lam,
                           noise=self._noise_eff, c=self.data.c)

    @property
    def padded_factors(self) -> GramFactors:
        """Fixed-capacity GramFactors views (shape-stable across extend()).

        The zero rows are exact for the cross-covariance query paths and
        Hessian matvecs — every padded kernel coefficient multiplies a
        zero Z/Xt column — so a compiled query step keyed on these shapes
        survives count changes without recompiling (``train/serve.py``).
        NOT safe for ``HessianOperator.solve`` (its inner W inverse sees
        the padding); use ``factors`` for that.
        """
        d = self.data
        return GramFactors(K1e=d.K1e, K2e=d.K2e, Xt=d.Xt, lam=d.lam,
                           noise=self._noise_eff, c=d.c)

    def set_precision(self, precision: str) -> "GPGState":
        """Switch the stream storage precision ('f32' | 'bf16').

        Owns the cache invalidation that goes with it.  NOTE: precision is
        a property of the STATE, shared by every serve bundle and
        ``posterior()`` caller on it — switching here changes what all of
        them stream (the f32 masters and every solve are unaffected).
        """
        backend.stream_dtype(precision)  # validate
        if precision != self.precision:
            self.precision = precision
            self._stream_cache = None
        return self

    @property
    def stream_factors(self):
        """(padded factors, Z) views in the STREAM storage precision.

        With ``precision='bf16'`` the DATA stream the query path reads —
        Xt (and the query batches, cast at request time) — is a bf16 copy
        cached per state revision (every mutation replaces the ``GPGData``
        pytree, so identity is an exact revision key); the (cap, cap)
        factors, the representers Z (a solve output) and the f32 masters
        are untouched.  All downstream contractions accumulate in f32 and
        return f32 (``core.backend`` precision rules).

        Stationary coordinates are stored RELATIVE to the first
        observation (``GramFactors.shift``) before casting — exactly
        invariant, and what keeps clustered-data r/m cancellations at
        storage precision instead of |x|-amplified (DESIGN.md sec. 12.2).
        The shifted view serves the MEAN path only; probe/std queries run
        on the f32 masters.
        """
        if self.precision != "bf16":
            return self.padded_factors, self.data.Z
        c = self._stream_cache
        if c is None or c[0] is not self.data:
            d = self.data
            if self.spec.is_stationary:
                shift = d.Xt[0]
                mask = (jnp.arange(d.capacity) < d.count)[:, None]
                # padded rows stay exactly zero (the serving contract)
                xt = jnp.where(mask, d.Xt - shift, 0.0)
            else:
                shift = None
                xt = d.Xt
            f = self.padded_factors._replace(Xt=xt.astype(jnp.bfloat16),
                                             shift=shift)
            # Z stays f32: it is a SOLVE output (precision rule 3), and
            # representers cancel by orders of magnitude in the mean —
            # quantizing them is |Z|/|mean|-amplified.  Only the data
            # stream Xt (and queries) carry bf16 storage.
            self._stream_cache = (d, f, d.Z)
        return self._stream_cache[1], self._stream_cache[2]

    @property
    def stats(self) -> dict:
        return {
            "n": self.n,
            "n_refactor": int(self.data.n_refactor),
            "n_solve": int(self.data.n_solve),
            "cg_iters": int(self.data.cg_iters),
            "resnorm": float(self.data.resnorm),
        }

    def posterior(self, Xq: Array, *, probe: Array | None = None,
                  microbatch: int | None = None, return_std: bool = False,
                  return_grad_std: bool = False):
        """Batched posterior queries against the cached solve (zero re-solves).

        ``return_std``/``return_grad_std`` add posterior stds via ONE
        structured factorization of the noisy Gram (``repro.hyper.
        variance``).  See :func:`repro.core.query.posterior_batch`.

        On a compressed state (the 'compress' capacity action) queries are
        projected into the reduced frame and gradient outputs lifted back
        to R^D — exact for in-span queries (regime/reduction.py theorem);
        the out-of-span query mass is published as a gauge.
        """
        if self._reduction is not None:
            return self._posterior_reduced(
                Xq, probe=probe, microbatch=microbatch,
                return_std=return_std, return_grad_std=return_grad_std)
        return self._posterior_raw(Xq, probe=probe, microbatch=microbatch,
                                   return_std=return_std,
                                   return_grad_std=return_grad_std)

    def _posterior_reduced(self, Xq, *, probe, microbatch, return_std,
                           return_grad_std):
        from repro.regime.reduction import lift_gradients, project_points

        if return_grad_std:
            # typed (and a NotImplementedError subclass for legacy
            # callers): serve loops catch this and degrade to mean-only
            # instead of killing the request loop
            raise UnsupportedQueryError(
                "grad_std on a compressed state: per-coordinate gradient "
                "stds do not rotate through the reduction basis without "
                "the full gradient covariance")
        red = self._reduction
        Yq, out = project_points(red, jnp.atleast_2d(Xq))
        if _obs.enabled() and out.size:
            _obs.REGISTRY.set_gauge("regime.query_out_of_span",
                                    float(jnp.max(out)))
        probe_r = None if probe is None else jnp.asarray(probe) @ red.basis
        pb = self._posterior_raw(Yq, probe=probe_r, microbatch=microbatch,
                                 return_std=return_std,
                                 return_grad_std=False)
        return pb._replace(
            grad=lift_gradients(red, pb.grad),
            hess_v=(None if pb.hess_v is None
                    else lift_gradients(red, pb.hess_v)))

    def _posterior_raw(self, Xq: Array, *, probe, microbatch, return_std,
                       return_grad_std):
        from .query import posterior_batch

        solver = None
        if return_std or return_grad_std:
            from repro.hyper.variance import make_solver

            # the variance FACTORIZATION always runs on the f32 masters
            # (precision rule 3); only the streams may be bf16
            solver = make_solver(self.spec, self.factors, noise=self.noise,
                                 signal=self.signal)
        if probe is not None or solver is not None:
            # probe/std paths need the unshifted f32 masters; the mean
            # path still quantizes in-chunk under precision='bf16'
            f, Zq = self.factors, self.Z
        else:
            fp, Zp = self.stream_factors
            k = self.n
            f = fp._replace(K1e=fp.K1e[:k, :k], K2e=fp.K2e[:k, :k],
                            Xt=fp.Xt[:k])
            Zq = Zp[:k]
        return posterior_batch(self.spec, jnp.atleast_2d(Xq), f,
                               Zq, probe=probe, microbatch=microbatch,
                               return_std=return_std,
                               return_grad_std=return_grad_std,
                               solver=solver, precision=self.precision)

    def __repr__(self):
        s = self.stats
        return (f"GPGState(kernel={self.spec.name!r}, n={s['n']}, "
                f"d={self.d}, window={self.window}, "
                f"solves={s['n_solve']}, refactors={s['n_refactor']})")
