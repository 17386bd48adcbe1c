"""Multi-tenant GP fleet: a (B,)-stacked ``GPGData`` stepped by ONE program.

The ROADMAP north star is "millions of users" — i.e. millions of
*independent* gradient-GP posteriors, not one big one.  ``GPGData`` is
already a fixed-capacity, jit-compatible pytree, so the whole incremental
lifecycle (``core/state.py``) batches with ``jax.vmap``:

  ``FleetGPGData``   — every ``GPGData`` leaf stacked to ``(B, ...)``, plus
                       per-tenant ``noise``/``signal`` hyper vectors and an
                       ``active`` lane mask.  Per-tenant count, Lambda,
                       noise, and signal all ride as TRACED arrays, so one
                       compiled program serves a heterogeneous tenant mix
                       (different N, different hypers) without retracing.
  ``fleet_extend``   — vmapped bordered-Cholesky append (auto-evict at the
                       window), masked per lane: unselected/inactive lanes
                       pass through bit-untouched.
  ``fleet_evict``    — vmapped sliding-window evict.
  ``fleet_resolve``  — vmapped re-solve against new per-tenant RHS.
  ``fleet_posterior``— vmapped batched posterior queries (B, Q, D).
  ``fleet_refit``    — vmapped MLL ascent (``hyper.fit.fit_scan_fn`` on the
                       per-tenant (N, N) evidence strips) + refactor.

Masking convention (DESIGN.md sec. 15): ops take a ``(B,)`` boolean lane
mask; the vmapped update is computed for every lane and the result is
selected leaf-wise against the old pytree, so masked lanes are EXACTLY the
old bits — a lane full of garbage (or NaNs) can never taint its neighbours
(there is no cross-lane contraction anywhere in the vmapped program) and
fleet-level reductions (``fleet_total_mll``) zero inactive lanes before
summing.  Per-tenant correctness is the single-tenant state machine's:
lane b of a fleet trajectory equals the same op sequence driven through
``GPGState`` (fuzz-asserted in tests/test_property_invariants.py).

``GPFleet`` is the host-facing wrapper (slot allocation, compile-watched
launches, revision bookkeeping); the continuous-batching request front end
lives in ``train/serve.py::GPFleetServer``.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.obs import compile_watch as _cw
from repro.obs import trace as _obs

from .kernels import KernelSpec, get_kernel
from .gram import GramFactors
from .query import PosteriorBatch, make_query_fn
from .state import (GPGData, gpg_evict, gpg_extend, gpg_init, gpg_refactor,
                    gpg_resolve)

Array = jnp.ndarray


class FleetGPGData(NamedTuple):
    """B independent posterior states as one jit-compatible pytree.

    data:   ``GPGData`` with every leaf stacked to (B, ...) — per-lane
            X/G/Xt/Z (B, cap, D), factor strips + L (B, cap, cap), lam /
            count / solver stats (B,).
    noise:  (B,) raw per-tenant noise variance sigma^2.
    signal: (B,) per-tenant signal variance s^2.
    active: (B,) bool — live tenant lanes; inactive lanes are zeroed-out
            empty states and every fleet op masks them.
    """

    data: GPGData
    noise: Array
    signal: Array
    active: Array

    @property
    def batch(self) -> int:
        return self.data.count.shape[0]

    @property
    def capacity(self) -> int:
        return self.data.X.shape[1]

    @property
    def d(self) -> int:
        return self.data.X.shape[2]


def _lane_select(op: Array, new, old):
    """Leaf-wise ``where`` on the leading lane axis: masked lanes keep the
    OLD bits exactly (the no-taint contract)."""
    def pick(a, b):
        o = op.reshape(op.shape + (1,) * (a.ndim - 1))
        return jnp.where(o, a, b)

    return jax.tree_util.tree_map(pick, new, old)


def _noise_eff(fleet: FleetGPGData) -> Array:
    """(B,) effective noise sigma^2/s^2 — what the unscaled solves see."""
    return fleet.noise / fleet.signal


def fleet_lane(fleet: FleetGPGData, b: int) -> GPGData:
    """Lane ``b`` as a plain single-tenant ``GPGData`` view."""
    return jax.tree_util.tree_map(lambda leaf: leaf[b], fleet.data)


def fleet_init(
    spec: KernelSpec,
    d: int,
    capacity: int,
    batch: int,
    *,
    lam=1.0,
    noise=0.0,
    signal=1.0,
    active: bool = False,
    dtype=None,
) -> FleetGPGData:
    """An empty B-lane fleet (every lane an empty ``gpg_init`` state)."""
    single = gpg_init(spec, int(d), int(capacity), lam=1.0, dtype=dtype)
    data = jax.tree_util.tree_map(
        lambda leaf: jnp.broadcast_to(leaf, (int(batch),) + leaf.shape),
        single)
    dt = data.X.dtype
    ones = jnp.ones((int(batch),), dt)
    data = data._replace(lam=jnp.asarray(lam, dt) * ones)
    return FleetGPGData(
        data=data,
        noise=jnp.asarray(noise, dt) * ones,
        signal=jnp.asarray(signal, dt) * ones,
        active=jnp.full((int(batch),), bool(active)),
    )


def _resolve_op(fleet: FleetGPGData, op: Optional[Array]) -> Array:
    op = fleet.active if op is None else jnp.asarray(op) & fleet.active
    return op


# ---------------------------------------------------------------------------
# Lifecycle ops: vmapped + lane-masked (all pure and jit/vmap-safe)
# ---------------------------------------------------------------------------


def fleet_extend(
    spec: KernelSpec,
    fleet: FleetGPGData,
    X: Array,
    G: Array,
    op: Optional[Array] = None,
    *,
    window: Optional[int] = None,
    jitter: float = 1e-10,
    deg_thresh: float = 1e-8,
    tol: float = 1e-10,
    maxiter: Optional[int] = None,
    solve: bool = True,
) -> FleetGPGData:
    """Append one (x, grad) observation per selected lane — one launch.

    X/G: (B, D) payload rows (ignored on unselected lanes).  With a static
    ``window``, selected lanes already at the window auto-evict their
    oldest observation first (solve deferred to the post-extend re-solve),
    mirroring ``GPGState.extend``.  Lanes must satisfy count < capacity
    (window lanes do by construction; the host wrapper enforces the rest).
    """
    op = _resolve_op(fleet, op)
    data = fleet.data
    noise = _noise_eff(fleet)
    mi = int(maxiter) if maxiter is not None else 10 * fleet.capacity + 50
    if window is not None:
        evict_mask = op & (data.count >= int(window))
        evicted = jax.vmap(
            lambda d, nz: gpg_evict(spec, d, noise=nz, solve=False)
        )(data, noise)
        data = _lane_select(evict_mask, evicted, data)
    # full lanes never extend (count would drift past capacity and corrupt
    # the row mask); window lanes just evicted, so this only trims no-window
    # misuse — the host wrapper raises instead of silently dropping
    op = op & (data.count < fleet.capacity)
    new = jax.vmap(
        lambda d, x, g, nz: gpg_extend(
            spec, d, x, g, noise=nz, jitter=jitter, deg_thresh=deg_thresh,
            tol=tol, maxiter=mi, solve=solve)
    )(data, jnp.asarray(X, data.X.dtype), jnp.asarray(G, data.X.dtype),
      noise)
    return fleet._replace(data=_lane_select(op, new, data))


def fleet_evict(
    spec: KernelSpec,
    fleet: FleetGPGData,
    op: Optional[Array] = None,
    *,
    tol: float = 1e-10,
    maxiter: Optional[int] = None,
    solve: bool = True,
) -> FleetGPGData:
    """Drop the oldest observation on each selected lane — one launch."""
    op = _resolve_op(fleet, op) & (fleet.data.count > 0)
    mi = int(maxiter) if maxiter is not None else 10 * fleet.capacity + 50
    new = jax.vmap(
        lambda d, nz: gpg_evict(spec, d, noise=nz, tol=tol, maxiter=mi,
                                solve=solve)
    )(fleet.data, _noise_eff(fleet))
    return fleet._replace(data=_lane_select(op, new, fleet.data))


def fleet_resolve(
    spec: KernelSpec,
    fleet: FleetGPGData,
    rhs: Array,
    op: Optional[Array] = None,
    *,
    tol: float = 1e-10,
    maxiter: Optional[int] = None,
) -> FleetGPGData:
    """Re-solve selected lanes against new (B, cap, D) right-hand sides.

    Zero refactorization (the GP-X path): factors and Cholesky untouched,
    so per-tenant variance-solver caches keyed on the factor revision stay
    valid across this op (``train/serve.py``).
    """
    op = _resolve_op(fleet, op)
    mi = int(maxiter) if maxiter is not None else 10 * fleet.capacity + 50
    new = jax.vmap(
        lambda d, r, nz: gpg_resolve(spec, d, r, noise=nz, tol=tol,
                                     maxiter=mi)
    )(fleet.data, jnp.asarray(rhs, fleet.data.X.dtype), _noise_eff(fleet))
    return fleet._replace(data=_lane_select(op, new, fleet.data))


def fleet_refactor(
    spec: KernelSpec,
    fleet: FleetGPGData,
    lam: Optional[Array] = None,
    op: Optional[Array] = None,
    *,
    jitter: float = 1e-10,
    tol: float = 1e-10,
    maxiter: Optional[int] = None,
) -> FleetGPGData:
    """Full per-lane factor rebuild (e.g. after a Lambda refresh)."""
    op = _resolve_op(fleet, op)
    mi = int(maxiter) if maxiter is not None else 10 * fleet.capacity + 50
    lam_b = fleet.data.lam if lam is None else jnp.asarray(
        lam, fleet.data.X.dtype)
    new = jax.vmap(
        lambda d, lm, nz: gpg_refactor(spec, d, lm, noise=nz, jitter=jitter,
                                       tol=tol, maxiter=mi)
    )(fleet.data, lam_b, _noise_eff(fleet))
    return fleet._replace(data=_lane_select(op, new, fleet.data))


def fleet_posterior(
    spec: KernelSpec,
    fleet: FleetGPGData,
    Xq: Array,
) -> PosteriorBatch:
    """Batched posterior means for every lane: Xq (B, Q, D) -> (B, Q[, D]).

    Pure cross-covariance contractions against each lane's cached solve —
    zero re-solves, exactly the single-tenant query path vmapped over the
    lane axis (padded rows are inert, so fixed-capacity views keep the
    compiled shapes stable across per-tenant count changes).  Lanes with
    count == 0 (including inactive lanes) return exact zeros.
    """
    qfn = make_query_fn(spec)

    def one(d: GPGData, xq: Array) -> PosteriorBatch:
        f = GramFactors(K1e=d.K1e, K2e=d.K2e, Xt=d.Xt, lam=d.lam,
                        noise=0.0, c=None)
        return qfn(f, d.Z, xq)

    return jax.vmap(one)(fleet.data, jnp.asarray(Xq, fleet.data.X.dtype))


# ---------------------------------------------------------------------------
# Model selection: vmapped evidence + refit
# ---------------------------------------------------------------------------


def _lane_hypers(d: GPGData, noise: Array, signal: Array):
    """Per-lane ``HyperParams`` from the traced lam/noise/signal scalars."""
    from repro.hyper import HyperParams

    return HyperParams(
        log_lengthscale2=-jnp.log(d.lam),
        log_signal=jnp.log(signal),
        log_noise=jnp.log(jnp.maximum(noise, 1e-30)),
    )


def _lane_strips(d: GPGData):
    """The lane's (cap, cap) evidence strips; zero-padded rows are inert."""
    from repro.hyper.mll import strips_for_mll

    return strips_for_mll(d.X, d.G)


def fleet_mll(spec: KernelSpec, fleet: FleetGPGData) -> Array:
    """(B,) exact per-lane log marginal likelihood at the current hypers.

    Evidence is computed from the per-lane (N, N) strips with the count
    mask (``hyper.mll.mll_from_strips``), so uneven per-tenant N shares
    one compiled program.  Empty lanes evaluate to exactly 0.
    """
    from repro.hyper.mll import mll_from_strips

    d_dim = fleet.d

    def one(d: GPGData, nz: Array, sg: Array) -> Array:
        S0, C, GG = _lane_strips(d)
        h = _lane_hypers(d, nz, sg)
        return mll_from_strips(spec, S0, C, GG, d_dim, h, count=d.count)

    return jax.vmap(one)(fleet.data, fleet.noise, fleet.signal)


def fleet_total_mll(spec: KernelSpec, fleet: FleetGPGData) -> Array:
    """Masked fleet evidence: sum of per-lane MLL over ACTIVE, non-empty
    lanes only — padded/inactive tenants contribute exactly zero (the
    invariant tests/test_fleet.py taints for)."""
    per = fleet_mll(spec, fleet)
    keep = fleet.active & (fleet.data.count > 0)
    return jnp.sum(jnp.where(keep, per, 0.0))


def fleet_refit(
    spec: KernelSpec,
    fleet: FleetGPGData,
    op: Optional[Array] = None,
    *,
    steps: int = 16,
    lr: float = 0.1,
    mask=None,
    jitter: float = 1e-10,
    tol: float = 1e-10,
    maxiter: Optional[int] = None,
) -> tuple[FleetGPGData, Array]:
    """Refit every selected lane's hypers by MLL ascent, then refactor.

    The vmapped analogue of ``GPGState.refit``: per lane, a fixed-step
    traceable Adam ascent (``hyper.fit.fit_scan_fn``) on the strips-form
    evidence closure seeded from the lane's current hypers, followed by
    the one legitimate full refactorization + re-solve at the fitted
    Lambda/noise.  Selected lanes need count >= 2 (others are masked out).
    Returns ``(fleet', (B,) fitted mll)`` — masked lanes keep their old
    state bit-exactly and report mll 0.
    """
    from repro.hyper.fit import fit_scan_fn
    from repro.hyper.mll import make_mll_strips_fn

    op = _resolve_op(fleet, op) & (fleet.data.count >= 2)
    d_dim = fleet.d
    mi = int(maxiter) if maxiter is not None else 10 * fleet.capacity + 50

    def one(d: GPGData, nz: Array, sg: Array):
        S0, C, GG = _lane_strips(d)
        fn = make_mll_strips_fn(spec, S0, C, GG, d_dim, count=d.count)
        h, m = fit_scan_fn(fn, _lane_hypers(d, nz, sg), steps=steps, lr=lr,
                           mask=mask)
        new = gpg_refactor(spec, d, h.lam, noise=h.noise_eff, jitter=jitter,
                           tol=tol, maxiter=mi)
        return new, h.noise, h.signal, m

    news, nzs, sgs, mlls = jax.vmap(one)(fleet.data, fleet.noise,
                                         fleet.signal)
    return fleet._replace(
        data=_lane_select(op, news, fleet.data),
        noise=jnp.where(op, nzs, fleet.noise),
        signal=jnp.where(op, sgs, fleet.signal),
    ), jnp.where(op, mlls, 0.0)


# ---------------------------------------------------------------------------
# Tenant lifecycle: join / leave (lane reset keeps inactive lanes taint-free)
# ---------------------------------------------------------------------------


def _reset_lane(fleet: FleetGPGData, slot: Array, *, lam, noise, signal,
                active: bool) -> FleetGPGData:
    d0 = fleet.data
    cap, dim = fleet.capacity, fleet.d
    dt = d0.X.dtype
    zrow = jnp.zeros((cap, dim), dt)
    znn = jnp.zeros((cap, cap), dt)
    zero = jnp.zeros((), dt)
    data = d0._replace(
        X=d0.X.at[slot].set(zrow), G=d0.G.at[slot].set(zrow),
        Xt=d0.Xt.at[slot].set(zrow), Z=d0.Z.at[slot].set(zrow),
        K1e=d0.K1e.at[slot].set(znn), K2e=d0.K2e.at[slot].set(znn),
        L=d0.L.at[slot].set(jnp.eye(cap, dtype=dt)),
        lam=d0.lam.at[slot].set(jnp.asarray(lam, dt)),
        count=d0.count.at[slot].set(0),
        n_refactor=d0.n_refactor.at[slot].set(0),
        n_solve=d0.n_solve.at[slot].set(0),
        cg_iters=d0.cg_iters.at[slot].set(0),
        resnorm=d0.resnorm.at[slot].set(zero),
    )
    return fleet._replace(
        data=data,
        noise=fleet.noise.at[slot].set(jnp.asarray(noise, dt)),
        signal=fleet.signal.at[slot].set(jnp.asarray(signal, dt)),
        active=fleet.active.at[slot].set(bool(active)),
    )


def fleet_join(fleet: FleetGPGData, slot: Array, *, lam=1.0, noise=0.0,
               signal=1.0) -> FleetGPGData:
    """Claim lane ``slot`` for a new tenant: a fresh empty state with the
    tenant's hypers, active.  ``slot`` may be traced (one compile serves
    every join)."""
    return _reset_lane(fleet, slot, lam=lam, noise=noise, signal=signal,
                       active=True)


def fleet_leave(fleet: FleetGPGData, slot: Array) -> FleetGPGData:
    """Release lane ``slot``: zero the lane AND deactivate it, so a freed
    slot can never taint fleet-level reductions or future joins."""
    return _reset_lane(fleet, slot, lam=1.0, noise=0.0, signal=1.0,
                       active=False)


# ---------------------------------------------------------------------------
# Host-facing wrapper: slot allocation + compile-watched launches
# ---------------------------------------------------------------------------


class GPFleet:
    """A fleet of independent streaming GP posteriors behind ONE program
    per op.

    >>> fl = GPFleet("rbf", d=8, window=4, batch=16)
    >>> fl.join("alice", lam=0.1, noise=1e-8)
    >>> fl.extend({"alice": (x, g)})          # one vmapped launch
    >>> out = fl.posterior({"alice": Xq})     # one vmapped launch
    >>> fl.refit(["alice"])                   # vmapped MLL ascent

    Every lifecycle op is a single compile-watched jitted launch over the
    whole fleet; per-tenant count/noise/signal/Lambda are traced arrays,
    so tenant churn (join, extend to capacity, evict, refit, leave) reuses
    one executable per op — asserted in tests/test_fleet.py.  Capacity and
    batch are static; the batch grows by doubling (each doubling is one
    new signature, so signatures stay O(log tenants)).
    """

    def __init__(
        self,
        kernel: str | KernelSpec = "rbf",
        d: int | None = None,
        *,
        capacity: int = 8,
        batch: int = 8,
        window: int | None = None,
        lam=1.0,
        noise: float = 0.0,
        signal: float = 1.0,
        jitter: float = 1e-10,
        deg_thresh: float = 1e-8,
        tol: float = 1e-10,
        maxiter: int | None = None,
        dtype=None,
    ):
        if d is None:
            raise TypeError("GPFleet needs the input dimension d")
        self.spec = get_kernel(kernel) if isinstance(kernel, str) else kernel
        self.window = int(window) if window else None
        cap = self.window if self.window else int(capacity)
        self.defaults = {"lam": lam, "noise": float(noise),
                         "signal": float(signal)}
        self.jitter = float(jitter)
        self.deg_thresh = float(deg_thresh)
        self.tol = float(tol)
        self.maxiter = maxiter
        self.fleet = fleet_init(self.spec, int(d), cap, int(batch),
                                lam=lam, noise=noise, signal=signal,
                                active=False, dtype=dtype)
        self._slots: dict = {}                  # tenant id -> lane index
        self._free = list(range(int(batch)))[::-1]
        # per-lane monotonic revision counters (same contract as GPGState:
        # factor_revision keys the serve layer's variance-solver LRU)
        self.revision = [0] * int(batch)
        self.factor_revision = [0] * int(batch)
        self._ops: dict = {}
        if _obs.enabled():
            for name in ("fleet.launches", "fleet.extend_calls",
                         "fleet.evict_calls", "fleet.refit_calls",
                         "fleet.query_calls", "fleet.joins", "fleet.leaves"):
                _obs.REGISTRY.inc(name, 0)

    # -- slot management ---------------------------------------------------

    @property
    def batch(self) -> int:
        return self.fleet.batch

    @property
    def capacity(self) -> int:
        return self.fleet.capacity

    @property
    def d(self) -> int:
        return self.fleet.d

    @property
    def tenants(self) -> list:
        return list(self._slots)

    def slot_of(self, tenant) -> int:
        return self._slots[tenant]

    def n(self, tenant) -> int:
        return int(self.fleet.data.count[self._slots[tenant]])

    def state_view(self, tenant) -> GPGData:
        """The tenant's lane as a plain single-tenant ``GPGData``."""
        return fleet_lane(self.fleet, self._slots[tenant])

    def hypers_of(self, tenant) -> dict:
        b = self._slots[tenant]
        return {"lam": float(self.fleet.data.lam[b]),
                "noise": float(self.fleet.noise[b]),
                "signal": float(self.fleet.signal[b])}

    def _grow(self) -> None:
        """Double the lane count by zero-padding every leaf (exact; a new
        compile signature per doubling)."""
        b0 = self.batch
        fl = self.fleet

        def pad(leaf):
            return jnp.concatenate(
                [leaf, jnp.zeros((b0,) + leaf.shape[1:], leaf.dtype)])

        data = jax.tree_util.tree_map(pad, fl.data)
        # padded lanes must be valid EMPTY states, not all-zero garbage
        eye = jnp.broadcast_to(jnp.eye(self.capacity, dtype=data.L.dtype),
                               (b0, self.capacity, self.capacity))
        data = data._replace(
            L=data.L.at[b0:].set(eye),
            lam=data.lam.at[b0:].set(1.0))
        self.fleet = FleetGPGData(
            data=data, noise=pad(fl.noise),
            signal=jnp.concatenate(
                [fl.signal, jnp.ones((b0,), fl.signal.dtype)]),
            active=jnp.concatenate(
                [fl.active, jnp.zeros((b0,), bool)]))
        self._free = list(range(b0, 2 * b0))[::-1] + self._free
        self.revision += [0] * b0
        self.factor_revision += [0] * b0

    def join(self, tenant, *, lam=None, noise=None, signal=None) -> int:
        """Admit a tenant (grows the fleet when full); returns its lane."""
        if tenant in self._slots:
            raise ValueError(f"tenant {tenant!r} already joined")
        if not self._free:
            self._grow()
        slot = self._free.pop()
        dd = self.defaults
        self.fleet = self._launch(
            "join", lambda fl, s, lm, nz, sg: fleet_join(
                fl, s, lam=lm, noise=nz, signal=sg),
            self.fleet, jnp.asarray(slot, jnp.int32),
            jnp.asarray(dd["lam"] if lam is None else lam),
            jnp.asarray(dd["noise"] if noise is None else noise),
            jnp.asarray(dd["signal"] if signal is None else signal))
        self._slots[tenant] = slot
        self._bump(slot)
        if _obs.enabled():
            _obs.REGISTRY.inc("fleet.joins")
            _obs.REGISTRY.set_gauge("fleet.active_tenants", len(self._slots))
        return slot

    def leave(self, tenant) -> None:
        """Evict a tenant and free its lane (zeroed: no residual taint)."""
        slot = self._slots.pop(tenant)
        self.fleet = self._launch(
            "leave", lambda fl, s: fleet_leave(fl, s),
            self.fleet, jnp.asarray(slot, jnp.int32))
        self._free.append(slot)
        self._bump(slot)
        if _obs.enabled():
            _obs.REGISTRY.inc("fleet.leaves")
            _obs.REGISTRY.set_gauge("fleet.active_tenants", len(self._slots))

    def quarantine(self, tenant) -> None:
        """Isolate a poisoned tenant: flip its active mask off and free
        the lane (a ``leave``, NOT a repack — the other lanes' bits and
        the compile signature are untouched)."""
        self.leave(tenant)
        if _obs.enabled():
            _obs.REGISTRY.inc("fleet.quarantines")
            _obs.REGISTRY.inc("resilience.quarantined")
        _obs.emit({"type": "quarantine", "tenant": str(tenant)})

    # -- compile-watched launches ------------------------------------------

    def _launch(self, name: str, make_fn, *args):
        """Run op ``name`` through its cached compile-watched jit (ONE
        executable per op x signature — the fleet compile-stability
        contract)."""
        step = self._ops.get(name)
        if step is None:
            step = self._ops[name] = _cw.wrap(make_fn, name=f"fleet_{name}")
        if _obs.enabled():
            _obs.REGISTRY.inc("fleet.launches")
        return step(*args)

    def _bump(self, slot: int, factors: bool = True) -> None:
        self.revision[slot] += 1
        if factors:
            self.factor_revision[slot] += 1

    def _mask_of(self, tenants) -> Array:
        import numpy as np

        m = np.zeros((self.batch,), bool)
        for t in tenants:
            m[self._slots[t]] = True
        return jnp.asarray(m)

    # -- batched lifecycle -------------------------------------------------

    def extend(self, obs: dict) -> "GPFleet":
        """Append one (x, g) observation per tenant: ``{tenant: (x, g)}``
        — ONE vmapped launch for the whole group (auto-evict at the
        window)."""
        import numpy as np

        from repro.resilience import guardrails as _guard

        if not obs:
            return self
        with _obs.span("fleet.pack"):
            for t, (x, g) in obs.items():
                _guard.check_finite(x, g, what="observation", tenant=t)
            if not self.window:
                for t in obs:
                    if self.n(t) >= self.capacity:
                        raise ValueError(
                            f"tenant {t!r} is at capacity={self.capacity} "
                            "(no window configured)")
            X = np.zeros((self.batch, self.d), dtype=np.asarray(
                self.fleet.data.X).dtype)
            G = np.zeros_like(X)
            for t, (x, g) in obs.items():
                b = self._slots[t]
                X[b], G[b] = np.asarray(x), np.asarray(g)
            args = (jnp.asarray(X), jnp.asarray(G), self._mask_of(obs))
        with _obs.span("fleet.extend", tenants=len(obs)):
            self.fleet = self._launch(
                "extend", lambda fl, X_, G_, op: fleet_extend(
                    self.spec, fl, X_, G_, op, window=self.window,
                    jitter=self.jitter, deg_thresh=self.deg_thresh,
                    tol=self.tol, maxiter=self.maxiter),
                self.fleet, *args)
            if _obs.enabled():
                _obs.REGISTRY.inc("fleet.extend_calls", len(obs))
        for t in obs:
            self._bump(self._slots[t])
        return self

    def evict(self, tenants) -> "GPFleet":
        """Drop the oldest observation of each listed tenant — one launch."""
        tenants = list(tenants)
        if not tenants:
            return self
        with _obs.span("fleet.evict", tenants=len(tenants)):
            self.fleet = self._launch(
                "evict", lambda fl, op: fleet_evict(
                    self.spec, fl, op, tol=self.tol, maxiter=self.maxiter),
                self.fleet, self._mask_of(tenants))
            if _obs.enabled():
                _obs.REGISTRY.inc("fleet.evict_calls", len(tenants))
        for t in tenants:
            self._bump(self._slots[t])
        return self

    def resolve(self, rhs: dict) -> "GPFleet":
        """Re-solve listed tenants against new RHS: ``{tenant: (n, D)}``.
        Factors untouched — per-tenant ``factor_revision`` keys stay
        valid."""
        import numpy as np

        if not rhs:
            return self
        with _obs.span("fleet.pack"):
            R = np.zeros((self.batch, self.capacity, self.d),
                         dtype=np.asarray(self.fleet.data.X).dtype)
            for t, r in rhs.items():
                r = np.atleast_2d(np.asarray(r))
                R[self._slots[t], : r.shape[0]] = r
            args = (jnp.asarray(R), self._mask_of(rhs))
        with _obs.span("fleet.resolve", tenants=len(rhs)):
            self.fleet = self._launch(
                "resolve", lambda fl, R_, op: fleet_resolve(
                    self.spec, fl, R_, op, tol=self.tol,
                    maxiter=self.maxiter),
                self.fleet, *args)
        for t in rhs:
            self._bump(self._slots[t], factors=False)
        return self

    def refit(self, tenants, *, steps: int = 16, lr: float = 0.1,
              mask=None) -> dict:
        """MLL-refit the listed tenants (vmapped fit + refactor — one
        launch); returns ``{tenant: fitted mll}``."""
        tenants = [t for t in tenants if self.n(t) >= 2]
        if not tenants:
            return {}
        with _obs.span("fleet.refit", tenants=len(tenants)):
            self.fleet, mlls = self._launch(
                f"refit{steps}", lambda fl, op: fleet_refit(
                    self.spec, fl, op, steps=steps, lr=lr, mask=mask,
                    jitter=self.jitter, tol=self.tol, maxiter=self.maxiter),
                self.fleet, self._mask_of(tenants))
            if _obs.enabled():
                _obs.REGISTRY.inc("fleet.refit_calls", len(tenants))
        for t in tenants:
            self._bump(self._slots[t])
        return {t: float(mlls[self._slots[t]]) for t in tenants}

    def posterior(self, queries: dict, *, q_pad: int | None = None) -> dict:
        """Batched posterior means: ``{tenant: (q, D)}`` -> ``{tenant:
        PosteriorBatch}`` — ONE vmapped launch, requests padded to a
        shared Q bucket (``q_pad`` or the next power of two)."""
        import numpy as np

        if not queries:
            return {}
        with _obs.span("fleet.pack"):
            qs = {t: np.atleast_2d(np.asarray(x))
                  for t, x in queries.items()}
            qmax = max(x.shape[0] for x in qs.values())
            Q = int(q_pad) if q_pad else 1 << (qmax - 1).bit_length()
            if qmax > Q:
                raise ValueError(f"request of {qmax} queries exceeds "
                                 f"q_pad={Q}")
            Xq = np.zeros((self.batch, Q, self.d), dtype=np.asarray(
                self.fleet.data.X).dtype)
            for t, x in qs.items():
                Xq[self._slots[t], : x.shape[0]] = x
            Xq = jnp.asarray(Xq)
        with _obs.span("fleet.query", tenants=len(qs), q=Q):
            out = self._launch(
                "posterior", lambda fl, Xq_: fleet_posterior(
                    self.spec, fl, Xq_),
                self.fleet, Xq)
            if _obs.enabled():
                _obs.REGISTRY.inc("fleet.query_calls", len(qs))
                _obs.REGISTRY.inc("fleet.query_points",
                                  sum(x.shape[0] for x in qs.values()))
        with _obs.span("fleet.unpack"):
            return {
                t: PosteriorBatch(
                    value=out.value[self._slots[t], : x.shape[0]],
                    grad=out.grad[self._slots[t], : x.shape[0]])
                for t, x in qs.items()
            }

    def mll(self, tenants=None) -> dict:
        """Per-tenant exact MLL at current hypers (one vmapped launch)."""
        tenants = self.tenants if tenants is None else list(tenants)
        per = self._launch(
            "mll", lambda fl: fleet_mll(self.spec, fl), self.fleet)
        return {t: float(per[self._slots[t]]) for t in tenants
                if self.n(t) > 0}

    def __repr__(self):
        return (f"GPFleet(kernel={self.spec.name!r}, tenants="
                f"{len(self._slots)}/{self.batch}, cap={self.capacity}, "
                f"d={self.d}, window={self.window})")
