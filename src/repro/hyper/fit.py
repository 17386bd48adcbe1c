"""Hyperparameter fitting: maximize the structured exact MLL.

Two entry points over the unconstrained log-reparameterized ``HyperParams``
pytree (``jax.grad`` through ``mll.mll`` is the exact evidence gradient —
no ELBOs, no sampling):

  * :func:`fit`      — host-facing: one jit-compiled Adam step, a python
                       loop with patience-based early stopping, bound
                       guards, and non-finite-step rejection.  Returns a
                       :class:`FitResult` scorecard.
  * :func:`fit_scan` — pure/traceable fixed-step ``lax.scan`` variant for
                       use INSIDE a jitted consumer (the periodic MLL
                       refresh of ``optim/gp_precond.py`` runs this in the
                       sharded training step).

Bound guards: after every Adam step the log-hypers are clamped into
``BOUNDS`` (wide but finite boxes) so a bad gradient can never drive the
lengthscale or noise to 0/inf and poison downstream Cholesky/CG.  A
``mask`` pytree (1.0 = trainable) freezes individual hypers — the
in-training refresh fits the lengthscale only, holding the configured
noise fixed.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

# aliased import: fit_scan's scan outputs are locally named ``trace``
from repro.obs import injit as _obs_tap
from repro.obs import trace as _obs

from .mll import _as_spec, make_mll_fn, mll
from .params import HyperParams

Array = jnp.ndarray

#: Hard boxes on the log-hypers (natural values: ell^2 in [1e-6, 1e12],
#: s^2 in [1e-8, 1e8], sigma^2 in [1e-14, 1e2]).
BOUNDS = HyperParams(
    log_lengthscale2=(math.log(1e-6), math.log(1e12)),
    log_signal=(math.log(1e-8), math.log(1e8)),
    log_noise=(math.log(1e-14), math.log(1e2)),
)

FULL_MASK = HyperParams(1.0, 1.0, 1.0)
LENGTHSCALE_ONLY = HyperParams(1.0, 0.0, 0.0)


def _clip(h: HyperParams) -> HyperParams:
    return HyperParams(*[
        jnp.clip(v, lo, hi) for v, (lo, hi) in zip(h, BOUNDS)])


def _mask_grad(g: HyperParams, mask: HyperParams) -> HyperParams:
    """Zero non-finite gradient entries and frozen (mask=0) fields,
    preserving each leaf's dtype (the f32 in-jit path must stay f32)."""
    return jax.tree_util.tree_map(
        lambda g_, msk: jnp.where(jnp.isfinite(g_), g_, 0.0)
        * jnp.asarray(msk, g_.dtype), g, mask)


class FitResult(NamedTuple):
    """What a fit did: fitted hypers + the evidence trajectory endpoints."""

    hypers: HyperParams
    mll: Array            # best (= final reported) log marginal likelihood
    mll0: Array           # MLL at the init — improvement = mll - mll0
    n_steps: int
    converged: bool       # early-stopped on the improvement tolerance
    history: Optional[Array] = None   # per-step MLL trace (host fit only)

    @property
    def improvement(self) -> float:
        return float(self.mll - self.mll0)


def _adam_update(g, m, v, step, lr, b1=0.9, b2=0.999, eps=1e-8):
    m = jax.tree_util.tree_map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
    v = jax.tree_util.tree_map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_,
                               v, g)
    t = step + 1
    mh = jax.tree_util.tree_map(lambda m_: m_ / (1 - b1 ** t), m)
    vh = jax.tree_util.tree_map(lambda v_: v_ / (1 - b2 ** t), v)
    upd = jax.tree_util.tree_map(
        lambda m_, v_: lr * m_ / (jnp.sqrt(v_) + eps), mh, vh)
    return upd, m, v


def fit_scan_fn(
    fn,
    init: HyperParams,
    *,
    steps: int = 16,
    lr: float = 0.1,
    mask: Optional[HyperParams] = None,
) -> tuple[HyperParams, Array]:
    """Traceable Adam ascent on an arbitrary hypers->mll closure.

    The engine under :func:`fit_scan`; also consumed directly with
    ``mll.make_mll_strips_fn`` closures, where the (N, N) strips were
    psummed once and every fit step is collective-free under sharding.
    Guards: non-finite gradients are zeroed (the step is a no-op instead
    of a poison), every iterate is clamped into ``BOUNDS``, and the
    returned hypers are the LAST iterate with a final non-finite fallback
    to the init.  Safe to call under jit / shard_map.
    """
    vg = jax.value_and_grad(fn)
    m0 = FULL_MASK if mask is None else mask

    zeros = jax.tree_util.tree_map(lambda v: jnp.zeros_like(jnp.asarray(v)),
                                   init)

    def body(carry, step):
        h, m, v = carry
        val, g = vg(h)
        g = _mask_grad(g, m0)
        upd, m, v = _adam_update(g, m, v, step, lr)
        h = _clip(jax.tree_util.tree_map(
            lambda p, u: (p + u).astype(jnp.asarray(p).dtype), h, upd))
        return (h, m, v), val

    (h, _, _), trace = jax.lax.scan(body, (init, zeros, zeros),
                                    jnp.arange(steps))
    final = fn(h)
    ok = jnp.isfinite(final) & jax.tree_util.tree_reduce(
        lambda a, b: a & b,
        jax.tree_util.tree_map(lambda v: jnp.all(jnp.isfinite(v)), h))
    _obs_tap.tap("hyper.fit_scan.final_mll", final)
    _obs_tap.tap("hyper.fit_scan.nonfinite_fallback", ~ok, kind="counter")
    h = jax.tree_util.tree_map(
        lambda a, b: jnp.where(ok, a, b), h, _clip(init))
    return h, jnp.where(ok, final, trace[0] if steps else final)


def fit_scan(
    kernel,
    X: Array,
    G: Array,
    init: HyperParams,
    *,
    steps: int = 16,
    lr: float = 0.1,
    c: Optional[Array] = None,
    mask: Optional[HyperParams] = None,
) -> tuple[HyperParams, Array]:
    """Fixed-step traceable Adam ascent on the MLL; returns (hypers, mll).

    Thin wrapper: builds the (X, G) evidence closure and runs
    :func:`fit_scan_fn` (see there for the in-scan guards).
    """
    fn = make_mll_fn(kernel, X, G, c=c)
    return fit_scan_fn(fn, init, steps=steps, lr=lr, mask=mask)


def fit(
    kernel,
    X: Array,
    G: Array,
    init: Optional[HyperParams] = None,
    *,
    c: Optional[Array] = None,
    steps: int = 200,
    lr: float = 0.08,
    tol: float = 1e-6,
    patience: int = 12,
    mask: Optional[HyperParams] = None,
) -> FitResult:
    """Maximize the exact structured MLL with early stopping.

    One Adam step is jit-compiled once; the python loop tracks the best
    iterate and stops after ``patience`` steps without a relative
    improvement > ``tol``.  ``init=None`` seeds the lengthscale from the
    mean-pairwise-distance heuristic (``optim.gp_directions.
    auto_lengthscale`` — exactly the init the MLL fit is meant to beat).
    """
    X = jnp.atleast_2d(X)
    G = jnp.asarray(G)
    if init is None:
        from repro.optim.gp_directions import auto_lengthscale  # deferred:
        # optim imports repro.hyper at module level; this import runs at
        # call time when both packages are complete.
        init = HyperParams.from_lam(auto_lengthscale(X), signal=1.0,
                                    noise=1e-8)
    spec = _as_spec(kernel)

    def fn(hypers, X, G, c):
        return mll(spec, X, G, hypers, c=c)

    return fit_fn(fn, init, args=(X, G, c), steps=steps, lr=lr, tol=tol,
                  patience=patience, mask=mask)


def fit_fn(
    fn,
    init: HyperParams,
    *,
    args: tuple = (),
    steps: int = 200,
    lr: float = 0.08,
    tol: float = 1e-6,
    patience: int = 12,
    mask: Optional[HyperParams] = None,
) -> FitResult:
    """Host fit loop over an arbitrary hypers->mll closure (engine of
    :func:`fit`; also consumed with ``mll.make_mll_strips_fn`` closures by
    the sharded state's ``refit`` — the strips are psummed once, then the
    whole fit is replicated host compute with zero collectives).

    ``fn`` is called as ``fn(hypers, *args)``.  Data arrays belong in
    ``args``: they then reach the compiled Adam step as arguments, where a
    closure over them would bake them into the executable as constants (at
    D = 2**24 a 3.9 GB executable and minutes of compile)."""
    init = _clip(jax.tree_util.tree_map(jnp.asarray, init))
    vg = jax.value_and_grad(fn)
    m0 = FULL_MASK if mask is None else mask

    @jax.jit
    def step_fn(h, m, v, step, args):
        val, g = vg(h, *args)
        g = _mask_grad(g, m0)
        upd, m, v = _adam_update(g, m, v, step, lr)
        h_new = _clip(jax.tree_util.tree_map(
            lambda p, u: (p + u).astype(jnp.asarray(p).dtype), h, upd))
        return h_new, m, v, val

    zeros = jax.tree_util.tree_map(lambda v: jnp.zeros_like(v), init)
    h, m, v = init, zeros, zeros
    best_h, best_val = init, -jnp.inf
    mll0 = None
    history = []
    stall = 0
    converged = False
    k = 0
    with _obs.span("hyper.fit", steps=steps):
        for k in range(steps):
            h_new, m, v, val = step_fn(h, m, v, jnp.asarray(k), args)
            history.append(float(val))
            if mll0 is None and bool(jnp.isfinite(val)):
                mll0 = val        # the first FINITE evidence (at the init
                # on step 0; improvement stays NaN-free even if the very
                # first evaluation tripped the bound guards)
            if not bool(jnp.isfinite(val)):
                # bound guard tripped anyway — reject the step, keep going
                # from the best iterate with the optimizer state reset
                h, m, v = best_h, zeros, zeros
                stall += 1
            else:
                if float(val) > float(best_val) + tol * (1.0
                                                         + abs(float(val))):
                    best_h, best_val, stall = h, val, 0
                else:
                    stall += 1
                h = h_new
            if stall >= patience:
                converged = True
                break
    # the loop scores iterates BEFORE stepping, so the last Adam iterate is
    # still unevaluated here — score it and adopt it if it won (this is
    # also what makes fit(steps=1) perform a real step, not a no-op)
    final = fn(h, *args)
    if bool(jnp.isfinite(final)) and float(final) > float(best_val):
        best_h, best_val = h, final
    if mll0 is None:
        mll0 = best_val           # never finite during the loop: report
        # zero improvement rather than a NaN baseline
    if _obs.enabled():
        _obs.REGISTRY.inc("hyper.fit.calls")
        _obs.REGISTRY.inc("hyper.fit.stop.early" if converged
                          else "hyper.fit.stop.max_steps")
        _obs.REGISTRY.set_gauge("hyper.fit.steps", k + 1)
        _obs.REGISTRY.set_gauge("hyper.fit.improvement",
                                float(best_val) - float(mll0))
    return FitResult(
        hypers=best_h,
        mll=jnp.asarray(best_val),
        mll0=jnp.asarray(mll0),
        n_steps=k + 1,
        converged=converged,
        history=jnp.asarray(history) if history else None,
    )
