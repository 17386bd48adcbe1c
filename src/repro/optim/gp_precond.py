"""GP-preconditioned training optimizer (the paper's method as a first-class
distributed optimizer).

Maintains a bounded sliding window of m flattened (params, grads) pairs as
ONE incrementally updated posterior state (``repro.core.state.GPGData`` —
two (m, D) matrices sharded over the WHOLE mesh like every D-vector, plus
replicated (m, m) factor strips) and produces a quasi-Newton step from the
nonparametric Hessian posterior (GP-H) or the flipped optimum inference
(GP-X). Until the window fills, it falls back to plain momentum.

Update policy per training step (all inside the jitted, sharded step —
the state functions are pure and traceable):

  * window full  -> ``gpg_evict`` (rank-1 Cholesky update, O(m^2)), then
    ``gpg_extend`` (bordered factor update + warm-started CG re-solve);
  * every ``refresh_every`` steps (and on first fill) the lengthscale is
    re-estimated from the live window and the state does one full
    ``gpg_refactor`` — Lambda changes invalidate every Gram entry, so this
    is the one place a full O(m^2 D + m^3) rebuild is correct;
  * a degenerate bordered pivot triggers the same refactor fallback
    inside ``gpg_extend`` automatically.

Why this is cheap at scale (DESIGN.md sec. 2): all O(D) work in the GP
solve is the skinny contraction X̃ᵀΛV; under jit+GSPMD with D sharded, the
per-step collective cost on top of the gradient all-reduce is a handful of
m×m psums — O(m²) bytes, independent of D and of chip count.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import GramFactors, get_kernel, infer_optimum, posterior_hessian
from repro.core.dist_state import (SGPGData, _base_specs, sgpg_direct_solve,
                                   sgpg_evict, sgpg_extend, sgpg_init,
                                   sgpg_refactor)
from repro.core.distributed import psum_fused
from repro.core.state import gpg_evict, gpg_extend, gpg_init, gpg_refactor
from repro.hyper import (LENGTHSCALE_ONLY, HyperParams, fit_scan, fit_scan_fn,
                         make_mll_strips_fn)
from repro.obs import injit as _obs_tap
from repro.utils.flat import flatten_pytree, make_flat_spec, unflatten_pytree

from .gp_directions import auto_lengthscale
from .optimizers import Optimizer

Array = jnp.ndarray


def gp_precond(
    lr: float = 1.0,
    *,
    history: int = 6,
    mode: str = "gph",            # 'gph' | 'gpx'
    kernel: str = "rbf",
    lengthscale_factor: float = 10.0,
    noise: float = 1e-6,
    fallback_lr: float = 3e-4,
    fallback_beta: float = 0.9,
    max_step_rms: float = 1e-2,
    pad_to: int = 1,
    refresh_every: int = 8,
    refresh_mode: str = "heuristic",   # 'heuristic' | 'mll'
    mll_steps: int = 8,
    mll_lr: float = 0.15,
    cg_tol: float = 1e-6,
    cg_maxiter: int | None = None,
    jitter: float = 1e-6,
    mesh=None,
) -> Optimizer:
    """GP-H/GP-X as a drop-in pytree optimizer (trust-region-clipped).

    ``refresh_mode='mll'`` replaces the median-distance lengthscale
    heuristic of the periodic refresh with ``mll_steps`` traceable Adam
    steps on the exact structured log marginal likelihood
    (``repro.hyper.fit_scan``, lengthscale only — signal/noise stay at the
    configured values), still inside the jitted sharded training step.

    ``mesh`` switches the whole update to the D-sharded state machine
    (``repro.core.dist_state``): the flat parameter/gradient vectors and
    every (m, D) history matrix are sharded over all mesh axes, the state
    mutations run as ``sgpg_*`` phases inside ONE shard_map program, and
    the per-step collective traffic is at most THREE fused psums of O(m^2)
    bytes — extend border (+ the flipped-mode observation partials),
    direction reductions, and the trust-region scalars — independent of D
    and of device count.  The CG re-solve of the single-device path is
    replaced by the strips-based exact Woodbury solve (zero psums), so
    trajectories match the unsharded optimizer to solver tolerance.
    """
    if refresh_mode not in ("heuristic", "mll"):
        raise ValueError(f"refresh_mode must be 'heuristic' or 'mll', "
                         f"got {refresh_mode!r}")
    spec = get_kernel(kernel)
    flipped = mode != "gph"       # GP-X: inputs are gradients
    if mesh is not None:
        return _gp_precond_sharded(
            spec, mesh, flipped=flipped, lr=lr, history=history, mode=mode,
            lengthscale_factor=lengthscale_factor, noise=noise,
            fallback_lr=fallback_lr, fallback_beta=fallback_beta,
            max_step_rms=max_step_rms, pad_to=pad_to,
            refresh_every=refresh_every, refresh_mode=refresh_mode,
            mll_steps=mll_steps, mll_lr=mll_lr, jitter=jitter)
    solve_kw = dict(noise=noise, tol=cg_tol,
                    maxiter=cg_maxiter if cg_maxiter else 4 * history + 16)

    def init(params):
        fspec = make_flat_spec(params, pad_to=pad_to)
        d = fspec.padded
        return {
            "step": jnp.zeros((), jnp.int32),
            "count": jnp.zeros((), jnp.int32),
            "gpg": gpg_init(spec, d, history, lam=1.0, dtype=jnp.float32),
            "m": jnp.zeros((d,), jnp.float32),
        }

    def update(grads, state, params):
        fspec = make_flat_spec(params, pad_to=pad_to)
        x_t = flatten_pytree(params, fspec)
        g_t = flatten_pytree(grads, fspec)
        a_t, b_t = (g_t, x_t) if flipped else (x_t, g_t)

        data = state["gpg"]
        step = state["step"]
        prev = data.count
        count_after = jnp.minimum(prev + 1, history)
        gp_on = count_after >= history
        refresh_now = gp_on & ((prev < history)
                               | (step % refresh_every == 0))

        data = jax.lax.cond(
            prev >= history,
            lambda d: gpg_evict(spec, d, solve=False), lambda d: d, data)

        def _rhs(d):
            # GP-X observations are displacements X - x_t: they move with
            # x_t every step, so the RHS is rebuilt and re-solved against
            # the cached factors (never refactored for it).
            if not flipped:
                return None
            mask = (jnp.arange(history) < d.count)[:, None]
            return jnp.where(mask, d.G - x_t[None], 0.0)

        def br_fill(d):       # window not full yet: append, skip the solve
            return gpg_extend(spec, d, a_t, b_t, noise=noise, jitter=jitter,
                              solve=False)

        def br_refresh(d):    # lengthscale refresh: one full refactor
            d = gpg_extend(spec, d, a_t, b_t, noise=noise, jitter=jitter,
                           solve=False)
            lam_heur = auto_lengthscale(d.G if flipped else d.X,
                                        lengthscale_factor)
            if refresh_mode == "mll":
                # traceable MLL ascent on the window (lengthscale only) —
                # exact evidence gradient, heuristic kept as the seed AND
                # the non-finite fallback (bound guards live in fit_scan).
                # The evidence sees only the TRUE parameter columns: the
                # pad_to tail is identically-zero fake dimensions that
                # would bias the per-dimension logdet/quad terms (the
                # slice bound fspec.total is static, so this jits fine)
                obs = _rhs(d) if flipped else d.G
                init = HyperParams.from_lam(lam_heur, signal=1.0,
                                            noise=max(noise, 1e-12))
                fitted, _ = fit_scan(spec, d.X[:, :fspec.total],
                                     obs[:, :fspec.total], init,
                                     steps=mll_steps, lr=mll_lr,
                                     mask=LENGTHSCALE_ONLY)
                lam_new = jnp.where(jnp.isfinite(fitted.lam), fitted.lam,
                                    lam_heur)
            else:
                lam_new = lam_heur
            return gpg_refactor(spec, d, lam_new, jitter=jitter,
                                rhs=_rhs(d), **solve_kw)

        def br_incr(d):       # steady state: bordered update + warm CG
            return gpg_extend(spec, d, a_t, b_t, jitter=jitter,
                              rhs=_rhs(d), **solve_kw)

        idx = jnp.where(~gp_on, 0, jnp.where(refresh_now, 1, 2))
        data = jax.lax.switch(idx, [br_fill, br_refresh, br_incr], data)
        # in-jit taps: trace-time no-ops when observability is off, so the
        # training-step jaxpr is unchanged (tests/test_obs.py)
        _obs_tap.tap("gp_precond.steps", 1, kind="counter")
        _obs_tap.tap("gp_precond.refresh", refresh_now, kind="counter")
        _obs_tap.tap("gp_precond.cg_iters", data.cg_iters, kind="hist")
        _obs_tap.tap("gp_precond.resnorm", data.resnorm)
        m_buf = fallback_beta * state["m"] + g_t

        def gp_branch(_):
            # window is full here, so every padded row is valid
            f = GramFactors(K1e=data.K1e, K2e=data.K2e, Xt=data.Xt,
                            lam=data.lam, noise=float(noise), c=None)
            if mode == "gph":
                H = posterior_hessian(spec, x_t, f, data.Z)
                d_ = -H.solve(g_t, jitter=1e-8)
            else:
                d_ = infer_optimum(spec, f, data.Z, x_t) - x_t
                # descent safeguard (paper Alg. 1: flip if uphill)
                d_ = jnp.where(jnp.vdot(d_, g_t) > 0, -d_, d_)
            # trust region: clip update RMS; reject non-finite directions
            d_ = jnp.where(jnp.isfinite(d_), d_, 0.0)
            rms = jnp.sqrt(jnp.mean(d_ * d_) + 1e-30)
            d_ = d_ * jnp.minimum(1.0, max_step_rms / rms)
            return lr * d_

        def fallback_branch(_):
            return -fallback_lr * m_buf

        upd = jax.lax.cond(gp_on, gp_branch, fallback_branch, operand=None)
        new_flat = x_t + upd
        new_params = jax.tree_util.tree_map(
            lambda n, o: n.astype(o.dtype), unflatten_pytree(new_flat, fspec),
            params)
        return new_params, {
            "step": step + 1, "count": count_after,
            "gpg": data, "m": m_buf,
        }

    return Optimizer(init, update, f"gp_{mode}")


def _auto_lengthscale_strip(M: Array, n: int, factor: float) -> Array:
    """``auto_lengthscale(X, factor)`` re-derived from the replicated strip
    M = X X^T — same statistic, zero collectives (the strip already paid
    the D-reduction)."""
    sq = jnp.diagonal(M)
    r = sq[:, None] + sq[None, :] - 2.0 * M
    mean_r = jnp.sum(jnp.maximum(r, 0.0)) / jnp.maximum(n * (n - 1), 1)
    return 1.0 / jnp.maximum(factor * mean_r, 1e-20)


def _gp_precond_sharded(
    spec, mesh, *, flipped, lr, history, mode, lengthscale_factor, noise,
    fallback_lr, fallback_beta, max_step_rms, pad_to, refresh_every,
    refresh_mode, mll_steps, mll_lr, jitter,
) -> Optimizer:
    """The D-sharded update: one shard_map program, <= 3 fused psums/step.

    Collective schedule (DESIGN.md sec. 14):

      1. extend border  — the O(m)-byte strip border partials, with the
         flipped-mode observation reductions (v = X~ x_t, w = G x_t,
         |x_t|^2) fused in as ``extra_partials``; everything downstream of
         this psum (evict surgery, bordered Cholesky, refactor, the exact
         Woodbury solve, the whole MLL refresh) is replicated algebra.
      2. direction      — GP-H: the fused (r, m, P^T P, P^T g) tuple of the
         factored Hessian solve (the diag term is constant over D for
         scalar Lambda, so the inner (2m, 2m) system is replicated and the
         output assembly local).  GP-X stationary: the single m-vector
         x~_b^T Lambda Z_b (the query point g = 0 kills every other
         reduction); GP-X dot: none.
      3. scalars        — the trust-region RMS (and, for GP-X, the uphill
         flip inner product) as one fused scalar psum; the flip is applied
         AFTER the psum since the RMS is flip-invariant.
    """
    names = tuple(mesh.axis_names)
    ndev = int(mesh.size)
    pad_eff = math.lcm(max(int(pad_to), 1), ndev)
    h_jitter = 1e-8               # matches the unsharded H.solve call

    def init(params):
        fspec = make_flat_spec(params, pad_to=pad_eff)
        return {
            "step": jnp.zeros((), jnp.int32),
            "count": jnp.zeros((), jnp.int32),
            "gpg": sgpg_init(spec, fspec.padded, history, lam=1.0,
                             dtype=jnp.float32),
            "m": jnp.zeros((fspec.padded,), jnp.float32),
        }

    def update(grads, state, params):
        fspec = make_flat_spec(params, pad_to=pad_eff)
        x_t = flatten_pytree(params, fspec)
        g_t = flatten_pytree(grads, fspec)
        step = state["step"]
        d_pad = fspec.padded

        def body(data, x_t, g_t, m, step):
            a_t, b_t = (g_t, x_t) if flipped else (x_t, g_t)
            prev = data.base.count
            count_after = jnp.minimum(prev + 1, history)
            gp_on = count_after >= history
            refresh_now = gp_on & ((prev < history)
                                   | (step % refresh_every == 0))

            data = jax.lax.cond(
                prev >= history,
                lambda d: sgpg_evict(spec, d, solve=False), lambda d: d, data)

            if flipped:
                # Local partials of the flipped-mode observation strips
                # (rhs = G - x_t moves with x_t, so rhs X~^T = C - 1 v^T
                # and the MLL's GG_obs shift off three cheap reductions) —
                # fused into the extend psum below, not a 4th collective.
                n_row = data.base.count
                Xt_p = data.base.Xt.at[n_row].set(a_t)
                G_p = data.base.G.at[n_row].set(b_t)
                extra = (Xt_p @ x_t, G_p @ x_t, jnp.vdot(x_t, x_t))
            else:
                extra = None

            data, extras = sgpg_extend(
                spec, data, a_t, b_t, axis_names=names, noise=noise,
                jitter=jitter, solve=False, extra_partials=extra)

            def _rhs_pair(d):
                if not flipped:
                    return None, None
                mask = (jnp.arange(history) < d.base.count)[:, None]
                rhs = jnp.where(mask, d.base.G - x_t[None, :], 0.0)
                v = extras[0]
                C_rhs = jnp.where(mask & mask.T, d.C - v[None, :], 0.0)
                return rhs, C_rhs

            def br_fill(d):       # window not full yet: append only
                return d

            def br_refresh(d):    # lengthscale refresh off the strips
                rhs, C_rhs = _rhs_pair(d)
                lam_heur = _auto_lengthscale_strip(
                    d.GG if flipped else d.S0, history, lengthscale_factor)
                if refresh_mode == "mll":
                    if flipped:
                        v, w, s2 = extras
                        C_obs = C_rhs
                        GG_obs = d.GG - w[None, :] - w[:, None] + s2
                    else:
                        C_obs, GG_obs = d.C, d.GG
                    # the evidence sees only the TRUE parameter columns via
                    # d=fspec.total — the pad tail is zero in every strip
                    fn = make_mll_strips_fn(spec, d.S0, C_obs, GG_obs,
                                            fspec.total)
                    seed = HyperParams.from_lam(lam_heur, signal=1.0,
                                                noise=max(noise, 1e-12))
                    fitted, _ = fit_scan_fn(fn, seed, steps=mll_steps,
                                            lr=mll_lr, mask=LENGTHSCALE_ONLY)
                    lam_new = jnp.where(jnp.isfinite(fitted.lam), fitted.lam,
                                        lam_heur)
                else:
                    lam_new = lam_heur
                d = sgpg_refactor(spec, d, lam_new, noise=noise,
                                  jitter=jitter, solve=False)
                return sgpg_direct_solve(spec, d, noise=noise, jitter=jitter,
                                         rhs=rhs, C_rhs=C_rhs)

            def br_incr(d):       # steady state: exact strips solve
                rhs, C_rhs = _rhs_pair(d)
                return sgpg_direct_solve(spec, d, noise=noise, jitter=jitter,
                                         rhs=rhs, C_rhs=C_rhs)

            idx = jnp.where(~gp_on, 0, jnp.where(refresh_now, 1, 2))
            data = jax.lax.switch(idx, [br_fill, br_refresh, br_incr], data)
            m_new = fallback_beta * m + g_t

            def gp_branch(_):
                b = data.base
                lam = jnp.asarray(b.lam)
                if mode == "gph":
                    # posterior_hessian + H.solve with the D-reductions
                    # hoisted into one fused psum; W and the (2m, 2m) inner
                    # solve are replicated, P stays a local (D_loc, 2m).
                    if spec.is_stationary:
                        Xtq = x_t[None, :] - b.Xt
                        r_p = jnp.sum((Xtq * lam) * Xtq, axis=-1)
                        m_p = jnp.sum((Xtq * lam) * b.Z, axis=-1)
                    else:
                        Xtq = b.Xt
                        r_p = jnp.sum((Xtq * lam) * x_t[None, :], axis=-1)
                        m_p = jnp.sum(x_t[None, :] * lam * b.Z, axis=-1)
                    Pl = jnp.concatenate([(Xtq * lam).T, (b.Z * lam).T],
                                         axis=1)
                    r, mv, PtP, Ptg = psum_fused(
                        (r_p, m_p, Pl.T @ Pl, Pl.T @ g_t), names)
                    if spec.is_stationary:
                        r = jnp.maximum(r, 0.0)
                        k2, k3 = spec.k2(r), spec.k3(r)
                        M = jnp.diag(-8.0 * k3 * mv)
                        Mh = jnp.diag(-4.0 * k2)
                        # constant over D for scalar Lambda -> replicated
                        d0 = lam * jnp.sum(-4.0 * k2 * mv)
                    else:
                        M = jnp.diag(spec.k3(r) * mv)
                        Mh = jnp.diag(spec.k2(r))
                        d0 = jnp.zeros((), x_t.dtype)
                    W = jnp.block([[M, Mh],
                                   [Mh, jnp.zeros((history, history),
                                                  M.dtype)]])
                    d0 = jnp.where(jnp.abs(d0) < h_jitter, h_jitter, d0)
                    eye = jnp.eye(2 * history, dtype=x_t.dtype)
                    inner = jnp.linalg.inv(W + h_jitter * eye) + PtP / d0
                    y = jnp.linalg.solve(inner + h_jitter * eye, Ptg / d0)
                    d_ = -(g_t / d0 - (Pl / d0) @ y)
                else:
                    # GP-X: cross_grad_matvec at the query g = 0 — the
                    # cross strips collapse to r = lam diag(S0) (free) and
                    # one m-vector psum (stationary) / nothing (dot).
                    if spec.is_stationary:
                        r_q = lam * jnp.maximum(jnp.diagonal(data.S0), 0.0)
                        mz = jax.lax.psum(
                            lam * jnp.sum(b.Xt * b.Z, axis=-1), names)
                        Mt = spec.k2e(r_q) * (-mz)
                        d_ = (spec.k1e(r_q) @ b.Z - Mt @ b.Xt) * lam
                    else:
                        r_q = jnp.zeros((history,), x_t.dtype)
                        d_ = (spec.k1e(r_q) @ b.Z) * lam
                d_f = jnp.where(jnp.isfinite(d_), d_, 0.0)
                if mode == "gph":
                    ss = jax.lax.psum(jnp.sum(d_f * d_f), names)
                else:
                    # fused: uphill-flip inner product + trust-region RMS
                    # (flip applied after the psum — RMS is flip-invariant)
                    dg, ss = psum_fused(
                        (jnp.vdot(d_, g_t), jnp.sum(d_f * d_f)), names)
                    d_f = jnp.where(dg > 0, -d_f, d_f)
                rms = jnp.sqrt(ss / d_pad + 1e-30)
                return lr * d_f * jnp.minimum(1.0, max_step_rms / rms)

            upd = jax.lax.cond(gp_on, gp_branch,
                               lambda _: -fallback_lr * m_new, operand=None)
            return data, upd, m_new

        dspec = SGPGData(base=_base_specs(names, False), S0=P(), C=P(),
                         GG=P())
        vec = P(names)
        sm = jax.shard_map(body, mesh=mesh,
                           in_specs=(dspec, vec, vec, vec, P()),
                           out_specs=(dspec, vec, vec), check_vma=False)
        data, upd, m_buf = sm(state["gpg"], x_t, g_t, state["m"], step)

        prev = state["gpg"].base.count
        count_after = jnp.minimum(prev + 1, history)
        refresh_now = (count_after >= history) & (
            (prev < history) | (step % refresh_every == 0))
        _obs_tap.tap("gp_precond.steps", 1, kind="counter")
        _obs_tap.tap("gp_precond.refresh", refresh_now, kind="counter")
        _obs_tap.tap("gp_precond.cg_iters", data.base.cg_iters, kind="hist")
        _obs_tap.tap("gp_precond.resnorm", data.base.resnorm)

        new_flat = x_t + upd
        new_params = jax.tree_util.tree_map(
            lambda n, o: n.astype(o.dtype), unflatten_pytree(new_flat, fspec),
            params)
        return new_params, {
            "step": step + 1, "count": count_after,
            "gpg": data, "m": m_buf,
        }

    return Optimizer(init, update, f"gp_{mode}")
