"""Streaming first-order Bayesian optimization on the incremental state.

The online loop the serving layer exists for (cf. Ament & Gomes 2022,
"Scalable First-Order Bayesian Optimization", and the paper's Sec. 4.1
optimizer workloads):

    observe gradient  ->  GPGState.extend()       (bordered O(N^2 D) update,
                                                   sliding window, NO
                                                   refactorization)
                      ->  batched candidate scoring over the compiled
                          serve step               (Q candidates along the
                                                   gradient ray, EXPECTED
                                                   IMPROVEMENT acquisition
                                                   from the posterior
                                                   mean + std,
                                                   ZERO re-solves)
                      ->  pick the next point, evaluate, repeat.

Every iteration touches the inner system exactly once (the extend's
warm-started re-solve); all Q candidate evaluations ride the cached
factors through train/serve.py's fixed-shape jitted query step — the same
executable across all rounds, because extend() never changes array shapes
(and hypers enter as dynamic solver arrays, so even a refit would not
recompile).

Acquisition: with ``return_std`` on (the default) candidates are ranked by
EI against the incumbent's *model* value — the gradient-only posterior
mean is defined up to an additive constant, so the incumbent is scored in
the SAME batch and the constant cancels.  ``--mean-only`` falls back to
pure posterior-mean exploitation (the pre-uncertainty behavior).

``--chaos`` runs the same loop under a seeded ``ChaosInjector``:
observation payloads are randomly NaN-corrupted (the admission guardrail
rejects them and the loop retries with the clean gradient) and the live
Cholesky is randomly poisoned (the post-extend watchdog heals it on the
jitter ladder).  The loop must still converge — and with ``REPRO_OBS=on``
the log passes ``tools/check_telemetry.py --expect-recovery``.

Run:   PYTHONPATH=src python examples/streaming_bo.py [--smoke] [--mean-only]
                                                      [--chaos]
"""
import sys
import time

import jax

jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
from jax.scipy.special import erf

from repro.core import GPGState
from repro.train.serve import build_gp_serve_step
from repro.utils import compile_cache

compile_cache.enable()

SMOKE = "--smoke" in sys.argv
CHAOS = "--chaos" in sys.argv
USE_STD = "--mean-only" not in sys.argv   # EI needs return_std on the step
D = 64 if SMOKE else 500          # search-space dimension
ROUNDS = 6 if SMOKE else 30       # BO iterations
Q = 64                            # candidates scored per round (batched)
WINDOW = 8                        # bounded posterior window (evict oldest)


def f(x):                         # ill-conditioned quadratic + ripple
    w = 1.0 + 9.0 * jnp.arange(D) / D
    return 0.5 * jnp.sum(w * x * x) + 0.1 * jnp.sum(jnp.cos(3.0 * x)) / D


fg = jax.jit(jax.value_and_grad(f))


def _phi(z):                      # standard normal pdf
    return jnp.exp(-0.5 * z * z) / jnp.sqrt(2.0 * jnp.pi)


def _Phi(z):                      # standard normal cdf
    return 0.5 * (1.0 + erf(z / jnp.sqrt(2.0)))


def expected_improvement(mu, sigma, mu_best):
    """EI for MINIMIZATION: E[max(mu_best - f, 0)] under N(mu, sigma^2)."""
    sigma = jnp.maximum(sigma, 1e-12)
    imp = mu_best - mu
    z = imp / sigma
    return imp * _Phi(z) + sigma * _phi(z)


key = jax.random.PRNGKey(0)
x0 = 2.0 * jax.random.normal(key, (D,))
st = GPGState("rbf", d=D, window=WINDOW, lam=1.0 / D, noise=1e-9)
serve = build_gp_serve_step(st, microbatch=Q + 1, return_std=USE_STD)

if CHAOS:
    from repro.resilience import ChaosInjector, guardrails
    from repro.resilience.errors import NonFiniteObservationError

    chaos = ChaosInjector(seed=7, rates={"nan_payload": 0.3,
                                         "degenerate_factor": 0.2})


def observe(x, g):
    """Stream one gradient observation, optionally under chaos."""
    if CHAOS and chaos.draw("nan_payload"):
        try:                          # the admission guardrail rejects it
            st.extend(x, chaos.corrupt_payload(g))
        except NonFiniteObservationError:
            guardrails.record_recovery("nan_payload")
    if CHAOS and st.n >= 1 and chaos.poison_factor(st):
        pass                          # the extend below heals it in-line
    st.extend(x, g)

best_x = x0
best_f, best_g = fg(x0)
best_f = float(best_f)
f0 = best_f
alpha = 0.05                      # adaptive trust-region step scale
incumbent_fresh = True            # extend the incumbent only when it moved
t0 = time.time()
for it in range(ROUNDS):
    # 1. stream the gradient at the incumbent into the posterior state —
    #    but only a NEW incumbent: re-appending an unchanged best_x every
    #    stalled round would fill the sliding window with duplicates and
    #    degenerate the bordered factorization
    if incumbent_fresh:
        observe(best_x, best_g)
        incumbent_fresh = False

    # 2. candidates along the (jittered) gradient ray at Q step sizes,
    #    plus the incumbent itself (the EI reference — the posterior mean
    #    from gradients is only defined up to a constant, which cancels
    #    inside one batch); ONE batched query scores them all
    key, k1 = jax.random.split(key)
    steps = alpha * jnp.logspace(-2.0, 1.0, Q)[:, None]
    jitterd = (0.05 * jnp.linalg.norm(best_g) / jnp.sqrt(D)
               * jax.random.normal(k1, (Q, D)))
    cands = best_x[None] - steps * (best_g[None] + jitterd)
    batch = jnp.concatenate([cands, best_x[None]], axis=0)
    pb = serve.query(batch)
    if pb.std is not None:        # EI acquisition (falls back to mean)
        mu, mu_best = pb.value[:Q], pb.value[Q]
        ei = expected_improvement(mu, pb.std[:Q], mu_best)
        pick = cands[int(jnp.argmax(ei))]
    else:
        pick = cands[int(jnp.argmin(pb.value[:Q]))]

    # 3. the ONLY true function/gradient evaluation of the round
    fx, gx = fg(pick)
    if float(fx) < best_f:
        best_x, best_f, best_g = pick, float(fx), gx
        incumbent_fresh = True
        alpha = min(alpha * 1.5, 10.0)         # grow the trust region
    else:
        observe(pick, gx)                      # failed pick still informs
        alpha = max(alpha * 0.5, 1e-5)
    if it % 5 == 0 or SMOKE:
        s = st.stats
        print(f"round {it:3d}  f(pick)={float(fx):+.4f}  best={best_f:+.4f}"
              f"  n={s['n']}  solves={s['n_solve']}"
              f"  refactors={s['n_refactor']}  cg_iters={s['cg_iters']}")

acq = "EI" if USE_STD else "mean"
print(f"\n{ROUNDS} rounds ({acq} acquisition), {Q} candidates/round in "
      f"{time.time()-t0:.1f}s: f {f0:+.3f} -> {best_f:+.3f}  ({st})")
assert best_f < f0, "BO loop failed to improve on the start point"
