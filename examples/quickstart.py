"""Quickstart: a streaming gradient-GP posterior in ~40 lines.

Condition on gradient evaluations of a 10,000-dimensional function ONE AT
A TIME (the operation the paper makes O(N^2 D) instead of O((ND)^3)) and
serve batched posterior queries off the single cached solve.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import time

import jax

jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp

from repro.core import GPGState
from repro.utils import compile_cache

compile_cache.enable()

D = 10_000                   # dimension — the axis the paper makes cheap
N = 8                        # gradient observations (low-data regime N < D)


def f(x):                    # some smooth high-dimensional function
    return jnp.sum(jnp.sin(x) * jnp.roll(x, 1)) / D


grad_f = jax.grad(f)

key = jax.random.PRNGKey(0)
X = jax.random.normal(key, (N, D))

# stream the observations in: each extend() is a bordered O(N^2 D) factor
# update + warm-started re-solve — never a from-scratch refactorization
st = GPGState("rbf", d=D, window=N, lam=1.0 / D, noise=1e-10)
t0 = time.time()
for i in range(N):
    st.extend(X[i], grad_f(X[i]))
print(f"streamed {N} gradients in R^{D} in {time.time()-t0:.2f}s — {st}")
assert st.stats["n_refactor"] == 0, "extends were incremental"

# with N << D the model is LOCAL (exactly how the paper uses it: optimizer
# steps, HMC trajectories) — query near the data, not across the void.
# One batched call serves values, gradients AND Hessian-probe products
# for all queries with ZERO re-solves (factor reuse).
Xq = X[:2] + 0.05 * jax.random.normal(jax.random.fold_in(key, 1), (2, D))
v = jax.random.normal(jax.random.fold_in(key, 2), (D,))
pb = st.posterior(Xq, probe=v)
true = jax.vmap(grad_f)(Xq)
print("pred/true cosine near data:",
      [round(float(jnp.vdot(p, t) /
                   (jnp.linalg.norm(p) * jnp.linalg.norm(t))), 3)
       for p, t in zip(pb.grad, true)])
print("Hessian probe applied:", float(jnp.linalg.norm(pb.hess_v[0])))
print("solves:", st.stats["n_solve"], "(queries added none)")
print("(never materialized the", f"{N*D}x{N*D}", "Gram matrix —",
      f"state holds {4*N*D + 3*N*N} numbers)")
