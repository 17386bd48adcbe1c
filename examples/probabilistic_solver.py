"""Probabilistic linear-algebra example (paper Sec. 4.2 / Fig. 2):
solve Ax = b with the GP-X solution-based solver vs conjugate gradients.

Run:  PYTHONPATH=src python examples/probabilistic_solver.py
"""
import jax

jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
import numpy as np

from repro.linalg import (cg_solve, hessian_probabilistic_solver,
                          make_test_matrix, solution_probabilistic_solver)
from repro.utils import compile_cache

compile_cache.enable()

D = 100
A = make_test_matrix(D)                    # App. F.1 spectrum, kappa = 200
rng = np.random.RandomState(0)
x0 = jnp.asarray(rng.randn(D) * 5.0)
xstar = jnp.asarray(rng.randn(D) - 2.0)
b = A @ xstar

print(f"solving a {D}x{D} system, kappa={100/0.5:.0f}")
for name, fn in [("conjugate gradients  ", cg_solve),
                 ("GP-X solution solver ", solution_probabilistic_solver),
                 ("GP-H Hessian solver  ", hessian_probabilistic_solver)]:
    tr = fn(A, b, x0, tol=1e-5, max_iters=100)
    bar = "#" * max(1, int(40 * min(tr.iters, 100) / 100))
    print(f"  {name} iters={tr.iters:3d} relres={tr.relres[-1]:.1e} {bar}")

print("\nGP-X matches CG (paper Fig. 2); GP-H's fixed c=0 'compromises")
print("the performance' — reproduced, not a bug.")
