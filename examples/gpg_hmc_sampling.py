"""GPG-HMC example (paper Sec. 5.3): sample a 100-D banana density with a
GP gradient surrogate trained on ~sqrt(D) true gradient evaluations.

Run:  PYTHONPATH=src python examples/gpg_hmc_sampling.py
"""
import math

import jax

jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp

from repro.hyper import HyperParams
from repro.sampling import banana_energy, gpg_hmc, hmc
from repro.utils import compile_cache

compile_cache.enable()

D = 100
fourth = math.ceil(D ** 0.25)
eps = 4e-3 / fourth
steps = 32 * fourth
n_samples = 300

key = jax.random.PRNGKey(0)
x0 = jax.random.normal(key, (D,))

print(f"target: 100-D banana; eps={eps:.4f}, T={steps} leapfrog steps")
res = hmc(banana_energy, x0, key, n_samples=n_samples, eps=eps, steps=steps)
print(f"HMC      accept={float(res.accept_rate):.2f} "
      f"(true-gradient calls: {n_samples * (steps + 1):,})")

hp = HyperParams.create(lengthscale2=0.4 * D, noise=1e-8)  # App. F.3 init
res2 = gpg_hmc(banana_energy, x0, jax.random.PRNGKey(1),
               n_samples=n_samples, eps=eps, steps=steps,
               hypers=hp, budget=int(math.sqrt(D)))
print(f"GPG-HMC  accept={res2.accept_rate:.2f} "
      f"(true-gradient calls: {res2.n_true_grad_calls} — "
      f"{n_samples * (steps + 1) / res2.n_true_grad_calls:,.0f}x fewer)")
print(f"surrogate hypers (shared container): {res2.surrogate.hypers}")
print("samples stay valid: the Metropolis test uses the TRUE energy;")
print("the surrogate only trades acceptance rate for gradient cost.")

m = res2.samples[:, :2].mean(axis=0)
print(f"banana-plane sample mean: ({float(m[0]):.2f}, {float(m[1]):.2f})")
