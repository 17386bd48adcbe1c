"""Seeded traffic data, made on the device: the one general generator.

Every tenant observes its own smooth function f(x) = sum(a * sin(x + b))
over R^d.  Observation ``i`` of a tenant is an input x_i ~ N(0, I) with its
gradient a * cos(x_i + b); query batch ``j`` is ``q`` points scattered
``spread`` per coordinate around an input.  The same (seed, tenant, index)
always gives the same float32 arrays, so the reference rebuilds any window
and any query batch from the seed alone, without reading the program's
state.  (The construction follows ``chip_smoke.make_problem``.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

TWO_PI = 6.283185307179586


def base_key(seed: int):
    """A threefry key from all 64 bits of ``seed`` (seeds may pass
    2**31; ``PRNGKey`` would take only 32 of them)."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    words = jnp.asarray([seed >> 32, seed & 0xFFFFFFFF], dtype=jnp.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


@functools.partial(jax.jit, static_argnums=1)
def _problem(key, d: int):
    a = jax.random.normal(jax.random.fold_in(key, 1), (d,), jnp.float32)
    b = TWO_PI * jax.random.uniform(jax.random.fold_in(key, 2), (d,),
                                    jnp.float32)
    return a, b


@jax.jit
def _observation(key, a, b, i):
    x = jax.random.normal(jax.random.fold_in(key, 1000 + i), a.shape,
                          jnp.float32)
    return x, a * jnp.cos(x + b)


@functools.partial(jax.jit, static_argnums=3)
def _queries(key, j, around, q: int, spread):
    z = jax.random.normal(jax.random.fold_in(key, 5_000_000 + j),
                          (q,) + around.shape, jnp.float32)
    return around[None, :] + spread * z


class Problem:
    """One tenant's function; its observations and query batches by index."""

    def __init__(self, seed: int, d: int, tenant: int = 0):
        self.key = jax.random.fold_in(base_key(seed), tenant)
        self.d = int(d)
        self.a, self.b = _problem(self.key, self.d)

    def observation(self, i: int):
        """(x_i, g_i), each (d,) float32 on the default device."""
        return _observation(self.key, self.a, self.b, jnp.int32(i))

    def queries(self, j: int, around, q: int, spread: float):
        """Query batch j: (q, d) float32 around the (d,) point ``around``."""
        return _queries(self.key, jnp.int32(j), around, int(q),
                        jnp.float32(spread))


def hypers(cfg: dict) -> dict:
    """Isotropic Lambda = I/d puts r = lam |x - x'|^2 at O(1) for N(0, I)
    inputs; the noise is ``noise_frac`` of the prior gradient variance lam
    (``chip_smoke.hypers``)."""
    lam = 1.0 / cfg["d"]
    return {"lam": lam, "noise": cfg["noise_frac"] * lam,
            "signal": float(cfg.get("signal", 1.0))}
