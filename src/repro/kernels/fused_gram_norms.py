"""Pallas TPU kernel: one-pass gram + row norms for stationary pairwise r.

Stationary kernels need  r_ab = |x_a|^2_L + |x_b|^2_L - 2 x_a^T L x_b  for
*cross* sets (queries vs. data). A naive implementation streams A and B
three times (gram, norm_A, norm_B); this kernel produces all three partials
in a single pass — the r assembly itself is an O(Na*Nb) epilogue outside.

Outputs: P (Na, Nb) f32, na (Na, 1) f32, nb (Nb, 1) f32.
Padding contract as in skinny_gram (zero-padded lam kills padding exactly).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._mosaic import HIGHEST, block

Array = jnp.ndarray


def _kernel(a_ref, b_ref, lam_ref, p_ref, na_ref, nb_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        p_ref[...] = jnp.zeros_like(p_ref)
        na_ref[...] = jnp.zeros_like(na_ref)
        nb_ref[...] = jnp.zeros_like(nb_ref)

    lam = lam_ref[...].astype(jnp.float32)
    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    al = a * lam
    p_ref[...] += jax.lax.dot_general(
        al, b, (((1,), (1,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32
    )
    na_ref[...] += jnp.sum(al * a, axis=1, keepdims=True)
    nb_ref[...] += jnp.sum((b * lam) * b, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def fused_gram_norms_padded(
    A: Array, B: Array, lam: Array, *, block_d: int = 1024, interpret: bool = False
):
    na_, d = A.shape
    nb_, _ = B.shape
    assert d % block_d == 0, (d, block_d)
    lam2 = jnp.broadcast_to(lam, (d,)).reshape(1, d)
    grid = (d // block_d,)
    return pl.pallas_call(
        _kernel,
        name="fused_gram_norms",
        grid=grid,
        in_specs=[
            block((na_, block_d), lambda i: (0, i)),
            block((nb_, block_d), lambda i: (0, i)),
            block((1, block_d), lambda i: (0, i)),
        ],
        out_specs=[
            block((na_, nb_), lambda i: (0, 0)),
            block((na_, 1), lambda i: (0, 0)),
            block((nb_, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((na_, nb_), jnp.float32),
            jax.ShapeDtypeStruct((na_, 1), jnp.float32),
            jax.ShapeDtypeStruct((nb_, 1), jnp.float32),
        ],
        interpret=interpret,
    )(A, B, lam2)
