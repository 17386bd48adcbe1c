"""Pallas TPU kernel: tall-skinny scaled Gram  P = (A * lam) @ B^T.

THE hot contraction of the paper's method (DESIGN.md sec. 3): every O(D)
object only appears inside this product. A: (Na, D), B: (Nb, D) with
Na, Nb <= ~128 and D ~ 1e6..1e9 (per-device shard).

TPU adaptation: the MXU wants 128x128 tiles but Na/Nb are tiny, so the
kernel is *memory-bound by construction* (arithmetic intensity ~ Na flops
per byte of B-stream). The grid runs over D-blocks (lane-major streaming);
an (Na, Nb) f32 accumulator lives in the output VMEM block across grid
steps (revisiting pattern), so HBM sees exactly one read of A and B and a
single small write — the HBM roofline, which is the best achievable.

Padding contract (enforced by ops.py): Na, Nb multiples of 8, D a multiple
of block_d, lam zero-padded (zero lam rows exactly cancel padded columns).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._mosaic import HIGHEST, block

Array = jnp.ndarray


def _kernel(a_ref, b_ref, lam_ref, o_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = a_ref[...].astype(jnp.float32) * lam_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    o_ref[...] += jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32
    )


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def skinny_gram_padded(
    A: Array, B: Array, lam: Array, *, block_d: int = 1024, interpret: bool = False
) -> Array:
    """P[a, b] = sum_d A[a, d] * lam[d] * B[b, d]; pre-padded inputs only."""
    na, d = A.shape
    nb, _ = B.shape
    assert d % block_d == 0, (d, block_d)
    lam2 = jnp.broadcast_to(lam, (d,)).reshape(1, d)
    grid = (d // block_d,)
    return pl.pallas_call(
        _kernel,
        name="skinny_gram",
        grid=grid,
        in_specs=[
            block((na, block_d), lambda i: (0, i)),
            block((nb, block_d), lambda i: (0, i)),
            block((1, block_d), lambda i: (0, i)),
        ],
        out_specs=block((na, nb), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((na, nb), jnp.float32),
        interpret=interpret,
    )(A, B, lam2)
