"""Pallas TPU megakernel: single-sweep factor build (DESIGN.md sec. 12).

Every structured factor of the method is a reduction of the same (N, D)
data stream, yet the pre-fusion solve path made three-to-four separate
passes over X (and G) per solve: ``scaled_gram`` for the pairwise-r gram,
``fused_gram_norms`` for the stationary row norms, a Woodbury
``K1i @ G`` D-stream plus its ``@ Xt^T`` contraction, and the query-side
cross-gram. This kernel emits ALL of those skinny factors in one launch —
one read of each operand over the D grid, f32 VMEM accumulators:

  P  (Na, Nb) = (A * lam) @ B^T     the scaled (cross-)gram
  na (Na, 1)  = sum_d A*lam*A       row norms of A   (stationary r assembly)
  nb (Nb, 1)  = sum_d B*lam*B       row norms of B
  C  (Nb, Na) = (V * vs) @ A^T      the right-hand contraction
  tv (Nb, 1)  = sum_d B*lam*V       row dots of B against V

``V`` must share B's row count. The two hot instantiations:

  solve (Woodbury/poly2):  A = B = Xt, V = G,  vs = 1
      P = S = (Xt L) Xt^T;  C = G Xt^T, so T0 = (K1i G) Xt^T = K1i @ C
      by associativity — the Woodbury right-hand side needs NO extra
      stream of G and never materializes the (N, D) intermediate K1i G.
  query (posterior mean):  A = Xq, B = Xt, V = Z, vs = lam
      P/na/nb assemble pairwise r;  C^T = (Xq L) Z^T is the cross
      contraction of BOTH the value and grad posterior means;  tv is the
      stationary row-dot correction.

Inputs may be bf16 (storage precision): every accumulation runs in f32
via ``preferred_element_type`` and all five outputs are f32.

Grid runs over D-blocks only; the five outputs use constant index maps so
their f32 accumulators stay resident in VMEM across the whole sweep
(revisiting pattern) while the pallas pipeline double-buffers the streamed
A/B/V blocks. Padding contract as in skinny_gram: rows to sublane
multiples with zero rows (annihilated in every product), D to block_d
multiples with lam/vs zero-padded (kills padded lanes exactly).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._mosaic import HIGHEST, block

Array = jnp.ndarray


def _kernel(a_ref, b_ref, v_ref, lam_ref, vs_ref,
            p_ref, na_ref, nb_ref, c_ref, tv_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        p_ref[...] = jnp.zeros_like(p_ref)
        na_ref[...] = jnp.zeros_like(na_ref)
        nb_ref[...] = jnp.zeros_like(nb_ref)
        c_ref[...] = jnp.zeros_like(c_ref)
        tv_ref[...] = jnp.zeros_like(tv_ref)

    lam = lam_ref[...].astype(jnp.float32)
    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    al = a * lam
    bl = b * lam
    p_ref[...] += jax.lax.dot_general(
        al, b, (((1,), (1,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32
    )
    na_ref[...] += jnp.sum(al * a, axis=1, keepdims=True)
    nb_ref[...] += jnp.sum(bl * b, axis=1, keepdims=True)
    c_ref[...] += jax.lax.dot_general(
        v * vs_ref[...].astype(jnp.float32), a,
        (((1,), (1,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32
    )
    tv_ref[...] += jnp.sum(bl * v, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def fused_factor_build_padded(
    A: Array, B: Array, V: Array, lam: Array, vs: Array,
    *, block_d: int = 1024, interpret: bool = False,
):
    """(P, na, nb, C, tv) in ONE launch; pre-padded inputs only."""
    na_, d = A.shape
    nb_, _ = B.shape
    assert B.shape == (nb_, d) and V.shape == (nb_, d), (A.shape, B.shape,
                                                        V.shape)
    assert d % block_d == 0, (d, block_d)
    lam2 = jnp.broadcast_to(lam, (d,)).reshape(1, d)
    vs2 = jnp.broadcast_to(vs, (d,)).reshape(1, d)
    grid = (d // block_d,)
    return pl.pallas_call(
        _kernel,
        name="fused_factor_build",
        grid=grid,
        in_specs=[
            block((na_, block_d), lambda i: (0, i)),
            block((nb_, block_d), lambda i: (0, i)),
            block((nb_, block_d), lambda i: (0, i)),
            block((1, block_d), lambda i: (0, i)),
            block((1, block_d), lambda i: (0, i)),
        ],
        out_specs=[
            block((na_, nb_), lambda i: (0, 0)),
            block((na_, 1), lambda i: (0, 0)),
            block((nb_, 1), lambda i: (0, 0)),
            block((nb_, na_), lambda i: (0, 0)),
            block((nb_, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((na_, nb_), jnp.float32),
            jax.ShapeDtypeStruct((na_, 1), jnp.float32),
            jax.ShapeDtypeStruct((nb_, 1), jnp.float32),
            jax.ShapeDtypeStruct((nb_, na_), jnp.float32),
            jax.ShapeDtypeStruct((nb_, 1), jnp.float32),
        ],
        interpret=interpret,
    )(A, B, V, lam2, vs2)
