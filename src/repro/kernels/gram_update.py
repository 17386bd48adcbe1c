"""Pallas TPU kernel: fused Gram-MVM second sweep  W = (K1 @ (V*vs) + M @ X) * lam + noise*V.

This is the D-streaming half of paper Alg. 2 (the (N,N) Hadamard/L-operator
algebra happens outside — it is O(N^2) and irrelevant). Fusing the two small
matmuls, the Lambda scaling, the optional per-lane V pre-scale ``vs`` and the
noise ridge into one pass keeps HBM traffic at the roofline (read V, read X,
write W — no intermediates), which is what matters for a memory-bound op.

``vs`` (v_scale) lets Woodbury's  Z = K1i @ (G/lam - corr @ Xt)  run as a
single launch with vs = 1/lam and lam = 1 (see core/woodbury.py); ``noise``
folds the sigma^2 * V ridge of the Gram MVM so no caller needs an extra
O(ND) elementwise pass.

Grid over D-blocks; every block does two (N,N)x(N,block_d) MXU matmuls.
Padding contract as in skinny_gram; K1/M are (N, N) and live in VMEM whole;
vs is zero-padded like lam (padded lanes of V are zero anyway). ``noise``
is a compile-time constant baked into the kernel body.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._mosaic import HIGHEST, block

Array = jnp.ndarray


def _out_dtype(dt):
    """bf16 storage in, f32 out: the accumulator is f32 and the precision
    policy (DESIGN.md sec. 12) never rounds results back to storage."""
    return jnp.float32 if dt == jnp.bfloat16 else dt


def _kernel(k1_ref, m_ref, v_ref, x_ref, lam_ref, vs_ref, o_ref, *, noise: float):
    k1 = k1_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)
    vs = v * vs_ref[...].astype(jnp.float32)
    acc = jnp.dot(k1, vs, precision=HIGHEST,
                  preferred_element_type=jnp.float32)
    acc += jnp.dot(m, x, precision=HIGHEST,
                   preferred_element_type=jnp.float32)
    out = acc * lam_ref[...].astype(jnp.float32)
    if noise:
        out = out + jnp.float32(noise) * v
    o_ref[...] = out.astype(o_ref.dtype)


def _small_matmul_kernel(k_ref, v_ref, s_ref, o_ref):
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    out = jnp.dot(k, v, precision=HIGHEST,
                  preferred_element_type=jnp.float32)
    o_ref[...] = (out * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def small_matmul_padded(
    K: Array, V: Array, scale: Array,
    *, block_d: int = 1024, interpret: bool = False,
) -> Array:
    """W = (K @ V) * scale — the lean (N,N)x(N,D) stream with a fused
    per-lane epilogue (Kronecker-preconditioner application: scale = 1/lam).

    Exactly one read of V and one write of W; no M/X operands streamed.
    """
    n, d = V.shape
    nq = K.shape[0]
    assert K.shape == (nq, n) and d % block_d == 0, (K.shape, d, block_d)
    s2 = jnp.broadcast_to(scale, (d,)).reshape(1, d)
    grid = (d // block_d,)
    return pl.pallas_call(
        _small_matmul_kernel,
        name="small_matmul",
        grid=grid,
        in_specs=[
            block((nq, n), lambda i: (0, 0)),
            block((n, block_d), lambda i: (0, i)),
            block((1, block_d), lambda i: (0, i)),
        ],
        out_specs=block((nq, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((nq, d), _out_dtype(V.dtype)),
        interpret=interpret,
    )(K, V, s2)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret", "noise"))
def gram_update_padded(
    K1: Array, M: Array, V: Array, X: Array, lam: Array, vs: Array,
    *, block_d: int = 1024, interpret: bool = False, noise: float = 0.0,
) -> Array:
    """W = (K1 @ (V*vs) + M @ X) * lam + noise*V; V, X: (N, D) streamed.

    K1/M may be rectangular (Nq, N) — the cross-covariance query path —
    in which case W is (Nq, D) and the noise ridge requires Nq == N.
    """
    n, d = V.shape
    nq = K1.shape[0]
    assert X.shape == (n, d) and K1.shape == (nq, n) and M.shape == (nq, n)
    assert d % block_d == 0, (d, block_d)
    assert not noise or nq == n, "noise ridge needs a square update"
    lam2 = jnp.broadcast_to(lam, (d,)).reshape(1, d)
    vs2 = jnp.broadcast_to(vs, (d,)).reshape(1, d)
    grid = (d // block_d,)
    return pl.pallas_call(
        functools.partial(_kernel, noise=float(noise)),
        name="gram_update",
        grid=grid,
        in_specs=[
            block((nq, n), lambda i: (0, 0)),
            block((nq, n), lambda i: (0, 0)),
            block((n, block_d), lambda i: (0, i)),
            block((n, block_d), lambda i: (0, i)),
            block((1, block_d), lambda i: (0, i)),
            block((1, block_d), lambda i: (0, i)),
        ],
        out_specs=block((nq, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((nq, d), _out_dtype(V.dtype)),
        interpret=interpret,
    )(K1, M, V, X, lam2, vs2)
