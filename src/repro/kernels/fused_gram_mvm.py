"""Pallas TPU megakernel: the full Alg.-2 Gram MVM in ONE pallas_call.

W = (K1e @ V + small @ Xt) * lam  (+ noise * V), where ``small`` is the
(N, N) Hadamard/L-operator algebra of paper Alg. 2:

  dot:         small = K2e * M,                      M = (Xt*lam) @ V^T
  stationary:  small = diag(rowsum(Mt)) - Mt,        Mt = K2e * (M - diag(M)[None, :])

Two-phase grid (phase, d_block), phase-major so the whole D-stream of
phase 0 completes before phase 1 starts:

  phase 0: accumulate M into an (N, N) f32 VMEM scratch (one read of
           Xt and V blocks per step);
  epilogue (first phase-1 step): form ``small`` from K1e/K2e/M entirely
           on-chip — including the stationary l_op/lt_op fold — and
           overwrite the scratch in place;
  phase 1: stream the output update (second read of Xt/V, one write of W).

HBM traffic per MVM: 2 reads of Xt, 2 reads of V, 1 write of W, plus the
(N, N) operands — zero HBM round-trips of any (N, D) or (N, N)
intermediate, and one kernel launch instead of three (see DESIGN.md §4.3
for the byte accounting vs. the unfused sequence).

The multi-RHS variant stacks V as (R, N, D) and amortizes the two Xt
streams across all R right-hand sides: (2 + 3R) N*D-sized transfers
instead of 5R — this is what CG over R RHS (Hessian operator columns,
HMC predictive gradients) rides on.

The output index map is (0, j * phase): during phase 0 every step parks on
output block 0, so no block transition occurs and nothing is flushed to HBM
until phase 1 writes real values.

Padding contract (enforced by ops.py): N to sublane multiples with K1e/K2e
zero-padded (zero rows/cols are annihilated in every term), D to block_d
multiples with lam zero-padded (kills padded lanes exactly). ``stationary``
and ``noise`` are compile-time constants baked into the kernel body.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._mosaic import HIGHEST, block

Array = jnp.ndarray

from .gram_update import _out_dtype  # bf16 storage in -> f32 out


def _eye(n: int) -> Array:
    # 2D iota (TPU cannot lower 1D iota); used for on-chip diag extraction.
    rows = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return (rows == cols).astype(jnp.float32)


def _small_from_m(m: Array, k2: Array, stationary: bool) -> Array:
    """The O(N^2) epilogue: Alg.-2 ``small`` matrix from M and K2e."""
    if not stationary:
        return k2 * m
    n = m.shape[-1]
    eye = _eye(n)
    # diag(M)[b] = M[b, b] as a row vector, via a masked reduction (no
    # jnp.diagonal inside the kernel — gather-free, Mosaic-friendly).
    diag_m = jnp.sum(m * eye, axis=-2, keepdims=True)
    mt = k2 * (m - diag_m)
    rowsum = jnp.sum(mt, axis=-1, keepdims=True)
    return eye * rowsum - mt


def _kernel(k1_ref, k2_ref, x_ref, v_ref, lam_ref, o_ref, m_ref,
            *, stationary: bool, noise: float):
    p = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((p == 0) & (j == 0))
    def _init():
        m_ref[...] = jnp.zeros_like(m_ref)

    @pl.when(p == 0)
    def _accumulate():
        xl = x_ref[...].astype(jnp.float32) * lam_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        m_ref[...] += jax.lax.dot_general(
            xl, v, (((1,), (1,)), ((), ())), precision=HIGHEST,
            preferred_element_type=jnp.float32
        )

    @pl.when((p == 1) & (j == 0))
    def _epilogue():
        m_ref[...] = _small_from_m(m_ref[...], k2_ref[...].astype(jnp.float32),
                                   stationary)

    @pl.when(p == 1)
    def _update():
        k1 = k1_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        x = x_ref[...].astype(jnp.float32)
        acc = jnp.dot(k1, v, precision=HIGHEST,
                      preferred_element_type=jnp.float32)
        acc += jnp.dot(m_ref[...], x, precision=HIGHEST,
                       preferred_element_type=jnp.float32)
        out = acc * lam_ref[...].astype(jnp.float32)
        if noise:
            out = out + jnp.float32(noise) * v
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("stationary", "noise", "block_d",
                                             "interpret"))
def fused_gram_mvm_padded(
    K1e: Array, K2e: Array, Xt: Array, V: Array, lam: Array,
    *, stationary: bool, noise: float = 0.0, block_d: int = 1024,
    interpret: bool = False,
) -> Array:
    """Single-launch Alg.-2 MVM; pre-padded inputs only (see module doc)."""
    n, d = V.shape
    assert Xt.shape == (n, d) and K1e.shape == (n, n) and K2e.shape == (n, n)
    assert d % block_d == 0, (d, block_d)
    lam2 = jnp.broadcast_to(lam, (d,)).reshape(1, d)
    grid = (2, d // block_d)
    return pl.pallas_call(
        functools.partial(_kernel, stationary=stationary, noise=float(noise)),
        name="fused_gram_mvm",
        grid=grid,
        in_specs=[
            block((n, n), lambda p, j: (0, 0)),
            block((n, n), lambda p, j: (0, 0)),
            block((n, block_d), lambda p, j: (0, j)),
            block((n, block_d), lambda p, j: (0, j)),
            block((1, block_d), lambda p, j: (0, j)),
        ],
        out_specs=block((n, block_d), lambda p, j: (0, j * p)),
        out_shape=jax.ShapeDtypeStruct((n, d), _out_dtype(V.dtype)),
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        interpret=interpret,
    )(K1e, K2e, Xt, V, lam2)


def _kernel_multi(k1_ref, k2_ref, x_ref, v_ref, lam_ref, o_ref, m_ref,
                  *, stationary: bool, noise: float):
    p = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((p == 0) & (j == 0))
    def _init():
        m_ref[...] = jnp.zeros_like(m_ref)

    @pl.when(p == 0)
    def _accumulate():
        xl = x_ref[...].astype(jnp.float32) * lam_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        # M[r, a, b] = sum_d (Xt*lam)[a, d] V[r, b, d]
        m_ref[...] += jax.lax.dot_general(
            v, xl, (((2,), (1,)), ((), ())), precision=HIGHEST,
            preferred_element_type=jnp.float32
        ).transpose(0, 2, 1)

    @pl.when((p == 1) & (j == 0))
    def _epilogue():
        m_ref[...] = _small_from_m(m_ref[...], k2_ref[...].astype(jnp.float32),
                                   stationary)

    @pl.when(p == 1)
    def _update():
        k1 = k1_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        x = x_ref[...].astype(jnp.float32)
        # (R, N, bd): K1e @ V_r batches over r; small_r @ Xt batches over r.
        acc = jax.lax.dot_general(
            v, k1, (((1,), (1,)), ((), ())), precision=HIGHEST,
            preferred_element_type=jnp.float32
        ).transpose(0, 2, 1)
        acc += jax.lax.dot_general(
            m_ref[...], x, (((2,), (0,)), ((), ())),
            precision=HIGHEST,
            preferred_element_type=jnp.float32,
        )
        out = acc * lam_ref[...].astype(jnp.float32)
        if noise:
            out = out + jnp.float32(noise) * v
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("stationary", "noise", "block_d",
                                             "interpret"))
def fused_gram_mvm_multi_padded(
    K1e: Array, K2e: Array, Xt: Array, V: Array, lam: Array,
    *, stationary: bool, noise: float = 0.0, block_d: int = 1024,
    interpret: bool = False,
) -> Array:
    """Stacked-RHS Alg.-2 MVM: V (R, N, D) -> W (R, N, D), one launch."""
    r, n, d = V.shape
    assert Xt.shape == (n, d) and K1e.shape == (n, n) and K2e.shape == (n, n)
    assert d % block_d == 0, (d, block_d)
    lam2 = jnp.broadcast_to(lam, (d,)).reshape(1, d)
    grid = (2, d // block_d)
    return pl.pallas_call(
        functools.partial(_kernel_multi, stationary=stationary,
                          noise=float(noise)),
        name="fused_gram_mvm_multi",
        grid=grid,
        in_specs=[
            block((n, n), lambda p, j: (0, 0)),
            block((n, n), lambda p, j: (0, 0)),
            block((n, block_d), lambda p, j: (0, j)),
            block((r, n, block_d), lambda p, j: (0, 0, j)),
            block((1, block_d), lambda p, j: (0, j)),
        ],
        out_specs=block((r, n, block_d), lambda p, j: (0, 0, j * p)),
        out_shape=jax.ShapeDtypeStruct((r, n, d), _out_dtype(V.dtype)),
        scratch_shapes=[pltpu.VMEM((r, n, n), jnp.float32)],
        interpret=interpret,
    )(K1e, K2e, Xt, V, lam2)
