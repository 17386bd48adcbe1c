"""Backend dispatch for the O(ND) hot contractions (DESIGN.md §4).

Every pass over an (N, D) array at D ~ 1e6..1e9 is an HBM roofline event,
so the core inference engine never spells out those contractions in raw
``jnp`` — it routes them through this module, which picks between

  * ``"pallas"``  — the fused TPU kernels in ``repro.kernels`` (interpret
    mode on CPU, so the same code path is CI-testable), and
  * ``"jnp"``     — the plain-jnp oracle forms, bit-identical to the
    pre-dispatch implementation (full precision under x64; used as the
    correctness reference everywhere).

Resolution order: ``set_backend()``/``use_backend()`` > the
``REPRO_BACKEND`` env var (any value other than ``jnp``/``pallas`` raises
``ValueError``) > auto (pallas on TPU, jnp elsewhere). The jnp path
accumulates in the input dtype; the pallas path takes float32 or bfloat16
(N, D) operands and accumulates in f32 (the TPU-native contract).  A
float64 operand on the pallas path raises ``TypeError`` naming the op and
the dtype — it is never rerouted or downcast.  x64 therefore belongs to
the jnp oracles: on a TPU the auto backend is pallas, so a state built
with x64 on must pass ``dtype=jnp.float32`` (or run under
``use_backend("jnp")``).

The functions here are the complete vocabulary of O(ND) work in the solve
path: if a core module multiplies something (N, D)-shaped outside this
module, that is a bug (grep-enforced in tests/test_backend_dispatch.py).
"""
from __future__ import annotations

import contextlib
import os
from typing import Iterator

import jax
import jax.numpy as jnp

from repro import kernels as _k
from repro.kernels import ref as _kref

Array = jnp.ndarray

_VALID = ("jnp", "pallas")
_FORCED: str | None = None

_VALID_PRECISION = ("f32", "bf16")
_FORCED_PRECISION: str | None = None


def resolve_backend() -> str:
    """The backend the next hot contraction will use: 'jnp' | 'pallas'."""
    if _FORCED is not None:
        return _FORCED
    env = os.environ.get("REPRO_BACKEND", "").strip().lower()
    if env:
        if env not in _VALID:
            raise ValueError(f"REPRO_BACKEND must be one of {_VALID} or "
                             f"unset, got {env!r}")
        return env
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def set_backend(name: str | None) -> None:
    """Force the backend ('jnp' | 'pallas'); None restores auto-resolution."""
    global _FORCED
    if name is not None and name not in _VALID:
        raise ValueError(f"backend must be one of {_VALID} or None, got {name!r}")
    _FORCED = name


@contextlib.contextmanager
def use_backend(name: str) -> Iterator[None]:
    """Scoped ``set_backend`` — the test suite's parity harness."""
    prev = _FORCED
    set_backend(name)
    try:
        yield
    finally:
        set_backend(prev)


def _pallas(op: str, *operands) -> bool:
    """True when ``op`` runs on the Pallas kernels.  Those take float32 or
    bfloat16 streams only: a float64 operand raises instead of reaching a
    kernel (Mosaic has no 64-bit types) or being downcast behind the
    caller's back.  ``lam``/``v_scale`` are exempt — the kernel wrappers
    cast those per-lane scales to f32 explicitly."""
    if resolve_backend() != "pallas":
        return False
    for a in operands:
        dt = getattr(a, "dtype", None)
        if dt is not None and jnp.dtype(dt) == jnp.float64:
            raise TypeError(
                f"backend.{op}: the pallas backend takes float32/bfloat16 "
                f"operands, got {jnp.dtype(dt).name} {tuple(a.shape)}; build "
                f"the data in float32 or run under use_backend('jnp')")
    return True


# ---------------------------------------------------------------------------
# Precision policy (DESIGN.md sec. 12): bf16 STORAGE, f32 ACCUMULATION.
#
# The method's hot paths are memory-bound streams over (N, D) data, so the
# input dtype — not the math — sets the wall clock.  The policy has exactly
# three rules:
#   1. (N, D) stream operands (X, G, Z, queries) MAY be stored/streamed
#      bf16; halving their bytes halves the HBM roofline of every sweep.
#   2. every contraction accumulates in f32 (``preferred_element_type`` in
#      the Pallas kernels; an explicit upcast on the jnp fallback so the
#      oracle path never silently accumulates in bf16).
#   3. all factor outputs (grams, norms, K1e/K2e, solves Z) stay f32 —
#      results are never rounded back to storage precision.
# ``resolve_precision`` is a session knob consumed by the state/serve
# layers when casting their stream copies; the backend ops themselves are
# polymorphic (they accept whatever storage dtype the caller holds).
# ---------------------------------------------------------------------------

def resolve_precision() -> str:
    """The storage precision streams default to: 'f32' | 'bf16'."""
    if _FORCED_PRECISION is not None:
        return _FORCED_PRECISION
    env = os.environ.get("REPRO_PRECISION", "").strip().lower()
    if env in _VALID_PRECISION:
        return env
    return "f32"


def set_precision(name: str | None) -> None:
    """Force the stream storage precision; None restores auto-resolution."""
    global _FORCED_PRECISION
    if name is not None and name not in _VALID_PRECISION:
        raise ValueError(
            f"precision must be one of {_VALID_PRECISION} or None, got {name!r}")
    _FORCED_PRECISION = name


@contextlib.contextmanager
def use_precision(name: str) -> Iterator[None]:
    """Scoped ``set_precision``."""
    prev = _FORCED_PRECISION
    set_precision(name)
    try:
        yield
    finally:
        set_precision(prev)


def stream_dtype(precision: str | None = None):
    """The jnp dtype of (N, D) stream storage under ``precision``."""
    p = resolve_precision() if precision is None else precision
    if p not in _VALID_PRECISION:
        raise ValueError(f"precision must be one of {_VALID_PRECISION}, got {p!r}")
    return jnp.bfloat16 if p == "bf16" else jnp.float32


def _acc(x: Array) -> Array:
    """Accumulation-dtype view: upcast sub-f32 storage so the jnp fallback
    matches the kernels' bf16-in/f32-accum contract (rule 2 above)."""
    x = jnp.asarray(x)
    return x.astype(jnp.float32) if x.dtype in (jnp.bfloat16, jnp.float16) else x


# ---------------------------------------------------------------------------
# The O(ND) contraction vocabulary
# ---------------------------------------------------------------------------

def scaled_gram(A: Array, B: Array, lam) -> Array:
    """(N_a, N_b) matrix  A Lambda B^T — THE hot contraction of the method."""
    if _pallas("scaled_gram", A, B):
        return _k.skinny_gram(A, B, lam)
    A, B = _acc(A), _acc(B)
    return (A * lam) @ B.T


def gram_norms(A: Array, B: Array, lam):
    """(P, |A|^2_lam rowwise, |B|^2_lam rowwise) in one logical pass."""
    if _pallas("gram_norms", A, B):
        return _k.fused_gram_norms(A, B, lam)
    A, B = _acc(A), _acc(B)
    P = (A * lam) @ B.T
    na = jnp.sum((A * lam) * A, axis=-1)
    nb = jnp.sum((B * lam) * B, axis=-1)
    return P, na, nb


def fused_factor_build(A: Array, B: Array, V: Array | None, lam, *,
                       v_scale=1.0):
    """The single-sweep factor bundle (P, na, nb, C, tv) — DESIGN.md sec. 12.

    ONE pass over A/B/V emits every skinny factor of a solve or query
    microbatch: P = (A*lam) @ B^T, lam-weighted row norms na/nb,
    C = (V*v_scale) @ A^T, tv = rowdots(B, V, lam).  On the pallas backend
    this is a single ``kernels.fused_factor_build`` launch; the jnp form
    spells out the same contractions (XLA is free to fuse them, and the
    x64 oracle semantics are preserved for f32/f64 inputs).
    """
    if _pallas("fused_factor_build", A, B, V):
        return _k.fused_factor_build(A, B, V, lam, v_scale=v_scale)
    A, B = _acc(A), _acc(B)
    V = B if V is None else _acc(V)
    P = (A * lam) @ B.T
    na = jnp.sum((A * lam) * A, axis=-1)
    nb = jnp.sum((B * lam) * B, axis=-1)
    C = (V * v_scale) @ A.T
    tv = jnp.sum((B * lam) * V, axis=-1)
    return P, na, nb, C, tv


def pairwise_r(spec, A: Array, B: Array, lam, c=None) -> Array:
    """r(x_a, x_b) for all pairs; A: (Na, D), B: (Nb, D) -> (Na, Nb)."""
    if spec.is_stationary:
        g, da, db = gram_norms(A, B, lam)
        return jnp.maximum(da[:, None] + db[None, :] - 2.0 * g, 0.0)
    At = A if c is None else A - c
    Bt = B if c is None else B - c
    return scaled_gram(At, Bt, lam)


def row_dots(A: Array, B: Array, lam) -> Array:
    """sum_d A[:, d] * lam[d] * B[:, d] — one (N,) strip, pure VPU traffic.

    Bandwidth-identical on both backends (a single elementwise pass with an
    axis reduction), so there is no pallas kernel for it.
    """
    A, B = _acc(A), _acc(B)
    return jnp.sum((A * lam) * B, axis=-1)


def gram_update(K1: Array, small: Array, V: Array, X: Array, lam, *,
                v_scale=None, noise: float = 0.0) -> Array:
    """W = (K1 @ (V * v_scale) + small @ X) * lam + noise * V.

    The D-streaming half of Alg. 2 and the workhorse of every exact solve:
    Woodbury's final assembly runs it with v_scale = 1/lam, lam = 1.
    """
    if _pallas("gram_update", K1, small, V, X):
        return _k.gram_update(K1, small, V, X, lam, v_scale=v_scale,
                              noise=noise)
    V, X = _acc(V), _acc(X)
    Vs = V if v_scale is None else V * v_scale
    W = (_acc(K1) @ Vs + _acc(small) @ X) * lam
    if noise:
        W = W + noise * V
    return W


def kron_precond(K1i: Array, V: Array, lam) -> Array:
    """B^{-1} vec(V) for the free Kronecker preconditioner B = K1e x Lam.

    V may be (N, D) or stacked (R, N, D); K1i is the (N, N) inverse factor.
    """
    if V.ndim == 2 and _pallas("kron_precond", K1i, V):
        return _k.small_matmul(K1i, V, 1.0 / jnp.asarray(lam))
    return (_acc(K1i) @ _acc(V)) / lam


def fused_gram_mvm(K1e: Array, K2e: Array, Xt: Array, V: Array, lam, *,
                   stationary: bool, noise: float = 0.0) -> Array:
    """The full Alg.-2 Gram MVM as one fused op (paper Eq. 9).

    Pallas: a single two-phase pallas_call (``kernels.fused_gram_mvm``) —
    two HBM reads of Xt/V, one write of W, zero materialized intermediates.
    jnp: the einsum oracle in f32 accumulation. V (N, D) or stacked
    (R, N, D); the stacked form amortizes the Xt stream across RHS.
    """
    if _pallas("fused_gram_mvm", K1e, K2e, Xt, V):
        return _k.fused_gram_mvm(K1e, K2e, Xt, V, lam, stationary=stationary,
                                 noise=noise)
    # Native-dtype oracle (keeps x64 precision; broadcast over stacked RHS);
    # bf16 storage upcasts first so accumulation stays f32 (precision rule 2).
    return _kref.gram_mvm_oracle(_acc(K1e), _acc(K2e), _acc(Xt), _acc(V),
                                 lam, stationary=stationary, noise=noise)
