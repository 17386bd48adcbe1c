"""The plain reference: the exact gradient-GP posterior of an RBF window.

Written from the model alone, with nothing of the program imported.  With
k(x, x') = exp(-lam |x - x'|^2 / 2) and N gradient observations G (N, D) at
X (N, D) under noise ``noise`` on every gradient coordinate, the Gram block
(i, j) is k_ij (lam I - lam^2 d_ij d_ij^T) with d_ij = x_i - x_j.  Its
correction lies in span(X), so the representer weights Z (N, D) solving

    (K + noise I) vec(Z) = vec(G)

follow from an N^2-unknown linear system in R = X Z^T:

    A = lam K1 + noise I,   B_ij = k_ij (R_ij - R_jj),   L(B) = diag(B 1) - B
    Z = A^-1 (G + lam^2 L(B) X)
    R - lam^2 (X X^T) L(B)^T A^-1 = (X G^T) A^-1

and the posterior means at query points q are

    value_q = sum_j k_qj lam (q - x_j) . z_j
    grad_q  = lam sum_j k_qj z_j - lam sum_j k_qj m_qj (q - x_j),
              m_qj = lam (q - x_j) . z_j.

Every D-long product is an entry of the Gram matrix of [X; G; Xq], and the
gradients are combinations of those rows.  ``posterior`` runs it in
float64 on the host with numpy: the oracle; ``check`` compares served
answers with it, streaming over D.  ``posterior_control`` runs the same
algebra in float32 on the default device with every product at three bf16
passes (``precision='high'``, made explicit so that it means the same on
any backend): the control, the step below the stated precision that a
faster implementation would take.
"""
from __future__ import annotations

import numpy as np


def _algebra(xp, mm, M, n: int, q: int, lam, noise):
    """From the Gram matrix M = B B^T of B = [X; G; Xq] (m = 2n + q rows):
    the posterior values (q,) and the coefficients W (q, m) of the
    posterior gradients, grad = W B.  Only (n, n)-sized algebra is left:
    every D-long product is in M."""
    S, XG = M[:n, :n], M[:n, n:2 * n]
    PX, PG = M[2 * n:, :n], M[2 * n:, n:2 * n]
    sq = xp.diagonal(S)
    sqq = xp.diagonal(M[2 * n:, 2 * n:])
    eye = xp.eye(n, dtype=M.dtype)
    K1 = xp.exp(-0.5 * xp.maximum(
        lam * (sq[:, None] + sq[None, :] - 2.0 * S), 0.0))
    Ainv = xp.linalg.inv(lam * K1 + noise * eye)

    def laplacian(R):
        # B_ij = k_ij (R_ij - R_jj), L(B) = diag(B 1) - B, over a stack of R
        B = K1 * (R - xp.diagonal(R, axis1=-2, axis2=-1)[..., None, :])
        return xp.sum(B, axis=-1)[..., :, None] * eye - B

    def op(R):
        return R - lam ** 2 * mm(mm(S, xp.swapaxes(laplacian(R), -1, -2)),
                                 Ainv)

    basis = xp.eye(n * n, dtype=M.dtype).reshape(n * n, n, n)
    R = xp.linalg.solve(op(basis).reshape(n * n, n * n).T,
                        mm(XG, Ainv).reshape(n * n)).reshape(n, n)
    CX = lam ** 2 * mm(Ainv, laplacian(R))      # Z = Ainv G + CX X
    Kq = xp.exp(-0.5 * xp.maximum(
        lam * (sqq[:, None] + sq[None, :] - 2.0 * PX), 0.0))
    Mq = lam * (mm(PG, Ainv) + mm(PX, CX.T) - xp.diagonal(R)[None, :])
    value = xp.sum(Kq * Mq, axis=1)
    W = xp.concatenate([lam * (mm(Kq, CX) + Kq * Mq), lam * mm(Kq, Ainv),
                        -lam * value[:, None] * xp.eye(q, dtype=M.dtype)],
                       axis=1)
    return value, W


def posterior(X, G, Xq, lam: float, noise: float):
    """Oracle: posterior mean (value (Q,), grad (Q, D)) in float64, numpy."""
    B = np.concatenate([X, G, Xq]).astype(np.float64)
    value, W = _algebra(np, np.matmul, B @ B.T, len(X), len(Xq), float(lam),
                        float(noise))
    return value, W @ B


def check(X, G, Xq, lam: float, noise: float, value, grad,
          chunk: int = 1 << 16) -> dict:
    """The served (value, grad) against the oracle, in float64, streaming
    over D in chunks (two passes: the Gram matrix, then the errors):

      value_err_sq      ||value - value*||^2
      value_ref_sq      ||value*||^2
      grad_rel_err      ||grad - grad*|| / ||grad*||
      grad_in_span_err  the part of grad - grad* in the span of the rows
                        of X, G and Xq, over ||grad*||
      grad_out_of_span  the part outside it, over ||grad*||

    grad* lies in that span, so the last is the served gradient's own
    departure from it: the error of the final contraction alone."""
    n, q, d = len(X), len(Xq), X.shape[1]

    def blocks():
        for lo in range(0, d, chunk):
            yield lo, np.concatenate(
                [X[:, lo:lo + chunk], G[:, lo:lo + chunk],
                 Xq[:, lo:lo + chunk]]).astype(np.float64)

    M = sum(B @ B.T for _, B in blocks())
    want, W = _algebra(np, np.matmul, M, n, q, float(lam), float(noise))
    e2, Y = 0.0, 0.0
    for lo, B in blocks():
        E = np.asarray(grad[:, lo:lo + chunk], np.float64) - W @ B
        e2 += float(np.sum(E * E))
        Y = Y + B @ E.T
    w2 = float(np.sum(W * (W @ M)))
    inside2 = float(np.sum(Y * np.linalg.solve(M, Y)))
    dv = np.asarray(value, np.float64) - want
    return {"value_err_sq": float(dv @ dv), "value_ref_sq": float(want @ want),
            "grad_rel_err": (e2 / w2) ** 0.5,
            "grad_in_span_err": (max(inside2, 0.0) / w2) ** 0.5,
            "grad_out_of_span": (max(e2 - inside2, 0.0) / w2) ** 0.5}


def _mm_bf16x3(a, b):
    """a @ b from three bf16 products accumulated in float32: the hi*hi,
    hi*lo and lo*hi terms of a split of each operand (XLA's 'high')."""
    import jax.numpy as jnp

    def split(x):
        hi = x.astype(jnp.bfloat16)
        return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)

    (ah, al), (bh, bl) = split(a), split(b)
    dot = lambda u, v: jnp.matmul(u, v, preferred_element_type=jnp.float32)
    return dot(ah, bh) + dot(ah, bl) + dot(al, bh)


def posterior_control(X, G, Xq, lam: float, noise: float):
    """Control: the reference in float32 at three bf16 passes per product,
    on JAX's default device.  Returns host float32 arrays."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("high"):
        B = jnp.concatenate([jnp.asarray(a, jnp.float32) for a in (X, G, Xq)])
        value, W = _algebra(jnp, _mm_bf16x3, _mm_bf16x3(B, B.T), len(X),
                            len(Xq), jnp.float32(lam), jnp.float32(noise))
        return jax.device_get((value, _mm_bf16x3(W, B)))


def rel_err(got, want) -> float:
    """Normwise relative error ||got - want|| / ||want||, in float64."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want)
                 / max(float(np.linalg.norm(want)), 1e-300))
