"""D-sharded incremental posterior state: the mesh-parallel state machine.

``core/distributed.py`` proved the communication story for ONE-SHOT solves:
every O(D) object of the paper's decomposition only ever appears inside
tall-skinny contractions that reduce to (N, N), so sharding the D axis
over the whole mesh costs O(N^2) collective bytes per solve — independent
of D and of device count.  This module extends that scheme to the ENTIRE
incremental pipeline (extend / evict / resolve / refit / query) with a
stronger invariant: **at most ONE fused psum per phase**, and several
phases with none at all.

The trick is what the state carries.  Alongside the local (cap, D_loc)
shards of X/G/Xt/Z, :class:`SGPGData` maintains three replicated UNSCALED
(cap, cap) strips

    S0 = X~ X~^T        (lambda-free!)
    C  = G  X~^T
    GG = G  G^T

which are exactly the reductions every downstream phase needs:

  extend    — the border of all three strips against the new (x, g) row is
              four cap-vectors of local partials, psummed ONCE as a fused
              tuple (O(N) bytes!).  The kernel border columns, the bordered
              Cholesky append and the degraded-pivot O(N^3) fallback are
              replicated (N, N) algebra — no further collective.
  solve     — the exact Woodbury solve re-associates its two historical
              psums away: S = lam * S0 and T0 = K1i @ (rhs X~^T) = K1i @ C
              come straight off the strips, the (N^2, N^2) inner system is
              replicated, and the output assembly is one purely local
              ``backend.gram_update`` launch.  ZERO psums (the per-extend
              warm CG of the single-device path would cost one psum PER
              ITERATION — the direct solve is the communication-optimal
              choice here).
  evict     — row surgery on local shards + replicated strips.  ZERO psums.
  refactor  — a lengthscale change re-derives r from S0 (stationary
              r = lam*(d0_a + d0_b - 2 S0); dot r = lam*S0).  ZERO psums.
  resolve   — a NEW right-hand side needs C_rhs = psum(rhs_loc X~_loc^T):
              ONE psum of one (N, N) matrix.
  refit     — the entire MLL hyper-fit runs off the maintained strips
              (``hyper.mll.mll_from_strips``), replicated: ZERO psums for
              any number of fit steps.
  query     — one fused psum of the 5-tuple of cross strips per microbatch
              (``core.query._mean_strips``), then the replicated value and
              the local (Q, D_loc) grad assembly.  A ring (ppermute)
              variant overlaps the reduction of chunk i with the local
              compute of chunk i+1 (Megatron-style pipelining).

Scalar Lambda only: the unscaled-S0 maintenance is what buys the zero-psum
refactor/refit, and it requires lam to fold out of the strips (the paper's
own experiments are isotropic; ``core/woodbury.py`` has the same exact-path
restriction).

All ``sgpg_*`` functions are pure and written for use INSIDE shard_map
(local shards in, explicit psums over ``axis_names``).  The host-facing
:class:`ShardedGPGState` mirrors the ``GPGState`` API: it builds the mesh
program once per shape (``obs/compile_watch.wrap`` — compile-stable across
extend/evict/refit because count/noise are traced arguments), pads D to a
multiple of the device count (zero columns are exactly inert in every
strip), and serves posterior mean value/grad batches.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.scipy.linalg import cho_solve
from jax.sharding import PartitionSpec as P

from repro.obs import compile_watch as _cw
from repro.obs import trace as _obs

from . import backend
from .distributed import psum_fused, ring_psum
from .gram import GramFactors
from .kernels import KernelSpec, get_kernel
from .mvm import l_op, lt_op
from .query import _mean_assemble, _mean_strips
from .state import (GPGData, _chol_append, _row_mask, gpg_evict as
                    _base_evict, gpg_init)

Array = jnp.ndarray


class SGPGData(NamedTuple):
    """Sharded incremental state: local (cap, D_loc) shards + replicated
    (cap, cap) strips.

    base: a ``GPGData`` whose X/G/Xt/Z are LOCAL shards (inside shard_map)
          or D-sharded global arrays (outside); K1e/K2e/L and the counters
          are replicated.  ``base.c``, when present, is sharded like X.
    S0/C/GG: the replicated UNSCALED strips (see module docstring); rows
          and columns >= count are zero.
    """

    base: GPGData
    S0: Array
    C: Array
    GG: Array

    @property
    def capacity(self) -> int:
        return self.base.capacity

    @property
    def count(self) -> Array:
        return self.base.count


def _f32_matmuls(fn):
    """Trace ``fn`` with its float32 matmuls at full float32 precision.

    A TPU contracts float32 operands in one bf16 pass unless told
    otherwise.  The sharded state solves directly: no CG step measures and
    corrects the residual, as the single-device state's does, so a bf16
    pass in the replicated (N, N) algebra reaches Z and the posterior
    (value 3.4e-3 off a float64 oracle on four v5e chips at d = 2**25).
    The D-long contractions are Pallas kernels with their own precision.
    """
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)

    return wrapped


def sgpg_init(spec: KernelSpec, d: int, capacity: int, *, lam=1.0,
              c: Optional[Array] = None, dtype=None) -> SGPGData:
    """Empty sharded state (``d`` is the PADDED global dimension)."""
    base = gpg_init(spec, d, capacity, lam=lam, c=c, dtype=dtype)
    if jnp.asarray(base.lam).ndim != 0:
        raise ValueError("the D-sharded state requires scalar (isotropic) "
                         "Lambda — the unscaled-strip maintenance that buys "
                         "the zero-psum refactor/refit folds lam out of S0")
    znn = jnp.zeros((capacity, capacity), base.X.dtype)
    return SGPGData(base=base, S0=znn, C=znn, GG=znn)


# ---------------------------------------------------------------------------
# Internals (replicated algebra; no collectives)
# ---------------------------------------------------------------------------


def _full_chol_t(base: GPGData, noise, jitter: float) -> Array:
    """``state._full_chol`` with a TRACED noise scalar (no recompile when
    the host refit changes the noise)."""
    mask = _row_mask(base)
    shift = jnp.asarray(noise) / jnp.asarray(base.lam) + jitter
    K1n = base.K1e + jnp.diag(jnp.where(mask, shift, 1.0))
    L = jnp.linalg.cholesky(K1n)
    bad = ~jnp.all(jnp.isfinite(L))
    tr = jnp.trace(K1n) / jnp.maximum(base.count, 1)
    K1r = K1n + jnp.diag(jnp.where(mask, 1e-6 * tr, 0.0))
    return jnp.where(bad, jnp.linalg.cholesky(K1r), L)


def _r_from_strips(spec: KernelSpec, S0: Array, lam) -> Array:
    """Pairwise r of the whole window from the UNSCALED S0 strip."""
    if spec.is_stationary:
        d0 = jnp.diagonal(S0)
        return lam * jnp.maximum(d0[:, None] + d0[None, :] - 2.0 * S0, 0.0)
    return lam * S0


@_f32_matmuls
def sgpg_direct_solve(
    spec: KernelSpec,
    data: SGPGData,
    *,
    noise=0.0,
    jitter: float = 1e-10,
    rhs: Optional[Array] = None,
    C_rhs: Optional[Array] = None,
) -> SGPGData:
    """Exact Woodbury solve off the maintained strips — ZERO collectives.

    The two (N, N) psums of ``distributed.local_woodbury_solve`` are
    re-associated away: S = lam * S0, and the inner right-hand side
    T0 = (K1i rhs) X~^T = K1i @ C_rhs with C_rhs = rhs X~^T = the
    maintained C when rhs is the stored G (default).  The padded algebra
    is made exact by MASKING the inner operator (not just its inputs):
    with ``inner(Q) = where(mm, F(where(mm, Q, 0)), Q)`` the (N^2, N^2)
    system is block-diagonal [[A_vv, 0], [0, I]], so the embedded
    valid-block solution IS the unpadded solution.  (The naive unmasked
    padded system is NOT equivalent: ``lt_op`` writes M[a, a] into padded
    columns, which the unmasked A would constrain against garbage.)

    ``rhs``: local (cap, D_loc) right-hand side, default ``base.G``; rows
    >= count must be zero.  ``C_rhs``: its replicated (cap, cap) strip
    rhs @ X~^T — REQUIRED whenever rhs is not the stored G (the resolve
    phase psums it; extend fuses it into the border psum).
    """
    b = data.base
    cap = b.capacity
    dtype = b.K1e.dtype
    lam = jnp.asarray(b.lam)
    mask = _row_mask(b)
    mm = mask[:, None] & mask[None, :]

    # L factorizes K1n = K1e + (noise/lam + jitter) I with an identity
    # tail, so K1i is block-diagonal: exact inverse on the valid block.
    K1i = cho_solve((b.L, True), jnp.eye(cap, dtype=dtype))
    S = lam * jnp.where(mm, data.S0, 0.0)
    K2m = jnp.where(mm, b.K2e, 1.0)  # padded entries divide by 1, not 0

    if rhs is None:
        rhs = b.G
    if C_rhs is None:
        C_rhs = data.C
    T0 = K1i @ jnp.where(mm, C_rhs, 0.0)
    T = jnp.where(mm, lt_op(T0) if spec.is_stationary else T0, 0.0)

    if spec.is_stationary:
        def F(Q):
            return -Q.T / K2m + lt_op(K1i @ l_op(Q) @ S)
    else:
        def F(Q):
            return Q.T / K2m + K1i @ Q @ S

    def inner(Q):
        return jnp.where(mm, F(jnp.where(mm, Q, 0.0)), Q)

    eye = jnp.eye(cap * cap, dtype=dtype).reshape(cap * cap, cap, cap)
    A = jax.vmap(inner)(eye).reshape(cap * cap, cap * cap).T
    q = jnp.linalg.solve(A + jitter * jnp.eye(cap * cap, dtype=dtype),
                         T.reshape(-1))
    Q = q.reshape(cap, cap)

    QL = l_op(Q) if spec.is_stationary else Q
    Z = backend.gram_update(K1i, -(K1i @ QL), rhs, b.Xt, 1.0,
                            v_scale=1.0 / lam)
    Z = jnp.where(mask[:, None] & jnp.isfinite(Z), Z, 0.0)
    b = b._replace(Z=Z, n_solve=b.n_solve + 1,
                   cg_iters=jnp.zeros((), jnp.int32),
                   resnorm=jnp.zeros((), b.resnorm.dtype))
    return data._replace(base=b)


# ---------------------------------------------------------------------------
# The phase functions (called INSIDE shard_map)
# ---------------------------------------------------------------------------


@_f32_matmuls
def sgpg_extend(
    spec: KernelSpec,
    data: SGPGData,
    x: Array,
    g: Array,
    *,
    axis_names,
    noise=0.0,
    jitter: float = 1e-10,
    deg_thresh: float = 1e-8,
    solve: bool = True,
    rhs: Optional[Array] = None,
    extra_partials=None,
):
    """Append one observation: ONE fused psum of O(N)-byte border partials.

    ``x``/``g`` (and the optional ``rhs`` override) are LOCAL (D_loc,) /
    (cap, D_loc) shards.  The psum carries the four border cap-vectors of
    the strips (s0_col, c_col, c_row, gg_col), the rhs strip when ``rhs``
    is given, and any caller ``extra_partials`` pytree (the optimizer step
    fuses its direction reductions here) — still one collective.

    Returns ``(data, extras)`` where ``extras`` is the psummed
    ``extra_partials`` (None if not given).
    """
    b = data.base
    cap = b.capacity
    n = b.count
    x = jnp.asarray(x, b.X.dtype)
    g = jnp.asarray(g, b.X.dtype)
    xt_new = x if (spec.is_stationary or b.c is None) else x - b.c

    Xt_p = b.Xt.at[n].set(xt_new)
    G_p = b.G.at[n].set(g)
    # Local border partials: [x~_new; g] against the appended strips.
    pair = jnp.stack([xt_new, g])
    S2 = backend.scaled_gram(pair, Xt_p, 1.0)   # rows: x~_new.x~_b, g.x~_b
    G2 = backend.scaled_gram(pair, G_p, 1.0)    # rows: x~_new.g_a, g.g_a
    parts = (S2, G2)
    if rhs is not None:
        parts = parts + (backend.scaled_gram(rhs, Xt_p, 1.0),)
    if extra_partials is not None:
        parts = parts + (extra_partials,)
    parts = psum_fused(parts, axis_names)       # the ONE extend collective
    S2, G2 = parts[0], parts[1]
    C_rhs = parts[2] if rhs is not None else None
    extras = parts[-1] if extra_partials is not None else None

    s0_col, c_col = S2[0], S2[1]                # S0[:, n] and C[n, :]
    c_row, gg_col = G2[0], G2[1]                # C[:, n] and GG[:, n]
    S0 = data.S0.at[n, :].set(s0_col).at[:, n].set(s0_col)
    C = data.C.at[n, :].set(c_col).at[:, n].set(c_row)
    GG = data.GG.at[n, :].set(gg_col).at[:, n].set(gg_col)

    # Border kernel columns from the replicated strip border (state._border
    # math, minus its D-streaming sweep — the strips already paid it).
    lam = jnp.asarray(b.lam)
    mask_pre = jnp.arange(cap) < n
    if spec.is_stationary:
        d0 = jnp.diagonal(S0)
        r_col = lam * jnp.maximum(d0 + s0_col[n] - 2.0 * s0_col, 0.0)
        r_self = jnp.zeros((), x.dtype)
    else:
        r_col = lam * s0_col
        r_self = lam * s0_col[n]
    k1_col = jnp.where(mask_pre, spec.k1e(r_col), 0.0)
    k2_col = jnp.where(mask_pre, spec.k2e(r_col), 0.0)
    k1_diag = spec.k1e(r_self)
    shift = jnp.asarray(noise) / lam + jitter

    K1e = b.K1e.at[n, :].set(k1_col).at[:, n].set(k1_col)
    K1e = K1e.at[n, n].set(k1_diag)
    K2e = b.K2e.at[n, :].set(k2_col).at[:, n].set(k2_col)
    K2e = K2e.at[n, n].set(spec.k2e(r_self))
    b = b._replace(X=b.X.at[n].set(x), G=G_p, Xt=Xt_p, K1e=K1e, K2e=K2e,
                   count=n + 1)

    L_new, degraded, _ = _chol_append(b.L, k1_col, k1_diag + shift, n,
                                      deg_thresh)
    b = jax.lax.cond(
        degraded,
        lambda d: d._replace(L=_full_chol_t(d, noise, jitter),
                             n_refactor=d.n_refactor + 1),
        lambda d: d._replace(L=L_new),
        b,
    )
    data = data._replace(base=b, S0=S0, C=C, GG=GG)
    if solve:
        data = sgpg_direct_solve(spec, data, noise=noise, jitter=jitter,
                                 rhs=rhs, C_rhs=C_rhs)
    return data, extras


@_f32_matmuls
def sgpg_evict(
    spec: KernelSpec,
    data: SGPGData,
    *,
    noise=0.0,
    jitter: float = 1e-10,
    solve: bool = True,
) -> SGPGData:
    """Drop the oldest observation: pure row surgery, ZERO collectives."""
    n = data.base.count
    cap = data.base.capacity
    keep = jnp.arange(cap) < jnp.maximum(n - 1, 0)
    kmm = keep[:, None] & keep[None, :]

    def upleft(A):
        return jnp.where(kmm, jnp.roll(jnp.roll(A, -1, 0), -1, 1), 0.0)

    base = _base_evict(spec, data.base, solve=False)
    data = data._replace(base=base, S0=upleft(data.S0), C=upleft(data.C),
                         GG=upleft(data.GG))
    if solve:
        data = sgpg_direct_solve(spec, data, noise=noise, jitter=jitter)
    return data


@_f32_matmuls
def sgpg_refactor(
    spec: KernelSpec,
    data: SGPGData,
    lam=None,
    *,
    noise=0.0,
    jitter: float = 1e-10,
    solve: bool = True,
) -> SGPGData:
    """Lengthscale refresh: r re-derived from the UNSCALED S0 strip.

    ZERO collectives — this is the payoff of storing S0 lambda-free: a
    refit's refactorization is replicated (N, N) algebra, where the
    single-device path re-streams the whole (N, D) window.
    """
    b = data.base
    if lam is not None:
        b = b._replace(lam=jnp.asarray(lam, b.X.dtype))
    mask = _row_mask(b)
    mm = mask[:, None] & mask[None, :]
    r = _r_from_strips(spec, data.S0, jnp.asarray(b.lam))
    b = b._replace(K1e=jnp.where(mm, spec.k1e(r), 0.0),
                   K2e=jnp.where(mm, spec.k2e(r), 0.0),
                   n_refactor=b.n_refactor + 1)
    b = b._replace(L=_full_chol_t(b, noise, jitter))
    data = data._replace(base=b)
    if solve:
        data = sgpg_direct_solve(spec, data, noise=noise, jitter=jitter)
    return data


@_f32_matmuls
def sgpg_resolve(
    spec: KernelSpec,
    data: SGPGData,
    rhs: Array,
    *,
    axis_names,
    noise=0.0,
    jitter: float = 1e-10,
) -> SGPGData:
    """Solve against a NEW local rhs shard: ONE psum of its (N, N) strip."""
    b = data.base
    mask = _row_mask(b)
    rhs = jnp.where(mask[:, None], jnp.asarray(rhs, b.X.dtype), 0.0)
    C_rhs = jax.lax.psum(backend.scaled_gram(rhs, b.Xt, 1.0), axis_names)
    return sgpg_direct_solve(spec, data, noise=noise, jitter=jitter,
                             rhs=rhs, C_rhs=C_rhs)


@_f32_matmuls
def sgpg_rebuild(
    spec: KernelSpec,
    data: SGPGData,
    *,
    axis_names,
    noise=0.0,
    jitter: float = 1e-10,
    solve: bool = True,
) -> SGPGData:
    """Bulk (re)build of all three strips from the local shards: ONE fused
    psum (bulk conditioning / ``from_data``), then the zero-psum refactor
    path rebuilds factors, Cholesky and the solve."""
    b = data.base
    mask = _row_mask(b)
    Xt = jnp.where(mask[:, None], b.Xt, 0.0)
    G = jnp.where(mask[:, None], b.G, 0.0)
    P_, _, _, C, _ = backend.fused_factor_build(Xt, Xt, G, 1.0)
    GGp = backend.scaled_gram(G, G, 1.0)
    S0, C, GG = psum_fused((P_, C, GGp), axis_names)
    data = data._replace(base=b._replace(Xt=Xt, G=G), S0=S0, C=C, GG=GG)
    return sgpg_refactor(spec, data, noise=noise, jitter=jitter, solve=solve)


@_f32_matmuls
def sgpg_posterior_mean(
    spec: KernelSpec,
    data: SGPGData,
    Xq: Array,
    *,
    axis_names,
):
    """Posterior mean value/grad at local (Q, D_loc) query rows.

    ONE fused psum of the 5-tuple of cross strips (``query._mean_strips``
    run on the local shard), then the replicated value and the local
    (Q, D_loc) grad assembly — exactly the single-device ``_mean_chunk``
    split at its reduction boundary.
    """
    b = data.base
    Xq = jnp.asarray(Xq, b.X.dtype)
    if not spec.is_stationary and b.c is not None:
        Xq = Xq - b.c
    f = GramFactors(K1e=b.K1e, K2e=b.K2e, Xt=b.Xt, lam=b.lam, c=None)
    strips = psum_fused(_mean_strips(Xq, f, b.Z), axis_names)
    return _mean_assemble(spec, strips, Xq, f, b.Z)


@_f32_matmuls
def sgpg_posterior_mean_pipelined(
    spec: KernelSpec,
    data: SGPGData,
    Xq: Array,
    *,
    axis_name: str,
    axis_size: int,
    chunks: int,
):
    """Chunked query with ring-reduced strips (Megatron-style overlap).

    The psum of chunk i's strips is replaced by a ``ppermute`` ring
    reduction carried OUT of chunk i's scan step: chunk i+1's local factor
    sweep has no data dependence on the in-flight ring hops, so XLA's
    latency-hiding scheduler overlaps collective and compute.  Requires a
    flat one-axis mesh (``launch.mesh.make_d_mesh``) and Q divisible by
    ``chunks``; numerics are identical to :func:`sgpg_posterior_mean` up
    to summation order.
    """
    b = data.base
    Xq = jnp.asarray(Xq, b.X.dtype)
    if not spec.is_stationary and b.c is not None:
        Xq = Xq - b.c
    f = GramFactors(K1e=b.K1e, K2e=b.K2e, Xt=b.Xt, lam=b.lam, c=None)
    q = Xq.shape[0]
    if q % chunks:
        raise ValueError(f"Q={q} not divisible by chunks={chunks}")
    Xqc = Xq.reshape(chunks, q // chunks, Xq.shape[1])

    def assemble(strips_local, xq):
        strips = ring_psum(strips_local, axis_name, axis_size)
        return _mean_assemble(spec, strips, xq, f, b.Z)

    if chunks == 1:
        return assemble(_mean_strips(Xqc[0], f, b.Z), Xqc[0])

    def body(carry, xq):
        prev_strips, prev_xq = carry
        out = assemble(prev_strips, prev_xq)     # ring hops for chunk i
        cur = _mean_strips(xq, f, b.Z)           # local sweep of chunk i+1
        return (cur, xq), out

    first = (_mean_strips(Xqc[0], f, b.Z), Xqc[0])
    (last_strips, last_xq), outs = jax.lax.scan(body, first, Xqc[1:])
    v_last, g_last = assemble(last_strips, last_xq)
    value = jnp.concatenate([outs[0].reshape(-1), v_last])
    grad = jnp.concatenate([outs[1].reshape(-1, Xq.shape[1]), g_last])
    return value, grad


# ---------------------------------------------------------------------------
# Communication-volume model (the claim BENCH_distributed.json checks)
# ---------------------------------------------------------------------------

#: psum launches per phase — the jaxpr gate contract (utils.hlo.count_psums)
PHASE_PSUMS = {
    "extend": 1, "evict": 0, "refactor": 0, "resolve": 1, "rebuild": 1,
    "query": 1, "solve": 0, "refit": 0,
}


def psum_bytes(phase: str, *, cap: int, q: int = 0, itemsize: int = 4,
               with_rhs: bool = False) -> int:
    """Analytic per-device collective bytes of one phase.

    All-reduce result bytes (what ``utils.hlo.collective_bytes`` counts):
    O(N^2) at worst, O(N) for extend — NEVER a function of D or of the
    device count.  This model feeds the ``collective.psum_bytes`` gauge
    and the BENCH_distributed claim gate.
    """
    if phase == "extend":
        n = 2 * 2 * cap + (cap * cap if with_rhs else 0)  # S2 + G2 (+ rhs)
        return n * itemsize
    if phase == "resolve":
        return cap * cap * itemsize
    if phase == "rebuild":
        return 3 * cap * cap * itemsize
    if phase == "query":
        # fused 5-tuple: P (q, cap), na (q,), nb (cap,), C (cap, q), tz (cap,)
        return (2 * q * cap + q + 2 * cap) * itemsize
    if phase in ("evict", "refactor", "solve", "refit"):
        return 0
    raise ValueError(f"unknown phase {phase!r}")


# ---------------------------------------------------------------------------
# Host-facing wrapper (mirrors GPGState; one compiled program per phase)
# ---------------------------------------------------------------------------


def _base_specs(names: tuple, has_c: bool) -> GPGData:
    dn = P(None, names)
    r = P()
    return GPGData(X=dn, G=dn, Xt=dn, K1e=r, K2e=r, L=r, Z=dn, lam=r,
                   count=r, n_refactor=r, n_solve=r, cg_iters=r, resnorm=r,
                   c=(P(names) if has_c else None))


class ShardedGPGState:
    """A D-sharded ``GPGState``: stream observations on a device mesh.

    >>> mesh = make_d_mesh()                      # all local devices
    >>> st = ShardedGPGState("rbf", d=2**16, window=8, mesh=mesh,
    ...                      lam=1e-4, noise=1e-8)
    >>> st.extend(x, g)        # ONE O(N)-byte fused psum + replicated algebra
    >>> pb = st.posterior(Xq)  # ONE O(QN)-byte fused psum per microbatch

    D is padded to a multiple of the mesh size (zero columns are exactly
    inert: they contribute zero to every strip and carry zero gradients);
    queries/outputs are transparently padded/trimmed.  Posterior serves the
    MEAN value/grad paths; probe/std queries require the (N, D)-resident
    variance solver and stay on the single-device state.

    Compile stability: every phase is ONE ``compile_watch``-wrapped jitted
    shard_map program, with count and noise as traced arguments — extends,
    evicts and refits never retrace (asserted in tests/test_dist_state.py).
    """

    def __init__(
        self,
        kernel: str | KernelSpec = "rbf",
        d: int | None = None,
        *,
        mesh=None,
        capacity: int = 8,
        window: int | None = None,
        lam=1.0,
        noise: float = 0.0,
        signal: float = 1.0,
        c=None,
        jitter: float = 1e-10,
        deg_thresh: float = 1e-8,
        dtype=None,
    ):
        if d is None:
            raise TypeError("ShardedGPGState needs the input dimension d")
        if mesh is None:
            from repro.launch.mesh import make_d_mesh

            mesh = make_d_mesh()
        self.spec = get_kernel(kernel) if isinstance(kernel, str) else kernel
        self.mesh = mesh
        self._names = tuple(mesh.axis_names)
        self.ndev = int(mesh.size)
        self.d_orig = int(d)
        self.d_pad = -(-self.d_orig // self.ndev) * self.ndev
        self.noise = float(noise)
        self.signal = float(signal)
        self.jitter = float(jitter)
        self.deg_thresh = float(deg_thresh)
        self.window = int(window) if window else None
        cap = self.window if self.window else int(capacity)
        if c is not None:
            c = jnp.pad(jnp.asarray(c, dtype), (0, self.d_pad - self.d_orig))
        self._has_c = c is not None and not self.spec.is_stationary
        # built in place with the phase programs' shardings: the (cap, D)
        # strips never sit whole on one device, and the first extend sees
        # the same input shardings as every later one (no retrace)
        from jax.sharding import NamedSharding

        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), self._data_spec(),
            is_leaf=lambda s: isinstance(s, P))
        spec = self.spec
        self.data = jax.jit(
            lambda c: sgpg_init(spec, self.d_pad, cap, lam=lam, c=c,
                                dtype=dtype),
            out_shardings=shardings)(c)
        self.revision = 0
        self._fns: dict = {}
        self._phase_raws: dict = {}
        self._query_fns: dict = {}
        self._query_raws: dict = {}
        if _obs.enabled():
            _obs.REGISTRY.inc("distributed.extend_calls", 0)

    # -- compiled phase programs (built once per shape) --------------------

    def _data_spec(self) -> SGPGData:
        r = P()
        return SGPGData(base=_base_specs(self._names, self._has_c), S0=r,
                        C=r, GG=r)

    def _phase(self, name: str):
        """The compiled shard_map program for one phase (cached)."""
        fn = self._fns.get(name)
        if fn is not None:
            return fn
        spec = self.spec
        names = self._names
        dspec = self._data_spec()
        vec = P(names)
        dn = P(None, names)
        jitter, deg = self.jitter, self.deg_thresh

        if name == "extend":
            def raw(data, x, g, noise):
                out, _ = sgpg_extend(spec, data, x, g, axis_names=names,
                                     noise=noise, jitter=jitter,
                                     deg_thresh=deg)
                return out
            in_specs = (dspec, vec, vec, P())
        elif name == "evict":
            def raw(data, noise):
                return sgpg_evict(spec, data, noise=noise, jitter=jitter)
            in_specs = (dspec, P())
        elif name == "refactor":
            def raw(data, lam, noise):
                return sgpg_refactor(spec, data, lam, noise=noise,
                                     jitter=jitter)
            in_specs = (dspec, P(), P())
        elif name == "resolve":
            def raw(data, rhs, noise):
                return sgpg_resolve(spec, data, rhs, axis_names=names,
                                    noise=noise, jitter=jitter)
            in_specs = (dspec, dn, P())
        elif name == "rebuild":
            def raw(data, noise):
                return sgpg_rebuild(spec, data, axis_names=names,
                                    noise=noise, jitter=jitter)
            in_specs = (dspec, P())
        else:
            raise KeyError(name)

        sm = jax.shard_map(raw, mesh=self.mesh, in_specs=in_specs,
                           out_specs=dspec, check_vma=False)
        fn = _cw.wrap(sm, name=f"distributed.{name}")
        self._fns[name] = fn
        self._phase_raws[name] = sm
        return fn

    def _phase_raw(self, name: str):
        """The UNWRAPPED shard_map program of a phase (for jaxpr/HLO
        inspection — tracing it never touches the compile watch)."""
        self._phase(name)
        return self._phase_raws[name]

    def _query_fn(self, q: int, chunks: Optional[int]):
        key = (q, chunks)
        fn = self._query_fns.get(key)
        if fn is not None:
            return fn
        spec = self.spec
        names = self._names
        dspec = self._data_spec()
        dn = P(None, names)
        if chunks is None:
            def raw(data, Xq):
                return sgpg_posterior_mean(spec, data, Xq, axis_names=names)
        else:
            if len(names) != 1:
                raise ValueError("pipelined queries need a flat one-axis "
                                 "mesh (launch.mesh.make_d_mesh)")
            axis, size = names[0], self.ndev

            def raw(data, Xq):
                return sgpg_posterior_mean_pipelined(
                    spec, data, Xq, axis_name=axis, axis_size=size,
                    chunks=chunks)
        sm = jax.shard_map(raw, mesh=self.mesh, in_specs=(dspec, dn),
                           out_specs=(P(), dn), check_vma=False)
        fn = _cw.wrap(sm, name=f"distributed.query.q{q}"
                      + (f".pipe{chunks}" if chunks else ""))
        self._query_fns[key] = fn
        self._query_raws[key] = sm
        return fn

    def _query_raw(self, q: int, chunks: Optional[int] = None):
        """The UNWRAPPED shard_map query program (for lowering and jaxpr
        inspection — a lowering must never hit the compile-watched
        entry)."""
        self._query_fn(q, chunks)
        return self._query_raws[(q, chunks)]

    # -- padding helpers ---------------------------------------------------

    def _pad_cols(self, A: Array) -> Array:
        A = jnp.asarray(A, self.data.base.X.dtype)
        pad = self.d_pad - A.shape[-1]
        if pad == 0:
            return A
        width = [(0, 0)] * (A.ndim - 1) + [(0, pad)]
        return jnp.pad(A, width)

    def _gauge(self, phase: str, q: int = 0):
        if _obs.enabled():
            itemsize = jnp.dtype(self.data.base.X.dtype).itemsize
            _obs.REGISTRY.set_gauge(
                "collective.psum_bytes",
                psum_bytes(phase, cap=self.data.capacity, q=q,
                           itemsize=itemsize))

    # -- streaming updates (GPGState API) ----------------------------------

    @property
    def _noise_eff(self) -> float:
        return self.noise / self.signal

    def extend(self, x: Array, g: Array) -> "ShardedGPGState":
        """Append one observation (auto-evicts at the window bound)."""
        from repro.resilience import guardrails as _guard

        _guard.check_finite(x, g, what="observation")
        with _obs.span("distributed.extend", d=self.d_orig,
                       shards=self.ndev):
            if self.window and self.n >= self.window:
                self.data = self._phase("evict")(
                    self.data, jnp.asarray(0.0))  # solve follows the extend
            elif self.n >= self.data.capacity:
                raise ValueError("capacity exhausted (no window set)")
            self.data = self._phase("extend")(
                self.data, self._pad_cols(jnp.asarray(x)),
                self._pad_cols(jnp.asarray(g)),
                jnp.asarray(self._noise_eff))
            self._gauge("extend")
            if _obs.enabled():
                _obs.REGISTRY.inc("distributed.extend_calls")
                _obs.REGISTRY.set_gauge("state.n", self.n)
        self.revision += 1
        return self

    def evict(self, k: int = 1) -> "ShardedGPGState":
        with _obs.span("distributed.evict", k=k):
            for _ in range(k):
                self.data = self._phase("evict")(
                    self.data, jnp.asarray(self._noise_eff))
            self._gauge("evict")
        self.revision += 1
        return self

    def refactor(self, lam=None) -> "ShardedGPGState":
        with _obs.span("distributed.refactor"):
            lam = self.data.base.lam if lam is None else lam
            self.data = self._phase("refactor")(
                self.data, jnp.asarray(lam, self.data.base.X.dtype),
                jnp.asarray(self._noise_eff))
            self._gauge("refactor")
        self.revision += 1
        return self

    def resolve(self, rhs: Array) -> Array:
        """Solve against a new (n, d) RHS; returns the trimmed global Z."""
        with _obs.span("distributed.resolve"):
            full = jnp.zeros((self.data.capacity, self.d_orig),
                             self.data.base.X.dtype)
            full = full.at[: rhs.shape[0]].set(
                jnp.asarray(rhs, full.dtype))
            self.data = self._phase("resolve")(
                self.data, self._pad_cols(full),
                jnp.asarray(self._noise_eff))
            self._gauge("resolve")
        self.revision += 1
        return self.Z

    @classmethod
    def from_data(cls, kernel, X: Array, G: Array, **kw) -> "ShardedGPGState":
        """Bulk-condition on (X, G): ONE strip-building psum + one solve."""
        X = jnp.atleast_2d(X)
        n, d = X.shape
        kw.setdefault("capacity", max(n, 1))
        st = cls(kernel, d, **kw)
        cap = st.data.capacity
        if n > cap:
            raise ValueError(f"{n} observations exceed capacity={cap}")
        Xp = st._pad_cols(jnp.pad(jnp.asarray(X, st.data.base.X.dtype),
                                  ((0, cap - n), (0, 0))))
        Gp = st._pad_cols(jnp.pad(jnp.asarray(G, st.data.base.X.dtype),
                                  ((0, cap - n), (0, 0))))
        c = st.data.base.c
        Xt = Xp if (st.spec.is_stationary or c is None) else Xp - c[None, :]
        mask = (jnp.arange(cap) < n)[:, None]
        base = st.data.base._replace(X=Xp, G=Gp, Xt=jnp.where(mask, Xt, 0.0),
                                     count=jnp.asarray(n, jnp.int32))
        st.data = st.data._replace(base=base)
        st.data = st._phase("rebuild")(st.data,
                                       jnp.asarray(st._noise_eff))
        st._gauge("rebuild")
        return st

    # -- snapshot/restore (repro.resilience.snapshot) ----------------------

    _SNAP_D = ("X", "G", "Xt", "Z")             # leaves with a D axis
    _SNAP_R = ("K1e", "K2e", "L", "lam", "count", "n_refactor", "n_solve",
               "cg_iters", "resnorm")           # replicated leaves

    def snapshot_arrays(self) -> dict:
        """Host-gathered leaves, D-axes TRIMMED to ``d_orig`` — the
        mesh-independent logical state (pad columns are exactly zero by
        the module contract, so nothing is lost)."""
        import numpy as np

        b = self.data.base
        k = self.d_orig
        out = {f: np.asarray(jax.device_get(getattr(b, f)))[:, :k]
               for f in self._SNAP_D}
        out.update({f: np.asarray(jax.device_get(getattr(b, f)))
                    for f in self._SNAP_R})
        for f in ("S0", "C", "GG"):
            out[f] = np.asarray(jax.device_get(getattr(self.data, f)))
        if b.c is not None:
            out["c"] = np.asarray(jax.device_get(b.c))[:k]
        return out

    def load_snapshot_arrays(self, named: dict) -> "ShardedGPGState":
        """Install snapshot leaves VERBATIM, re-padded for THIS mesh and
        device_put with the phase programs' shardings.

        Restoring factors directly (instead of re-running ``rebuild``)
        is what preserves bit-identity: the live factors were built
        incrementally (bordered updates), and a from-scratch rebuild
        would round differently.  Same-mesh restores are bitwise; a
        different mesh re-pads with zero columns, which are exactly
        inert going forward.
        """
        import numpy as np
        from jax.sharding import NamedSharding

        dspec = self._data_spec()
        dt = self.data.base.X.dtype

        def putD(name, spec):
            a = np.asarray(named[name])
            a = np.pad(a, ((0, 0), (0, self.d_pad - a.shape[1])))
            return jax.device_put(jnp.asarray(a, dt),
                                  NamedSharding(self.mesh, spec))

        def putR(name, leaf, spec):
            a = jnp.asarray(np.asarray(named[name]), leaf.dtype)
            return jax.device_put(a, NamedSharding(self.mesh, spec))

        b = self.data.base
        kw = {f: putD(f, getattr(dspec.base, f)) for f in self._SNAP_D}
        kw.update({f: putR(f, getattr(b, f), getattr(dspec.base, f))
                   for f in self._SNAP_R})
        if b.c is not None and "c" in named:
            c = np.asarray(named["c"])
            c = np.pad(c, (0, self.d_pad - c.shape[0]))
            kw["c"] = jax.device_put(jnp.asarray(c, dt),
                                     NamedSharding(self.mesh, dspec.base.c))
        base = b._replace(**kw)
        self.data = self.data._replace(
            base=base,
            S0=putR("S0", self.data.S0, dspec.S0),
            C=putR("C", self.data.C, dspec.C),
            GG=putR("GG", self.data.GG, dspec.GG))
        self.revision += 1
        return self

    # -- model selection off the maintained strips -------------------------

    @property
    def hypers(self):
        from repro.hyper import HyperParams

        return HyperParams.create(
            lengthscale2=1.0 / float(jnp.asarray(self.data.base.lam)),
            signal=self.signal, noise=max(self.noise, 1e-30))

    def mll(self):
        """Exact MLL of the current window off the strips — ZERO psums."""
        from repro.hyper import mll_from_strips

        if self.n < 1:
            raise ValueError("mll() needs at least one observation")
        return mll_from_strips(self.spec, self.data.S0, self.data.C,
                               self.data.GG, self.d_orig, self.hypers,
                               count=self.data.base.count)

    def refit(self, *, mask=None, steps: int = 150, lr: float = 0.08,
              **fit_kw):
        """MLL-fit the hypers from the maintained strips, then the
        zero-psum refactor.  The whole fit is replicated host compute —
        no collective is issued for ANY number of fit steps."""
        from repro.hyper import fit_fn, make_mll_strips_fn

        if self.n < 2:
            raise ValueError("refit() needs at least two observations")
        with _obs.span("distributed.refit", steps=steps):
            fn = make_mll_strips_fn(
                self.spec, self.data.S0, self.data.C, self.data.GG,
                self.d_orig, count=self.data.base.count)
            res = fit_fn(fn, self.hypers, mask=mask, steps=steps, lr=lr,
                         **fit_kw)
            self.noise = float(res.hypers.noise)
            self.signal = float(res.hypers.signal)
            self.refactor(lam=res.hypers.lam)
        return res

    # -- queries -----------------------------------------------------------

    def posterior(self, Xq: Array, *, chunks: Optional[int] = None,
                  probe=None, return_std: bool = False,
                  return_grad_std: bool = False):
        """Posterior mean value/grad at Xq; ``chunks`` enables the ring-
        pipelined path (flat meshes).  Probe/std paths are not served
        sharded — use the single-device state for those."""
        from .query import PosteriorBatch

        if probe is not None or return_std or return_grad_std:
            raise NotImplementedError(
                "sharded posterior serves mean value/grad only; probe/std "
                "need the (N, D)-resident variance solver (single-device)")
        Xq = jnp.atleast_2d(Xq)
        q = Xq.shape[0]
        with _obs.span("distributed.query", q=q):
            value, grad = self._query_fn(q, chunks)(
                self.data, self._pad_cols(Xq))
            self._gauge("query", q=q)
        return PosteriorBatch(value=value, grad=grad[:, : self.d_orig])

    # -- views -------------------------------------------------------------

    @property
    def n(self) -> int:
        return int(self.data.base.count)

    @property
    def d(self) -> int:
        return self.d_orig

    @property
    def X(self) -> Array:
        return jnp.asarray(self.data.base.X)[: self.n, : self.d_orig]

    @property
    def G(self) -> Array:
        return jnp.asarray(self.data.base.G)[: self.n, : self.d_orig]

    @property
    def Z(self) -> Array:
        return jnp.asarray(self.data.base.Z)[: self.n, : self.d_orig]

    @property
    def stats(self) -> dict:
        b = self.data.base
        return {"n": self.n, "n_refactor": int(b.n_refactor),
                "n_solve": int(b.n_solve), "d_pad": self.d_pad,
                "shards": self.ndev}

    def __repr__(self):
        return (f"ShardedGPGState(kernel={self.spec.name!r}, n={self.n}, "
                f"d={self.d_orig} (pad {self.d_pad}), "
                f"shards={self.ndev}, window={self.window})")
