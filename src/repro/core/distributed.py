"""D-sharded (shard_map) variants of the structured Gram operations.

The paper's decomposition has one systems-defining property: every O(D)
object only appears inside tall-skinny contractions that reduce to (N, N).
Sharding the dimension axis over the WHOLE mesh therefore makes each Gram
op a purely local (N, D_loc) computation plus a psum of a few N x N
matrices — O(N^2) bytes of collective traffic per solve, independent of D
and of device count. That is the communication-avoiding scheme this module
implements (DESIGN.md sec. 2/6).

All functions here are written for use INSIDE shard_map (they take local
shards and issue explicit psums over `axis_names`). ``sharded_*`` wrappers
construct the shard_map for callers holding global arrays.

Layout: (N, D) rows=observations, D sharded on the last axis. Lambda must
be scalar, or a (D,) diagonal sharded like the data.
"""
from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import backend
from .gram import FactorBundle, GramFactors
from .kernels import KernelSpec
from .mvm import gram_matvec, l_op, lt_op

Array = jnp.ndarray


# ---------------------------------------------------------------------------
# Collective-side primitives (called inside shard_map)
# ---------------------------------------------------------------------------

def psum_fused(x, axis_names):
    """``jax.lax.psum`` of a pytree as ONE collective.

    ``jax.lax.psum`` binds one psum per leaf, so a tuple of strips would
    cost one all-reduce launch each.  This packs the leaves into one flat
    buffer of their common dtype, reduces it once and unpacks — the
    one-psum-per-phase contract of ``core/dist_state.py`` (gated by
    ``utils.hlo.count_psums``) holds at the jaxpr level.
    """
    leaves, treedef = jax.tree_util.tree_flatten(x)
    if len(leaves) <= 1:
        return jax.lax.psum(x, axis_names)
    dt = jnp.result_type(*leaves)
    flat = jax.lax.psum(
        jnp.concatenate([jnp.ravel(a).astype(dt) for a in leaves]), axis_names)
    out, off = [], 0
    for a in leaves:
        out.append(flat[off:off + a.size].reshape(a.shape).astype(a.dtype))
        off += a.size
    return jax.tree_util.tree_unflatten(treedef, out)


def ring_psum(x, axis_name: str, size: int):
    """All-reduce built from ``size - 1`` ppermute ring hops (pytree-safe).

    Numerically a psum (up to summation order), but each hop is an
    independent point-to-point ``ppermute`` whose result the caller only
    consumes at the END of its pipeline stage — so XLA's latency-hiding
    scheduler can overlap the hops with unrelated local compute (the
    Megatron-style collective/compute overlap; ``core.dist_state.
    sgpg_posterior_mean_pipelined`` carries the in-flight reduction across
    a scan step).  Requires a flat one-axis mesh; ``size`` must be the
    static axis size.
    """
    if size == 1:
        return x
    perm = [(i, (i + 1) % size) for i in range(size)]
    acc, cur = x, x
    for _ in range(size - 1):
        cur = jax.tree_util.tree_map(
            lambda t: jax.lax.ppermute(t, axis_name, perm), cur)
        acc = jax.tree_util.tree_map(jnp.add, acc, cur)
    return acc

def local_scaled_gram(A: Array, B: Array, lam, axis_names: Sequence[str]) -> Array:
    """psum_d (A*lam) @ B^T for D-sharded A, B: the N^2-byte collective.

    The local partial routes through the backend dispatch, so on TPU each
    device runs the Pallas skinny-gram kernel over its (N, D_loc) shard.
    """
    part = backend.scaled_gram(A, B, lam)
    return jax.lax.psum(part, axis_names)


def local_pairwise_r(
    spec: KernelSpec, A: Array, B: Array, lam, axis_names: Sequence[str],
    c: Array | None = None,
) -> Array:
    """Pairwise r for D-sharded inputs; one fused psum of (gram, norms)."""
    if spec.is_stationary:
        part, da, db = backend.gram_norms(A, B, lam)
        g, da, db = psum_fused((part, da, db), axis_names)
        return jnp.maximum(da[:, None] + db[None, :] - 2.0 * g, 0.0)
    At = A if c is None else A - c
    Bt = B if c is None else B - c
    return local_scaled_gram(At, Bt, lam, axis_names)


def local_build_factors(
    spec: KernelSpec, X: Array, lam, axis_names: Sequence[str],
    c: Array | None = None, noise: float = 0.0,
) -> GramFactors:
    """GramFactors with local (N, D_loc) Xt but *global* (replicated) K1e/K2e."""
    r = local_pairwise_r(spec, X, X, lam, axis_names, c=c)
    Xt = X if (spec.is_stationary or c is None) else X - c
    return GramFactors(K1e=spec.k1e(r), K2e=spec.k2e(r), Xt=Xt, lam=lam,
                       noise=float(noise), c=None if spec.is_stationary else c)


def local_gram_matvec(
    f: GramFactors, V: Array, *, stationary: bool, axis_names: Sequence[str],
) -> Array:
    """(grad K grad') vec(V) with D-sharded V/Xt. One N^2 psum, rest local.

    Identical math to core.mvm.gram_matvec: the only cross-device term is
    M = (Xt*lam) @ V^T; the (N,N) algebra is replicated and the final
    (N,N) @ (N,D_loc) update runs locally as one backend.gram_update
    launch (via gram_matvec's precomputed-gram path).
    """
    M = local_scaled_gram(f.Xt, V, f.lam, axis_names)
    return gram_matvec(f, V, stationary=stationary, gram_xv=M)


def local_factor_bundle(
    spec: KernelSpec, X: Array, G: Array, lam, axis_names: Sequence[str],
    c: Array | None = None, noise: float = 0.0,
) -> FactorBundle:
    """D-sharded ``build_factor_bundle``: ONE fused psum for everything.

    The single ``backend.fused_factor_build`` sweep of the local (N, D_loc)
    shards emits the gram/norm partials AND the RHS contraction C = G X~^T,
    so one stacked psum replicates every (N, N) strip a solve needs —
    where ``local_build_factors`` + ``local_woodbury_solve`` used to issue
    three separate collectives per solve.  The bundle's ``factors.Xt``
    stays LOCAL (it only ever feeds local output-assembly streams).
    """
    Xt = X if (spec.is_stationary or c is None) else X - c
    P_, na, nb, C, _ = backend.fused_factor_build(Xt, Xt, G, lam)
    P_, na, C = psum_fused((P_, na, C), axis_names)
    if spec.is_stationary:
        r = jnp.maximum(na[:, None] + na[None, :] - 2.0 * P_, 0.0)
    else:
        r = P_
    f = GramFactors(K1e=spec.k1e(r), K2e=spec.k2e(r), Xt=Xt, lam=lam,
                    noise=float(noise), c=None if spec.is_stationary else c)
    return FactorBundle(factors=f, S=P_, C=C)


def local_woodbury_solve(
    spec: KernelSpec, f: GramFactors, G: Array, axis_names: Sequence[str],
    jitter: float = 1e-10, S: Array | None = None, C: Array | None = None,
) -> Array:
    """Exact Woodbury solve with D-sharded Xt/G (paper Eq. 6-8, distributed).

    Cross-device traffic: two (N,N) psums (S and the RHS skinny
    contraction) — or ZERO when a prebuilt bundle supplies them: pass
    ``S``/``C`` from :func:`local_factor_bundle` and the solve reuses the
    replicated strips (T0 = (K1i G) X~^T re-associates to K1i @ C), so
    repeated solves against cached factors issue no collectives at all.
    The N^2 x N^2 inner system is replicated on every device and solved
    redundantly (cheaper than sharding an N<=64 solve).
    """
    n = f.n
    dtype = G.dtype
    K1 = f.K1e
    if f.noise:
        lam_s = jnp.asarray(f.lam)
        K1 = K1 + (f.noise / lam_s) * jnp.eye(n, dtype=dtype)
    K1i = jnp.linalg.inv(K1 + jitter * jnp.eye(n, dtype=dtype))
    if S is None:
        S = local_scaled_gram(f.Xt, f.Xt, f.lam, axis_names)
    if C is not None:
        T = K1i @ C
    else:
        W0 = backend.kron_precond(K1i, G, 1.0)            # local (N, D_loc)
        T = local_scaled_gram(W0, f.Xt, 1.0, axis_names)  # skinny + psum

    if spec.is_stationary:
        T = lt_op(T)

        def inner(Q):
            return -Q.T / f.K2e + lt_op(K1i @ l_op(Q) @ S)

    else:

        def inner(Q):
            return Q.T / f.K2e + K1i @ Q @ S

    eye = jnp.eye(n * n, dtype=dtype).reshape(n * n, n, n)
    A = jax.vmap(inner)(eye).reshape(n * n, n * n).T
    q = jnp.linalg.solve(A + jitter * jnp.eye(n * n, dtype=dtype), T.reshape(-1))
    Q = q.reshape(n, n)

    QL = l_op(Q) if spec.is_stationary else Q
    return backend.gram_update(K1i, -(K1i @ QL), G, f.Xt, 1.0,
                               v_scale=1.0 / jnp.asarray(f.lam))


def local_cross_grad_matvec(
    spec: KernelSpec, Xq: Array, f: GramFactors, V: Array,
    axis_names: Sequence[str],
) -> Array:
    """Posterior-mean gradient at D-sharded query rows Xq: (Nq, D_loc)."""
    lam = f.lam
    if spec.is_stationary:
        r = local_pairwise_r(spec, Xq, f.Xt, lam, axis_names)
        K1e, K2e = spec.k1e(r), spec.k2e(r)
        m_part = backend.scaled_gram(Xq, V, lam) - \
            backend.row_dots(f.Xt, V, lam)[None, :]
        m = jax.lax.psum(m_part, axis_names)
        Mt = K2e * m
        W = backend.gram_update(K1e, -Mt, V, f.Xt, lam)
        return W + (Xq * jnp.sum(Mt, axis=1)[:, None]) * lam
    Xqt = Xq if f.c is None else Xq - f.c
    r = local_scaled_gram(Xqt, f.Xt, lam, axis_names)
    K1e, K2e = spec.k1e(r), spec.k2e(r)
    m = local_scaled_gram(Xqt, V, lam, axis_names)
    return backend.gram_update(K1e, K2e * m, V, f.Xt, lam)


# ---------------------------------------------------------------------------
# shard_map wrappers over a full mesh (callers hold global arrays)
# ---------------------------------------------------------------------------

def _d_sharding(mesh: Mesh):
    """Shard the last (D) axis over ALL mesh axes jointly."""
    return P(None, tuple(mesh.axis_names))


def sharded_gram_matvec(mesh: Mesh, spec: KernelSpec):
    """Returns fn(f: GramFactors[global], V[global]) -> W[global]."""
    names = tuple(mesh.axis_names)
    dspec = _d_sharding(mesh)
    lam_spec = P()  # scalar lam replicated; diagonal handled by caller

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(None, None), P(None, None), dspec, lam_spec, dspec),
        out_specs=dspec,
    )
    def _run(K1e, K2e, Xt, lam, V):
        f = GramFactors(K1e=K1e, K2e=K2e, Xt=Xt, lam=lam, noise=0.0, c=None)
        return local_gram_matvec(f, V, stationary=spec.is_stationary,
                                 axis_names=names)

    def apply(f: GramFactors, V: Array) -> Array:
        return _run(f.K1e, f.K2e, f.Xt, jnp.asarray(f.lam), V)

    return apply


def sharded_factor_bundle(mesh: Mesh, spec: KernelSpec, noise: float = 0.0):
    """Returns fn(X[global], G[global], lam, c) -> FactorBundle.

    The bundle's ``factors.Xt`` comes back D-SHARDED (it only feeds local
    output streams); K1e/K2e/S/C are replicated.  Pass the result to
    :func:`sharded_woodbury_solve`'s ``bundle=`` to amortize the ONE
    build collective across repeated solves.
    """
    names = tuple(mesh.axis_names)
    dspec = _d_sharding(mesh)
    rep = P(None, None)
    out = (rep, rep, dspec, rep, rep)  # K1e, K2e, Xt(local), S, C

    def _arrays(b: FactorBundle):
        f = b.factors
        return f.K1e, f.K2e, f.Xt, b.S, b.C

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(dspec, dspec, P()),
        out_specs=out,
    )
    def _run_stationary(X, G, lam):
        return _arrays(local_factor_bundle(spec, X, G, lam, names,
                                           noise=noise))

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(dspec, dspec, P(), dspec),
        out_specs=out,
    )
    def _run_dot(X, G, lam, c):
        return _arrays(local_factor_bundle(spec, X, G, lam, names, c=c,
                                           noise=noise))

    def build(X: Array, G: Array, lam=1.0,
              c: Array | None = None) -> FactorBundle:
        lam = jnp.asarray(lam)
        if spec.is_stationary:
            K1e, K2e, Xt, S, C = _run_stationary(X, G, lam)
        else:
            if c is None:
                c = jnp.zeros((1, X.shape[1]), X.dtype)
            K1e, K2e, Xt, S, C = _run_dot(X, G, lam, jnp.atleast_2d(c))
        # Xt comes back pre-centered for dot kernels: c=None by design
        f = GramFactors(K1e=K1e, K2e=K2e, Xt=Xt, lam=lam,
                        noise=float(noise), c=None)
        return FactorBundle(factors=f, S=S, C=C)

    return build


def sharded_woodbury_solve(mesh: Mesh, spec: KernelSpec, noise: float = 0.0):
    """Returns fn(X[global], G[global], lam, c, bundle) -> Z[global].

    Without ``bundle``: builds factors and solves in one shard_map (one
    fused build psum + one RHS psum).  With a ``bundle`` from
    :func:`sharded_factor_bundle`: the prebuilt local factors and
    replicated S/C strips are REUSED — the solve issues ZERO collectives,
    matching the single-device ``woodbury_solve(bundle=...)`` fast path
    (which this wrapper used to ignore, re-streaming X per solve).
    """
    names = tuple(mesh.axis_names)
    dspec = _d_sharding(mesh)
    rep = P(None, None)

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(dspec, dspec, P()),
        out_specs=dspec,
    )
    def _run_stationary(X, G, lam):
        b = local_factor_bundle(spec, X, G, lam, names, noise=noise)
        return local_woodbury_solve(spec, b.factors, G, names, S=b.S, C=b.C)

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(dspec, dspec, P(), dspec),
        out_specs=dspec,
    )
    def _run_dot(X, G, lam, c):
        b = local_factor_bundle(spec, X, G, lam, names, c=c, noise=noise)
        return local_woodbury_solve(spec, b.factors, G, names, S=b.S, C=b.C)

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(rep, rep, dspec, P(), rep, rep, dspec),
        out_specs=dspec,
    )
    def _run_bundle(K1e, K2e, Xt, lam, S, C, G):
        f = GramFactors(K1e=K1e, K2e=K2e, Xt=Xt, lam=lam,
                        noise=float(noise), c=None)
        return local_woodbury_solve(spec, f, G, names, S=S, C=C)

    def solve(X: Array, G: Array, lam=1.0, c: Array | None = None,
              bundle: FactorBundle | None = None) -> Array:
        if bundle is not None:
            f = bundle.factors
            Xt = f.Xt if f.c is None else f.Xt - f.c  # fold dot centering
            return _run_bundle(f.K1e, f.K2e, Xt, jnp.asarray(f.lam),
                               bundle.S, bundle.C, G)
        lam = jnp.asarray(lam)
        if spec.is_stationary:
            return _run_stationary(X, G, lam)
        if c is None:
            c = jnp.zeros((1, X.shape[1]), X.dtype)
        return _run_dot(X, G, lam, jnp.atleast_2d(c))

    return solve
