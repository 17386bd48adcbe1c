"""Single-sweep fused factor build + precision policy (DESIGN.md sec. 12).

Three claim families:
  * kernel parity: ``fused_factor_build`` (Pallas, interpret mode) against
    the ref.py oracle across shapes/dtypes/scalings;
  * structural single-sweep: the lowered ``woodbury_solve`` and query
    microbatch consume the X data stream in exactly ONE factor-build
    (reduction) contraction plus the one unavoidable output-assembly
    stream — counted on the jaxpr by ``utils.hlo.count_data_streams``;
  * precision: bf16 storage / f32 accumulation tracks the f32 pipeline to
    <= 1e-3 normwise on every fused entry point, and the state/serve
    layers cache the bf16 stream copies per revision.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.core import (build_factor_bundle, build_factors, dense_solve,
                        get_kernel, use_backend, use_precision,
                        woodbury_solve)
from repro.core import backend
from repro.core.query import _query_chunk, posterior_batch
from repro.core.state import GPGState
from repro.kernels import fused_factor_build, fused_factor_build_ref
from repro.utils.hlo import count_data_streams

D_STREAM = 384  # > max(N, Q)^2 for every shape below: the taint axis is unambiguous


def _rel(a, b):
    a = jnp.asarray(a, jnp.float64)
    b = jnp.asarray(b, jnp.float64)
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


# ---------------------------------------------------------------------------
# Kernel parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("na,nb,d", [(3, 5, 64), (8, 8, 128), (5, 12, 1000),
                                     (1, 1, 257)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("lam_kind", ["scalar", "diag"])
def test_fused_factor_build_parity(na, nb, d, dtype, lam_kind, rng):
    A = jax.random.normal(jax.random.fold_in(rng, 1), (na, d),
                          jnp.float32).astype(dtype)
    B = jax.random.normal(jax.random.fold_in(rng, 2), (nb, d),
                          jnp.float32).astype(dtype)
    V = jax.random.normal(jax.random.fold_in(rng, 3), (nb, d),
                          jnp.float32).astype(dtype)
    lam = 0.4 if lam_kind == "scalar" else \
        jnp.abs(jax.random.normal(jax.random.fold_in(rng, 4), (d,))) + 0.1
    vs = lam if lam_kind == "diag" else 0.8
    got = fused_factor_build(A, B, V, lam, v_scale=vs, interpret=True)
    want = fused_factor_build_ref(A, B, V, lam, vs)
    for g, w in zip(got, want):
        assert g.dtype == jnp.float32  # f32 outputs regardless of storage
        assert _rel(g, w.reshape(g.shape)) < 1e-5


def test_fused_factor_build_v_none_reuses_b(rng):
    A = jax.random.normal(jax.random.fold_in(rng, 1), (4, 200), jnp.float32)
    B = jax.random.normal(jax.random.fold_in(rng, 2), (6, 200), jnp.float32)
    got = fused_factor_build(A, B, None, 0.5, interpret=True)
    want = fused_factor_build(A, B, B, 0.5, interpret=True)
    for g, w in zip(got, want):
        assert jnp.array_equal(g, w)


def test_fused_factor_build_padding_exact(rng):
    """Zero lam/vs pad lanes kill garbage pad columns exactly."""
    A = jax.random.normal(jax.random.fold_in(rng, 1), (4, 1000))
    B = jax.random.normal(jax.random.fold_in(rng, 2), (6, 1000))
    V = jax.random.normal(jax.random.fold_in(rng, 3), (6, 1000))
    got = fused_factor_build(A, B, V, 1.0, v_scale=1.0, interpret=True)
    junk = 1e6 * jnp.ones((16, 24))
    ext = lambda M: jnp.concatenate([M, junk[: M.shape[0]]], axis=1)
    lam2 = jnp.concatenate([jnp.ones(1000), jnp.zeros(24)])
    embedded = fused_factor_build(ext(A), ext(B), ext(V), lam2, v_scale=lam2,
                                  interpret=True)
    for g, e in zip(got, embedded):
        assert jnp.array_equal(g, e)


@pytest.mark.parametrize("name", ["rbf", "expdot"])
def test_backend_fused_factor_build_parity(name, rng):
    """pallas (interpret) and jnp backends agree through the dispatch."""
    d = 96
    A = jax.random.normal(jax.random.fold_in(rng, 1), (5, d))
    B = jax.random.normal(jax.random.fold_in(rng, 2), (7, d))
    V = jax.random.normal(jax.random.fold_in(rng, 3), (7, d))
    lam = jnp.abs(jax.random.normal(jax.random.fold_in(rng, 4), (d,))) + 0.1
    # float32 streams on the pallas side (it refuses float64 operands); the
    # jnp reference stays in float64
    A32, B32, V32 = (a.astype(jnp.float32) for a in (A, B, V))
    with use_backend("pallas"):
        p = backend.fused_factor_build(A32, B32, V32, lam, v_scale=lam)
    with use_backend("jnp"):
        j = backend.fused_factor_build(A, B, V, lam, v_scale=lam)
    for gp, gj in zip(p, j):
        assert _rel(gp, gj) < 1e-5


# ---------------------------------------------------------------------------
# Bundle-consuming solves
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["rbf", "expdot"])
def test_bundle_solve_matches_dense(name, rng):
    n, d = 5, 24
    spec = get_kernel(name)
    c = None if spec.is_stationary else \
        0.05 * jax.random.normal(jax.random.fold_in(rng, 9), (d,))
    X = jax.random.normal(jax.random.fold_in(rng, 1), (n, d))
    G = jax.random.normal(jax.random.fold_in(rng, 2), (n, d))
    b = build_factor_bundle(spec, X, G, lam=0.5, c=c)
    Z = woodbury_solve(spec, b.factors, G, bundle=b)
    Zref = dense_solve(spec, X, G, lam=0.5, c=c)
    assert _rel(Z, Zref) < 1e-6


@pytest.mark.parametrize("name", ["rbf", "expdot", "poly2"])
def test_bundle_solve_identical_to_unbundled(name, rng):
    """Passing the prebuilt bundle must not change the solve AT ALL —
    same S/C contractions, just computed in the shared sweep."""
    n, d = 5, 24
    spec = get_kernel(name)
    c = None if spec.is_stationary else \
        0.05 * jax.random.normal(jax.random.fold_in(rng, 9), (d,))
    X = jax.random.normal(jax.random.fold_in(rng, 1), (n, d))
    G = jax.random.normal(jax.random.fold_in(rng, 2), (n, d))
    b = build_factor_bundle(spec, X, G, lam=0.5, c=c)
    f = build_factors(spec, X, lam=0.5, c=c)
    Z0 = woodbury_solve(spec, f, G)
    Zb = woodbury_solve(spec, b.factors, G, bundle=b)
    assert jnp.array_equal(Z0, Zb)


def test_bundle_matches_build_factors(rng):
    """build_factor_bundle == build_factors + the separate contractions."""
    n, d = 6, 40
    for name in ("rbf", "expdot"):
        spec = get_kernel(name)
        c = None if spec.is_stationary else jnp.full((d,), 0.02)
        X = jax.random.normal(jax.random.fold_in(rng, 1), (n, d))
        G = jax.random.normal(jax.random.fold_in(rng, 2), (n, d))
        b = build_factor_bundle(spec, X, G, lam=0.3, c=c)
        f = build_factors(spec, X, lam=0.3, c=c)
        assert _rel(b.factors.K1e, f.K1e) < 1e-12
        assert _rel(b.factors.K2e, f.K2e) < 1e-12
        assert _rel(b.S, (f.Xt * 0.3) @ f.Xt.T) < 1e-12
        assert _rel(b.C, G @ f.Xt.T) < 1e-12


# ---------------------------------------------------------------------------
# Structural single-sweep asserts (the acceptance-criteria jaxpr gate)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["rbf", "expdot"])
def test_woodbury_single_x_stream(name, rng):
    """The lowered exact solve consumes the X stream in exactly ONE
    factor-build contraction (plus the one output-assembly stream)."""
    n, d = 5, D_STREAM
    spec = get_kernel(name)
    c = None if spec.is_stationary else jnp.full((d,), 0.01, jnp.float32)
    X = jax.random.normal(jax.random.fold_in(rng, 1), (n, d), jnp.float32)
    G = jax.random.normal(jax.random.fold_in(rng, 2), (n, d), jnp.float32)
    with use_backend("pallas"):
        f = build_factors(spec, X, lam=0.5, c=c, noise=1e-3)
        closed = jax.make_jaxpr(
            lambda Xt, g: woodbury_solve(spec, f._replace(Xt=Xt), g))(f.Xt, G)
    streams = count_data_streams(closed, 0, d)
    assert streams == {"reduction": 1, "expansion": 1}, streams


@pytest.mark.parametrize("name", ["rbf", "expdot"])
def test_query_chunk_single_x_stream(name, rng):
    """Per query microbatch: ONE reduction stream of the stored data X
    (and of the query batch), plus only the (Q, D) grad output stream."""
    n, q, d = 5, 4, D_STREAM
    spec = get_kernel(name)
    c = None if spec.is_stationary else jnp.full((d,), 0.01, jnp.float32)
    X = jax.random.normal(jax.random.fold_in(rng, 1), (n, d), jnp.float32)
    Z = jax.random.normal(jax.random.fold_in(rng, 2), (n, d), jnp.float32)
    Xq = jax.random.normal(jax.random.fold_in(rng, 3), (q, d), jnp.float32)
    with use_backend("pallas"):
        f = build_factors(spec, X, lam=0.5, c=c)
        closed = jax.make_jaxpr(
            lambda Xt, z, xq: _query_chunk(spec, xq, f._replace(Xt=Xt), z,
                                           None))(f.Xt, Z, Xq)
    xt_streams = count_data_streams(closed, 0, d)
    xq_streams = count_data_streams(closed, 2, d)
    assert xt_streams == {"reduction": 1, "expansion": 1}, xt_streams
    assert xq_streams["reduction"] == 1, xq_streams


def test_query_chunk_matches_unfused_matvecs(rng):
    """The fused mean chunk == the original cross_*_matvec contractions."""
    from repro.core.mvm import cross_grad_matvec, cross_value_matvec

    n, q, d = 6, 5, 48
    for name in ("rbf", "expdot"):
        spec = get_kernel(name)
        c = None if spec.is_stationary else jnp.full((d,), 0.03)
        X = jax.random.normal(jax.random.fold_in(rng, 1), (n, d))
        Z = jax.random.normal(jax.random.fold_in(rng, 2), (n, d))
        Xq = jax.random.normal(jax.random.fold_in(rng, 3), (q, d))
        f = build_factors(spec, X, lam=0.4, c=c)
        pb = _query_chunk(spec, Xq, f, Z, None)
        assert _rel(pb.value, cross_value_matvec(spec, Xq, f, Z)) < 1e-10
        assert _rel(pb.grad, cross_grad_matvec(spec, Xq, f, Z)) < 1e-10


# ---------------------------------------------------------------------------
# Precision policy: bf16 storage / f32 accumulation
# ---------------------------------------------------------------------------

def test_precision_resolution():
    assert backend.resolve_precision() in ("f32", "bf16")
    with use_precision("bf16"):
        assert backend.resolve_precision() == "bf16"
        assert backend.stream_dtype() == jnp.bfloat16
    assert backend.stream_dtype("f32") == jnp.float32
    with pytest.raises(ValueError):
        backend.set_precision("fp8")
    with pytest.raises(ValueError):
        backend.stream_dtype("f16")


@pytest.mark.parametrize("name", ["rbf", "expdot"])
def test_posterior_batch_bf16_tracks_f32(name, rng):
    """bf16 streams track the f32 query pipeline to ~storage precision."""
    n, q, d = 6, 9, 512
    spec = get_kernel(name)
    c = None if spec.is_stationary else jnp.full((d,), 0.01, jnp.float32)
    X = jax.random.normal(jax.random.fold_in(rng, 1), (n, d), jnp.float32)
    Z = jax.random.normal(jax.random.fold_in(rng, 2), (n, d), jnp.float32)
    Xq = 0.3 * jax.random.normal(jax.random.fold_in(rng, 3), (q, d),
                                 jnp.float32)
    f = build_factors(spec, X, lam=1.0 / d, c=c)
    pb32 = posterior_batch(spec, Xq, f, Z, precision="f32")
    pb16 = posterior_batch(spec, Xq, f, Z, precision="bf16")
    assert pb16.grad.dtype == jnp.float32   # outputs never round to bf16
    # end-to-end error is storage quantization (~1e-3) amplified by the
    # kernel nonlinearity and value-sum cancellation — the KERNEL-level
    # <=1e-3 contract (same stored data) is gated in test_kernels_pallas
    assert _rel(pb16.value, pb32.value) < 3e-2
    assert _rel(pb16.grad, pb32.grad) < 1e-2


def test_state_caches_bf16_stream_copies(rng):
    d = 32
    X = jax.random.normal(jax.random.fold_in(rng, 1), (5, d))
    G = jax.random.normal(jax.random.fold_in(rng, 2), (5, d))
    st = GPGState.from_data("rbf", X, G, lam=1.0 / d, noise=1e-8,
                            precision="bf16")
    f1, z1 = st.stream_factors
    assert f1.Xt.dtype == jnp.bfloat16
    assert z1.dtype != jnp.bfloat16         # Z is a solve output: NEVER bf16
    assert f1.shift is not None             # stationary: spread-scale coords
    f2, z2 = st.stream_factors
    assert f2.Xt is f1.Xt and z2 is z1      # cached per revision
    st.extend(X[0] + 0.1, G[0])
    f3, _ = st.stream_factors
    assert f3.Xt is not f1.Xt               # revision bumped -> fresh copies
    # posterior means off the bf16 stream track the f32 state
    st32 = GPGState.from_data("rbf", st.X, st.G, lam=1.0 / d, noise=1e-8)
    pb16 = st.posterior(X[:3])
    pb32 = st32.posterior(X[:3])
    assert _rel(pb16.grad, pb32.grad) < 2e-2
    assert _rel(pb16.value, pb32.value) < 2e-2


def test_bf16_clustered_window_no_cancellation_blowup(rng):
    """The failure mode that forced both precision rules (DESIGN 12.2):
    an optimizer-style CLUSTERED window (spread 0.05 at |x| ~ sqrt(D))
    has |Z| >> |grad| and r/m assembled from near-equal norms.  Naive
    bf16 storage (absolute coords + quantized Z) measured ~12% grad
    error here; the shipped policy (shifted coords, f32 Z) must stay at
    storage precision."""
    from repro.configs.paper_gp import GPServeConfig
    from repro.train.serve import build_gp_serve_step

    d = 1024
    key = jax.random.fold_in(rng, 77)
    fobj = lambda x: jnp.sum(jnp.sin(x) * jnp.roll(x, 1)) / d
    gf = jax.grad(fobj)
    st = GPGState("rbf", d=d, window=6, lam=1.0 / d, noise=1e-8,
                  dtype=jnp.float32)
    x = jax.random.normal(key, (d,), jnp.float32)
    for s in range(7):
        st.extend(x, gf(x))
        x = x + 0.05 * jax.random.normal(jax.random.fold_in(key, s), (d,),
                                         jnp.float32)
    Xq = x[None] + 0.02 * jax.random.normal(jax.random.fold_in(key, 99),
                                            (9, d), jnp.float32)
    ref = st.posterior(Xq)
    srv16 = build_gp_serve_step(st, config=GPServeConfig(microbatch=4,
                                                         precision="bf16"))
    out = srv16.query(Xq)
    assert _rel(out.grad, ref.grad) < 1e-3, _rel(out.grad, ref.grad)
    assert _rel(out.value, ref.value) < 3e-2
    # the state's own posterior path (cached shifted stream) agrees too
    pb = st.posterior(Xq)
    assert _rel(pb.grad, ref.grad) < 1e-3


def test_bf16_dot_kernel_centers_before_cast(rng):
    """Dot-kernel twin of the clustered-window rule: with data near a
    large center c, queries must be centered BEFORE bf16 quantization on
    the pre-quantized (cached/serve) path too — cast-then-center loses
    |x|/|x-c| of the resolution the centered storage keeps."""
    from repro.core import build_factors

    n, q, d = 6, 5, 1024
    spec = get_kernel("expdot")
    c = 3.0 * jax.random.normal(jax.random.fold_in(rng, 9), (d,),
                                jnp.float32)
    X = c[None] + 0.05 * jax.random.normal(jax.random.fold_in(rng, 1),
                                           (n, d), jnp.float32)
    Z = jax.random.normal(jax.random.fold_in(rng, 2), (n, d), jnp.float32)
    Xq = c[None] + 0.05 * jax.random.normal(jax.random.fold_in(rng, 3),
                                            (q, d), jnp.float32)
    f = build_factors(spec, X, lam=1.0 / d, c=c)
    ref = _query_chunk(spec, Xq, f, Z, None)
    # the pre-quantized view the state/serve layers cache: centered bf16 Xt
    f16 = f._replace(Xt=f.Xt.astype(jnp.bfloat16))
    pb = _query_chunk(spec, Xq, f16, Z, None)
    assert _rel(pb.grad, ref.grad) < 5e-3, _rel(pb.grad, ref.grad)
    assert _rel(pb.value, ref.value) < 5e-3, _rel(pb.value, ref.value)
    # and the in-chunk quantization path agrees
    pb2 = _query_chunk(spec, Xq, f, Z, None, stream_dt=jnp.bfloat16)
    assert _rel(pb2.grad, ref.grad) < 5e-3


def test_serve_step_bf16_precision(rng):
    from repro.configs.paper_gp import GPServeConfig
    from repro.train.serve import build_gp_serve_step

    d = 24
    X = jax.random.normal(jax.random.fold_in(rng, 1), (4, d))
    G = jax.random.normal(jax.random.fold_in(rng, 2), (4, d))
    st = GPGState.from_data("rbf", X, G, lam=1.0 / d, noise=1e-8)
    ref = st.posterior(X)
    srv = build_gp_serve_step(st, config=GPServeConfig(microbatch=2,
                                                       precision="bf16"))
    assert st.precision == "bf16"
    out = srv.query(X)
    assert _rel(out.grad, ref.grad) < 5e-3


# ---------------------------------------------------------------------------
# Serving-layer LRU solver cache
# ---------------------------------------------------------------------------

def test_serve_solver_cache_is_bounded_lru(rng):
    from repro.train.serve import GPServeBundle, build_gp_serve_step

    d = 16
    X = jax.random.normal(jax.random.fold_in(rng, 1), (4, d))
    G = jax.random.normal(jax.random.fold_in(rng, 2), (4, d))
    st = GPGState.from_data("rbf", X, G, lam=1.0 / d, noise=1e-6)
    srv = build_gp_serve_step(st, microbatch=2, return_std=True)
    s0 = srv.refresh_solver()
    assert srv.refresh_solver() is s0          # hit on unchanged revision
    for i in range(2 + GPServeBundle._SOLVER_CACHE_MAX):
        st.extend(X[0] + 0.01 * (i + 1), G[0])  # new revision each time
        srv.refresh_solver()
        assert len(srv._solver_cache) <= GPServeBundle._SOLVER_CACHE_MAX
    # the original (evicted) revision would need a rebuild; current hits
    s_now = srv.refresh_solver()
    assert srv.refresh_solver() is s_now
