"""D-sharded incremental state machine (core/dist_state.py): parity with
the single-device GPGState, psum-count jaxpr gates, per-shard single-X-
stream gates, and the sharded gp_precond optimizer step.

Host-process tests run on the 1-device contract (a 1-device mesh exercises
the identical shard_map programs); real 8-fake-device parity — including
uneven shards (D % devices != 0), ring/pipelined queries and the
collective-bytes model — runs in a subprocess with
``xla_force_host_platform_device_count=8`` (same pattern as
tests/test_distributed.py).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.core import GPGState, ShardedGPGState, get_kernel
from repro.core.dist_state import PHASE_PSUMS, psum_bytes
from repro.hyper import HyperParams, mll, mll_from_strips, strips_for_mll
from repro.utils.hlo import count_data_streams, count_psums

KERNELS = ["rbf", "expdot"]


def _mk(rng, n, d, seed=0):
    X = jax.random.normal(jax.random.fold_in(rng, seed + 1), (n, d))
    G = jax.random.normal(jax.random.fold_in(rng, seed + 2), (n, d))
    return X, G


# ---------------------------------------------------------------------------
# Strips-based MLL (hyper/mll.py) — replicated-evidence parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["rbf", "expdot", "poly2"])
def test_mll_from_strips_matches_mll(name, rng):
    n, d = 6, 24
    spec = get_kernel(name)
    X, G = _mk(rng, n, d)
    lam = 0.5 if spec.is_stationary else 0.5 / d
    h = HyperParams.from_lam(jnp.asarray(lam), signal=1.3, noise=1e-4)
    ref = mll(spec, X, G, h)
    S0, C, GG = strips_for_mll(X, G)
    got = mll_from_strips(spec, S0, C, GG, d, h)
    assert jnp.abs(got - ref) / (jnp.abs(ref) + 1.0) < 1e-8

    # value AND gradient parity (the refit path differentiates this)
    def f_ref(lam_):
        return mll(spec, X, G, HyperParams.from_lam(lam_, signal=1.3,
                                                    noise=1e-4))

    def f_strips(lam_):
        return mll_from_strips(spec, S0, C, GG, d,
                               HyperParams.from_lam(lam_, signal=1.3,
                                                    noise=1e-4))

    g_ref = jax.grad(f_ref)(jnp.asarray(lam))
    g_got = jax.grad(f_strips)(jnp.asarray(lam))
    assert jnp.abs(g_got - g_ref) / (jnp.abs(g_ref) + 1.0) < 1e-6


@pytest.mark.parametrize("name", KERNELS)
def test_mll_from_strips_padded_count(name, rng):
    """Padded strip rows (count < cap) are exactly inert."""
    n, cap, d = 4, 7, 16
    spec = get_kernel(name)
    X, G = _mk(rng, n, d, seed=3)
    lam = 0.4 if spec.is_stationary else 0.4 / d
    h = HyperParams.from_lam(jnp.asarray(lam), signal=1.0, noise=1e-5)
    S0, C, GG = strips_for_mll(X, G)
    pad = ((0, cap - n), (0, cap - n))
    got = mll_from_strips(spec, jnp.pad(S0, pad), jnp.pad(C, pad),
                          jnp.pad(GG, pad), d, h, count=n)
    ref = mll_from_strips(spec, S0, C, GG, d, h)
    assert jnp.abs(got - ref) < 1e-10 * (1.0 + jnp.abs(ref))


# ---------------------------------------------------------------------------
# Kernel-launch geometry: _pick_block_d sizes against the LOCAL shard
# ---------------------------------------------------------------------------


def test_pick_block_d_shard_aware():
    from repro.kernels.ops import _pick_block_d, use_data_shards

    d = 4096
    whole = _pick_block_d(d)
    sharded = _pick_block_d(d, shards=8)
    # one grid step over the 512-wide local shard, not the global D
    assert sharded == _pick_block_d(512)
    assert sharded <= whole
    with use_data_shards(8):
        assert _pick_block_d(d) == sharded
    assert _pick_block_d(d) == whole          # context restored


# ---------------------------------------------------------------------------
# 1-device-mesh parity: the same shard_map programs, exact expectations
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", KERNELS)
def test_sharded_state_matches_unsharded_1dev(name, rng):
    from repro.launch.mesh import make_d_mesh

    d, window, steps = 12, 4, 7
    spec = get_kernel(name)
    lam = 0.6 if spec.is_stationary else 0.6 / d
    kw = dict(window=window, lam=lam, noise=1e-6)
    st = ShardedGPGState(name, d, mesh=make_d_mesh(), **kw)
    ref = GPGState(name, d, tol=1e-12, **kw)
    X, G = _mk(rng, steps, d, seed=11)
    Xq, _ = _mk(rng, 3, d, seed=17)
    for i in range(steps):
        st.extend(X[i], G[i])
        ref.extend(X[i], G[i])
        assert jnp.max(jnp.abs(st.Z - ref.Z)) < 1e-6
    pb, pr = st.posterior(Xq), ref.posterior(Xq)
    assert jnp.max(jnp.abs(pb.value - pr.value)) < 1e-6
    assert jnp.max(jnp.abs(pb.grad - pr.grad)) < 1e-6
    # evict + resolve parity
    st.evict(); ref.evict()
    rhs = jax.random.normal(jax.random.fold_in(rng, 23), (st.n, d))
    Zs = st.resolve(rhs)
    Zr = ref.resolve(rhs)
    assert jnp.max(jnp.abs(Zs - Zr[: st.n])) < 1e-6


def test_sharded_refit_matches_unsharded(rng):
    d, n = 10, 6
    X, G = _mk(rng, n, d, seed=31)
    st = ShardedGPGState.from_data("rbf", X, G, lam=0.5, noise=1e-4)
    ref = GPGState.from_data("rbf", X, G, lam=0.5, noise=1e-4, tol=1e-12)
    m0 = st.mll()
    assert jnp.abs(m0 - ref.mll()) / (jnp.abs(m0) + 1.0) < 1e-6
    rs = st.refit(steps=40)
    rr = ref.refit(steps=40)
    assert jnp.abs(rs.hypers.lam - rr.hypers.lam) / rr.hypers.lam < 1e-4
    assert jnp.abs(st.mll() - ref.mll()) / (jnp.abs(m0) + 1.0) < 1e-5


# ---------------------------------------------------------------------------
# The jaxpr gates: at most ONE psum per phase, one local X stream per solve
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", KERNELS)
def test_phase_psum_counts(name, rng):
    """Every compiled phase program issues EXACTLY the collective count of
    the PHASE_PSUMS contract (the fused-psum invariant, jaxpr-level)."""
    from repro.launch.mesh import make_d_mesh

    d = 12
    st = ShardedGPGState(name, d, window=4, mesh=make_d_mesh(),
                         lam=0.5, noise=1e-6)
    x = jnp.zeros((st.d_pad,))
    g = jnp.zeros((st.d_pad,))
    rhs = jnp.zeros((st.data.capacity, st.d_pad))
    nz = jnp.asarray(1e-6)
    lam = jnp.asarray(0.5)
    cases = {
        "extend": ((st.data, x, g, nz), PHASE_PSUMS["extend"]),
        "evict": ((st.data, nz), PHASE_PSUMS["evict"]),
        "refactor": ((st.data, lam, nz), PHASE_PSUMS["refactor"]),
        "resolve": ((st.data, rhs, nz), PHASE_PSUMS["resolve"]),
        "rebuild": ((st.data, nz), PHASE_PSUMS["rebuild"]),
    }
    for phase, (args, want) in cases.items():
        st._phase(phase)  # build (and cache) the program
        raw = st._fns[phase]
        fn = getattr(raw, "fn", raw)      # unwrap CompileWatch if obs on
        jx = jax.make_jaxpr(fn)(*args)
        assert count_psums(jx) == want, (phase, count_psums(jx), want)
    jq = jax.make_jaxpr(st._query_raw(3))(st.data, jnp.zeros((3, st.d_pad)))
    assert count_psums(jq) == PHASE_PSUMS["query"]


def test_solve_single_local_x_stream(rng):
    """Per shard, one solve = ONE reduction stream of the local Xt shard
    (the extend border) + the ONE output-assembly expansion stream (the
    taint-walk teeth of DESIGN.md sec. 12, applied per-shard)."""
    from jax.sharding import PartitionSpec as P

    from repro.core.dist_state import sgpg_extend
    from repro.launch.mesh import make_d_mesh

    n, d = 5, 256                 # (cap, cap) psum outputs stay < d_loc
    spec = get_kernel("rbf")
    mesh = make_d_mesh()
    names = tuple(mesh.axis_names)
    st = ShardedGPGState("rbf", d, window=n, mesh=mesh, lam=0.3, noise=1e-6)
    X, G = _mk(rng, n - 1, d, seed=41)
    for i in range(n - 1):
        st.extend(X[i], G[i])
    data = st.data
    x, g = _mk(rng, 1, d, seed=47)

    def fn(Xt, x, g, data):
        d2 = data._replace(base=data.base._replace(Xt=Xt))
        out, _ = sgpg_extend(spec, d2, x, g, axis_names=names, noise=1e-6,
                             solve=True)
        return out.base.Z

    # the mesh-sharded state rides in as an argument: shard_map refuses
    # sharded arrays closed over from outside the map
    sm = jax.shard_map(fn, mesh=mesh,
                       in_specs=(P(None, names), P(names), P(names),
                                 st._data_spec()),
                       out_specs=P(None, names), check_vma=False)
    closed = jax.make_jaxpr(sm)(data.base.Xt, x[0], g[0], data)
    d_loc = d // mesh.size
    streams = count_data_streams(closed, 0, d_loc)
    assert streams == {"reduction": 1, "expansion": 1}, streams


def test_gp_precond_sharded_psum_budget():
    """The whole sharded training step is <= 3 fused psums in every mode
    (extend border, direction reductions, trust-region scalars)."""
    from repro.launch.mesh import make_d_mesh
    from repro.optim.gp_precond import gp_precond

    mesh = make_d_mesh()
    params = {"w": jnp.zeros((13,), jnp.float32)}
    grads = {"w": jnp.ones((13,), jnp.float32)}
    for mode in ("gph", "gpx"):
        for kern in KERNELS:
            for rmode in ("heuristic", "mll"):
                opt = gp_precond(mode=mode, kernel=kern, refresh_mode=rmode,
                                 history=4, mesh=mesh)
                st = opt.init(params)
                jx = jax.make_jaxpr(opt.update)(grads, st, params)
                got = count_psums(jx)
                assert got <= 3, (mode, kern, rmode, got)


def test_gp_precond_sharded_matches_unsharded_1dev(rng):
    """Short-trajectory parity of the sharded optimizer against the classic
    one (well-conditioned configs; the exact strips solve replaces CG, so
    the tolerance is solver-level, not bitwise)."""
    from repro.launch.mesh import make_d_mesh
    from repro.optim.gp_precond import gp_precond

    d = 11
    A = jax.random.normal(jax.random.fold_in(rng, 51), (d, d)) * 0.3 \
        + jnp.eye(d)
    H = A @ A.T

    def loss(p):
        return 0.5 * p["w"] @ H @ p["w"]

    mesh = make_d_mesh()
    for mode, kern in [("gph", "rbf"), ("gpx", "rbf"), ("gpx", "expdot")]:
        kw = dict(mode=mode, kernel=kern, history=4, refresh_every=3,
                  noise=1e-5, fallback_lr=0.05, max_step_rms=0.05)
        o0 = gp_precond(**kw, cg_tol=1e-12)
        o1 = gp_precond(**kw, mesh=mesh)
        p0 = {"w": jax.random.normal(jax.random.fold_in(rng, 53), (d,))}
        p1 = {"w": p0["w"]}
        s0, s1 = o0.init(p0), o1.init(p1)
        u0, u1 = jax.jit(o0.update), jax.jit(o1.update)
        for _ in range(7):
            g0 = jax.grad(loss)(p0)
            g1 = jax.grad(loss)(p1)
            p0, s0 = u0(g0, s0, p0)
            p1, s1 = u1(g1, s1, p1)
        dw = float(jnp.max(jnp.abs(p0["w"] - p1["w"])))
        assert dw < 5e-3, (mode, kern, dw)


def test_sharded_phase_compile_stability():
    """extend / evict / refactor never retrace: count and noise are traced
    arguments, so a refit or a shrinking window reuses the executable."""
    from repro.launch.mesh import make_d_mesh
    from repro.obs import trace as _obs

    _obs.set_enabled(True)
    try:
        st = ShardedGPGState("rbf", 8, window=3, mesh=make_d_mesh(),
                             lam=0.5, noise=1e-6)
        key = jax.random.PRNGKey(7)
        for i in range(6):      # wraps the window -> evict + extend mix
            x = jax.random.normal(jax.random.fold_in(key, 2 * i), (8,))
            g = jax.random.normal(jax.random.fold_in(key, 2 * i + 1), (8,))
            st.extend(x, g)
        st.refit(steps=5)       # changes lam AND noise
        x = jax.random.normal(jax.random.fold_in(key, 99), (8,))
        st.extend(x, x)
        for name, fn in st._fns.items():
            fn.assert_stable()
            assert fn.n_compiles() == 1, (name, fn.n_compiles())
    finally:
        _obs.set_enabled(None)


def _dot_precisions(jaxpr) -> list:
    """``precision`` of every dot_general in a jaxpr, sub-jaxprs included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(eqn.params["precision"])
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out += _dot_precisions(inner)
    return out


def test_sharded_phases_trace_f32_matmuls_at_highest():
    """Every matmul of the sharded phase and query programs is traced at
    HIGHEST precision: a TPU otherwise contracts float32 in one bf16 pass,
    and the direct solve has no CG step to correct it."""
    from repro.launch.mesh import make_d_mesh

    st = ShardedGPGState("rbf", 8, window=3, mesh=make_d_mesh(), lam=0.5,
                         noise=1e-6, dtype=jnp.float32)
    key = jax.random.PRNGKey(5)
    for i in range(2):
        x = jax.random.normal(jax.random.fold_in(key, i), (8,), jnp.float32)
        st.extend(x, jnp.sin(x))
    nz = jnp.asarray(st._noise_eff)
    xp = st._pad_cols(x)
    programs = {
        "extend": (st._phase_raw("extend"), (st.data, xp, xp, nz)),
        "evict": (st._phase_raw("evict"), (st.data, nz)),
        "refactor": (st._phase_raw("refactor"),
                     (st.data, jnp.asarray(0.3, jnp.float32), nz)),
        "query": (st._query_raw(2), (st.data, jnp.stack([xp, xp]))),
    }
    highest = (jax.lax.Precision.HIGHEST,) * 2
    for phase, (fn, args) in programs.items():
        precisions = _dot_precisions(jax.make_jaxpr(fn)(*args).jaxpr)
        assert precisions, phase
        assert all(p == highest for p in precisions), (phase, precisions)


def test_psum_bytes_model_sanity():
    assert psum_bytes("extend", cap=6) == 4 * 2 * 2 * 6
    assert psum_bytes("extend", cap=6, with_rhs=True) == 4 * (24 + 36)
    assert psum_bytes("resolve", cap=6) == 4 * 36
    assert psum_bytes("rebuild", cap=6) == 3 * 4 * 36
    assert psum_bytes("query", cap=6, q=4) == 4 * (2 * 4 * 6 + 4 + 2 * 6)
    for ph in ("evict", "refactor", "solve", "refit"):
        assert psum_bytes(ph, cap=6) == 0
    # the claim itself: NEVER a function of D (no d parameter exists)


# ---------------------------------------------------------------------------
# Real 8-fake-device parity (subprocess; uneven shards included)
# ---------------------------------------------------------------------------

_SUBPROCESS_SRC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core import GPGState, ShardedGPGState, get_kernel
from repro.core.dist_state import PHASE_PSUMS, psum_bytes
from repro.core.distributed import ring_psum
from repro.launch.mesh import make_d_mesh
from repro.utils.hlo import collective_bytes, count_psums

mesh = make_d_mesh()
assert mesh.size == 8, mesh
failures = []
key = jax.random.PRNGKey(0)

def mk(n, d, seed):
    return (jax.random.normal(jax.random.fold_in(key, seed), (n, d)),
            jax.random.normal(jax.random.fold_in(key, seed + 1), (n, d)))

# full trajectory parity: extend -> evict -> posterior -> refit -> resolve,
# even (D=64) and UNEVEN (D=61, 61 % 8 != 0) shards, both kernel families
for kern in ("rbf", "expdot"):
    for d in (64, 61):
        spec = get_kernel(kern)
        lam = 0.6 if spec.is_stationary else 0.6 / d
        window, steps = 4, 6
        st = ShardedGPGState(kern, d, window=window, mesh=mesh, lam=lam,
                             noise=1e-6)
        ref = GPGState(kern, d, window=window, lam=lam, noise=1e-6,
                       tol=1e-12)
        X, G = mk(steps, d, 100 + d)
        for i in range(steps):
            st.extend(X[i], G[i]); ref.extend(X[i], G[i])
            e = float(jnp.max(jnp.abs(st.Z - ref.Z)))
            if e > 1e-5: failures.append((kern, d, "extend", i, e))
        Xq, _ = mk(3, d, 200 + d)
        pb, pr = st.posterior(Xq), ref.posterior(Xq)
        ev = float(jnp.max(jnp.abs(pb.value - pr.value)))
        eg = float(jnp.max(jnp.abs(pb.grad - pr.grad)))
        if max(ev, eg) > 1e-5: failures.append((kern, d, "posterior", ev, eg))
        rs = st.refit(steps=30); rr = ref.refit(steps=30)
        el = abs(float(rs.hypers.lam - rr.hypers.lam)) / float(rr.hypers.lam)
        if el > 1e-4: failures.append((kern, d, "refit", el))
        e = float(jnp.max(jnp.abs(st.Z - ref.Z)))
        if e > 1e-5: failures.append((kern, d, "refit-Z", e))
        st.evict(); ref.evict()
        rhs, _ = mk(st.n, d, 300 + d)
        Zs = st.resolve(rhs)
        Zr = ref.resolve(rhs)
        e = float(jnp.max(jnp.abs(Zs - Zr[: st.n])))
        if e > 1e-5: failures.append((kern, d, "resolve", e))

# ring_psum == psum (ppermute ring reduction): each device holds a (3,)
# shard; the ring all-reduce must equal the cross-device sum, replicated
x = jnp.arange(8.0 * 3)
names = tuple(mesh.axis_names)
ring = jax.shard_map(lambda v: ring_psum(v, names[0], 8),
                     mesh=mesh, in_specs=(P(names),), out_specs=P(),
                     check_vma=False)(x)
if float(jnp.max(jnp.abs(ring - x.reshape(8, 3).sum(0)))) > 1e-12:
    failures.append(("ring_psum", ring))

# pipelined (ppermute-overlapped) query == plain fused-psum query
st = ShardedGPGState("rbf", 64, window=4, mesh=mesh, lam=0.6, noise=1e-6)
X, G = mk(4, 64, 400)
for i in range(4):
    st.extend(X[i], G[i])
Xq, _ = mk(6, 64, 500)
p0 = st.posterior(Xq)
p1 = st.posterior(Xq, chunks=3)
if float(jnp.max(jnp.abs(p0.value - p1.value))) > 1e-10 or \
   float(jnp.max(jnp.abs(p0.grad - p1.grad))) > 1e-10:
    failures.append(("pipelined-query",))

# jaxpr psum gates on the REAL 8-device mesh + measured collective bytes
# vs the O(N^2) analytic model at two D values (D-independence)
vols = {}
for d in (64, 128):
    st = ShardedGPGState("rbf", d, window=4, mesh=mesh, lam=0.6, noise=1e-6)
    cap = st.data.capacity
    x = jnp.zeros((st.d_pad,)); nz = jnp.asarray(1e-6)
    st._phase("extend")
    fn = getattr(st._fns["extend"], "fn", st._fns["extend"])
    jx = jax.make_jaxpr(fn)(st.data, x, x, nz)
    if count_psums(jx) != PHASE_PSUMS["extend"]:
        failures.append(("gate-extend", count_psums(jx)))
    hlo = jax.jit(fn).lower(st.data, x, x, nz).compile().as_text()
    vols[d] = collective_bytes(hlo)
    itemsize = jnp.dtype(st.data.base.X.dtype).itemsize
    want = psum_bytes("extend", cap=cap, itemsize=itemsize)
    if vols[d] != want:
        failures.append(("bytes-extend", d, vols[d], want))
if vols[64] != vols[128]:
    failures.append(("bytes-D-dependent", vols))

# sharded gp_precond on the real mesh vs the classic optimizer
from repro.optim.gp_precond import gp_precond
d = 24
A = jax.random.normal(jax.random.fold_in(key, 900), (d, d)) * 0.3 + jnp.eye(d)
H = A @ A.T
loss = lambda p: 0.5 * p["w"] @ H @ p["w"]
kw = dict(mode="gpx", kernel="rbf", history=4, refresh_every=3, noise=1e-5,
          fallback_lr=0.05, max_step_rms=0.05)
o0 = gp_precond(**kw, cg_tol=1e-12)
o1 = gp_precond(**kw, mesh=mesh)
p0 = {"w": jax.random.normal(jax.random.fold_in(key, 901), (d,))}
p1 = {"w": p0["w"]}
s0, s1 = o0.init(p0), o1.init(p1)
u0, u1 = jax.jit(o0.update), jax.jit(o1.update)
for _ in range(7):
    g0 = jax.grad(loss)(p0); g1 = jax.grad(loss)(p1)
    p0, s0 = u0(g0, s0, p0)
    p1, s1 = u1(g1, s1, p1)
dw = float(jnp.max(jnp.abs(p0["w"] - p1["w"])))
if dw > 5e-3:
    failures.append(("gp_precond", dw))

assert not failures, failures
print("SUBPROCESS_OK")
"""


def test_sharded_state_parity_8dev():
    r = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_SRC],
        capture_output=True, text=True, timeout=900,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")})
    assert "SUBPROCESS_OK" in r.stdout, r.stdout + r.stderr
