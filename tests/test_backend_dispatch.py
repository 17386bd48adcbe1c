"""Backend-dispatch layer: resolution rules, pallas/jnp parity through the
full solver stack, single-launch guarantee for the fused MVM, and the
no-raw-hot-path source contract for the exact/iterative solvers."""
import pathlib

import jax
import jax.numpy as jnp
import pytest

from repro.core import (build_factors, get_kernel, gram_cg_solve,
                        gram_cg_solve_multi, gram_matvec, gram_matvec_multi,
                        resolve_backend, set_backend, use_backend,
                        woodbury_solve)
from repro.core import backend

SRC = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"


def _setup(name, rng, n=5, d=64, dtype=jnp.float64):
    spec = get_kernel(name)
    c = None if spec.is_stationary else \
        jax.random.normal(jax.random.fold_in(rng, 9), (d,), dtype) * 0.05
    X = jax.random.normal(jax.random.fold_in(rng, 1), (n, d), dtype)
    G = jax.random.normal(jax.random.fold_in(rng, 2), (n, d), dtype)
    return spec, X, G, c


def _f32(tree):
    """float32 copies of a pytree's float arrays: the pallas backend refuses
    float64 operands, while the jnp reference stays in float64."""
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32)
        if isinstance(a, jax.Array) and jnp.issubdtype(a.dtype, jnp.floating)
        else a, tree)


# ---------------------------------------------------------------------------
# Resolution
# ---------------------------------------------------------------------------

def test_resolution_order(monkeypatch):
    monkeypatch.delenv("REPRO_BACKEND", raising=False)
    assert resolve_backend() in ("jnp", "pallas")
    if jax.default_backend() != "tpu":
        assert resolve_backend() == "jnp"
    monkeypatch.setenv("REPRO_BACKEND", "pallas")
    assert resolve_backend() == "pallas"
    with use_backend("jnp"):
        assert resolve_backend() == "jnp"  # explicit beats env
    assert resolve_backend() == "pallas"
    monkeypatch.delenv("REPRO_BACKEND")
    with pytest.raises(ValueError):
        set_backend("tpu-magic")
    # an invalid env value is outside input: rejected, never ignored
    monkeypatch.setenv("REPRO_BACKEND", "tpu-magic")
    with pytest.raises(ValueError, match="REPRO_BACKEND"):
        resolve_backend()
    with use_backend("jnp"):
        assert resolve_backend() == "jnp"  # an explicit choice still wins
    monkeypatch.setenv("REPRO_BACKEND", "")
    assert resolve_backend() in ("jnp", "pallas")  # empty == unset


def test_pallas_refuses_float64(rng):
    """A float64 operand never reaches a Pallas call: the dispatcher names
    the op and the dtype instead of downcasting or rerouting to jnp."""
    A = jax.random.normal(jax.random.fold_in(rng, 1), (5, 64), jnp.float64)
    with use_backend("pallas"):
        with pytest.raises(TypeError, match="scaled_gram.*float64"):
            backend.scaled_gram(A, A, 0.5)
        got = backend.scaled_gram(A.astype(jnp.float32),
                                  A.astype(jnp.float32), 0.5)
    assert got.dtype == jnp.float32
    with use_backend("jnp"):
        assert backend.scaled_gram(A, A, 0.5).dtype == jnp.float64


# ---------------------------------------------------------------------------
# Parity: the same solves through the pallas kernel path (interpret on CPU)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["rbf", "expdot"])
@pytest.mark.parametrize("lam_kind", ["scalar", "diag"])
def test_gram_matvec_parity(name, lam_kind, rng):
    d = 64
    spec, X, G, c = _setup(name, rng, d=d)
    lam = 0.5 if lam_kind == "scalar" else \
        jnp.abs(jax.random.normal(jax.random.fold_in(rng, 3), (d,))) + 0.2
    noise = 0.0 if lam_kind == "diag" else 1e-2
    with use_backend("jnp"):
        f = build_factors(spec, X, lam=lam, c=c, noise=noise)
        want = gram_matvec(f, G, stationary=spec.is_stationary)
    with use_backend("pallas"):
        got = gram_matvec(_f32(f), _f32(G), stationary=spec.is_stationary)
    assert jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)) < 1e-5


@pytest.mark.parametrize("name", ["rbf", "expdot"])
def test_gram_cg_solve_parity(name, rng):
    spec, X, G, c = _setup(name, rng)
    with use_backend("jnp"):
        f = build_factors(spec, X, lam=0.5, c=c, noise=1e-6)
        want = gram_cg_solve(spec, f, G, tol=1e-6).x
    with use_backend("pallas"):
        got = gram_cg_solve(spec, _f32(f), _f32(G), tol=1e-6, maxiter=200).x
    # pallas path accumulates in f32; compare through the operator
    with use_backend("jnp"):
        rw = gram_matvec(f, got, stationary=spec.is_stationary) - G
    assert float(jnp.linalg.norm(rw) / jnp.linalg.norm(G)) < 1e-3
    assert jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)) < 1e-2


@pytest.mark.parametrize("name", ["rbf", "expdot"])
def test_woodbury_solve_parity(name, rng):
    spec, X, G, c = _setup(name, rng)
    with use_backend("jnp"):
        f = build_factors(spec, X, lam=0.5, c=c)
        want = woodbury_solve(spec, f, G)
    with use_backend("pallas"):
        got = woodbury_solve(spec, _f32(f), _f32(G))
    assert jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)) < 1e-3


def test_cg_multi_matches_single(rng):
    """Joint CG over stacked RHS == per-RHS solves (block-diag operator).

    x64-precision tolerances, so the jnp backend is pinned explicitly —
    the suite must also pass under an exported REPRO_BACKEND=pallas.
    """
    spec, X, G, c = _setup("rbf", rng)
    with use_backend("jnp"):
        f = build_factors(spec, X, lam=0.3, noise=1e-8)
        G2 = jax.random.normal(jax.random.fold_in(rng, 7), G.shape, G.dtype)
        Gs = jnp.stack([G, G2])
        zs = gram_cg_solve_multi(spec, f, Gs, tol=1e-10).x
        for i, g in enumerate([G, G2]):
            z = gram_cg_solve(spec, f, g, tol=1e-10).x
            assert jnp.max(jnp.abs(zs[i] - z)) / jnp.max(jnp.abs(z)) < 1e-6
        W = gram_matvec_multi(f, zs, stationary=spec.is_stationary)
        assert float(jnp.linalg.norm(W - Gs) / jnp.linalg.norm(Gs)) < 1e-8


# ---------------------------------------------------------------------------
# Single-launch guarantee
# ---------------------------------------------------------------------------

from repro.utils.hlo import count_primitive as _count_primitive


def test_single_pallas_call_per_mvm(rng):
    """One fused MVM == exactly one pallas_call in the compiled program."""
    spec, X, G, c = _setup("rbf", rng, d=256, dtype=jnp.float32)
    f = build_factors(spec, X, lam=0.5, noise=1e-3)
    with use_backend("pallas"):
        jaxpr = jax.make_jaxpr(
            lambda v: gram_matvec(f, v, stationary=True))(G)
    assert _count_primitive(jaxpr.jaxpr, "pallas_call") == 1

    with use_backend("pallas"):
        jaxpr = jax.make_jaxpr(
            lambda v: gram_matvec_multi(f, v, stationary=True))(
                jnp.stack([G, G]))
    assert _count_primitive(jaxpr.jaxpr, "pallas_call") == 1


# ---------------------------------------------------------------------------
# Source contract: no raw jnp O(ND) contraction left in the solver modules
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("module,forbidden", [
    ("core/solvers.py", ["K1i @ V", "(K1i @", "@ f.Xt", "f.Xt @", "/ f.lam"]),
    ("core/woodbury.py", ["W0 @", "@ f.Xt.T", "K1i @ G", "K1i @ (G",
                          "f.Xt @ Gt"]),
])
def test_no_raw_hot_path(module, forbidden):
    import re

    src = (SRC / module).read_text()
    # dense_solve is the documented O((ND)^3) test-only reference — exempt.
    src = src.split("def dense_solve", 1)[0]
    # the contract is about code, not the derivations in docstrings/comments
    src = re.sub(r'""".*?"""', "", src, flags=re.S)
    src = "\n".join(line.split("#", 1)[0] for line in src.splitlines())
    for pattern in forbidden:
        assert pattern not in src, (module, pattern)
    assert "backend." in src


def test_backend_vocabulary_parity(rng):
    """Every backend op agrees with its jnp form under the pallas backend."""
    d = 70
    A = jax.random.normal(jax.random.fold_in(rng, 1), (5, d))
    B = jax.random.normal(jax.random.fold_in(rng, 2), (7, d))
    lam = jnp.abs(jax.random.normal(jax.random.fold_in(rng, 3), (d,))) + 0.1
    spec = get_kernel("rbf")
    A32, B32 = _f32((A, B))
    with use_backend("pallas"):
        p_gram = backend.scaled_gram(A32, B32, lam)
        p_r = backend.pairwise_r(spec, A32, B32, lam)
        p_norms = backend.gram_norms(A32, B32, lam)
    with use_backend("jnp"):
        j_gram = backend.scaled_gram(A, B, lam)
        j_r = backend.pairwise_r(spec, A, B, lam)
        j_norms = backend.gram_norms(A, B, lam)
    assert jnp.allclose(p_gram, j_gram, rtol=1e-5, atol=1e-5)
    assert jnp.allclose(p_r, j_r, rtol=1e-5, atol=1e-5)
    for p, j in zip(p_norms, j_norms):
        assert jnp.allclose(p, j, rtol=1e-5, atol=1e-5)
