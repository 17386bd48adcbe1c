"""Resilience subsystem tests (DESIGN.md sec. 17).

Deterministic coverage of the fault-tolerance stack:

  * typed error taxonomy + admission guardrails (single / fleet paths);
  * the jitter-escalation ladder healing a poisoned Cholesky;
  * the CG-divergence watchdog falling back to the exact solver;
  * the bf16-drift trip-wire re-casting from the f32 masters;
  * snapshot/restore roundtrips for all three state flavors (fleet
    elastic repack included; sharded same-mesh in a subprocess);
  * the op journal (torn tail vs torn interior, digest verification);
  * serve-loop hardening: shedding, deadlines, bounded retry, quarantine,
    degraded queries;
  * the zero-cost contract: guardrails on/off leave the serve jaxprs
    byte-identical.

The randomized crash/restore trajectories live in
tests/test_property_invariants.py (hypothesis over fuzz_machine's
``check_recovery_*``); this file is the always-on pinned suite.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fleet import GPFleet
from repro.core.state import GPGState
from repro.obs.trace import counter_value, use_obs
from repro.resilience import (ChaosInjector, Journal, errors, guardrails,
                              replay_single, restore, take_snapshot)
from repro.runtime.recovery import SimulatedFailure
from repro.train.serve import GPFleetServer, build_gp_serve_step


def _mk_state(d=4, window=4, **kw):
    kw.setdefault("noise", 1e-6)
    st = GPGState("rbf", d, window=window, **kw)
    r = np.random.RandomState(0)
    for _ in range(3):
        st.extend(r.randn(d), r.randn(d))
    return st


# ---------------------------------------------------------------------------
# Error taxonomy
# ---------------------------------------------------------------------------


def test_error_taxonomy_types():
    """Every typed failure is a ResilienceError; the two compatibility
    bridges (ValueError / NotImplementedError) hold for legacy callers."""
    assert issubclass(errors.NonFiniteObservationError, errors.ResilienceError)
    assert issubclass(errors.NonFiniteObservationError, ValueError)
    assert issubclass(errors.UnsupportedQueryError, NotImplementedError)
    for name in ("DeadlineExceededError", "QueueOverloadError",
                 "RetryExhaustedError", "TenantQuarantinedError",
                 "JournalCorruptionError"):
        assert issubclass(getattr(errors, name), errors.ResilienceError)
    shed = errors.ShedResponse(reason="queue_full", queue_depth=9)
    assert shed.queue_depth == 9 and not isinstance(shed, Exception)


# ---------------------------------------------------------------------------
# Admission guardrails
# ---------------------------------------------------------------------------


def test_single_state_rejects_nonfinite_admission():
    st = _mk_state()
    before = np.asarray(st.data.L).copy()
    x = np.ones(4)
    x[2] = np.nan
    with pytest.raises(errors.NonFiniteObservationError):
        st.extend(x, np.ones(4))
    # the poison never touched a factor
    assert np.array_equal(np.asarray(st.data.L), before)
    assert st.n == 3


def test_fleet_rejects_nonfinite_admission():
    fl = GPFleet("rbf", d=3, batch=2, window=4)
    fl.join("a")
    fl.join("b")
    fl.extend({"a": (np.ones(3), np.ones(3))})
    bad = np.array([1.0, np.inf, 0.0])
    with pytest.raises(errors.NonFiniteObservationError):
        fl.extend({"a": (np.ones(3), np.ones(3)), "b": (bad, np.ones(3))})
    assert fl.n("a") == 1 and fl.n("b") == 0   # whole group rejected


def test_guardrails_disabled_admits_anything():
    with guardrails.use_guardrails(False):
        st = _mk_state()
        x = np.ones(4)
        x[0] = np.nan
        st.extend(x, np.ones(4))        # no admission check: NaN goes in
        assert st.n == 4


KINDS = ("numpy", "device", "mixed")


def _payload(kind, x, g):
    """(x, g) as the input kind: numpy arrays, arrays on the default
    device, or a device x beside a numpy g."""
    if kind == "numpy":
        return np.asarray(x), np.asarray(g)
    if kind == "device":
        return jnp.asarray(x), jnp.asarray(g)
    return jnp.asarray(x), np.asarray(g)


@pytest.mark.parametrize("where", ["x", "g"])
@pytest.mark.parametrize("bad", [np.nan, np.inf])
@pytest.mark.parametrize("kind", KINDS)
def test_extend_rejects_nonfinite_by_input_kind(kind, bad, where):
    st = _mk_state()
    L, n = np.asarray(st.data.L).copy(), st.n
    x, g = np.ones(4), np.ones(4)
    (x if where == "x" else g)[1] = bad
    rejected = counter_value("resilience.rejected_nonfinite")
    with pytest.raises(errors.NonFiniteObservationError):
        st.extend(*_payload(kind, x, g))
    assert np.array_equal(np.asarray(st.data.L), L) and st.n == n
    assert counter_value("resilience.rejected_nonfinite") == rejected + 1


@pytest.mark.parametrize("kind", KINDS)
def test_fleet_and_server_reject_nonfinite_by_input_kind(kind):
    bad = np.array([1.0, np.nan, 0.0])
    fl = GPFleet("rbf", d=3, batch=2, window=4)
    fl.join("a")
    fl.join("b")
    with pytest.raises(errors.NonFiniteObservationError, match="'b'"):
        fl.extend({"a": _payload(kind, np.ones(3), np.ones(3)),
                   "b": _payload(kind, bad, np.ones(3))})
    assert fl.n("a") == 0 and fl.n("b") == 0

    srv = _server()
    req = srv.submit("t0", "extend", _payload(kind, bad, np.ones(3)))
    assert req.done
    assert isinstance(req.result, errors.NonFiniteObservationError)
    assert "'t0'" in str(req.result)


def test_check_finite_bf16_device_array():
    a = jnp.array([1.0, 2.0, 3.0], dtype=jnp.bfloat16)
    guardrails.check_finite(a)
    for bad in (jnp.nan, jnp.inf):
        with pytest.raises(errors.NonFiniteObservationError):
            guardrails.check_finite(a.at[1].set(bad))


@pytest.mark.parametrize("kind", KINDS)
def test_guardrails_off_launches_nothing(kind, monkeypatch):
    launched = []
    monkeypatch.setattr(guardrails, "_all_finite",
                        lambda arrays: launched.append(arrays))
    x = np.ones(4)
    x[0] = np.nan
    with guardrails.use_guardrails(False):
        guardrails.check_finite(*_payload(kind, x, np.ones(4)))
    assert launched == []


@pytest.mark.parametrize("kind", KINDS)
def test_admission_counters_count_the_path(kind):
    names = ("resilience.admission.device", "resilience.admission.host")
    before = [counter_value(k) for k in names]
    with use_obs(True):
        guardrails.check_finite(*_payload(kind, np.ones(4), np.ones(4)))
    want = {"numpy": [0, 1], "device": [1, 0], "mixed": [1, 1]}[kind]
    assert [counter_value(k) - b for k, b in zip(names, before)] == want


@pytest.mark.parametrize("kind", KINDS)
def test_admission_builds_one_executable_per_shape(kind):
    # a shape no other test checks, so the cache starts without it
    shape = {"numpy": (5, 7), "device": (7, 5), "mixed": (7, 7)}[kind]
    size = guardrails._all_finite._cache_size()
    for v in (1.0, 2.0):
        guardrails.check_finite(*_payload(kind, np.full(shape, v),
                                          np.full(shape, v)))
    built = guardrails._all_finite._cache_size() - size
    assert built == (0 if kind == "numpy" else 1)


def test_sharded_extend_rejects_device_nan_1dev():
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core.dist_state import ShardedGPGState
    from repro.launch.mesh import make_d_mesh

    d = 8
    st = ShardedGPGState("rbf", d, window=3, mesh=make_d_mesh(), noise=1e-6)
    r = np.random.RandomState(0)
    st.extend(r.randn(d), r.randn(d))
    L = np.asarray(st.data.base.L).copy()
    put = NamedSharding(st.mesh, PartitionSpec(st.mesh.axis_names))
    x = jax.device_put(jnp.asarray(r.randn(d)).at[3].set(jnp.nan), put)
    g = jax.device_put(jnp.asarray(r.randn(d)), put)
    device = counter_value("resilience.admission.device")
    with use_obs(True), \
            pytest.raises(errors.NonFiniteObservationError):
        st.extend(x, g)
    assert counter_value("resilience.admission.device") == device + 1
    assert st.n == 1 and np.array_equal(np.asarray(st.data.base.L), L)


# ---------------------------------------------------------------------------
# Jitter ladder / factor healing
# ---------------------------------------------------------------------------


def test_heal_ladder_recovers_poisoned_factor():
    st = _mk_state()
    want_Z = np.asarray(st.Z).copy()
    st.data = st.data._replace(L=jnp.full_like(st.data.L, jnp.nan),
                               resnorm=jnp.asarray(jnp.nan, st.data.resnorm.dtype))
    assert not guardrails.factor_ok(st)
    rung = guardrails.heal_factorization(st)
    assert rung == 0                    # masters were fine: plain refactor
    assert guardrails.factor_ok(st)
    np.testing.assert_allclose(np.asarray(st.Z), want_Z, rtol=1e-8)


def test_extend_self_heals_after_poison():
    """The post-mutation watchdog inside extend() heals a factor poisoned
    BETWEEN ops — the stream keeps going with correct answers."""
    st = _mk_state()
    inj = ChaosInjector(seed=3, rates={"degenerate_factor": 1.0})
    assert inj.poison_factor(st)
    r = np.random.RandomState(7)
    st.extend(r.randn(4), r.randn(4))   # watchdog fires in here
    assert guardrails.factor_ok(st)
    # the healed trajectory matches a clean rebuild of the same window
    clean = GPGState.from_data("rbf", st.X, st.G, noise=st.noise)
    np.testing.assert_allclose(np.asarray(st.Z), np.asarray(clean.Z),
                               rtol=1e-6, atol=1e-8)


def test_heal_gives_up_and_restores_jitter():
    """A state whose MASTERS are poisoned cannot be healed by jitter —
    the ladder gives up, restores the base jitter, and does not raise."""
    st = _mk_state()
    st.data = st.data._replace(X=jnp.full_like(st.data.X, jnp.nan))
    base = st.jitter
    assert guardrails.heal_factorization(st, max_rungs=2) == -1
    assert st.jitter == base


# ---------------------------------------------------------------------------
# CG-divergence watchdog
# ---------------------------------------------------------------------------


def test_cg_divergence_predicate():
    assert guardrails.cg_diverged(np.nan, 1.0)
    assert guardrails.cg_diverged(np.inf, 1.0)
    assert guardrails.cg_diverged(100.0, 1.0)
    assert not guardrails.cg_diverged(1e-9, 1.0)
    assert not guardrails.cg_diverged(5.0, 1.0)   # large-but-sane: no trip


def test_regime_solve_falls_back_on_poisoned_warm_start():
    from repro.core import build_factors, dense_solve, get_kernel
    from repro.regime import solve

    spec = get_kernel("rbf")
    r = np.random.RandomState(1)
    n, d = 9, 4                         # n > d: the iterative regime
    X, G = r.randn(n, d), r.randn(n, d)
    f = build_factors(spec, X, lam=0.7, noise=1e-6)
    inj = ChaosInjector(seed=0)
    z0 = inj.poison_warm_start((n, d))
    Z, info = solve(spec, f, G, policy="iterative", z0=z0, maxiter=4)
    assert info["fallback"] is True and info["regime"] == "exact"
    want = dense_solve(spec, X, G, lam=0.7, noise=1e-6, jitter=0.0)
    np.testing.assert_allclose(np.asarray(Z), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_regime_solve_no_fallback_on_healthy_solve():
    from repro.core import build_factors, get_kernel
    from repro.regime import solve

    spec = get_kernel("rbf")
    r = np.random.RandomState(2)
    X, G = r.randn(9, 4), r.randn(9, 4)
    f = build_factors(spec, X, lam=0.7, noise=1e-6)
    _, info = solve(spec, f, G, policy="iterative")
    assert info["regime"] == "iterative" and info["fallback"] is False


# ---------------------------------------------------------------------------
# bf16 trip-wire
# ---------------------------------------------------------------------------


def test_bf16_tripwire_recaches_poisoned_stream():
    st = _mk_state(precision="bf16")
    _ = st.stream_factors               # materialize the bf16 cache
    rev, f = st._stream_cache[0], st._stream_cache[1]
    st._stream_cache = (rev, f._replace(
        Xt=jnp.full_like(f.Xt, jnp.nan)),) + tuple(st._stream_cache[2:])
    assert guardrails.bf16_tripwire(st)
    assert st._stream_cache is None     # next query re-casts from masters
    f2, _ = st.stream_factors
    assert bool(jnp.all(jnp.isfinite(f2.Xt.astype(jnp.float32))))


def test_bf16_tripwire_quiet_on_healthy_stream():
    st = _mk_state(precision="bf16")
    _ = st.stream_factors
    assert not guardrails.bf16_tripwire(st)
    assert st._stream_cache is not None


# ---------------------------------------------------------------------------
# Snapshot / restore roundtrips
# ---------------------------------------------------------------------------


def _assert_same_leaves(a, b, fields=("X", "G", "Xt", "K1e", "K2e", "L",
                                      "Z", "lam", "count")):
    for f in fields:
        assert np.array_equal(np.asarray(getattr(a, f)),
                              np.asarray(getattr(b, f))), f


def test_snapshot_restore_single_bitwise(tmp_path):
    st = _mk_state()
    take_snapshot(st, str(tmp_path), step=1)
    back = restore(str(tmp_path))
    _assert_same_leaves(st.data, back.data)
    assert (back.noise, back.window, back.revision, back.factor_revision) \
        == (st.noise, st.window, st.revision, st.factor_revision)
    # the restored state keeps streaming correctly
    r = np.random.RandomState(5)
    x, g = r.randn(4), r.randn(4)
    st.extend(x, g)
    back.extend(x, g)
    _assert_same_leaves(st.data, back.data)


def test_snapshot_restore_compressed_state(tmp_path):
    """A compressed state persists its reduction frame + raw copies and
    keeps answering queries (and degrading grad_std) after restore."""
    d, window = 6, 3
    st = GPGState("rbf", d, window=window, noise=1e-6, policy="compress")
    r = np.random.RandomState(3)
    base = r.randn(d)
    for _ in range(window + 2):         # overflow the window -> compress
        t = r.randn(2)
        x = base + t[0] * np.eye(d)[0] + t[1] * np.eye(d)[1]
        g = r.randn(d)
        st.extend(x, g)
    assert st._reduction is not None
    take_snapshot(st, str(tmp_path), step=7)
    back = restore(str(tmp_path))
    assert back._reduction is not None
    Xq = np.stack([base + 0.1 * np.eye(d)[0], base + 0.2 * np.eye(d)[1]])
    a, b = st.posterior(Xq), back.posterior(Xq)
    assert np.array_equal(np.asarray(a.value), np.asarray(b.value))
    assert np.array_equal(np.asarray(a.grad), np.asarray(b.grad))
    with pytest.raises(errors.UnsupportedQueryError):
        back.posterior(Xq, return_std=True, return_grad_std=True)


def test_snapshot_restore_fleet_elastic(tmp_path):
    fl = GPFleet("rbf", d=3, batch=2, window=3)
    r = np.random.RandomState(11)
    for t in ("x", "y"):
        fl.join(t)
    for _ in range(2):
        fl.extend({t: (r.randn(3), r.randn(3)) for t in ("x", "y")})
    take_snapshot(fl, str(tmp_path), step=2)
    for target in (2, 4):               # same packing, then elastic
        back = restore(str(tmp_path), batch=target)
        assert back.batch == target
        for t in ("x", "y"):
            _assert_same_leaves(fl.state_view(t), back.state_view(t))
        assert back.hypers_of("x") == fl.hypers_of("x")
    with pytest.raises(ValueError):
        restore(str(tmp_path), batch=1)  # 2 tenants cannot pack into 1


def test_restore_skips_corrupt_snapshot(tmp_path):
    from repro.checkpoint import manifest_index

    st = _mk_state()
    take_snapshot(st, str(tmp_path), step=1)
    st.extend(np.ones(4), np.ones(4))
    take_snapshot(st, str(tmp_path), step=2)
    idx = manifest_index(str(tmp_path), 2)
    leaf = tmp_path / "step_000000002" / idx["L"]["file"]
    leaf.write_bytes(leaf.read_bytes()[:-32])     # torn write
    back = restore(str(tmp_path))       # falls back to step 1
    assert back.n == 3


def test_sharded_snapshot_restore_subprocess(tmp_path):
    """Sharded flavor: snapshot on a 4-device mesh, restore on the SAME
    mesh shape bitwise, and on a 2-device mesh to exact values (the
    D-leaves are stored trimmed and re-padded per mesh)."""
    import subprocess
    import sys

    src = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d"
import numpy as np
from repro.core.dist_state import ShardedGPGState
from repro.resilience import restore, take_snapshot
r = np.random.RandomState(0)
d, cap = 6, 3
if %d == 4:
    st = ShardedGPGState("rbf", d, capacity=cap, noise=1e-6)
    for _ in range(3):
        st.extend(r.randn(d), r.randn(d))
    take_snapshot(st, {str(str(tmp_path))!r}, step=1)
    np.save({str(str(tmp_path))!r} + "/want.npy", st.snapshot_arrays()["Z"])
else:
    back = restore({str(str(tmp_path))!r})
    want = np.load({str(str(tmp_path))!r} + "/want.npy")
    got = back.snapshot_arrays()["Z"]   # mesh-independent logical leaves
    assert np.array_equal(got, want), np.max(np.abs(got - want))
    xq = np.random.RandomState(1).randn(2, d)
    back.posterior(xq)                 # restored state still serves
print("OK")
"""
    for n in (4, 4, 2):
        code = src % (n, n)
        r = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=600,
            env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                 "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")})
        assert "OK" in r.stdout, r.stdout + r.stderr


# ---------------------------------------------------------------------------
# Journal
# ---------------------------------------------------------------------------


def test_journal_roundtrip_and_replay(tmp_path):
    st = _mk_state()
    jpath = str(tmp_path / "ops.jsonl")
    take_snapshot(st, str(tmp_path), step=0, journal=Journal(jpath))
    j = Journal(jpath)
    r = np.random.RandomState(9)
    x, g = r.randn(4), r.randn(4)
    st.extend(x, g)
    j.record("extend", payload={"x": x, "g": g})
    st.evict()
    j.record("evict", args={"k": 1})
    back = restore(str(tmp_path))
    replay_single(back, Journal.since_snapshot(Journal.read(jpath)))
    _assert_same_leaves(st.data, back.data)


def test_journal_torn_tail_dropped_torn_interior_raises(tmp_path):
    p = str(tmp_path / "j.jsonl")
    j = Journal(p)
    j.record("extend", payload={"x": np.ones(2), "g": np.ones(2)})
    j.record("evict", args={"k": 1})
    with open(p, "a") as f:
        f.write('{"op": "ext')         # crash mid-append
    entries = Journal.read(p)           # torn TAIL: safely dropped
    assert [e["op"] for e in entries] == ["extend", "evict"]
    lines = open(p).read().splitlines()
    lines[0] = lines[0][:-5]            # torn INTERIOR: corruption
    open(p, "w").write("\n".join(lines) + "\n")
    with pytest.raises(errors.JournalCorruptionError):
        Journal.read(p)


def test_journal_digest_catches_tamper(tmp_path):
    import json

    p = str(tmp_path / "j.jsonl")
    Journal(p).record("extend", payload={"x": np.ones(3), "g": np.ones(3)})
    e = json.loads(open(p).read())
    e["payload"]["x"][0] = 2.0          # silent bit-flip
    from repro.resilience.journal import decode_payload

    with pytest.raises(errors.JournalCorruptionError):
        decode_payload(e)


# ---------------------------------------------------------------------------
# Serve-loop hardening
# ---------------------------------------------------------------------------


def _server(**kw):
    srv = GPFleetServer(kernel="rbf", d=3, **kw)
    srv.connect("t0")
    return srv


def test_server_load_shedding():
    from repro.configs.paper_gp import GPFleetConfig

    srv = _server(config=GPFleetConfig(max_queue=2))
    r = np.random.RandomState(0)
    reqs = [srv.submit("t0", "extend", (r.randn(3), r.randn(3)))
            for _ in range(4)]
    shed = [q for q in reqs if isinstance(q.result, errors.ShedResponse)]
    assert len(shed) == 2 and all(q.done for q in shed)
    assert shed[0].result.reason == "queue_full"
    srv.drain()
    assert all(q.done for q in reqs)


def test_server_deadline_expiry():
    from repro.configs.paper_gp import GPFleetConfig

    srv = _server(config=GPFleetConfig(deadline_steps=2))
    req = srv.submit("t0", "query", np.zeros((1, 3)))
    req.not_before = 10**9              # park it (a stuck dependency)
    for _ in range(4):
        srv.step()
    assert req.done
    assert isinstance(req.result, errors.DeadlineExceededError)


def test_server_retry_then_exhaustion():
    from repro.configs.paper_gp import GPFleetConfig

    r = np.random.RandomState(1)
    # one injected kill: absorbed by a retry
    srv = _server(injector=ChaosInjector(seed=0, rates={"kill_step": 1.0},
                                         max_faults=1))
    req = srv.submit("t0", "extend", (r.randn(3), r.randn(3)))
    srv.drain()
    assert req.done and req.result is None and req.attempts == 1
    assert srv.fleet.n("t0") == 1
    # unbounded kills: the retry budget runs out, typed failure
    srv2 = _server(config=GPFleetConfig(max_retries=1),
                   injector=ChaosInjector(seed=0,
                                          rates={"kill_step": 1.0}))
    req2 = srv2.submit("t0", "extend", (r.randn(3), r.randn(3)))
    srv2.drain(max_steps=64)
    assert req2.done
    assert isinstance(req2.result, errors.RetryExhaustedError)
    assert srv2.fleet.n("t0") == 0      # the op never half-applied


def test_server_quarantines_poison_tenant():
    srv = _server(injector=ChaosInjector(seed=0,
                                         rates={"nan_payload": 1.0}))
    srv.connect("ok")
    r = np.random.RandomState(2)
    for _ in range(3):                  # quarantine_threshold defaults to 3
        q = srv.submit("t0", "extend", (r.randn(3), r.randn(3)))
        assert isinstance(q.result, errors.NonFiniteObservationError)
    assert "t0" not in srv.tenants and "ok" in srv.tenants
    with pytest.raises(errors.TenantQuarantinedError):
        srv.submit("t0", "query", np.zeros((1, 3)))
    with pytest.raises(errors.TenantQuarantinedError):
        srv.connect("t0")
    # the healthy tenant is untouched
    inj = srv.injector
    srv.injector = None
    ok = srv.submit("ok", "extend", (r.randn(3), r.randn(3)))
    srv.drain()
    assert ok.done and srv.fleet.n("ok") == 1
    assert inj.injected["nan_payload"] == 3


def test_server_straggler_expires_via_deadline():
    from repro.configs.paper_gp import GPFleetConfig

    srv = _server(config=GPFleetConfig(deadline_steps=3),
                  injector=ChaosInjector(seed=0,
                                         rates={"straggler": 1.0}))
    req = srv.submit("t0", "query", np.zeros((1, 3)))
    assert req.chaos_kind == "straggler"
    for _ in range(6):
        srv.step()
    assert isinstance(req.result, errors.DeadlineExceededError)


def test_degraded_grad_std_query_on_compressed_state():
    """Satellite 1: a grad_std serve bundle over a state that compressed
    mid-stream degrades to grad_std=None instead of dying."""
    d, window = 6, 3
    st = GPGState("rbf", d, window=window, noise=1e-6, policy="compress")
    bundle = build_gp_serve_step(st, microbatch=2, return_std=True,
                                 return_grad_std=True)
    r = np.random.RandomState(4)
    base = r.randn(d)
    for _ in range(window + 2):
        t = r.randn(2)
        st.extend(base + t[0] * np.eye(d)[0] + t[1] * np.eye(d)[1],
                  r.randn(d))
    assert st._reduction is not None
    out = bundle.query(np.stack([base, base + 0.1 * np.eye(d)[0]]))
    assert out.value.shape == (2,)
    assert out.grad_std is None         # degraded, typed + counted
    assert out.std is not None


# ---------------------------------------------------------------------------
# Zero-cost contract
# ---------------------------------------------------------------------------


def test_guardrails_zero_cost_jaxpr_identity():
    """The compiled serve/extend programs are byte-identical with
    guardrails on or off — every guardrail runs on the host."""
    from repro.core import get_kernel
    from repro.core.query import make_query_fn
    from repro.core.state import gpg_extend, gpg_init

    spec = get_kernel("rbf")
    data = gpg_init(spec, 4, 4)
    x = jnp.ones(4)
    st = _mk_state()
    f, Z = st.padded_factors, st.data.Z
    Xq = jnp.ones((2, 4))

    def trace_pair(make):
        with guardrails.use_guardrails(False):
            off = str(jax.make_jaxpr(make())(*args))
        with guardrails.use_guardrails(True):
            on = str(jax.make_jaxpr(make())(*args))
        return off, on

    args = (data, x, x)
    off, on = trace_pair(
        lambda: (lambda d_, x_, g_: gpg_extend(spec, d_, x_, g_,
                                               noise=1e-8)))
    assert off == on
    args = (f, Z, Xq)
    off, on = trace_pair(lambda: make_query_fn(spec))
    assert off == on


def test_guardrails_idle_no_counters():
    """A healthy trajectory with guardrails on trips NOTHING — no heals,
    no escalations, no recoveries (the watchdog is non-finite-only)."""
    from repro.obs import trace as obs_trace

    reg = obs_trace.REGISTRY
    before = {k: reg.snapshot()["counters"].get(k, 0)
              for k in ("resilience.factor_faults",
                        "resilience.jitter_escalations",
                        "resilience.faults_recovered")}
    st = _mk_state(d=3, window=5)
    r = np.random.RandomState(8)
    for _ in range(6):
        st.extend(r.randn(3), r.randn(3))
    st.posterior(r.randn(2, 3))
    after = reg.snapshot()["counters"]
    for k, v in before.items():
        assert after.get(k, 0) == v, k
