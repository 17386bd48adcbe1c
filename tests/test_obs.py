"""repro.obs: registry/span/sink semantics, spans on the profiler's clock
and the compile counter, the zero-cost disabled-mode guarantee
(jaxpr/HLO), in-jit taps, the recompile sentinel, the serve LRU revision
keying, health probes, the --check null handling, and the check_telemetry
gate."""
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.core.kernels import get_kernel
from repro.core.state import GPGState, gpg_extend, gpg_init
from repro.obs import compile_watch, health, injit
from repro.obs import trace as obs
from repro.train.serve import build_gp_serve_step
from repro.utils.hlo import count_primitive

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))


@pytest.fixture(autouse=True)
def _clean_registry():
    obs.reset()
    obs.configure(None)
    compile_watch._WATCHES.clear()
    yield
    obs.reset()
    obs.configure(None)
    obs.set_enabled(None)
    compile_watch._WATCHES.clear()


# ---------------------------------------------------------------------------
# trace: registry + spans + sink
# ---------------------------------------------------------------------------

def test_registry_counters_gauges_hists():
    r = obs.Registry()
    r.inc("c")
    r.inc("c", 2.5)
    r.set_gauge("g", 7.0)
    r.observe("h", 1.0)
    r.observe("h", 3.0)
    snap = r.snapshot()
    assert snap["counters"]["c"] == 3.5
    assert snap["gauges"]["g"] == 7.0
    assert snap["hists"]["h"]["count"] == 2
    assert snap["hists"]["h"]["total"] == 4.0
    assert snap["hists"]["h"]["min"] == 1.0 and snap["hists"]["h"]["max"] == 3.0
    # delta vs an earlier snapshot drops zero-change counters
    r2_before = r.snapshot()
    r.inc("c")
    r.inc("untouched", 0)
    d = r.delta(r2_before)
    assert d["counters"] == {"c": 1.0}
    assert d["hists"] == {}


def test_span_nesting_and_jsonl_sink(tmp_path):
    log = tmp_path / "t.jsonl"
    obs.configure(str(log))
    with obs.use_obs(True):
        with obs.span("outer"):
            with obs.span("inner", tag="x"):
                pass
        obs.flush()
    events = [json.loads(ln) for ln in log.read_text().splitlines()]
    spans = [e for e in events if e["type"] == "span"]
    assert [s["path"] for s in spans] == ["outer.inner", "outer"]
    assert spans[0]["attrs"] == {"tag": "x"}
    assert all(s["dur_s"] >= 0 for s in spans)
    snap = [e for e in events if e["type"] == "snapshot"][-1]
    assert "span.outer.seconds" in snap["hists"]
    assert "span.outer.inner.seconds" in snap["hists"]


def test_disabled_span_is_noop_and_sink_silent(tmp_path):
    log = tmp_path / "t.jsonl"
    obs.configure(str(log))
    with obs.use_obs(False):
        with obs.span("never"):
            pass
        obs.emit({"type": "x"})
    assert not log.exists()
    assert obs.REGISTRY.hists == {}


def test_enabled_resolution_env(monkeypatch):
    obs.set_enabled(None)
    monkeypatch.delenv("REPRO_OBS", raising=False)
    assert not obs.enabled()
    monkeypatch.setenv("REPRO_OBS", "on")
    assert obs.enabled()
    monkeypatch.setenv("REPRO_OBS", "off")
    assert not obs.enabled()
    obs.set_enabled(True)
    assert obs.enabled()        # forced override beats the env
    obs.set_enabled(None)


# ---------------------------------------------------------------------------
# spans on the profiler's clock + the compile counter
# ---------------------------------------------------------------------------

def _host_events(logdir):
    from jax.profiler import ProfileData

    found = list(pathlib.Path(logdir).rglob("*.xplane.pb"))
    assert len(found) == 1
    return [(e.name, int(e.start_ns), int(e.duration_ns))
            for plane in ProfileData.from_file(str(found[0])).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def test_span_lands_in_profiler_trace_with_obs_off(tmp_path):
    import time

    with obs.use_obs(False):
        jax.profiler.start_trace(str(tmp_path))
        try:
            with obs.span("state.probe", k=1):
                with obs.span("guard.probe"):
                    time.sleep(0.002)
        finally:
            jax.profiler.stop_trace()
    ev = {n: (s, d) for n, s, d in _host_events(tmp_path)}
    # each span annotates under its leaf name; the profiler nests by time
    (o_s, o_d), (i_s, i_d) = ev["state.probe"], ev["guard.probe"]
    assert i_d >= 2_000_000
    assert o_s <= i_s and i_s + i_d <= o_s + o_d
    # the registry stays behind REPRO_OBS
    assert obs.REGISTRY.hists == {}
    # the spans kept for the session: same durations, same nesting, on
    # the wall clock the profiler's host annotations share
    kept = {n: (s, e, d) for n, s, e, d in obs.profiled()["spans"]}
    assert kept["guard.probe"][2] == 1 and kept["state.probe"][2] == 0
    for name, (s, d) in ev.items():
        if name in kept:
            assert abs((kept[name][1] - kept[name][0]) - d) < 1_000_000
    off = {kept[n][0] - ev[n][0] for n in kept}
    assert max(off) - min(off) < 1_000_000


def test_profiled_keeps_the_newest_session(tmp_path):
    for i in range(2):
        jax.profiler.start_trace(str(tmp_path / str(i)))
        try:
            with obs.span(f"state.session{i}"):
                pass
        finally:
            jax.profiler.stop_trace()
    assert [n for n, *_ in obs.profiled()["spans"]] == ["state.session1"]
    with obs.span("state.unprofiled"):
        pass
    assert [n for n, *_ in obs.profiled()["spans"]] == ["state.session1"]


def test_compile_attributed_to_innermost_span():
    before = obs.compiles_by_span()
    x = jnp.arange(5.0)
    with obs.use_obs(True):
        with obs.span("outer.cc"):
            with obs.span("inner.cc"):
                # a function never seen before: one backend compile
                jax.jit(lambda v: v * 3.25 + 0.5)(x).block_until_ready()
            jax.jit(lambda v: v - 1.75)(x).block_until_ready()
    after = obs.compiles_by_span()
    assert after["inner.cc"]["count"] - \
        before.get("inner.cc", {}).get("count", 0) == 1
    assert after["inner.cc"]["seconds"] > 0
    assert after["outer.cc"]["count"] - \
        before.get("outer.cc", {}).get("count", 0) == 1
    assert obs.counter_value("compile.span.inner.cc.count") == 1
    assert obs.counter_value("compile.span.inner.cc.seconds") > 0
    # outside any span the compile counts under "none"
    n0 = obs.compiles_by_span().get("none", {}).get("count", 0)
    jax.jit(lambda v: v * 0.125)(x).block_until_ready()
    assert obs.compiles_by_span()["none"]["count"] == n0 + 1


def test_serve_query_never_syncs_and_keeps_its_jaxpr(monkeypatch):
    synced = []
    real = jax.block_until_ready

    def counting(x):
        synced.append(1)
        return real(x)

    st = _mk_state(d=4, n=3)
    Xq = jnp.ones((3, 4))
    jaxprs = {}
    for on in (False, True):
        with obs.use_obs(on):
            serve = build_gp_serve_step(st, microbatch=2)
            monkeypatch.setattr(jax, "block_until_ready", counting)
            out = serve.query(Xq)
            monkeypatch.setattr(jax, "block_until_ready", real)
            jaxprs[on] = str(
                jax.make_jaxpr(lambda q: serve.query(q).value)(Xq))
    assert synced == []
    assert out.value.shape == (3,)
    # the query program is the same with telemetry on and off; on, the
    # compile watcher's shim wraps the same step function
    same = {on: " ".join(j.replace("shimmed", "fn").split())
            for on, j in jaxprs.items()}
    assert same[False] == same[True]


# ---------------------------------------------------------------------------
# injit: taps enter the jaxpr ONLY when enabled (the zero-cost proof)
# ---------------------------------------------------------------------------

def _trace_extend(spec, data, x, g):
    # fresh closure per call: jax.make_jaxpr caches on function identity,
    # so reusing one callable across enabled-modes would alias the traces
    return jax.make_jaxpr(
        lambda d, x_, g_: gpg_extend(spec, d, x_, g_, noise=1e-8))(
            data, x, g)


def test_extend_jaxpr_clean_when_disabled_tapped_when_enabled():
    spec = get_kernel("rbf")
    data = gpg_init(spec, 4, 4)
    x = jnp.ones(4)
    g = jnp.ones(4)
    with obs.use_obs(False):
        j_off = _trace_extend(spec, data, x, g)
    with obs.use_obs(True):
        j_on = _trace_extend(spec, data, x, g)
    # REPRO_OBS=off: not a single callback primitive in the whole program
    # — the compiled extend is bit-identical to a build without repro.obs
    assert count_primitive(j_off.jaxpr, "debug_callback") == 0
    # enabled: pivot2 + degenerate flag + CG iters + CG resnorm all tapped
    assert count_primitive(j_on.jaxpr, "debug_callback") >= 4


def test_query_step_jaxpr_identical_on_and_off():
    from repro.core.query import make_query_fn

    spec = get_kernel("rbf")
    st = GPGState.from_data("rbf", jnp.eye(3, 4), jnp.ones((3, 4)),
                            noise=1e-8)
    f, Z = st.padded_factors, st.data.Z
    Xq = jnp.ones((2, 4))
    with obs.use_obs(False):
        j_off = jax.make_jaxpr(make_query_fn(spec))(f, Z, Xq)
    with obs.use_obs(True):
        j_on = jax.make_jaxpr(make_query_fn(spec))(f, Z, Xq)
    # the batched query path is pure math — no taps on either side, and
    # the serve step's program is untouched by observability entirely
    assert str(j_off) == str(j_on)
    assert count_primitive(j_on.jaxpr, "debug_callback") == 0


def test_tap_accumulates_under_jit_and_cond():
    with obs.use_obs(True):
        @jax.jit
        def f(x, flag):
            injit.tap("t.sum", jnp.sum(x), kind="counter")
            return jax.lax.cond(
                flag,
                lambda v: (injit.tap("t.branch", 1, kind="counter"), v * 2)[1],
                lambda v: v,
                x)

        f(jnp.ones(3), True).block_until_ready()
        f(jnp.ones(3), False).block_until_ready()
        assert obs.counter_value("t.sum") == 6.0
        assert obs.counter_value("t.branch") == 1.0   # only the taken branch


def test_fold_metrics_host_side():
    with obs.use_obs(True):
        injit.fold({"a.x": jnp.asarray(3.0)}, kind="counter")
        injit.fold({"a.g": 2.0})
        assert obs.counter_value("a.x") == 3.0
        assert obs.gauge_value("a.g") == 2.0


# ---------------------------------------------------------------------------
# compile_watch: the recompile sentinel
# ---------------------------------------------------------------------------

def test_compile_watch_counts_signatures():
    with obs.use_obs(True):
        w = compile_watch.wrap(lambda x: x * 2, name="cw_t")
        w(jnp.ones(3))
        w(jnp.ones(3))          # cache hit: no new trace
        w(jnp.ones(5))          # new shape: one new compile
        assert isinstance(w, compile_watch.CompileWatch)
        assert w.calls == 3
        assert w.n_signatures() == 2
        assert w.n_compiles() == 2
        assert w.violations() == []
        w.assert_stable()
        assert obs.counter_value("compile.cw_t.compiles") == 2
        assert obs.counter_value("compile.cw_t.recompiles") == 0


def test_compile_watch_detects_forced_recompile():
    with obs.use_obs(True):
        w = compile_watch.wrap(lambda x: x + 1, name="cw_v")
        w(jnp.ones(3))
        jax.clear_caches()      # force XLA to re-trace the same signature
        w(jnp.ones(3))
        assert w.n_compiles() == 2 and w.n_signatures() == 1
        assert len(w.violations()) == 1
        assert obs.counter_value("compile.cw_v.recompiles") == 1
        with pytest.raises(AssertionError, match="recompiled"):
            w.assert_stable()


def test_wrap_is_plain_jit_when_disabled():
    fn = lambda x: x * 3          # noqa: E731
    with obs.use_obs(False):
        w = compile_watch.wrap(fn, name="cw_off")
    assert not isinstance(w, compile_watch.CompileWatch)
    # bit-identical lowering to an undecorated jax.jit of the same fn
    x = jnp.ones(3)
    assert jax.jit(fn).lower(x).as_text() == w.lower(x).as_text()


# ---------------------------------------------------------------------------
# serve wiring: revision-keyed LRU + the recompile-sentinel regression test
# ---------------------------------------------------------------------------

def _mk_state(d=4, n=3, noise=1e-6):
    X = jnp.eye(n, d) * 2.0
    G = jnp.ones((n, d))
    return GPGState.from_data("rbf", X, G, noise=noise, capacity=4)


def test_solver_cache_revision_keyed_with_counters():
    with obs.use_obs(True):
        st = _mk_state()
        serve = build_gp_serve_step(st, microbatch=2, return_std=True)
        Xq = jnp.ones((2, 4))
        serve.query(Xq)
        assert obs.counter_value("serve.solver_cache.misses") == 1
        serve.query(Xq)                      # unchanged revision: HIT
        assert obs.counter_value("serve.solver_cache.hits") == 1
        # resolve() rebuilds the data pytree but NOT the factorization —
        # the revision key keeps the entry (the identity key this replaced
        # would have re-factorized and double-cached here)
        st.resolve(st.G)
        serve.query(Xq)
        assert obs.counter_value("serve.solver_cache.hits") == 2
        assert obs.counter_value("serve.solver_cache.misses") == 1
        st.extend(3.0 * jnp.ones(4), jnp.ones(4))   # factors changed: MISS
        serve.query(Xq)
        assert obs.counter_value("serve.solver_cache.misses") == 2


def test_solver_cache_eviction_counter():
    with obs.use_obs(True):
        st = _mk_state()
        serve = build_gp_serve_step(st, microbatch=2, return_std=True)
        Xq = jnp.ones((2, 4))
        for i in range(serve._SOLVER_CACHE_MAX + 1):
            serve.query(Xq)
            st.refactor()        # bump the factor revision every round
        assert obs.counter_value("serve.solver_cache.evictions") == 1


def test_serve_step_compile_stable_across_extend_evict_refit_precision():
    """The tentpole invariant as a regression test: extend -> evict ->
    refit -> precision toggle, exactly ONE compile per distinct shape
    signature, zero recompiles."""
    with obs.use_obs(True):
        st = _mk_state(d=4, n=3, noise=1e-6)
        serve = build_gp_serve_step(st, microbatch=2, return_std=True)
        Xq = jnp.ones((2, 4))
        serve.query(Xq)
        st.extend(3.0 * jnp.ones(4), jnp.ones(4))
        serve.query(Xq)
        st.evict()
        serve.query(Xq)
        st.refit(steps=5)        # noise/signal/lam change VALUES only
        serve.query(Xq)
        w = serve.step
        assert w.n_signatures() == 1
        assert w.n_compiles() == 1
        w.assert_stable()

        # mean-only endpoint: a precision toggle changes the stream dtype
        # — a genuinely NEW signature, one (and only one) extra compile
        mean = build_gp_serve_step(st, microbatch=2)
        mean.query(Xq)
        st.set_precision("bf16")
        mean.query(Xq)
        st.set_precision("f32")
        mean.query(Xq)           # back to sig 1: jit cache hit, no trace
        assert mean.step.n_signatures() == 2
        assert mean.step.n_compiles() == 2
        mean.step.assert_stable()
        compile_watch.assert_all_stable()


# ---------------------------------------------------------------------------
# health + cost
# ---------------------------------------------------------------------------

def test_health_probes_and_monitor():
    with obs.use_obs(True):
        st = _mk_state(d=4, n=3)
        assert health.condition_proxy(st.data) >= 1.0
        assert health.solve_residual(st.spec, st.data,
                                     noise=st._noise_eff) < 1e-6
        assert health.precision_drift(st) < 0.1
        mon = health.HealthMonitor(cadence=2, drift=False)
        st.attach_health(mon)
        st.extend(3.0 * jnp.ones(4), jnp.ones(4))   # tick 1: no sample
        assert obs.counter_value("health.samples") == 0
        st.extend(4.0 * jnp.ones(4), jnp.ones(4))   # tick 2: sample
        assert obs.counter_value("health.samples") == 1
        assert obs.gauge_value("health.cond_k1n") >= 1.0


# ---------------------------------------------------------------------------
# benchmarks/run.py --check: null/absent metrics + telemetry skip
# ---------------------------------------------------------------------------

def test_check_skips_null_metrics_and_telemetry():
    import benchmarks.run as br

    failures = []
    base = {
        "pallas_seconds": None,          # interpret-mode baseline column
        "ratio": None,
        "claim_holds": True,
        "speed_err": 1.0,
        "telemetry": {"counters": {"hot_bytes": 1.0}},
    }
    fresh = {
        "pallas_seconds": 2.0,
        "ratio": 5.0,                    # None baseline: not gated
        "claim_holds": None,             # None fresh: not a flip
        "speed_err": None,               # measured -> absent: not gated
        "telemetry": {"counters": {"hot_bytes": 1e9}},  # never gated
    }
    br._walk_regressions(base, fresh, ("kernels",), failures)
    assert failures == []
    # real regressions are still caught
    failures = []
    br._walk_regressions({"ratio": 1.0, "claim_holds": True},
                         {"ratio": 2.0, "claim_holds": False},
                         ("kernels",), failures)
    assert {f[0] for f in failures} == {"kernels.ratio",
                                        "kernels.claim_holds"}


# ---------------------------------------------------------------------------
# tools/check_telemetry.py: the CI smoke gate
# ---------------------------------------------------------------------------

def test_check_telemetry_on_instrumented_run(tmp_path):
    from tools.check_telemetry import check

    log = tmp_path / "run.jsonl"
    obs.configure(str(log))
    with obs.use_obs(True):
        st = _mk_state(d=4, n=3)
        serve = build_gp_serve_step(st, microbatch=2)
        st.extend(3.0 * jnp.ones(4), jnp.ones(4))
        serve.query(jnp.ones((2, 4)))
        obs.flush()
    assert check(str(log)) == []


def test_check_telemetry_flags_violations(tmp_path):
    from tools.check_telemetry import check

    log = tmp_path / "bad.jsonl"
    lines = [
        {"type": "span", "name": "state.extend", "path": "state.extend",
         "dur_s": -1.0},
        {"type": "compile", "watch": "gp_serve_step", "sig": "s", "nth": 2},
        {"type": "snapshot", "counters": {"state.extend_calls": 5.0},
         "gauges": {}},
    ]
    log.write_text("\n".join(json.dumps(e) for e in lines) + "\nnot json\n")
    failures = check(str(log))
    text = "\n".join(failures)
    assert "serve.query" in text              # missing required span
    assert "bad duration" in text
    assert "recompile-sentinel violation" in text
    assert "malformed JSON" in text
    assert "state.refactor_fallback" in text  # missing counter
    assert "counter/span mismatch" in text    # 5 claimed vs 1 span event
    # --allow-recompile downgrades exactly the sentinel failure
    relaxed = check(str(log), allow_recompile=True)
    assert all("recompile-sentinel" not in f for f in relaxed)
    assert check(str(tmp_path / "missing.jsonl"))
