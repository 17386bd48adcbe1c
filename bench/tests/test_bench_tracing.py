"""The reduction from a trace to the per-layer metrics, against hand
counts on a recorded event list (bench/tests/data/trace_events.json)."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import harness  # noqa: E402
import tracing  # noqa: E402


@pytest.fixture
def tr():
    with open(os.path.join(HERE, "data", "trace_events.json")) as f:
        raw = json.load(f)
    return {"t0": raw["t0"], "t1": raw["t1"],
            "devices": [[tuple(e) for e in d] for d in raw["devices"]],
            "spans": [tuple(s) for s in raw["spans"]]}


def rec_of(tr, **kw):
    return dict({"trace": tr, "steps": 2, "least_s": {}, "window_s": 1.0},
                **kw)


def test_union_merges_and_clips():
    ops = [("a", 0, 10), ("b", 5, 10), ("c", 20, 5), ("d", 40, 100)]
    assert tracing.union(ops, 2, 50) == [(2, 15), (20, 25), (40, 50)]


def test_busy_and_idle_share(tr):
    # chip 0 busy 100 + 50 + 200 + 50 = 400 ns (the loop holds
    # loop_fusion; copy is clipped at the window's end); chip 1 340 ns
    assert tracing.busy_s(tr) == pytest.approx(370e-9)
    assert tracing.window_s(tr) == pytest.approx(1e-6)
    for name in ("idle_share.observe", "idle_share.serve"):
        got = harness.load_module("metrics", name).read(rec_of(tr))
        assert got == pytest.approx(63.0)


def test_annotation_attribution(tr):
    # extend [100, 400]: chip 0 150 ns, chip 1 140 ns; query [400, 700]:
    # 200 on each
    assert tracing.busy_in(tr, "extend") == [
        (pytest.approx(300e-9), pytest.approx(145e-9))]
    assert tracing.busy_in(tr, "query") == [
        (pytest.approx(300e-9), pytest.approx(200e-9))]
    least = {"extend": 29e-9, "query": 40e-9}
    rec = rec_of(tr, least_s=least)
    assert harness.load_module("metrics", "extend_roofline").read(rec) \
        == pytest.approx(20.0)
    assert harness.load_module("metrics", "query_roofline").read(rec) \
        == pytest.approx(20.0)


def test_host_ms_per_step(tr):
    # server.step [700, 900]: no device op runs inside it on either chip,
    # so all 200 ns of the one step are host time
    assert tracing.busy_in(tr, "server.step") == [
        (pytest.approx(200e-9), 0.0)]
    got = harness.load_module("metrics", "host_ms_per_step").read(rec_of(tr))
    assert got == pytest.approx(200e-9 * 1e3)
    assert harness.load_module("metrics", "host_ms_per_step").read(
        rec_of(dict(tr, spans=[]))) is None


def test_op_names_and_self_time():
    assert tracing.op_name("%fused_gram_mvm_padded.3 = f32[8,16]{1,0} "
                           "custom-call(f32[8,8] %a.1)") == \
        "fused_gram_mvm_padded"
    assert tracing.op_name("%while = (f32[8]) while(%tuple.2)") == "while"
    assert tracing.op_name("copy-start.12") == "copy-start"
    # a loop [0, 100] around a body op [10, 40] that holds [20, 30], then
    # an op after it: no nanosecond is counted twice
    ops = [("while", 0, 100), ("body", 10, 30), ("inner", 20, 10),
           ("next", 100, 5)]
    got = {n: d for n, _, d in tracing.self_times(ops)}
    assert got == {"while": 70, "body": 20, "inner": 10, "next": 5}


def test_breakdown(tr):
    top = dict(tracing.top_ops(tr))
    # by self time: the loop keeps 160 ns on chip 0 (40 are its body's)
    assert top == pytest.approx({"while": 180e-9, "fusion": 100e-9,
                                 "all-reduce": 45e-9, "copy": 25e-9,
                                 "loop_fusion": 20e-9})
    idle = dict(tracing.idle_by_span(tr))
    assert idle == pytest.approx({"server.step": 200e-9, "extend": 155e-9,
                                  "none": 125e-9, "query": 100e-9,
                                  "client": 50e-9})
    # idle time split by span adds up to the idle time
    assert sum(idle.values()) == pytest.approx(
        tracing.window_s(tr) - tracing.busy_s(tr))


def test_readers_without_device_find_nothing(tr):
    empty = dict(tr, devices=[])
    for name in ("idle_share.observe", "extend_roofline", "query_roofline"):
        assert harness.load_module("metrics", name).read(
            rec_of(empty, least_s={"extend": 1.0, "query": 1.0})) is None
    for name in ("mfu.observe", "mfu.serve"):
        assert harness.load_module("metrics", name).read(rec_of(tr)) is None


def test_load_reads_window_and_annotations(tmp_path):
    """A trace recorded here (CPU: no device planes) gives the window and
    the benchmark's annotations on one clock."""
    import glob

    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a: a @ a)
    x = jnp.ones((64, 64), jnp.float32)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with harness.annotate("window"):
        with harness.annotate("extend"):
            f(x).block_until_ready()
        with harness.annotate("query"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    got = tracing.load(path, ("client", "extend", "query"))
    assert got["devices"] == []
    names = [s[0] for s in got["spans"]]
    assert names == ["extend", "query"]
    for _, s, d in got["spans"]:
        assert got["t0"] <= s and s + d <= got["t1"]
