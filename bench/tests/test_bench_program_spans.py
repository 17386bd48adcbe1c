"""The program's spans beside the device trace: their placement on the
trace's clock and the per-layer metrics that read them, against hand
counts on a recorded event list (bench/tests/data/program_trace_events.json),
and a traced run on the CPU, where the profiler's clock is real."""
import json
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import harness  # noqa: E402
import program_spans  # noqa: E402

NS = 1e-9


@pytest.fixture
def rec():
    with open(os.path.join(HERE, "data", "program_trace_events.json")) as f:
        raw = json.load(f)
    w = raw["wall_offset"]
    tr = {"t0": raw["t0"], "t1": raw["t1"],
          "devices": [[tuple(e) for e in d] for d in raw["devices"]],
          "spans": [tuple(s) for s in raw["spans"]]}
    prog = {"spans": [(n, s + w, e + w, d)
                      for n, s, e, d in raw["program"]["spans"]],
            "compiles": [(n, t + w, sec)
                         for n, t, sec in raw["program"]["compiles"]]}
    return {"trace": tr, "program": prog, "steps": 2, "least_s": {},
            "window_s": 1.0}


def read(name, rec):
    return harness.load_module("metrics", name).read(rec)


def test_program_spans_land_on_the_trace_clock(rec):
    # guard.check_finite opens with the "extend" annotation at 50, so the
    # smallest shift that puts every outermost span inside an annotation
    # undoes the wall clock's offset exactly; hyper.fit is not a program
    # prefix the benchmark reads
    prog = program_spans.load(rec)
    got = {(n, s, d) for n, s, d, _ in prog["spans"]}
    assert ("guard.check_finite", 50, 50) in got
    assert ("fleet.serve.step", 860, 80) in got
    assert all(n != "hyper.fit" for n, *_ in prog["spans"])
    assert program_spans.intervals(prog, ("fleet.pack",)) == [
        (860, 880), (920, 930)]


def test_idle_inside_the_union_of_spans(rec):
    # guard spans [50, 100] and [400, 440]: chip 0 idle 50 + 40 (its op
    # ends at 400), chip 1 idle 50 + 0 (busy to 450): mean 70 ns
    prog = program_spans.load(rec)
    ivs = program_spans.intervals(
        prog, ("guard.check_finite", "guard.after_mutation"))
    assert ivs == [(50, 100), (400, 440)]
    assert program_spans.idle_in(rec["trace"], ivs) == pytest.approx(70 * NS)
    # a span reaching past the window counts only inside it
    assert program_spans.idle_in(rec["trace"], [(950, 1200)]) == \
        pytest.approx(50 * NS)


def test_the_four_metrics(rec):
    # per step of 2: guard 70 ns, pack 20 + 10 ns, unpack 20 ns, all idle
    # on both chips; compiles 0.25 + 0.05 + 0.1 s
    ms = NS * 1e3 / 2
    assert read("guard_idle_ms_per_step", rec) == pytest.approx(70 * ms)
    assert read("pack_idle_ms_per_step", rec) == pytest.approx(30 * ms)
    assert read("unpack_idle_ms_per_step", rec) == pytest.approx(20 * ms)
    assert read("compile_ms_per_step", rec) == pytest.approx(0.4 / 2 * 1e3)


def test_idle_by_innermost_span(rec):
    # the whole window: chip 0 idle 750 ns, chip 1 770 ns, by the deepest
    # span open at each instant (means over the two chips)
    got = dict(program_spans.idle_by_innermost(rec))
    want = {"none": 245, "serve.query": 255, "state.solve.exact": 100,
            "guard.check_finite": 50, "fleet.pack": 30,
            "guard.after_mutation": 20, "fleet.query": 20,
            "fleet.unpack": 20, "fleet.serve.step": 10, "state.extend": 5,
            "state.evict": 5}
    assert got == pytest.approx({k: v * NS for k, v in want.items()})
    # inside the "extend" annotation [50, 450] alone: 185 ns idle, of
    # which 5 ns fall outside every program span
    inside = dict(program_spans.idle_by_innermost(rec, within=("extend",)))
    assert sum(inside.values()) == pytest.approx(185 * NS)
    assert inside["none"] == pytest.approx(5 * NS)


def test_metrics_are_silent_without_the_programs_record(rec, monkeypatch):
    # a program older than its spans keeps no record: every new metric
    # reads nothing, and none raises
    from repro.obs import trace as program_trace

    bare = {k: v for k, v in rec.items() if k != "program"}
    monkeypatch.delattr(program_trace, "profiled")
    for name in ("guard_idle_ms_per_step", "compile_ms_per_step",
                 "pack_idle_ms_per_step", "unpack_idle_ms_per_step"):
        assert read(name, bare) is None
        assert read(name, dict(rec, trace=None)) is None
    # no program span inside any annotation: nothing to place
    assert read("guard_idle_ms_per_step",
                dict(rec, trace=dict(rec["trace"], spans=[]))) is None


TINY = {"stream_rbf_resnet18.observe": {"d": 256},
        "fleet_rbf_d4k.mixed": {"d": 64, "tenants": 4, "lanes": 4}}
WITHIN = {"stream_rbf_resnet18.observe": ("extend", "query"),
          "fleet_rbf_d4k.mixed": ("server.step",)}


@pytest.mark.parametrize("workload", sorted(TINY))
def test_traced_run_places_the_programs_spans(workload, monkeypatch):
    # a real profiler session on the CPU: every outermost program span
    # lands inside the benchmark annotation around the call that made it
    seen = {}
    real = harness.read_metrics

    def keep(entries, rec):
        seen["rec"] = rec
        return real(entries, rec)

    monkeypatch.setattr(harness, "read_metrics", keep)
    out = harness.run(workload, 2 ** 31 + 5, 0.5, True,
                      t0=time.perf_counter(), require_tpu=False,
                      overrides=TINY[workload], cache=False)
    assert out["correct"] is True
    rec = seen["rec"]
    prog = program_spans.load(rec)
    bench = [(s, s + d) for n, s, d in rec["trace"]["spans"]
             if n in WITHIN[workload]]
    top = [(s, s + d) for _, s, d, depth in prog["spans"] if depth == 0]
    for bs, be in bench:
        assert any(bs <= s and e <= be for s, e in top), (bs, be)
    # the fleet drains its queue after its last annotated step
    last = max(be for _, be in bench)
    for s, e in top:
        assert s >= last or any(bs <= s and e <= be for bs, be in bench)
    names = {n for n, *_ in prog["spans"]}
    if workload.startswith("stream"):
        assert {"guard.check_finite", "state.extend", "state.evict",
                "guard.after_mutation", "serve.query"} <= names
        assert "compile_ms_per_step" in out["metrics"]
    else:
        assert {"fleet.serve.step", "fleet.pack", "fleet.extend",
                "fleet.query", "fleet.unpack"} <= names
