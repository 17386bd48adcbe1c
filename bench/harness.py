"""The benchmark's shared core.

One run of one cell: refuse a host without the chips the cell asks for,
build the system under test from the cell's configuration file, fill and
warm it with the cell's traffic, measure for ``--seconds``, check what the
window served against the float64 reference, and print one JSON line.

Everything that belongs to one piece is found by name:

    BENCHMARK.json                  cells, configurations, metrics
    bench/configs/<config>.json     a deployment: system, sizes, hypers,
                                    the limits of the correctness check
    bench/traffic/<traffic>.json    a traffic mix, read by the system's
                                    closed-loop clients
    bench/systems/<system>.py       how to build and drive one entry point
    bench/metrics/<metric>.py       ``read(rec)`` for one metric
    bench/counts/<op>.py            the least bytes and flops of one op
    bench/peaks.json                the chips' published peaks
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import sys
import tempfile
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(BENCH, ".jax_cache")
TRACE_DIR = os.path.join(BENCH, ".traces")
# the program's own switches: the benchmark runs its defaults, with
# telemetry off (REPRO_OBS on changes the served path)
PROGRAM_ENV = ("REPRO_OBS", "REPRO_OBS_PROFILER", "REPRO_OBS_JSONL",
               "REPRO_BACKEND", "REPRO_PRECISION", "REPRO_GUARDRAILS")
# what the correctness check reads off each sampled answer (bench/reference)
NUMBERS = ("value_rel_err", "grad_rel_err", "grad_in_span_err",
           "grad_out_of_span")


class Refused(Exception):
    """The run cannot measure here (no chip, too few chips, no program)."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """bench/<kind>/<name>.py as a module (names may hold dots)."""
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _named(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"bench: no {what} named {name!r} in BENCHMARK.json")


def cell_spec(workload: str, overrides=None) -> dict:
    """The cell, its configuration, its traffic and its metrics."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = _named(bench["workloads"], workload, "workload")
    entry = _named(bench["configs"], cell["config"], "configuration")
    cfg = dict(load_json(os.path.join(ROOT, entry["file"])), **(overrides or {}))
    traffic = load_json(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in reported)]
    return {"cell": cell, "config": cfg, "traffic": traffic,
            "end_to_end": e2e, "per_layer": layer}


# ---------------------------------------------------------------------------
# Environment, device, compile cache
# ---------------------------------------------------------------------------


def clean_env() -> None:
    for k in PROGRAM_ENV:
        os.environ.pop(k, None)


def enable_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout;
    every program is kept, so only a cell's first run there compiles."""
    import jax

    os.makedirs(CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def devices(chips: int, require_tpu: bool) -> list:
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise Refused(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def import_program(require_tpu: bool) -> None:
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.core import resolve_backend
    except ImportError as e:
        raise Refused(f"no program beside the benchmark: {e}") from None
    if require_tpu and "REPRO_BACKEND" not in os.environ \
            and resolve_backend() != "pallas":
        raise Refused(f"the program resolved the {resolve_backend()!r} "
                      "backend, not pallas")


class Compiles:
    """Executables built outside JAX's in-memory caches: XLA compiles and
    loads from the persistent cache alike (``jax.monitoring``)."""

    n = 0
    _armed = False

    @classmethod
    def arm(cls) -> None:
        if cls._armed:
            return
        from jax import monitoring

        def listener(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                cls.n += 1

        monitoring.register_event_duration_secs_listener(listener)
        cls._armed = True


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def peak_bytes(devs):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------------
# Least work, checks, metrics
# ---------------------------------------------------------------------------


def least_seconds(cfg: dict, shapes: dict, ops: dict, kind: str,
                  chips: int) -> dict:
    """Per op: the least time on one chip of the window's ops of that kind,
    max(bytes / bandwidth, flops / peak), the work split over the chips."""
    peak = load_json(os.path.join(BENCH, "peaks.json"))["chips"].get(kind)
    if peak is None:
        raise Refused(f"no published peaks for device kind {kind!r} in "
                      "bench/peaks.json")
    out = {}
    for op, n in ops.items():
        c = load_module("counts", op).count(cfg, **shapes[op])
        out[op] = n * max(c["bytes"] / (peak["hbm_bytes_per_s"] * chips),
                          c["flops"] / (peak["flops_per_s"] * chips))
    return out


def worst(values) -> float:
    values = list(values)
    if not values or any(math.isnan(v) for v in values):
        return float("nan")
    return max(values)


def compare(samples, cfg: dict, answer=None) -> dict:
    """Each of ``NUMBERS`` beside the limit the configuration gives it
    (None: read, not compared).  ``value_rel_err`` is ||value - value*|| /
    ||value*|| over every sampled row of every sampled answer together: the
    posterior mean of a point can lie near 0, where its own relative error
    says nothing; the gradients' numbers are the worst over the answers.
    ``answer`` replaces the served answers (the control)."""
    import reference

    errs: dict = {k: [] for k in NUMBERS}
    err_sq = ref_sq = 0.0
    for s in samples:
        got = (s["value"], s["grad"]) if answer is None else answer(s)
        r = reference.check(s["X"], s["G"], s["Xq"], s["lam"], s["noise"],
                            *got)
        err_sq += r.pop("value_err_sq")
        ref_sq += r.pop("value_ref_sq")
        for k, v in r.items():
            errs[k].append(v)
    errs["value_rel_err"] = [(err_sq / ref_sq) ** 0.5 if samples and ref_sq
                             else float("nan")]
    limits = cfg["limits"]
    return {k: {"value": worst(v), "limit": limits.get(k)}
            for k, v in errs.items()}


def control_answer(s):
    import reference

    return reference.posterior_control(s["X"], s["G"], s["Xq"], s["lam"],
                                       s["noise"])


def read_metrics(entries, rec: dict) -> dict:
    out = {}
    for m in entries:
        v = load_module("metrics", m["name"]).read(rec)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t0: float, require_tpu: bool = True, overrides=None,
        cache: bool = True, control: bool = False, fault=None,
        program_env=None) -> dict:
    """One measured run of one cell; returns the result object.
    ``require_tpu=False`` and ``overrides`` (smaller sizes) serve the CPU
    tests; ``control=True`` adds the control's readings; ``fault`` plants
    one of ``faults.KINDS`` after set-up; ``program_env`` sets program
    switches (another path of the program, as a second witness).  The
    benchmark's own runs use none of these."""
    spec = cell_spec(workload, overrides)
    cell, cfg, traffic = spec["cell"], spec["config"], spec["traffic"]
    clean_env()
    os.environ.update(program_env or {})
    import jax

    if cache:
        enable_cache()
    devs = devices(cell["chips"], require_tpu)
    import_program(require_tpu)
    Compiles.arm()
    system = load_module("systems", cfg["system"]).System(
        cfg, traffic, seed, cell["chips"])
    system.setup(annotate)
    if fault is not None:
        import faults

        faults.plant(fault, system)
        # one step to build what the broken path compiles
        system.run(0.0, annotate)
        system.kept.clear()
        system.seen = 0
    trace_path = None
    if trace:
        os.makedirs(TRACE_DIR, exist_ok=True)
        trace_path = tempfile.mkdtemp(dir=TRACE_DIR)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_path, profiler_options=opts)
    setup_s = time.perf_counter() - t0
    c0 = Compiles.n
    w0 = time.perf_counter()
    with annotate("window"):
        res = system.run(seconds, annotate)
    window_s = time.perf_counter() - w0
    compiles = Compiles.n - c0
    tr = None
    if trace:
        jax.profiler.stop_trace()
        tr = _read_trace(trace_path, system.SPANS)
    memory = peak_bytes(devs)
    samples = system.samples()
    shapes = system.shapes()
    system.free()
    del system
    c0 = time.perf_counter()
    program = compare(samples, cfg)
    checks = {k: c for k, c in program.items() if c["limit"] is not None}
    print(f"bench: setup {setup_s:.3f} s, window {window_s:.3f} s, "
          f"{compiles} executables built in the window, "
          f"check {time.perf_counter() - c0:.3f} s", file=sys.stderr)
    readings = None
    if control or fault or program_env:
        readings = {"program": program}
    if control:
        readings["control"] = compare(samples, cfg, answer=control_answer)
    kind = devs[0].device_kind
    rec = {"setup_s": setup_s, "window_s": window_s, "chips": cell["chips"],
           "compiles": compiles, "trace": tr, **res,
           "least_s": least_seconds(cfg, shapes, res["ops"], kind,
                                    cell["chips"])
           if require_tpu else {}}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory}
    out = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
           "attempted": res["attempted"], "failed": res["failed"],
           "metrics": read_metrics(spec["per_layer"] if trace
                                   else spec["end_to_end"], rec),
           "device": device}
    if tr is not None:
        import tracing

        device["busy_s"] = tracing.busy_s(tr)
        device["window_s"] = tracing.window_s(tr)
        out["breakdown"] = {"device_ops": tracing.top_ops(tr),
                            "idle_gaps": tracing.idle_by_span(tr)}
    if readings is not None:
        out["readings"] = readings
    out["checks"] = checks
    return out


def _read_trace(path: str, span_names) -> dict:
    import glob
    import shutil

    import tracing

    try:
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if len(found) != 1:
            raise RuntimeError(f"{len(found)} traces under {path}")
        return tracing.load(found[0], span_names)
    finally:
        shutil.rmtree(path, ignore_errors=True)


def main(argv, *, t0: float) -> int:
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace),
                  t0=t0)
    except Refused as e:
        print(f"bench: refused: {e}", file=sys.stderr)
        return 3
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0
