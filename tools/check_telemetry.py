"""Telemetry smoke gate: validate one run's REPRO_OBS JSONL log.

CI runs an instrumented workload (``examples/streaming_bo.py --smoke``
with ``REPRO_OBS=on REPRO_OBS_JSONL=<log>``), then gates on this script:

    python tools/check_telemetry.py <log.jsonl> [--allow-recompile]
                                    [--require-span NAME ...]
                                    [--expect-regime-switch-at N]
                                    [--expect-recovery]

Checks (each failure is one line on stderr; exit 1 on any):

  * every line parses as a JSON object with a ``type``;
  * required spans occurred (default: ``state.extend``, ``serve.query``)
    and no span has a negative duration;
  * the recompile sentinel stayed clean: no ``compile`` event with
    ``nth > 1`` (``--allow-recompile`` downgrades this for workloads
    that legitimately re-trace, e.g. after ``jax.clear_caches()``);
  * a final ``snapshot`` event exists and carries the core counters
    (extend calls, pivot fallbacks, serve requests, solver-cache misses,
    serve-step compiles);
  * the snapshot counters are self-consistent with the event stream
    (``state.extend_calls`` == number of ``state.extend`` span events;
    ``serve.requests`` == number of ``serve.query`` span events);
  * with ``--expect-regime-switch-at N``: the run's FIRST
    ``{"type": "regime", "event": "switch", ..., "to": "iterative"}``
    event fired at exactly n == N — the analytic crossover of the
    regime cost model (``repro.regime.policy``) agreeing with the live
    stream is what makes the flop model auditable, not advisory.

The log must come from ONE process run (the sink appends; point each run
at a fresh file, as CI does).
"""
from __future__ import annotations

import argparse
import json
import sys

DEFAULT_REQUIRED_SPANS = ("state.extend", "serve.query")

REQUIRED_COUNTERS = (
    "state.extend_calls",
    "state.refactor_fallback",
    "serve.requests",
    "serve.solver_cache.misses",
    "compile.gp_serve_step.compiles",
)


def check(path: str, *, required_spans=DEFAULT_REQUIRED_SPANS,
          allow_recompile: bool = False,
          expect_regime_switch_at: int | None = None,
          expect_recovery: bool = False) -> list[str]:
    """Validate one telemetry log; return a list of failure strings."""
    failures: list[str] = []
    events: list[dict] = []
    try:
        with open(path, encoding="utf-8") as fh:
            for lineno, line in enumerate(fh, 1):
                line = line.strip()
                if not line:
                    continue
                try:
                    ev = json.loads(line)
                except json.JSONDecodeError as e:
                    failures.append(f"line {lineno}: malformed JSON ({e})")
                    continue
                if not isinstance(ev, dict) or "type" not in ev:
                    failures.append(f"line {lineno}: event without a 'type'")
                    continue
                events.append(ev)
    except OSError as e:
        return [f"cannot read {path}: {e}"]
    if not events:
        return [f"{path}: no telemetry events"]

    spans = [e for e in events if e.get("type") == "span"]
    span_names = [e.get("name") for e in spans]
    for name in required_spans:
        if name not in span_names:
            failures.append(f"required span never recorded: {name}")
    for e in spans:
        if not isinstance(e.get("dur_s"), (int, float)) or e["dur_s"] < 0:
            failures.append(f"span {e.get('path')}: bad duration "
                            f"{e.get('dur_s')!r}")

    recompiles = [e for e in events
                  if e.get("type") == "compile" and e.get("nth", 1) > 1]
    if recompiles and not allow_recompile:
        for e in recompiles:
            failures.append(
                f"recompile-sentinel violation: watch={e.get('watch')} "
                f"traced a seen signature again (nth={e.get('nth')})")

    if expect_regime_switch_at is not None:
        switches = [e for e in events
                    if e.get("type") == "regime"
                    and e.get("event") == "switch"
                    and e.get("to") == "iterative"]
        if not switches:
            failures.append(
                "no regime switch to 'iterative' recorded (expected one "
                f"at n={expect_regime_switch_at})")
        else:
            first = switches[0]
            if int(first.get("n", -1)) != int(expect_regime_switch_at):
                failures.append(
                    "regime switch fired off-model: first exact->iterative "
                    f"at n={first.get('n')} but the cost model says "
                    f"n={expect_regime_switch_at} "
                    f"(crossover_n={first.get('crossover_n')})")

    snaps = [e for e in events if e.get("type") == "snapshot"]
    if not snaps:
        failures.append("no final registry snapshot (trace.flush() missing)")
        return failures
    snap = snaps[-1]
    counters = snap.get("counters", {})
    for name in REQUIRED_COUNTERS:
        if name not in counters:
            failures.append(f"snapshot missing required counter: {name}")

    if expect_recovery:
        # the chaos-drill gate: every injected fault was recovered (the
        # totals MATCH — a drill that injected nothing proves nothing),
        # and recovery never triggered a recompile (checked above; this
        # flag refuses --allow-recompile as a matter of policy)
        inj = int(counters.get("resilience.faults_injected", 0))
        rec = int(counters.get("resilience.faults_recovered", 0))
        if "resilience.faults_injected" not in counters:
            failures.append("--expect-recovery: no "
                            "resilience.faults_injected counter (the "
                            "chaos injector never ran)")
        elif inj == 0:
            failures.append("--expect-recovery: zero faults injected — "
                            "the drill proved nothing")
        elif inj != rec:
            failures.append(
                f"--expect-recovery: injected {inj} != recovered {rec} "
                "(an unhandled fault class, or double-counted recovery)")
        if allow_recompile:
            failures.append("--expect-recovery is incompatible with "
                            "--allow-recompile: zero-recompile recovery "
                            "IS the claim under test")

    # self-consistency: the registry's call counters must agree with the
    # number of span events the same call sites emitted
    for counter, span_name in (("state.extend_calls", "state.extend"),
                               ("serve.requests", "serve.query")):
        if counter in counters:
            n_events = span_names.count(span_name)
            if int(counters[counter]) != n_events:
                failures.append(
                    f"counter/span mismatch: {counter}={counters[counter]} "
                    f"but {n_events} '{span_name}' span events")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("log", help="path to the REPRO_OBS_JSONL file")
    ap.add_argument("--allow-recompile", action="store_true",
                    help="do not fail on compile events with nth > 1")
    ap.add_argument("--require-span", action="append", default=None,
                    metavar="NAME",
                    help="span name that must appear (repeatable; default: "
                         + ", ".join(DEFAULT_REQUIRED_SPANS) + ")")
    ap.add_argument("--expect-regime-switch-at", type=int, default=None,
                    metavar="N",
                    help="assert the first exact->iterative regime switch "
                         "event fired at exactly this n (the modeled "
                         "crossover)")
    ap.add_argument("--expect-recovery", action="store_true",
                    help="assert resilience.faults_injected == "
                         "resilience.faults_recovered > 0 and zero "
                         "recompiles (the chaos-drill gate)")
    args = ap.parse_args(argv)
    required = tuple(args.require_span) if args.require_span \
        else DEFAULT_REQUIRED_SPANS
    failures = check(args.log, required_spans=required,
                     allow_recompile=args.allow_recompile,
                     expect_regime_switch_at=args.expect_regime_switch_at,
                     expect_recovery=args.expect_recovery)
    if failures:
        for f in failures:
            print(f"TELEMETRY FAIL: {f}", file=sys.stderr)
        return 1
    print(f"telemetry OK: {args.log}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
