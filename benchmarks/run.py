"""Benchmark orchestrator: one module per paper table/figure + systems
benches. ``PYTHONPATH=src python -m benchmarks.run [--only a,b]``.
A bench that raises makes the run exit non-zero.

Each bench returns a dict with a ``claim_holds`` verdict tying the
measurement back to the paper's statement; the summary table at the end is
the reproduction scorecard.

``--check`` turns the committed ``BENCH_*.json`` baselines into a
regression gate: fresh results are diffed against them and any claim
metric that regresses by more than ``CHECK_TOLERANCE`` (20%) — byte
ratios/totals growing, error metrics growing past a floating-point jitter
floor, a ``claim_holds`` flipping to false — fails the run.  Wall-clock
and GB/s columns are excluded (machine-dependent noise); the gated
metrics are the deterministic models and accuracy numbers that define the
perf story.
"""
import argparse
import json
import time
import traceback

CHECK_TOLERANCE = 0.20      # fail on > 20% regression of a claim metric
_ERR_FLOOR = 1e-5           # abs floor under which error metrics are noise


def _is_claim_metric(key: str) -> bool:
    # "unfused_*" is the baseline side of a model, not a deliverable
    return (key == "claim_holds" or key == "ratio" or key.endswith("_err")
            or (key.endswith("_bytes") and not key.startswith("unfused"))
            or key.endswith("_rel") or key.startswith("ratio_"))


def _walk_regressions(base, fresh, path, failures):
    """Recursively diff claim metrics; append (path, old, new) regressions.

    Higher is worse for every gated numeric metric (byte counts/ratios and
    error magnitudes); ``claim_holds`` must not flip true -> false.
    Structure drift (new/removed keys) is NOT a failure — baselines are
    refreshed by committing the new JSON.
    """
    if isinstance(base, dict) and isinstance(fresh, dict):
        for k in base:
            if k == "telemetry":
                # observability sections are machine/run-dependent (and
                # full of *_bytes gauge names) — never regression-gated
                continue
            if k in fresh:
                _walk_regressions(base[k], fresh[k], path + (str(k),),
                                  failures)
        return
    if isinstance(base, list) and isinstance(fresh, list):
        for i, (b, f) in enumerate(zip(base, fresh)):
            _walk_regressions(b, f, path + (str(i),), failures)
        return
    key = path[-1] if path else ""
    # a metric is gated by its own key, or by sitting inside a gated
    # container (e.g. the per-kernel entries of bf16_vs_f32_oracle_rel)
    if not (_is_claim_metric(key)
            or any(_is_claim_metric(p) for p in path[:-1])):
        return
    if not _is_claim_metric(key):
        key = next(p for p in path if _is_claim_metric(p))
    # null/absent metrics are "not measured here", never a regression:
    # interpret-mode baselines carry e.g. ``pallas_seconds: null`` and a
    # compiled column must not trip against them (nor vice versa)
    if base is None or fresh is None:
        return
    if isinstance(base, bool) or isinstance(fresh, bool):
        if base is True and fresh is not True:
            failures.append((".".join(path), base, fresh))
        return
    if isinstance(base, (int, float)) and isinstance(fresh, (int, float)):
        limit = base * (1.0 + CHECK_TOLERANCE)
        if key.endswith("_err") or key.endswith("_rel"):
            limit = max(limit, _ERR_FLOOR)
        if fresh > limit:
            failures.append((".".join(path), base, fresh))


def check_against_baselines(results: dict, root: str) -> list:
    """Diff fresh results vs the committed BENCH_*.json; list regressions."""
    import os

    failures = []
    for key in PERF_TRACKED:
        if key not in results:
            continue
        base_path = os.path.join(root, f"BENCH_{key}.json")
        if not os.path.exists(base_path):
            continue    # first run for this bench: nothing to regress from
        with open(base_path) as f:
            base = json.load(f)
        _walk_regressions(base, results[key], (key,), failures)
    return failures

BENCHES = [
    ("fig2_linalg", "benchmarks.bench_fig2_linalg",
     "Fig. 2: CG vs GP-X vs GP-H on 100-D quadratic"),
    ("fig3_rosenbrock", "benchmarks.bench_fig3_rosenbrock",
     "Fig. 3: Alg. 1 vs BFGS on relaxed 100-D Rosenbrock"),
    ("fig4_surface", "benchmarks.bench_fig4_surface",
     "Fig. 4/Sec 5.2: N>D matrix-free CG + surface recovery"),
    ("fig5_hmc", "benchmarks.bench_fig5_hmc",
     "Fig. 5/Sec 5.3: GPG-HMC vs HMC acceptance + budget"),
    ("scaling", "benchmarks.bench_scaling",
     "Sec. 2.3: O(D)-linear exact inference"),
    ("memory", "benchmarks.bench_memory",
     "Sec. 2.3/5.2: storage 74GB -> 25MB"),
    ("iterative", "benchmarks.bench_iterative",
     "Sec. 2.3: free Kronecker preconditioner"),
    ("kernels", "benchmarks.bench_kernels",
     "Pallas kernels vs oracles + throughput"),
    ("gp_collectives", "benchmarks.bench_gp_optimizer_collectives",
     "DESIGN 2: GP optimizer collective footprint"),
    ("hyper", "benchmarks.bench_hyper",
     "DESIGN 11: structured exact MLL + hyperparameter fit"),
    ("distributed", "benchmarks.bench_distributed",
     "DESIGN 14: D-sharded state machine O(N^2)-byte collectives"),
    ("fleet", "benchmarks.bench_fleet",
     "DESIGN 15: multi-tenant vmapped fleet + continuous batching"),
    ("regime", "benchmarks.bench_regime",
     "DESIGN 16: regime crossover, Krylov posterior + SLQ past N<D"),
    ("resilience", "benchmarks.bench_resilience",
     "DESIGN 17: bitwise snapshot/journal recovery + zero-cost guardrails"),
]

# Benches whose JSON lands at the repo root for cross-PR tracking; also
# the set --check regresses against.
PERF_TRACKED = ("kernels", "iterative", "hyper", "distributed", "fleet",
                "regime", "resilience")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--out", default="results/bench.json")
    ap.add_argument("--strict", action="store_true",
                    help="exit nonzero if any executed claim gate fails "
                         "(used by CI to enforce the perf/repro gates)")
    ap.add_argument("--check", action="store_true",
                    help="regression gate: diff fresh results against the "
                         "committed BENCH_*.json baselines and exit nonzero "
                         "on a >20%% regression of any claim metric")
    args = ap.parse_args()

    from repro.utils import compile_cache

    compile_cache.enable()

    # with REPRO_OBS=on each bench row grows a ``telemetry`` section (the
    # registry delta across the bench: CG iterations, fallbacks, spans);
    # --check skips the subtree, so telemetry never gates perf
    from repro.obs import trace as obs

    obs_on = obs.enabled()

    results = {}
    raised = []
    for key, module, desc in BENCHES:
        if args.only and key not in args.only.split(","):
            continue
        t0 = time.time()
        print(f"=== {key}: {desc}", flush=True)
        snap = obs.snapshot() if obs_on else None
        try:
            mod = __import__(module, fromlist=["run"])
            r = mod.run()
            r["_seconds"] = round(time.time() - t0, 1)
            if obs_on:
                r["telemetry"] = obs.REGISTRY.delta(snap)
            results[key] = r
            print(json.dumps(r, indent=1, default=str), flush=True)
        except Exception as e:  # noqa: BLE001
            results[key] = {"error": str(e), "claim_holds": False,
                            "_trace": traceback.format_exc()[-1500:]}
            raised.append(key)
            print(f"ERROR {e}", flush=True)
    if obs_on:
        obs.flush()     # final registry snapshot into the JSONL sink

    print("\n===== reproduction scorecard =====")
    for key, module, desc in BENCHES:
        if key in results:
            v = results[key].get("claim_holds")
            print(f"  {key:18s} {'PASS' if v else 'FAIL':4s}  {desc}")
    import os

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1, default=str)
    # Regression gate BEFORE the baselines are overwritten below.
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    regressions = check_against_baselines(results, root) if args.check else []
    if regressions:
        print(f"\n===== --check: {len(regressions)} claim-metric "
              f"regression(s) vs committed baselines =====")
        for path, old, new in regressions:
            print(f"  REGRESSED {path}: {old} -> {new}")
    elif args.check:
        print("\n--check: no claim-metric regressions vs committed baselines")
    # Per-PR perf trajectory: the roofline-scored benches land at the repo
    # root so successive PRs can diff them (CI uploads them as artifacts).
    # NEVER overwrite the baselines with results that just failed the
    # regression gate — a rerun would then compare regressed-vs-regressed
    # and pass, masking the regression.
    if regressions:
        print("(baselines left untouched — fix the regression or commit "
              "new baselines deliberately with a run without --check)")
    else:
        for key in PERF_TRACKED:
            if key in results:
                with open(os.path.join(root, f"BENCH_{key}.json"), "w") as f:
                    json.dump(results[key], f, indent=1, default=str)
    n_fail = sum(1 for r in results.values() if not r.get("claim_holds"))
    print(f"\n{len(results) - n_fail}/{len(results)} claims hold")
    if raised:
        print(f"benches that raised: {', '.join(raised)}")
    if raised or (args.strict and n_fail) or regressions:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
