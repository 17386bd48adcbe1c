"""The correctness check's readings for one cell, on the chip.

    python bench/control.py --workload <cell> --seconds <s> --seeds 11 12 13
        [--fault <kind>] [--backend jnp]

For each seed, one run of the cell as ``bench/run.py`` makes it (set-up,
a window of ``--seconds``, the sampled answers against the float64
oracle), and beside the program's numbers the control's: the reference
in the program's place, in float32 at three bf16 passes per product
(``reference.posterior_control``), on the same inputs.  With ``--fault``
the program runs with that fault planted under the timed path
(``bench/faults.py``) and no control is read; with ``--backend`` it runs
on that backend of its own (``REPRO_BACKEND``), a second witness.  All
seeds run in one process; one JSON line per seed, then a summary line
with, per number, the largest program reading and the smallest control
reading.  The benchmark's own runs never run any of these.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="bench/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--backend", default=None)
    args = ap.parse_args(argv)
    env = {"REPRO_BACKEND": args.backend} if args.backend else None
    lower: dict = {}
    upper: dict = {}
    t0 = T0
    for seed in args.seeds:
        try:
            out = harness.run(args.workload, seed, args.seconds, False,
                              t0=t0, control=args.fault is None,
                              fault=args.fault, program_env=env)
        except harness.Refused as e:
            print(f"bench: refused: {e}", file=sys.stderr)
            return 3
        got = {side: {k: c["value"] for k, c in r.items()}
               for side, r in out["readings"].items()}
        for k, v in got["program"].items():
            lower[k] = max(lower.get(k, 0.0), v)
        for k, v in got.get("control", {}).items():
            upper[k] = min(upper.get(k, float("inf")), v)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "metrics": out["metrics"], **got}), flush=True)
        t0 = time.perf_counter()
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "fault": args.fault, "backend": args.backend,
                      "program_max": lower, "control_min": upper}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
