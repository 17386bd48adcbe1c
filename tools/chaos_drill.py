"""Chaos drill: inject every serve fault class, prove every one recovers.

CI's ``chaos`` job runs this under full telemetry and gates the log:

    REPRO_OBS=on REPRO_OBS_JSONL=/tmp/chaos.jsonl \
        PYTHONPATH=src python tools/chaos_drill.py
    python tools/check_telemetry.py /tmp/chaos.jsonl --expect-recovery

The drill exercises, in one process (one telemetry log):

  happy path          a ``GPServeBundle`` extend/query workload — the
                      required core counters/spans (``state.extend``,
                      ``serve.query``) come from here, so the
                      gate proves chaos rode on a REAL serving stack;
  nan_payload         corrupted observations rejected at admission with a
                      typed error (server path);
  kill_step           a killed serve step absorbed by bounded retry;
  straggler           a parked request expired by the deadline sweep;
  degenerate_factor   a poisoned Cholesky healed by the jitter ladder
                      inside ``extend``'s post-mutation watchdog;
  cg_divergence       a poisoned warm start caught by the CG watchdog,
                      answered by the exact solver;
  crash               the live state destroyed mid-trajectory, restored
                      bit-identically from snapshot + journal tail.

Accounting contract (asserted by ``--expect-recovery``): every injection
bumps ``resilience.faults_injected`` exactly once, every handler bumps
``resilience.faults_recovered`` exactly once, and recovery triggers ZERO
recompiles of the serving executables.
"""
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir, "src"))

import jax

jax.config.update("jax_enable_x64", True)
import numpy as np

from repro.core import get_kernel
from repro.core.state import GPGState
from repro.obs import trace as obs
from repro.resilience import (ChaosInjector, Journal, errors, guardrails,
                              replay_single, restore, take_snapshot)
from repro.train.serve import GPFleetServer, build_gp_serve_step

D = 6
WINDOW = 4


def happy_path() -> None:
    """An uninjected serve workload: the telemetry gate's core counters."""
    st = GPGState("rbf", D, window=WINDOW, noise=1e-6)
    bundle = build_gp_serve_step(st, microbatch=4, return_std=True)
    r = np.random.RandomState(0)
    for _ in range(WINDOW + 2):
        st.extend(r.randn(D), r.randn(D))
    for _ in range(3):
        out = bundle.query(r.randn(3, D))
        assert np.all(np.isfinite(np.asarray(out.value)))


def drill_nan_payload() -> None:
    srv = GPFleetServer(kernel="rbf", d=D,
                        injector=ChaosInjector(
                            seed=1, rates={"nan_payload": 1.0}, max_faults=2))
    srv.connect("t0")
    r = np.random.RandomState(1)
    for _ in range(2):
        q = srv.submit("t0", "extend", (r.randn(D), r.randn(D)))
        assert isinstance(q.result, errors.NonFiniteObservationError)
    srv.injector = None                 # clean op proves the tenant lives
    srv.submit("t0", "extend", (r.randn(D), r.randn(D)))
    srv.drain()
    assert srv.fleet.n("t0") == 1


def drill_kill_step() -> None:
    srv = GPFleetServer(kernel="rbf", d=D,
                        injector=ChaosInjector(
                            seed=2, rates={"kill_step": 1.0}, max_faults=2))
    srv.connect("t0")
    r = np.random.RandomState(2)
    req = srv.submit("t0", "extend", (r.randn(D), r.randn(D)))
    srv.drain()
    assert req.done and req.result is None      # retries absorbed both kills
    assert srv.fleet.n("t0") == 1


def drill_straggler() -> None:
    from repro.configs.paper_gp import GPFleetConfig

    srv = GPFleetServer(kernel="rbf", d=D,
                        config=GPFleetConfig(deadline_steps=2),
                        injector=ChaosInjector(
                            seed=3, rates={"straggler": 1.0}, max_faults=1))
    srv.connect("t0")
    req = srv.submit("t0", "query", np.zeros((1, D)))
    for _ in range(4):
        srv.step()
    assert isinstance(req.result, errors.DeadlineExceededError)


def drill_degenerate_factor() -> None:
    st = GPGState("rbf", D, window=WINDOW, noise=1e-6)
    r = np.random.RandomState(4)
    for _ in range(3):
        st.extend(r.randn(D), r.randn(D))
    inj = ChaosInjector(seed=4, rates={"degenerate_factor": 1.0})
    assert inj.poison_factor(st)
    st.extend(r.randn(D), r.randn(D))   # the watchdog heals inside here
    assert guardrails.factor_ok(st)


def drill_cg_divergence() -> None:
    from repro.core import build_factors
    from repro.regime import solve

    spec = get_kernel("rbf")
    r = np.random.RandomState(5)
    n = 9                               # n > d: the iterative regime
    X, G = r.randn(n, D), r.randn(n, D)
    f = build_factors(spec, X, lam=0.7, noise=1e-6)
    inj = ChaosInjector(seed=5)
    z0 = inj.poison_warm_start((n, D))
    Z, info = solve(spec, f, G, policy="iterative", z0=z0, maxiter=4)
    assert info["fallback"] is True
    assert np.all(np.isfinite(np.asarray(Z)))


def drill_crash(root: str) -> None:
    jpath = os.path.join(root, "ops.jsonl")
    st = GPGState("rbf", D, window=WINDOW, noise=1e-6)
    j = Journal(jpath)
    r = np.random.RandomState(6)
    for _ in range(2):
        x, g = r.randn(D), r.randn(D)
        st.extend(x, g)
        j.record("extend", payload={"x": x, "g": g})
    take_snapshot(st, root, step=2, journal=j)
    for _ in range(2):                  # the journal tail past the snapshot
        x, g = r.randn(D), r.randn(D)
        st.extend(x, g)
        j.record("extend", payload={"x": x, "g": g})
    want_Z = np.asarray(st.data.Z).copy()
    inj = ChaosInjector(seed=6)
    inj.record("crash", step=4)
    del st                              # the process state is gone
    back = restore(root)
    replay_single(back, Journal.since_snapshot(Journal.read(jpath)))
    assert np.array_equal(np.asarray(back.data.Z), want_Z), \
        "crash recovery was not bit-identical"
    guardrails.record_recovery("crash", restored_step=2)


def main() -> int:
    if not obs.enabled():
        print("chaos_drill: run with REPRO_OBS=on REPRO_OBS_JSONL=<log> "
              "(the drill exists to produce a gateable telemetry log)",
              file=sys.stderr)
        return 2
    happy_path()
    drill_nan_payload()
    drill_kill_step()
    drill_straggler()
    drill_degenerate_factor()
    drill_cg_divergence()
    with tempfile.TemporaryDirectory() as td:
        drill_crash(td)
    snap = obs.snapshot()["counters"]
    inj = int(snap.get("resilience.faults_injected", 0))
    rec = int(snap.get("resilience.faults_recovered", 0))
    print(f"chaos drill: {inj} faults injected, {rec} recovered")
    obs.flush()
    return 0 if inj == rec and inj > 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
