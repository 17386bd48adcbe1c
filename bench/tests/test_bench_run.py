"""The harness end to end on the CPU at tiny sizes: traffic, reference,
the result line, and the refusals.  The measurement path itself refuses a
host without a TPU, so these runs call ``harness.run`` with
``require_tpu=False``; no number from them is a device number."""
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import gen  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402

TINY = {"stream_rbf_resnet18.observe": {"d": 256},
        "stream_rbf_resnet18.serve": {"d": 256},
        "fleet_rbf_d4k.mixed": {"d": 64, "tenants": 4, "lanes": 4}}
BIG_SEED = 2 ** 31 + 987_654_321


def tiny_run(workload, seed=BIG_SEED, seconds=0.5, **kw):
    return harness.run(workload, seed, seconds, False,
                       t0=time.perf_counter(), require_tpu=False,
                       overrides=TINY[workload], cache=False, **kw)


def test_generator_is_a_function_of_the_seed():
    a = gen.Problem(BIG_SEED, 64).observation(3)
    b = gen.Problem(BIG_SEED, 64).observation(3)
    c = gen.Problem(BIG_SEED + 1, 64).observation(3)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    assert not np.array_equal(np.asarray(a[0]), np.asarray(c[0]))
    assert a[0].dtype == np.float32 and a[0].shape == (64,)
    with pytest.raises(ValueError):
        gen.base_key(2 ** 64)


def dense_posterior(X, G, Xq, lam, noise):
    """The textbook (ND, ND) Gram solve, block by block."""
    n, d = X.shape
    K = np.zeros((n * d, n * d))
    for i in range(n):
        for j in range(n):
            dij = X[i] - X[j]
            k = np.exp(-0.5 * lam * dij @ dij)
            K[i * d:(i + 1) * d, j * d:(j + 1) * d] = k * (
                lam * np.eye(d) - lam ** 2 * np.outer(dij, dij))
    Z = np.linalg.solve(K + noise * np.eye(n * d), G.ravel()).reshape(n, d)
    value, grad = [], []
    for q in Xq:
        dq = q - X
        k = np.exp(-0.5 * lam * np.sum(dq * dq, axis=1))
        m = np.sum(dq * Z, axis=1)
        value.append(np.sum(k * lam * m))
        grad.append(lam * (k @ Z) - lam ** 2 * ((k * m) @ dq))
    return np.array(value), np.array(grad)


@pytest.mark.parametrize("n,d", [(1, 5), (4, 6), (8, 9)])
def test_reference_matches_the_dense_solve(n, d):
    rng = np.random.RandomState(n * 100 + d)
    X, G = rng.randn(n, d), rng.randn(n, d)
    Xq = X[:1] + 0.3 * rng.randn(3, d)
    lam = 1.0 / d
    want = dense_posterior(X, G, Xq, lam, 0.01 * lam)
    got = reference.posterior(X, G, Xq, lam, 0.01 * lam)
    assert reference.rel_err(got[0], want[0]) < 1e-11
    assert reference.rel_err(got[1], want[1]) < 1e-11


@pytest.mark.parametrize("workload", sorted(TINY))
def test_cell_runs_and_is_correct(workload):
    out = tiny_run(workload)
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    spec = harness.cell_spec(workload)
    assert set(out["checks"]) == set(spec["config"]["limits"])
    assert set(out["metrics"]) == {m["name"] for m in spec["end_to_end"]}
    for m in spec["end_to_end"]:
        assert out["metrics"][m["name"]]["unit"] == m["unit"]
        assert out["metrics"][m["name"]]["value"] > 0
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    json.dumps(out)


def test_main_prints_checks_last_and_one_json_line(monkeypatch, capsys):
    canned = {"correct": True, "attempted": 3, "failed": 0,
              "metrics": {"step_s": {"value": 1.5, "unit": "s"}},
              "device": {"platform": "tpu", "kind": "TPU v5 lite",
                         "count": 1, "memory_peak_bytes": 1},
              "checks": {"value_rel_err": {"value": 1e-6, "limit": 1e-4}}}
    monkeypatch.setattr(harness, "run", lambda *a, **k: canned)
    rc = harness.main(["--workload", "x", "--seed", "1", "--seconds", "1",
                       "--trace", "0"], t0=0.0)
    cap = capsys.readouterr()
    assert rc == 0
    assert json.loads(cap.out.strip().splitlines()[-1]) == canned
    assert cap.err.strip().splitlines()[-1] == \
        "check value_rel_err 1e-06 limit 0.0001"


def test_run_refuses_a_host_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "stream_rbf_resnet18.serve", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    """A checkout with only BENCHMARK.json and bench/ has no program."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    code = ("import sys; sys.path.insert(0, 'bench'); import harness\n"
            "try:\n    harness.import_program(False)\n"
            "except harness.Refused:\n    sys.exit(7)\n")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 7, p.stderr[-2000:]
