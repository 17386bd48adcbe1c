"""Each fault a cell can have, planted under the timed path after set-up
(``bench/faults.py``), makes ``correct`` come out false: a step that
leaves the state unchanged, half of the batch left out, an answer altered
where it is produced (its gradient or its value), a stale value.  No cell
spans chips, so none can leave out an exchange.  CPU, tiny sizes, the
chip check skipped (``require_tpu=False``)."""
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import faults  # noqa: E402
import harness  # noqa: E402

TINY = {"stream_rbf_resnet18.observe": {"d": 256, "window": 3},
        "stream_rbf_resnet18.serve": {"d": 256, "window": 3},
        "fleet_rbf_d4k.mixed": {"d": 64, "window": 3, "tenants": 4,
                                "lanes": 4}}
# the serve cell sends no extend, so its state cannot be left unchanged
CASES = [(w, k) for w in sorted(TINY) for k in faults.KINDS
         if not (w.endswith(".serve") and k == "state_unchanged")]


@pytest.mark.parametrize("workload,kind", CASES,
                         ids=[f"{w}-{k}" for w, k in CASES])
def test_fault_makes_the_run_incorrect(workload, kind):
    # a window of some steps: a stale window shows from a tenant's second
    # request after the fault
    out = harness.run(workload, 2 ** 33 + 17, 1.0, False,
                      t0=time.perf_counter(), require_tpu=False,
                      overrides=TINY[workload], cache=False, fault=kind)
    assert out["correct"] is False, (out["checks"], out["attempted"])
    assert set(out["readings"]["program"]) >= set(out["checks"])


def test_unknown_fault_is_refused():
    with pytest.raises(ValueError):
        faults.plant("no_such_fault", object())
