"""The control comes out not correct: the reference in the program's
place, at three bf16 passes per product (``posterior_control``), read by
the same check against the float64 oracle and the configuration's limits,
at a size a test run holds (CPU, d = 4096).  The program itself passes."""
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import harness  # noqa: E402


@pytest.mark.parametrize("workload,sizes", [
    ("stream_rbf_resnet18.observe", {"d": 4096}),
    ("fleet_rbf_d4k.mixed", {"tenants": 4, "lanes": 4}),
])
def test_control_is_not_correct(workload, sizes):
    out = harness.run(workload, 2 ** 32 + 77, 0.5, False,
                      t0=time.perf_counter(), require_tpu=False,
                      overrides=sizes, cache=False, control=True)
    limits = harness.cell_spec(workload)["config"]["limits"]
    program, control = out["readings"]["program"], out["readings"]["control"]
    assert out["correct"] is True
    assert all(program[k]["value"] <= lim for k, lim in limits.items())
    assert any(control[k]["value"] > lim for k, lim in limits.items()), \
        control
