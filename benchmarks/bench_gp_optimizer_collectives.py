"""The systems thesis bench: the GP-H optimizer's collective footprint on
the production mesh vs the gradient all-reduce it rides on.

Lowered on 8 host devices (subprocess-free: this bench re-execs itself
with the device-count flag if needed), the train step is compiled twice —
momentum vs gp — and the per-step collective bytes are compared. The
paper's structure guarantees the GP addition is O(history^2) bytes,
independent of D; the gradient all-reduce is O(D).
"""
import json
import os
import subprocess
import sys

_SRC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.launch.mesh import make_test_mesh
from repro.optim import get_optimizer
from repro.train import build_train_step
from repro.utils.hlo_cost import analyze_hlo

mesh = make_test_mesh((2, 4), ("data", "model"))
cfg = get_config("gemma3-1b", smoke=True)
out = {}
for name in ["momentum", "gp", "gp_tree"]:
    if name == "momentum":
        opt = get_optimizer(name, lr=1e-3)
    elif name == "gp":
        opt = get_optimizer("gp", lr=1.0, history=6, pad_to=8)
    else:
        opt = get_optimizer("gp_tree", lr=1.0, history=6)
    b = build_train_step(cfg, opt, mesh, shape="smoke_train", donate=False)
    hlo = b.step.lower(b.abstract_params, b.abstract_opt_state,
                       b.abstract_batch).compile().as_text()
    c = analyze_hlo(hlo)
    out[name] = {"collective_bytes": c.coll_bytes,
                 "by_kind": {k: v for k, v in c.coll_by_kind.items()}}
d = sum(x.size for x in jax.tree_util.tree_leaves(
    jax.eval_shape(lambda r: None, 0) or []) ) if False else 0
out["gp_overhead_fraction"] = (out["gp"]["collective_bytes"] -
    out["momentum"]["collective_bytes"]) / \
    max(out["momentum"]["collective_bytes"], 1)
out["gp_tree_overhead_fraction"] = (out["gp_tree"]["collective_bytes"] -
    out["momentum"]["collective_bytes"]) / \
    max(out["momentum"]["collective_bytes"], 1)
print("RESULT" + json.dumps(out))
"""


def run() -> dict:
    r = subprocess.run(
        [sys.executable, "-c", _SRC], capture_output=True, text=True,
        timeout=900,
        # the child only lowers on 8 fake host devices: it must never
        # reach for the accelerator the parent process may hold
        env={"PYTHONPATH": "src", "PATH": os.environ.get("PATH", ""),
             "JAX_PLATFORMS": "cpu"})
    for line in r.stdout.splitlines():
        if line.startswith("RESULT"):
            out = json.loads(line[len("RESULT"):])
            out["paper_claim"] = (
                "pytree-native GP-H adds ~O(m^2) collective bytes on top "
                "of the grad all-reduce; the flat-vector variant pays an "
                "extra O(D) reshard (kept as the measured baseline)")
            out["claim_holds"] = bool(
                out["gp_tree_overhead_fraction"] <
                0.5 * max(out["gp_overhead_fraction"], 0.1))
            return out
    return {"error": r.stdout[-500:] + r.stderr[-2000:], "claim_holds": False}


if __name__ == "__main__":
    print(json.dumps(run(), indent=2))
