import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import/init: jax locks device count on first use.
# (No `from __future__` here — the env var lines above must stay first.)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. builds train_step (train_*), prefill (prefill_*) or serve/decode step
     (decode_* / long_*) with full sharding annotations,
  3. .lower(<ShapeDtypeStructs>).compile()  — no arrays are ever allocated,
  4. records memory_analysis(), cost_analysis(), per-collective byte counts
     parsed from the partitioned HLO, and the three roofline terms
     (EXPERIMENTS.md §Dry-run / §Roofline).

Usage:
  python -m repro.launch.dryrun --arch gemma3-4b --shape train_4k --mesh single
  python -m repro.launch.dryrun --arch all --shape all --mesh both --out r.json
"""
import argparse
import json
import time
import traceback

import jax
import numpy as np

from repro.configs import ARCHS, get_config, get_optimizer_name
from repro.launch.mesh import make_production_mesh
from repro.models import SHAPES, batch_specs, shape_applicable
from repro.optim import get_optimizer
from repro.train import build_decode_step, build_prefill_step, build_train_step
from repro.utils import roofline_terms
from repro.utils.hlo_cost import analyze_hlo
from repro.utils.roofline import chip_for

ASSIGNED_SHAPES = ("train_4k", "prefill_32k", "decode_32k", "long_500k")
HBM_BYTES = 16e9            # v5e per-chip HBM
# The dry run compiles on host devices and models the chip it targets.
TARGET_DEVICE_KIND = "TPU v5 lite"
TRAIN_MICROBATCHES = 8


def _active_params(pa) -> tuple[float, float]:
    """(total, active) param counts from the abstract tree; routed-expert
    weights count as active * top_k / n_experts (handled by caller)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(pa)
    total = routed = 0.0
    for path, leaf in flat:
        n = float(np.prod(leaf.shape))
        total += n
        keys = [str(getattr(p, "key", "")) for p in path]
        if any(k in ("w_gate", "w_up", "w_down") for k in keys):
            routed += n
    return total, routed


def model_flops_of(cfg, pa, shape_name: str) -> float:
    ss = SHAPES[shape_name]
    total, routed = _active_params(pa)
    if cfg.n_experts:
        active = total - routed + routed * cfg.top_k / cfg.n_experts
    else:
        active = total
    if ss.kind == "train":
        tokens = ss.global_batch * ss.seq_len
        per_tok = 6.0
    elif ss.kind == "prefill":
        tokens = ss.global_batch * ss.seq_len
        per_tok = 2.0
    else:                       # decode: one token per sequence
        tokens = ss.global_batch
        per_tok = 2.0
    return per_tok * active * tokens


def build_cell(arch: str, shape_name: str, mesh):
    cfg = get_config(arch)
    ss = SHAPES[shape_name]
    if ss.kind == "train":
        opt = get_optimizer(get_optimizer_name(arch))
        b = build_train_step(cfg, opt, mesh, shape=shape_name,
                             microbatches=TRAIN_MICROBATCHES)
        args = (b.abstract_params, b.abstract_opt_state, b.abstract_batch)
        return b.step, args, b.abstract_params
    if ss.kind == "prefill":
        b = build_prefill_step(cfg, mesh, shape=shape_name)
        return b.step, (b.abstract_params,) + b.abstract_inputs, \
            b.abstract_params
    b = build_decode_step(cfg, mesh, shape=shape_name)
    return b.step, (b.abstract_params,) + b.abstract_inputs, b.abstract_params


def run_cell(arch: str, shape_name: str, mesh_kind: str) -> dict:
    cfg = get_config(arch)
    ok, reason = shape_applicable(cfg, shape_name)
    row = {"arch": arch, "shape": shape_name, "mesh": mesh_kind}
    if not ok:
        row.update(status="skipped", reason=reason)
        return row
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
        chips = int(np.prod(mesh.devices.shape))
        with mesh:
            step, args, pa = build_cell(arch, shape_name, mesh)
            lowered = step.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
        # trip-count-aware structural cost model (repro.utils.hlo_cost) —
        # compiled.cost_analysis() counts while bodies once, which under-
        # reports scanned-layer models by ~n_layers x.
        costs = analyze_hlo(hlo)
        coll = {k: float(v) for k, v in costs.coll_by_kind.items()}
        coll_bytes = float(costs.coll_bytes)
        flops = float(costs.flops)
        hbm_bytes = float(costs.bytes_hbm)      # pessimistic (CPU-fusion)
        hbm_bytes_opt = float(costs.bytes_out)  # optimistic (perfect fusion)
        xla_flops = float(cost.get("flops", 0.0))
        mf = model_flops_of(cfg, pa, shape_name)
        chip = chip_for(TARGET_DEVICE_KIND)
        rt = roofline_terms(
            flops_per_device=flops, hbm_bytes_per_device=hbm_bytes,
            collective_bytes_per_device=coll_bytes, chips=chips,
            chip=chip, model_flops=mf)
        arg_b = float(mem.argument_size_in_bytes)
        tmp_b = float(mem.temp_size_in_bytes)
        out_b = float(mem.output_size_in_bytes)
        # arguments and outputs alias for donated params/opt-state
        peak = arg_b + tmp_b
        row.update(
            status="ok",
            chips=chips,
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            arg_bytes=arg_b, temp_bytes=tmp_b, out_bytes=out_b,
            peak_bytes=peak, fits_hbm=bool(peak <= HBM_BYTES),
            flops_per_dev=flops, hbm_bytes_per_dev=hbm_bytes,
            hbm_bytes_opt_per_dev=hbm_bytes_opt,
            memory_s_opt=hbm_bytes_opt / chip.hbm_bw,
            collective_bytes_per_dev=coll_bytes,
            collectives=coll, xla_flops_per_dev=xla_flops,
            model_flops=mf,
            compute_s=rt.compute_s, memory_s=rt.memory_s,
            collective_s=rt.collective_s, dominant=rt.dominant,
            useful_ratio=rt.useful_ratio, mfu_bound=rt.mfu_bound,
        )
    except Exception as e:  # noqa: BLE001 — a failed cell is a bug report
        row.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    return row


def fmt_row(r: dict) -> str:
    if r["status"] == "skipped":
        return f"{r['arch']:24s} {r['shape']:12s} {r['mesh']:6s} SKIP ({r['reason'][:40]})"
    if r["status"] == "error":
        return f"{r['arch']:24s} {r['shape']:12s} {r['mesh']:6s} ERROR {r['error'][:70]}"
    return (f"{r['arch']:24s} {r['shape']:12s} {r['mesh']:6s} "
            f"peak={r['peak_bytes']/1e9:7.2f}GB fits={int(r['fits_hbm'])} "
            f"C={r['compute_s']*1e3:8.3f}ms M={r['memory_s']*1e3:8.3f}ms "
            f"K={r['collective_s']*1e3:8.3f}ms dom={r['dominant'][:4]} "
            f"useful={r['useful_ratio']:.2f} mfu_bound={r['mfu_bound']:.3f} "
            f"[compile {r['compile_s']:.0f}s]")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    archs = ARCHS if args.arch == "all" else tuple(args.arch.split(","))
    shapes = ASSIGNED_SHAPES if args.shape == "all" \
        else tuple(args.shape.split(","))
    meshes = ("single", "multi") if args.mesh == "both" else (args.mesh,)

    rows = []
    for arch in archs:
        for shape in shapes:
            for mk in meshes:
                r = run_cell(arch, shape, mk)
                rows.append(r)
                print(fmt_row(r), flush=True)
    if args.out:
        existing = []
        if os.path.exists(args.out):
            with open(args.out) as f:
                existing = json.load(f)
        # replace rows with same (arch, shape, mesh)
        keyset = {(r["arch"], r["shape"], r["mesh"]) for r in rows}
        existing = [r for r in existing
                    if (r["arch"], r["shape"], r["mesh"]) not in keyset]
        with open(args.out, "w") as f:
            json.dump(existing + rows, f, indent=1)
    n_ok = sum(r["status"] == "ok" for r in rows)
    n_err = sum(r["status"] == "error" for r in rows)
    print(f"\n{n_ok} ok / {n_err} error / "
          f"{sum(r['status'] == 'skipped' for r in rows)} skipped")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
