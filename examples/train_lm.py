"""End-to-end driver: train a ~100M-param LM for a few hundred steps on the
synthetic induction-pattern stream, with checkpoint/restart enabled.

The model is a scaled-down gemma3-style transformer (sliding-window
interleave); success criterion: loss on the copy region falls well below
the iid entropy floor log(vocab) — the model must learn induction, not
just unigram statistics.

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 300] [--gp]
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.data import DataConfig, batch_for_step
from repro.launch.mesh import make_test_mesh
from repro.models import ModelConfig
from repro.optim import get_optimizer
from repro.runtime import RecoveryConfig, run_with_recovery
from repro.train import build_train_step
from repro.models.registry import SHAPES, ShapeSpec
from repro.utils import compile_cache

compile_cache.enable()


def main() -> None:
    ap = argparse.ArgumentParser()
    # defaults are CPU-container-sized (~1s/step); the "real" run is
    #   --dim 768 --layers 12 --seq 1024 --batch 32  (~100M params), which
    # needs accelerator hardware for a few hundred steps.
    ap.add_argument("--steps", type=int, default=600)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--gp", action="store_true",
                    help="use the GP-H preconditioned optimizer")
    ap.add_argument("--ckpt", default="",
                    help="checkpoint dir (default: fresh temp dir)")
    args = ap.parse_args()

    cfg = ModelConfig(
        arch="example-lm", family="dense", n_layers=args.layers,
        d_model=args.dim, n_heads=8, n_kv_heads=4, d_ff=4 * args.dim,
        vocab_size=args.vocab, window=64, global_every=4,
        param_dtype=jnp.float32, compute_dtype=jnp.float32, remat=False)

    seq_len, batch = args.seq, args.batch
    SHAPES["example"] = ShapeSpec("example", seq_len, batch, "train")

    mesh = make_test_mesh((1, len(jax.devices())), ("data", "model"))
    opt = get_optimizer("gp", lr=1.0, history=4, fallback_lr=1e-3,
                        max_step_rms=2e-3) if args.gp else \
        get_optimizer("adamw", lr=args.lr)
    bundle = build_train_step(cfg, opt, mesh, shape="example", donate=False)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(
        bundle.abstract_params))
    print(f"model: {n_params/1e6:.1f}M params, optimizer: {opt.name}")

    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=seq_len,
                    global_batch=batch)
    params = bundle.model.init(jax.random.PRNGKey(0))
    opt_state = bundle.opt.init(params)

    entropy_floor = float(jnp.log(cfg.vocab_size))
    t0 = time.time()
    hist = []

    def on_metrics(step, metrics):
        hist.append(float(metrics["loss"]))
        if step % 20 == 0 or step == 1:
            print(f"step {step:4d}  loss {hist[-1]:.3f}  "
                  f"(iid floor ~{entropy_floor:.2f})  "
                  f"{time.time()-t0:.0f}s", flush=True)

    import tempfile

    ckpt_dir = args.ckpt or tempfile.mkdtemp(prefix="repro_lm_")
    params, opt_state, stats = run_with_recovery(
        bundle.step, lambda s: batch_for_step(dc, s), params, opt_state,
        n_steps=args.steps,
        config=RecoveryConfig(ckpt_dir=ckpt_dir, ckpt_every=100),
        on_metrics=on_metrics)

    # copy-region loss: the second half of every sequence is a repeat, so a
    # model with induction heads beats the entropy floor there by a lot
    final = sum(hist[-10:]) / 10
    # average loss mixes random half (floor) and copy half (low): the
    # mixture must drop clearly below the floor
    print(f"final loss {final:.3f} vs iid floor {entropy_floor:.3f} "
          f"-> {'LEARNED copy pattern' if final < 0.8 * entropy_floor else 'available headroom unexploited (train longer)'}")


if __name__ == "__main__":
    main()
