"""GPipe-style pipeline parallelism over the 'pod' axis (optional mapping).

Default multi-pod mapping keeps pod=DP (gradient all-reduce is the most
latency-tolerant collective, so it belongs on the slow inter-pod links).
This module provides the alternative stage=pod mapping for models whose
weights cannot be FSDP'd effectively: layers split into `n_stages`
contiguous stages; microbatches stream through with the classic GPipe
schedule expressed as a shard_map over the stage axis + collective_permute
boundary transfers.

Schedule: for S stages and M microbatches, T = M + S - 1 ticks; at tick t
stage s processes microbatch (t - s) when 0 <= t - s < M. Implemented as a
lax.scan over ticks inside shard_map: every stage runs every tick (SPMD),
with masking for pipeline bubbles — the standard single-program GPipe
formulation. Backward runs through jax.grad of the whole pipelined
forward; XLA schedules the reverse permutes automatically.

Scope note: this is the structural/space-proof implementation (validated
for forward/backward equivalence against the sequential model on a
multi-device mesh in tests/test_pipeline.py); fusing it with the MoE/
attention layer stacks of models/ is future work — it operates on a
caller-supplied per-stage apply function.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Array = jnp.ndarray


def gpipe_forward(
    stage_apply: Callable[[Any, Array], Array],
    stage_params: Any,              # pytree, leaves with leading (S,) axis
    x_mb: Array,                    # (M, mb, ...) microbatched input
    *,
    mesh: Mesh,
    stage_axis: str = "pod",
) -> Array:
    """Run x through S pipeline stages living on `stage_axis`.

    Returns the (M, mb, ...) outputs after the last stage. stage_params
    leaves are sharded P(stage_axis, ...); x_mb is replicated along the
    stage axis (each stage masks to its own schedule slot).
    """
    n_stages = mesh.shape[stage_axis]
    n_mb = x_mb.shape[0]
    ticks = n_mb + n_stages - 1

    param_specs = jax.tree_util.tree_map(
        lambda _: P(stage_axis), stage_params)

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
    )
    def run(params_local, x_all):
        # params_local leaves: (1, ...) — this device's stage
        p_stage = jax.tree_util.tree_map(lambda l: l[0], params_local)
        sidx = jax.lax.axis_index(stage_axis)

        def tick(carry, t):
            outputs, inflight = carry
            # stage s consumes microbatch (t - s); stage 0 reads fresh input
            mb_id = t - sidx
            fresh = x_all[jnp.clip(mb_id, 0, n_mb - 1)]
            x_in = jnp.where(sidx == 0, fresh, inflight)
            active = (mb_id >= 0) & (mb_id < n_mb)
            y = stage_apply(p_stage, x_in)
            y = jnp.where(active, y, inflight)
            # last stage writes its finished microbatch (mask-folded write —
            # lax.cond trips over varying manual axes under shard_map)
            idx = jnp.clip(mb_id, 0, n_mb - 1)
            upd = jnp.where(active & (sidx == n_stages - 1), y, outputs[idx])
            outputs = outputs.at[idx].set(upd)
            # boundary transfer: stage s -> s+1 (ring; wraparound ignored)
            nxt = jax.lax.ppermute(
                y, stage_axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (outputs, nxt), None

        # initial carries must be marked device-varying along the stage axis
        out0 = jax.lax.pcast(jnp.zeros_like(x_all), (stage_axis,), to="varying")
        inflight0 = jax.lax.pcast(jnp.zeros_like(x_all[0]), (stage_axis,),
                                  to="varying")
        (outputs, _), _ = jax.lax.scan(tick, (out0, inflight0),
                                       jnp.arange(ticks))
        # outputs live on the last stage; broadcast to all members so the
        # out_specs=P() (replicated) contract holds
        outputs = jax.lax.psum(
            jnp.where(sidx == n_stages - 1, outputs, 0.0), stage_axis)
        return outputs

    return run(stage_params, x_mb)


def reference_forward(stage_apply, stage_params, x_mb):
    """Sequential oracle: apply all stages to every microbatch."""
    n_stages = jax.tree_util.tree_leaves(stage_params)[0].shape[0]

    def one(x):
        for s in range(n_stages):
            p = jax.tree_util.tree_map(lambda l: l[s], stage_params)
            x = stage_apply(p, x)
        return x

    return jax.vmap(one)(x_mb)
