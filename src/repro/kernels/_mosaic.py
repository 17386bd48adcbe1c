"""Two Mosaic lowering conventions every kernel in this package follows.

* Index maps return int32 block indices (:func:`block`).  Under
  ``jax_enable_x64`` a Python int literal in an index map (the ``0`` of
  ``lambda i: (0, i)``) traces as int64, and Mosaic refuses the kernel
  ("failed to legalize operation 'func.return' ... (i64, i64)").
* Contractions run at ``HIGHEST`` precision (``#tpu.contract_precision
  <fp32>``) instead of leaving the precision of float32 operands to
  Mosaic's default: the state's stated precision is float32, and the
  default may take fewer, bf16-based MXU passes.  HIGHEST costs more MXU
  passes per block; that cost has not been measured.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

HIGHEST = jax.lax.Precision.HIGHEST


def block(block_shape, index_map) -> pl.BlockSpec:
    """``pl.BlockSpec(block_shape, index_map)`` with int32 indices."""
    def index_map_i32(*grid):
        return tuple(jnp.asarray(b, jnp.int32) for b in index_map(*grid))

    return pl.BlockSpec(block_shape, index_map_i32)

