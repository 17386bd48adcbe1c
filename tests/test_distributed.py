"""Distributed-equivalence tests: shard_map Gram ops == single-device math,
straggler masking, gradient compression. Multi-device cases run in a
subprocess with xla_force_host_platform_device_count=8 so the main test
process keeps the 1-device contract.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim.compression import ef_int8_compress, ef_int8_decompress

_SUBPROCESS_SRC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
from functools import partial
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.core import build_factors, get_kernel, gram_matvec, woodbury_solve
from repro.core.distributed import sharded_gram_matvec, sharded_woodbury_solve
from repro.runtime import masked_gradient_mean
from repro.launch.mesh import make_test_mesh

mesh = make_test_mesh((2, 4), ("data", "model"))
key = jax.random.PRNGKey(0)
N, D = 6, 64
failures = []
for name in ["rbf", "poly2", "expdot"]:
    spec = get_kernel(name)
    c = None if spec.is_stationary else \
        jax.random.normal(jax.random.fold_in(key, 9), (D,)) * 0.1
    X = jax.random.normal(jax.random.fold_in(key, 1), (N, D))
    G = jax.random.normal(jax.random.fold_in(key, 2), (N, D))
    V = jax.random.normal(jax.random.fold_in(key, 3), (N, D))
    # dot-kernel r grows with D: scale lam so exp/poly stay conditioned
    lam = 0.7 if spec.is_stationary else 0.7 / D
    f = build_factors(spec, X, lam=lam, c=c)
    w_ref = gram_matvec(f, V, stationary=spec.is_stationary)
    w_sh = sharded_gram_matvec(mesh, spec)(f, V)
    e1 = float(jnp.max(jnp.abs(w_sh - w_ref)) / jnp.max(jnp.abs(w_ref)))
    Z_sh = sharded_woodbury_solve(mesh, spec)(X, G, lam=lam, c=c)
    # equivalence with the single-device exact solver (the point of the
    # test): identical math modulo psum reduction order
    Z_ref = woodbury_solve(spec, f, G)
    e2 = float(jnp.max(jnp.abs(Z_sh - Z_ref)) /
               (jnp.max(jnp.abs(Z_ref)) + 1e-300))
    if e1 > 1e-12 or e2 > 1e-4:     # e2: psum ordering noise amplified by
        failures.append((name, e1, e2))  # the inner N^2 solve's conditioning

# straggler masked mean over the data axis
@partial(shard_map, mesh=mesh, in_specs=(P("data"), P("data")),
         out_specs=(P("data"), P()))
def masked(g, alive):
    out, n = masked_gradient_mean({"g": g}, alive[0], "data")
    return out["g"], n

g = jnp.arange(8, dtype=jnp.float64).reshape(2, 4)[:, :1] * jnp.ones((2, 4))
g = jnp.arange(2, dtype=jnp.float64)[:, None] * jnp.ones((2, 4))
alive = jnp.array([1.0, 0.0])
out, n = masked(g, alive)
# only replica 0 alive -> mean == replica 0's grads == zeros
if float(n) != 1.0 or float(jnp.max(jnp.abs(out[0]))) > 1e-12:
    failures.append(("straggler", float(n), float(jnp.max(jnp.abs(out)))))

assert not failures, failures
print("SUBPROCESS_OK")
"""


def test_sharded_ops_match_reference_8dev():
    r = subprocess.run([sys.executable, "-c", _SUBPROCESS_SRC],
                       capture_output=True, text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")})
    assert "SUBPROCESS_OK" in r.stdout, r.stdout + r.stderr


def test_ef_int8_compression_roundtrip(rng):
    x = jax.random.normal(rng, (1000,)) * 5.0
    err0 = jnp.zeros_like(x)
    codes, scales, err = ef_int8_compress(x, err0)
    back = ef_int8_decompress(codes, scales, 1000)
    # error feedback carries exactly the quantization residual
    assert jnp.allclose(back + err, x, rtol=1e-6, atol=1e-6)
    # quantization error bounded by scale/2 per block
    assert float(jnp.max(jnp.abs(err))) <= float(jnp.max(scales)) * 0.51


def test_ef_compression_error_feedback_converges(rng):
    """Summing dequantized payloads + final error == sum of true grads
    (the EF invariant that keeps SGD unbiased over time)."""
    true = jax.random.normal(rng, (512,))
    err = jnp.zeros_like(true)
    acc = jnp.zeros_like(true)
    for i in range(20):
        codes, scales, err = ef_int8_compress(true, err)
        acc = acc + ef_int8_decompress(codes, scales, 512)
    total_sent = acc + err
    assert jnp.allclose(total_sent, 20.0 * true, rtol=1e-4, atol=1e-4)
