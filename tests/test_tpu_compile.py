"""The main path's Pallas kernels compiled for a described TPU v5e chip.

Nothing here runs: each kernel is lowered and compiled at full width
(N in {8, 64}, local D = 2**24) for one chip of a ``v5e:2x2`` topology
that is described, not attached — which catches what interpret mode hides
(Mosaic refusals, tiling, VMEM, HBM fit).  The suite's x64 stays on, so
these also guard the int32 index maps (``kernels/_blockspec.py``).

This is the only test file that describes the chip, and it does so in a
module fixture only: never at import time.
"""
import jax
import jax.numpy as jnp
import pytest

from repro import kernels

D = 2**24


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import compilation_cache, topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.compilation_cache.reset_cache()
    return SingleDeviceSharding(topo.devices[0])


def _args(sharding, n, dtype, names):
    shapes = {"nn": (n, n), "nd": (n, D), "rnd": (2 if n == 8 else 1, n, D),
              "lam": (D,)}
    # the stacked-RHS stack is 2 deep at N=8; at N=64 one (N, D) f32 slab
    # is 4 GiB, so depth 1 keeps Xt + V + W inside one chip's 16 GB
    return [jax.ShapeDtypeStruct(shapes[k],
                                 jnp.float32 if k in ("nn", "lam") else dtype,
                                 sharding=sharding) for k in names]


KERNELS = {
    "fused_gram_mvm": (
        lambda K1, K2, X, V, lam: kernels.fused_gram_mvm(
            K1, K2, X, V, lam, stationary=True, noise=1e-2, interpret=False),
        ("nn", "nn", "nd", "nd", "lam")),
    "fused_gram_mvm_multi": (
        lambda K1, K2, X, V, lam: kernels.fused_gram_mvm_multi(
            K1, K2, X, V, lam, stationary=True, interpret=False),
        ("nn", "nn", "nd", "rnd", "lam")),
    "fused_factor_build": (
        lambda A, B, V, lam: kernels.fused_factor_build(
            A, B, V, lam, v_scale=lam, interpret=False),
        ("nd", "nd", "nd", "lam")),
    "gram_update": (
        lambda K1, M, V, X, lam: kernels.gram_update(
            K1, M, V, X, lam, interpret=False),
        ("nn", "nn", "nd", "nd", "lam")),
    "skinny_gram": (
        lambda A, B, lam: kernels.skinny_gram(A, B, lam, interpret=False),
        ("nd", "nd", "lam")),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("n", [8, 64])
@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name, n, dtype):
    assert jax.config.jax_enable_x64      # the int64 index-map trap is live
    fn, names = KERNELS[name]
    compiled = jax.jit(fn).lower(*_args(one_chip, n, dtype, names)).compile()
    assert "tpu_custom_call" in compiled.as_text()
