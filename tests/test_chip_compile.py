"""Ahead-of-time compiles of the fused scorer kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached.  These tests
compile the two Pallas kernels the serving path runs on the chip — the
block kernel (``fused_mlp_score``, unmasked sweeps) and the row kernel
(``fused_mlp_score_rows``, cell-masked sweeps) — at the default MLP width
for one chip of a described ``v5e:2x2`` topology, and check that the HLO
carries the Mosaic kernel.  Nothing runs: a passing compile says the
chip's compiler accepts the kernel (tiling, VMEM budget), not that its
answers are right.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import fused_mlp_score as fms

#: kinds, layers, hidden width and row block of the default scorer
#: (``predictor.DEFAULT_MLP_CFG``: 3 hidden layers + output, width 256)
K, L, H, BLOCK_M = 4, 4, 256, 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache without the chip: keep these out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("n_blocks", [1, 64])
@pytest.mark.parametrize("kernel", ["block", "rows"])
def test_scorer_kernel_compiles_for_v5e(one_chip, kernel, n_blocks):
    rows = n_blocks * BLOCK_M
    weights = _spec((K, L, H, H), jnp.float32, one_chip)
    biases = _spec((K, L, H), jnp.float32, one_chip)
    x = _spec((rows, H), jnp.float32, one_chip)
    if kernel == "block":
        fn = functools.partial(fms.fused_mlp_score, block_m=BLOCK_M)
        kinds = _spec((n_blocks,), jnp.int32, one_chip)
    else:
        fn = functools.partial(fms.fused_mlp_score_rows, block_m=BLOCK_M)
        kinds = _spec((rows,), jnp.int32, one_chip)
    compiled = jax.jit(fn).lower(x, kinds, weights, biases).compile()
    assert "tpu_custom_call" in compiled.as_text()
