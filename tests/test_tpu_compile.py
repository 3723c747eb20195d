"""The layered-matmul Pallas kernel, and the held-share expert layer's
grouped products, compiled for a described TPU v5e.

No chip is needed: the TPU compiler builds for a topology that is
described, not attached.  The shapes are the LM heads the kernel serves
at published widths, ``(K, M, N)`` for ``a (K, M)``, ``b (K, N)``:

  yi-6b          K = 4096, N = 64000, M = 4 and 128 rows
  internvl2-1b   K = 896,  N = 151680, M = 4 rows

and DeepSeek-V2-Lite's expert layer (d_model 2048, experts of 1408, a
64-wide router, top-6, 8 experts held) over a decode step's 64 tokens and
a prefill's 2048.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import MoEConfig
from repro.kernels.layered_matmul import layered_matmul_kernel_call
from repro.models import moe as moe_lib

M_PLANES, D = 2, 7


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but not read back without one; keep it out of the cache
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.mark.parametrize("K,M,N", [(4096, 4, 64000), (4096, 128, 64000),
                                   (896, 4, 151680)])
def test_kernel_compiles_for_v5e(one_chip, K, M, N):
    a = jax.ShapeDtypeStruct((M_PLANES, K, M), jnp.int8, sharding=one_chip)
    b = jax.ShapeDtypeStruct((M_PLANES, K, N), jnp.int8, sharding=one_chip)
    # the block sizes ops.layered_matmul_partials picks for these shapes
    bm = 128 if M % 128 == 0 else M
    bk = 512 if K % 512 == 0 else K
    compiled = layered_matmul_kernel_call.lower(
        a, b, m=M_PLANES, d=D, bm=bm, bn=128, bk=bk).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("tokens", [64, 2048])
def test_held_expert_layer_compiles_for_v5e(one_chip, tokens):
    cfg = MoEConfig(num_experts=64, top_k=6, d_ff_expert=1408,
                    d_ff_shared=2816, held_experts=(0, 8))
    params = jax.eval_shape(lambda: moe_lib.init_moe_params(
        jax.random.PRNGKey(0), 2048, cfg, jnp.bfloat16))
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        params)
    params["router"] = jax.ShapeDtypeStruct((2048, 64), jnp.float32,
                                            sharding=one_chip)
    x = jax.ShapeDtypeStruct((tokens, 2048), jnp.bfloat16, sharding=one_chip)
    text = jax.jit(lambda p, x: moe_lib.held_moe_block(p, x, cfg)).lower(
        params, x).compile().as_text()
    # each projection's grouped product is one TPU kernel of its own
    assert text.count("custom_call_target=\"tpu_custom_call\"") >= 3
    assert "ragged-dot" in text
