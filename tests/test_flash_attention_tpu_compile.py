"""The flash kernels, at the tiling the rule chooses, through the TPU's own
compiler at real widths — for a v5e that is described, not attached. What
interpret mode cannot show (a slice Mosaic cannot lay out, more scoped VMEM
than a kernel may use) fails here, at no chip time. Nothing runs: no number
comes from this file.

Kept in ONE file and behind fixtures: only the xdist worker that is given this
file loads the TPU's library (on-chip-measurement guide, section 2).
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.ops.attention import (
    flash_attention, flash_attention_with_lse, flash_tiling_decisions,
    mha_backward_chunk,
)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these out of it
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("shape", [
    (8, 12, 1024, 64),      # gpt2-124m, one chip
    (8, 25, 1024, 64),      # a gpt2-xl shard under fsdp=4: 200 rows
    (2, 16, 4096, 128),     # llama-like: hd 128, S 4,096
], ids=["gpt2-124m", "gpt2-xl-shard", "llama-4k"])
def test_chosen_tiling_compiles_for_the_v5e(one_chip, shape):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def grads(q, k, v):
        def loss(q, k, v):
            o = flash_attention(q, k, v, causal=True, layout="bhsd",
                                interpret=False)
            return jnp.sum(o.astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    hlo = jax.jit(grads).lower(x, x, x).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2
    rows = shape[0] * shape[1]
    mine = {d["kernel"]: d for d in flash_tiling_decisions()
            if (d["rows"], d["Sq"], d["hd"]) == (rows, shape[2], shape[3])}
    assert set(mine) == {"fwd", "bwd"}
    # the target tile fits at these shapes: Mosaic took what the rule chose
    assert all((d["block_q"], d["block_k"]) == (512, 512)
               for d in mine.values())


@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
def test_ring_chunk_kernels_compile_for_the_v5e(one_chip, kernel):
    """Ring attention's building blocks (a q chunk against an earlier kv
    chunk, global offsets): every other test of them interprets."""
    B, S, H, hd = 2, 512, 4, 64
    x = jax.ShapeDtypeStruct((B, S, H, hd), jnp.bfloat16, sharding=one_chip)
    lse = jax.ShapeDtypeStruct((B, H, S), jnp.float32, sharding=one_chip)

    def fwd(q, k, v):
        return flash_attention_with_lse(q, k, v, S, 0, interpret=False)

    def bwd(q, k, v, o, lse, do):
        return mha_backward_chunk(q, k, v, o, lse, do, S, 0, interpret=False)

    lowered = (jax.jit(fwd).lower(x, x, x) if kernel == "fwd"
               else jax.jit(bwd).lower(x, x, x, x, lse, x))
    hlo = lowered.compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
