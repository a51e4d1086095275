"""Compile the serving path's Pallas kernels for a described TPU v5e.

No chip is attached: the TPU compiler that ships with JAX compiles for a
described ``v5e:2x2`` topology and refuses what the chip's compiler would
refuse (block tiling, unsupported primitives, fast-memory budget), which
interpret mode cannot show.  Widths are dsv2-lite's.  Each test asserts the
kernel is in the compiled program (``tpu_custom_call``).

Only the worker given this file loads the TPU library, from inside the
fixture: nothing here touches it at import or collection time.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.aebs.kernel import aebs_pallas
from repro.kernels.decode_attention.kernel import paged_decode_attention_pallas
from repro.kernels.expert_ffn.kernel import expert_ffn_pallas
from repro.kernels.expert_ffn.ops import _ff_tile

D_MODEL, N_EXPERTS, D_FF_EXPERT, TOP_K = 2048, 64, 1408, 6  # dsv2-lite
HEAD_DIM, PAGE_SIZE, CACHE_LEN, BATCH = 128, 16, 2048, 16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip: keep the cache off around these
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def restore():
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler can be loaded here
        restore()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    restore()


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("capacity", [64, 512], ids=["decode", "prefill_chunk"])
def test_expert_ffn_grouped_compiles(one_chip, capacity):
    """17 slots over the 64 logical experts: capacity 64 at decode, and 512 —
    a 512-token prefill chunk at its drop-free capacity — to bound VMEM."""
    S = 17
    spec = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    ff_tile = _ff_tile(D_FF_EXPERT, 512)
    text = _compiled_text(
        lambda x, wg, wu, wd, s2e, act: expert_ffn_pallas(
            x, wg, wu, wd, act, s2e, ff_tile=ff_tile, interpret=False
        ),
        spec((S, capacity, D_MODEL)),
        spec((N_EXPERTS, D_MODEL, D_FF_EXPERT)),
        spec((N_EXPERTS, D_MODEL, D_FF_EXPERT)),
        spec((N_EXPERTS, D_FF_EXPERT, D_MODEL)),
        spec((S,), jnp.int32),
        spec((S,), jnp.int32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n_heads,n_kv", [(16, 16), (24, 8)], ids=["dsv2_lite_mha", "phi4_mini_gqa"])
def test_paged_decode_attention_compiles(one_chip, n_heads, n_kv):
    num_pages = BATCH * CACHE_LEN // PAGE_SIZE + 1
    spec = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    text = _compiled_text(
        lambda q, k, v, bt, ln: paged_decode_attention_pallas(q, k, v, bt, ln, interpret=False),
        spec((BATCH, n_heads, HEAD_DIM)),
        spec((num_pages, PAGE_SIZE, n_kv, HEAD_DIM)),
        spec((num_pages, PAGE_SIZE, n_kv, HEAD_DIM)),
        spec((BATCH, CACHE_LEN // PAGE_SIZE), jnp.int32),
        spec((BATCH,), jnp.int32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("tokens", [32, 256])
def test_aebs_compiles(one_chip, tokens):
    """E 64, top-6 over 4 MoE instances of 17 slots (≤ 2 replicas each)."""
    n_e, replicas = 4, 2
    spec = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    text = _compiled_text(
        lambda e, h, c, so: aebs_pallas(e, h, c, so, interpret=False),
        spec((tokens, TOP_K)),
        spec((N_EXPERTS, replicas)),
        spec((N_EXPERTS,)),
        spec((N_EXPERTS, n_e)),
    )
    assert "tpu_custom_call" in text
