"""Compile-only tests of the paged decode kernels for a TPU v5e.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these run on the CPU and refuse what the chip's
compiler would refuse (block shapes off the (8, 128) tiling, stores it
cannot prove aligned, VMEM overuse) — none of which interpret mode sees.
Shapes are the published widths of the served models: internlm2-1.8b for
the GQA kernels, deepseek-v2 for the MLA ones.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and a worker that decided at
import time which tests exist would desynchronise pytest-xdist's workers.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import paged_attention as pa

BLOCK = 16          # serving default block size (serving/config.py)
SLOTS = 8
MAX_LEN = 2048
M = MAX_LEN // BLOCK + 1
N_BLOCKS = SLOTS * M + 1


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described-chip compile can be written to the persistent cache but
    # never read back without the chip; keep it out of the cache
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        cc.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "fp32"])
def test_gqa_paged_decode_compiles(one_chip, dtype):
    """The multi-page kernel at the table widths the engine hands it: the
    full table with q in the pages' dtype, then 16 slots at the largest
    power-of-two prefix (128) and at the clamp (145, not a multiple of
    the 8-page block) with q in fp32, as served (rope leaves it fp32)."""
    cfg = get_config("internlm2-1.8b")
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    assert pa.gqa_pages_per_block(BLOCK, KV, hd, jnp.bfloat16) == 8
    fn = functools.partial(pa.paged_flash_decode_gqa, interpret=False)
    for slots, m, q_dtype in ((SLOTS, M, dtype), (16, 128, jnp.float32),
                              (16, 145, jnp.float32)):
        _compile(fn, one_chip,
                 ((slots, 1, H, hd), q_dtype),
                 ((N_BLOCKS, BLOCK, KV, hd), dtype),
                 ((N_BLOCKS, BLOCK, KV, hd), dtype),
                 ((slots, m), jnp.int32), ((slots,), jnp.int32))


def _leaf_shapes():
    """Per-token feature shape of every paged cache leaf the write kernel
    serves: GQA k/v (internlm2) and MLA ckv/kr (deepseek-v2)."""
    g = get_config("internlm2-1.8b")
    mla = get_config("deepseek-v2-236b").mla
    return {"gqa_kv": (g.n_kv_heads, g.head_dim),
            "mla_ckv": (mla.kv_lora,),
            "mla_kr": (mla.rope_dim,)}


@pytest.mark.parametrize("leaf", ["gqa_kv", "mla_ckv", "mla_kr"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "fp32"])
def test_paged_write_token_compiles(one_chip, dtype, leaf):
    feat = _leaf_shapes()[leaf]
    compiled = _compile(
        functools.partial(pa.paged_write_token, interpret=False), one_chip,
        ((N_BLOCKS, BLOCK) + feat, dtype), ((SLOTS, M), jnp.int32),
        ((SLOTS,), jnp.int32), ((SLOTS,) + feat, dtype))
    # the kernel writes the leaf (operand 3) in place
    assert "output_to_operand_aliasing={{}: (3, {})}" in compiled.as_text()


def test_mla_paged_decode_compiles(one_chip):
    cfg = get_config("deepseek-v2-236b")
    H, r, dr = cfg.n_heads, cfg.mla.kv_lora, cfg.mla.rope_dim
    dt = jnp.bfloat16
    _compile(functools.partial(pa.paged_flash_decode_mla,
                               scale=(cfg.head_dim + dr) ** -0.5,
                               interpret=False),
             one_chip,
             ((SLOTS, 1, H, r), dt), ((SLOTS, 1, H, dr), dt),
             ((N_BLOCKS, BLOCK, r), dt), ((N_BLOCKS, BLOCK, dr), dt),
             ((r,), dt), ((SLOTS, M), jnp.int32), ((SLOTS,), jnp.int32))


def test_windowed_gqa_paged_decode_compiles(one_chip):
    """Mellum's windowed layers: the window arrives as a traced scalar (the
    layer scan carries each layer's kind), at 4 KV heads of 128 (16 pages
    per block) and 16 slots at a 145-column table, q in fp32."""
    cfg = get_config("mellum2-12b-a2.5b")
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    assert pa.gqa_pages_per_block(BLOCK, KV, hd, jnp.bfloat16) == 16
    fn = functools.partial(pa.paged_flash_decode_gqa, interpret=False)
    _compile(lambda q, k, v, t, p, w: fn(q, k, v, t, p, window=w), one_chip,
             ((16, 1, H, hd), jnp.float32),
             ((N_BLOCKS, BLOCK, KV, hd), jnp.bfloat16),
             ((N_BLOCKS, BLOCK, KV, hd), jnp.bfloat16),
             ((16, 145), jnp.int32), ((16,), jnp.int32), ((), jnp.int32))


@pytest.mark.parametrize("rows", [1, 16])
def test_moe_gmm_decode_compiles(one_chip, rows):
    """The held-expert decode kernel at Mellum's widths (8 held experts of
    2304 x 896, the whole width per grid step) over all 28 layers'
    experts stacked, the layer a traced scalar, named so the device
    trace finds it."""
    from repro.kernels import moe_gmm
    cfg = get_config("mellum2-12b-a2.5b")
    d, f, E, L = (cfg.d_model, cfg.moe.d_ff_expert, cfg.moe.n_held,
                  cfg.n_layers)
    assert moe_gmm.ffn_tile(d, f, 2) == f
    compiled = _compile(
        functools.partial(moe_gmm.moe_gmm_decode, interpret=False),
        one_chip, ((rows, d), jnp.bfloat16), ((rows, E), jnp.float32),
        ((L, E, d, f), jnp.bfloat16), ((L, E, d, f), jnp.bfloat16),
        ((L, E, f, d), jnp.bfloat16), ((), jnp.int32))
    assert moe_gmm.NAME in compiled.as_text()
