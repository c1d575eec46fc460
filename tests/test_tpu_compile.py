"""Compile-only tests of the paged decode kernels for a TPU v5e.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these run on the CPU and refuse what the chip's
compiler would refuse (block shapes off the (8, 128) tiling, stores it
cannot prove aligned, VMEM overuse) — none of which interpret mode sees.
Shapes are the published widths of the served models: internlm2-1.8b for
the GQA kernels, deepseek-v2 for the MLA ones. The kernels take the pool's
leaves stacked over a layer group (`[L, n_blocks+1, block_size, F]`) and
the layer as a traced scalar, as the layer scan hands them. The engine's
paged decode step and prefill chunk are compiled whole, with the pool
donated, to show the pool is updated in place.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and a worker that decided at
import time which tests exist would desynchronise pytest-xdist's workers.
"""
import functools
import math
import os
import re

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
LAYER = ((), jnp.int32)      # the layer of a stacked leaf, traced


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
    the 8-page block) with q in fp32, as served (rope leaves it fp32).
    K/V are every layer's pages stacked, read in place at a traced
    layer."""
    cfg = get_config("internlm2-1.8b")
    H, KV, hd, L = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.n_layers
    assert pa.gqa_pages_per_block(BLOCK, KV, hd, jnp.bfloat16) == 8
    fn = functools.partial(pa.paged_flash_decode_gqa, interpret=False)
    for slots, m, q_dtype in ((SLOTS, M, dtype), (16, 128, jnp.float32),
                              (16, 145, jnp.float32)):
        _compile(fn, one_chip,
                 ((slots, 1, H, hd), q_dtype),
                 ((L, N_BLOCKS, BLOCK, KV * hd), dtype),
                 ((L, N_BLOCKS, BLOCK, KV * hd), dtype),
                 ((slots, m), jnp.int32), ((slots,), jnp.int32), LAYER)


def _leaf_shapes():
    """(layers, per-token features) of every paged cache leaf the write
    kernel serves: GQA k/v (internlm2) and MLA ckv/kr (deepseek-v2)."""
    g = get_config("internlm2-1.8b")
    d = get_config("deepseek-v2-236b")
    return {"gqa_kv": (g.n_layers, g.n_kv_heads * g.head_dim),
            "mla_ckv": (d.n_layers, d.mla.kv_lora),
            "mla_kr": (d.n_layers, d.mla.rope_dim)}


@pytest.mark.parametrize("leaf", ["gqa_kv", "mla_ckv", "mla_kr"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "fp32"])
def test_paged_write_token_compiles(one_chip, dtype, leaf):
    L, F = _leaf_shapes()[leaf]
    compiled = _compile(
        functools.partial(pa.paged_write_token, interpret=False), one_chip,
        ((L, N_BLOCKS, BLOCK, F), dtype), ((SLOTS, M), jnp.int32),
        ((SLOTS,), jnp.int32), ((SLOTS, F), dtype), LAYER)
    # the kernel writes the stacked leaf (operand 4, after the block ids,
    # positions and layer it prefetches, and the values) in place
    assert "output_to_operand_aliasing={{}: (4, {})}" in compiled.as_text()


def test_mla_paged_decode_compiles(one_chip):
    cfg = get_config("deepseek-v2-236b")
    H, r, dr = cfg.n_heads, cfg.mla.kv_lora, cfg.mla.rope_dim
    L = cfg.n_layers
    dt = jnp.bfloat16
    _compile(functools.partial(pa.paged_flash_decode_mla,
                               scale=(cfg.head_dim + dr) ** -0.5,
                               interpret=False),
             one_chip,
             ((SLOTS, 1, H, r), dt), ((SLOTS, 1, H, dr), dt),
             ((L, N_BLOCKS, BLOCK, r), dt), ((L, N_BLOCKS, BLOCK, dr), dt),
             ((r,), dt), ((SLOTS, M), jnp.int32), ((SLOTS,), jnp.int32),
             LAYER)


def test_windowed_gqa_paged_decode_compiles(one_chip):
    """Mellum's windowed layers: the window arrives as a traced scalar (the
    layer scan carries each layer's kind), at 4 KV heads of 128 (16 pages
    per block) and 16 slots at a 145-column table, q in fp32, over the
    stacked pool at a traced layer."""
    cfg = get_config("mellum2-12b-a2.5b")
    H, KV, hd, L = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.n_layers
    assert pa.gqa_pages_per_block(BLOCK, KV, hd, jnp.bfloat16) == 16
    fn = functools.partial(pa.paged_flash_decode_gqa, interpret=False)
    _compile(lambda q, k, v, t, p, w, lyr: fn(q, k, v, t, p, lyr, window=w),
             one_chip,
             ((16, 1, H, hd), jnp.float32),
             ((L, N_BLOCKS, BLOCK, KV * hd), jnp.bfloat16),
             ((L, N_BLOCKS, BLOCK, KV * hd), jnp.bfloat16),
             ((16, 145), jnp.int32), ((16,), jnp.int32), ((), jnp.int32),
             LAYER)


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


# the engine's paged programs at served widths, cut in depth: one pool per
# model, 1401 blocks of 16 as the benchmark serves them, 16 slots
STEP_SLOTS, STEP_BLOCKS, STEP_TABLE, STEP_MAX_NEW = 16, 1401, 128, 256
# ops that, at a pool-sized result, would be a copy or relayout of the pool
_COPY_OPS = ("dynamic-slice", "dynamic-update-slice", "reshape", "copy")
_HLO_LINE = re.compile(
    r"^\s*(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\(", re.M)


def _step_config(name):
    """internlm2-1.8b at 4 layers; Mellum at one period of its layer
    kinds (3 windowed, 1 full), 8 held experts."""
    cfg = get_config(name)
    return cfg.replace(n_layers=len(cfg.layer_kinds) or 4)


def _pool_copies(text, elems):
    """(name, op, dims) of every instruction of a compiled program whose
    name or opcode is one of `_COPY_OPS` and whose result holds one or
    more whole layers' pool leaves (`elems` elements each), as any slice,
    copy or relayout of a leaf does."""
    out = []
    for name, dims, op in _HLO_LINE.findall(text):
        n = math.prod(int(d) for d in dims.split(",") if d)
        if (n >= elems and n % elems == 0
                and any(w in name or w in op for w in _COPY_OPS)):
            out.append((name, op, dims))
    return out


@pytest.mark.parametrize("program", ["step", "prefill"])
@pytest.mark.parametrize("model", ["internlm2-1.8b", "mellum2-12b-a2.5b"])
def test_paged_programs_update_pool_in_place(one_chip, monkeypatch, model,
                                             program):
    """The engine's jitted paged decode step (Pallas kernels) and prefill
    chunk (XLA scatter and gather), the pool donated: the compiled program
    has no dynamic-slice, dynamic-update-slice, reshape or copy with a
    pool-sized result (the layer scan carries the stacked pool and each
    layer indexes its own blocks), and aliases at least the pool's bytes
    from input to output."""
    from repro.kernels import ops as kernel_ops
    from repro.models import transformer as tfm
    from repro.serving import (CascadeSpec, ContinuousCascadeEngine,
                               EngineConfig, ModelRunner)
    from repro.serving.paged_pool import paged_layout
    # the CPU backend would trace the kernels in interpret mode
    monkeypatch.setattr(kernel_ops, "_default_interpret", lambda: False)
    cfg = _step_config(model)

    def placed(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = placed(jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.PRNGKey(0))))
    runner = ModelRunner(cfg, params)
    eng = ContinuousCascadeEngine(CascadeSpec.two_tier(runner, runner),
                                  EngineConfig(n_slots=STEP_SLOTS))
    prefill_fn, _, step_fn, _ = eng._build_paged_fns(STEP_MAX_NEW, True)
    shapes, _ = paged_layout(cfg, STEP_BLOCKS, BLOCK)
    cache = placed(shapes)
    S = STEP_SLOTS
    if program == "step":
        state = {"last_tok": (S,), "pos": (S,), "n_gen": (S,),
                 "budget": (S,), "tokens": (S, STEP_MAX_NEW)}
        state = {k: ((v, jnp.int32)) for k, v in state.items()}
        state.update(conf_sum=((S,), jnp.float32), active=((S,), bool))
        if tfm.held_experts(cfg):
            state["moe"] = ((cfg.n_layers - cfg.moe.n_dense_layers, 2),
                            jnp.int32)
        state = placed({k: jax.ShapeDtypeStruct(*v)
                        for k, v in state.items()})
        tables = placed(jax.ShapeDtypeStruct((S, STEP_TABLE), jnp.int32))
        compiled = step_fn.lower(params, cache, state, tables).compile()
        assert "paged_flash_decode_gqa" in compiled.as_text()
    else:
        args = placed((jax.ShapeDtypeStruct((1, 256), jnp.int32),
                       jax.ShapeDtypeStruct((1, 145), jnp.int32),
                       jax.ShapeDtypeStruct((), jnp.int32),
                       jax.ShapeDtypeStruct((1,), jnp.int32)))
        compiled = prefill_fn.lower(params, *args, cache).compile()
    leaves = jax.tree.leaves(cache)
    layer_elems = min(math.prod(a.shape[1:]) for a in leaves)
    assert _pool_copies(compiled.as_text(), layer_elems) == []
    pool_bytes = sum(a.size * a.dtype.itemsize for a in leaves)
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes
