"""Model configuration registry + assigned input shapes.

Each assigned architecture lives in its own module (src/repro/configs/<id>.py)
exporting `CONFIG`. `get_config(name)` resolves ids; `reduced(cfg)` builds the
CPU-smoke variant (<=2 layers, d_model<=512, <=4 experts) of the same family.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MoESpec:
    n_experts: int
    top_k: int
    d_ff_expert: int
    n_shared_experts: int = 0
    shared_d_ff: Optional[int] = None
    n_dense_layers: int = 1          # leading layers with dense FFN
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    # experts this device holds, [first_held, first_held + n_held) of
    # n_experts (expert parallelism: the rest live on other devices);
    # None = every expert, on the sharded paths above
    n_held: Optional[int] = None
    first_held: int = 0


@dataclasses.dataclass(frozen=True)
class RopeSpec:
    """Rotary embedding of one attention kind. `kind="yarn"` rescales the
    frequencies as YaRN does (arXiv:2309.00071: interpolate the slow
    dimensions by `factor`, keep the fast ones, ramp between the
    `beta_fast`/`beta_slow` rotation counts over `original_max_positions`)
    and multiplies cos and sin by `attention_factor`."""
    theta: float
    kind: str = "default"            # default | yarn
    factor: float = 1.0
    original_max_positions: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclasses.dataclass(frozen=True)
class AttnKind:
    """One kind of attention layer: its window (None = full causal) and
    its rotary embedding (None = the config's `rope_theta`)."""
    name: str
    window: Optional[int] = None
    rope: Optional[RopeSpec] = None


@dataclasses.dataclass(frozen=True)
class MLASpec:
    q_lora: int = 1536
    kv_lora: int = 512
    rope_dim: int = 64
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class EncoderSpec:
    """Encoder stack for enc-dec (audio) models. The modality frontend is a
    stub: input_specs provide precomputed frame embeddings [B, n_frames, d]."""
    n_layers: int
    n_frames: int = 1500


@dataclasses.dataclass(frozen=True)
class VisionSpec:
    """VLM stub frontend: precomputed patch embeddings [B, n_patches, d]."""
    n_patches: int = 576


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | ssm_rwkv | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 128
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    moe: Optional[MoESpec] = None
    mla: Optional[MLASpec] = None
    ssm_state: int = 64
    ssm_head_dim: int = 64
    shared_attn_every: int = 0       # hybrid: shared attn block period
    encoder: Optional[EncoderSpec] = None
    vision: Optional[VisionSpec] = None
    sliding_window: Optional[int] = None
    # per-layer attention kinds, repeated with this period: layer i is of
    # kind layer_kinds[i % len(layer_kinds)] (empty = every layer alike).
    # Windowed kinds keep full-length caches and mask by position.
    layer_kinds: Tuple[AttnKind, ...] = ()
    # runtime policy
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    remat: str = "none"              # none | full | dots
    scan_layers: bool = True
    attn_chunk: int = 0              # >0: online-softmax KV-chunked attention
    microbatches: int = 1            # train-step gradient accumulation
    source: str = ""                 # citation

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm_rwkv"

    @property
    def sub_quadratic(self) -> bool:
        return (self.family in ("ssm_rwkv", "hybrid")
                or self.sliding_window is not None)

    def pdtype(self):
        return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[self.param_dtype]

    def cdtype(self):
        return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[self.compute_dtype]

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str        # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}

ARCH_IDS = (
    "kimi_k2_1t_a32b",
    "deepseek_v2_236b",
    "qwen1_5_32b",
    "llama3_405b",
    "whisper_small",
    "rwkv6_3b",
    "phi_3_vision_4_2b",
    "qwen1_5_4b",
    "internlm2_1_8b",
    "zamba2_1_2b",
    "mellum2_12b_a2_5b",
)

# public ids (with dashes) -> module names
_ALIAS = {i.replace("_", "-"): i for i in ARCH_IDS}
_ALIAS.update({
    "kimi-k2-1t-a32b": "kimi_k2_1t_a32b",
    "deepseek-v2-236b": "deepseek_v2_236b",
    "qwen1.5-32b": "qwen1_5_32b",
    "llama3-405b": "llama3_405b",
    "whisper-small": "whisper_small",
    "rwkv6-3b": "rwkv6_3b",
    "phi-3-vision-4.2b": "phi_3_vision_4_2b",
    "qwen1.5-4b": "qwen1_5_4b",
    "internlm2-1.8b": "internlm2_1_8b",
    "zamba2-1.2b": "zamba2_1_2b",
    "mellum2-12b-a2.5b": "mellum2_12b_a2_5b",
})


def get_config(name: str) -> ModelConfig:
    mod_name = _ALIAS.get(name, name.replace("-", "_").replace(".", "_"))
    mod = importlib.import_module(f"repro.configs.{mod_name}")
    return mod.CONFIG


def list_configs() -> Tuple[str, ...]:
    return ARCH_IDS


def reduced(cfg: ModelConfig) -> ModelConfig:
    """CPU-smoke variant of the same family: 2 layers, d_model<=512,
    <=4 experts, small vocab."""
    kw = dict(
        name=cfg.name + "-reduced",
        n_layers=2,
        d_model=min(cfg.d_model, 256),
        n_heads=4, n_kv_heads=2 if cfg.n_kv_heads < cfg.n_heads else 4,
        head_dim=32,
        d_ff=min(cfg.d_ff, 512),
        vocab_size=min(cfg.vocab_size, 512),
        param_dtype="float32", compute_dtype="float32",
    )
    if cfg.moe is not None:
        held = cfg.moe.n_held is not None
        kw["moe"] = dataclasses.replace(
            cfg.moe, n_experts=4, top_k=2, d_ff_expert=64,
            n_shared_experts=min(cfg.moe.n_shared_experts, 1),
            shared_d_ff=64 if cfg.moe.n_shared_experts else None,
            n_dense_layers=min(cfg.moe.n_dense_layers, 1),
            n_held=2 if held else None)
    if cfg.layer_kinds:
        # one period of kinds, windows cut to the small sizes
        kw["n_layers"] = len(cfg.layer_kinds)
        kw["layer_kinds"] = tuple(
            dataclasses.replace(k, window=min(k.window, 16))
            if k.window else k for k in cfg.layer_kinds)
    if cfg.mla is not None:
        kw["mla"] = MLASpec(q_lora=64, kv_lora=32, rope_dim=16, v_head_dim=32)
        kw["head_dim"] = 32
    if cfg.encoder is not None:
        kw["encoder"] = EncoderSpec(n_layers=2, n_frames=16)
    if cfg.vision is not None:
        kw["vision"] = VisionSpec(n_patches=8)
    if cfg.family == "hybrid":
        kw["n_layers"] = 4
        kw["shared_attn_every"] = 2
    if cfg.family == "ssm_rwkv":
        kw["d_model"] = 128   # 2 heads of 64
    return cfg.replace(**kw)
