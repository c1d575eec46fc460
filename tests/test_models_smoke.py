"""Per-architecture smoke tests (assignment requirement): a REDUCED variant
of each assigned family runs one forward + one Gatekeeper train step on CPU,
asserting output shapes and no NaNs; plus prefill/decode consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, reduced, SHAPES
from repro.core.gatekeeper import GatekeeperConfig
from repro.launch.steps import make_train_step
from repro.models import encdec as encdec_lib
from repro.models import transformer as tfm
from repro.sharding import ParallelContext
from repro.training import optim

ARCHS = [a.replace("_", "-") for a in ARCH_IDS]
CTX = ParallelContext()


def _batch_for(cfg, key, B=2, T=16):
    b = {}
    if cfg.family == "vlm":
        P = cfg.vision.n_patches
        b["tokens"] = jax.random.randint(key, (B, T - P), 0, cfg.vocab_size)
        b["patches"] = jax.random.normal(key, (B, P, cfg.d_model))
        b["targets"] = jax.random.randint(key, (B, T), 0, cfg.vocab_size)
    elif cfg.family == "encdec":
        b["tokens"] = jax.random.randint(key, (B, T), 0, cfg.vocab_size)
        b["frames"] = jax.random.normal(key, (B, cfg.encoder.n_frames,
                                              cfg.d_model))
        b["targets"] = jax.random.randint(key, (B, T), 0, cfg.vocab_size)
    else:
        b["tokens"] = jax.random.randint(key, (B, T), 0, cfg.vocab_size)
        b["targets"] = jax.random.randint(key, (B, T), 0, cfg.vocab_size)
    return b


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_shapes(arch):
    cfg = reduced(get_config(arch))
    assert cfg.n_layers <= 4 and cfg.d_model <= 512
    if cfg.moe:
        assert cfg.moe.n_experts <= 4
    key = jax.random.PRNGKey(0)
    params = tfm.init_params(cfg, key)
    B, T = 2, 16
    batch = _batch_for(cfg, key, B, T)
    if cfg.family == "encdec":
        logits = encdec_lib.forward(params, cfg, batch["frames"],
                                    batch["tokens"], CTX)
    else:
        logits = tfm.forward(params, cfg, batch["tokens"], CTX,
                             batch.get("patches"))
    assert logits.shape == (B, T, cfg.vocab_size)
    assert not bool(jnp.isnan(logits).any())


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(arch):
    cfg = reduced(get_config(arch))
    key = jax.random.PRNGKey(1)
    params = tfm.init_params(cfg, key)
    opt_state = optim.adamw_init(params)
    step = make_train_step(cfg, CTX, gk=GatekeeperConfig(alpha=0.3),
                           opt_cfg=optim.AdamWConfig(lr=1e-3, total_steps=10))
    batch = _batch_for(cfg, key)
    new_params, _, metrics = step(params, opt_state, batch)
    assert np.isfinite(float(metrics["loss"]))
    # params actually changed
    delta = jax.tree.reduce(
        lambda a, b: a + b,
        jax.tree.map(lambda a, b: float(jnp.abs(a - b).sum()),
                     params, new_params))
    assert delta > 0
    for leaf in jax.tree.leaves(new_params):
        assert not bool(jnp.isnan(leaf).any())


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "deepseek-v2-236b",
                                  "rwkv6-3b", "zamba2-1.2b",
                                  "kimi-k2-1t-a32b", "qwen1.5-4b",
                                  "mellum2-12b-a2.5b"])
def test_prefill_decode_matches_forward(arch):
    cfg = reduced(get_config(arch))
    key = jax.random.PRNGKey(2)
    params = tfm.init_params(cfg, key)
    T = 12
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, T), 0,
                              cfg.vocab_size)
    full = tfm.forward(params, cfg, toks, CTX)
    cache = tfm.init_cache(cfg, 2, T + 4, dtype=jnp.float32)
    lg, cache = tfm.prefill(params, cfg, toks[:, :T - 1], cache, CTX)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(full[:, :T - 1]),
                               atol=1e-3, rtol=1e-3)
    step_logits, cache = tfm.decode_step(params, cfg, toks[:, T - 1], T - 1,
                                         cache, CTX)
    np.testing.assert_allclose(np.asarray(step_logits),
                               np.asarray(full[:, -1]), atol=1e-3, rtol=1e-3)


def test_encdec_prefill_decode():
    cfg = reduced(get_config("whisper-small"))
    key = jax.random.PRNGKey(4)
    params = tfm.init_params(cfg, key)
    frames = jax.random.normal(key, (2, cfg.encoder.n_frames, cfg.d_model))
    T = 8
    toks = jax.random.randint(key, (2, T), 0, cfg.vocab_size)
    full = encdec_lib.forward(params, cfg, frames, toks, CTX)
    cache = encdec_lib.init_cache(cfg, 2, T + 2, dtype=jnp.float32)
    lg, cache = encdec_lib.prefill(params, cfg, frames, toks[:, :T - 1],
                                   cache, CTX)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(full[:, :T - 1]),
                               atol=1e-3, rtol=1e-3)
    step_logits, _ = encdec_lib.decode_step(params, cfg, toks[:, T - 1],
                                            T - 1, cache, CTX)
    np.testing.assert_allclose(np.asarray(step_logits),
                               np.asarray(full[:, -1]), atol=1e-3, rtol=1e-3)


def test_sliding_window_variant_lowers_memory():
    """The long_500k carve-out: sliding-window cache is bounded."""
    cfg = reduced(get_config("internlm2-1.8b")).replace(sliding_window=8)
    cache = tfm.init_cache(cfg, 2, 1024, dtype=jnp.float32)
    assert cache["dense"]["k"].shape[2] == 8     # window, not 1024


def test_sliding_window_decode_ring_buffer():
    """Ring-buffer decode == full-cache decode when window >= history."""
    cfg = reduced(get_config("internlm2-1.8b"))
    cfg_win = cfg.replace(sliding_window=32)
    key = jax.random.PRNGKey(5)
    params = tfm.init_params(cfg, key)
    T = 12
    toks = jax.random.randint(key, (1, T), 0, cfg.vocab_size)
    full = tfm.forward(params, cfg, toks, CTX)
    cache = tfm.init_cache(cfg_win, 1, 64, dtype=jnp.float32)
    _, cache = tfm.prefill(params, cfg_win, toks[:, :T - 1], cache, CTX)
    step_logits, _ = tfm.decode_step(params, cfg_win, toks[:, T - 1], T - 1,
                                     cache, CTX)
    np.testing.assert_allclose(np.asarray(step_logits),
                               np.asarray(full[:, -1]), atol=1e-3, rtol=1e-3)


def test_all_shapes_registered():
    assert set(SHAPES) == {"train_4k", "prefill_32k", "decode_32k",
                           "long_500k"}
    assert SHAPES["long_500k"].seq_len == 524288
    assert SHAPES["train_4k"].global_batch == 256
