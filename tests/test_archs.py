"""Per-architecture smoke tests (assignment requirement): reduced config of
the same family, one forward/train step on CPU, shape + finiteness asserts,
and serving-path consistency."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, list_archs
from repro.models import build_model, init_params, param_count

B, S = 2, 32


def _batch(cfg, key=2, seq=S):
    ks = jax.random.split(jax.random.key(key), 4)
    if cfg.family == "encdec":
        return {"frames": jax.random.normal(ks[0], (B, 16, cfg.d_model)),
                "tokens": jax.random.randint(ks[1], (B, seq + 1), 0,
                                             cfg.vocab)}
    batch = {"tokens": jax.random.randint(ks[1], (B, seq + 1), 0, cfg.vocab)}
    if cfg.family == "vlm":
        batch["vision_embeds"] = jax.random.normal(ks[2],
                                                   (B, 8, cfg.d_model))
        batch["mrope_positions"] = jnp.broadcast_to(jnp.arange(seq),
                                                    (3, B, seq))
    return batch


def test_all_archs_registered():
    assert len(list_archs()) == 11


@pytest.mark.parametrize("arch", list_archs())
def test_arch_full_config_matches_table(arch):
    """Exact table numbers (the full configs are only lowered, never run)."""
    cfg = get_config(arch)
    table = {
        "starcoder2-15b": (40, 6144, 48, 4, 24576, 49152),
        "qwen3-14b": (40, 5120, 40, 8, 17408, 151936),
        "tinyllama-1.1b": (22, 2048, 32, 4, 5632, 32000),
        "nemotron-4-15b": (32, 6144, 48, 8, 24576, 256000),
        "kimi-k2-1t-a32b": (61, 7168, 64, 8, 2048, 163840),
        "qwen3-moe-235b-a22b": (94, 4096, 64, 4, 1536, 151936),
        "qwen2-vl-72b": (80, 8192, 64, 8, 29568, 152064),
        "mamba2-370m": (48, 1024, 0, 0, 0, 50280),
        "jamba-1.5-large-398b": (72, 8192, 64, 8, 24576, 65536),
        "seamless-m4t-large-v2": (24, 1024, 16, 16, 8192, 256206),
        "granite-4.0-h-small": (40, 4096, 32, 8, 768, 100352),
    }
    L, d, h, kv, ff, v = table[arch]
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.d_ff, cfg.vocab) == (L, d, h, kv, ff, v)
    if arch == "kimi-k2-1t-a32b":
        assert (cfg.n_experts, cfg.top_k) == (384, 8)
    if arch == "qwen3-moe-235b-a22b":
        assert (cfg.n_experts, cfg.top_k) == (128, 8)
    if arch == "jamba-1.5-large-398b":
        assert (cfg.n_experts, cfg.top_k) == (16, 2)
        assert cfg.hybrid_period == 8          # 1 attn : 7 mamba
    if arch == "mamba2-370m":
        assert cfg.ssm_state == 128
    if arch == "granite-4.0-h-small":
        assert (cfg.n_experts, cfg.top_k, cfg.shared_width) == (72, 10, 1536)
        assert (cfg.hybrid_period, cfg.hybrid_attn_index,
                cfg.hybrid_moe_every) == (10, 5, 1)   # 1 attn : 9 mamba
        assert not cfg.use_rope and cfg.attention_multiplier == 1 / 128


@pytest.mark.parametrize("arch", list_archs())
def test_arch_smoke_train_step(arch):
    """Reduced config: one loss+grad step, finite, right shapes."""
    cfg = get_config(arch, reduced=True)
    model = build_model(cfg)
    params = init_params(model.param_decls(), jax.random.key(0))
    assert param_count(model.param_decls()) > 0
    batch = _batch(cfg)
    loss, grads = jax.jit(jax.value_and_grad(model.loss_fn))(params, batch)
    assert jnp.isfinite(loss), arch
    assert 0.0 < float(loss) < 20.0
    for leaf in jax.tree.leaves(grads):
        assert bool(jnp.all(jnp.isfinite(leaf))), arch


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "qwen3-moe-235b-a22b",
                                  "mamba2-370m", "jamba-1.5-large-398b",
                                  "seamless-m4t-large-v2", "qwen2-vl-72b"])
def test_arch_serving_consistency(arch):
    """prefill(S) + decode(1) ≍ full forward(S+1) — with a no-drop MoE
    capacity so capacity-based routing cannot couple token sets."""
    cfg = get_config(arch, reduced=True)
    if cfg.n_experts:
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    model = build_model(cfg)
    params = init_params(model.param_decls(), jax.random.key(0))
    Sx = 16
    batch = _batch(cfg, seq=Sx)
    toks = batch["tokens"]
    if cfg.family == "encdec":
        cache = model.init_cache(B, max_len=cfg.max_cache_len, memory_len=16)
        pre, cache = jax.jit(model.prefill)(params, batch["frames"],
                                            toks[:, :Sx], cache)
        dec, _ = jax.jit(model.decode_step)(params, toks[:, Sx:Sx + 1], cache)
        full, _ = model.forward(params, batch["frames"], toks)
    else:
        kw = {}
        cache = model.init_cache(B, max_len=cfg.max_cache_len)
        pre, cache = jax.jit(model.prefill)(params, toks[:, :Sx], cache)
        dec, _ = jax.jit(model.decode_step)(params, toks[:, Sx:Sx + 1], cache)
        full, _ = model.forward(params, toks)
    ref = full[:, Sx].astype(jnp.float32)
    got = dec[:, 0].astype(jnp.float32)
    rel = float(jnp.abs(got - ref).max() / (jnp.abs(ref).max() + 1e-9))
    assert rel < 0.06, (arch, rel)
    assert bool((got.argmax(-1) == ref.argmax(-1)).all()), arch


def test_reduced_configs_stay_in_family():
    for arch in list_archs():
        full = get_config(arch)
        red = get_config(arch, reduced=True)
        assert red.family == full.family
        assert red.n_layers <= 8
        assert red.d_model <= 128
