"""granite-4.0-h-small through the program at a small size on the CPU,
against the benchmark's plain reference (``benchmarks/chip/references/
granite_hybrid.py``, float32, a token-by-token Mamba-2 recurrence, every
expert run densely), on seeded random weights drawn the same way for both;
and the hybrid layout that Jamba keeps."""

from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model, init_params
from repro.models.moe import moe, moe_decls
from repro.sharding.context import mesh_context
from repro.launch.mesh import make_local_mesh

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP / "references"))

import granite_hybrid as ref  # noqa: E402
from weights import make_params  # noqa: E402

# two blocks of five layers (attention at index 2), 4 of 8 experts held
# from expert 2, top-2; every other width as the benchmark's sizes
SIZES = dict(
    json.loads((CHIP / "configs" / "pd-granite-4.0-h-small-stage.json")
               .read_text()),
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    mamba_n_heads=8, mamba_d_head=16, mamba_d_state=128,
    intermediate_size=32, shared_intermediate_size=64, vocab_size=256,
    num_hidden_layers=10,
    layer_types=(["mamba"] * 2 + ["attention"] + ["mamba"] * 2) * 2,
    experts_published=8, num_local_experts=4, first_expert=2,
    num_experts_per_tok=2, initializer_range=0.125)


def small_config(**kw):
    return dataclasses.replace(
        get_config("granite-4.0-h-small"), n_layers=10, d_model=64,
        n_heads=4, n_kv_heads=2, head_dim=16, d_ff=32, vocab=256,
        n_experts=8, top_k=2, shared_d_ff=64, ssm_head_dim=16,
        hybrid_period=5, hybrid_attn_index=2,
        **{"experts_held": 4, "first_expert": 2, **kw})


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_prefill_then_decode_matches_reference(seed):
    """Prefill 48 tokens, decode 7 more through the cache; the logits of
    each of the 8 steps against the reference's full forward pass."""
    model = build_model(small_config())
    params = make_params(seed, ref.weight_spec(SIZES))
    P, G = 48, 8
    toks = np.random.default_rng(seed).integers(0, 256, P + G)
    toks = toks.astype(np.int32)
    cache = model.init_cache(1, P + G)
    logits, cache = jax.jit(model.prefill)(params, jnp.asarray(toks[None, :P]),
                                           cache)
    got = [logits[0, -1]]
    decode = jax.jit(model.decode_step)
    for t in range(P, P + G - 1):
        logits, cache = decode(params, jnp.asarray(toks[None, t:t + 1]),
                               cache)
        got.append(logits[0, -1])
    got = np.asarray(jnp.stack(got).astype(jnp.float32))
    want = ref.logits_at(SIZES, seed, [(toks[:P + G - 1], P - 1)])[0]
    err = np.abs(got - want) / np.abs(want).max()
    # The program computes in bf16 and emits bf16 logits: rounding the
    # logits alone costs up to 2^-9 of the largest (about 0.001 on
    # average); seeds 0-5 read a mean of 0.0009-0.0013.
    assert err.mean() < 0.003
    # At a near-tie of the router, bf16 noise can pick another expert at
    # one position, moving it by that expert's gated share: up to 0.016
    # over seeds 0-5, at most 0.005 elsewhere.
    assert err.max() < 0.03


def test_decode_step_loops_over_hit_experts_prefill_keeps_ragged_dot():
    """Batch-1 decode (T·k = 2 pairs, 4 experts held) runs the routed
    experts as a loop over the held experts a token picked; a 48-token
    prefill (96 pairs) keeps the grouped matmul."""
    model = build_model(small_config())
    params = jax.eval_shape(
        lambda: init_params(model.param_decls(), jax.random.key(0)))
    cache = model.init_cache(1, 56)

    def step_text(step, n):
        return str(jax.make_jaxpr(step)(
            params, jax.ShapeDtypeStruct((1, n), jnp.int32), cache))
    decode, prefill = step_text(model.decode_step, 1), \
        step_text(model.prefill, 48)
    assert "ragged_dot_general" not in decode
    # one loop over the hit experts a layer
    assert decode.count("while[") == 10
    assert prefill.count("ragged_dot_general") == 3 * 10


def _moe_inputs(seed=0, T=24):
    cfg = small_config(experts_held=0, first_expert=0)
    params = init_params(moe_decls(cfg), jax.random.key(seed))
    params["router"] = params["router"] * 20.0    # routing well apart
    x = jax.random.normal(jax.random.key(seed + 1), (1, T, 64), jnp.float32)
    return cfg, params, x.astype(jnp.bfloat16)


def _reference_moe(params, x, *, first, held, k):
    w = {n: jnp.asarray(v, jnp.float32) for n, v in params.items()}
    w.update({n: w[n][first:first + held]
              for n in ("w_gate", "w_up", "w_down")})
    with jax.default_matmul_precision("highest"):
        return ref._moe(jnp.asarray(x[0], jnp.float32), w, k=k, first=first,
                        low=None)


def test_expert_shares_add_up_to_the_whole_layer():
    """Each share of the experts (4 held of 8, from 0 and from 4) returns
    its experts' part plus the shared MLP; the parts with the shared MLP
    counted once are the uncut layer."""
    cfg, params, x = _moe_inputs()
    whole = _reference_moe(params, x, first=0, held=8, k=cfg.top_k)
    shared = _reference_moe(params, x, first=0, held=0, k=cfg.top_k)
    parts = []
    with mesh_context(make_local_mesh()):
        for first in (0, 4):
            share = dataclasses.replace(cfg, experts_held=4,
                                        first_expert=first)
            p = dict(params, **{n: params[n][first:first + 4]
                                for n in ("w_gate", "w_up", "w_down")})
            y, _ = jax.jit(lambda p, x: moe(p, x, share, dropless=True))(p, x)
            parts.append(np.asarray(y[0], np.float32))
            want = _reference_moe(params, x, first=first, held=4,
                                  k=cfg.top_k)
            # bf16 activations and output against float32: 2% of the
            # largest output
            scale = np.abs(want).max()
            assert np.abs(parts[-1] - want).max() < 0.02 * scale
    total = sum(parts) - np.asarray(shared)
    assert np.abs(total - whole).max() < 0.02 * np.abs(whole).max()


def test_serving_drops_no_token_when_one_expert_takes_all():
    """A router that sends every token to expert 0 first: the capacity
    path (training) drops most of them; serving runs every one."""
    cfg, params, x = _moe_inputs(T=32)
    cfg = dataclasses.replace(cfg, top_k=1)
    x = x.at[..., 0].set(4.0)
    params["router"] = params["router"].at[0, 0].set(1e3)
    want = _reference_moe(params, x, first=0, held=8, k=1)
    with mesh_context(make_local_mesh()):
        served, _ = jax.jit(lambda p, x: moe(p, x, cfg, dropless=True))(
            params, x)
        trained, _ = jax.jit(lambda p, x: moe(p, x, cfg))(params, x)
    scale = np.abs(want).max()
    served, trained = (np.asarray(a[0], np.float32) for a in (served,
                                                             trained))
    assert np.abs(served - want).max() < 0.02 * scale
    # capacity ceil(32 * 1 / 8 * 1.25) = 5 of the 32 tokens reach expert 0
    assert np.abs(trained - want).max() > 0.2 * scale


# Jamba's reduced configuration at a fixed seed (capacity factor 8, so
# training drops nothing either): the layout and the outputs the hybrid
# family keeps for it
JAMBA_SHAPES = {
    "embed": (256, 64), "final_norm": (64,), "head": (64, 256),
    "layers/attn/wq": (1, 64, 4, 16), "layers/attn/wk": (1, 64, 2, 16),
    "layers/attn/wv": (1, 64, 2, 16), "layers/attn/wo": (1, 4, 16, 64),
    "layers/ln_mix": (1, 8, 64), "layers/ln_ffn": (1, 8, 64),
    "layers/mamba/w_z": (1, 7, 64, 128), "layers/mamba/w_x": (1, 7, 64, 128),
    "layers/mamba/w_B": (1, 7, 64, 16), "layers/mamba/w_C": (1, 7, 64, 16),
    "layers/mamba/w_dt": (1, 7, 64, 8), "layers/mamba/dt_bias": (1, 7, 8),
    "layers/mamba/A_log": (1, 7, 8), "layers/mamba/D": (1, 7, 8),
    "layers/mamba/conv_x": (1, 7, 4, 128),
    "layers/mamba/conv_B": (1, 7, 4, 16), "layers/mamba/conv_C": (1, 7, 4, 16),
    "layers/mamba/norm": (1, 7, 128),
    "layers/mamba/out_proj": (1, 7, 128, 64),
    "layers/mlp/w_gate": (1, 4, 64, 128), "layers/mlp/w_up": (1, 4, 64, 128),
    "layers/mlp/w_down": (1, 4, 128, 64),
    "layers/moe/router": (1, 4, 64, 8),
    "layers/moe/w_gate": (1, 4, 8, 64, 128),
    "layers/moe/w_up": (1, 4, 8, 64, 128),
    "layers/moe/w_down": (1, 4, 8, 128, 64),
}


def test_jamba_layout_and_outputs_unchanged():
    cfg = dataclasses.replace(get_config("jamba-1.5-large-398b", reduced=True),
                              capacity_factor=8.0)
    model = build_model(cfg)
    decls = model.param_decls()
    flat = {"/".join(k.key for k in path): tuple(d.shape) for path, d in
            jax.tree_util.tree_flatten_with_path(
                decls, is_leaf=lambda d: hasattr(d, "axes"))[0]}
    assert flat == JAMBA_SHAPES
    params = init_params(decls, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(2), (2, 17), 0, cfg.vocab)
    full, aux = jax.jit(model.forward)(params, toks)
    full = np.asarray(full, np.float32)
    # training forward: the same computation as before, to the bit
    assert full[0, 3, :6].tolist() == [0.453125, 1.03125, -0.6171875,
                                       -0.474609375, 0.016357421875,
                                       -0.002655029296875]
    assert full[1, 16, :6].tolist() == [-0.212890625, -0.62109375,
                                        0.00115203857421875, 0.98828125,
                                        -1.453125, -0.1396484375]
    assert float(np.abs(full).sum()) == pytest.approx(6965.873382568359,
                                                      rel=1e-6)
    assert float(aux) == pytest.approx(4.083216667175293, rel=1e-6)
    # serving now runs the blocks unrolled and the experts as a grouped
    # matmul: the same sums in another order, so within bf16 rounding
    cache = model.init_cache(2, cfg.max_cache_len)
    pre, cache = jax.jit(model.prefill)(params, toks[:, :16], cache)
    dec, _ = jax.jit(model.decode_step)(params, toks[:, 16:17], cache)
    was_pre = [[0.265625, -0.1865234375, -0.0301513671875, 1.625,
                0.322265625, -0.2177734375],
               [-0.376953125, -0.06982421875, -1.0078125, -2.546875,
                0.09423828125, -0.96875]]
    was_dec = [[-0.228515625, 0.58984375, -0.8359375, -0.0849609375,
                0.4375, 0.73046875],
               [-0.2080078125, -0.6171875, -0.00010824203491210938,
                0.984375, -1.453125, -0.1513671875]]
    np.testing.assert_allclose(np.asarray(pre[:, 0, :6], np.float32),
                               was_pre, atol=0.02)
    np.testing.assert_allclose(np.asarray(dec[:, 0, :6], np.float32),
                               was_dec, atol=0.02)
