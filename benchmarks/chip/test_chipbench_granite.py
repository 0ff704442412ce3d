"""The Granite hybrid cell's whole harness path on the CPU at a small size,
the faults its check must catch, and ``flops_hybrid.py`` against a count
by hand.

Its sizes are its own: two blocks of five layers (four Mamba-2, attention
at index 2), d_state 128, 4 of 8 experts held from expert 2, top-2, 8
output tokens, 96 of them compared.  The check limit is set for this size
from readings (CPU): over seeds 1-10 the program read a mean logit error of
0.00012-0.00058, most of it bf16 rounding of the logits; over seeds 5 and
11-13 a zeroed state read 0.00089-0.00186, an expert left out
0.00122-0.00244 and an unscaled residual 0.16-0.19."""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402

CELL = "granite_h_pd.rag"
KEYS = ["correct", "attempted", "failed", "metrics", "device", "check"]
SIZES = {"hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 2, "mamba_n_heads": 8, "mamba_d_head": 16,
         "mamba_d_state": 128, "intermediate_size": 32,
         "shared_intermediate_size": 64, "vocab_size": 256,
         "num_hidden_layers": 10,
         "layer_types": (["mamba"] * 2 + ["attention"] + ["mamba"] * 2) * 2,
         "experts_published": 8, "num_local_experts": 4, "first_expert": 2,
         "num_experts_per_tok": 2, "initializer_range": 0.125}
TRAFFIC = {"rate_per_s": 8.0, "trace_slice_s": 0.5,
           "prompt_tokens": [[32, 0.5], [64, 0.5]],
           "output_tokens": [[8, 1.0]]}
LIMITS = {"mean_logit_error": 0.0007}


def overrides(served: int = 96) -> dict:
    traffic = harness.load_cell(CELL).traffic
    check = dict(traffic["check"], served_tokens=served, limits=LIMITS)
    return {"sizes": SIZES, "traffic": dict(traffic, **TRAFFIC, check=check)}


def run(seed: int, trace: bool = False) -> dict:
    return harness.run_cell(CELL, seed, 1.0, trace,
                            t_start=time.monotonic(), require_chip=False,
                            overrides=overrides())


def test_window_result_line():
    out = run(2**33 + 7)
    assert list(out) == KEYS
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == 8
    bench = json.loads((harness.CHECKOUT / "BENCHMARK.json").read_text())
    assert set(out["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    json.dumps(out, allow_nan=False)


def test_traced_result_line():
    out = run(13, trace=True)
    assert list(out) == KEYS[:3] + ["breakdown"] + KEYS[3:]
    assert out["correct"] is True
    # on the CPU there is no device plane: the device metrics stay silent
    assert not {"body.prefill_mfu", "body.decode_hbm_share"} \
        & set(out["metrics"])
    assert {"busy_s", "window_s"} <= set(out["device"])


def zeroed_state(monkeypatch):
    """decode Gets a cache whose Mamba-2 state is zero, not the one that
    prefill Put."""
    import jax.numpy as jnp
    from repro.core.dscheduler import InstanceRun

    real = InstanceRun._fetch_inputs

    def fetch(self, node, f):
        kw = real(self, node, f)
        if "cache" in kw:
            ssm = kw["cache"].ssm
            kw["cache"] = kw["cache"]._replace(
                ssm=ssm._replace(state=jnp.zeros_like(ssm.state)))
        return kw
    monkeypatch.setattr(InstanceRun, "_fetch_inputs", fetch)


def expert_left_out(monkeypatch):
    """The first held expert's output never reaches the sum."""
    import jax.numpy as jnp
    import repro.models.moe as moe

    real = moe._experts_dropless

    def experts(t, lid_f, gates, w_gate, w_up, w_down, act, **kw):
        lid_f = jnp.where(lid_f == 0, w_up.shape[-3], lid_f)
        return real(t, lid_f, gates, w_gate, w_up, w_down, act, **kw)
    monkeypatch.setattr(moe, "_experts_dropless", experts)


def residual_unscaled(monkeypatch):
    """Sublayer outputs added to the residual without the multiplier."""
    from repro.models.lm import LM

    monkeypatch.setattr(LM, "_residual", lambda self, x, h: x + h)


FAULTS = {"zeroed_state": zeroed_state, "expert_left_out": expert_left_out,
          "residual_unscaled": residual_unscaled}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_check_catches(monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    out = run(5)
    assert out["correct"] is False
    assert out["check"]["mean_logit_error"]["value"] \
        > out["check"]["mean_logit_error"]["limit"], out["check"]


def test_flops_and_bytes_by_hand():
    from flops_hybrid import step_bytes, step_flops

    s = json.loads((harness.HERE / "configs"
                    / "pd-granite-4.0-h-small-stage.json").read_text())
    s.update(SIZES)
    # 8 Mamba-2 layers: M 64, d_inner 128, 8 heads, d_state 128, conv 4
    mamba = (2 * 64 * (2 * 128 + 2 * 128 + 8) + 2 * 128 * 64
             + 2 * 4 * (128 + 256) + 4 * 128 * 128)       # 151,552 a token
    # 2 attention layers: 4 heads, 2 KV heads, head_dim 16
    attn_proj = 2 * (2 * 64 * 4 * 16 + 2 * 64 * 2 * 16)   # 24,576 a token
    # 10 MoE layers: router over 8, 2 x 4/8 = 1 held expert a token,
    # expert width 32, shared width 64
    moe = 2 * 64 * 8 + 1 * 6 * 64 * 32 + 6 * 64 * 64     # 37,888 a token
    head = 2 * 64 * 256
    T = 32                                    # prefill: 528 query-key pairs
    want = (8 * mamba * T + 2 * (attn_proj * T + 4 * 4 * 16 * 528)
            + 10 * moe * T + head)
    assert step_flops(s, T, 0) == want
    # decode at context 40: 41 pairs
    want = 8 * mamba + 2 * (attn_proj + 4 * 4 * 16 * 41) + 10 * moe + head
    assert step_flops(s, 1, 40) == want

    mamba_w = 2 * (64 * 520 + 128 * 64 + 4 * 384 + 128) + 4 * 3 * 8
    attn_w = 2 * (2 * 64 * 64 + 2 * 64 * 32)
    moe_w = 4 * 64 * 8 + 2 * 3 * 64 * (1 * 32 + 64)  # 4 (1 - (1 - 2/8))
    norms = 2 * 21 * 64
    state = 4 * (8 * 16 * 128 + 3 * 384)
    embed = 2 * 256 * 64
    kv = 2 * 2 * 2 * 16                                    # per position
    want = (8 * mamba_w + 2 * attn_w + 10 * moe_w + norms + embed
            + 8 * 2 * state + 2 * kv * 41 + 2 * 64)
    assert step_bytes(s, 1, 40) == pytest.approx(want)
