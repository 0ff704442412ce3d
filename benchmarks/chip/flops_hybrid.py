"""Model FLOPs and model HBM bytes of one step of a Granite-4.0-H stage,
computed from shapes (the configuration file's keys).

Counted as the model needs them, not as the program happens to compute
them.  FLOPs: a multiply-add is 2; a Mamba-2 layer's state update and
read-out cost ``4 d_inner d_state`` a token (its recurrence); causal
attention over a prompt of S tokens scores S(S+1)/2 query-key pairs and a
decode step the positions in its cache and itself; a routed token runs
``top_k`` experts, of which ``top_k * held / published`` are expected to be
held here; only the last position's logits are computed.  Element-wise
work (norms, activations, the convolution's gating) is left out.

Bytes: every weight the step needs, read once (norm scales included); of
the routed experts the ones the step's tokens are expected to pick,
``held * (1 - (1 - top_k / published) ** tokens)``; the cache the step
reads (a decode step's Mamba-2 state and convolution tails, and its keys
and values) and writes (the new state and tails, the new tokens' keys and
values); the embedding rows of the new tokens.  Activations between
operations are left out: a step that kept them on the chip would not move
them.
"""

from __future__ import annotations

__all__ = ["widths", "step_flops", "step_bytes"]

BF16, F32 = 2, 4


def widths(sizes: dict) -> dict:
    """Layer counts and widths of the stage."""
    types = sizes["layer_types"][:sizes["num_hidden_layers"]]
    M = sizes["hidden_size"]
    Ha = sizes["num_attention_heads"]
    return {"mamba": types.count("mamba"), "attn": types.count("attention"),
            "moe": len(types), "M": M, "DI": sizes["mamba_expand"] * M,
            "H": sizes["mamba_n_heads"], "N": sizes["mamba_d_state"],
            "K": sizes["mamba_d_conv"], "Ha": Ha,
            "Hk": sizes["num_key_value_heads"], "D": M // Ha,
            "E": sizes["experts_published"],
            "Eh": sizes["num_local_experts"],
            "k": sizes["num_experts_per_tok"],
            "F": sizes["intermediate_size"],
            "Fs": sizes["shared_intermediate_size"],
            "V": sizes["vocab_size"]}


def _pairs(new_tokens: int, context: float) -> float:
    # each new token t (0-based) sees context + t + 1 positions
    return new_tokens * context + new_tokens * (new_tokens + 1) / 2


def step_flops(sizes: dict, new_tokens: int, context: float) -> float:
    """``new_tokens`` tokens appended to a cache of ``context`` positions
    (0 for a prefill)."""
    w = widths(sizes)
    M, DI, H, N, K = w["M"], w["DI"], w["H"], w["N"], w["K"]
    Ha, Hk, D = w["Ha"], w["Hk"], w["D"]
    T = new_tokens
    mamba = (2 * M * (2 * DI + 2 * N + H) + 2 * DI * M
             + 2 * K * (DI + 2 * N) + 4 * DI * N) * T
    attn = (2 * (2 * M * Ha * D + 2 * M * Hk * D) * T
            + 2 * 2 * Ha * D * _pairs(T, context))
    experts_per_token = w["k"] * w["Eh"] / w["E"]
    moe = (2 * M * w["E"] + experts_per_token * 6 * M * w["F"]
           + 6 * M * w["Fs"]) * T
    return float(w["mamba"] * mamba + w["attn"] * attn + w["moe"] * moe
                 + 2 * M * w["V"])


def step_bytes(sizes: dict, new_tokens: int, context: float) -> float:
    """Bytes of the same step: weights, cache read and written, embedding
    rows."""
    w = widths(sizes)
    M, DI, H, N, K = w["M"], w["DI"], w["H"], w["N"], w["K"]
    Ha, Hk, D = w["Ha"], w["Hk"], w["D"]
    T = new_tokens
    mamba_w = (BF16 * (M * (2 * DI + 2 * N + H) + DI * M + K * (DI + 2 * N)
                       + DI) + F32 * 3 * H)
    attn_w = BF16 * (2 * M * Ha * D + 2 * M * Hk * D)
    picked = w["Eh"] * (1 - (1 - w["k"] / w["E"]) ** T)
    moe_w = (F32 * M * w["E"] + BF16 * 3 * M * (picked * w["F"] + w["Fs"]))
    norms = BF16 * (2 * w["moe"] + 1) * M
    state = F32 * (H * (DI // H) * N + (K - 1) * (DI + 2 * N))
    kv_token = BF16 * 2 * Hk * D
    cache = (w["mamba"] * state * (2 if context else 1)
             + w["attn"] * kv_token * (context + T))
    return float(w["mamba"] * mamba_w + w["attn"] * attn_w
                 + w["moe"] * moe_w + norms + BF16 * w["V"] * M + cache
                 + BF16 * T * M)
