"""Model FLOPs of one serving step, computed from shapes.

Counted as the model needs them, not as the program happens to compute
them: a multiply-add is 2 FLOPs; causal attention over a prompt of S
tokens scores S(S+1)/2 query-key pairs; a decode step attends to the
positions already in its cache and itself; only the last position's
logits are computed (the program's prefill and decode both project just
that one).  Element-wise work (norms, activations, rotary embeddings) is
left out.  Decode in these cells is bound by memory and not by FLOPs, so
the share of peak this gives is an honest, small number.
"""

from __future__ import annotations

__all__ = ["dense_step_flops", "mamba2_step_flops"]


def dense_step_flops(sizes: dict, new_tokens: int, context: int) -> float:
    """A StarCoder2-style decoder step: ``new_tokens`` tokens appended to
    a cache that already holds ``context`` positions."""
    M, H = sizes["hidden_size"], sizes["num_attention_heads"]
    Hk, D = sizes["num_key_value_heads"], sizes["head_dim"]
    F, V = sizes["intermediate_size"], sizes["vocab_size"]
    L = sizes["num_hidden_layers"]
    T, c = new_tokens, context
    per_token = 2 * (M * H * D + 2 * M * Hk * D + H * D * M + 2 * M * F)
    # query-key pairs: each new token t (0-based) sees c + t + 1 positions
    pairs = T * c + T * (T + 1) // 2
    attn = 2 * 2 * H * D * pairs          # scores and weighted values
    return float(L * (per_token * T + attn) + 2 * M * V)


def mamba2_step_flops(sizes: dict, new_tokens: int) -> float:
    """A Mamba-2 step over ``new_tokens`` tokens (the state carries the
    context, so the cost does not grow with it)."""
    M, N = sizes["d_model"], sizes["d_state"]
    DI = sizes["expand"] * M
    H = DI // sizes["headdim"]
    K, L = sizes["d_conv"], sizes["n_layer"]
    m = sizes["pad_vocab_size_multiple"]
    V = -(-sizes["vocab_size"] // m) * m
    proj = 2 * M * (2 * DI + 2 * N + H) + 2 * DI * M
    conv = 2 * K * (DI + 2 * N)
    # state update h = a*h + dt*x*B and read-out y = C.h, per token
    ssd = 2 * 2 * DI * N
    return float(L * (proj + conv + ssd) * new_tokens + 2 * M * V)
