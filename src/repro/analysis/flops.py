"""Analytic FLOP/parameter accounting per architecture × shape cell.

``MODEL_FLOPS`` follows the brief: 6·N·D for dense training (N params,
D tokens), 6·N_active·D for MoE; decode/prefill use the forward-only 2·N·D
plus the attention term.  These are the "useful compute" yardsticks the
roofline compares XLA's HLO FLOPs against (ratio ≈ 1/3 for an ideal
remat-free fwd, <1 when remat recompute or causal over-compute inflates the
compiled program).
"""

from __future__ import annotations

from ..models.config import ModelConfig

__all__ = ["param_counts", "active_params", "model_flops"]


def _attn_params(cfg: ModelConfig) -> int:
    M, H, Hk, D = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return M * H * D + 2 * M * Hk * D + H * D * M


def _mlp_params(cfg: ModelConfig, d_ff: int) -> int:
    mult = 3 if cfg.glu else 2
    return mult * cfg.d_model * d_ff


def _expert_params(cfg: ModelConfig) -> int:
    return _mlp_params(cfg, cfg.d_ff)


def _shared_params(cfg: ModelConfig) -> int:
    return _mlp_params(cfg, cfg.shared_width) if cfg.n_shared_experts else 0


def _mamba_params(cfg: ModelConfig) -> int:
    M, DI, N, H = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    return (2 * M * DI          # w_z, w_x
            + 2 * M * N         # w_B, w_C
            + M * H             # w_dt
            + DI * M)           # out_proj


def param_counts(cfg: ModelConfig) -> dict:
    """total / active parameter counts (embedding included once)."""
    V, M, L = cfg.vocab, cfg.d_model, cfg.n_layers
    embed = V * M * (1 if cfg.tie_embeddings else 2)
    fam = cfg.family
    if fam in ("dense", "vlm"):
        layer = _attn_params(cfg) + _mlp_params(cfg, cfg.d_ff)
        total = active = L * layer
    elif fam == "moe":
        shared = _shared_params(cfg)
        layer_fixed = _attn_params(cfg) + shared + M * cfg.n_experts
        total = L * (layer_fixed + cfg.n_experts * _expert_params(cfg))
        active = L * (layer_fixed + cfg.top_k * _expert_params(cfg))
    elif fam == "ssm":
        total = active = L * _mamba_params(cfg)
    elif fam == "hybrid":
        per = cfg.hybrid_period
        nb = L // per
        n_moe = per // cfg.hybrid_moe_every
        n_mlp = per - n_moe
        mixers = _attn_params(cfg) + (per - 1) * _mamba_params(cfg)
        moe_fixed = _shared_params(cfg) + M * cfg.n_experts
        ffn_total = (n_mlp * _mlp_params(cfg, cfg.d_ff) + n_moe
                     * (moe_fixed + cfg.n_experts * _expert_params(cfg)))
        ffn_active = (n_mlp * _mlp_params(cfg, cfg.d_ff) + n_moe
                      * (moe_fixed + cfg.top_k * _expert_params(cfg)))
        total = nb * (mixers + ffn_total)
        active = nb * (mixers + ffn_active)
    elif fam == "encdec":
        enc = cfg.n_encoder_layers * (_attn_params(cfg)
                                      + _mlp_params(cfg, cfg.d_ff))
        dec = cfg.n_layers * (2 * _attn_params(cfg)
                              + _mlp_params(cfg, cfg.d_ff))
        total = active = enc + dec
    else:
        raise ValueError(fam)
    return {"total": total + embed, "active": active + embed,
            "embed": embed}


def active_params(cfg: ModelConfig) -> int:
    return param_counts(cfg)["active"]


def _attn_quadratic_flops(cfg: ModelConfig, batch: int, seq: int,
                          n_attn_layers: int, causal: bool = True) -> float:
    """QK^T + PV matmul flops (2 matmuls × 2 flops/MAC), causal halved."""
    H, D = cfg.n_heads, cfg.head_dim
    full = 4.0 * batch * H * seq * seq * D
    return n_attn_layers * (full / 2 if causal else full)


def _n_attn_layers(cfg: ModelConfig) -> int:
    if cfg.family in ("dense", "vlm", "moe"):
        return cfg.n_layers
    if cfg.family == "ssm":
        return 0
    if cfg.family == "hybrid":
        return cfg.n_layers // cfg.hybrid_period
    if cfg.family == "encdec":
        return cfg.n_encoder_layers + 2 * cfg.n_layers
    raise ValueError(cfg.family)


def model_flops(cfg: ModelConfig, cell) -> dict:
    """MODEL_FLOPS for the cell (per executed step, whole mesh)."""
    counts = param_counts(cfg)
    Na = counts["active"]
    B, S = cell.batch, cell.seq
    kind = cell.kind
    if kind == "train":
        tokens = B * S
        matmul = 6.0 * Na * tokens
        attn = 3.0 * _attn_quadratic_flops(cfg, B, S, _n_attn_layers(cfg))
        return {"model_flops": matmul + attn, "matmul_6nd": matmul,
                "attention": attn, "tokens": tokens,
                "params_total": counts["total"],
                "params_active": counts["active"]}
    if kind == "prefill":
        tokens = B * S
        matmul = 2.0 * Na * tokens
        attn = _attn_quadratic_flops(cfg, B, S, _n_attn_layers(cfg))
        return {"model_flops": matmul + attn, "matmul_6nd": matmul,
                "attention": attn, "tokens": tokens,
                "params_total": counts["total"],
                "params_active": counts["active"]}
    # decode: one token per sequence against a seq-long cache
    tokens = B
    matmul = 2.0 * Na * tokens
    H, D = cfg.n_heads, cfg.head_dim
    attn = 4.0 * B * H * S * D * _n_attn_layers(cfg)
    return {"model_flops": matmul + attn, "matmul_6nd": matmul,
            "attention": attn, "tokens": tokens,
            "params_total": counts["total"],
            "params_active": counts["active"]}
