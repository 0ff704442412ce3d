"""Model configuration — one dataclass covers all 10 assigned families.

``family`` selects the assembly:
  * ``dense``   — decoder-only transformer (GQA + MLP)
  * ``moe``     — decoder-only with MoE FFN layers
  * ``ssm``     — Mamba-2 (SSD) stack, attention-free
  * ``hybrid``  — Mamba-2 super-blocks with one attention sublayer and MoE
                  on every ``hybrid_moe_every``-th FFN (Jamba: 1:7, every
                  2nd; Granite-4.0-H: 1:9, every FFN)
  * ``encdec``  — encoder-decoder (seamless-m4t backbone)
  * ``vlm``     — decoder-only with M-RoPE + vision-embedding inputs (the
                  modality frontend is a stub: inputs are precomputed patch
                  embeddings, per the assignment brief)
"""

from __future__ import annotations

import dataclasses
from typing import Literal

__all__ = ["ModelConfig"]


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: Literal["dense", "moe", "ssm", "hybrid", "encdec", "vlm"]
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int | None = None          # default d_model // n_heads
    # TP divisibility: lower with this many heads, the extras hard-masked to
    # zero (output-exact; ~H_pad/H extra attention FLOPs — see DESIGN.md).
    pad_heads_to: int | None = None

    # positional / norm flavor
    rope_theta: float = 10000.0
    use_rope: bool = True                # False: no positions (NoPE)
    use_mrope: bool = False              # qwen2-vl
    qk_norm: bool = False                # qwen3
    activation: str = "silu"             # "silu" | "gelu" | "squared_relu"
    glu: bool = True                     # gated FFN (SwiGLU); False → plain
    tie_embeddings: bool = False
    # Granite-style scalings: embeddings × embedding_multiplier, each
    # sublayer's output × residual_multiplier before its residual add,
    # logits ÷ logits_scaling; attention_multiplier is the softmax scale
    # (None: head_dim ** -0.5).
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    attention_multiplier: float | None = None

    # MoE
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    shared_d_ff: int = 0                 # shared MLP width (0: d_ff × n)
    capacity_factor: float = 1.25
    # expert parallelism: this device holds experts [first_expert,
    # first_expert + experts_held) of every MoE layer (0: all n_experts);
    # the router still covers all n_experts.
    experts_held: int = 0
    first_expert: int = 0

    # SSM (mamba2)
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_head_dim: int = 64
    ssm_expand: int = 2

    # hybrid: layers per super-block and which are attention / MoE
    hybrid_period: int = 8
    hybrid_attn_index: int = 3           # 1 attn : 7 mamba
    hybrid_moe_every: int = 2            # MoE on every 2nd sublayer (1: all)

    # encdec
    n_encoder_layers: int = 0

    # attention implementation for prefill/train ("xla" blockwise ref or
    # "pallas" kernels — kernels target TPU; dry-run lowers the xla path)
    attention_impl: str = "xla"
    q_chunk: int = 512
    kv_chunk: int = 1024

    # training-time behavior
    remat: bool = True                   # checkpoint each scanned layer
    microbatches: int = 1                # grad-accumulation steps

    # inference
    max_cache_len: int = 32768
    # §Perf levers (hillclimb; defaults = paper-faithful baseline):
    # one-hot masked cache write instead of dynamic_update_slice — elementwise
    # and sharding-preserving, avoids the per-layer cache all-gather that
    # SPMD inserts for a traced-offset DUS into a sequence-sharded cache.
    onehot_cache_update: bool = False
    # decode with unexpanded GQA K/V (the (Hk,G) reshape is negligible for a
    # single query token; skips materializing the G-times-expanded cache).
    decode_unexpanded_gqa: bool = False
    # map the model axis to extra data parallelism (small archs for which
    # 16-way tensor parallel is pure overhead).
    dp_only: bool = False
    # attention softmax pipeline dtype on the XLA path ("float32" matches
    # the kernels' fp32 VMEM accumulators; "bfloat16" halves the HBM
    # traffic of the materialized score pipeline at ~1e-2 rel tolerance).
    softmax_dtype: str = "float32"
    # remat policy for the layer scan: "full" (recompute everything),
    # "dots" (save matmul outputs — trades HBM capacity for bandwidth).
    remat_policy: str = "full"
    # MoE data plane: "scatter" materializes (T·k, M) dispatch/combine
    # tensors (baseline); "gather" inverts the slot→token map so only
    # (E_local·C, M) tensors ever exist — O(k·capacity_factor/E_local)
    # smaller (§Perf hillclimb on the MoE cells).
    moe_dispatch: str = "scatter"

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim",
                               self.d_model // max(self.n_heads, 1))
        if self.family in ("moe", "hybrid") and not self.n_experts:
            raise ValueError(f"{self.name}: MoE family needs n_experts")
        if self.family in ("ssm", "hybrid") and not self.ssm_state:
            raise ValueError(f"{self.name}: SSM family needs ssm_state")

    # -- derived -----------------------------------------------------------
    @property
    def n_heads_eff(self) -> int:
        """Lowered head count (pad_heads_to when set)."""
        return self.pad_heads_to or self.n_heads

    @property
    def d_inner(self) -> int:
        """Mamba inner width."""
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def n_experts_held(self) -> int:
        return self.experts_held or self.n_experts

    @property
    def shared_width(self) -> int:
        """Width of the shared-expert MLP beside the routed experts."""
        return self.shared_d_ff or self.d_ff * self.n_shared_experts

    @property
    def attends(self) -> bool:
        return self.family != "ssm"

    def reduced(self, seq_hint: int = 128) -> "ModelConfig":
        """Tiny same-family config for CPU smoke tests."""
        kv = max(1, min(self.n_kv_heads, 2))
        heads = max(kv * 2, 4)
        return dataclasses.replace(
            self,
            name=self.name + "-reduced",
            n_layers=min(self.n_layers, 4) if self.family != "hybrid"
                     else min(self.hybrid_period, 8),
            hybrid_period=min(self.hybrid_period, 8),
            hybrid_attn_index=min(self.hybrid_attn_index, 7),
            d_model=64,
            n_heads=heads,
            n_kv_heads=kv,
            head_dim=16,
            d_ff=128,
            vocab=256,
            n_experts=min(self.n_experts, 8) if self.n_experts else 0,
            top_k=min(self.top_k, 2) if self.top_k else 0,
            n_shared_experts=min(self.n_shared_experts, 1),
            shared_d_ff=min(self.shared_d_ff, 256),
            experts_held=0,
            first_expert=0,
            ssm_state=min(self.ssm_state, 16) if self.ssm_state else 0,
            ssm_head_dim=16,
            n_encoder_layers=min(self.n_encoder_layers, 2),
            pad_heads_to=None,
            q_chunk=max(seq_hint // 2, 16),
            kv_chunk=max(seq_hint // 2, 16),
            max_cache_len=seq_hint,
            microbatches=1,
        )
