"""GQA attention: blockwise (flash-style) training/prefill + cached decode.

The training/prefill path is *blockwise*: an outer ``lax.scan`` over query
chunks and an inner ``lax.scan`` over KV chunks with an online-softmax
running (max, denominator, accumulator).  This keeps the live working set at
``O(q_chunk × kv_chunk)`` instead of ``O(S²)`` — mandatory for the 32k
prefill shapes, and it is the exact algorithm the Pallas kernel
(:mod:`repro.kernels.flash_attention`) implements on TPU VMEM tiles; this
jnp version doubles as its oracle.

GQA is handled *ungrouped*: K/V keep ``n_kv_heads`` and Q is reshaped to
``(kv_heads, group)`` so no K/V repetition is materialized.

Cached decode: single-token queries against a fixed-capacity cache with a
length mask (used by ``serve_step``; 32k and 500k decode cells).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .common import apply_mrope, apply_rope, rms_norm, rope_table
from .config import ModelConfig
from .param import ArrayDecl, normal_init, ones_init

__all__ = ["attention_decls", "attention", "blockwise_attention",
           "decode_attention", "KVCache", "init_cache"]

NEG_INF = -1e30


class KVCache(NamedTuple):
    k: jax.Array          # (B, max_len, kv_heads, head_dim)
    v: jax.Array          # (B, max_len, kv_heads, head_dim)
    length: jax.Array     # () int32 — tokens currently valid


def attention_decls(cfg: ModelConfig, layers: int | None = None) -> dict:
    """Parameter declarations; ``layers`` adds a leading stacked-layer axis.

    ``n_heads_eff`` (zero-mask-padded when the table head count does not
    divide the model axis) keeps every attention activation flat on a
    single shardable heads dimension — no (Hk, G) split reshapes, which
    SPMD cannot re-partition without involuntary rematerialization."""
    H, Hk, D, M = cfg.n_heads_eff, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    lead = (layers,) if layers else ()
    lax_ = ("layers",) if layers else ()
    decls = {
        "wq": ArrayDecl(lead + (M, H, D), lax_ + ("embed", "heads", "head_dim")),
        "wk": ArrayDecl(lead + (M, Hk, D), lax_ + ("embed", "kv_heads", "head_dim")),
        "wv": ArrayDecl(lead + (M, Hk, D), lax_ + ("embed", "kv_heads", "head_dim")),
        "wo": ArrayDecl(lead + (H, D, M), lax_ + ("heads", "head_dim", "embed")),
    }
    if cfg.qk_norm:
        decls["q_norm"] = ArrayDecl(lead + (D,), lax_ + (None,),
                                    init=ones_init)
        decls["k_norm"] = ArrayDecl(lead + (D,), lax_ + (None,),
                                    init=ones_init)
    return decls


# ----------------------------------------------------------------------
def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool, q_chunk: int, kv_chunk: int,
                        q_offset: int = 0,
                        softmax_dtype=jnp.float32,
                        scale: float | None = None) -> jax.Array:
    """Online-softmax attention.

    q: (B, Sq, H, D);  k, v: (B, Skv, Hk, D) with H % Hk == 0.
    Returns (B, Sq, H, D).  ``q_offset`` shifts query positions for causal
    masking (prefill continuation).  ``softmax_dtype`` sets the materialized
    score-pipeline dtype (running max/denominator stay fp32).  ``scale``
    multiplies the scores (default ``D ** -0.5``).
    """
    B, Sq, H, D = q.shape
    _, Skv, Hk, _ = k.shape
    G = H // Hk
    scale = 1.0 / math.sqrt(D) if scale is None else scale
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Skv)
    if Sq % q_chunk or Skv % kv_chunk:
        raise ValueError(f"chunking must divide: {Sq}%{q_chunk}, "
                         f"{Skv}%{kv_chunk}")
    nq, nk = Sq // q_chunk, Skv // kv_chunk

    qr = (q.reshape(B, nq, q_chunk, Hk, G, D) * scale).astype(q.dtype)
    kr = k.reshape(B, nk, kv_chunk, Hk, D)
    vr = v.reshape(B, nk, kv_chunk, Hk, D)
    # scan over q chunks (leading axis first)
    qr = jnp.moveaxis(qr, 1, 0)           # (nq, B, cq, Hk, G, D)
    kr = jnp.moveaxis(kr, 1, 0)           # (nk, B, ck, Hk, D)
    vr = jnp.moveaxis(vr, 1, 0)

    q_pos_base = jnp.arange(q_chunk)
    k_pos_base = jnp.arange(kv_chunk)

    def q_body(_, qi_qc):
        qi, qc = qi_qc                    # qc: (B, cq, Hk, G, D)
        m0 = jnp.full((B, q_chunk, Hk, G), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, q_chunk, Hk, G), jnp.float32)
        a0 = jnp.zeros((B, q_chunk, Hk, G, D), jnp.float32)

        def kv_body(carry, ki_kc):
            m, l, acc = carry
            ki, kc, vc = ki_kc
            s = jnp.einsum("bqhgd,bkhd->bqhgk", qc, kc,
                           preferred_element_type=softmax_dtype)
            if causal:
                qpos = q_offset + qi * q_chunk + q_pos_base   # (cq,)
                kpos = ki * kv_chunk + k_pos_base             # (ck,)
                mask = qpos[:, None] >= kpos[None, :]
                s = jnp.where(mask[None, :, None, None, :],
                              s, jnp.asarray(NEG_INF, s.dtype))
            m_new = jnp.maximum(m, s.max(axis=-1).astype(jnp.float32))
            p = jnp.exp(s - m_new[..., None].astype(s.dtype))
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1).astype(jnp.float32)
            pv = jnp.einsum("bqhgk,bkhd->bqhgd", p.astype(vc.dtype), vc,
                            preferred_element_type=jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        (m, l, acc), _ = jax.lax.scan(
            kv_body, (m0, l0, a0), (jnp.arange(nk), kr, vr))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_body, None, (jnp.arange(nq), qr))
    # outs: (nq, B, cq, Hk, G, D) -> (B, Sq, H, D)
    outs = jnp.moveaxis(outs, 0, 1).reshape(B, Sq, Hk, G, D)
    return outs.reshape(B, Sq, H, D)


def decode_attention(q: jax.Array, cache: KVCache,
                     scale: float | None = None) -> jax.Array:
    """Single-step attention against a masked fixed-size cache.

    q: (B, 1, H, D); cache.k/v: (B, L, Hk, D).  Returns (B, 1, H, D).
    """
    B, Sq, H, D = q.shape
    _, L, Hk, _ = cache.k.shape
    G = H // Hk
    scale = 1.0 / math.sqrt(D) if scale is None else scale
    qr = q.reshape(B, Sq, Hk, G, D) * scale
    s = jnp.einsum("bqhgd,bkhd->bqhgk", qr, cache.k,
                   preferred_element_type=jnp.float32)
    valid = jnp.arange(L) < cache.length                  # (L,)
    s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqhgk,bkhd->bqhgd", p.astype(cache.v.dtype), cache.v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Sq, H, D).astype(q.dtype)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> KVCache:
    return KVCache(
        k=jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.head_dim), dtype),
        v=jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.head_dim), dtype),
        length=jnp.zeros((), jnp.int32),
    )


# ----------------------------------------------------------------------
def attention(params: dict, x: jax.Array, cfg: ModelConfig, *,
              positions: jax.Array | None = None,
              mrope_positions: jax.Array | None = None,
              causal: bool = True,
              cache: KVCache | None = None,
              kv_source: jax.Array | None = None):
    """Full attention sublayer: projections + rope + core + output proj.

    x: (B, S, M).  Modes:
      * cache is None                    → training / full prefill;
      * cache given and S == 1           → cached decode step;
      * cache given and S > 1            → prefill that fills the cache.
    ``kv_source`` (encoder memory) switches to cross-attention (no rope,
    no cache update, not causal).  ``cfg.use_rope`` False leaves positions
    out (NoPE); ``cfg.attention_multiplier`` sets the softmax scale.
    Returns (out, new_cache_or_None).
    """
    B, S, M = x.shape
    H, Hk, D = cfg.n_heads_eff, cfg.n_kv_heads, cfg.head_dim
    G = H // Hk
    q = jnp.einsum("bsm,mhd->bshd", x, params["wq"])
    kv_in = x if kv_source is None else kv_source
    k = jnp.einsum("bsm,mhd->bshd", kv_in, params["wk"])
    v = jnp.einsum("bsm,mhd->bshd", kv_in, params["wv"])

    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"])
        k = rms_norm(k, params["k_norm"])

    def expand_kv(t):
        """(B, S', Hk, D) -> (B, S', H, D): local broadcast on the XLA path
        (KV is model-replicated; the Pallas kernel keeps true GQA on TPU)."""
        if G == 1:
            return t
        return jnp.repeat(t, G, axis=2)

    is_cross = kv_source is not None
    scale = cfg.attention_multiplier
    if not is_cross and cfg.use_rope:
        if positions is None:
            base = cache.length if cache is not None else 0
            positions = base + jnp.arange(S)[None, :]          # (1, S)
            positions = jnp.broadcast_to(positions, (B, S))
        if cfg.use_mrope and mrope_positions is not None:
            q = apply_mrope(q, mrope_positions, D, theta=cfg.rope_theta)
            k = apply_mrope(k, mrope_positions, D, theta=cfg.rope_theta)
        else:
            cos, sin = rope_table(positions, D, cfg.rope_theta)
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)

    new_cache = None
    if cache is not None and not is_cross:
        if cfg.onehot_cache_update and S == 1:
            # Elementwise masked write: SPMD keeps the cache sharding (a
            # traced-offset DUS into a seq-sharded cache all-gathers it).
            sel = (jnp.arange(cache.k.shape[1]) == cache.length)
            sel = sel[None, :, None, None]
            k_all = jnp.where(sel, k.astype(cache.k.dtype), cache.k)
            v_all = jnp.where(sel, v.astype(cache.v.dtype), cache.v)
        else:
            k_all = jax.lax.dynamic_update_slice_in_dim(
                cache.k, k.astype(cache.k.dtype), cache.length, axis=1)
            v_all = jax.lax.dynamic_update_slice_in_dim(
                cache.v, v.astype(cache.v.dtype), cache.length, axis=1)
        new_cache = KVCache(k_all, v_all, cache.length + S)
        if S == 1:
            if cfg.decode_unexpanded_gqa:
                out = decode_attention(q, new_cache, scale)
            else:
                out = decode_attention(
                    q, KVCache(expand_kv(k_all), expand_kv(v_all),
                               new_cache.length), scale)
        else:
            # Prefill: attend over the fresh tokens blockwise (cache assumed
            # empty before a prefill; continuation uses q_offset).
            out = blockwise_attention(
                q, expand_kv(k), expand_kv(v), causal=causal,
                q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
                softmax_dtype=jnp.dtype(cfg.softmax_dtype), scale=scale)
    else:
        out = blockwise_attention(
            q, expand_kv(k), expand_kv(v), causal=causal and not is_cross,
            q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
            softmax_dtype=jnp.dtype(cfg.softmax_dtype), scale=scale)

    if cfg.pad_heads_to:
        # Hard-mask the padded heads: output-exact w.r.t. the table config.
        mask = (jnp.arange(H) < cfg.n_heads).astype(out.dtype)
        out = out * mask[None, None, :, None]
    y = jnp.einsum("bshd,hdm->bsm", out, params["wo"])
    return y, new_cache
