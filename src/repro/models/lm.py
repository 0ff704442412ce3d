"""Decoder-only LM assembly for all decoder families.

One scan over stacked per-layer parameters (bounded compile time for the
40–94-layer assigned configs), `jax.checkpoint` remat per scanned layer,
activation sharding constraints at layer boundaries, and three entry points:

* ``loss_fn(params, batch)``          — next-token CE (+ MoE aux) for train;
* ``prefill(params, tokens, ...)``    — fills a stacked KV/SSM cache;
* ``decode_step(params, cache, tok)`` — one token (the ``decode_*`` and
  ``long_500k`` dry-run cells lower this).

Families: ``dense`` | ``moe`` | ``ssm`` (mamba-2) | ``hybrid`` (jamba,
granite-4.0-h) |
``vlm`` (M-RoPE + precomputed patch embeddings — frontend stubbed per the
assignment).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .attention import (KVCache, attention, attention_decls, init_cache)
from .common import cross_entropy_loss, rms_norm
from .config import ModelConfig
from .ffn import mlp, mlp_decls
from .moe import moe, moe_decls
from .param import ArrayDecl, normal_init, ones_init
from .ssm import SSMCache, init_ssm_cache, mamba_block, ssm_decls
from ..sharding.context import current_mesh, data_axes

__all__ = ["LM", "Cache"]

AUX_COEF = 0.01


class Cache(NamedTuple):
    """Stacked per-layer serving cache (members may be None per family)."""
    kv: Any = None           # KVCache with leading layer dim
    ssm: Any = None          # SSMCache with leading layer dim


def _constrain_tokens(x: jax.Array, cfg=None) -> jax.Array:
    """batch→data sharding hint on (B, S, M) activations (dp_only archs
    spread the batch over the model axis as well)."""
    mesh = current_mesh()
    d = data_axes(mesh)
    if cfg is not None and getattr(cfg, "dp_only", False) \
            and "model" in mesh.axis_names:
        d = d + ("model",)
    if not d:
        return x
    spec = P(tuple(d) if len(d) > 1 else d[0], *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


class LM:
    def __init__(self, cfg: ModelConfig):
        if cfg.family not in ("dense", "moe", "ssm", "hybrid", "vlm"):
            raise ValueError(cfg.family)
        self.cfg = cfg

    # ------------------------------------------------------------------
    # parameter schema
    # ------------------------------------------------------------------
    def _layer_decls(self) -> dict:
        cfg = self.cfg
        L = cfg.n_layers
        if cfg.family in ("dense", "vlm"):
            return {
                "ln1": ArrayDecl((L, cfg.d_model), ("layers", "embed"),
                                 init=ones_init),
                "attn": attention_decls(cfg, layers=L),
                "ln2": ArrayDecl((L, cfg.d_model), ("layers", "embed"),
                                 init=ones_init),
                "mlp": mlp_decls(cfg, layers=L),
            }
        if cfg.family == "moe":
            return {
                "ln1": ArrayDecl((L, cfg.d_model), ("layers", "embed"),
                                 init=ones_init),
                "attn": attention_decls(cfg, layers=L),
                "ln2": ArrayDecl((L, cfg.d_model), ("layers", "embed"),
                                 init=ones_init),
                "moe": moe_decls(cfg, layers=L),
            }
        if cfg.family == "ssm":
            return {
                "ln1": ArrayDecl((L, cfg.d_model), ("layers", "embed"),
                                 init=ones_init),
                "mamba": ssm_decls(cfg, layers=L),
            }
        # hybrid: super-blocks of `period` sublayers
        nb = cfg.n_layers // cfg.hybrid_period
        per = cfg.hybrid_period
        n_mamba = per - 1
        n_moe = per // cfg.hybrid_moe_every
        n_mlp = per - n_moe
        sub = {
            "mamba": ssm_decls(cfg, layers=n_mamba),
            "attn": attention_decls(cfg),
            "moe": moe_decls(cfg, layers=n_moe),
            "ln_mix": ArrayDecl((per, cfg.d_model), (None, "embed"),
                                init=ones_init),
            "ln_ffn": ArrayDecl((per, cfg.d_model), (None, "embed"),
                                init=ones_init),
        }
        if n_mlp:
            sub["mlp"] = mlp_decls(cfg, layers=n_mlp)

        def add_block_dim(d: ArrayDecl) -> ArrayDecl:
            return ArrayDecl((nb,) + d.shape, ("layers",) + d.axes,
                             dtype=d.dtype, init=d.init)
        return jax.tree.map(add_block_dim, sub,
                            is_leaf=lambda x: isinstance(x, ArrayDecl))

    def param_decls(self) -> dict:
        cfg = self.cfg
        decls = {
            "embed": ArrayDecl((cfg.vocab, cfg.d_model), ("vocab", "embed"),
                               init=normal_init(0.02)),
            "final_norm": ArrayDecl((cfg.d_model,), ("embed",),
                                    init=ones_init),
            "layers": self._layer_decls(),
        }
        if not cfg.tie_embeddings:
            decls["head"] = ArrayDecl((cfg.d_model, cfg.vocab),
                                      ("embed", "vocab"))
        return decls

    # ------------------------------------------------------------------
    # layer bodies
    # ------------------------------------------------------------------
    def _embed(self, params, tokens):
        x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.bfloat16)
        if self.cfg.embedding_multiplier != 1.0:
            x = x * self.cfg.embedding_multiplier
        return x

    def _residual(self, x, h):
        """``x + h``, the sublayer output ``h`` scaled by the residual
        multiplier first."""
        if self.cfg.residual_multiplier != 1.0:
            h = h * self.cfg.residual_multiplier
        return x + h

    def _logits(self, params, x, *, last: bool = False):
        """Logits of every position of ``x``, or of its last (``last``)."""
        cfg = self.cfg
        head = params["embed"].T if cfg.tie_embeddings else params["head"]
        if last:
            x = x[:, -1:]
        logits = jnp.einsum("bsm,mv->bsv", x, head.astype(x.dtype))
        if cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling
        return logits

    def _dense_layer(self, lp, x, *, mrope_positions=None, cache=None,
                     positions=None):
        cfg = self.cfg
        h, new_kv = attention(lp["attn"], rms_norm(x, lp["ln1"]), cfg,
                              mrope_positions=mrope_positions, cache=cache,
                              positions=positions)
        x = self._residual(x, h)
        if "moe" in lp:
            y, aux = moe(lp["moe"], rms_norm(x, lp["ln2"]), cfg,
                         dropless=cache is not None)
            # name the EP-psum result so the "names" remat policy can save
            # it — otherwise the backward re-executes the fwd psum (§Perf).
            from jax.ad_checkpoint import checkpoint_name
            y = checkpoint_name(y, "moe_out")
        else:
            y, aux = mlp(lp["mlp"], rms_norm(x, lp["ln2"]), cfg), 0.0
        return self._residual(x, y), aux, new_kv

    def _ssm_layer(self, lp, x, *, cache=None):
        h, new_ssm = mamba_block(lp["mamba"], rms_norm(x, lp["ln1"]),
                                 self.cfg, cache=cache)
        return self._residual(x, h), new_ssm

    def _hybrid_block(self, bp, x, *, block=(), cache=None, positions=None):
        """One super-block: `period` sublayers, attn at one index, Mamba-2
        at the others; MoE on every ``hybrid_moe_every``-th FFN, a dense
        MLP on the rest.  cache = (KVCache, SSMCache[n_mamba]).

        ``bp`` and ``cache`` hold this block's parameters and cache, or
        with ``block=(b,)`` every block's, stacked, of which block ``b`` is
        run: each weight is then read with one index, and the routed
        experts in place, so no block's weights are copied out (XLA
        materializes a slice of a slice, and any operand of the grouped
        matmul)."""
        cfg = self.cfg
        per = cfg.hybrid_period
        every = cfg.hybrid_moe_every

        def take(tree, *i):
            return jax.tree.map(lambda a: a[block + i], tree)
        aux_total = 0.0
        mi = fi_moe = fi_mlp = 0
        kv_in = take(cache.kv) if cache is not None else None
        kv_out, ssm_out = None, []
        n_moe = per // every
        moe_p = jax.tree.map(
            lambda a: a.reshape((-1,) + a.shape[len(block) + 1:]), bp["moe"])
        moe_base = block[0] * n_moe if block else 0
        for i in range(per):
            xn = rms_norm(x, bp["ln_mix"][block + (i,)])
            with jax.named_scope("mixer"):
                if i == cfg.hybrid_attn_index:
                    h, kv_out = attention(take(bp["attn"]), xn, cfg,
                                          cache=kv_in, positions=positions)
                else:
                    sc = take(cache.ssm, mi) if cache is not None else None
                    h, s_new = mamba_block(take(bp["mamba"], mi), xn, cfg,
                                           cache=sc)
                    if s_new is not None:
                        # the new state's convolution tails are slices of
                        # (B, S, d_inner) activations: take them now, or
                        # XLA keeps every layer's activations alive until
                        # the cache is stacked
                        ssm_out.append(jax.lax.optimization_barrier(s_new))
                    mi += 1
            x = self._residual(x, h)
            xn = rms_norm(x, bp["ln_ffn"][block + (i,)])
            if i % every == every - 1:
                y, aux = moe(moe_p, xn, cfg, dropless=cache is not None,
                             layer=moe_base + fi_moe)
                aux_total = aux_total + aux
                fi_moe += 1
            else:
                y = mlp(take(bp["mlp"], fi_mlp), xn, cfg)
                fi_mlp += 1
            x = self._residual(x, y)
        new_cache = None
        if cache is not None:
            new_cache = Cache(
                kv=kv_out,
                ssm=jax.tree.map(lambda *xs: jnp.stack(xs), *ssm_out)
                if ssm_out else None)
        return x, aux_total, new_cache

    # ------------------------------------------------------------------
    # forward (training / full-sequence)
    # ------------------------------------------------------------------
    def forward(self, params, tokens, *, vision_embeds=None,
                mrope_positions=None):
        """tokens: (B, S) → logits (B, S, V); also returns aux loss."""
        cfg = self.cfg
        x = self._embed(params, tokens)
        if vision_embeds is not None:
            nv = vision_embeds.shape[1]
            x = jnp.concatenate(
                [vision_embeds.astype(x.dtype), x[:, :-nv or None]], axis=1) \
                if nv else x
            x = x[:, :tokens.shape[1]]
        x = _constrain_tokens(x, cfg)

        lp = params["layers"]
        fam = cfg.family

        def body(carry, layer_params):
            x, aux = carry
            if fam in ("dense", "vlm", "moe"):
                x2, a, _ = self._dense_layer(
                    layer_params, x, mrope_positions=mrope_positions)
            elif fam == "ssm":
                x2, _ = self._ssm_layer(layer_params, x)
                a = 0.0
            else:
                x2, a, _ = self._hybrid_block(layer_params, x)
            x2 = _constrain_tokens(x2, cfg)
            return (x2, aux + a), None

        if cfg.remat:
            if cfg.remat_policy == "dots":
                body = jax.checkpoint(
                    body, policy=jax.checkpoint_policies.checkpoint_dots)
            elif cfg.remat_policy == "names":
                body = jax.checkpoint(
                    body,
                    policy=jax.checkpoint_policies.save_only_these_names(
                        "moe_out"))
            else:
                body = jax.checkpoint(body)
        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), lp)
        x = rms_norm(x, params["final_norm"])
        return self._logits(params, x), aux

    def loss_fn(self, params, batch):
        """batch: {'tokens': (B, S+1), optional 'vision_embeds',
        'mrope_positions', 'mask'} → scalar fp32 loss."""
        tokens = batch["tokens"]
        logits, aux = self.forward(
            params, tokens[:, :-1],
            vision_embeds=batch.get("vision_embeds"),
            mrope_positions=batch.get("mrope_positions"))
        ce = cross_entropy_loss(logits, tokens[:, 1:], batch.get("mask"))
        return ce + AUX_COEF * aux

    # ------------------------------------------------------------------
    # serving
    # ------------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int) -> Cache:
        cfg = self.cfg
        if cfg.family in ("dense", "vlm", "moe"):
            kv = init_cache(cfg, batch, max_len)
            return Cache(kv=jax.tree.map(
                lambda a: (jnp.broadcast_to(a, (cfg.n_layers,) + a.shape)
                           if a.ndim else
                           jnp.broadcast_to(a, (cfg.n_layers,))), kv))
        if cfg.family == "ssm":
            ssm = init_ssm_cache(cfg, batch)
            return Cache(ssm=jax.tree.map(
                lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape),
                ssm))
        nb = cfg.n_layers // cfg.hybrid_period
        nm = cfg.hybrid_period - 1
        kv = init_cache(cfg, batch, max_len)
        ssm = init_ssm_cache(cfg, batch)
        return Cache(
            kv=jax.tree.map(
                lambda a: (jnp.broadcast_to(a, (nb,) + a.shape)
                           if a.ndim else jnp.broadcast_to(a, (nb,))), kv),
            ssm=jax.tree.map(
                lambda a: jnp.broadcast_to(a, (nb, nm) + a.shape), ssm))

    def _apply_cached(self, params, tokens, cache: Cache, *,
                      mrope_positions=None):
        cfg = self.cfg
        fam = cfg.family
        x = _constrain_tokens(self._embed(params, tokens), cfg)

        def body(carry, inp):
            x = carry
            layer_params, layer_cache = inp
            if fam in ("dense", "vlm", "moe"):
                x2, _, new_kv = self._dense_layer(
                    layer_params, x, cache=layer_cache.kv,
                    mrope_positions=mrope_positions)
                new_cache = Cache(kv=new_kv)
            else:
                x2, new_ssm = self._ssm_layer(layer_params, x,
                                              cache=layer_cache.ssm)
                new_cache = Cache(ssm=new_ssm)
            return x2, new_cache

        if fam == "hybrid":
            # unrolled over the blocks, each reading the stacked weights
            # in place (see _hybrid_block)
            blocks = []
            for b in range(cfg.n_layers // cfg.hybrid_period):
                x, _, c = self._hybrid_block(params["layers"], x, block=(b,),
                                             cache=cache)
                blocks.append(c)
            new_caches = jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)
        else:
            x, new_caches = jax.lax.scan(body, x, (params["layers"], cache))
        x = rms_norm(x, params["final_norm"])
        return self._logits(params, x, last=True), new_caches

    def prefill(self, params, tokens, cache: Cache, **kw):
        """tokens: (B, S).  Returns (last-token logits, filled cache)."""
        return self._apply_cached(params, tokens, cache, **kw)

    def decode_step(self, params, token, cache: Cache, **kw):
        """token: (B, 1).  Returns (logits (B,1,V), updated cache)."""
        return self._apply_cached(params, token, cache, **kw)
