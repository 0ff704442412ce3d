"""Plain reference of a StarCoder2-style decoder, as the benchmark runs it.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``highest`` precision; no cache, no batching, no blockwise attention.  It
imports nothing of the program: its weights are drawn again from the seed
by :mod:`weights`, one layer at a time, so that a stage of 4.4 billion
parameters fits in float32 next to its activations.

Architecture (arXiv:2402.19173; ``bigcode/starcoder2-15b``) with the
departures the configuration file lists: RMSNorm in place of LayerNorm, no
biases, no sliding window (the window never binds at the cells' lengths).
Per layer: ``x += Wo attn(rope(Wq n1(x)), rope(Wk n1(x)), Wv n1(x))``,
``x += Wdown gelu_tanh(Wup n2(x))``; grouped-query attention, rotary
embeddings in the split-half convention, causal softmax scaled by
``head_dim ** -0.5``.  Logits: ``n_f(x) @ head``.

``quant="int8"`` or ``"fp8"`` is a control, computed in a precision below
the bfloat16 the configuration states: every weight matrix product takes
operands rounded to it (``lowp.py``), the weights with one scale per output
channel and the activations with one scale per token (W8A8); attention's
own products stay in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from lowp import round_to
from weights import Leaf, drawer, root_key

__all__ = ["weight_spec", "logits_at"]


def weight_spec(sizes: dict) -> dict[str, Leaf]:
    M, H = sizes["hidden_size"], sizes["num_attention_heads"]
    Hk, D = sizes["num_key_value_heads"], sizes["head_dim"]
    F, V = sizes["intermediate_size"], sizes["vocab_size"]
    L = sizes["num_hidden_layers"]
    std = ("normal", sizes["initializer_range"])
    return {
        "embed": Leaf((V, M), std),
        "final_norm": Leaf((M,), ("ones",)),
        "head": Leaf((M, V), std),
        "layers/ln1": Leaf((M,), ("ones",), layers=L),
        "layers/attn/wq": Leaf((M, H, D), std, layers=L),
        "layers/attn/wk": Leaf((M, Hk, D), std, layers=L),
        "layers/attn/wv": Leaf((M, Hk, D), std, layers=L),
        "layers/attn/wo": Leaf((H, D, M), std, layers=L),
        "layers/ln2": Leaf((M,), ("ones",), layers=L),
        "layers/mlp/w_up": Leaf((M, F), std, layers=L),
        "layers/mlp/w_down": Leaf((F, M), std, layers=L),
    }


# contraction axes of each matrix: a control's scales run over the others
_CONTRACT = {"embed": (1,), "head": (0,), "wq": (0,), "wk": (0,),
             "wv": (0,), "wo": (0, 1), "w_up": (0,), "w_down": (0,)}


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    # x: (T, heads, D); split-half rotation
    T, _, D = x.shape
    half = D // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _act(x, axes, low: str | None):
    """Activations entering a weight product: rounded per token under a
    control."""
    return round_to(x, axes, low) if low else x


def _layer(x, w, *, theta, eps, low=None):
    T = x.shape[0]
    H, Hk, D = w["wq"].shape[1], w["wk"].shape[1], w["wq"].shape[2]
    h = _act(_rms(x, w["ln1"], eps), (1,), low)
    q = _rope(jnp.einsum("tm,mhd->thd", h, w["wq"]), theta)
    k = _rope(jnp.einsum("tm,mhd->thd", h, w["wk"]), theta)
    v = jnp.einsum("tm,mhd->thd", h, w["wv"])
    k = jnp.repeat(k, H // Hk, axis=1)
    v = jnp.repeat(v, H // Hk, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * D ** -0.5
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = _act(jnp.einsum("hqk,khd->qhd", p, v), (1, 2), low)
    x = x + jnp.einsum("thd,hdm->tm", o, w["wo"])
    h = _act(_rms(x, w["ln2"], eps), (1,), low)
    u = _act(jax.nn.gelu(h @ w["w_up"], approximate=True), (1,), low)
    return x + u @ w["w_down"]


def logits_at(sizes: dict, seed: int, seqs, *, quant: str | None = None
              ) -> list[np.ndarray]:
    """Logits of each sequence at its read positions.

    ``seqs`` is a list of ``(tokens, first)``: int token ids (T,) and the
    first position whose logits are wanted; returns one float32 array
    ``(T - first, V)`` per sequence.  Each sequence goes through each
    layer in a call of its own, so a program is compiled once per length,
    whatever mix of lengths a sample holds."""
    spec = weight_spec(sizes)
    eps, theta = sizes["norm_epsilon"], float(sizes["rope_theta"])
    root = root_key(seed)
    layer_paths = [p for p in spec if p.startswith("layers/")]
    draw_layer = drawer(spec, layer_paths)
    top = drawer(spec, ["embed", "final_norm", "head"])(root, jnp.int32(0))

    def weights_q(w):
        return {k: round_to(v, _CONTRACT[k], quant)
                if quant and k in _CONTRACT else v for k, v in w.items()}

    @jax.jit
    def layer(xs, w):
        w = weights_q({p.rsplit("/", 1)[-1]: v for p, v in w.items()})
        return jax.lax.map(lambda x: _layer(x, w, theta=theta, eps=eps,
                                            low=quant), xs)

    @jax.jit
    def logits(x, fn, head):
        return _act(_rms(x, fn, eps), (1,), quant) @ head

    groups = {j: [j] for j in range(len(seqs))}
    out: list = [None] * len(seqs)
    with jax.default_matmul_precision("highest"):
        embed = weights_q({"embed": top["embed"]})["embed"]
        xs = {n: jnp.take(embed, jnp.asarray(np.stack(
            [seqs[j][0] for j in idx]), jnp.int32), axis=0)
            for n, idx in groups.items()}
        del embed
        for l in range(sizes["num_hidden_layers"]):
            w = draw_layer(root, jnp.int32(l))
            xs = {n: layer(x, w) for n, x in xs.items()}
            del w
        head = weights_q({"head": top["head"]})["head"]
        for n, idx in groups.items():
            for k, j in enumerate(idx):
                first = seqs[j][1]
                out[j] = np.asarray(logits(xs[n][k, first:],
                                           top["final_norm"], head))
    return out
