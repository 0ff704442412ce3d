"""Plain reference of a Granite-4.0-H stage, as the benchmark runs it.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``highest`` precision; no cache, no chunked scan, no grouped matmul.  It
imports nothing of the program: its weights are drawn again from the seed
by :mod:`weights`, one block of ``period`` layers at a time, in the bf16
values the program is served and widened to float32 one layer at a time.

Architecture (``ibm-granite/granite-4.0-h-small``, model type
``granitemoehybrid``) with the departures the configuration file lists
(no convolution bias; RMSNorm epsilon 1e-6).  With ``n(x)`` an RMSNorm and
``r`` the residual multiplier, layer ``i`` of a block is::

    x += r * mixer_i(n(x))                 # Mamba-2, or attention at one index
    x += r * (moe(n(x)) + shared_mlp(n(x)))

* Mamba-2 (one group), written as its recurrence, one token after another::

      z, xs, B, C = u Wz, u Wx, u WB, u WC
      dt = softplus(u Wdt + dt_bias);  A = -exp(A_log)           (per head)
      xs, B, C = silu(causal_conv(xs)), silu(causal_conv(B)), silu(...(C))
      h_t = exp(dt_t A) h_{t-1} + dt_t xs_t B_t^T;  y_t = h_t C_t + D xs_t
      out = Wout n_g(y * silu(z))

* attention: causal grouped-query softmax attention with no positional
  embedding (NoPE), scores scaled by ``attention_multiplier``, computed a
  block of queries at a time so that 8k-token sequences fit;
* MoE: the router's logits over all ``experts_published`` experts, the
  top ``num_experts_per_tok`` of them, gates the softmax over those logits;
  the output is the gated sum of the experts this chip holds
  (``first_expert`` .. + ``num_local_experts``), each a SwiGLU MLP run
  densely over every token, its gate zero where the token did not pick it;
  the absent experts' part is left out, as in the program;
* shared MLP: one SwiGLU MLP of width ``shared_intermediate_size``.

Embeddings are multiplied by ``embedding_multiplier``; logits are
``n_f(x) @ embed^T / logits_scaling`` (tied).

``quant="int8"`` or ``"fp8"`` is a control, computed in a precision below
the bfloat16 the configuration states: every weight matrix product takes
operands rounded to it (``lowp.py``), the weights with one scale per output
channel and the activations with one scale per token (W8A8); the
convolution, the recurrence and attention's own products stay in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from lowp import round_to
from weights import Leaf, _draw_traced, drawer, root_key

__all__ = ["weight_spec", "logits_at", "layout"]

QUERY_BLOCK = 256


def layout(sizes: dict) -> dict:
    """Blocks, layers per block, the attention layer's index in a block,
    and the widths the reference computes with."""
    types = sizes["layer_types"]
    L = sizes["num_hidden_layers"]
    attn = [i for i, t in enumerate(types) if t == "attention"]
    period = attn[1] - attn[0] if len(attn) > 1 else L
    if L % period or any((t == "attention") != (i % period == attn[0] % period)
                         for i, t in enumerate(types)):
        raise ValueError("layer_types is not one attention layer per block "
                         "of equal length")
    M = sizes["hidden_size"]
    return {"blocks": L // period, "period": period,
            "attn_index": attn[0] % period, "M": M,
            "DI": sizes["mamba_expand"] * M, "H": sizes["mamba_n_heads"],
            "P": sizes["mamba_d_head"], "N": sizes["mamba_d_state"],
            "K": sizes["mamba_d_conv"],
            "Ha": sizes["num_attention_heads"],
            "Hk": sizes["num_key_value_heads"],
            "D": M // sizes["num_attention_heads"],
            "E": sizes["experts_published"],
            "Eh": sizes["num_local_experts"],
            "F": sizes["intermediate_size"],
            "Fs": sizes["shared_intermediate_size"],
            "V": sizes["vocab_size"]}


def weight_spec(sizes: dict) -> dict[str, Leaf]:
    g = layout(sizes)
    M, DI, H, N, K = g["M"], g["DI"], g["H"], g["N"], g["K"]
    Ha, Hk, D = g["Ha"], g["Hk"], g["D"]
    Eh, E, F, Fs = g["Eh"], g["E"], g["F"], g["Fs"]
    nb, per = g["blocks"], g["period"]
    nm = per - 1
    std = ("normal", sizes["initializer_range"])
    conv = ("normal", (3 * K) ** -0.5)

    def blk(shape, init=std, dtype="bfloat16"):
        return Leaf(shape, init, dtype=dtype, layers=nb)
    return {
        "embed": Leaf((g["V"], M), std),
        "final_norm": Leaf((M,), ("ones",)),
        "layers/ln_mix": blk((per, M), ("ones",)),
        "layers/ln_ffn": blk((per, M), ("ones",)),
        "layers/mamba/w_z": blk((nm, M, DI)),
        "layers/mamba/w_x": blk((nm, M, DI)),
        "layers/mamba/w_B": blk((nm, M, N)),
        "layers/mamba/w_C": blk((nm, M, N)),
        "layers/mamba/w_dt": blk((nm, M, H)),
        "layers/mamba/dt_bias": blk((nm, H), ("dt_bias", 1e-3, 1e-1),
                                    "float32"),
        "layers/mamba/A_log": blk((nm, H), ("log_uniform", 1, 16),
                                  "float32"),
        "layers/mamba/D": blk((nm, H), ("ones",), "float32"),
        # depthwise conv: the variance of PyTorch's U(+-1/sqrt(K)) default
        "layers/mamba/conv_x": blk((nm, K, DI), conv),
        "layers/mamba/conv_B": blk((nm, K, N), conv),
        "layers/mamba/conv_C": blk((nm, K, N), conv),
        "layers/mamba/norm": blk((nm, DI), ("ones",)),
        "layers/mamba/out_proj": blk((nm, DI, M)),
        "layers/attn/wq": blk((M, Ha, D)),
        "layers/attn/wk": blk((M, Hk, D)),
        "layers/attn/wv": blk((M, Hk, D)),
        "layers/attn/wo": blk((Ha, D, M)),
        "layers/moe/router": blk((per, M, E), std, "float32"),
        "layers/moe/w_gate": blk((per, Eh, M, F)),
        "layers/moe/w_up": blk((per, Eh, M, F)),
        "layers/moe/w_down": blk((per, Eh, F, M)),
        "layers/moe/shared_gate": blk((per, M, Fs)),
        "layers/moe/shared_up": blk((per, M, Fs)),
        "layers/moe/shared_down": blk((per, Fs, M)),
    }


# contraction axes of each matrix (per layer): a control's scales run over
# the others
_CONTRACT = {"w_z": (0,), "w_x": (0,), "w_B": (0,), "w_C": (0,),
             "w_dt": (0,), "out_proj": (0,), "wq": (0,), "wk": (0,),
             "wv": (0,), "wo": (0, 1), "router": (0,), "w_gate": (1,),
             "w_up": (1,), "w_down": (1,), "shared_gate": (0,),
             "shared_up": (0,), "shared_down": (0,), "embed": (1,)}


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _conv(u, w):
    # causal depthwise conv over time; u: (T, C), w: (K, C)
    K = w.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), u.dtype), u])
    return sum(padded[j:j + u.shape[0]] * w[j] for j in range(K))


def _act(x, low: str | None):
    """Activations entering a weight product: rounded per token under a
    control."""
    return round_to(x, tuple(range(1, x.ndim)), low) if low else x


def _widen(w: dict, low: str | None) -> dict:
    """One layer's weights in float32, rounded under a control."""
    out = {}
    for k, v in w.items():
        v = v.astype(jnp.float32)
        out[k] = round_to(v, _CONTRACT[k], low) if low and k in _CONTRACT \
            else v
    return out


def _mamba(u, w, *, P, low):
    T = u.shape[0]
    u = _act(u, low)
    z = u @ w["w_z"]
    xs = jax.nn.silu(_conv(u @ w["w_x"], w["conv_x"]))
    B = jax.nn.silu(_conv(u @ w["w_B"], w["conv_B"]))
    C = jax.nn.silu(_conv(u @ w["w_C"], w["conv_C"]))
    dt = jax.nn.softplus(u @ w["w_dt"] + w["dt_bias"])         # (T, H)
    A = -jnp.exp(w["A_log"])                                    # (H,)
    H = A.shape[0]
    xh = xs.reshape(T, H, P)

    def step(h, inp):
        x_t, dt_t, B_t, C_t = inp
        h = (h * jnp.exp(dt_t * A)[:, None, None]
             + (dt_t[:, None] * x_t)[:, :, None] * B_t)
        return h, h @ C_t
    h0 = jnp.zeros((H, P, B.shape[1]), jnp.float32)
    _, y = jax.lax.scan(step, h0, (xh, dt, B, C))               # (T, H, P)
    y = y + w["D"][:, None] * xh
    return y, z


def _mamba_out(y, z, w, eps, low):
    T = y.shape[0]
    y = _rms(y.reshape(T, -1) * jax.nn.silu(z), w["norm"], eps)
    return _act(y, low) @ w["out_proj"]


def _attention(u, w, *, scale, low):
    T = u.shape[0]
    u = _act(u, low)
    q = jnp.einsum("tm,mhd->thd", u, w["wq"])
    k = jnp.einsum("tm,mhd->thd", u, w["wk"])
    v = jnp.einsum("tm,mhd->thd", u, w["wv"])
    G = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
    nq = -(-T // QUERY_BLOCK)
    qp = jnp.pad(q, ((0, nq * QUERY_BLOCK - T), (0, 0), (0, 0)))
    keys = jnp.arange(T)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(qp, i * QUERY_BLOCK, QUERY_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", qi, k) * scale
        pos = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        s = jnp.where(pos[:, None] >= keys[None, :], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
    o = jax.lax.map(block, jnp.arange(nq)).reshape(nq * QUERY_BLOCK,
                                                   *q.shape[1:])[:T]
    return jnp.einsum("thd,hdm->tm", _act(o, low), w["wo"])


def _swiglu(u, w_gate, w_up, w_down, low):
    h = jax.nn.silu(u @ w_gate) * (u @ w_up)
    return _act(h, low) @ w_down


def _moe(u, w, *, k, first, low):
    u = _act(u, low)
    logits = u @ w["router"]                                    # (T, E)
    top, idx = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(top, axis=-1)                        # (T, k)
    y = jnp.zeros_like(u)
    for e in range(w["w_up"].shape[0]):
        gate = jnp.sum(jnp.where(idx == first + e, gates, 0.0), -1)
        y = y + gate[:, None] * _swiglu(u, w["w_gate"][e], w["w_up"][e],
                                        w["w_down"][e], low)
    return y + _swiglu(u, w["shared_gate"], w["shared_up"],
                       w["shared_down"], low)


def logits_at(sizes: dict, seed: int, seqs, *, quant: str | None = None
              ) -> list[np.ndarray]:
    """Logits of each sequence at its read positions.

    ``seqs`` is a list of ``(tokens, first)``: int token ids (T,) and the
    first position whose logits are wanted; returns one float32 array
    ``(T - first, V)`` per sequence.  Each sequence goes through each
    layer in a call of its own, so a program is compiled once per length,
    whatever mix of lengths a sample holds."""
    g = layout(sizes)
    spec = weight_spec(sizes)
    eps = sizes["rms_norm_eps"]
    r = sizes["residual_multiplier"]
    scale = sizes["attention_multiplier"]
    k, first = sizes["num_experts_per_tok"], sizes["first_expert"]
    root = root_key(seed)
    block_paths = [p for p in spec if p.startswith("layers/")]
    top = drawer(spec, ["embed", "final_norm"])(root, jnp.int32(0))

    @jax.jit
    def draw_block(root, l):
        # bf16 (as served), so one block of layers fits beside the rest
        return {p.split("/", 1)[1]: _draw_traced(root, p, spec[p], l)
                for p in block_paths}

    @jax.jit
    def mamba_layer(xs, ln, w):
        w = _widen(w, quant)
        u = _rms(xs, ln.astype(jnp.float32), eps)
        y, z = jax.vmap(lambda u: _mamba(u, w, P=g["P"], low=quant))(u)
        return xs + r * jax.lax.map(
            lambda yz: _mamba_out(*yz, w, eps, quant), (y, z))

    @jax.jit
    def attn_layer(xs, ln, w):
        w = _widen(w, quant)
        return jax.lax.map(lambda x: x + r * _attention(
            _rms(x, ln.astype(jnp.float32), eps), w, scale=scale, low=quant),
            xs)

    @jax.jit
    def moe_layer(xs, ln, w):
        w = _widen(w, quant)
        return jax.lax.map(lambda x: x + r * _moe(
            _rms(x, ln.astype(jnp.float32), eps), w, k=k, first=first,
            low=quant), xs)

    @jax.jit
    def logits(x, fn, embed):
        return (_act(_rms(x, fn, eps), quant) @ embed.T
                / sizes["logits_scaling"])

    with jax.default_matmul_precision("highest"):
        embed = round_to(top["embed"], _CONTRACT["embed"], quant) if quant \
            else top["embed"]
        # one (1, T, M) residual stream a sequence
        xs = [sizes["embedding_multiplier"] * jnp.take(
            embed, jnp.asarray(tokens[None], jnp.int32), axis=0)
            for tokens, _ in seqs]
        for b in range(g["blocks"]):
            blk = draw_block(root, jnp.int32(b))
            mi = 0
            for i in range(g["period"]):
                if i == g["attn_index"]:
                    w = {p.split("/")[1]: v for p, v in blk.items()
                         if p.startswith("attn/")}
                    xs = [attn_layer(x, blk["ln_mix"][i], w) for x in xs]
                else:
                    w = {p.split("/")[1]: v[mi] for p, v in blk.items()
                         if p.startswith("mamba/")}
                    xs = [mamba_layer(x, blk["ln_mix"][i], w) for x in xs]
                    mi += 1
                w = {p.split("/")[1]: v[i] for p, v in blk.items()
                     if p.startswith("moe/")}
                xs = [moe_layer(x, blk["ln_ffn"][i], w) for x in xs]
                del w
            del blk
        return [np.asarray(logits(x[0, read:], top["final_norm"], embed))
                for x, (_, read) in zip(xs, seqs)]
