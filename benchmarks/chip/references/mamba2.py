"""Plain reference of a Mamba-2 language model, as the benchmark runs it.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``highest`` precision.  It imports nothing of the program: its weights are
drawn again from the seed by :mod:`weights`, one layer at a time.

Architecture (arXiv:2405.21060; ``state-spaces/mamba2-370m``), one group
(``ngroups`` 1), with the departures the configuration file lists: no
convolution bias, the residual stream kept in the served type.  Per layer,
with ``u = n1(x)``::

    z, xs, B, C = u Wz, u Wx, u WB, u WC
    dt = softplus(u Wdt + dt_bias);  A = -exp(A_log)        (one per head)
    xs, B, C = silu(causal_conv(xs)), silu(causal_conv(B)), silu(...(C))
    y_t = sum_{s<=t} exp(A sum_{s<r<=t} dt_r) (C_t . B_s) dt_s xs_s + D xs_t
    x += Wout n_g(y * silu(z))

The state-space sum is written out in its quadratic (masked) form over the
whole sequence: the definition, not the program's chunked scan or its
recurrence.  Logits: ``n_f(x) @ embed^T`` (tied).

``quant="int8"`` or ``"fp8"`` is a control, computed in a precision below
the bfloat16 the configuration states: every weight matrix product takes
operands rounded to it (``lowp.py``), the weights with one scale per output
channel and the activations with one scale per token (W8A8); the
convolution and the state-space sum stay in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from lowp import round_to
from weights import Leaf, drawer, root_key

__all__ = ["weight_spec", "logits_at", "padded_vocab"]


def padded_vocab(sizes: dict) -> int:
    m = sizes["pad_vocab_size_multiple"]
    return -(-sizes["vocab_size"] // m) * m


def weight_spec(sizes: dict) -> dict[str, Leaf]:
    M, L = sizes["d_model"], sizes["n_layer"]
    N, K = sizes["d_state"], sizes["d_conv"]
    DI = sizes["expand"] * M
    H = DI // sizes["headdim"]
    V = padded_vocab(sizes)
    fan_in = ("normal", M ** -0.5)
    return {
        "embed": Leaf((V, M), ("normal", sizes["initializer_range"])),
        "final_norm": Leaf((M,), ("ones",)),
        "layers/ln1": Leaf((M,), ("ones",), layers=L),
        "layers/mamba/w_z": Leaf((M, DI), fan_in, layers=L),
        "layers/mamba/w_x": Leaf((M, DI), fan_in, layers=L),
        "layers/mamba/w_B": Leaf((M, N), fan_in, layers=L),
        "layers/mamba/w_C": Leaf((M, N), fan_in, layers=L),
        "layers/mamba/w_dt": Leaf((M, H), fan_in, layers=L),
        "layers/mamba/dt_bias": Leaf((H,), ("dt_bias", sizes["dt_min"],
                                            sizes["dt_max"]),
                                     dtype="float32", layers=L),
        "layers/mamba/A_log": Leaf((H,), ("log_uniform",
                                          *sizes["A_init_range"]),
                                   dtype="float32", layers=L),
        "layers/mamba/D": Leaf((H,), ("ones",), dtype="float32", layers=L),
        # depthwise conv: the variance of PyTorch's U(+-1/sqrt(K)) default
        "layers/mamba/conv_x": Leaf((K, DI), ("normal", (3 * K) ** -0.5),
                                    layers=L),
        "layers/mamba/conv_B": Leaf((K, N), ("normal", (3 * K) ** -0.5),
                                    layers=L),
        "layers/mamba/conv_C": Leaf((K, N), ("normal", (3 * K) ** -0.5),
                                    layers=L),
        "layers/mamba/norm": Leaf((DI,), ("ones",), layers=L),
        # out_proj rescaled by 1/sqrt(n_layer), as the published init does
        "layers/mamba/out_proj": Leaf((DI, M),
                                      ("normal", (DI * L) ** -0.5),
                                      layers=L),
    }


_MATRICES = ("w_z", "w_x", "w_B", "w_C", "w_dt", "out_proj")


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
    return round_to(x, (1,), low) if low else x


def _layer(x, w, *, eps, headdim, low=None):
    T = x.shape[0]
    u = _act(_rms(x, w["ln1"], eps), low)
    z = u @ w["w_z"]
    xs = jax.nn.silu(_conv(u @ w["w_x"], w["conv_x"]))
    B = jax.nn.silu(_conv(u @ w["w_B"], w["conv_B"]))
    C = jax.nn.silu(_conv(u @ w["w_C"], w["conv_C"]))
    dt = jax.nn.softplus(u @ w["w_dt"] + w["dt_bias"])        # (T, H)
    A = -jnp.exp(w["A_log"])                                    # (H,)
    H = A.shape[0]
    xh = xs.reshape(T, H, headdim)
    cum = jnp.cumsum(dt * A, axis=0)                            # (T, H)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    decay = jnp.where(causal[:, :, None],
                      jnp.exp(jnp.where(causal[:, :, None],
                                        cum[:, None, :] - cum[None, :, :],
                                        0.0)), 0.0)             # (t, s, H)
    scores = (C @ B.T)[:, :, None] * decay * dt[None, :, :]     # (t, s, H)
    y = jnp.einsum("tsh,shp->thp", scores, xh) + w["D"][:, None] * xh
    y = _rms(y.reshape(T, -1) * jax.nn.silu(z), w["norm"], eps)
    return x + _act(y, low) @ w["out_proj"]


def logits_at(sizes: dict, seed: int, seqs, *, quant: str | None = None
              ) -> list[np.ndarray]:
    """Logits of each sequence at its read positions.

    ``seqs`` is a list of ``(tokens, first)``: int token ids (T,) and the
    first position whose logits are wanted; returns one float32 array
    ``(T - first, V)`` per sequence.  Sequences of one length go through
    each layer in one call, one after another inside it."""
    spec = weight_spec(sizes)
    eps, headdim = sizes["norm_epsilon"], sizes["headdim"]
    root = root_key(seed)
    layer_paths = [p for p in spec if p.startswith("layers/")]
    draw_layer = drawer(spec, layer_paths)
    top = drawer(spec, ["embed", "final_norm"])(root, jnp.int32(0))

    def weights_q(w):
        return {k: round_to(v, (0,), quant) if quant and k in _MATRICES
                else v for k, v in w.items()}

    @jax.jit
    def layer(xs, w):
        w = weights_q({p.rsplit("/", 1)[-1]: v for p, v in w.items()})
        return jax.lax.map(lambda x: _layer(x, w, eps=eps, headdim=headdim,
                                            low=quant), xs)

    @jax.jit
    def logits(x, fn, embed):
        return _act(_rms(x, fn, eps), quant) @ embed.T

    groups: dict[int, list[int]] = {}
    for j, (tokens, _) in enumerate(seqs):
        groups.setdefault(len(tokens), []).append(j)
    out: list = [None] * len(seqs)
    with jax.default_matmul_precision("highest"):
        embed = round_to(top["embed"], (1,), quant) if quant \
            else top["embed"]
        xs = {n: jnp.take(embed, jnp.asarray(np.stack(
            [seqs[j][0] for j in idx]), jnp.int32), axis=0)
            for n, idx in groups.items()}
        for l in range(sizes["n_layer"]):
            w = draw_layer(root, jnp.int32(l))
            xs = {n: layer(x, w) for n, x in xs.items()}
            del w
        for n, idx in groups.items():
            for k, j in enumerate(idx):
                first = seqs[j][1]
                out[j] = np.asarray(logits(xs[n][k, first:],
                                           top["final_norm"], embed))
    return out
