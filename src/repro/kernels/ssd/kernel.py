"""Mamba-2 SSD chunked-scan Pallas TPU kernel.

Grid: (B, H, n_chunks) with the chunk axis innermost/sequential; the
recurrent state (P, N) is carried in VMEM scratch across chunk steps — the
TPU-native shape of the SSD "state passing" from the paper (arXiv:2405.21060
§6): intra-chunk work is the dual quadratic form (three MXU matmuls of
shapes (Q,N)@(N,Q), (Q,Q)@(Q,P), (Q,N)@(N,P)), inter-chunk work is a rank-Q
state update.

Per-step VMEM: x (Q,P) + B/C (Q,N) + L (Q,Q) + state (P,N) fp32 — for
Q=128, P=64, N=128: ~250 KB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_kernel", "ssd_pallas"]


def ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, state_ref, *,
               chunk: int, num_chunks: int):
    ic = pl.program_id(2)
    h = pl.program_id(1)
    Q = chunk
    P = x_ref.shape[1]
    N = b_ref.shape[1]

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    A = a_ref[h]                                     # scalar (negative)
    x = x_ref[...].astype(jnp.float32)               # (Q, P)
    dt_row = dt_ref[...].astype(jnp.float32)         # (1, Q)
    Bm = b_ref[...].astype(jnp.float32)              # (Q, N)
    Cm = c_ref[...].astype(jnp.float32)              # (Q, N)

    # Prefix sums and row->column moves as masked (Q, Q) reductions: the
    # TPU lowering has no cumsum and no (1, Q) <-> (Q, 1) reshape.
    ri = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    causal = ri >= ci
    diag = ri == ci
    logd_row = dt_row * A                            # (1, Q)
    cum = jnp.sum(jnp.where(causal, logd_row, 0.0), axis=1,
                  keepdims=True)                     # (Q, 1) inclusive
    cum_row = jnp.sum(jnp.where(diag, cum, 0.0), axis=0,
                      keepdims=True)                 # (1, Q)
    total = jnp.sum(logd_row, axis=1, keepdims=True)  # (1, 1) = cum[Q-1]
    dt = jnp.sum(jnp.where(diag, dt_row, 0.0), axis=1,
                 keepdims=True)                      # (Q, 1)
    xdt = x * dt                                     # (Q, P)

    # intra-chunk: ((C @ B^T) ∘ L) @ xdt   with L = exp(segsum) lower-tri
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (Q,Q)
    L = jnp.where(causal, jnp.exp(cum - cum_row), 0.0)  # log decay j -> i
    y = jax.lax.dot_general(scores * L, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)       # (Q,P)

    # inter-chunk: contribution of the carried state
    state = state_ref[...]                           # (P, N)
    y_inter = jax.lax.dot_general(Cm, state, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y = y + y_inter * jnp.exp(cum)

    # state update: state' = state·exp(sum logd) + (decay_out·xdt)^T @ B
    decay_out = jnp.exp(total - cum)                 # (Q, 1)
    upd = jax.lax.dot_general(xdt * decay_out, Bm,
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)     # (P,N)
    state_ref[...] = state * jnp.exp(total) + upd
    y_ref[...] = y.astype(y_ref.dtype)


def ssd_pallas(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
               Cm: jax.Array, *, chunk: int = 128,
               interpret: bool = False):
    """x: (B, S, H, P); dt: (B, S, H); A: (H,); Bm, Cm: (B, S, N).

    Returns y: (B, S, H, P).  (Final state retrieval is the jnp path's job —
    the kernel targets the training/prefill hot loop.)
    """
    Bb, S, H, P = x.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    if S % chunk:
        raise ValueError(f"S {S} % chunk {chunk}")
    nc = S // chunk

    # kernel-major layouts
    xk = x.transpose(0, 2, 1, 3)                     # (B, H, S, P)
    dtk = dt.transpose(0, 2, 1)[:, :, None, :]       # (B, H, 1, S)

    kernel = functools.partial(ssd_kernel, chunk=chunk, num_chunks=nc)
    y = pl.pallas_call(
        kernel,
        grid=(Bb, H, nc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                 # A (H,)
            pl.BlockSpec((None, None, chunk, P),
                         lambda b, h, ic: (b, h, ic, 0)),          # x
            pl.BlockSpec((None, None, 1, chunk),
                         lambda b, h, ic: (b, h, 0, ic)),          # dt
            pl.BlockSpec((None, chunk, N), lambda b, h, ic: (b, ic, 0)),
            pl.BlockSpec((None, chunk, N), lambda b, h, ic: (b, ic, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, chunk, P),
                               lambda b, h, ic: (b, h, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((Bb, H, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(jnp.asarray(A, jnp.float32), xk, dtk, Bm, Cm)
    return y.transpose(0, 2, 1, 3)                   # (B, S, H, P)
