"""Mixture-of-Experts FFN with expert parallelism via ``shard_map``.

This is the DFlow-style data plane applied inside one layer (DESIGN.md §3):
experts are sharded over the ``model`` mesh axis; every model-rank receives
the (data-sharded, model-replicated) token block, routes it, and *locally*
dispatches only the tokens destined for its resident experts — a
receiver-driven exchange in which each expert shard pulls exactly its own
work, and the only collective is the final ``psum`` combine (the same
all-reduce shape dense tensor-parallel FFNs pay).

Dispatch is scatter-based (sort → rank-in-expert → scatter into an
``(E_local, C, M)`` buffer), never materializing the ``(tokens, E, C)``
one-hot of the classic GShard formulation — with 384-expert configs that
tensor would be ~100 GB.  Token overflow beyond the per-expert capacity
``C = ceil(T·k/E · capacity_factor)`` is dropped (standard GShard dropping
semantics); a load-balance auxiliary loss keeps the router honest.

Serving drops nothing (``dropless``): the (token, expert) pairs are sorted
by expert; where they outnumber the experts held (prefill) the experts run
as one grouped matmul over the sorted rows (``jax.lax.ragged_dot``), whose
groups are as long as the routing makes them, and else (decode) as a loop
over the held experts that have rows, so a step reads no other expert's
weights.

A device may hold a share of the experts (``cfg.experts_held`` from
``cfg.first_expert``, then split over the ``model`` axis): the router still
covers all ``n_experts`` and picks ``top_k`` of them, and the layer returns
only the part of the result its own experts give, plus the shared MLP.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import ACTIVATIONS, softmax_fp32
from .config import ModelConfig
from .param import ArrayDecl, normal_init
from ..sharding.context import current_mesh, data_axes, model_axis

__all__ = ["moe_decls", "moe"]


def moe_decls(cfg: ModelConfig, layers: int | None = None) -> dict:
    M, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    Eh = cfg.n_experts_held
    lead = (layers,) if layers else ()
    lax_ = ("layers",) if layers else ()
    decls = {
        "router": ArrayDecl(lead + (M, E), lax_ + ("embed", None),
                            init=normal_init(0.02), dtype=jnp.float32),
        "w_up": ArrayDecl(lead + (Eh, M, F),
                          lax_ + ("experts", "embed", "expert_mlp")),
        "w_down": ArrayDecl(lead + (Eh, F, M),
                            lax_ + ("experts", "expert_mlp", "embed")),
    }
    if cfg.glu:
        decls["w_gate"] = ArrayDecl(lead + (Eh, M, F),
                                    lax_ + ("experts", "embed", "expert_mlp"))
    if cfg.n_shared_experts:
        Fs = cfg.shared_width
        decls["shared_up"] = ArrayDecl(lead + (M, Fs), lax_ + ("embed", "mlp"))
        decls["shared_gate"] = ArrayDecl(lead + (M, Fs), lax_ + ("embed", "mlp"))
        decls["shared_down"] = ArrayDecl(lead + (Fs, M), lax_ + ("mlp", "embed"))
    return decls


_EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def _capacity(tokens: int, k: int, n_experts: int, factor: float) -> int:
    c = int(math.ceil(tokens * k / n_experts * factor))
    return max(c, 4)


def _sort_pairs(lid_f, E_loc: int):
    """Order of the (token, slot) pairs by local expert (stable: arrival
    order within an expert), the sorted experts, and where each expert's
    rows begin, (E_loc + 1,)."""
    order = jnp.argsort(lid_f, stable=True)
    sorted_lid = lid_f[order]
    starts = jnp.searchsorted(sorted_lid, jnp.arange(E_loc + 1))
    return order, sorted_lid, starts


def _experts_capacity(t, lid_f, gates, w_gate, w_up, w_down, act, *,
                      cfg: ModelConfig, k: int):
    """The held experts over an ``(E_loc, C, M)`` buffer of at most ``C``
    tokens each (``C = ceil(T·k/E · capacity_factor)``); the overflow is
    dropped.  Same arguments as :func:`_experts_dropless`; returns the
    gated sum (T, M) in fp32."""
    T, M = t.shape
    E_loc = w_up.shape[0]
    # Capacity per (local) expert: expected load is T·k/E tokens from this
    # data shard's block, padded by the capacity factor.
    C = _capacity(T, k, cfg.n_experts, cfg.capacity_factor)
    order, sorted_lid, starts = _sort_pairs(lid_f, E_loc)
    # rank within expert (stable sort → arrival-order priority on overflow)
    pos_sorted = jnp.arange(T * k) - starts[sorted_lid]

    if cfg.moe_dispatch == "gather":
        # Index-inverted data plane: build slot→(token,k) once (O(T·k) int
        # scatter, no M factor), then dispatch = one (E_loc·C, M) gather
        # and combine = one (E_loc·C, M) scatter-add.  The (T·k, M)
        # dispatch/combine tensors of the baseline never materialize.
        Cp1 = C + 1
        slot_sorted = jnp.minimum(pos_sorted, C)
        flat_sorted = sorted_lid * Cp1 + slot_sorted       # (T*k,) in
        n_flat = (E_loc + 1) * Cp1                         # incl. drop rows
        tok_k_for_flat = jnp.zeros((n_flat,), jnp.int32).at[
            flat_sorted].set(order.astype(jnp.int32))
        valid_flat = jnp.zeros((n_flat,), jnp.bool_).at[flat_sorted].set(
            (pos_sorted < C) & (sorted_lid < E_loc))
        grid = tok_k_for_flat.reshape(E_loc + 1, Cp1)[:E_loc, :C]
        vgrid = valid_flat.reshape(E_loc + 1, Cp1)[:E_loc, :C]
        tok_grid = grid // k                               # (E_loc, C)
        buf = jnp.where(vgrid[..., None], t[tok_grid], 0)  # (E_loc, C, M)
    else:
        pos = jnp.zeros_like(pos_sorted).at[order].set(pos_sorted)
        keep = (lid_f < E_loc) & (pos < C)
        slot = jnp.where(keep, pos, C)                     # C = trash slot
        eid = jnp.where(keep, lid_f, 0)
        tok = jnp.repeat(jnp.arange(T), k)
        x_rep = jnp.where(keep[:, None], t[tok], 0).astype(t.dtype)
        buf = jnp.zeros((E_loc, C + 1, M), t.dtype)
        buf = buf.at[eid, slot].add(x_rep)
        buf = buf[:, :C]                                   # (E_loc, C, M)

    up = jnp.einsum("ecm,emf->ecf", buf, w_up)
    if w_gate is not None:
        g = jnp.einsum("ecm,emf->ecf", buf, w_gate)
        h = act(g) * up
    else:
        h = act(up)
    out_buf = jnp.einsum("ecf,efm->ecm", h, w_down)        # (E_loc, C, M)

    if cfg.moe_dispatch == "gather":
        gate_grid = jnp.where(vgrid, gates.reshape(-1)[grid], 0.0)
        contrib = (out_buf.astype(jnp.float32)
                   * gate_grid[..., None].astype(jnp.float32))
        return jnp.zeros((T, M), jnp.float32).at[tok_grid.reshape(-1)].add(
            contrib.reshape(-1, M))
    out_buf = jnp.concatenate(
        [out_buf, jnp.zeros((E_loc, 1, M), out_buf.dtype)], axis=1)
    y_tk = out_buf[eid, slot] * keep[:, None]              # (T*k, M)
    w = (gates.reshape(-1) * keep).astype(jnp.float32)
    return (y_tk.astype(jnp.float32) * w[:, None]).reshape(T, k, M).sum(1)


def _in_place(w_gate, w_up, w_down, *, layer, held: int):
    """The expert leaves as one axis of groups and the first group of this
    layer's experts: leaves stacked over layers, (L, held, ...), are read
    in place as (L·held, ...), never as a copy of the layer's slice."""
    if layer is None:
        return (w_gate, w_up, w_down), 0
    return tuple(None if w is None else w.reshape((-1,) + w.shape[2:])
                 for w in (w_gate, w_up, w_down)), layer * held


def _experts_ragged(t, order, sorted_lid, starts, w_gate, w_up, w_down, act,
                    *, k: int, layer=None):
    """The pairs, sorted by expert, through their experts as one grouped
    matmul a projection (``jax.lax.ragged_dot``) over every group of the
    leaves, empty for the other layers' experts.  Serves prefill, where
    the pairs outnumber the experts held.  Returns (T·k, M) in sorted
    order, 0 for a pair held elsewhere."""
    E_loc = starts.shape[0] - 1
    sizes = (starts[1:] - starts[:-1]).astype(jnp.int32)   # (E_loc,)
    (w_gate, w_up, w_down), base = _in_place(w_gate, w_up, w_down,
                                             layer=layer, held=E_loc)
    if layer is not None:
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((w_up.shape[0],), jnp.int32), sizes, (base,))
    rows = t[order // k]                                   # (T*k, M)
    up = jax.lax.ragged_dot(rows, w_up, sizes)
    if w_gate is not None:
        h = act(jax.lax.ragged_dot(rows, w_gate, sizes)) * up
    else:
        h = act(up)
    out = jax.lax.ragged_dot(h, w_down, sizes)             # (T*k, M)
    return jnp.where((sorted_lid < E_loc)[:, None], out, 0)


def _experts_hit(t, order, sorted_lid, starts, w_gate, w_up, w_down, act,
                 *, k: int, layer=None):
    """The same, one held expert that has rows at a time: a loop of as
    many trips as such experts (0 to ``min(T·k, E_loc)``), each reading
    its expert's three matrices by a dynamic index into the leaves and
    applying them to every row, of which it keeps its expert's.  Serves
    decode, where the experts held outnumber the pairs: the weight traffic
    is the hit experts' alone, where a grouped matmul steps through every
    group of the stack."""
    E_loc = starts.shape[0] - 1
    (w_gate, w_up, w_down), base = _in_place(w_gate, w_up, w_down,
                                             layer=layer, held=E_loc)
    rows = t[order // k]                                   # (T*k, M)
    first = jnp.concatenate([jnp.ones((1,), jnp.bool_),
                             sorted_lid[1:] != sorted_lid[:-1]])
    first = first & (sorted_lid < E_loc)
    hit = sorted_lid[jnp.nonzero(first, size=rows.shape[0])[0]]

    def dot(x, leaf, e):
        w = jax.lax.dynamic_index_in_dim(leaf, base + e, keepdims=False)
        return jnp.dot(x, w, preferred_element_type=jnp.float32
                       ).astype(rows.dtype)

    def trip(i, out):
        e = hit[i]
        up = dot(rows, w_up, e)
        if w_gate is not None:
            h = act(dot(rows, w_gate, e)) * up
        else:
            h = act(up)
        return jnp.where((sorted_lid == e)[:, None], dot(h, w_down, e), out)

    with jax.named_scope("routed_experts_hit"):
        return jax.lax.fori_loop(0, first.sum(), trip, jnp.zeros_like(rows))


def _experts_dropless(t, lid_f, gates, w_gate, w_up, w_down, act, *,
                      k: int, layer=None):
    """Every (token, held expert) pair through its expert: nothing is
    dropped.  t: (T, M); ``lid_f`` (T·k,) the local expert of each (token,
    slot) pair, E_loc for one held elsewhere; ``gates`` (T, k).  With
    ``layer``, the expert weights are stacked over layers, (L, E_loc, ...),
    and are read in place.

    The pairs are sorted by expert, then go one of two ways, chosen by
    shape: where they outnumber the experts held (prefill),
    :func:`_experts_ragged`, a grouped matmul a projection over the whole
    stack (the other layers' experts as empty groups); else (decode,
    ``T·k <= E_loc``) :func:`_experts_hit`, a loop over the held experts
    that have rows.  Returns the gated sum (T, M) in fp32."""
    T, M = t.shape
    E_loc = w_up.shape[-3]
    order, sorted_lid, starts = _sort_pairs(lid_f, E_loc)
    experts = _experts_hit if T * k <= E_loc else _experts_ragged
    out = experts(t, order, sorted_lid, starts, w_gate, w_up, w_down, act,
                  k=k, layer=layer)                        # (T*k, M)
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(T * k))
    inv = inv.reshape(T, k)
    gates = gates.astype(jnp.float32)
    y = jnp.zeros((T, M), jnp.float32)
    for j in range(k):
        # one (T, M) gather a slot, fused into the gated add: no
        # (T, k, M) fp32 tensor
        y = y + out[inv[:, j]].astype(jnp.float32) * gates[:, j, None]
    return y


def _moe_local(x, topi, gates, w_gate, w_up, w_down, shared, *,
               cfg: ModelConfig, n_model: int, has_model_axis: bool,
               d_axes: tuple[str, ...] = (), dropless: bool = False,
               layer=None):
    """Per-device block: x (B_loc, S, M); experts (E_loc, ...).

    Routing (``topi``/``gates``, (B_loc, S, k)) is computed *outside* the
    shard_map in global pjit land — computing it per-rank would make every
    routing intermediate a replicated value whose cotangent needs a psum
    over the model axis (measured: ~2 extra activation-sized all-reduces
    per layer, §Perf kimi iteration 2)."""
    B, S, M = x.shape
    k = cfg.top_k
    E_loc = cfg.n_experts_held // n_model
    act = ACTIVATIONS[cfg.activation]
    t = x.reshape(B * S, M)
    T = B * S
    topi = topi.reshape(T, k)
    gates = gates.reshape(T, k)

    with jax.named_scope("routed_experts"):
        rank = jax.lax.axis_index("model") if has_model_axis else 0
        e_base = cfg.first_expert + rank * E_loc
        local = topi - e_base                                  # (T, k)
        sel = (local >= 0) & (local < E_loc)
        lid = jnp.where(sel, local, E_loc)                     # E_loc = drop
        lid_f = lid.reshape(-1)                                # (T*k,)
        if dropless:
            y = _experts_dropless(t, lid_f, gates, w_gate, w_up, w_down, act,
                                  k=k, layer=layer)
        else:
            if layer is not None:
                w_gate, w_up, w_down = (None if w is None else w[layer]
                                        for w in (w_gate, w_up, w_down))
            y = _experts_capacity(t, lid_f, gates, w_gate, w_up, w_down, act,
                                  cfg=cfg, k=k)

    if shared is not None:
        with jax.named_scope("shared_mlp"):
            s_gate, s_up, s_down = shared
            g = t @ s_gate
            u = t @ s_up
            y = y + ((act(g) * u) @ s_down).astype(jnp.float32)

    if has_model_axis:
        y = jax.lax.psum(y, "model")
    return y.reshape(B, S, M).astype(x.dtype)


def moe(params: dict, x: jax.Array, cfg: ModelConfig, *,
        dropless: bool = False, layer=None):
    """MoE sublayer.  x: (B, S, M) → (y, aux_loss).  ``dropless`` (serving)
    runs every routed token through its expert, whatever the load, by one
    of two paths chosen by shape (:func:`_experts_dropless`): a grouped
    matmul over every held expert where the (token, slot) pairs outnumber
    the experts held (prefill), a loop over the held experts that have
    rows where they do not (decode).  With ``layer``, every leaf of
    ``params`` carries a leading axis of layers and this is the layer's
    index into it: both paths then read the routed experts in place, not
    from a copy of the layer's slice; the grouped matmul, over every layer's
    groups, serves prefill alone."""
    if layer is not None:
        params = {n: v if n in _EXPERT_LEAVES else v[layer]
                  for n, v in params.items()}
    mesh = current_mesh()
    m_axis = model_axis(mesh)
    d_axes = data_axes(mesh)
    n_model = mesh.shape[m_axis] if m_axis else 1
    has_model = m_axis is not None
    E, k = cfg.n_experts, cfg.top_k

    # -- routing in global pjit land (replicated math stays out of the
    # manual region; see _moe_local docstring) --------------------------
    with jax.named_scope("router"):
        logits = jnp.einsum("bsm,me->bse", x.astype(jnp.float32),
                            params["router"])
        probs = jax.nn.softmax(logits, axis=-1)
        topv, topi = jax.lax.top_k(probs, k)            # (B, S, k)
        gates = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
        # GShard load-balance aux: importance × top-1 load over tokens.
        me = probs.reshape(-1, E).mean(axis=0)
        ce = jax.nn.one_hot(topi[..., 0].reshape(-1), E,
                            dtype=jnp.float32).mean(axis=0)
        aux = E * jnp.sum(me * ce)

    shared = None
    if cfg.n_shared_experts:
        shared = (params["shared_gate"], params["shared_up"],
                  params["shared_down"])

    fn = partial(_moe_local, cfg=cfg, n_model=n_model,
                 has_model_axis=has_model, d_axes=d_axes, dropless=dropless,
                 layer=layer)

    nd = 1
    for a in d_axes:
        nd *= mesh.shape[a]
    if d_axes and x.shape[0] % nd == 0:
        bspec = tuple(d_axes) if len(d_axes) > 1 else d_axes[0]
    else:
        bspec = None        # tiny decode batches: replicate tokens
    dspec = P(bspec, None, None)                        # (B, S, M)
    kspec = P(bspec, None, None)                        # (B, S, k)
    espec3 = P(m_axis, None, None) if layer is None \
        else P(None, m_axis, None, None)                # ([L,] E, M, F)
    sspec = P(None, m_axis)                             # shared up/gate (M,Fs)
    sdspec = P(m_axis, None)                            # shared down (Fs,M)

    w_gate = params.get("w_gate")
    args = [x, topi, gates, params["w_up"], params["w_down"]]
    in_specs = [dspec, kspec, kspec, espec3, espec3]
    if w_gate is not None:
        args.append(w_gate)
        in_specs.append(espec3)
    if shared is not None:
        args.extend(shared)                 # gate, up, down
        in_specs.extend([sspec, sspec, sdspec])

    def wrapped(x_, ti_, g_, wu_, wd_, *rest):
        rest = list(rest)
        wg_ = rest.pop(0) if w_gate is not None else None
        sh_ = tuple(rest) if shared is not None else None  # (gate, up, down)
        return fn(x_, ti_, g_, wg_, wu_, wd_, sh_)

    y = jax.shard_map(
        wrapped, mesh=mesh,
        in_specs=tuple(in_specs), out_specs=dspec,
        check_vma=False,
    )(*args)
    return y, aux
