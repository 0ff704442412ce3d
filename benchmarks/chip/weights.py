"""Seeded weights, drawn the same way for the program and the reference.

A weight is named by its path (``"layers/attn/wq"``) and, for a stacked
per-layer leaf, by its layer.  Its values depend only on the seed, the
path, the layer and its per-layer shape, so the plain reference can draw
layer ``l`` again on its own, in float32, without taking anything the
program made.  ``make_params`` draws every leaf on the device in one
jitted call, in the type the program serves it in, one layer at a time
(``lax.map``), so no whole stacked leaf exists in float32.

A spec maps each path to a :class:`Leaf`; the init is one of

* ``("normal", std)``           -- N(0, std^2)
* ``("ones",)``                 -- 1
* ``("log_uniform", lo, hi)``   -- log of U(lo, hi)   (Mamba-2 ``A_log``)
* ``("dt_bias", lo, hi)``       -- softplus^-1 of a log-uniform dt in
  [lo, hi]  (Mamba-2 ``dt_bias``)
"""

from __future__ import annotations

import math
import zlib
from dataclasses import dataclass

import jax
import jax.numpy as jnp

__all__ = ["Leaf", "root_key", "drawer", "params_program", "make_params",
           "nest"]


@dataclass(frozen=True)
class Leaf:
    shape: tuple[int, ...]        # per-layer shape
    init: tuple
    dtype: str = "bfloat16"       # the type the program serves it in
    layers: int | None = None     # stacked over this many layers


def root_key(seed: int) -> jax.Array:
    """A key from all the bits of ``seed`` (``jax.random.key`` keeps only
    the low 32)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _leaf_key(root: jax.Array, path: str) -> jax.Array:
    return jax.random.fold_in(root, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def _sample(key: jax.Array, shape, init: tuple) -> jax.Array:
    kind = init[0]
    if kind == "normal":
        return jax.random.normal(key, shape, jnp.float32) * init[1]
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "log_uniform":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                          init[1], init[2]))
    if kind == "dt_bias":
        u = jax.random.uniform(key, shape, jnp.float32)
        lo, hi = math.log(init[1]), math.log(init[2])
        dt = jnp.exp(u * (hi - lo) + lo)
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(f"unknown init {init!r}")


def _draw_traced(root, path: str, leaf: Leaf, layer) -> jax.Array:
    key = _leaf_key(root, path)
    if layer is not None:
        key = jax.random.fold_in(key, layer)
    return _sample(key, leaf.shape, leaf.init).astype(leaf.dtype)


def drawer(spec: dict[str, Leaf], paths):
    """A jitted ``(root key, layer) -> {path: float32 array}`` drawing layer
    ``layer`` of every stacked leaf in ``paths`` (or the whole leaf, for one
    not stacked), holding the values the program is served (rounded to each
    leaf's type).  One program for all the layers."""
    paths = list(paths)

    def draw_all(root, layer):
        return {p: _draw_traced(root, p, spec[p],
                                layer if spec[p].layers is not None
                                else None).astype(jnp.float32)
                for p in paths}
    return jax.jit(draw_all)


def params_program(spec: dict[str, Leaf]):
    """The jitted program ``root key -> {path: leaf}`` of ``make_params``."""
    def build(root):
        flat = {}
        for path, leaf in spec.items():
            if leaf.layers is None:
                flat[path] = _draw_traced(root, path, leaf, None)
            else:
                flat[path] = jax.lax.map(
                    lambda l, path=path, leaf=leaf:
                    _draw_traced(root, path, leaf, l),
                    jnp.arange(leaf.layers, dtype=jnp.int32))
        return flat
    return jax.jit(build)


def make_params(seed: int, spec: dict[str, Leaf]) -> dict:
    """Every leaf of ``spec``, on the default device, as a nested dict keyed
    by the path's parts; stacked leaves carry the layer axis first."""
    return nest(params_program(spec)(root_key(seed)))


def nest(flat: dict) -> dict:
    """``{"a/b": x}`` -> ``{"a": {"b": x}}``."""
    out: dict = {}
    for path, value in flat.items():
        node = out
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = value
    return out
