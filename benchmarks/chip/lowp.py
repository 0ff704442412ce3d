"""Rounding to the precisions below bf16 that the controls compute in.

Symmetric, one scale over the axes ``axes`` reduce (per output channel for
a weight, per token for an activation): ``int8`` rounds to 255 levels,
``fp8`` to float8_e4m3fn with the largest magnitude at 448."""

from __future__ import annotations

import jax.numpy as jnp

__all__ = ["round_to"]

_TOP = {"int8": 127.0, "fp8": 448.0}


def round_to(x, axes: tuple[int, ...], kind: str):
    if kind not in _TOP:
        raise ValueError(f"unknown precision {kind!r}")
    scale = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / _TOP[kind]
    scale = jnp.where(scale == 0, 1.0, scale)
    if kind == "int8":
        return jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
