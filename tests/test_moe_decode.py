"""The dropless MoE's two paths for the routed experts agree: the loop over
the held experts that have rows (decode, ``T·k <= E_loc``) and the grouped
matmul over every group of the stacked leaves (prefill), forced in turn on
the same inputs."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models.moe as moe
from repro.models.common import ACTIVATIONS

L, HELD, M, F = 3, 6, 64, 32
DROP = HELD                      # the local id of a pair held elsewhere

# (held-expert ids of each token's k slots, layer, with w_gate)
CASES = {
    "no_held_expert_picked": ([[DROP, DROP], [DROP, DROP]], 1, True),
    "all_slots_held": ([[0, 3], [3, 5]], 1, True),
    "first_layer": ([[1, DROP], [4, 1]], 0, True),
    "last_layer": ([[1, DROP], [4, 1]], L - 1, True),
    "without_gate": ([[2, DROP], [DROP, 2]], 1, False),
    "unstacked": ([[5, 0], [DROP, 5]], None, True),
    "pairs_equal_held": ([[0, 1], [2, 3], [4, 5]], L - 1, True),
}


def _inputs(lid, layer, glu, seed=0):
    keys = jax.random.split(jax.random.key(seed), 5)
    lid = np.asarray(lid, np.int32)
    T, k = lid.shape
    lead = (L, HELD) if layer is not None else (HELD,)

    def w(key, shape, fan_in):
        return (jax.random.normal(key, lead + shape, jnp.float32)
                * fan_in ** -0.5).astype(jnp.bfloat16)
    t = jax.random.normal(keys[0], (T, M), jnp.float32).astype(jnp.bfloat16)
    gates = jax.nn.softmax(jax.random.normal(keys[1], (T, k)), axis=-1)
    return (t, jnp.asarray(lid.reshape(-1)), gates,
            w(keys[2], (M, F), M) if glu else None, w(keys[3], (M, F), M),
            w(keys[4], (F, M), F)), k


def _dropless(args, k, layer):
    def run(*a):
        return moe._experts_dropless(*a, ACTIVATIONS["silu"], k=k,
                                     layer=layer)
    return np.asarray(jax.jit(run)(*args)), str(jax.make_jaxpr(run)(*args))


@pytest.mark.parametrize("case", sorted(CASES))
def test_decode_path_matches_ragged_dot(monkeypatch, case):
    lid, layer, glu = CASES[case]
    args, k = _inputs(lid, layer, glu)
    hit, hit_jaxpr = _dropless(args, k, layer)
    # the shape picks the loop: T·k <= E_loc
    assert "ragged_dot" not in hit_jaxpr and "while" in hit_jaxpr
    monkeypatch.setattr(moe, "_experts_hit", moe._experts_ragged)
    ragged, ragged_jaxpr = _dropless(args, k, layer)
    assert "ragged_dot" in ragged_jaxpr
    if case == "no_held_expert_picked":
        assert not hit.any() and not ragged.any()
        return
    scale = np.abs(ragged).max()
    assert scale > 0.1
    # bf16 operands and outputs, fp32 accumulation in both: the same dots
    # summed in another order, within a bf16 step of the largest output
    assert np.abs(hit - ragged).max() <= 2 ** -8 * scale
