"""What the deployments share: named compiled steps, seeded prompts, the
greedy generation loop of a body, and the check that the program's
parameter layout is the one the reference draws.

A deployment module (``configs/<config>.py``) defines ``build(sizes,
traffic, requests, seed, reference)`` returning an object with

* ``setup()``: weights on the device, compiled steps, prompts staged;
* ``modules``: ``{module name: {"kind": "prefill"|"decode"|"other",
  "flops": model FLOPs of one call}}`` for every compiled step;
* ``workflow(wrap)``: the DFlow workflow, each body passed through
  ``wrap(name, fn)``;
* ``payload(i)``: the external inputs of request ``i``;
* ``warmup``: requests (indices past the window's) that warm every shape;
* ``served(i, outputs)``: ``[(tokens, first, served, top)]`` per
  generated sequence (``top``: the program's best logit at each served
  position), or None when the outputs are malformed;
* ``free()``: drop every array the program holds.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from weights import Leaf

__all__ = ["named_jit", "with_top_logit", "generate", "prompts",
           "check_layout"]


def named_jit(fn, name: str):
    """``jax.jit`` of a call to ``fn`` under a stable module name
    (``jit_<name>``), so that the device trace can tell the steps apart.
    The step itself is unchanged: the inner jitted program is inlined."""
    def step(*args):
        return fn(*args)
    step.__name__ = step.__qualname__ = name
    return jax.jit(step)


def with_top_logit(step):
    """A greedy step ``-> (logits, token, cache)`` that also returns its
    best logit at the last position, (B, 1), in the logits' own dtype: the
    logit the check compares with the reference's logit of the served
    token."""
    def run(*args):
        logits, tok, cache = step(*args)
        return logits, tok, cache, jnp.max(logits[:, -1], axis=-1,
                                           keepdims=True)
    return run


def generate(decode, params, tok, top, cache, n: int):
    """``n`` greedy tokens, the first of which is ``tok`` (best logit
    ``top``), and the best logit of each, as two (1, n) arrays."""
    toks, tops = [tok], [top]
    for _ in range(n - 1):
        _, tok, cache, top = decode(params, tok, cache)
        toks.append(tok)
        tops.append(top)
    return jnp.concatenate(toks, axis=1), jnp.concatenate(tops, axis=1)


def prompts(requests, seed: int, vocab: int) -> dict[int, np.ndarray]:
    """Seeded token ids of every request's prompt, uniform over the
    vocabulary, keyed by request index."""
    rng = np.random.default_rng([seed, 3])
    return {r.index: rng.integers(0, vocab, r.prompt_len, dtype=np.int32)
            for r in sorted(requests, key=lambda r: r.index)}


def check_layout(decls, spec: dict[str, Leaf]) -> None:
    """Raise unless the program declares exactly the leaves (paths and
    shapes) that the reference's weight spec draws."""
    from repro.models.param import ArrayDecl

    flat = {}

    def walk(node, prefix):
        if isinstance(node, ArrayDecl):
            flat[prefix] = (tuple(node.shape), jnp.dtype(node.dtype).name)
            return
        for k, v in node.items():
            walk(v, f"{prefix}/{k}" if prefix else k)
    walk(decls, "")
    want = {p: (((l.layers,) if l.layers else ()) + tuple(l.shape), l.dtype)
            for p, l in spec.items()}
    if flat != want:
        diff = sorted(set(flat.items()) ^ set(want.items()))
        raise ValueError(f"the program's parameters differ from the "
                         f"reference's weights: {diff}")
