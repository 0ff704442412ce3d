"""Serving driver: ``python -m repro.launch.serve --arch <id> ...``

Batched greedy prefill + decode over synthetic requests with seeded random
parameters: reduced configs by default (CPU), the published widths with
``--full`` (one accelerator).  Both steps are compiled before the timed
window; compile time is reported on its own line, and every timing ends in
``block_until_ready``.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, list_archs
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models import build_model, init_params
from repro.sharding.context import mesh_context

__all__ = ["main", "serve_loop", "greedy_steps"]


def greedy_steps(model, mesh, *, donate: bool = True):
    """Jitted ``(prefill, decode)`` steps that also pick the next token.

    Both return ``(logits, token (B, 1) int32, cache)``.  The mesh is bound
    inside the traced function, so a step traced on any thread sees it.
    ``donate=False`` keeps the input cache alive (a caller that still
    holds it, such as a DStore, must not have it deleted underneath)."""
    def greedy(step):
        def run(*args):
            with mesh_context(mesh):
                logits, cache = step(*args)
            tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
            return logits, tok.astype(jnp.int32), cache
        return run

    cache_arg = 3 if model.cfg.family == "encdec" else 2
    prefill = jax.jit(greedy(model.prefill),
                      donate_argnums=(cache_arg,) if donate else ())
    decode = jax.jit(greedy(model.decode_step),
                     donate_argnums=(2,) if donate else ())
    return prefill, decode


def serve_loop(arch: str, *, reduced: bool = True, params=None,
               batch: int = 4, prompt_len: int = 32, gen_tokens: int = 16,
               seed: int = 0) -> dict:
    """Greedy-serve ``batch`` seeded random prompts; ``params`` defaults to
    the model's seeded random initialisation."""
    cfg = get_config(arch, reduced=reduced)
    max_len = prompt_len + gen_tokens
    cfg = dataclasses.replace(cfg, q_chunk=max(prompt_len // 2, 16),
                              kv_chunk=max(prompt_len // 2, 16),
                              max_cache_len=max_len)
    mesh = make_local_mesh()
    model = build_model(cfg)
    with mesh_context(mesh):
        if params is None:
            params = init_params(model.param_decls(), jax.random.key(seed))
        rng = np.random.default_rng(seed)
        prompts = jnp.asarray(rng.integers(
            0, cfg.vocab, size=(batch, prompt_len)), jnp.int32)
        if cfg.family == "encdec":
            frames = jnp.asarray(
                rng.normal(size=(batch, 16, cfg.d_model)), jnp.bfloat16)
            cache = model.init_cache(batch, max_len=max_len, memory_len=16)
            inputs = (frames, prompts)
        else:
            cache = model.init_cache(batch, max_len=max_len)
            inputs = (prompts,)
        jax.block_until_ready((params, cache))

        prefill, decode = greedy_steps(model, mesh)
        t0 = time.perf_counter()
        prefill = prefill.lower(params, *inputs, cache).compile()
        tok0 = jnp.zeros((batch, 1), jnp.int32)
        decode = decode.lower(params, tok0, cache).compile()
        compile_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        logits, tok, cache = prefill(params, *inputs, cache)
        jax.block_until_ready(tok)
        prefill_s = time.perf_counter() - t0

        all_logits, generated = [logits], [tok]
        t0 = time.perf_counter()
        for _ in range(gen_tokens - 1):
            logits, tok, cache = decode(params, tok, cache)
            all_logits.append(logits)
            generated.append(tok)
        jax.block_until_ready(tok)
        decode_s = time.perf_counter() - t0

    steps = max(gen_tokens - 1, 1)
    return {
        "compile_s": compile_s,
        "prefill_s": prefill_s,
        "decode_s": decode_s,
        "decode_ms_per_token": 1e3 * decode_s / steps,
        "decode_tok_per_s": batch * (gen_tokens - 1) / max(decode_s, 1e-9),
        "tokens": np.asarray(jnp.concatenate(generated, axis=1)),
        "logits": jnp.concatenate(all_logits, axis=1),   # (B, gen, V)
        "prompts": prompts,
        "params": params,
        "model": model,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--full", action="store_true",
                    help="published widths (one accelerator)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-tokens", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()
    out = serve_loop(args.arch, reduced=not args.full, batch=args.batch,
                     prompt_len=args.prompt_len,
                     gen_tokens=args.gen_tokens, seed=args.seed)
    dev = jax.devices()[0]
    print(f"[serve] device={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())}")
    print(f"[serve] compile={out['compile_s']:.3f}s")
    print(f"[serve] prefill={1e3 * out['prefill_s']:.3f}ms "
          f"decode={out['decode_ms_per_token']:.3f}ms/token "
          f"({out['decode_tok_per_s']:.1f} tok/s)")
    print(f"[serve] sample tokens: {out['tokens'][0][:8].tolist()}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
