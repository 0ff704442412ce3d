"""Training driver: ``python -m repro.launch.train --arch <id> ...``

End-to-end loop on real devices (reduced configs on this CPU container;
full configs on TPU): data pipeline with background prefetch, jitted
sharded train step, checkpoint/restart fault tolerance, and optional
DFlow-orchestrated mode where the job DAG (fetch → step → async-ckpt) runs
under the paper's dataflow scheduler.

Fault tolerance: ``--simulate-failure K`` raises after step K; rerunning
the same command resumes from the last complete checkpoint and reproduces
the identical loss trajectory (the data pipeline is keyed by step).
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, list_archs
from repro.data import DataConfig, make_pipeline
from repro.checkpoint import CheckpointManager
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models import build_model
from repro.optim.adamw import AdamWConfig
from repro.runtime.train_lib import (build_train_step, init_train_state,
                                     make_train_state_specs)
from repro.sharding.context import mesh_context

__all__ = ["main", "train_loop"]


def train_loop(arch: str, *, steps: int = 20, batch: int = 8, seq: int = 128,
               reduced: bool = True, ckpt_dir: str | None = None,
               ckpt_every: int = 0, resume: bool = False,
               simulate_failure: int | None = None, seed: int = 0,
               log_every: int = 1, data: int = 1, model: int = 1,
               microbatches: int | None = None) -> dict:
    import dataclasses

    cfg = get_config(arch, reduced=reduced)
    cfg = dataclasses.replace(cfg, q_chunk=max(seq // 2, 16),
                              kv_chunk=max(seq // 2, 16),
                              microbatches=microbatches or 1)
    if cfg.family == "encdec":
        raise SystemExit("use examples/seamless_train.py for enc-dec")
    mesh = make_local_mesh(data=data, model=model)
    model_obj = build_model(cfg)
    opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=max(steps // 10, 1),
                          total_steps=steps)

    with mesh_context(mesh):
        step_fn, specs = build_train_step(model_obj, mesh, opt_cfg)
        state = init_train_state(model_obj, mesh, opt_cfg, seed=seed)

        mgr = None
        start_step = 0
        if ckpt_dir:
            mgr = CheckpointManager(ckpt_dir, keep=2, async_save=True)
            if resume:
                latest = mgr.latest()
                if latest is not None:
                    state, start_step = mgr.restore(state)
                    print(f"[train] resumed from step {start_step}")

        dcfg = DataConfig(vocab=cfg.vocab, seq_len=seq, global_batch=batch,
                          seed=seed)
        pipe = make_pipeline(dcfg, start_step=start_step)
        losses = []
        t0 = time.time()
        try:
            for i in range(start_step, steps):
                step_idx, np_batch = pipe.next()
                assert step_idx == i, (step_idx, i)
                batch_dev = {k: jax.numpy.asarray(v)
                             for k, v in np_batch.items()}
                state, metrics = step_fn(state, batch_dev)
                loss = float(metrics["loss"])
                losses.append(loss)
                if log_every and i % log_every == 0:
                    print(f"[train] step {i:4d} loss {loss:.4f} "
                          f"gnorm {float(metrics['grad_norm']):.3f}",
                          flush=True)
                if mgr and ckpt_every and (i + 1) % ckpt_every == 0:
                    mgr.save(i + 1, state)
                if simulate_failure is not None and i + 1 == simulate_failure:
                    raise RuntimeError(
                        f"simulated node failure at step {i + 1}")
        finally:
            pipe.close()
            if mgr:
                mgr.wait()
        wall = time.time() - t0
        tokens = (steps - start_step) * batch * seq
        return {"losses": losses, "wall_s": wall,
                "tokens_per_s": tokens / max(wall, 1e-9),
                "final_loss": losses[-1] if losses else float("nan"),
                "start_step": start_step}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--full", action="store_true",
                    help="full config (TPU pods only)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--simulate-failure", type=int, default=None)
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()
    out = train_loop(args.arch, steps=args.steps, batch=args.batch,
                     seq=args.seq, reduced=not args.full,
                     ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                     resume=args.resume,
                     simulate_failure=args.simulate_failure,
                     microbatches=args.microbatches, seed=args.seed)
    print(f"[train] done: final_loss={out['final_loss']:.4f} "
          f"tokens/s={out['tokens_per_s']:.0f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
