"""The WordCount cell's whole harness path on the CPU at a small size, and
the faults its check must catch: a decode step that leaves its state
unchanged, a token altered where it is produced, and an engine that hands
merge the maps' outputs out of order."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import chipbench_tiny  # noqa: E402
import harness  # noqa: E402

CELL = "mamba2_wc.fanout8"
KEYS = ["correct", "attempted", "failed", "metrics", "device", "check"]


def test_window_result_line():
    out = chipbench_tiny.run(CELL)
    assert list(out) == KEYS
    assert out["correct"] is True and out["failed"] == 0
    bench = json.loads((harness.CHECKOUT / "BENCHMARK.json").read_text())
    assert set(out["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    json.dumps(out, allow_nan=False)


def test_traced_result_line():
    out = chipbench_tiny.run(CELL, seed=12, trace=True)
    assert list(out) == KEYS[:3] + ["breakdown"] + KEYS[3:]
    assert out["correct"] is True
    assert {"client.launch_lag_p95_ms", "engine.dispatch_gap_ms",
            "dstore.put_ms_per_req"} <= set(out["metrics"])


@pytest.mark.parametrize("fault", ["state", "token"])
def test_check_catches_step_faults(monkeypatch, fault):
    import jax
    import repro.launch.serve as serve

    real = serve.greedy_steps

    def steps(model, mesh, *, donate=True):
        prefill, decode = real(model, mesh, donate=donate)
        if fault == "state":
            def decode2(params, tok, cache):
                logits, t, _ = decode(params, tok, cache)
                return logits, t, cache
            return prefill, jax.jit(decode2)

        def decode2(params, tok, cache):
            logits, t, c = decode(params, tok, cache)
            return logits, (t + 1) % model.cfg.vocab, c
        return prefill, jax.jit(decode2)
    monkeypatch.setattr(serve, "greedy_steps", steps)
    out = chipbench_tiny.run(CELL, seed=6)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for name, c in out["check"].items()
               if name.startswith(("widest_", "mean_"))), out["check"]


def test_check_catches_merge_inputs_out_of_order(monkeypatch):
    from repro.core.dscheduler import InstanceRun

    real = InstanceRun._fetch_inputs

    def fetch(self, node, f):
        kw = real(self, node, f)
        if "toks.0" in kw:
            kw["toks.0"], kw["toks.1"] = kw["toks.1"], kw["toks.0"]
        return kw
    monkeypatch.setattr(InstanceRun, "_fetch_inputs", fetch)
    out = chipbench_tiny.run(CELL, seed=8)
    assert out["correct"] is False
