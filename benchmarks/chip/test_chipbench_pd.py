"""The prefill -> decode cell's whole harness path on the CPU at a small
size, and the faults its check must catch: a decode step that leaves its
state unchanged, and a token altered where it is produced."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import chipbench_tiny  # noqa: E402
import harness  # noqa: E402

CELL = "starcoder2_pd.code_completion"
KEYS = ["correct", "attempted", "failed", "metrics", "device", "check"]


def bench():
    return json.loads((harness.CHECKOUT / "BENCHMARK.json").read_text())


def test_window_result_line():
    out = chipbench_tiny.run(CELL)
    assert list(out) == KEYS
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == 8
    assert set(out["metrics"]) == {m["name"] for m in bench()["end_to_end"]}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["check"]["failed_requests"]["value"] == 0
    json.dumps(out, allow_nan=False)


def test_traced_result_line():
    out = chipbench_tiny.run(CELL, seed=11, trace=True)
    assert list(out) == KEYS[:3] + ["breakdown"] + KEYS[3:]
    assert out["correct"] is True
    per_layer = {m["name"] for m in bench()["per_layer"]
                 if CELL in m["workloads"]}
    # on the CPU there is no device plane: the device metrics stay silent
    assert set(out["metrics"]) == {"client.launch_lag_p95_ms",
                                   "engine.dispatch_gap_ms",
                                   "dstore.put_ms_per_req"}
    assert set(out["metrics"]) <= per_layer
    assert {"busy_s", "window_s"} <= set(out["device"])


def broken_steps(monkeypatch, fault):
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

        def prefill2(params, prompt, cache):
            logits, t, c = prefill(params, prompt, cache)
            return logits, (t + 1) % model.cfg.vocab, c
        return jax.jit(prefill2), decode
    monkeypatch.setattr(serve, "greedy_steps", steps)


@pytest.mark.parametrize("fault", ["state", "token"])
def test_check_catches(monkeypatch, fault):
    broken_steps(monkeypatch, fault)
    out = chipbench_tiny.run(CELL, seed=5)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for name, c in out["check"].items()
               if name.startswith(("widest_", "mean_"))), out["check"]
