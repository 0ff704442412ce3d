"""The chat traffic file, which waits for a cell of its own in
``BENCHMARK.json``, drives the prefill -> decode deployment's whole
harness path on the CPU at a small size, with its own check."""

from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import chipbench_tiny  # noqa: E402
import harness  # noqa: E402

CELL = "starcoder2_pd.code_completion"


def test_window_result_line():
    chat = json.loads((harness.HERE / "traffic" / "chat.json").read_text())
    assert chat["config"] == harness.load_cell(CELL).traffic["config"]
    out = chipbench_tiny.run(CELL, seed=2**31 + 11, traffic="chat")
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "check"]
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"p50_ms", "p95_ms", "completed_rps",
                                   "setup_s"}
    assert set(chat["check"]["limits"]) <= set(out["check"])
