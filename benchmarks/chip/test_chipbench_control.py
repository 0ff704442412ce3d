"""The controls that the correctness limits were set against, at a size a
test run holds: the plain reference computed in the precision below the
bf16 the configurations state that each cell's traffic file names (int8,
W8A8), read at the positions the program served.  Judged with
``harness.judge``, the test that decides ``correct`` in a run, under the
cell's own limits (the WordCount cell's set for this size, see
``chipbench_tiny``), the program passes and the control fails on every
seed; on the chip, at the cells' own sizes and under their own limits,
the same holds (``control.py``, PERF.md)."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import chipbench_tiny  # noqa: E402
import control  # noqa: E402


@pytest.mark.parametrize("workload", ["starcoder2_pd.code_completion",
                                      "mamba2_wc.fanout8"])
def test_control_stands_out_of_the_program(workload):
    ov = chipbench_tiny.overrides(workload, chipbench_tiny.SMALL)
    want = ov["traffic"]["check"]["control"]
    for seed in (1, 2, 3):
        r = control.readings(workload, seed, 1.0, require_chip=False,
                             overrides=ov)
        assert r["failed"] == 0 and r["program"]["tokens"] >= 64
        assert r["program_correct"] is True, r
        assert r["control_correct"] == {want: False}, r
