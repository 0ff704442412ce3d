"""Small sizes at which the tests drive each cell's whole harness path on
the CPU: the same deployments, workflow, DServe and reference as on the
chip, with widths and lengths cut so a test run holds them.  The limit
each cell's check applies stays the one in its traffic file.

``SMALL`` is larger: big enough that the int8 control's error stands out
of the bf16 program's, as it does at the cells' own sizes on the chip.
The code-completion cell's own limit separates the two at this size too.
The WordCount cell's do not: its numbers grow with depth, and at 4 of 48
layers the int8 control reads 0.0135-0.0162 in mean logit error, under
what the program reads at 48 (0.025-0.029, PERF.md).  Its entry carries
limits set the same way from readings at this size (CPU, seeds 1-3:
program 0.0028-0.0042, int8 0.0135-0.0162)."""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402

TINY = {
    "pd-starcoder2-15b-stage": {
        "sizes": {"hidden_size": 64, "num_attention_heads": 4,
                  "num_key_value_heads": 2, "head_dim": 16,
                  "intermediate_size": 128, "vocab_size": 256,
                  "num_hidden_layers": 2, "initializer_range": 0.125},
        "traffic": {"rate_per_s": 8.0, "trace_slice_s": 0.5,
                    "prompt_tokens": [[32, 0.5], [64, 0.5]],
                    "output_tokens": [[4, 1.0]]},
        "served_tokens": 16},
    "wc-mamba2-370m": {
        "sizes": {"d_model": 64, "n_layer": 2, "vocab_size": 250,
                  "d_state": 16, "headdim": 16, "fanout": 4,
                  "map_tokens": 2, "chunk_size": 16},
        "traffic": {"rate_per_s": 8.0, "trace_slice_s": 0.5,
                    "prompt_tokens": [[64, 1.0]],
                    "output_tokens": [[4, 1.0]]},
        "served_tokens": 12},
}

SMALL = {
    "pd-starcoder2-15b-stage": {
        "sizes": {"hidden_size": 256, "num_attention_heads": 4,
                  "num_key_value_heads": 2, "head_dim": 64,
                  "intermediate_size": 1024, "vocab_size": 4096,
                  "num_hidden_layers": 4, "initializer_range": 0.0625},
        "traffic": {"rate_per_s": 8.0, "trace_slice_s": 0.5,
                    "prompt_tokens": [[64, 0.5], [128, 0.5]],
                    "output_tokens": [[8, 1.0]]},
        "served_tokens": 96},
    "wc-mamba2-370m": {
        "sizes": {"d_model": 256, "n_layer": 4, "vocab_size": 4096,
                  "d_state": 32, "headdim": 32, "fanout": 4,
                  "map_tokens": 4, "chunk_size": 32},
        "traffic": {"rate_per_s": 8.0, "trace_slice_s": 0.5,
                    "prompt_tokens": [[256, 1.0]],
                    "output_tokens": [[8, 1.0]]},
        "served_tokens": 96,
        "limits": {"mean_logit_error": 0.009}},
}


def overrides(workload: str, table: dict = TINY,
              traffic: str | None = None) -> dict:
    """Sizes and traffic for ``workload`` from ``table``, keeping its
    check limits unless the table gives its own; with ``traffic``, the
    check and settings of that traffic file in place of the cell's own."""
    cell = harness.load_cell(workload)
    base = cell.traffic if traffic is None else json.loads(
        (HERE / "traffic" / f"{traffic}.json").read_text())
    small = table[base["config"]]
    check = dict(base["check"], served_tokens=small["served_tokens"])
    if "limits" in small:
        check["limits"] = small["limits"]
    return {"sizes": small["sizes"],
            "traffic": dict(base, **small["traffic"], check=check)}


def run(workload: str, seed: int = 2**33 + 7, trace: bool = False,
        traffic: str | None = None) -> dict:
    return harness.run_cell(workload, seed, 1.0, trace,
                            t_start=time.monotonic(), require_chip=False,
                            overrides=overrides(workload, traffic=traffic))
