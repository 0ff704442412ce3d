"""Readings from which a cell's correctness limits are set, and the proof
that its control is judged not correct.

    python3 benchmarks/chip/control.py --workload <cell> --seconds <s> \\
        --seeds 1 2 3 ... [--controls int8 fp8]

For each seed, in one process: the cell's deployment with that seed's
weights serves a window at the cell's own rate and mix, then the plain
reference reads a seeded sample of the served tokens, as a run does
(``program``: the widest and the mean logit gap and logit error, see
``harness.compare``).  The control is read at the same positions: the
plain reference computed in a precision below the bf16 the configuration
states (W8A8; by default the one the cell's traffic file names), under
``control``.  Each reading is judged by the cell's own limits with
``harness.judge``, the test that decides ``correct`` in a run: the
program's must pass (``program_correct``) and each control's must fail
(``control_correct``).  Prints one JSON line per seed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))


def readings(workload: str, seed: int, seconds: float, *, controls=None,
             require_chip: bool = True, overrides=None) -> dict:
    import arrivals
    import harness

    cell = harness.load_cell(workload, overrides=overrides)
    if require_chip:
        harness.device_info(cell.chips)
        harness.use_compile_cache()
    check = cell.traffic["check"]
    controls = list(controls or [check["control"]])
    requests = arrivals.make_requests(cell.traffic, seconds)
    session = harness.Session(cell, seed, requests, trace=False)
    win = session.window(requests)
    session.close()
    cmp = harness.compare(cell, seed, win.served, quant=controls)
    control = cmp.pop("control")
    limits = check["limits"]
    return {"workload": workload, "seed": seed, "failed": win.failed,
            "limits": limits, "program": cmp,
            "program_correct": win.failed == 0 and harness.judge(cmp,
                                                                  limits),
            "control": control,
            "control_correct": {q: harness.judge(c, limits)
                                for q, c in control.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", nargs="*", choices=("int8", "fp8"))
    args = ap.parse_args(argv)
    import harness

    harness.keep_files_local()
    for seed in args.seeds:
        t = time.monotonic()
        out = readings(args.workload, seed, args.seconds,
                       controls=args.controls)
        out["seconds"] = time.monotonic() - t
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
