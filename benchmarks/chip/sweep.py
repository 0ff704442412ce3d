"""Find a cell's knee: the highest offered rate it serves without a
growing backlog.

    python3 benchmarks/chip/sweep.py --workload <cell> --seconds <s> \\
        --seed <n> --rates 0.5 1 1.5 ...

One process, one set-up: the cell's deployment serves one window of
``--seconds`` at each rate in turn, with the traffic file's mix.  A rate
holds when the median latency of the requests due in the window's second
half is at most ``--growth`` times that of the first half, and every
request completed.  The knee is the highest rate that holds with every
lower rate holding too; the cell's rate is set to about four fifths of it
by hand, in its traffic file.  Prints one JSON line per rate, then the
knee.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))


def halves(win) -> tuple[float | None, float | None]:
    from stats import percentile

    mid = win.opened + win.requests[-1].arrival / 2
    first = [r["done"] - r["due"] for r in win.rows
             if r["ok"] and r["due"] < mid]
    second = [r["done"] - r["due"] for r in win.rows
              if r["ok"] and r["due"] >= mid]
    return (percentile(first, 50.0) if first else None,
            percentile(second, 50.0) if second else None)


def drained(win) -> float | None:
    """Seconds from the last request's due time to the last completion."""
    done = [r["done"] for r in win.rows if r["ok"]]
    return max(done) - win.rows[-1]["due"] if done else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--growth", type=float, default=1.25)
    args = ap.parse_args(argv)

    import arrivals
    import harness

    harness.keep_files_local()
    cell = harness.load_cell(args.workload)
    harness.device_info(cell.chips)
    harness.use_compile_cache()
    plans, offset = [], 0
    for rate in args.rates:
        reqs = arrivals.make_requests(dict(cell.traffic, rate_per_s=rate),
                                      args.seconds)
        plans.append([dataclasses.replace(r, index=offset + r.index)
                      for r in reqs])
        offset += len(reqs)
    session = harness.Session(cell, args.seed,
                              [r for p in plans for r in p], trace=False)
    knee, holding = None, True
    for rate, reqs in zip(args.rates, plans):
        win = session.window(reqs)
        e2e = win.end_to_end(0.0)
        first, second = halves(win)
        held = (win.failed == 0 and first is not None and second is not None
                and second <= args.growth * first)
        holding = holding and held
        if holding:
            knee = rate
        print(json.dumps({"rate_per_s": rate, "requests": len(reqs),
                          "failed": win.failed, "p50_ms": e2e["p50_ms"],
                          "p95_ms": e2e["p95_ms"],
                          "completed_rps": e2e["completed_rps"],
                          "p50_first_half_ms": None if first is None
                          else 1e3 * first,
                          "p50_second_half_ms": None if second is None
                          else 1e3 * second,
                          "drained_s": drained(win),
                          "holds": held}), flush=True)
    print(json.dumps({"workload": args.workload, "knee_rate_per_s": knee}))
    session.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
