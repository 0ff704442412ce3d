"""DCheck overhead: serve_load smoke with the trace checker on vs off.

The recorder rides on the data plane's one hook, the DScope
:class:`Tracer`: zero-cost when detached (one ``is None`` test per
instrumentation point) and cheap when attached (a span per data-plane
operation, plus an event append + digest under one lock).  This benchmark pins both claims to a
number and writes ``BENCH_dcheck.json`` so later PRs (sharded DStore,
dynamic DAGs) can see whether they regressed the checker's overhead.

Methodology: the serve_load SMOKE configuration (one rate, 10 Poisson
arrivals of the 4-stage Srv chain) runs once with no tracer, once with a
``Tracer(recorder=TraceRecorder())`` attached (no stress sleeps — those
measure the *scheduler*, not the checker), and the p50/p99/wall numbers
are compared.
The traced run's events are then replayed through :class:`TraceChecker`
and its offline check time is reported separately — the checker never
sits on the serving path.

Run:  PYTHONPATH=src python -m benchmarks.dcheck_overhead [--out FILE]
"""

import argparse
import json
import time

from repro.core.check import TraceChecker, TraceRecorder
from repro.core.obs import Tracer, bench_doc, bench_metric
from repro.core.serve import DServe, poisson_arrivals
from repro.core.workloads import serving_chain

SMOKE = dict(rate=8.0, n=10, stages=4, exec_time=0.03, cold_start=0.15)


def _run_once(spans, *, rate, n, stages, exec_time, cold_start):
    wf = serving_chain(stages=stages, exec_time=exec_time,
                       cold_start=cold_start, payload=16 * 1024)
    srv = DServe(wf, n_nodes=2, pattern="dataflow", keepalive=10.0,
                 max_per_node=16, spans=spans)
    rep = srv.run(poisson_arrivals(rate, n, seed=7),
                  inputs={"request": b"req"})
    assert rep.failures == 0, "instances failed during benchmark"
    return rep


def measure(cfg=SMOKE, repeats: int = 3):
    """Best-of-``repeats`` for each mode (thread-scheduling noise on a
    shared runner dwarfs the effect being measured otherwise)."""
    off = min((_run_once(None, **cfg) for _ in range(repeats)),
              key=lambda r: r.wall_time)
    recorders = []

    def traced():
        rec = TraceRecorder()
        recorders.append(rec)
        return _run_once(Tracer(recorder=rec), **cfg)

    on = min((traced() for _ in range(repeats)),
             key=lambda r: r.wall_time)
    rec = max(recorders, key=len)
    t0 = time.perf_counter()
    violations = TraceChecker().check(rec.events())
    check_s = time.perf_counter() - t0
    assert not violations, [str(v) for v in violations]
    p99_ratio = round(on.p99 / max(off.p99, 1e-9), 3)
    wall_ratio = round(on.wall_time / max(off.wall_time, 1e-9), 3)
    # Standardized dflow-bench/v1 rows.  Ratios are gated (lower is
    # better; noise-relative, so they survive shared runners); absolute
    # wall-clock latencies are report-only.
    metrics = [
        bench_metric("dcheck", "p99_ratio", p99_ratio, "x",
                     direction="lower"),
        bench_metric("dcheck", "wall_ratio", wall_ratio, "x",
                     direction="lower"),
        bench_metric("dcheck", "p99_on_s", round(on.p99, 4), "s"),
        bench_metric("dcheck", "p99_off_s", round(off.p99, 4), "s"),
        bench_metric("dcheck", "offline_check_s", round(check_s, 5), "s"),
    ]
    return bench_doc(
        "dcheck_overhead", cfg, metrics,
        repeats=repeats,
        checker_off={"p50_s": round(off.p50, 4),
                     "p99_s": round(off.p99, 4),
                     "wall_s": round(off.wall_time, 4)},
        checker_on={"p50_s": round(on.p50, 4),
                    "p99_s": round(on.p99, 4),
                    "wall_s": round(on.wall_time, 4),
                    "events": len(rec)},
        overhead={"p99_ratio": p99_ratio, "wall_ratio": wall_ratio},
        offline_check={"events": len(rec),
                       "check_s": round(check_s, 5),
                       "violations": 0},
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_dcheck.json",
                    help="output JSON path (default: BENCH_dcheck.json)")
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args(argv)
    doc = measure(repeats=args.repeats)
    with open(args.out, "w", encoding="utf-8") as fh:
        json.dump(doc, fh, indent=2)
        fh.write("\n")
    print(json.dumps(doc, indent=2))
    ratio = doc["overhead"]["p99_ratio"]
    print(f"# checker-on p99 is {ratio:.2f}x checker-off "
          f"({doc['checker_on']['events']} events recorded, offline check "
          f"{doc['offline_check']['check_s'] * 1e3:.1f} ms)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
