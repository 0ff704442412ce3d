"""DPlan overhead + payoff: plan build time and plan-driven serving deltas.

Two questions, one JSON (``BENCH_dplan.json``) so later PRs can track
both:

1. **Analysis cost** — how long does :func:`repro.core.plan.build_plan`
   take per built-in workload (partition + liveness + slack DP + transfer
   matrix)?  The plan is built once per (workflow, placement) and reused
   across every serving instance, so this must be microseconds-to-
   milliseconds, never request-path work.
2. **Runtime payoff** — the serve_load SMOKE configuration run with the
   keep-alive heuristic (evict at instance completion, prewarm at
   precursor launch) vs plan-driven (evict at statically-last read,
   slack-timed boots).  Reported: peak resident DStore bytes, request-
   path cold starts, p99.  Best-of-``repeats`` per mode — thread noise
   on a shared runner dwarfs the effect otherwise.

The plan-driven run is also trace-recorded and replayed through
:class:`~repro.core.check.PlanConformance`, so the benchmark doubles as
an end-to-end conformance check on a real concurrent serving trace.

Run:  PYTHONPATH=src python -m benchmarks.dplan_overhead [--out FILE]
"""

import argparse
import json
import time

from repro.core.check import PlanConformance, TraceRecorder
from repro.core.obs import Tracer, bench_doc, bench_metric
from repro.core.partition import partition_workflow
from repro.core.plan import build_plan
from repro.core.serve import DServe, poisson_arrivals
from repro.core.workloads import BENCHMARKS, serving_chain

SMOKE = dict(rate=8.0, n=10, stages=4, exec_time=0.03, cold_start=0.15)


def plan_build_times(repeats: int = 5):
    """Best-of-``repeats`` build_plan wall time per builtin workload."""
    out = {}
    nodes = ["node0", "node1"]
    for name, mk in sorted(BENCHMARKS.items()):
        wf = mk()
        placement = partition_workflow(wf, nodes)
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            plan = build_plan(wf, placement)
            best = min(best, time.perf_counter() - t0)
        out[name] = {"build_us": round(best * 1e6, 1),
                     "functions": len(plan.functions),
                     "keys": len(plan.keys),
                     "evictable": len(plan.eviction_reads)}
    return out


def _run_once(*, plan, spans=None, rate, n, stages, exec_time, cold_start):
    wf = serving_chain(stages=stages, exec_time=exec_time,
                       cold_start=cold_start, payload=16 * 1024)
    srv = DServe(wf, n_nodes=2, pattern="dataflow", keepalive=10.0,
                 max_per_node=16, plan=plan, spans=spans)
    rep = srv.run(poisson_arrivals(rate, n, seed=7),
                  inputs={"request": b"req"})
    assert rep.failures == 0, "instances failed during benchmark"
    return rep, srv


def measure(cfg=SMOKE, repeats: int = 3):
    heur = min((_run_once(plan=False, **cfg)[0] for _ in range(repeats)),
               key=lambda r: r.wall_time)
    planned = min((_run_once(plan=True, **cfg)[0] for _ in range(repeats)),
                  key=lambda r: r.wall_time)

    # One traced plan-driven run, conformance-checked end to end.
    rec = TraceRecorder()
    traced, srv = _run_once(plan=True, spans=Tracer(recorder=rec), **cfg)
    violations = PlanConformance(srv.plan).check(
        rec.events(), instances=[s.instance for s in traced.stats])
    assert not violations, [str(v) for v in violations]

    def row(rep):
        return {"p50_s": round(rep.p50, 4), "p99_s": round(rep.p99, 4),
                "wall_s": round(rep.wall_time, 4),
                "cold_starts": rep.cold_starts,
                "prewarm_boots": rep.prewarm_boots,
                "container_seconds": round(rep.container_seconds, 3),
                "peak_resident_bytes": rep.peak_resident_bytes}

    builds = plan_build_times()
    delta = {
        "peak_resident_ratio": round(
            planned.peak_resident_bytes
            / max(heur.peak_resident_bytes, 1), 3),
        "p99_ratio": round(planned.p99 / max(heur.p99, 1e-9), 3),
        "cold_starts": planned.cold_starts - heur.cold_starts,
    }
    # peak_resident_ratio is the plan's headline win (deterministic byte
    # accounting, 9x headroom to the <1.0 assert) — gated with a loose
    # tolerance.  p99_ratio rides thread-scheduling noise, report-only.
    metrics = [
        bench_metric("dplan", "peak_resident_ratio",
                     delta["peak_resident_ratio"], "x",
                     direction="lower", tolerance=1.0),
        bench_metric("dplan", "p99_ratio", delta["p99_ratio"], "x"),
        bench_metric("dplan", "request_cold_starts",
                     planned.cold_starts, "boots"),
        bench_metric("dplan", "build_us_worst",
                     max(b["build_us"] for b in builds.values()), "us"),
    ]
    return bench_doc(
        "dplan_overhead", cfg, metrics,
        repeats=repeats,
        plan_build=builds,
        heuristic=row(heur),
        plan_driven=row(planned),
        delta=delta,
        conformance={"events": len(rec), "violations": 0},
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_dplan.json",
                    help="output JSON path (default: BENCH_dplan.json)")
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args(argv)
    doc = measure(repeats=args.repeats)
    with open(args.out, "w", encoding="utf-8") as fh:
        json.dump(doc, fh, indent=2)
        fh.write("\n")
    print(json.dumps(doc, indent=2))
    d = doc["delta"]
    assert d["peak_resident_ratio"] < 1.0, (
        "plan-driven eviction must bound resident bytes below the "
        f"keep-alive baseline (got {d['peak_resident_ratio']}x)")
    print(f"# plan-driven serving: {d['peak_resident_ratio']:.2f}x peak "
          f"resident bytes, {d['p99_ratio']:.2f}x p99, "
          f"{d['cold_starts']:+d} request-path cold starts vs heuristic")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
