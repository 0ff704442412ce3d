"""DScope overhead: serve_load smoke with full observability on vs off.

DScope's one hook, the :class:`Tracer`, costs one ``is None`` test per
instrumentation point when detached.  The *off* arm is the production
default — DServe's internal :class:`MetricsRegistry` with pull-only
collectors (scraped once per run, zero hot-path work).  The *on* arm
attaches everything at once: an explicit registry (which DServe fills
with its serving-latency histograms after the run) plus a
:class:`Tracer` recording the full request → invoke → acquire →
Get/Put span tree.

The acceptance gate (asserted here AND standardized into
``BENCH_obs.json`` for ``bench_compare``): **obs-on p99 <= 1.05x
obs-off p99**.  Both arms are best-of-``repeats`` — thread-scheduling
noise on a shared runner dwarfs the effect otherwise.

``--trace-out FILE`` additionally exports the on-arm's span tree as
Chrome ``trace_event`` JSON (the CI perfetto artifact).

Run:  PYTHONPATH=src python -m benchmarks.obs_overhead \
          [--out FILE] [--trace-out FILE]
"""

import argparse
import json

from repro.core.obs import (MetricsRegistry, Tracer, bench_doc,
                            bench_metric, to_chrome_trace)
from repro.core.serve import DServe, poisson_arrivals
from repro.core.workloads import serving_chain

SMOKE = dict(rate=8.0, n=10, stages=4, exec_time=0.03, cold_start=0.15)

P99_GATE = 1.05


def _run_once(*, metrics=None, spans=None, rate, n, stages, exec_time,
              cold_start):
    wf = serving_chain(stages=stages, exec_time=exec_time,
                       cold_start=cold_start, payload=16 * 1024)
    srv = DServe(wf, n_nodes=2, pattern="dataflow", keepalive=10.0,
                 max_per_node=16, metrics=metrics, spans=spans)
    rep = srv.run(poisson_arrivals(rate, n, seed=7),
                  inputs={"request": b"req"})
    assert rep.failures == 0, "instances failed during benchmark"
    return rep, srv


def measure(cfg=SMOKE, repeats: int = 3):
    off = min((_run_once(**cfg)[0] for _ in range(repeats)),
              key=lambda r: r.wall_time)

    runs = []

    def instrumented():
        reg, tr = MetricsRegistry(), Tracer()
        rep, _ = _run_once(metrics=reg, spans=tr, **cfg)
        runs.append((rep, reg, tr))
        return rep

    on = min((instrumented() for _ in range(repeats)),
             key=lambda r: r.wall_time)
    rep, reg, tr = next(r for r in runs if r[0] is on)
    dump = reg.collect()
    spans = tr.finished()
    n_hist = sum(h["count"] for h in dump["histograms"].values())

    p99_ratio = round(on.p99 / max(off.p99, 1e-9), 3)
    wall_ratio = round(on.wall_time / max(off.wall_time, 1e-9), 3)
    metrics = [
        bench_metric("dscope", "p99_ratio", p99_ratio, "x",
                     direction="lower", tolerance=P99_GATE - 1.0),
        bench_metric("dscope", "wall_ratio", wall_ratio, "x",
                     direction="lower"),
        bench_metric("dscope", "p99_on_s", round(on.p99, 4), "s"),
        bench_metric("dscope", "p99_off_s", round(off.p99, 4), "s"),
        bench_metric("dscope", "spans", len(spans), "spans"),
    ]
    return bench_doc(
        "obs_overhead", cfg, metrics,
        repeats=repeats,
        obs_off={"p50_s": round(off.p50, 4), "p99_s": round(off.p99, 4),
                 "wall_s": round(off.wall_time, 4)},
        obs_on={"p50_s": round(on.p50, 4), "p99_s": round(on.p99, 4),
                "wall_s": round(on.wall_time, 4),
                "spans": len(spans),
                "histogram_observations": n_hist,
                "registry_series": (len(dump["counters"])
                                    + len(dump["gauges"])
                                    + len(dump["histograms"]))},
        overhead={"p99_ratio": p99_ratio, "wall_ratio": wall_ratio},
    ), spans


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_obs.json",
                    help="output JSON path (default: BENCH_obs.json)")
    ap.add_argument("--trace-out", metavar="FILE",
                    help="export the instrumented arm's span tree as "
                    "Chrome trace_event JSON (perfetto)")
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args(argv)
    doc, spans = measure(repeats=args.repeats)
    with open(args.out, "w", encoding="utf-8") as fh:
        json.dump(doc, fh, indent=2)
        fh.write("\n")
    print(json.dumps(doc, indent=2))
    if args.trace_out:
        trace = to_chrome_trace(spans)
        with open(args.trace_out, "w", encoding="utf-8") as fh:
            json.dump(trace, fh)
            fh.write("\n")
        print(f"# wrote {len(trace['traceEvents'])} trace event(s) to "
              f"{args.trace_out}")
    ratio = doc["overhead"]["p99_ratio"]
    assert ratio <= P99_GATE, (
        f"full observability (registry + spans) cost {ratio:.3f}x p99 — "
        f"gate is {P99_GATE}x; an instrumentation point is doing hot-path "
        "work it shouldn't")
    print(f"# obs-on p99 is {ratio:.2f}x obs-off (gate {P99_GATE}x): "
          f"{doc['obs_on']['spans']} spans, "
          f"{doc['obs_on']['histogram_observations']} histogram "
          f"observations recorded")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
