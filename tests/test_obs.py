"""DScope observability: registry, span trees, exporters, attribution.

Layers under test (``repro.core.obs``):

* :class:`MetricsRegistry` — exact under concurrent increment, pull
  collectors, label typing.
* :class:`Tracer` — well-formed per-request span trees from real DServe
  runs (threaded, sharded) and simulator runs (virtual clock); JSONL and
  Chrome ``trace_event`` exporters round-trip.  The spans that break a
  request down where the work happens: ``admit`` (due time -> launch),
  ``slot`` and ``exec`` under each invoke, ``wait`` under each Get,
  ``digest`` and its per-leaf ``d2h`` under a co-writing Put; none of
  them exists without a tracer.
* :func:`attribute` — hand-built spans against a hand-built plan doc
  give exactly the drifts we constructed.
* The registry dump reproduces ``ServeReport.row()`` — one source of
  truth for every counter the serving layer reports.
* The fuzzed differential corpus stays byte-exact with full
  observability attached (quick stride here; 200-seed sweep is `slow`).
"""

import json
import math
import threading
import time

import numpy as np
import pytest
from strategies import external_inputs, oracle_run, random_workflow

from repro.core import obs
from repro.core.dag import FunctionSpec, Workflow
from repro.core.dscheduler import DFlowEngine
from repro.core.dstore import DStore
from repro.core.obs import (MetricsRegistry, Span, Tracer, attribute,
                            bench_doc, bench_metric, compare_docs,
                            plan_attribution, read_spans_jsonl,
                            to_chrome_trace, write_spans_jsonl)
from repro.core.serve import ContainerService, DServe, poisson_arrivals
from repro.core.workloads import serving_chain

N_SEEDS = 200


# ----------------------------------------------------------------------
# MetricsRegistry
# ----------------------------------------------------------------------

def test_registry_basics():
    reg = MetricsRegistry()
    reg.counter("hits", node="n0").inc()
    reg.counter("hits", node="n0").inc(2)
    reg.counter("hits", node="n1").inc()
    assert reg.counter("hits", node="n0").value == 3
    assert reg.total("hits") == 4
    assert reg.label_values("hits", "node") == {"n0": 3.0, "n1": 1.0}
    reg.gauge("depth").set(7)
    reg.gauge("depth").add(-2)
    assert reg.gauge("depth").value == 5
    h = reg.histogram("lat")
    for v in (0.1, 0.2, 0.3, 0.4):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 4 and math.isclose(s["sum"], 1.0)
    assert s["min"] == 0.1 and s["max"] == 0.4
    # Exact (interpolated) percentiles while the reservoir is complete.
    assert math.isclose(h.percentile(50.0), 0.25, rel_tol=1e-9)
    assert math.isclose(h.percentile(100.0), 0.4, rel_tol=1e-9)


def test_registry_type_conflict():
    reg = MetricsRegistry()
    reg.counter("x", node="n0")
    with pytest.raises(ValueError):
        reg.gauge("x", node="n0")
    with pytest.raises(ValueError):
        reg.histogram("x", node="n1")


def test_registry_concurrent_exact():
    """8 threads x 1000 increments + observations: exact totals, no lost
    updates (the counters sit on every hot path)."""
    reg = MetricsRegistry()
    n_threads, per = 8, 1000

    def worker(i):
        c = reg.counter("ops", worker=str(i % 2))
        h = reg.histogram("lat")
        for _ in range(per):
            c.inc()
            h.observe(0.001)

    ts = [threading.Thread(target=worker, args=(i,))
          for i in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert reg.total("ops") == n_threads * per
    assert reg.histogram("lat").count == n_threads * per


def test_registry_collector():
    """Pull collectors run at collect() and land in the same dump."""
    reg = MetricsRegistry()
    src = {"v": 0}
    reg.register_collector(
        lambda: reg.gauge("pulled", node="n0").set(src["v"]))
    src["v"] = 42
    dump = reg.collect()
    assert dump["gauges"]["pulled{node=n0}"] == 42.0
    src["v"] = 43
    assert reg.collect()["gauges"]["pulled{node=n0}"] == 43.0


# ----------------------------------------------------------------------
# Tracer: span trees from real runs
# ----------------------------------------------------------------------

def _serve_traced(*, sharded=False, n=6, nodes=2):
    wf = serving_chain(stages=3, exec_time=0.01, cold_start=0.05,
                       payload=8192)
    spans, reg = Tracer(), MetricsRegistry()
    srv = DServe(wf, n_nodes=nodes, pattern="dataflow", keepalive=5.0,
                 metrics=reg, spans=spans, plan=True, sharded=sharded)
    rep = srv.run(poisson_arrivals(20.0, n, seed=3),
                  inputs={"request": b"req"})
    assert rep.failures == 0
    return rep, srv, spans.finished(), reg


def check_well_formed(spans):
    """Every span ended; every parent exists, shares the trace, and
    (for non-evict spans) contains the child's interval."""
    by_id = {s.id: s for s in spans}
    assert len(by_id) == len(spans), "duplicate span ids"
    for s in spans:
        assert not math.isnan(s.end), (s.kind, s.name)
        assert s.end >= s.start or s.kind == "evict"
        if s.parent is not None:
            p = by_id[s.parent]
            assert p.trace == s.trace
            assert p.start - 1e-6 <= s.start and s.end <= p.end + 1e-6, (
                s.kind, s.name, p.kind, p.name)
            assert p.seq < s.seq, "parent must start before child"


def test_serve_span_tree_well_formed():
    rep, srv, spans, _ = _serve_traced()
    check_well_formed(spans)
    reqs = [s for s in spans if s.kind == "request"]
    assert len(reqs) == 6
    # Per-instance isolation: all spans of a trace belong to it, and
    # every instance got its own trace.
    assert len({r.trace for r in reqs}) == 6
    by_trace = {}
    for s in spans:
        by_trace.setdefault(s.trace, []).append(s)
    for trace, ss in by_trace.items():
        for s in ss:
            if s.kind in ("get", "put", "chunk", "chunk_put", "evict"):
                assert s.name.startswith(trace + ":"), (trace, s.name)
    # Gets/acquires nest under invokes, invokes under the request.
    by_id = {s.id: s for s in spans}
    for s in spans:
        if s.kind in ("get", "acquire"):
            parent = by_id.get(s.parent)
            if parent is not None and s.kind == "acquire":
                assert parent.kind == "invoke"
        if s.kind == "invoke":
            assert by_id[s.parent].kind == "request"
    # Request durations match the report's latencies (separate clock
    # reads of the same interval, so a few ms of slack).
    lat = sorted(r.duration for r in reqs)
    assert all(math.isclose(a, b, abs_tol=5e-3)
               for a, b in zip(lat, sorted(rep.latencies)))


def test_sharded_hop_spans_nested_under_gets():
    _, srv, spans, reg = _serve_traced(sharded=True, nodes=3)
    check_well_formed(spans)
    by_id = {s.id: s for s in spans}
    hops = [s for s in spans if s.kind == "hop"]
    assert hops, "cross-shard pulls should emit hop spans"
    for h in hops:
        assert by_id[h.parent].kind in ("get", "chunk")
        assert h.attrs["tier"] in ("ipc", "mem", "net")
    # The registry's routed-get count covers at least the hop spans.
    reg.collect()
    routed = sum(v for k, v in
                 reg.label_values("routing_gets", "hops").items()
                 if int(k) >= 1)
    assert routed >= len(hops)


def test_zero_cost_when_detached():
    """No hooks attached: the store carries None hooks and works."""
    store = DStore(["node0"])
    assert store._spans is None and store._metrics is None
    store.put("node0", "k", b"v")
    assert bytes(store.get("node0", "k")) == b"v"


def test_tracer_clock_is_monotonic_and_start_backdates():
    """Spans stay on ``time.monotonic`` (the clock a profiler trace is
    tied to), and ``start=`` backdates a span on that same clock."""
    tr = Tracer()
    lo = time.monotonic()
    sp = tr.start("x", "admit", start=lo - 0.25)
    tr.end(sp)
    hi = time.monotonic()
    assert sp.start == lo - 0.25
    assert lo <= sp.end <= hi
    with tr.span("y", "exec") as other:
        pass
    assert lo <= other.start <= other.end <= time.monotonic()


def test_exec_and_slot_nest_under_invoke():
    _, _, spans, _ = _serve_traced()
    check_well_formed(spans)
    by_id = {s.id: s for s in spans}
    invokes = [s for s in spans if s.kind == "invoke"]
    for kind in ("exec", "slot"):
        inner = [s for s in spans if s.kind == kind]
        assert len(inner) == len(invokes), kind
        for s in inner:
            parent = by_id[s.parent]
            assert parent.kind == "invoke" and parent.name == s.name
    for s in spans:
        if s.kind == "exec":
            slot = next(o for o in spans if o.kind == "slot"
                        and o.parent == s.parent)
            assert slot.end <= s.start


def test_slot_span_covers_the_wait_for_the_other_body():
    """One execution slot, two bodies ready at once: the second one's
    ``slot`` span lasts through the whole of the first one's ``exec``."""
    tr = Tracer()
    containers = ContainerService(["node0"], max_per_node=1,
                                  cold_start=0.0)
    engine = DFlowEngine(1, containers=containers, spans=tr)

    def body(out):
        def fn(x):
            time.sleep(0.03)
            return {out: x}
        return fn
    wf = Workflow("Two", [
        FunctionSpec("a", inputs=("x",), outputs=("ya",), fn=body("ya")),
        FunctionSpec("b", inputs=("x",), outputs=("yb",), fn=body("yb"))],
        {"x": 8})
    asked = threading.Semaphore(0)
    slot = containers.slot

    def counted_slot(node):
        asked.release()
        return slot(node)
    containers.slot = counted_slot
    with slot("node0"):             # hold the only slot ...
        run = engine.start(wf, {"x": b"x"})
        for _ in range(2):          # ... until both bodies ask for it
            assert asked.acquire(timeout=10.0)
    run.wait()
    spans = tr.finished()
    slots = sorted((s for s in spans if s.kind == "slot"),
                   key=lambda s: s.end)
    execs = {s.name: s for s in spans if s.kind == "exec"}
    assert [s.name for s in slots] == \
        sorted(execs, key=lambda n: execs[n].start)
    first, second = slots
    assert second.end >= execs[first.name].end
    assert second.duration >= execs[first.name].duration


def test_wait_ends_after_the_producers_put_publishes():
    """A consumer blocked in Get holds the key's metadata only once the
    Put has published it.  A first Put digests nothing: its value is
    published as it is.  (The Put span itself closes a few microseconds
    after the publish, so its end and the wait's may come in either
    order.)"""
    tr = Tracer()
    store = DStore(["node0", "node1"])
    store.attach_tracer(None)       # DCheck's recorder digests every Put
    store.attach_spans(tr)
    got = []
    consumer = threading.Thread(
        target=lambda: got.append(store.get("node1", "W#0:k", timeout=10)))
    consumer.start()
    time.sleep(0.05)
    store.put("node0", "W#0:k", np.arange(64, dtype=np.float32))
    consumer.join(10)
    assert got and np.array_equal(got[0], np.arange(64, dtype=np.float32))
    spans = tr.finished()
    by_id = {s.id: s for s in spans}
    (wait,) = [s for s in spans if s.kind == "wait"]
    (put,) = [s for s in spans if s.kind == "put"]
    assert not [s for s in spans if s.kind in ("digest", "d2h")]
    assert wait.name == put.name == "W#0:k"
    assert by_id[wait.parent].kind == "get"
    assert wait.start < put.start < wait.end
    # A co-write digests the first value and its own, under its Put.
    store.put("node0", "W#0:k", np.arange(64, dtype=np.float32))
    spans = tr.finished()
    by_id = {s.id: s for s in spans}
    cowrite = [s for s in spans if s.kind == "put"][-1]
    digests = [s for s in spans if s.kind == "digest"]
    assert len(digests) == 2
    assert all(by_id[d.parent] is cowrite for d in digests)
    # A local replica: the wait ends at once, before the Get does.
    store.get("node0", "W#0:k")
    spans = tr.finished()
    local = [s for s in spans if s.kind == "wait"][-1]
    (get,) = [s for s in spans if s.id == local.parent]
    assert get.start <= local.start <= local.end <= get.end


def test_digest_covers_one_d2h_per_array_leaf():
    tr = Tracer()
    store = DStore(["node0", "node1"])
    store.attach_tracer(None)       # DCheck's recorder digests every Put
    store.attach_spans(tr)

    def value():
        return {"k": np.ones((32, 16), np.float32),
                "v": (np.zeros(100, np.int8), np.arange(7.0)), "n": 3}
    store.put("node0", "W#0:cache", value())
    assert not [s for s in tr.finished() if s.kind in ("digest", "d2h")]
    assert store.directory.peek("W#0:cache").digest is None
    # The co-write digests both values: the first from its replica.
    store.put("node1", "W#0:cache", value())
    spans = tr.finished()
    digests = [s for s in spans if s.kind == "digest"]
    assert len(digests) == 2
    for digest in digests:
        d2h = [s for s in spans if s.kind == "d2h" and s.parent == digest.id]
        assert sorted(s.name for s in d2h) == [
            "float32(32, 16)", "float64(7,)", "int8(100,)"]
        for s in d2h:
            assert digest.start <= s.start <= s.end <= digest.end
        assert sum(s.duration for s in d2h) <= digest.duration
    assert len([s for s in spans if s.kind == "d2h"]) == 6
    # The digest is the one DCheck compares: tracing does not change it.
    from repro.core.check import content_digest
    assert store.directory.peek("W#0:cache").digest == \
        content_digest(value())


def test_put_size_sums_the_leaves_of_a_pytree():
    tr = Tracer()
    store = DStore(["node0"])
    store.attach_spans(tr)
    a, b = np.ones((8, 4), np.float32), np.zeros(10, np.int16)
    store.put("node0", "W#0:pair", {"a": a, "b": b})
    (put,) = [s for s in tr.finished() if s.kind == "put"]
    assert put.attrs["size"] == a.nbytes + b.nbytes == 148
    assert store.directory.peek("W#0:pair").size == 148
    assert store.resident_bytes() == 148
    # No sized leaf: an opaque value keeps its metadata-only size.
    store.put("node0", "W#0:words", ["a", "b"])
    assert store.directory.peek("W#0:words").size == 64


def test_admit_starts_at_the_due_time_and_latency_excludes_it():
    """The arrival loop launches instance 1 late (instance 0's payload
    blocks it): its ``admit`` span starts at its due time and lasts the
    launch lag, which ``InstanceStat.latency`` does not count."""
    wf = serving_chain(stages=2, exec_time=0.005, cold_start=0.0,
                       payload=64)
    tr = Tracer()
    srv = DServe(wf, n_nodes=2, spans=tr, cold_start=0.0)

    def payload(i):
        if i == 0:
            time.sleep(0.12)
        return {"request": b"r%d" % i}
    rep = srv.run([0.0, 0.01], payload)
    assert rep.failures == 0
    spans = tr.finished()
    admits = {s.trace: s for s in spans if s.kind == "admit"}
    reqs = {s.trace: s for s in spans if s.kind == "request"}
    s0, s1 = rep.stats
    a0, a1 = admits[s0.instance], admits[s1.instance]
    assert a0.parent is None and a1.parent is None
    assert a1.start - a0.start == pytest.approx(0.01, abs=1e-9)
    for stat, adm in ((s0, a0), (s1, a1)):
        assert adm.end <= reqs[stat.instance].start
        # Latency is the instance's own: admission to last output.
        assert stat.latency == pytest.approx(
            reqs[stat.instance].duration, abs=5e-3)
    assert a1.duration >= 0.1
    assert a0.duration < 0.1


def test_no_span_is_created_without_a_tracer(monkeypatch):
    made = []

    class Counted(Span):
        def __init__(self, *a, **kw):
            made.append(kw.get("kind"))
            super().__init__(*a, **kw)
    monkeypatch.setattr(obs, "Span", Counted)
    wf = serving_chain(stages=2, exec_time=0.005, cold_start=0.0,
                       payload=64)
    srv = DServe(wf, n_nodes=2, cold_start=0.0, max_per_node=1)
    rep = srv.run([0.0, 0.0], {"request": np.arange(16.0)})
    assert rep.failures == 0 and not made
    store = DStore(["node0"])
    store.put("node0", "k", {"a": np.ones(4)})
    store.get("node0", "k")
    assert not made
    # The same run with a tracer does make them, through the same class;
    # its first Puts digest nothing, and a co-write makes the digest spans.
    traced = DServe(wf, n_nodes=2, cold_start=0.0, spans=Tracer())
    traced.store.attach_tracer(None)    # DCheck's recorder digests
    traced.run([0.0], {"request": np.arange(16.0)})
    assert {"admit", "request", "invoke", "slot", "exec", "get", "wait",
            "put"} <= set(made)
    assert not {"digest", "d2h"} & set(made)
    store.attach_spans(Tracer())
    store.put("node0", "k", {"a": np.ones(4)})
    assert {"digest", "d2h"} <= set(made)


# ----------------------------------------------------------------------
# Exporters
# ----------------------------------------------------------------------

def test_jsonl_roundtrip(tmp_path):
    _, srv, spans, _ = _serve_traced(n=3)
    path = tmp_path / "spans.jsonl"
    plan_doc = plan_attribution(srv.plan)
    write_spans_jsonl(spans, str(path), plan=plan_doc,
                      meta={"bench": "test"})
    back, meta = read_spans_jsonl(str(path))
    assert meta["bench"] == "test"
    assert meta["plan"]["workflow"] == plan_doc["workflow"]
    assert len(back) == len(spans)
    for a, b in zip(sorted(spans, key=lambda s: s.seq),
                    sorted(back, key=lambda s: s.seq)):
        assert (a.id, a.parent, a.trace, a.name, a.kind) == \
               (b.id, b.parent, b.trace, b.name, b.kind)
        assert math.isclose(a.start, b.start) and math.isclose(a.end, b.end)
        assert a.attrs == b.attrs


def test_chrome_trace_shape():
    _, _, spans, _ = _serve_traced(n=3)
    doc = to_chrome_trace(spans)
    evs = doc["traceEvents"]
    assert evs
    complete = [e for e in evs if e["ph"] == "X"]
    instants = [e for e in evs if e["ph"] == "i"]
    metadata = [e for e in evs if e["ph"] == "M"]
    assert len(complete) + len(instants) + len(metadata) == len(evs)
    assert metadata, "process/thread name metadata expected"
    t0 = min(e["ts"] for e in complete)
    assert t0 >= 0, "timestamps must be t0-relative microseconds"
    for e in complete:
        assert e["dur"] >= 0
        assert e["pid"] and "tid" in e
    # One lane (tid) per function invocation within a request's pid.
    pids = {e["pid"] for e in complete}
    assert len(pids) == 3, "one pid per request trace"


# ----------------------------------------------------------------------
# Plan-vs-actual attribution (hand-built ground truth)
# ----------------------------------------------------------------------

def _mk(id_, parent, trace, name, kind, start, end, seq, **attrs):
    return Span(id=id_, parent=parent, trace=trace, name=name, kind=kind,
                start=start, seq=seq, end=end, end_seq=seq + 100,
                attrs=attrs)


def test_attribution_hand_built():
    """A request whose stage starts 50 ms later than planned, with a
    30 ms cold acquire, must show exactly those drifts."""
    plan_doc = {
        "workflow": "W", "critical_path": 0.200,
        "functions": {
            "a": {"est": 0.0, "eft": 0.100, "slack": 0.0,
                  "boot_at": -0.150, "cold_start": 0.15},
            "b": {"est": 0.100, "eft": 0.200, "slack": 0.0,
                  "boot_at": 0.050, "cold_start": 0.05},
        },
    }
    t = 1000.0  # arbitrary wall origin
    spans = [
        _mk(1, None, "W#0", "W#0", "request", t, t + 0.300, 1, ok=True),
        _mk(2, 1, "W#0", "a", "invoke", t + 0.000, t + 0.130, 2),
        _mk(3, 2, "W#0", "a", "acquire", t + 0.000, t + 0.030, 3,
            cold=True),
        _mk(4, 1, "W#0", "b", "invoke", t + 0.150, t + 0.300, 4),
        _mk(5, 4, "W#0", "b", "acquire", t + 0.150, t + 0.150, 5,
            cold=False),
        _mk(6, 4, "W#0", "W#0:k", "get", t + 0.150, t + 0.160, 6),
        _mk(7, None, "W#0", "W#0:k", "evict", t + 0.170, t + 0.170, 7),
    ]
    rep = attribute(spans, plan_doc)
    assert rep["requests"] == 1
    assert math.isclose(rep["latency"]["mean"], 0.300)
    assert math.isclose(rep["cp_drift"]["mean"], 0.100)
    rows = {r["function"]: r for r in rep["functions"]}
    assert math.isclose(rows["a"]["start_drift"]["mean"], 0.0,
                        abs_tol=1e-12)
    assert math.isclose(rows["a"]["finish_drift"]["mean"], 0.030)
    assert math.isclose(rows["a"]["acquire_wait"]["mean"], 0.030)
    assert rows["a"]["cold_rate"] == 1.0
    # b launched 50 ms late; prewarm fired 100 ms ahead of actual start.
    assert math.isclose(rows["b"]["start_drift"]["mean"], 0.050)
    assert math.isclose(rows["b"]["prewarm_lead"]["mean"], 0.100)
    assert rows["b"]["cold_rate"] == 0.0
    # Evict 10 ms after the key's last Get returned.
    assert rep["eviction_lag"]["n"] == 1
    assert math.isclose(rep["eviction_lag"]["mean"], 0.010)


def test_attribution_real_run_sane():
    rep, srv, spans, _ = _serve_traced()
    out = attribute(spans, plan_attribution(srv.plan))
    assert out["requests"] == 6
    assert {r["function"] for r in out["functions"]} == \
           set(srv.plan.functions)
    # Latency agg must reproduce the report's mean (separate clock
    # reads of the same interval, so a few ms of slack).
    assert math.isclose(out["latency"]["mean"],
                        sum(rep.latencies) / len(rep.latencies),
                        abs_tol=5e-3)


# ----------------------------------------------------------------------
# Registry dump == ServeReport (one source of truth)
# ----------------------------------------------------------------------

def test_registry_reproduces_serve_report():
    rep, srv, _, reg = _serve_traced()
    reg.collect()
    row = rep.row()
    assert int(reg.total("container_cold_starts")) >= row["cold_starts"]
    # The report counts the run's *delta*; this registry was created for
    # the run, so totals and deltas coincide.
    assert int(reg.total("container_cold_starts")) == row["cold_starts"]
    assert int(reg.total("container_prewarm_boots")) == row["prewarm_boots"]
    assert int(reg.total("container_warm_hits")) == row["warm_hits"]
    assert int(reg.total("container_prewarm_hits")) == row["prewarm_hits"]
    peaks = reg.label_values("dstore_peak_resident_bytes", "node")
    assert int(max(peaks.values())) == row["peak_resident_bytes"]
    assert rep.peak_resident_per_node == {
        n: int(v) for n, v in peaks.items()}
    # Serving aggregates published back into the registry.
    assert int(reg.total("serve_requests_total")) == row["n"]
    assert reg.histogram("serve_latency_seconds",
                         workflow=row["workflow"],
                         pattern=row["pattern"]).count == row["n"]


# ----------------------------------------------------------------------
# Simulator spans (virtual clock)
# ----------------------------------------------------------------------

def test_sim_spans_virtual_clock():
    from repro.core import make_workflow, run_open_loop

    tr = Tracer()
    res = run_open_loop("dflow", make_workflow("WC"), rate_per_min=20,
                        n_invocations=4, spans=tr)
    spans = tr.finished()
    check_well_formed(spans)
    reqs = sorted((s for s in spans if s.kind == "request"),
                  key=lambda s: s.seq)
    assert len(reqs) == 4
    # Durations are virtual seconds == the collected latencies.
    for s, lat in zip(reqs, res.latencies):
        assert math.isclose(s.duration, lat, rel_tol=1e-9), (s, lat)
    kinds = {s.kind for s in spans}
    assert {"request", "invoke", "acquire"} <= kinds


# ----------------------------------------------------------------------
# dflow-bench/v1 schema + regression gate
# ----------------------------------------------------------------------

def test_bench_metric_validation():
    with pytest.raises(ValueError):
        bench_metric("s", "m", 1.0, direction="sideways")
    row = bench_metric("s", "m", 1.0, "x", direction="lower",
                       tolerance=0.05)
    assert row["tolerance"] == 0.05
    doc = bench_doc("b", {"n": 1}, [row], extra={"k": 2})
    assert doc["schema"] == "dflow-bench/v1"
    assert doc["extra"] == {"k": 2}
    json.dumps(doc)  # must be JSON-serialisable


def test_compare_docs_gating():
    old = bench_doc("b", {}, [
        bench_metric("s", "p99", 1.0, "s", direction="lower"),
        bench_metric("s", "hits", 0.9, "", direction="higher"),
        bench_metric("s", "noise", 5.0, "s"),  # report-only
        bench_metric("s", "zero", 0, "", direction="lower"),
    ])
    # Within tolerance: pass.
    new = bench_doc("b", {}, [
        bench_metric("s", "p99", 1.09), bench_metric("s", "hits", 0.85),
        bench_metric("s", "noise", 50.0), bench_metric("s", "zero", 0),
    ])
    rows, failures = compare_docs(old, new)
    assert not failures
    assert [r["gated"] for r in rows] == [True, True, False, True]
    # Beyond tolerance in the bad direction: fail (both directions);
    # report-only metrics never gate; zero-valued gates fail on ANY rise.
    worse = bench_doc("b", {}, [
        bench_metric("s", "p99", 1.11), bench_metric("s", "hits", 0.80),
        bench_metric("s", "noise", 500.0), bench_metric("s", "zero", 1),
    ])
    rows, failures = compare_docs(old, worse)
    assert len(failures) == 3
    assert sum(r["regressed"] for r in rows) == 3
    # A committed metric missing from the fresh run is a failure.
    rows, failures = compare_docs(old, bench_doc("b", {}, []))
    assert len(failures) == 4


# ----------------------------------------------------------------------
# Differential corpus with observability attached
# ----------------------------------------------------------------------

def check_obs_enabled_differential(seed):
    """Full DScope instrumentation must never change engine results:
    byte-exact vs the oracle, and the recorded span tree is well-formed
    with every function's invoke span present exactly once."""
    oracle_wf = random_workflow(seed)
    ext = external_inputs(oracle_wf)
    expected = oracle_run(oracle_wf, ext)

    wf = random_workflow(seed)
    tr, reg = Tracer(), MetricsRegistry()
    engine = DFlowEngine(n_nodes=2, get_timeout=30.0, spans=tr)
    store = DStore(engine.nodes, engine.transport)
    store.attach_metrics(reg)
    rep = engine.start(wf, ext, store=store).wait()
    got = {k: bytes(v) for k, v in rep.outputs.items()}
    assert got == expected, f"seed {seed}"
    # wait() unblocks at the last mark_done; the executing thread's
    # invoke-span end (its finally block) can land a beat later.  Poll
    # until the snapshot is parent-complete.
    spans = tr.finished()
    for _ in range(500):
        ids = {s.id for s in spans}
        if all(s.parent is None or s.parent in ids for s in spans):
            break
        time.sleep(0.002)
        spans = tr.finished()
    check_well_formed(spans)
    invokes = [s.name for s in spans if s.kind == "invoke"
               and not s.attrs.get("duplicate")]
    assert sorted(invokes) == sorted(wf.functions), seed
    assert [s for s in spans if s.kind == "get"], seed


@pytest.mark.parametrize("seed", range(0, N_SEEDS, 16))
def test_obs_differential_quick(seed):
    check_obs_enabled_differential(seed)


@pytest.mark.slow
@pytest.mark.parametrize("seed", range(N_SEEDS))
def test_obs_differential_200(seed):
    check_obs_enabled_differential(seed)
