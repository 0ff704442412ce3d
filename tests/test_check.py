"""DCheck dynamic half: trace recording + invariant checking.

Two layers of evidence per invariant class:

* **hand-built traces** pin the checker's judgment precisely (a trace
  that violates exactly one invariant yields exactly that violation);
* **live seeded violations** break the real DStore in a way its public
  API forbids (bypassing Put, evicting under an in-flight remote pull,
  lying to the stream directory) and assert the recorded trace convicts.

Plus the negative contract: real engine/serve runs — including under the
schedule-perturbing stress mode — produce clean traces.

The module is marked ``notracecheck``: it seeds violations on purpose, so
the conftest's global DFLOW_TRACE_CHECK teardown must not re-judge them.
"""

import collections
import threading
import time

import pytest

from repro.core.check import (TraceChecker, TraceEvent, TraceRecorder,
                              content_digest)
from repro.core.dstore import DStore, Transport
from repro.core.obs import Tracer

pytestmark = pytest.mark.notracecheck


def ev(clock, kind, key="", node="", **kw):
    return TraceEvent(clock, kind, key, node, **kw)


def violations(events, invariant=None):
    out = TraceChecker().check(events)
    if invariant is not None:
        out = [v for v in out if v.invariant == invariant]
    return out


D1 = content_digest(b"one")
D2 = content_digest(b"two")


# ----------------------------------------------------------------------
# content_digest
# ----------------------------------------------------------------------

def test_digest_stable_across_representations():
    assert content_digest(b"abc") == content_digest(bytearray(b"abc"))
    assert content_digest(b"abc") == content_digest(memoryview(b"abc"))
    assert content_digest(b"abc") != content_digest(b"abd")
    assert content_digest({"v": 7}) == content_digest({"v": 7})
    assert content_digest([1, "a"]) == content_digest([1, "a"])


def test_digest_opaque_is_none():
    class Opaque:
        pass

    assert content_digest(Opaque()) is None
    # A list containing an opaque element is opaque as a whole.
    assert content_digest([1, Opaque()]) is None


def test_digest_arrays():
    np = pytest.importorskip("numpy")
    a = np.arange(6, dtype=np.int32)
    assert content_digest(a) == content_digest(a.copy())
    assert content_digest(a) != content_digest(a.reshape(2, 3))
    assert content_digest(a) != content_digest(a.astype(np.int64))


# ----------------------------------------------------------------------
# Hand-built traces: one violation class each.
# ----------------------------------------------------------------------

def test_clean_trace_passes():
    trace = [
        ev(1, "put", "k", "n0", digest=D1),
        ev(2, "get_block", "k", "n1"),
        ev(3, "replica", "k", "n1", digest=D1),
        ev(4, "get_return", "k", "n1", digest=D1),
        ev(5, "evict", "k"),
    ]
    assert violations(trace) == []


def test_ordering_get_before_any_publish():
    trace = [
        ev(1, "get_block", "k", "n1"),
        ev(2, "get_return", "k", "n1", digest=D1),
        ev(3, "put", "k", "n0", digest=D1),
    ]
    (v,) = violations(trace)
    assert v.invariant == "ordering"


def test_ordering_stale_read_wrong_bytes():
    trace = [
        ev(1, "put", "k", "n0", digest=D1),
        ev(2, "get_block", "k", "n1"),
        ev(3, "get_return", "k", "n1", digest=D2),
    ]
    (v,) = violations(trace)
    assert v.invariant == "ordering" and "stale" in v.message


def test_immutability_divergent_writes():
    trace = [
        ev(1, "put", "k", "n0", digest=D1),
        ev(2, "put", "k", "n1", digest=D2),
    ]
    (v,) = violations(trace)
    assert v.invariant == "immutability"


def test_immutability_identical_cowrite_clean():
    trace = [
        ev(1, "put", "k", "n0", digest=D1),
        ev(2, "put", "k", "n1", digest=D1),
        ev(3, "put", "k", "n2", digest=None),   # opaque: no judgment
    ]
    assert violations(trace) == []


def test_eviction_with_inflight_reader():
    trace = [
        ev(1, "put", "k", "n0", digest=D1),
        ev(2, "get_block", "k", "n1"),
        ev(3, "evict", "k"),
        ev(4, "get_return", "k", "n1", digest=D1),
    ]
    vs = violations(trace, "eviction")
    assert len(vs) == 1 and "in flight" in vs[0].message


def test_eviction_after_reader_finished_clean():
    trace = [
        ev(1, "put", "k", "n0", digest=D1),
        ev(2, "get_block", "k", "n1"),
        ev(3, "get_return", "k", "n1", digest=D1),
        ev(4, "evict", "k"),
    ]
    assert violations(trace) == []


def test_chunk_sequence_missing_chunk():
    trace = [
        ev(1, "put_chunk", "s", "n0", idx=0, digest=D1),
        ev(2, "stream_close", "s", size=2),
    ]
    (v,) = violations(trace)
    assert v.invariant == "chunk_sequence" and "never published" in v.message


def test_chunk_sequence_chunk_beyond_close():
    trace = [
        ev(1, "put_chunk", "s", "n0", idx=0, digest=D1),
        ev(2, "put_chunk", "s", "n0", idx=1, digest=D1),
        ev(3, "put_chunk", "s", "n0", idx=5, digest=D1),
        ev(4, "stream_close", "s", size=2),
    ]
    vs = violations(trace, "chunk_sequence")
    assert len(vs) == 1 and "[5]" in str(vs[0].message)


def test_chunk_sequence_divergent_totals():
    trace = [
        ev(1, "put_chunk", "s", "n0", idx=0, digest=D1),
        ev(2, "stream_close", "s", size=1),
        ev(3, "stream_close", "s", size=3),
    ]
    vs = violations(trace, "chunk_sequence")
    assert len(vs) == 1 and "divergent totals" in vs[0].message


def test_chunk_sequence_divergent_cowrite():
    trace = [
        ev(1, "put_chunk", "s", "n0", idx=0, digest=D1),
        ev(2, "put_chunk", "s", "n1", idx=0, digest=D2),
        ev(3, "stream_close", "s", size=1),
    ]
    vs = violations(trace, "chunk_sequence")
    assert len(vs) == 1 and "divergent bytes" in vs[0].message


def test_chunk_sequence_leaked_stream():
    trace = [ev(1, "put_chunk", "s", "n0", idx=0, digest=D1)]
    vs = violations(trace, "chunk_sequence")
    assert len(vs) == 1 and "never" in vs[0].message


def test_key_reuse_after_evict_is_clean():
    # Serving restarts instance numbering per run(): after an eviction
    # the same key name legitimately carries different content.
    trace = [
        ev(1, "put", "k", "n0", digest=D1),
        ev(2, "evict", "k"),
        ev(3, "put", "k", "n0", digest=D2),
        ev(4, "get_block", "k", "n1"),
        ev(5, "replica", "k", "n1", digest=D2),
        ev(6, "get_return", "k", "n1", digest=D2),
    ]
    assert violations(trace) == []


def test_stream_reuse_after_evict_judged_per_generation():
    trace = [
        ev(1, "put_chunk", "s", "n0", idx=0, digest=D1),
        ev(2, "stream_close", "s", size=1),
        ev(3, "evict", "s"),
        ev(4, "put_chunk", "s", "n0", idx=0, digest=D2),
        ev(5, "stream_close", "s", size=2),    # generation 2 lies
    ]
    vs = violations(trace, "chunk_sequence")
    assert len(vs) == 1 and "never published" in vs[0].message


def test_aborted_stream_not_judged():
    trace = [
        ev(1, "put_chunk", "s", "n0", idx=0, digest=D1),
        ev(2, "stream_abort", "s", "n0"),
    ]
    assert violations(trace) == []


# ----------------------------------------------------------------------
# Live seeded violations against the real DStore.
# ----------------------------------------------------------------------

def traced_store(nodes, stress=None, transport=None):
    ds = DStore(nodes, transport)
    rec = TraceRecorder(stress=stress)
    ds.attach_spans(Tracer(recorder=rec))
    return ds, rec


def test_live_ordering_violation_backdoor_write():
    # Bytes smuggled into a LocalStore behind Put's back: the Get's
    # fast path returns them although no availability event exists.
    ds, rec = traced_store(["n0"])
    ds.stores["n0"].write("k", b"smuggled")
    assert ds.get("n0", "k") == b"smuggled"
    vs = violations(rec.events(), "ordering")
    assert len(vs) == 1


def test_live_eviction_violation_under_inflight_pull():
    # A slow remote pull is mid-flight when the instance is evicted:
    # exactly the reader-starvation hazard eviction safety forbids.
    ds, rec = traced_store(["n0", "n1"],
                           transport=Transport(bandwidth=4096.0))
    ds.put("n0", "i1:k", b"x" * 4096)          # ~1 s pull at 4 KB/s
    got = []
    t = threading.Thread(target=lambda: got.append(ds.get("n1", "i1:k")))
    t.start()
    time.sleep(0.3)                            # reader inside transport.move
    ds.evict_instance("i1:")
    t.join()
    vs = violations(rec.events(), "eviction")
    assert len(vs) == 1 and "i1:k" in vs[0].message


def test_live_chunk_sequence_violation_lying_close():
    # A producer that closes the stream directory at a total it never
    # published (the engine never does this; the directory trusts it).
    ds, rec = traced_store(["n0"])
    ds.streams.claim("s", "n0")
    ds.put_chunk("n0", "s", 0, b"c0")
    ds.streams.close("s", 3)
    vs = violations(rec.events(), "chunk_sequence")
    assert len(vs) == 1 and "never published" in vs[0].message


def test_live_immutability_enforced_and_traceable():
    # The directory rejects a divergent co-write outright; a trace that
    # somehow contains one (recorder events injected here) is convicted
    # by the same digest evidence.
    from repro.core.dstore import ImmutabilityError

    ds, rec = traced_store(["n0", "n1"])
    ds.put("n0", "k", b"one")
    with pytest.raises(ImmutabilityError):
        ds.put("n1", "k", b"two")
    rec.record("put", "k", "n1", digest=content_digest(b"two"))
    vs = violations(rec.events(), "immutability")
    assert len(vs) == 1


# ----------------------------------------------------------------------
# Negative contract: real runs trace clean (stress mode on).
# ----------------------------------------------------------------------

def _engine_run_traced(seed, stress):
    from strategies import external_inputs, oracle_run, random_workflow

    from repro.core.dscheduler import DFlowEngine

    wf = random_workflow(seed)
    eng = DFlowEngine(n_nodes=3)
    ds = DStore(eng.nodes, eng.transport)
    rec = TraceRecorder(stress=stress)
    ds.attach_spans(Tracer(recorder=rec))
    rep = eng.start(wf, external_inputs(wf), store=ds).wait()
    assert rep.outputs == oracle_run(wf, external_inputs(wf))
    return rec


@pytest.mark.parametrize("seed", [3, 11, 42])
def test_engine_runs_trace_clean_under_stress(seed):
    rec = _engine_run_traced(seed, stress=seed)
    assert len(rec) > 0
    TraceChecker().check_or_raise(rec.events())


def test_serve_run_traces_clean_under_stress():
    from repro.core.serve import DServe
    from repro.core.workloads import BENCHMARKS

    rec = TraceRecorder(stress=7)
    srv = DServe(BENCHMARKS["Srv"](), n_nodes=2, cold_start=0.01,
                 spans=Tracer(recorder=rec))
    rep = srv.run([0.0, 0.05, 0.1, 0.15],
                  inputs=lambda i: {"request": b"r%d" % i})
    assert rep.failures == 0 and len(rep.stats) == 4
    assert len(rec) > 0
    TraceChecker().check_or_raise(rec.events())


def test_recorder_thread_safety_and_clocks():
    rec = TraceRecorder()
    threads = [threading.Thread(
        target=lambda i=i: [rec.record("put", f"k{i}.{j}", "n0")
                            for j in range(50)]) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    events = rec.events()
    assert len(events) == 400
    assert sorted(e.clock for e in events) == list(range(1, 401))


def test_stress_mode_is_deterministically_seeded():
    a = TraceRecorder(stress=5)
    b = TraceRecorder(stress=5)
    for _ in range(20):
        a.record("put", "k", "n")
        b.record("put", "k", "n")
    assert a._stress == b._stress


# ----------------------------------------------------------------------
# One hook: the recorder rides on the DScope Tracer.
# ----------------------------------------------------------------------

def _bound_wordcount(shards=4):
    """The built-in WordCount with a deterministic body per function."""
    import dataclasses
    import hashlib

    from repro.core.dag import Workflow
    from repro.core.workloads import wordcount

    def body(outs):
        def fn(**kw):
            h = hashlib.sha256()
            for k in sorted(kw):
                h.update(k.encode() + bytes(kw[k]))
            return {o: h.digest() + o.encode() for o in outs}
        return fn

    wc = wordcount(shards)
    return Workflow(wc.name, [
        dataclasses.replace(f, fn=body(f.outputs), exec_time=0.0,
                            cold_start=0.0)
        for f in wc.functions.values()], {"corpus": 64})


def _serve_wordcount(spans, sharded):
    from repro.core.serve import DServe

    # Control flow: every Get finds its key published, so the trace is
    # the same from run to run.
    srv = DServe(_bound_wordcount(), n_nodes=2, pattern="controlflow",
                 cold_start=0.0, sharded=sharded, spans=spans)
    rep = srv.run([0.0], inputs={"corpus": b"the quick brown fox"})
    assert rep.failures == 0
    # run() returns once the sink is out; the last container release and
    # span ends land a beat later.
    for _ in range(500):
        done = spans.finished()
        ids = {s.id for s in done}
        rec = spans.recorder
        released = rec is None or (
            sum(e.kind == "container_release" for e in rec.events()) ==
            sum(e.kind == "cold_boot" for e in rec.events()))
        if released and all(s.parent is None or s.parent in ids
                            for s in done):
            break
        time.sleep(0.002)
    by_id = {s.id: s for s in done}
    return collections.Counter(
        (s.kind, s.name, by_id[s.parent].kind if s.parent else None)
        for s in done)


@pytest.mark.parametrize("sharded", [False, True],
                         ids=["DStore", "ShardedDStore"])
def test_recorder_on_the_tracer_leaves_spans_and_events_as_they_were(
        sharded):
    rec = TraceRecorder()
    tr = Tracer(recorder=rec)
    spans = _serve_wordcount(tr, sharded)
    # (a) The recorder changes no span: kind, name and parent kind.
    assert spans == _serve_wordcount(Tracer(), sharded)
    # (b) DCheck's events per key, as a DServe(tracer=rec) run recorded
    # them before the recorder moved onto the tracer.
    kinds: dict[str, collections.Counter] = collections.defaultdict(
        collections.Counter)
    for e in rec.events():
        kinds[e.key][e.kind] += 1
    data = ["corpus", "result"] + [f"{p}.{i}" for p in ("shard", "wc")
                                   for i in range(4)]
    images = ["split", "merge"] + [f"count.{i}" for i in range(4)]
    expected = {f"WC#0:{k}": {"put": 1, "get_block": 1, "get_return": 1,
                              "evict": 1} for k in data}
    expected.update({f"WC/{f}": {"cold_boot": 1, "container_release": 1}
                     for f in images})
    assert {k: dict(v) for k, v in kinds.items()} == expected
    # (c) The trace is clean.
    TraceChecker().check_or_raise(rec.events())
    # One clock: span ticks and event stamps never collide.
    ticks = [e.clock for e in rec.events()]
    for s in tr.finished():
        ticks += [s.seq] if s.end_seq == s.seq else [s.seq, s.end_seq]
    assert len(ticks) == len(set(ticks))
