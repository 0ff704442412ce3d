"""DStream tests: chunked Put/Get overlap, engine streaming equivalence,
mid-stream failure, simulator plane, and the dflow-stream system."""

import threading
import time

import pytest

from repro.core import SYSTEMS, SimConfig, make_system, run_open_loop
from repro.core.dag import FunctionSpec, Workflow
from repro.core.dscheduler import DFlowEngine
from repro.core.dstore import DStore, GetTimeout, Transport
from repro.core.sim import Env
from repro.core.simcluster import Cluster
from repro.core.stream import StreamBroken
from repro.core.workloads import make_workflow


# ----------------------------------------------------------------------
# Real (threaded) DStore streaming
# ----------------------------------------------------------------------

def test_put_get_stream_roundtrip():
    ds = DStore(["n0", "n1"])
    payload = bytes(range(256)) * 100           # 25600 B
    w = ds.put_stream("n0", "k", chunk_size=4096)
    w.write(payload)
    w.close()
    got = ds.get_stream("n1", "k", timeout=5).read_all()
    assert got == payload
    # chunk-granular receiver-driven pulls: one transfer per chunk
    assert ds.transport.transfers == 7          # ceil(25600 / 4096)
    # the monolithic twin serves plain Gets too
    assert ds.get("n1", "k", timeout=1) == payload


def test_get_stream_overlaps_in_progress_put_stream():
    """A consumer pulls chunk 0 while the producer is still emitting."""
    ds = DStore(["n0", "n1"])
    arrivals = []

    def consume():
        for chunk in ds.get_stream("n1", "s", timeout=10):
            arrivals.append((time.monotonic(), chunk))
    th = threading.Thread(target=consume)
    th.start()
    w = ds.put_stream("n0", "s", chunk_size=1024)
    for i in range(6):
        w.write(bytes([i]) * 1024)
        time.sleep(0.03)
    t_close = time.monotonic()
    w.close()
    th.join(10)
    chunks = [c for _, c in arrivals]
    assert chunks == [bytes([i]) * 1024 for i in range(6)]   # in order
    # first chunk observed well before the stream closed
    assert arrivals[0][0] < t_close


def test_stream_duplicate_writers_coalesce():
    """Duplicate producers (straggler re-issue; deterministic functions)
    co-write one stream: per-chunk publication is idempotent, the first
    closer seals it, and readers see exactly one copy of the payload."""
    ds = DStore(["n0", "n1"])
    w1 = ds.put_stream("n0", "k", chunk_size=8)
    w2 = ds.put_stream("n1", "k", chunk_size=8)
    payload = b"deadbeef" * 3
    w1.write(payload[:16])                   # original stalls after chunk 1
    w2.write(payload)                        # duplicate emits everything
    w2.close()
    assert ds.get_stream("n0", "k", timeout=2).read_all() == payload
    w1.write(payload[16:])                   # original wakes; no-ops
    w1.close()
    assert ds.get_stream("n1", "k", timeout=2).read_all() == payload


def test_engine_straggler_duplicate_completes_stalled_stream():
    """A streaming producer that stalls mid-emission gets a duplicate
    issued; the duplicate finishes the stream and the consumer completes
    instead of hanging until timeout."""
    calls = []

    def producer():
        calls.append(threading.get_ident())
        first = len(calls) == 1

        def gen():
            for i in range(4):
                if first and i == 1:
                    time.sleep(3.0)          # straggler stalls mid-stream
                yield bytes([i]) * 256
        return {"blob": gen()}

    wf = Workflow("strag", [
        FunctionSpec("prod", (), ("blob",), fn=producer, exec_time=0.02,
                     stream_outputs=("blob",), chunk_size=256),
        FunctionSpec("cons", ("blob",), ("digest",),
                     fn=lambda blob: {"digest": b"".join(blob)},
                     exec_time=0.01, stream_inputs=("blob",)),
    ])
    eng = DFlowEngine(n_nodes=2, straggler_factor=3.0, get_timeout=8.0)
    t0 = time.monotonic()
    rep = eng.run(wf)
    assert rep.outputs["digest"] == b"".join(bytes([i]) * 256
                                             for i in range(4))
    assert len(calls) >= 2                   # duplicate actually issued
    assert time.monotonic() - t0 < 3.0       # did not wait out the straggler


def test_get_stream_plain_fallback():
    """get_stream on a monolithically-Put key chunks the value locally."""
    ds = DStore(["n0"])
    ds.put("n0", "k", b"x" * 1000)
    assert ds.get_stream("n0", "k", timeout=2).read_all() == b"x" * 1000
    # non-bytes values arrive as a single-item stream
    ds.put("n0", "obj", {"a": 1})
    assert list(ds.get_stream("n0", "obj", timeout=2)) == [{"a": 1}]


def test_stream_node_failure_mid_stream_raises_clean_error():
    ds = DStore(["n0", "n1"])
    errors = []
    done = threading.Event()

    def consume():
        try:
            for _ in ds.get_stream("n1", "k", timeout=10):
                pass
        except StreamBroken as exc:
            errors.append(exc)
        done.set()
    th = threading.Thread(target=consume)
    th.start()
    w = ds.put_stream("n0", "k", chunk_size=16)
    w.write(b"a" * 48)                           # 3 chunks out, not closed
    time.sleep(0.05)
    ds.fail_node("n0")
    assert done.wait(5)
    assert errors and "before close" in str(errors[0])
    th.join(5)


def test_get_stream_timeout():
    ds = DStore(["n0"])
    with pytest.raises(GetTimeout):
        next(iter(ds.get_stream("n0", "never", timeout=0.05)))


def test_closed_stream_reclaimable_after_node_failure():
    """Losing a node after its stream closed must let a recovery rerun
    re-claim and re-publish the stream (regression: the stale claim used to
    silently discard the rerun's writes)."""
    ds = DStore(["n0", "n1"])
    w = ds.put_stream("n0", "k", chunk_size=8)
    w.write(b"payload!" * 4)
    w.close()
    lost = ds.fail_node("n0")
    assert "k" in lost                          # sole replica was on n0
    w2 = ds.put_stream("n1", "k", chunk_size=8)  # re-claim after eviction
    w2.write(b"payload!" * 4)
    w2.close()
    assert ds.get_stream("n1", "k", timeout=2).read_all() == b"payload!" * 4


def test_engine_recovers_stream_outputs_after_node_failure():
    """Incremental recovery re-runs a streaming producer whose node died
    after completion; the workflow still finishes with correct bytes."""
    runs = {"n": 0}

    def producer():
        runs["n"] += 1
        return {"blob": (bytes([i]) * 256 for i in range(4))}

    def consumer(blob):
        return {"digest": b"".join(blob)}

    wf = Workflow("rec", [
        FunctionSpec("prod", (), ("blob",), fn=producer, exec_time=0.01,
                     stream_outputs=("blob",), chunk_size=256),
        FunctionSpec("cons", ("blob",), ("digest",), fn=consumer,
                     exec_time=0.01, stream_inputs=("blob",)),
    ])
    eng = DFlowEngine(n_nodes=2, get_timeout=10.0)
    placement = eng.gs.assign(wf)
    rep = eng.run(wf, inject_failure=placement["prod"])
    assert rep.outputs["digest"] == b"".join(bytes([i]) * 256
                                             for i in range(4))


def test_concurrent_instances_mid_stream_failure_recovers_per_instance():
    """Serve-path fault handling: two namespaced instances stream through
    one shared DStore; the producer node dies *mid-stream*.  Incremental
    recovery re-runs only the lost producers (per instance), re-claims the
    aborted streams, and consumers retry instead of wedging — both
    instances finish with the exact bytes."""
    calls: dict[str, int] = {}
    lock = threading.Lock()

    def mk_producer(inst):
        def producer(seed):
            with lock:
                calls[inst] = calls.get(inst, 0) + 1

            def gen():
                for i in range(6):
                    time.sleep(0.02)          # still emitting when node dies
                    yield bytes(seed) * 128
            return {"blob": gen()}
        return producer

    def consumer(blob):
        return {"digest": b"".join(blob)}

    eng = DFlowEngine(n_nodes=2, get_timeout=10.0)
    store = DStore(eng.nodes, eng.transport)
    runs = []
    for i in range(2):
        wf = Workflow("mid", [
            FunctionSpec("prod", ("seed",), ("blob",),
                         fn=mk_producer(f"prod#{i}"), exec_time=0.12,
                         stream_outputs=("blob",), chunk_size=128),
            FunctionSpec("cons", ("blob",), ("digest",), fn=consumer,
                         exec_time=0.01, stream_inputs=("blob",)),
        ])
        runs.append(eng.start(wf, {"seed": b"%d" % i}, store=store,
                              instance=f"mid#{i}"))
    time.sleep(0.06)                          # both producers mid-emission
    prod_node = runs[0].placement["prod"]
    lost = store.fail_node(prod_node)
    for run in runs:
        run.recover(lost)
    for i, run in enumerate(runs):
        rep = run.wait()
        assert rep.outputs["digest"] == (b"%d" % i) * 6 * 128, i
    # each lost producer re-ran at least once; nothing ran wild
    assert all(1 <= calls[f"prod#{i}"] <= 3 for i in range(2)), calls


# ----------------------------------------------------------------------
# Threaded engine with streaming FunctionSpecs
# ----------------------------------------------------------------------

def _streaming_workflow(n_chunks=6, chunk=4096):
    def producer():
        def gen():
            for i in range(n_chunks):
                time.sleep(0.01)
                yield bytes([i]) * chunk
        return {"blob": gen()}

    def consumer(blob):
        return {"digest": b"".join(blob)}

    return Workflow("stream-wf", [
        FunctionSpec("prod", (), ("blob",), fn=producer, exec_time=0.06,
                     stream_outputs=("blob",), chunk_size=chunk,
                     output_sizes={"blob": n_chunks * chunk}),
        FunctionSpec("cons", ("blob",), ("digest",), fn=consumer,
                     exec_time=0.01, stream_inputs=("blob",)),
    ])


@pytest.mark.parametrize("pattern", ["dataflow", "controlflow"])
def test_engine_streaming_patterns_byte_identical(pattern):
    rep = DFlowEngine(n_nodes=2, pattern=pattern).run(_streaming_workflow())
    expected = b"".join(bytes([i]) * 4096 for i in range(6))
    assert rep.outputs["digest"] == expected


def test_engine_streaming_generator_error_propagates():
    def bad_producer():
        def gen():
            yield b"ok" * 100
            raise ValueError("mid-stream kaput")
        return {"blob": gen()}

    wf = Workflow("bad", [
        FunctionSpec("prod", (), ("blob",), fn=bad_producer,
                     stream_outputs=("blob",), chunk_size=64),
        FunctionSpec("cons", ("blob",), ("d",),
                     fn=lambda blob: {"d": b"".join(blob)},
                     stream_inputs=("blob",)),
    ])
    with pytest.raises(RuntimeError):
        DFlowEngine(n_nodes=2, get_timeout=5.0).run(wf)


def test_functionspec_stream_validation():
    with pytest.raises(ValueError, match="stream_inputs"):
        FunctionSpec("f", inputs=("a",), stream_inputs=("b",))
    with pytest.raises(ValueError, match="stream_outputs"):
        FunctionSpec("f", outputs=("x",), stream_outputs=("y",))


def test_parser_accepts_stream_fields():
    from repro.core.dag import parse_workflow
    wf = parse_workflow({
        "name": "p",
        "functions": {
            "a": {"inputs": ["src"], "outputs": ["mid"],
                  "stream_outputs": ["mid"], "chunk_size": "64KB"},
            "b": {"inputs": ["mid"], "outputs": ["out"],
                  "stream_inputs": ["mid"]},
        },
    })
    assert wf.functions["a"].stream_outputs == ("mid",)
    assert wf.functions["a"].chunk_size == 64 * 1024
    assert wf.functions["b"].stream_inputs == ("mid",)


# ----------------------------------------------------------------------
# Simulator: StreamingDStorePlane / dflow-stream
# ----------------------------------------------------------------------

def test_dflow_stream_registered():
    assert "dflow-stream" in SYSTEMS
    env = Env()
    cluster = Cluster(env, SimConfig())
    sys_ = make_system("dflow-stream", env, cluster, make_workflow("WC"))
    assert sys_.streaming and sys_.plane.name == "dstore-stream"


def test_sim_streaming_plane_chunks_overlap_production():
    """A consumer's chunk pulls start before the producer finishes."""
    env = Env()
    cluster = Cluster(env, SimConfig(bandwidth=25e6, stream_chunk=1e6))
    plane = make_system("dflow-stream", env, cluster,
                        make_workflow("WC")).plane
    plane.put_stream("node1", "k", 8e6, produce_time=1.0)
    got = plane.get_stream("node2", "k")
    env.run(until=30.0)
    assert got.triggered and got.value == pytest.approx(8e6)
    # first chunk transfer began while the producer was still emitting
    first_start = min(t0 for (_, _, _, t0, _, tag) in cluster.network.log
                      if tag.startswith("dstream:k"))
    assert first_start < 1.0


def test_dflow_stream_beats_dflow_on_large_outputs():
    """Acceptance: on a large-output workload under constrained bandwidth,
    dflow-stream beats monolithic dflow on simulated p99."""
    cfg = SimConfig(bandwidth=25e6)
    wf = make_workflow("WC-L")
    p99 = {}
    for system in ("dflow", "dflow-stream"):
        r = run_open_loop(system, wf, rate_per_min=6.0, n_invocations=4,
                          cfg=cfg)
        assert r.timeouts == 0
        p99[system] = r.p99
    assert p99["dflow-stream"] < p99["dflow"]


def test_real_engine_streaming_beats_monolithic_wall_time():
    """Acceptance: real-engine wall time improves with streaming when the
    producer emits incrementally and the consumer processes per chunk."""
    chunk, n = 64 * 1024, 10
    gap = 0.02

    def producer_stream():
        def gen():
            for i in range(n):
                time.sleep(gap)
                yield bytes([i]) * chunk
        return {"blob": gen()}

    def producer_mono():
        parts = []
        for i in range(n):
            time.sleep(gap)
            parts.append(bytes([i]) * chunk)
        return {"blob": b"".join(parts)}

    def consumer_stream(blob):
        total = 0
        for c in blob:
            time.sleep(gap / 2)
            total += len(c)
        return {"out": total}

    def consumer_mono(blob):
        time.sleep(gap / 2 * n)
        return {"out": len(blob)}

    def wall(prod, cons, streaming):
        extra = (dict(stream_outputs=("blob",), chunk_size=chunk)
                 if streaming else {})
        wf = Workflow("w", [
            FunctionSpec("p", (), ("blob",), fn=prod, exec_time=gap * n,
                         output_sizes={"blob": chunk * n}, **extra),
            FunctionSpec("c", ("blob",), ("out",), fn=cons,
                         exec_time=gap / 2 * n,
                         stream_inputs=("blob",) if streaming else ()),
        ])
        eng = DFlowEngine(n_nodes=2, transport=Transport(bandwidth=50e6))
        rep = eng.run(wf)
        assert rep.outputs["out"] == chunk * n
        return rep.wall_time

    wall(producer_stream, consumer_stream, True)        # warm-up (imports)
    t_stream = wall(producer_stream, consumer_stream, True)
    t_mono = wall(producer_mono, consumer_mono, False)
    assert t_stream < t_mono


# ----------------------------------------------------------------------
# DShard: mid-stream node loss heals from the producing shard only
# ----------------------------------------------------------------------

def test_sharded_mid_stream_failure_heals_from_producing_shard():
    """Sharded replay of the PR 2 recovery harness: two namespaced
    instances stream through one shared ShardedDStore, the producer node
    dies mid-stream (StreamBroken), and per-instance recovery re-runs only
    the lost producers.  Healing touches the producing shard only — a
    bystander shard's records are byte-for-byte untouched (no
    directory-wide scan) — and every post-failure Get still resolves in
    at most one hop (a failure re-home is not a misroute)."""
    from repro.core.check import TraceChecker, TraceRecorder
    from repro.core.obs import Tracer
    from repro.core.router import ShardedDStore

    calls: dict[str, int] = {}
    lock = threading.Lock()

    def mk_producer(inst):
        def producer(seed):
            with lock:
                calls[inst] = calls.get(inst, 0) + 1

            def gen():
                for i in range(6):
                    time.sleep(0.02)          # still emitting when node dies
                    yield bytes(seed) * 128
            return {"blob": gen()}
        return producer

    def consumer(blob):
        return {"digest": b"".join(blob)}

    eng = DFlowEngine(n_nodes=3, get_timeout=10.0, sharded=True)
    store = ShardedDStore(eng.nodes, eng.transport)
    rec = TraceRecorder()
    store.attach_spans(Tracer(recorder=rec))
    runs = []
    for i in range(2):
        wf = Workflow("mid", [
            FunctionSpec("prod", ("seed",), ("blob",),
                         fn=mk_producer(f"prod#{i}"), exec_time=0.12,
                         stream_outputs=("blob",), chunk_size=128),
            FunctionSpec("cons", ("blob",), ("digest",), fn=consumer,
                         exec_time=0.01, stream_inputs=("blob",)),
        ])
        runs.append(eng.start(wf, {"seed": b"%d" % i}, store=store,
                              instance=f"mid#{i}"))
    prod_node = runs[0].placement["prod"]
    used = set(runs[0].placement.values()) | set(runs[1].placement.values())
    bystander = next(n for n in eng.nodes if n not in used)
    store.put(bystander, "sentinel", b"innocent")     # homed on bystander

    time.sleep(0.06)                          # both producers mid-emission
    lost = store.fail_node(prod_node)
    bys_keys = sorted(store.shards[bystander].keys())
    for run in runs:
        run.recover(lost)
    for i, run in enumerate(runs):
        rep = run.wait()
        assert rep.outputs["digest"] == (b"%d" % i) * 6 * 128, i
    assert all(1 <= calls[f"prod#{i}"] <= 3 for i in range(2)), calls

    # Healing never scanned/mutated the bystander shard: same records,
    # same replica locations, bytes still served from it.
    assert sorted(store.shards[bystander].keys()) == bys_keys
    meta = store.shards[bystander].peek("sentinel")
    assert meta is not None and set(meta.locations) == {bystander}
    assert store.get(prod_node, "sentinel", timeout=5.0) == b"innocent"

    # 1-hop invariant held across the failure: no directory bounce, and
    # the full trace (incl. routing events) is checker-clean.
    assert store.hop_hist.get(2, 0) == 0, dict(store.hop_hist)
    TraceChecker().check_or_raise(rec.events())
