"""Tests of the per-layer metrics read from the program's own DScope spans
(``exec``, ``slot``, ``wait``, ``digest``, ``d2h``, ``admit``) on
hand-built records, and that each reads nothing from a program that has
none of those spans.  CPU only."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import trace_reduce as tr  # noqa: E402
from stats import mean, percentile  # noqa: E402

NEW = ("engine.ready_to_exec_ms", "engine.wake_lag_ms",
       "engine.slot_wait_ms", "dstore.digest_ms_per_req",
       "dstore.d2h_ms_per_req", "client.admit_lag_p95_ms",
       "device.idle_inflight_digest_share")

# Two prefill -> decode requests.  PD#0: the request starts at 0.95, the
# prompt is staged, prefill waits 0.01 for its slot and runs 0.972-1.2,
# Puts its token and cache (1.20-1.25; the cache's digest 1.21-1.245, its
# copy to the host 1.21-1.23); decode's Gets wait from 1.10 for the token,
# then the cache, waking at 1.252; its slot is free at once; it runs from
# 1.26.
# PD#1 starts at 1.0; its prefill Gets the prompt after its Put and runs
# from 1.05; it Puts nothing more.
SPANS = [
    ("PD#0", "admit", "PD#0", 0.90, 0.95),
    ("PD#0", "request", "PD#0", 0.95, 1.5),
    ("PD#0", "put", "PD#0:prompt", 0.951, 0.96),
    ("PD#0", "digest", "PD#0:prompt", 0.952, 0.958),
    ("PD#0", "d2h", "int32(1024,)", 0.952, 0.954),
    ("PD#0", "wait", "PD#0:prompt", 0.955, 0.962),
    ("PD#0", "slot", "prefill", 0.962, 0.972),
    ("PD#0", "exec", "prefill", 0.972, 1.2),
    ("PD#0", "put", "PD#0:token", 1.2, 1.21),
    ("PD#0", "digest", "PD#0:token", 1.201, 1.205),
    ("PD#0", "put", "PD#0:cache", 1.21, 1.25),
    ("PD#0", "digest", "PD#0:cache", 1.21, 1.245),
    ("PD#0", "d2h", "bfloat16(2,1024)", 1.21, 1.23),
    ("PD#0", "wait", "PD#0:token", 1.1, 1.211),
    ("PD#0", "wait", "PD#0:cache", 1.211, 1.252),
    ("PD#0", "wait", "PD#0:gen", 1.252, 1.2521),     # no put: not read
    ("PD#0", "slot", "decode", 1.253, 1.253),
    ("PD#0", "exec", "decode", 1.26, 1.4),
    ("PD#1", "admit", "PD#1", 1.0, 1.0),
    ("PD#1", "request", "PD#1", 1.0, 2.0),
    ("PD#1", "put", "PD#1:prompt", 1.001, 1.03),
    ("PD#1", "digest", "PD#1:prompt", 1.001, 1.02),
    ("PD#1", "d2h", "int32(1024,)", 1.001, 1.005),
    ("PD#1", "wait", "PD#1:prompt", 1.031, 1.0311),  # put before: not read
    ("PD#1", "slot", "prefill", 1.03, 1.04),
    ("PD#1", "exec", "prefill", 1.05, 1.9),
]


def record(**kw):
    # Device busy 1.0-1.2 and 1.3-1.33 in the window 1.0-1.5, with
    # requests in flight throughout: idle 1.2-1.3 and 1.33-1.5.
    trace = tr.DeviceTrace(
        modules=[("p", 1.0, 1.2), ("d", 1.3, 1.33)], devices=1)
    base = dict(
        requests=[{"due": 0.9, "launch": 0.95, "done": 1.5, "ok": True},
                  {"due": 1.0, "launch": 1.0, "done": 2.0, "ok": True}],
        bodies=[("PD#0", "prefill", 0.9721, 1.2),
                ("PD#0", "decode", 1.2601, 1.4),
                ("PD#1", "prefill", 1.0501, 1.9)],
        spans=list(SPANS),
        functions={"prefill": ["prompt", "gen"],
                   "decode": ["token", "cache", "gen"]},
        external={"prompt", "gen"}, instances={0: "PD#0", 1: "PD#1"},
        trace=trace, window=(1.0, 1.5), modules={}, peaks=None)
    base.update(kw)
    return harness.Record(**base)


def metric(name):
    return harness.load_module(HERE / "metrics" / f"{name}.py",
                               f"test_span_metric_{name}")


def test_ready_to_exec_is_the_dispatch_gap_from_inside():
    # prefill of PD#0: 0.972 - 0.95 (all its inputs are external, so
    # from the request start); decode of PD#0: 1.26 - 1.25 (the cache's
    # Put ends last); prefill of PD#1: 1.05 - 1.0 (request start)
    rec = record()
    want = 1e3 * mean([0.972 - 0.95, 1.26 - 1.25, 1.05 - 1.0])
    assert metric("engine.ready_to_exec_ms").read(rec) == \
        pytest.approx(want)
    # Body stamps a tenth of a millisecond after each exec start.
    assert metric("engine.dispatch_gap_ms").read(rec) == \
        pytest.approx(want + 0.1)


def test_wake_lag_reads_waits_blocked_on_a_put():
    want = 1e3 * mean([0.962 - 0.96, 1.211 - 1.21, 1.252 - 1.25])
    assert metric("engine.wake_lag_ms").read(record()) == \
        pytest.approx(want)


def test_slot_wait_is_the_mean_slot_span():
    want = 1e3 * mean([0.01, 0.0, 0.01])
    assert metric("engine.slot_wait_ms").read(record()) == \
        pytest.approx(want)


@pytest.mark.parametrize("name,per_req", [
    ("dstore.digest_ms_per_req", [0.006 + 0.004 + 0.035, 0.019]),
    ("dstore.d2h_ms_per_req", [0.002 + 0.02, 0.004]),
])
def test_digest_and_d2h_per_request(name, per_req):
    assert metric(name).read(record()) == \
        pytest.approx(1e3 * mean(per_req))


def test_d2h_digest_put_are_nested_per_request():
    rec = record()
    d2h, digest, put = (metric(n).read(rec) for n in (
        "dstore.d2h_ms_per_req", "dstore.digest_ms_per_req",
        "dstore.put_ms_per_req"))
    assert d2h <= digest <= put


def test_per_request_means_count_only_completed_requests():
    rec = record(requests=[{"due": 0.9, "launch": 0.95, "done": 1.5,
                            "ok": True},
                           {"due": 1.0, "launch": 1.0, "done": None,
                            "ok": False}])
    assert metric("dstore.digest_ms_per_req").read(rec) == \
        pytest.approx(1e3 * 0.045)


def test_admit_lag_p95():
    assert metric("client.admit_lag_p95_ms").read(record()) == \
        pytest.approx(1e3 * percentile([0.05, 0.0], 95.0))


def test_idle_inflight_digest_share():
    # idle in flight: 1.2-1.3 and 1.33-1.5 (0.27 s); digests open in
    # them: the token's 1.201-1.205 and the cache's 1.21-1.245 (0.039 s)
    assert metric("device.idle_inflight_digest_share").read(record()) == \
        pytest.approx(100 * 0.039 / 0.27)
    # the same idle set as device.idle_inflight_share
    assert metric("device.idle_inflight_share").read(record()) == \
        pytest.approx(100 * 0.27 / 0.5)


def test_idle_inflight_digest_share_none_without_idle_or_device():
    busy = tr.DeviceTrace(modules=[("p", 0.9, 2.1)], devices=1)
    assert metric("device.idle_inflight_digest_share").read(
        record(trace=busy)) is None
    assert metric("device.idle_inflight_digest_share").read(
        record(trace=tr.DeviceTrace())) is None


OLD_KINDS = ("request", "put", "get", "invoke", "acquire", "chunk", "hop",
             "evict")


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_from_a_program_without_the_spans(name):
    """A program older than these spans has only the older kinds: each
    new metric reads nothing there, and does not raise."""
    old = record(spans=[s for s in SPANS if s[1] in OLD_KINDS])
    assert metric(name).read(old) is None


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_from_an_empty_window(name):
    empty = record(requests=[], bodies=[], spans=[],
                   trace=tr.DeviceTrace())
    assert metric(name).read(empty) is None
