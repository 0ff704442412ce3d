"""Tests of the benchmark's yardstick: the trace reduction, each per-layer
metric reader, the traffic generator, the FLOP counts, the seeded weights
and the contract's shape of BENCHMARK.json.  CPU only; no chip, no
compile cache, no described topology."""

from __future__ import annotations

import json
import math
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import arrivals  # noqa: E402
import deploy  # noqa: E402
import flops  # noqa: E402
import harness  # noqa: E402
import trace_reduce as tr  # noqa: E402
from peaks import peaks_for  # noqa: E402
from stats import mean, percentile  # noqa: E402


def ev(name, start_ns, dur_ns):
    return NS(name=name, start_ns=start_ns, duration_ns=dur_ns)


def line(name, *events):
    return NS(name=name, events=list(events))


def synthetic_planes():
    """Host: sync at t=1000 ns, a body annotation 2000-9000 ns.  Device,
    whose clock reads 500 ns late: the sync step at 1500 ns, two module
    runs (prefill 2500-5500 ns, decode 6500-7500 ns); the line of ops is
    not read."""
    host = NS(name="/host:CPU", lines=[
        line("python3", ev(tr.SYNC, 1_000, 10), ev("body:prefill", 2_000,
                                                   7_000))])
    dev = NS(name="/device:TPU:0", lines=[
        line(tr.MODULES_LINE, ev("jit_bench_sync_step(3)", 1_500, 100),
             ev("jit_sc2_prefill_8_2(17)", 2_500, 3_000),
             ev("jit_sc2_decode_8_2(18)", 6_500, 1_000)),
        line("XLA Ops", ev("%fusion.1 = bf16[8,8]{1,0} fusion(...)", 2_500,
                           1_000))])
    return [host, dev]


def test_reduce_profile_aligns_clock_and_names_modules():
    t = tr.reduce_profile(synthetic_planes(), sync_mono_s=100.0)
    assert t.devices == 1
    # the host's sync at 1000 ns and the device's at 1500 ns are both
    # 100.0 s on the monotonic clock
    assert [m[0] for m in t.modules] == ["bench_sync_step", "sc2_prefill_8_2",
                                         "sc2_decode_8_2"]
    assert t.modules[1][1] == pytest.approx(100.0 + 1e-6)
    assert t.modules[1][2] - t.modules[1][1] == pytest.approx(3e-6)
    assert t.annotations == [("body:prefill", pytest.approx(100.0 + 1e-6),
                              pytest.approx(100.0 + 8e-6))]
    busy = t.busy()
    assert len(busy) == 3
    assert tr.total(busy) == pytest.approx(4.1e-6)


def test_reduce_profile_without_a_device_sync_uses_the_host_s():
    planes = synthetic_planes()
    planes[1].lines[0].events.pop(0)
    t = tr.reduce_profile(planes, sync_mono_s=100.0)
    assert t.modules[0][1] == pytest.approx(100.0 + 1.5e-6)


def test_reduce_profile_needs_the_sync_mark():
    planes = synthetic_planes()
    planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError, match="bench_sync"):
        tr.reduce_profile(planes, sync_mono_s=0.0)


def test_interval_algebra():
    assert tr.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert tr.intersect([(0, 2), (3, 5)], [(1, 4)]) == [(1, 2), (3, 4)]
    assert tr.gaps([(1, 2), (3, 4)], [(0, 5)]) == [(0, 1), (2, 3), (4, 5)]
    assert tr.gaps([], [(0, 1)]) == [(0, 1)]
    assert tr.total([(0, 1.5), (2, 2.5)]) == 2.0


def record(**kw):
    """A hand-built record: two requests of instance ``PD#0``/``PD#1``."""
    trace = tr.DeviceTrace(
        modules=[("p", 1.0, 1.2), ("d", 1.3, 1.31), ("d", 1.32, 1.33),
                 ("x", 1.4, 1.5)],
        annotations=[("body:decode", 1.31, 1.6)], devices=1)
    base = dict(
        requests=[{"due": 0.9, "launch": 0.95, "done": 1.5, "ok": True},
                  {"due": 1.0, "launch": 1.0, "done": 2.0, "ok": True}],
        bodies=[("PD#0", "prefill", 0.97, 1.2), ("PD#0", "decode", 1.26,
                                                  1.4),
                ("PD#1", "prefill", 1.05, 1.9)],
        spans=[("PD#0", "request", "PD#0", 0.95, 1.5),
               ("PD#0", "put", "PD#0:prompt", 0.951, 0.96),
               ("PD#0", "put", "PD#0:token", 1.2, 1.21),
               ("PD#0", "put", "PD#0:cache", 1.21, 1.25),
               ("PD#1", "request", "PD#1", 1.0, 2.0),
               ("PD#1", "put", "PD#1:prompt", 1.001, 1.03)],
        functions={"prefill": ["prompt", "gen"],
                   "decode": ["token", "cache", "gen"]},
        external={"prompt", "gen"}, instances={0: "PD#0", 1: "PD#1"},
        trace=trace, window=(1.0, 1.5),
        modules={"p": {"kind": "prefill", "flops": 197e12 * 0.1},
                 "d": {"kind": "decode", "flops": 197e12 * 0.001},
                 "x": {"kind": "other", "flops": 0.0}},
        peaks=peaks_for("TPU v5 lite"))
    base.update(kw)
    return harness.Record(**base)


def metric(name):
    return harness.load_module(HERE / "metrics" / f"{name}.py",
                               f"test_metric_{name}")


def test_metric_launch_lag():
    assert metric("client.launch_lag_p95_ms").read(record()) == \
        pytest.approx(1e3 * percentile([0.05, 0.0], 95.0))


def test_metric_dispatch_gap():
    # prefill of PD#0: 0.97 - 0.95 (request start); decode of PD#0:
    # 1.26 - 1.25 (cache Put ends last); prefill of PD#1: 1.05 - 1.0
    want = 1e3 * mean([0.02, 0.01, 0.05])
    assert metric("engine.dispatch_gap_ms").read(record()) == \
        pytest.approx(want)


def test_metric_put_per_request():
    want = 1e3 * mean([0.009 + 0.01 + 0.04, 0.029])
    assert metric("dstore.put_ms_per_req").read(record()) == \
        pytest.approx(want)


def test_metric_step_times_and_mfu():
    rec = record()
    assert metric("body.prefill_ms").read(rec) == pytest.approx(200.0)
    assert metric("body.decode_ms_per_token").read(rec) == \
        pytest.approx(10.0)
    # 0.102 s of peak-rate work in 0.22 s of prefill and decode
    assert metric("model.step_mfu").read(rec) == \
        pytest.approx(100 * 0.102 / 0.22)


def test_metric_idle_inflight():
    # in flight 0.95-2.0, window 1.0-1.5: 0.5 s; busy 0.2 + 0.02 + 0.1
    assert metric("device.idle_inflight_share").read(record()) == \
        pytest.approx(100 * (0.5 - 0.32) / 0.5)


def test_metrics_return_nothing_when_nothing_to_read():
    empty = record(requests=[], bodies=[], spans=[],
                   trace=tr.DeviceTrace())
    for name in ("client.launch_lag_p95_ms", "engine.dispatch_gap_ms",
                 "dstore.put_ms_per_req", "body.prefill_ms",
                 "body.decode_ms_per_token", "model.step_mfu",
                 "device.idle_inflight_share"):
        assert metric(name).read(empty) is None, name


def test_breakdown_labels_idle_gaps_by_body():
    out = harness.breakdown(record())
    assert out["device_ops"][0] == ["p", pytest.approx(0.2)]
    # idle while in flight: 1.2-1.3 (no body open), 1.33-1.4, 1.31-1.32
    assert out["idle_gaps"] == [["outside bodies", pytest.approx(0.1)],
                                ["body:decode", pytest.approx(0.07)],
                                ["body:decode", pytest.approx(0.01)]]


@pytest.mark.parametrize("seed", [0, 7, 2**33 + 5])
def test_traffic_is_the_same_work_in_another_order(seed):
    """Every seed sends the same schedule: the mix's lengths and the
    stratified gaps, shuffled by a fixed key; only the token ids follow the
    seed."""
    t = {"rate_per_s": 2.0, "prompt_tokens": [[1024, 0.5], [2048, 0.3],
                                              [3072, 0.2]],
         "output_tokens": [[16, 1.0]]}
    reqs = arrivals.make_requests(t, 45)
    assert reqs == arrivals.make_requests(t, 45)
    assert len(reqs) == 90
    assert reqs[0].arrival == 0.0
    gaps = np.diff([r.arrival for r in reqs])
    assert gaps.min() > 0
    assert [r.prompt_len for r in reqs].count(3072) == 18
    # shuffled: neither the lengths nor the gaps come in sorted order
    lens = [r.prompt_len for r in reqs]
    assert lens != sorted(lens) and list(gaps) != sorted(gaps)
    np.testing.assert_allclose(
        np.sort(gaps), np.sort(arrivals.exponential_gaps(2.0, 89)))
    assert reqs[-1].arrival == pytest.approx(44.5)
    toks = deploy.prompts(reqs, seed, 49152)
    assert [len(toks[r.index]) for r in reqs] == lens


def test_large_seeds_are_distinct():
    """Seeds that agree in their low 32 bits still draw other prompts."""
    t = {"rate_per_s": 1.0, "prompt_tokens": [[8, 0.5], [16, 0.5]],
         "output_tokens": [[4, 1.0]]}
    reqs = arrivals.make_requests(t, 20)
    a = deploy.prompts(reqs, 5, 1000)
    b = deploy.prompts(reqs, 5 + 2**32, 1000)
    assert any((a[i] != b[i]).any() for i in a)
    assert all((a[i] == deploy.prompts(reqs, 5, 1000)[i]).all() for i in a)


def test_counts_largest_remainder():
    assert arrivals.counts([[1, 0.5], [2, 0.3], [3, 0.2]], 7) == [4, 2, 1]
    assert sum(arrivals.counts([[1, 1], [2, 1], [3, 1]], 10)) == 10
    g = arrivals.exponential_gaps(4.0, 100)
    assert g.mean() == pytest.approx(0.25)


def test_percentile_and_mean():
    assert percentile([1, 2, 3, 4], 50) == 2.5
    assert percentile([5], 95) == 5
    assert math.isnan(percentile([], 50))
    with pytest.raises(ValueError):
        percentile([1], 101)
    assert mean([]) is None and mean([1, 3]) == 2


def test_dense_flops_by_hand():
    s = {"hidden_size": 4, "num_attention_heads": 2, "num_key_value_heads":
         1, "head_dim": 2, "intermediate_size": 8, "vocab_size": 10,
         "num_hidden_layers": 3}
    per_token = 2 * (4 * 4 + 2 * 4 * 2 + 4 * 4 + 2 * 4 * 8)
    # prefill of 3 tokens: 6 causal pairs; head once
    want = 3 * (per_token * 3 + 4 * 4 * 6) + 2 * 4 * 10
    assert flops.dense_step_flops(s, 3, 0) == want
    # decode after 5: one token sees 6 positions
    want = 3 * (per_token + 4 * 4 * 6) + 2 * 4 * 10
    assert flops.dense_step_flops(s, 1, 5) == want


def test_mamba_flops_by_hand():
    s = {"d_model": 4, "d_state": 2, "expand": 2, "headdim": 4, "d_conv": 2,
         "n_layer": 2, "vocab_size": 9, "pad_vocab_size_multiple": 4}
    proj = 2 * 4 * (16 + 4 + 2) + 2 * 8 * 4
    conv = 2 * 2 * (8 + 4)
    ssd = 4 * 8 * 2
    assert flops.mamba2_step_flops(s, 5) == \
        2 * (proj + conv + ssd) * 5 + 2 * 4 * 12


def test_peaks_refuse_unknown_devices():
    assert peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks_for("cpu")


def test_weights_drawn_alone_equal_the_program_s():
    import jax.numpy as jnp
    import weights

    spec = {"a": weights.Leaf((3, 4), ("normal", 0.5), layers=2),
            "b/c": weights.Leaf((5,), ("dt_bias", 1e-3, 0.1),
                                dtype="float32", layers=3),
            "d": weights.Leaf((6,), ("log_uniform", 1, 16))}
    seed = 2**32 + 9
    params = weights.make_params(seed, spec)
    assert params["a"].dtype == jnp.bfloat16
    draw = weights.drawer(spec, spec)
    root = weights.root_key(seed)
    for l in range(2):
        np.testing.assert_array_equal(
            np.asarray(params["a"][l], np.float32),
            np.asarray(draw(root, jnp.int32(l))["a"]))
    layer2 = draw(root, jnp.int32(2))
    np.testing.assert_array_equal(np.asarray(params["b"]["c"][2]),
                                  np.asarray(layer2["b/c"]))
    d = np.asarray(layer2["d"])
    np.testing.assert_array_equal(np.asarray(params["d"]), d)
    assert np.all((d >= 0) & (d <= math.log(16)))
    other = weights.make_params(9, spec)
    assert not np.array_equal(np.asarray(other["a"], np.float32),
                              np.asarray(params["a"], np.float32))


def test_logit_gaps_and_sample():
    logits = [np.array([[0.0, 2.0, 1.0], [3.0, 0.0, 0.5]])]
    np.testing.assert_allclose(harness.logit_gaps(logits, [[2, 0]]),
                               [1.0, 0.0])
    served = {0: [(np.zeros(10), 9, np.zeros(4, int))],
              1: [(np.zeros(50), 49, np.zeros(4, int))],
              2: [(np.zeros(20), 19, np.zeros(4, int))]}
    sample = harness.choose_sample(served, 8, seed=3)
    assert sample[0] == 1 and len(sample) == 2
    assert harness.choose_sample({}, 8, seed=3) == []


def test_logit_errors_and_judge():
    logits = [np.array([[0.0, 2.0, 1.0], [3.0, 0.0, 0.5]])]
    # the program's best logits 2.1 and 2.5 beside the reference's 1.0
    # and 3.0 for the tokens served there (2 and 0)
    np.testing.assert_allclose(
        harness.logit_errors(logits, [[2, 0]], [[2.1, 2.5]]), [1.1, 0.5])
    limits = {"widest_logit_error": 0.2}
    assert harness.judge({"widest_logit_error": 0.2, "other": 9}, limits)
    assert not harness.judge({"widest_logit_error": 0.21}, limits)
    assert not harness.judge({"widest_logit_error": None}, limits)
    assert not harness.judge({}, limits)


def test_benchmark_json_follows_the_contract():
    bench = json.loads((harness.CHECKOUT / "BENCHMARK.json").read_text())
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks/chip"]
    assert 1 <= bench["run_seconds"] <= 51
    names = {c["name"] for c in bench["configs"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert {"completed_rps", "setup_s"} <= set(e2e)
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    cells = {w["name"] for w in bench["workloads"]}
    for w in bench["workloads"]:
        assert w["config"] in names and w["chips"] == 1
        assert (HERE / "traffic" / f"{w['traffic']}.json").exists()
    for c in bench["configs"]:
        sizes = json.loads((harness.CHECKOUT / c["file"]).read_text())
        assert sizes["name"] == c["name"]
        assert set(c["reduced"]) <= set(sizes["reduced"])
        assert Path(harness.CHECKOUT / c["file"]).with_suffix(".py").exists()
    for m in bench["per_layer"]:
        assert e2e[m["moves"]]
        assert set(m["workloads"]) <= cells
        assert (HERE / "metrics" / f"{m['name']}.py").exists()


@pytest.mark.parametrize("kind,levels,rel", [("int8", 255, 1 / 254),
                                              ("fp8", None, 1 / 16)])
def test_lowp_rounding(kind, levels, rel):
    import jax.numpy as jnp
    from lowp import round_to

    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 64)),
                    jnp.float32)
    y = np.asarray(round_to(x, (1,), kind))
    top = np.abs(np.asarray(x)).max(1, keepdims=True)
    if levels:
        assert all(len(np.unique(row)) <= levels for row in y)
        assert np.all(np.abs(y - np.asarray(x)) <= top * rel + 1e-7)
    else:
        small = np.abs(np.asarray(x)) > top / 64
        err = np.abs(y - np.asarray(x))[small] / np.abs(np.asarray(x))[small]
        assert err.max() <= rel
    assert np.abs(y).max() <= top.max() * (1 + 1e-6)
    with pytest.raises(ValueError):
        round_to(x, (1,), "int4")
