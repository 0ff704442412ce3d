"""Analysis-layer tests: HLO parsing, analytic FLOPs, roofline records."""

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.flops import model_flops, param_counts
from repro.analysis.hlo import (collective_summary, count_scan_trips,
                                hbm_bytes, matmul_flops, parse_collectives)
from repro.analysis.roofline import analyze_record
from repro.configs import get_config
from repro.launch.input_specs import Cell, is_skipped, live_cells


# -------------------------------------------------------------- HLO parsing
SYNTH_HLO = """
HloModule test

%body.1 (p: (s32[], f32[64,128])) -> (s32[], f32[64,128]) {
  %p = (s32[], f32[64,128]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[64,128]{1,0} get-tuple-element(%p), index=1
  %w = f32[128,128]{1,0} constant(0)
  %dot.5 = f32[64,128]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[64,128]{1,0} all-reduce(%dot.5), channel_id=1, replica_groups=[16,16]<=[256], to_apply=%add.1
  %one = s32[] constant(1)
  %next = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[64,128]) tuple(%next, %ar)
}

%cond.1 (p: (s32[], f32[64,128])) -> pred[] {
  %p = (s32[], f32[64,128]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(10)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: f32[64,128]) -> f32[64,128] {
  %a = f32[64,128]{1,0} parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[64,128]) tuple(%zero, %a)
  %loop = (s32[], f32[64,128]) while(%init), condition=%cond.1, body=%body.1
  %ag = f32[64,2048]{1,0} all-gather(%a), channel_id=2, replica_groups={{0,1,2,3}}, dimensions={1}
  ROOT %out = f32[64,128]{1,0} get-tuple-element(%loop), index=1
}
"""


def test_scan_trip_detection():
    trips = count_scan_trips(SYNTH_HLO)
    assert trips == {"body.1": 10}


def test_matmul_flops_loop_scaled():
    # dot: 2*64*128*128 flops, executed 10 times in the while body.
    assert matmul_flops(SYNTH_HLO) == pytest.approx(2 * 64 * 128 * 128 * 10)


def test_collective_wire_bytes():
    ops = parse_collectives(SYNTH_HLO)
    kinds = {o["kind"] for o in ops}
    assert kinds == {"all-reduce", "all-gather"}
    ar = next(o for o in ops if o["kind"] == "all-reduce")
    # ring all-reduce in a 16-group, x10 loop trips
    expect = 2 * (64 * 128 * 4) * 15 / 16 * 10
    assert ar["wire_bytes"] == pytest.approx(expect)
    ag = next(o for o in ops if o["kind"] == "all-gather")
    assert ag["group"] == 4
    assert ag["wire_bytes"] == pytest.approx(64 * 2048 * 4 * 3 / 4)


def test_hbm_bytes_counts_loop_body():
    b = hbm_bytes(SYNTH_HLO)
    assert b > 2 * 64 * 128 * 4 * 10     # at least the dot results x10


def test_collective_summary_totals():
    s = collective_summary(SYNTH_HLO)
    assert s["n_ops"] == 2
    assert s["total_bytes"] > 0


# -------------------------------------------------------------- FLOPs model
def test_param_counts_match_declared_params():
    """Analytic totals track the actual ArrayDecl sizes within ~2%."""
    from repro.analysis.flops import param_counts
    from repro.models import build_model
    from repro.models.param import param_count
    for arch in ("tinyllama-1.1b", "qwen3-moe-235b-a22b", "mamba2-370m",
                 "jamba-1.5-large-398b", "seamless-m4t-large-v2"):
        cfg = get_config(arch)
        declared = param_count(build_model(cfg).param_decls())
        analytic = param_counts(cfg)["total"]
        assert abs(declared - analytic) / declared < 0.05, arch


def test_known_scale_sanity():
    assert 14e9 < param_counts(get_config("starcoder2-15b"))["total"] < 17e9
    assert 0.9e9 < param_counts(get_config("tinyllama-1.1b"))["total"] < 1.3e9
    kimi = param_counts(get_config("kimi-k2-1t-a32b"))
    assert kimi["total"] > 0.8e12           # ~1T total
    assert kimi["active"] < 0.05 * kimi["total"]   # sparse activation


def test_model_flops_train_vs_prefill():
    cfg = get_config("tinyllama-1.1b")
    tr = model_flops(cfg, Cell("tinyllama-1.1b", "train_4k"))
    pf = model_flops(cfg, Cell("tinyllama-1.1b", "prefill_32k"))
    assert tr["matmul_6nd"] == pytest.approx(3 * 2 *
                                             tr["params_active"] *
                                             tr["tokens"], rel=1e-6)
    assert pf["matmul_6nd"] == pytest.approx(2 * pf["params_active"] *
                                             pf["tokens"], rel=1e-6)


# -------------------------------------------------------------- cells
def test_live_cells_and_skips():
    cells = live_cells()
    assert len(cells) == 35                      # 11*3 + 2 long_500k
    assert is_skipped("starcoder2-15b", "long_500k")
    assert not is_skipped("mamba2-370m", "long_500k")
    assert not is_skipped("jamba-1.5-large-398b", "long_500k")


def test_analyze_record_terms():
    rec = {
        "arch": "x", "shape": "train_4k", "mesh": "single",
        "kind": "train", "n_chips": 256,
        "dot_flops_per_device": 197e12,          # exactly 1s compute
        "hbm_bytes_per_device": 819e9 / 2,       # 0.5s memory
        "hlo_flops": 1.0, "hlo_bytes": 1.0,
        "collectives": {"total_bytes": 50e9 * 2},  # 2s collective
        "model_flops": {"model_flops": 197e12 * 256 * 0.5},
        "memory_analysis": {},
    }
    out = analyze_record(rec)
    assert out["compute_s"] == pytest.approx(1.0)
    assert out["memory_s"] == pytest.approx(0.5)
    assert out["collective_s"] == pytest.approx(2.0)
    assert out["dominant"] == "collective"
    assert out["roofline_fraction"] == pytest.approx(0.25)
