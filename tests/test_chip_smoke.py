"""chip_smoke.py's phases on the CPU at the reduced tinyllama config.

The script itself refuses anything but a TPU; these tests drive its serve
and dflow phase functions directly, so the checks it makes on the chip
(teacher-forced consistency, DServe tokens == sequential reference) are
exercised on every run.
"""

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_local_mesh
from repro.launch.serve import greedy_steps

ROOT = Path(__file__).resolve().parents[1]
PROMPT, GEN = 32, 8


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def served(cs):
    return cs.serve_phase(reduced=True, prompt_len=PROMPT, gen_tokens=GEN)


def test_serve_phase_runs_reduced_tinyllama(served):
    assert served["model"].cfg.name == "tinyllama-1.1b-reduced"
    assert served["tokens"].shape == (4, GEN)
    assert served["logits"].shape == (4, GEN, served["model"].cfg.vocab)
    assert served["compile_s"] > 0 and served["decode_ms_per_token"] > 0


@pytest.mark.parametrize("fault", ["zeroed_kv", "shifted_position"])
def test_teacher_forced_check_rejects_a_wrong_cache(cs, served, fault):
    """Decoding from a cache whose contents or position are wrong must
    fail the check that the correct decode path passes."""
    model, params = served["model"], served["params"]
    prompts = served["prompts"]
    prefill, decode = greedy_steps(model, make_local_mesh(), donate=False)
    logits, tok, cache = prefill(params, prompts,
                                 model.init_cache(4, PROMPT + GEN))
    kv = cache.kv
    if fault == "zeroed_kv":
        kv = kv._replace(k=jnp.zeros_like(kv.k), v=jnp.zeros_like(kv.v))
    else:
        kv = kv._replace(length=kv.length + 1)
    cache = cache._replace(kv=kv)
    all_logits, toks = [logits], [tok]
    for _ in range(GEN - 1):
        logits, tok, cache = decode(params, tok, cache)
        all_logits.append(logits)
        toks.append(tok)
    agree, corr = cs.teacher_forced_check(
        model, params, prompts, np.asarray(jnp.concatenate(toks, axis=1)),
        jnp.concatenate(all_logits, axis=1))
    assert agree < cs.MIN_AGREEMENT or corr < cs.MIN_CORRELATION, \
        (agree, corr)


def test_dflow_tokens_equal_sequential_reference(cs, served):
    out = cs.dflow_phase(served["model"], served["params"], requests=8,
                         prompt_len=PROMPT, gen_tokens=GEN, rate=50.0)
    assert set(out["served"]) == {"dataflow", "controlflow"}
    for pattern, tokens in out["served"].items():
        row = out["rows"][pattern]
        assert row["n"] == 8 and row["failures"] == 0 and row["shed"] == 0
        for got, ref in zip(tokens, out["reference"]):
            assert got.shape == (1, GEN)
            np.testing.assert_array_equal(got, ref)
    assert out["cache_bytes"] > 0 and out["put_s"] > 0


def test_main_refuses_a_cpu_platform(cs, capsys):
    assert jax.devices()[0].platform == "cpu"
    assert cs.main() == 1
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs a TPU" in out.err
