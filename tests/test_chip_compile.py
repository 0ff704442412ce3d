"""Compile the chip's programs for a described TPU v5e, no chip attached.

The TPU compiler refuses what interpret mode accepts (unsupported
primitives, unaligned blocks, programs over the chip's memory).  Each test
compiles one kernel at the width of the model that uses it, or the
full-width tinyllama-1.1b serving steps on one described chip.

The topology is described only inside the module fixture: only one process
at a time may load the TPU library, so doing it at import would make
parallel test workers collect different tests.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd import ssd
from repro.launch.serve import greedy_steps
from repro.models import build_model
from repro.models.param import abstract_params

HBM_BYTES = 16e9          # one TPU v5e
SEQ = 2048


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # Programs compiled for a described chip cannot be read back from the
    # persistent cache here; keep them out of it.
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        from jax.experimental import topologies
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # noqa: BLE001 - any failure means no TPU
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)


def _kernel_call(name):
    """(fn, [(shape, dtype)]) for one kernel at its model's widths."""
    bf = jnp.bfloat16
    if name == "ssd":
        c = get_config("mamba2-370m")
        H, Pd, N = c.ssm_heads, c.ssm_head_dim, c.ssm_state
        return (lambda x, dt, A, B, C: ssd(x, dt, A, B, C, chunk=128),
                [((1, SEQ, H, Pd), bf), ((1, SEQ, H), bf),
                 ((H,), jnp.float32), ((1, SEQ, N), bf), ((1, SEQ, N), bf)])
    c = get_config("tinyllama-1.1b")
    H, Hk, D = c.n_heads, c.n_kv_heads, c.head_dim
    if name == "flash_attention":
        return (lambda q, k, v: flash_attention(q, k, v, causal=True),
                [((1, SEQ, H, D), bf), ((1, SEQ, Hk, D), bf),
                 ((1, SEQ, Hk, D), bf)])
    return (decode_attention,
            [((4, 1, H, D), bf), ((4, SEQ, Hk, D), bf),
             ((4, SEQ, Hk, D), bf), ((), jnp.int32)])


@pytest.mark.parametrize("name", ["flash_attention", "decode_attention",
                                  "ssd"])
def test_kernel_compiles_for_v5e(topo, name):
    one_chip = SingleDeviceSharding(topo.devices[0])
    fn, shapes = _kernel_call(name)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_full_width_tinyllama_step_fits_one_v5e(topo, step):
    """The steps chip_smoke.py serves: batch 4, 128 + 32 tokens."""
    batch, prompt, max_len = 4, 128, 160
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    rep = NamedSharding(mesh, P())
    cfg = get_config("tinyllama-1.1b")
    model = build_model(cfg)
    shaped = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep), t)
    params = shaped(abstract_params(model.param_decls()))
    cache = shaped(jax.eval_shape(lambda: model.init_cache(batch, max_len)))
    tokens = jax.ShapeDtypeStruct(
        (batch, prompt if step == "prefill" else 1), jnp.int32, sharding=rep)
    prefill, decode = greedy_steps(model, mesh)
    fn = prefill if step == "prefill" else decode
    compiled = fn.lower(params, tokens, cache).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < HBM_BYTES
    assert mem.argument_size_in_bytes > 2e9        # the 1.1B bf16 weights
