"""pd-granite-4.0-h-small-stage: disaggregated prefill -> decode on DFlow,
with one pipeline stage and one expert share of granite-4.0-h-small.

Request ``i`` stages its prompt and its output length; ``prefill`` runs
the program's compiled prefill step (``repro.launch.serve.greedy_steps``)
and Puts the next token (with its logit) and the filled hybrid cache: the
fp32 Mamba-2 state and convolution tails of the stage's 18 Mamba-2 layers
and the keys and values of its 2 attention layers; ``decode`` Gets both
and runs the program's compiled decode step until the request's output
length.  Each step also returns its best logit (``deploy.with_top_logit``),
so the check reads the program's logit of every served token.
One prefill and one decode program per (prompt, output) length pair of the
traffic mix, compiled before the window; the cache's keys and values are
sized to prompt plus output, as a serving system sizes them per request.
The decode program donates its cache, so each step writes its new cache
into the buffers of the one it was given, and the first step is given a
copy of the cache prefill Put (DStore still holds that one): a request's
steps are dispatched at once, and without donation each would hold a
cache of its own until it ran, 31 x 145 MB for an 8k prompt.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from deploy import check_layout, generate, named_jit, prompts, \
    with_top_logit
from flops_hybrid import step_bytes, step_flops
from weights import make_params

from repro.configs import get_config
from repro.core.dag import FunctionSpec, Workflow
from repro.launch.mesh import make_local_mesh
from repro.launch.serve import greedy_steps
from repro.models import build_model


def donating_jit(fn, name: str):
    """``deploy.named_jit`` whose third argument (the cache) is donated."""
    def step(*args):
        return fn(*args)
    step.__name__ = step.__qualname__ = name
    return jax.jit(step, donate_argnums=(2,))


def model_config(sizes: dict, layout: dict):
    """The program's model configuration at the file's sizes: this chip's
    stage (``num_hidden_layers``) and expert share (``num_local_experts``
    from ``first_expert``, of ``experts_published``)."""
    cfg = dataclasses.replace(
        get_config(sizes["program_arch"]),
        n_layers=sizes["num_hidden_layers"], d_model=sizes["hidden_size"],
        n_heads=sizes["num_attention_heads"],
        n_kv_heads=sizes["num_key_value_heads"], head_dim=layout["D"],
        d_ff=sizes["intermediate_size"], vocab=sizes["vocab_size"],
        n_experts=sizes["experts_published"],
        top_k=sizes["num_experts_per_tok"],
        experts_held=sizes["num_local_experts"],
        first_expert=sizes["first_expert"],
        shared_d_ff=sizes["shared_intermediate_size"],
        ssm_state=sizes["mamba_d_state"], ssm_conv=sizes["mamba_d_conv"],
        ssm_head_dim=sizes["mamba_d_head"], ssm_expand=sizes["mamba_expand"],
        hybrid_period=layout["period"],
        hybrid_attn_index=layout["attn_index"], hybrid_moe_every=1,
        use_rope=sizes["position_embedding_type"] != "nope",
        attention_multiplier=sizes["attention_multiplier"],
        embedding_multiplier=float(sizes["embedding_multiplier"]),
        residual_multiplier=sizes["residual_multiplier"],
        logits_scaling=float(sizes["logits_scaling"]),
        tie_embeddings=sizes["tie_word_embeddings"])
    if cfg.ssm_heads != sizes["mamba_n_heads"]:
        raise ValueError(f"{cfg.ssm_heads} Mamba-2 heads, the file says "
                         f"{sizes['mamba_n_heads']}")
    return cfg


class PrefillDecode:
    def __init__(self, sizes, traffic, requests, seed, reference):
        self.sizes, self.seed = sizes, seed
        self.requests = {r.index: r for r in requests}
        n = len(requests)
        pairs = sorted({(r.prompt_len, r.output_len) for r in requests})
        self.warmup = [dataclasses.replace(requests[0], index=n + j,
                                           prompt_len=p, output_len=g)
                       for j, (p, g) in enumerate(pairs)]
        for r in self.warmup:
            self.requests[r.index] = r
        self.pairs = pairs
        self.spec = reference.weight_spec(sizes)
        self.model = build_model(model_config(sizes,
                                              reference.layout(sizes)))
        check_layout(self.model.param_decls(), self.spec)
        self.modules = {}
        for p, g in pairs:
            self.modules[f"gr_prefill_{p}_{g}"] = {
                "kind": "prefill", "flops": step_flops(sizes, p, 0),
                "bytes": step_bytes(sizes, p, 0)}
            # decode step k (1-based) appends one token to p + k - 1
            mean_ctx = p + (g - 2) / 2
            self.modules[f"gr_decode_{p}_{g}"] = {
                "kind": "decode", "flops": step_flops(sizes, 1, mean_ctx),
                "bytes": step_bytes(sizes, 1, mean_ctx)}

    def setup(self) -> None:
        self.params = make_params(self.seed, self.spec)
        host = prompts(self.requests.values(), self.seed,
                       self.sizes["vocab_size"])
        self.host_prompts = host
        self.prompts = {i: jnp.asarray(t[None, :]) for i, t in host.items()}
        prefill, decode = greedy_steps(self.model, make_local_mesh(),
                                       donate=False)
        self.empty, self.prefill, self.decode = {}, {}, {}
        for p, g in self.pairs:
            empty = self.model.init_cache(1, p + g)
            self.empty[p, g] = empty
            self.prefill[p, g] = named_jit(with_top_logit(prefill),
                                           f"gr_prefill_{p}_{g}") \
                .lower(self.params, jnp.zeros((1, p), jnp.int32), empty) \
                .compile()
            self.decode[p, g] = donating_jit(with_top_logit(decode),
                                             f"gr_decode_{p}_{g}") \
                .lower(self.params, jnp.zeros((1, 1), jnp.int32), empty) \
                .compile()
        jax.block_until_ready((self.params, self.prompts, self.empty))

    def workflow(self, wrap) -> Workflow:
        params = self.params

        def prefill_fn(prompt, gen):
            key = (prompt.shape[1], gen)
            _, tok, cache, top = self.prefill[key](params, prompt,
                                                   self.empty[key])
            return {"token": (tok, top), "cache": cache}

        def decode_fn(token, cache, gen):
            key = (cache.kv.k.shape[2] - gen, gen)
            tok, top = token
            return {"tokens": generate(self.decode[key], params, tok, top,
                                       jax.tree.map(jnp.copy, cache), gen)}

        return Workflow("PD", [
            FunctionSpec("prefill", inputs=("prompt", "gen"),
                         outputs=("token", "cache"),
                         fn=wrap("prefill", prefill_fn), cold_start=0.0),
            FunctionSpec("decode", inputs=("token", "cache", "gen"),
                         outputs=("tokens",),
                         fn=wrap("decode", decode_fn), cold_start=0.0),
        ])

    def payload(self, i: int) -> dict:
        return {"prompt": self.prompts[i],
                "gen": self.requests[i].output_len}

    def served(self, i: int, outputs: dict):
        r = self.requests[i]
        out = outputs.get("tokens")
        if not isinstance(out, tuple) or len(out) != 2 \
                or any(tuple(a.shape) != (1, r.output_len) for a in out):
            return None
        toks, top = (np.asarray(a)[0] for a in out)
        if toks.min() < 0 or toks.max() >= self.sizes["vocab_size"]:
            return None
        prompt = self.host_prompts[i]
        return [(np.concatenate([prompt, toks[:-1]]), len(prompt) - 1, toks,
                 top)]

    def free(self) -> None:
        for name in ("params", "prompts", "empty", "prefill", "decode"):
            self.__dict__.pop(name, None)


def build(sizes, traffic, requests, seed, reference):
    return PrefillDecode(sizes, traffic, requests, seed, reference)
