"""wc-mamba2-370m: FaaSFlow WordCount (split -> map.i -> merge) on DFlow,
with mamba2-370m bodies.

Request ``i`` stages one document.  ``split`` cuts it into ``fanout``
equal chunks (a compiled slice on the device); ``map.j`` prefills chunk
``j`` with the program's compiled prefill step
(``repro.launch.serve.greedy_steps``) and generates ``map_tokens`` tokens
with its decode step; ``merge`` Gets the maps' tokens, joins them in map
order, prefills them and generates the request's output length.  It
returns the joined tokens beside its own, so the check sees what each map
produced as merge received it.  Each step also returns its best logit
(``deploy.with_top_logit``), which travels beside the tokens.  Only token
ids and their logits cross DStore; each body's SSM state stays inside it.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from deploy import check_layout, generate, named_jit, prompts, \
    with_top_logit
from flops import mamba2_step_flops
from weights import make_params

from repro.configs import get_config
from repro.core.dag import FunctionSpec, Workflow
from repro.launch.mesh import make_local_mesh
from repro.launch.serve import greedy_steps
from repro.models import build_model


def model_config(sizes: dict, vocab: int):
    """The program's model configuration at the file's sizes."""
    return dataclasses.replace(
        get_config(sizes["program_arch"]),
        n_layers=sizes["n_layer"], d_model=sizes["d_model"], vocab=vocab,
        ssm_state=sizes["d_state"], ssm_conv=sizes["d_conv"],
        ssm_head_dim=sizes["headdim"], ssm_expand=sizes["expand"],
        q_chunk=sizes["chunk_size"], tie_embeddings=True)


class WordCount:
    def __init__(self, sizes, traffic, requests, seed, reference):
        self.sizes, self.seed = sizes, seed
        self.fanout, self.map_tokens = sizes["fanout"], sizes["map_tokens"]
        self.requests = {r.index: r for r in requests}
        n = len(requests)
        shapes = sorted({(r.prompt_len, r.output_len) for r in requests})
        self.warmup = [dataclasses.replace(requests[0], index=n + j,
                                           prompt_len=d, output_len=g)
                       for j, (d, g) in enumerate(shapes)]
        for r in self.warmup:
            self.requests[r.index] = r
        for d, _ in shapes:
            if d % self.fanout:
                raise ValueError(f"a {d}-token document does not split "
                                 f"into {self.fanout} equal chunks")
        self.docs = sorted({d for d, _ in shapes})
        self.spec = reference.weight_spec(sizes)
        self.vocab = reference.padded_vocab(sizes)
        self.model = build_model(model_config(sizes, self.vocab))
        check_layout(self.model.param_decls(), self.spec)
        merge_len = self.fanout * self.map_tokens
        self.prompt_lens = sorted({d // self.fanout for d in self.docs}
                                  | {merge_len})
        self.modules = {f"m2_split_{d}": {"kind": "other", "flops": 0.0}
                        for d in self.docs}
        for s in self.prompt_lens:
            self.modules[f"m2_prefill_{s}"] = {
                "kind": "prefill", "flops": mamba2_step_flops(sizes, s)}
        self.modules["m2_decode"] = {
            "kind": "decode", "flops": mamba2_step_flops(sizes, 1)}

    def setup(self) -> None:
        self.params = make_params(self.seed, self.spec)
        host = prompts(self.requests.values(), self.seed,
                       self.sizes["vocab_size"])
        self.host_prompts = host
        self.prompts = {i: jnp.asarray(t[None, :]) for i, t in host.items()}
        prefill, decode = greedy_steps(self.model, make_local_mesh(),
                                       donate=False)
        self.empty = self.model.init_cache(1, 1)
        k = self.fanout

        def split(doc):
            return tuple(jnp.split(doc, k, axis=1))
        self.split = {
            d: named_jit(split, f"m2_split_{d}")
            .lower(jnp.zeros((1, d), jnp.int32)).compile()
            for d in self.docs}
        self.prefill = {
            s: named_jit(with_top_logit(prefill), f"m2_prefill_{s}")
            .lower(self.params, jnp.zeros((1, s), jnp.int32), self.empty)
            .compile()
            for s in self.prompt_lens}
        self.decode = named_jit(with_top_logit(decode), "m2_decode").lower(
            self.params, jnp.zeros((1, 1), jnp.int32), self.empty).compile()
        jax.block_until_ready((self.params, self.prompts, self.empty))

    def _body(self, prompt, n: int):
        """``n`` tokens after ``prompt`` and their best logits."""
        _, tok, cache, top = self.prefill[prompt.shape[1]](
            self.params, prompt, self.empty)
        return generate(self.decode, self.params, tok, top, cache, n)

    def workflow(self, wrap) -> Workflow:
        k, m = self.fanout, self.map_tokens
        chunks = [f"chunk.{j}" for j in range(k)]
        toks = [f"toks.{j}" for j in range(k)]

        def split_fn(doc):
            return dict(zip(chunks, self.split[doc.shape[1]](doc)))

        def map_fn(j):
            def fn(**kw):
                return {toks[j]: self._body(kw[chunks[j]], m)}
            return fn

        def merge_fn(gen, **kw):
            joined = jnp.concatenate([kw[t][0] for t in toks], axis=1)
            tops = jnp.concatenate([kw[t][1] for t in toks], axis=1)
            return {"summary": self._body(joined, gen),
                    "joined": (joined, tops)}

        fns = [FunctionSpec("split", inputs=("doc",), outputs=tuple(chunks),
                            fn=wrap("split", split_fn), cold_start=0.0)]
        fns += [FunctionSpec(f"map.{j}", inputs=(chunks[j],),
                             outputs=(toks[j],),
                             fn=wrap(f"map.{j}", map_fn(j)), cold_start=0.0)
                for j in range(k)]
        fns.append(FunctionSpec("merge", inputs=tuple(toks) + ("gen",),
                                outputs=("summary", "joined"),
                                fn=wrap("merge", merge_fn), cold_start=0.0))
        return Workflow("WC", fns)

    def payload(self, i: int) -> dict:
        return {"doc": self.prompts[i], "gen": self.requests[i].output_len}

    def served(self, i: int, outputs: dict):
        """Every map's chunk and tokens, then merge's prompt (the maps'
        tokens as served, in map order) and tokens.  A map handed the wrong
        chunk, or a merge handed the maps' tokens out of order, shows as
        tokens the reference does not pick."""
        r = self.requests[i]
        k, m = self.fanout, self.map_tokens

        def pair(value, n):
            if not isinstance(value, tuple) or len(value) != 2 \
                    or any(tuple(a.shape) != (1, n) for a in value):
                return None
            return tuple(np.asarray(a)[0] for a in value)
        summary = pair(outputs.get("summary"), r.output_len)
        joined = pair(outputs.get("joined"), k * m)
        if summary is None or joined is None:
            return None
        doc, (joined, tops) = self.host_prompts[i], joined
        c = len(doc) // k
        seqs = []
        for j in range(k):
            t = joined[j * m:(j + 1) * m]
            chunk = doc[j * c:(j + 1) * c]
            seqs.append((np.concatenate([chunk, t[:-1]]), c - 1, t,
                         tops[j * m:(j + 1) * m]))
        s, top = summary
        seqs.append((np.concatenate([joined, s[:-1]]), len(joined) - 1, s,
                     top))
        if any(t.min() < 0 or t.max() >= self.vocab for _, _, t, _ in seqs):
            return None
        return seqs

    def free(self) -> None:
        for name in ("params", "prompts", "empty", "split", "prefill",
                     "decode"):
            self.__dict__.pop(name, None)


def build(sizes, traffic, requests, seed, reference):
    return WordCount(sizes, traffic, requests, seed, reference)
