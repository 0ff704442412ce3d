"""Random valid Workflow DAGs for property-based / differential testing.

Two entry points over one generator:

* :func:`random_workflow` — fully deterministic: an LCG seeded by an int
  draws the DAG shape (fan-in/out, diamonds, multi-output functions,
  stream edges, external inputs).  Usable without hypothesis, so the
  200-seed differential sweep runs in every environment.
* :func:`workflows` — a hypothesis strategy wrapping the same generator
  (draws the seed + size bounds), so shrinking works when hypothesis *is*
  installed.

Every function gets a real callable producing a deterministic digest of
its (sorted) inputs, so a sequential topological oracle
(:func:`oracle_run`) predicts the exact output bytes of any engine
execution — the conformance contract for the threaded DFlowEngine in both
invocation patterns.
"""

from __future__ import annotations

import hashlib

from repro.core.dag import FunctionSpec, Workflow

try:
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:          # deterministic path still works
    st = None
    HAVE_HYPOTHESIS = False


class _Rng:
    """LCG (same family as workloads._Det) — no global RNG, ever."""

    def __init__(self, seed: int):
        self.s = (seed * 2654435761 + 0x9E3779B9) & 0xFFFFFFFF

    def next(self) -> float:
        self.s = (1103515245 * self.s + 12345) & 0x7FFFFFFF
        return self.s / 0x7FFFFFFF

    def randint(self, lo: int, hi: int) -> int:
        return lo + int(self.next() * (hi - lo + 1)) % (hi - lo + 1)

    def chance(self, p: float) -> bool:
        return self.next() < p

    def sample(self, items: list, k: int) -> list:
        pool = list(items)
        out = []
        for _ in range(min(k, len(pool))):
            out.append(pool.pop(self.randint(0, len(pool) - 1)))
        return out


def _normalize(kw: dict) -> dict:
    """Drain StreamReaders ONCE up front — a reader is an iterator, so
    per-output re-reads would observe an already-drained stream."""
    out = {}
    for k, v in kw.items():
        if hasattr(v, "read_all"):            # StreamReader (engine path)
            v = v.read_all()
        elif not isinstance(v, (bytes, bytearray)):
            v = repr(v).encode()
        out[k] = bytes(v)
    return out


def _value_bytes(tag: str, kw: dict) -> bytes:
    """Deterministic digest of a function's (normalized) inputs — the
    oracle contract."""
    h = hashlib.sha256(tag.encode())
    for k in sorted(kw):
        h.update(k.encode())
        h.update(kw[k])
    d = h.digest()
    return (d * 40)[:1280]                    # ~1.3 KB payloads


def _make_fn(outputs: tuple[str, ...], stream_outputs: tuple[str, ...],
             as_generator: bool, calls: dict[str, int] | None, name: str):
    def fn(**kw):
        if calls is not None:
            calls[name] = calls.get(name, 0) + 1
        kw = _normalize(kw)
        out = {}
        for o in outputs:
            v = _value_bytes(o, kw)
            if o in stream_outputs and as_generator:
                out[o] = (v[i:i + 256] for i in range(0, len(v), 256))
            else:
                out[o] = v
        return out
    return fn


def random_workflow(seed: int, *, max_functions: int = 8,
                    stream_prob: float = 0.15,
                    calls: dict[str, int] | None = None) -> Workflow:
    """Deterministic random DAG: linear chains, diamonds, fan-in/out and
    multi-consumer outputs all arise from the edge draw.  ``calls``, when
    given, is filled with per-function execution counts (exactly-once
    assertions)."""
    rng = _Rng(seed)
    n = rng.randint(2, max_functions)
    produced: list[str] = []                 # keys available to later fns
    specs: list[FunctionSpec] = []
    for i in range(n):
        # Draw 0-3 inputs from earlier outputs; fns that drew none take
        # the external "x" (keys never produced are external by contract),
        # so every function has a data edge — generated DAGs lint clean
        # (no error/warning diagnostics; see lint_clean below).
        k = rng.randint(0, min(3, len(produced)))
        inputs = tuple(sorted(rng.sample(produced, k)))
        if not inputs:
            inputs = ("x",)
        n_out = 2 if rng.chance(0.25) else 1
        outputs = tuple(f"o{i}" if j == 0 else f"o{i}.{j}"
                        for j in range(n_out))
        stream = rng.chance(stream_prob)
        stream_inputs = tuple(k for k in inputs if k != "x"
                              and rng.chance(0.5)) if stream else ()
        stream_outputs = outputs if stream and rng.chance(0.5) else ()
        specs.append(FunctionSpec(
            name=f"f{i}", inputs=inputs, outputs=outputs,
            fn=_make_fn(outputs, stream_outputs,
                        as_generator=rng.chance(0.5), calls=calls,
                        name=f"f{i}"),
            exec_time=0.001, cold_start=0.001,
            stream_inputs=stream_inputs, stream_outputs=stream_outputs,
            chunk_size=256,
            output_sizes={o: 1280 for o in outputs}))
        produced.extend(outputs)
    return Workflow(f"fuzz{seed}", specs)


def lint_clean(wf: Workflow) -> list:
    """Generator contract: a random workflow may carry *info*-level
    diagnostics (unconsumed by-products and stream fallbacks arise from
    random shapes and are by-design byte-exact) but never a warning or
    error.  Returns the offending diagnostics (empty = clean)."""
    from repro.core.lint import lint_workflow

    return [d for d in lint_workflow(wf, require_fns=True)
            if d.severity in ("warning", "error")]


def oracle_run(wf: Workflow, inputs: dict) -> dict:
    """Sequential topological-order execution — the ground truth every
    engine schedule must match.  Returns the sink outputs exactly as
    RunReport.outputs collects them (produced-but-unconsumed keys plus
    exit functions' outputs)."""
    data = dict(inputs)
    for fname in wf.topo_order:
        f = wf.functions[fname]
        result = f.fn(**{k: data[k] for k in f.inputs})
        for o in f.outputs:
            v = result[o]
            if not isinstance(v, (bytes, bytearray)):
                v = b"".join(v)              # drain generator outputs
            data[o] = bytes(v)
    consumed = {k for f in wf.functions.values() for k in f.inputs}
    out = {}
    for f in wf.functions.values():
        for k in f.outputs:
            if k not in consumed or f.name in wf.exit_points:
                out[k] = data[k]
    return out


def external_inputs(wf: Workflow) -> dict:
    return {k: b"ext:" + k.encode() for k in wf.external_inputs}


def sharded_run(seed: int, n_nodes: int, *, pattern: str = "dataflow",
                stress: int | None = None):
    """One engine run of ``random_workflow(seed)`` over a DShard
    :class:`~repro.core.router.ShardedDStore` with its own trace recorder
    (attached explicitly so the routing invariant is exercised even when
    the conftest DFLOW_TRACE_CHECK fixture is off).  Returns
    ``(outputs, store, events)`` — the caller asserts byte-equality
    against the oracle/baseline and runs the TraceChecker."""
    from repro.core.check import TraceRecorder
    from repro.core.dscheduler import DFlowEngine
    from repro.core.obs import Tracer
    from repro.core.router import ShardedDStore

    wf = random_workflow(seed)
    eng = DFlowEngine(n_nodes=n_nodes, pattern=pattern, get_timeout=30.0,
                      sharded=True)
    store = ShardedDStore(eng.nodes, eng.transport)
    rec = TraceRecorder(stress=stress)
    store.attach_spans(Tracer(recorder=rec))
    rep = eng.start(wf, external_inputs(wf), store=store).wait()
    outputs = {k: bytes(v) for k, v in rep.outputs.items()}
    return outputs, store, rec.events()


if HAVE_HYPOTHESIS:
    @st.composite
    def workflows(draw, max_functions: int = 8):
        seed = draw(st.integers(min_value=0, max_value=2**32 - 1))
        return random_workflow(seed, max_functions=max_functions)
else:                                        # pragma: no cover - shim env
    def workflows(max_functions: int = 8):
        return None
