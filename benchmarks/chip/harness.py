"""Run one cell of the benchmark: set up, serve a window, check, report.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by its name in ``BENCHMARK.json``:

* ``configs/<config>.json``: the sizes as run (the entry's ``file``), and
  beside it ``configs/<config>.py``, the deployment (``deploy.py`` says
  what it defines); the JSON names its plain reference,
  ``references/<reference>.py``;
* ``traffic/<traffic>.json``: the rate, length mix and check settings,
  read by the one generator in ``arrivals.py``;
* ``metrics/<metric>.py``: a reader ``read(rec) -> float | None`` of one
  per-layer metric from a traced run's record.

A run: weights drawn on the device from the seed, every step compiled for
the cell's shapes (from the compile cache after the first run), one warm-up
request per shape through the same ``DServe``, then an open-loop window of
``--seconds`` in which the requests fall due, and the drain.  Client latency
of a request runs from its due time, so a late arrival loop shows.  After
the window the program's arrays are freed and the plain reference checks a
seeded sample of the served tokens.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

import arrivals
import trace_reduce
from peaks import peaks_for
from stats import percentile

__all__ = ["Cell", "load_cell", "run_cell", "compare", "judge", "NoChip",
           "CHECKOUT"]

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]
CACHE_DIR = HERE / ".cache" / "jax"
TRACE_DIR = HERE / ".cache" / "trace"
SERVE_NODES = 2


class NoChip(RuntimeError):
    """JAX sees no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    sizes: dict
    traffic: dict
    deployment: object
    reference: object
    end_to_end: list
    per_layer: list


def load_cell(workload: str, *, overrides: dict | None = None) -> Cell:
    """The cell named ``workload`` in ``BENCHMARK.json``; ``overrides``
    (tests only) replace entries of its sizes and traffic."""
    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}")
    cell = cells[workload]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    sizes_path = CHECKOUT / config["file"]
    sizes = json.loads(sizes_path.read_text())
    traffic = json.loads(
        (HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    if traffic["config"] != cell["config"]:
        raise ValueError(f"traffic {cell['traffic']!r} is for "
                         f"{traffic['config']!r}, not {cell['config']!r}")
    overrides = overrides or {}
    sizes.update(overrides.get("sizes", {}))
    traffic.update(overrides.get("traffic", {}))
    reference = load_module(HERE / "references" / f"{sizes['reference']}.py",
                            f"bench_reference_{sizes['reference']}")
    deployment = load_module(sizes_path.with_suffix(".py"),
                             f"bench_config_{cell['config']}")

    def for_cell(m):
        return workload in m.get("workloads", [workload])
    return Cell(workload, cell["chips"], sizes, traffic, deployment,
                reference, [m for m in bench["end_to_end"] if for_cell(m)],
                [m for m in bench["per_layer"] if for_cell(m)])


def keep_files_local() -> None:
    """Point the TPU runtime's logs into the checkout (unless the caller
    chose a place), before JAX starts its backend: a run writes nowhere
    else but its checkout and the temporary directories it is given."""
    os.environ.setdefault("TPU_LOG_DIR", str(HERE / ".cache" / "tpu_logs"))


def use_compile_cache() -> None:
    """JAX's persistent compilation cache at one fixed path inside the
    checkout, whatever the environment says, so only a cell's first run in
    a checkout compiles and two checkouts share nothing."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu" or len(devs) < chips:
        raise NoChip(f"the cell needs {chips} accelerator chip(s); JAX "
                     f"sees {len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int | None:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# ----------------------------------------------------------------------
# stamps the benchmark takes around the program
# ----------------------------------------------------------------------

@dataclass
class Stamps:
    """Host-clock stamps: when each request was launched, and when each
    body was entered and left (traced runs only).  In a traced run a body
    is left once its outputs are ready on the device, so that the Puts
    that follow time the copy to the host and the digest, not the wait for
    the device."""
    launch: dict = field(default_factory=dict)      # request -> time
    bodies: list = field(default_factory=list)      # (instance, fn, in, out)

    def wrap(self, name: str, fn, *, traced: bool):
        if not traced:
            return fn
        import jax

        prefix = "dflow-"
        suffix = f"-{name}"

        def body(**kw):
            thread = threading.current_thread().name
            instance = thread[len(prefix):-len(suffix)] \
                if thread.startswith(prefix) and thread.endswith(suffix) \
                else None
            t0 = time.monotonic()
            with jax.profiler.TraceAnnotation(f"body:{name}"):
                out = jax.block_until_ready(fn(**kw))
            self.bodies.append((instance, name, t0, time.monotonic()))
            return out
        return body


class CompileCounter:
    """Counts compilations while armed (none may happen in the window)."""

    def __init__(self):
        self.armed = False
        self.count = 0

    def __call__(self, event: str, duration: float, **_) -> None:
        if self.armed and "backend_compile" in event:
            self.count += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self)


class SliceProfiler:
    """A ``jax.profiler`` trace of ``[start_at, start_at + length]`` on the
    host's monotonic clock, taken from a thread of its own."""

    def __init__(self, log_dir: Path, start_at: float, length: float,
                 sync_step):
        self.log_dir, self.start_at, self.length = log_dir, start_at, length
        self.sync_step = sync_step
        self.lo = self.hi = None
        self.error: BaseException | None = None
        self.thread = threading.Thread(target=self._run, daemon=True,
                                       name="bench-profiler")

    def _run(self) -> None:
        import jax

        try:
            time.sleep(max(0.0, self.start_at - time.monotonic()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(self.log_dir),
                                     profiler_options=opts)
            try:
                sync = time.monotonic()
                with jax.profiler.TraceAnnotation(trace_reduce.SYNC):
                    self.sync_step()
                self.lo = sync
                time.sleep(max(0.0, sync + self.length - time.monotonic()))
                self.hi = time.monotonic()
            finally:
                jax.profiler.stop_trace()
        except BaseException as exc:       # noqa: BLE001 - re-raised
            self.error = exc

    def start(self) -> None:
        self.thread.start()

    def join(self) -> None:
        self.thread.join(600)
        if self.thread.is_alive():
            raise TimeoutError("the profiler did not stop")
        if self.error is not None:
            raise self.error

    def read(self) -> trace_reduce.DeviceTrace:
        files = sorted(self.log_dir.rglob("*.xplane.pb"))
        if not files:
            raise FileNotFoundError(f"no trace under {self.log_dir}")
        return trace_reduce.read_xplane(str(files[-1]), self.lo)


# ----------------------------------------------------------------------
# the check
# ----------------------------------------------------------------------

def choose_sample(served: dict, want_tokens: int, seed: int) -> list:
    """Requests to compare: the longest (most tokens in its sequences),
    then others drawn from the seed until ``want_tokens`` served tokens."""
    if not served:
        return []
    size = {i: sum(len(seq[0]) for seq in s) for i, s in served.items()}
    longest = max(sorted(served), key=lambda i: size[i])
    rest = [i for i in sorted(served) if i != longest]
    order = [longest] + list(np.random.default_rng([seed, 2])
                             .permutation(rest))
    out, n = [], 0
    for i in order:
        if n >= want_tokens:
            break
        out.append(int(i))
        n += sum(len(seq[2]) for seq in served[i])
    return out


def logit_gaps(ref_logits, served_tokens) -> np.ndarray:
    """How far below the reference's best logit each served token's logit
    lies, at each served position."""
    out = []
    for logits, toks in zip(ref_logits, served_tokens):
        logits = np.asarray(logits, np.float64)
        out.append(logits.max(-1) - logits[np.arange(len(toks)), toks])
    return np.concatenate(out) if out else np.zeros(0)


def logit_errors(ref_logits, served_tokens, tops) -> np.ndarray:
    """How far the program's best logit at each served position lies from
    the reference's logit of the token it served there, the latter rounded
    to the dtype the program emits its logits in (bf16): what is left is
    the error of the computation, not the rounding of its output."""
    out = []
    for logits, toks, top in zip(ref_logits, served_tokens, tops):
        top = np.asarray(top)
        ref = np.asarray(logits, np.float32)[np.arange(len(toks)), toks]
        out.append(np.abs(top.astype(np.float64)
                          - ref.astype(top.dtype).astype(np.float64)))
    return np.concatenate(out) if out else np.zeros(0)


def judge(numbers: dict, limits: dict) -> bool:
    """Whether every number compared lies within its limit: the one test
    that decides ``correct``, for the program and for its control."""
    return all(numbers.get(name) is not None and numbers[name] <= limit
               for name, limit in limits.items())


# ----------------------------------------------------------------------
# a serving session and its windows
# ----------------------------------------------------------------------

@dataclass
class Window:
    """One open-loop window: per-request rows (host-clock stamps) and the
    served sequences of the requests that completed well formed."""
    requests: list
    rows: list          # {"due", "launch", "done" (None if failed), "ok"}
    served: dict        # request index -> [(tokens, first, served, top)]
    opened: float
    spans: list = field(default_factory=list)
    bodies: list = field(default_factory=list)
    profiler: object = None

    @property
    def failed(self) -> int:
        return sum(1 for row in self.rows if not row["ok"])

    def end_to_end(self, setup_s: float) -> dict:
        ok = [row for row in self.rows if row["ok"]]
        lat = [row["done"] - row["due"] for row in ok]
        return {
            "p50_ms": 1e3 * percentile(lat, 50.0) if lat else None,
            "p95_ms": 1e3 * percentile(lat, 95.0) if lat else None,
            "completed_rps": len(ok) / (
                max(row["done"] for row in ok)
                - min(row["due"] for row in self.rows)) if ok else 0.0,
            "setup_s": setup_s,
        }


class Session:
    """A cell's deployment, set up once (weights, compiled steps, warm-up
    through its ``DServe``), serving one or more windows.  ``requests``
    holds every request any window will send, each with its own index."""

    def __init__(self, cell: Cell, seed: int, requests, *, trace: bool):
        from repro.core.obs import MetricsRegistry, Tracer
        from repro.core.serve import DServe

        self.cell, self.seed, self.trace = cell, seed, trace
        self.dep = cell.deployment.build(cell.sizes, cell.traffic, requests,
                                         seed, cell.reference)
        t = time.monotonic()
        self.dep.setup()
        log(f"weights, prompts and {len(self.dep.modules)} steps ready in "
            f"{time.monotonic() - t:.3f} s")
        self.stamps = Stamps()
        self.wf = self.dep.workflow(
            lambda name, fn: self.stamps.wrap(name, fn, traced=trace))
        self.tracer = Tracer() if trace else None
        self.serve = DServe(self.wf, n_nodes=SERVE_NODES, pattern="dataflow",
                            cold_start=0.0, spans=self.tracer,
                            metrics=MetricsRegistry() if trace else None)
        t = time.monotonic()
        warm = self.dep.warmup
        rep = self.serve.run([0.0] * len(warm),
                             lambda k: self.dep.payload(warm[k].index))
        if rep.failures:
            raise RuntimeError(f"warm-up failed: "
                               f"{[s.error for s in rep.stats if s.error]}")
        log(f"{len(warm)} warm-up request(s) in "
            f"{time.monotonic() - t:.3f} s")
        self.sync_step = None
        if trace:
            import jax
            import jax.numpy as jnp
            from deploy import named_jit

            one = jnp.zeros((), jnp.int32)
            step = named_jit(lambda x: x + 1, trace_reduce.SYNC_STEP) \
                .lower(one).compile()
            self.sync_step = lambda: jax.block_until_ready(step(one))

    def window(self, requests, *, profile_dir: Path | None = None,
               profile_at: float = 0.35, profile_s: float = 0.0) -> Window:
        """Serve ``requests`` open-loop from now; returns when all are
        done.  With ``profile_dir``, traces ``profile_s`` seconds from
        ``profile_at`` of the way into the arrivals."""
        stamps = self.stamps
        stamps.launch.clear()
        stamps.bodies.clear()
        if self.tracer is not None:
            self.tracer.clear()

        def payload(k: int) -> dict:
            i = requests[k].index
            stamps.launch[i] = time.monotonic()
            return self.dep.payload(i)

        profiler = None
        span = requests[-1].arrival
        with CompileCounter() as compiles:
            gc.collect()
            opened = time.monotonic()
            if profile_dir is not None:
                shutil.rmtree(profile_dir, ignore_errors=True)
                profiler = SliceProfiler(profile_dir,
                                         opened + profile_at * span,
                                         min(profile_s, 0.5 * span),
                                         self.sync_step)
                profiler.start()
            compiles.armed = True
            report = self.serve.run([r.arrival for r in requests], payload)
            compiles.armed = False
        log(f"{len(requests)} requests over {span:.3f} s, drained "
            f"{time.monotonic() - opened - span:.3f} s after the last was "
            f"due; {compiles.count} compilation(s) in the window")
        if profiler is not None:
            profiler.join()
        rows, served = [], {}
        for r, stat in zip(requests, report.stats):
            row = {"due": opened + r.arrival, "launch": stamps.launch.get(
                r.index), "ok": stat.ok, "done": None}
            if stat.ok:
                row["done"] = row["launch"] + stat.latency
                seqs = self.dep.served(r.index, stat.outputs)
                if seqs is None:
                    row["ok"] = False
                else:
                    served[r.index] = seqs
            rows.append(row)
        spans = [(s.trace, s.kind, s.name, s.start, s.end)
                 for s in self.tracer.finished()] if self.tracer else []
        return Window(requests, rows, served, opened,
                      spans=spans, bodies=list(stamps.bodies),
                      profiler=profiler)

    def record(self, win: Window, peaks) -> "Record":
        """What the per-layer metric readers get from a traced window."""
        wf = self.wf
        prof = win.profiler
        device_trace = prof.read()
        shutil.rmtree(prof.log_dir, ignore_errors=True)
        return Record(
            requests=win.rows, bodies=win.bodies, spans=win.spans,
            functions={f.name: list(f.inputs)
                       for f in wf.functions.values()},
            external=set(wf.external_inputs),
            instances={k: f"{wf.name}#{k}" for k in range(len(win.rows))},
            trace=device_trace, window=(prof.lo, prof.hi),
            modules=self.dep.modules, peaks=peaks)

    def close(self) -> None:
        """Drop the program's arrays and the server, so the reference has
        the device to itself."""
        self.serve = self.wf = None
        self.dep.free()
        gc.collect()


def compare(cell: Cell, seed: int, served: dict, *, quant=()) -> dict:
    """The plain reference over a seeded sample of the served sequences.

    For each served token: how far its logit lies below the reference's
    best at that position (``*_logit_gap``), and how far the program's
    best logit there lies from the reference's logit of the served token
    (``*_logit_error``); the widest and the mean of each, and how many
    tokens were compared.  With ``quant`` (a list of precisions), also the
    same numbers of the control at each precision, under ``control``: the
    reference computed at that lower precision, read at the same positions
    for the token it puts first, its logits emitted in the program's
    dtype."""
    check = cell.traffic["check"]
    sample = choose_sample(served, check["served_tokens"], seed)
    seqs = [s for i in sample for s in served[i]]
    inputs = [(tok, first) for tok, first, _, _ in seqs]
    t = time.monotonic()
    ref = cell.reference.logits_at(cell.sizes, seed, inputs)

    def numbers(toks, tops):
        out = {}
        for kind, v in (("gap", logit_gaps(ref, toks)),
                        ("error", logit_errors(ref, toks, tops))):
            out[f"widest_logit_{kind}"] = float(v.max()) if v.size else None
            out[f"mean_logit_{kind}"] = float(v.mean()) if v.size else None
        return out
    tops = [np.asarray(s[3]) for s in seqs]
    out = numbers([s[2] for s in seqs], tops)
    out.update(tokens=sum(len(s[2]) for s in seqs), requests=len(sample))
    if quant:
        out["control"] = {}
        for q in quant:
            low = [np.asarray(l, np.float64) for l in cell.reference
                   .logits_at(cell.sizes, seed, inputs, quant=q)]
            emit = tops[0].dtype if tops else np.float64
            out["control"][q] = numbers([l.argmax(-1) for l in low],
                                        [l.max(-1).astype(emit) for l in low])
    log(f"reference over {len(sample)} request(s), {out['tokens']} served "
        f"tokens in {time.monotonic() - t:.3f} s")
    return out


# ----------------------------------------------------------------------
# one run
# ----------------------------------------------------------------------

def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True,
             overrides: dict | None = None) -> dict:
    """One run of a cell; returns the result line as a dict (its
    ``check`` entry last).  Raises :class:`NoChip` before any work when
    ``require_chip`` and JAX finds no accelerator."""
    cell = load_cell(workload, overrides=overrides)
    import jax

    if require_chip:
        device = device_info(cell.chips)
        peaks = peaks_for(device["kind"])
        use_compile_cache()
    else:
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": len(jax.devices())}
        peaks = None

    requests = arrivals.make_requests(cell.traffic, seconds)
    session = Session(cell, seed, requests, trace=trace)
    win = session.window(
        requests, profile_dir=TRACE_DIR / f"{workload}-{seed}"
        if trace else None, profile_s=float(cell.traffic["trace_slice_s"]))
    setup_s = win.opened - t_start
    peak_bytes = memory_peak_bytes()
    rec = session.record(win, peaks) if trace else None
    session.close()

    cmp = compare(cell, seed, win.served)
    limits = cell.traffic["check"]["limits"]
    correct = win.failed == 0 and judge(cmp, limits)
    check = {name: {"value": cmp[name], "limit": limit}
             for name, limit in limits.items()}
    check.update(
        failed_requests={"value": win.failed, "limit": 0},
        tokens_compared={"value": cmp["tokens"],
                         "limit": cell.traffic["check"]["served_tokens"]})
    device = dict(device, memory_peak_bytes=peak_bytes)
    result = {"correct": bool(correct), "attempted": len(requests),
              "failed": win.failed}
    if not trace:
        values = win.end_to_end(setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    else:
        metrics = {}
        for m in cell.per_layer:
            reader = load_module(HERE / "metrics" / f"{m['name']}.py",
                                 f"bench_metric_{m['name']}")
            value = reader.read(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        busy = trace_reduce.intersect(rec.trace.busy(), [rec.window])
        device.update(busy_s=trace_reduce.total(busy),
                      window_s=rec.window[1] - rec.window[0])
        result["breakdown"] = breakdown(rec)
    result.update(metrics=metrics, device=device, check=check)
    return result


@dataclass
class Record:
    """What a traced run hands the per-layer metric readers.  Times are
    seconds on the host's monotonic clock."""
    requests: list      # {"due", "launch", "done" (None if failed), "ok"}
    bodies: list        # (instance, function, entered, left)
    spans: list         # program spans: (trace, kind, name, start, end)
    functions: dict     # function -> its input keys
    external: set       # keys staged from outside the workflow
    instances: dict     # position in the window -> instance name
    trace: object       # trace_reduce.DeviceTrace of the traced slice
    window: tuple       # (start, end) of the traced slice
    modules: dict       # compiled step -> {"kind", "flops"}
    peaks: dict | None  # peaks.PEAKS entry of the device

    def in_flight(self) -> list:
        """Union of the intervals in which some request was in flight."""
        return trace_reduce.union(
            (r["launch"], r["done"]) for r in self.requests
            if r["launch"] is not None and r["done"] is not None)


def breakdown(rec: Record, top: int = 10) -> dict:
    """The compiled steps that took most device time in the slice, and the
    longest device-idle gaps while requests were in flight, each named by
    the bodies the host was in (or "outside bodies")."""
    win = [rec.window]
    per_op: dict = {}
    for name, s, e in rec.trace.modules:
        part = trace_reduce.total(trace_reduce.intersect([(s, e)], win))
        if part > 0:
            per_op[name] = per_op.get(name, 0.0) + part
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    inflight = trace_reduce.intersect(rec.in_flight(), win)
    idle = trace_reduce.gaps(rec.trace.busy(), inflight)
    idle = sorted(idle, key=lambda g: g[0] - g[1])[:top]
    labelled = []
    for lo, hi in idle:
        inside = sorted({name for name, s, e in rec.trace.annotations
                         if s < hi and e > lo})
        labelled.append([", ".join(inside) or "outside bodies", hi - lo])
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": labelled}
