"""DFlow core — the paper's contribution (dataflow workflow execution).

Layers:

* :mod:`repro.core.dag`          — workflow DAG model + parser.
* :mod:`repro.core.partition`    — Global-Scheduler DAG partitioning.
* :mod:`repro.core.dstore`       — real threaded DStore (Table 1 API).
* :mod:`repro.core.router`       — DShard: per-node DStore shards behind
  local routing tables + a coordinator (1-hop transfers, transport tiers).
* :mod:`repro.core.stream`       — DStream: chunked pipelined Get/Put
  (beyond-paper; overlaps producer writes with consumer reads).
* :mod:`repro.core.dscheduler`   — real threaded DScheduler + engine.
* :mod:`repro.core.serve`        — DServe: concurrent multi-instance
  serving with explicit container pools (cold boot / keep-alive TTL /
  dataflow-triggered prewarm) and open-loop load generation.
* :mod:`repro.core.sim*`         — deterministic cluster simulator used by
  every paper-figure experiment (CFlow/FaaSFlow/.../KNIX baselines).
* :mod:`repro.core.workloads`    — paper benchmarks (WC/FP/Cyc/Epi/Gen/Soy).
* :mod:`repro.core.experiments`  — open/closed-loop drivers + metrics.
* :mod:`repro.core.lint`         — DCheck static workflow linter (stable
  DF diagnostic codes; ``python -m repro.lint`` CLI).
* :mod:`repro.core.check`        — DCheck dynamic invariant checker
  (a trace recorder that rides on the DScope Tracer + offline
  happens-before/immutability validation).
* :mod:`repro.core.scale`        — DScale: rate-estimating pool
  autoscaler, SLO-aware prewarm budgets (container-seconds), and
  inhomogeneous (diurnal / bursty) arrival generators.
* :mod:`repro.core.obs`          — DScope observability: MetricsRegistry,
  per-request span Tracer (the data plane's one instrumentation hook,
  which also carries DCheck's recorder; JSONL/Perfetto exporters),
  plan-vs-actual attribution, and the standardized ``dflow-bench/v1``
  schema (``python -m repro.obs`` CLI).
"""

from .check import (TraceChecker, TraceEvent, TraceRecorder, Violation,
                    content_digest)
from .dag import FunctionSpec, Workflow, parse_workflow
from .dscheduler import (DFlowEngine, GlobalScheduler, InstanceRun,
                         dataflow_initial_frontier, dataflow_next_frontier)
from .dstore import (DStore, DataDirectoryService, ImmutabilityError,
                     LocalStore, Transport)
from .lint import (Diagnostic, WorkflowLintError, check_workflow, lint,
                   lint_workflow)
from .obs import (MetricsRegistry, Span, Tracer, attribute,
                  bench_doc, bench_metric, compare_docs, plan_attribution,
                  read_spans_jsonl, to_chrome_trace, write_spans_jsonl)
from .experiments import (ExperimentResult, cold_start_latency,
                          percentile, run_closed_loop, run_open_loop)
from .partition import cut_bytes, partition_workflow, stage_node
from .router import (Coordinator, RoutingTable, ShardedDStore,
                     TieredTransport, routes_from_plan, static_routes)
from .scale import (AutoscalerConfig, PoolAutoscaler, PoolSpec,
                    PrewarmBudget, PrewarmGrant, RateEstimator,
                    ScaleDecision, allocate_prewarms, bursty_arrivals,
                    diurnal_arrivals)
from .serve import (ContainerPool, ContainerService, DServe, Lease,
                    ServeReport, poisson_arrivals, trace_arrivals)
from .sim_systems import SYSTEMS, make_system
from .simcluster import SimConfig
from .stream import StreamBroken, StreamReader, StreamWriter
from .workloads import BENCHMARKS, make_workflow

__all__ = [
    "FunctionSpec", "Workflow", "parse_workflow",
    "TraceChecker", "TraceEvent", "TraceRecorder", "Violation",
    "content_digest", "ImmutabilityError",
    "Diagnostic", "WorkflowLintError", "check_workflow", "lint",
    "lint_workflow",
    "DFlowEngine", "GlobalScheduler", "InstanceRun",
    "dataflow_initial_frontier", "dataflow_next_frontier",
    "DStore", "DataDirectoryService", "LocalStore", "Transport",
    "StreamBroken", "StreamReader", "StreamWriter",
    "ContainerPool", "ContainerService", "DServe", "Lease", "ServeReport",
    "poisson_arrivals", "trace_arrivals",
    "AutoscalerConfig", "PoolAutoscaler", "PoolSpec",
    "PrewarmBudget", "PrewarmGrant", "RateEstimator", "ScaleDecision",
    "allocate_prewarms", "bursty_arrivals", "diurnal_arrivals",
    "ExperimentResult", "cold_start_latency", "percentile",
    "run_closed_loop", "run_open_loop",
    "cut_bytes", "partition_workflow", "stage_node",
    "Coordinator", "RoutingTable", "ShardedDStore", "TieredTransport",
    "routes_from_plan", "static_routes",
    "SYSTEMS", "make_system", "SimConfig",
    "BENCHMARKS", "make_workflow",
    "MetricsRegistry", "Span", "Tracer", "attribute",
    "bench_doc", "bench_metric", "compare_docs", "plan_attribution",
    "read_spans_jsonl", "to_chrome_trace", "write_spans_jsonl",
]
