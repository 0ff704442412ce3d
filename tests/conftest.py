"""Shared test fixtures/shims.

``hypothesis`` is an optional dev dependency (requirements-dev.txt): when it
is missing, property-based tests skip while the rest of their modules run.
Test modules import the shim via ``from conftest import given, settings, st``.

DCheck trace validation (opt-in): ``DFLOW_TRACE_CHECK=1`` gives every
DStore a test constructs a :class:`repro.core.check.TraceRecorder`, riding
on the store's DScope :class:`repro.core.obs.Tracer` (a tracer the test
attaches later records into the same recorder), and replays the trace
through :class:`TraceChecker` at teardown — any
happens-before / immutability / eviction / chunk-sequence violation fails
the test.  ``DFLOW_TRACE_STRESS=<seed>`` additionally injects seeded
random sleeps at every instrumentation point so thread interleavings are
actually explored.  Tests that *deliberately* violate invariants opt out
with ``@pytest.mark.notracecheck``.
"""

import os

import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running sweeps (the 200-seed differential run); "
        'CI quick tier runs -m "not slow"')
    config.addinivalue_line(
        "markers",
        "notracecheck: skip DFLOW_TRACE_CHECK validation (test seeds "
        "deliberate invariant violations, or its premise is a store with "
        "no tracer, where the check would attach one)")


if os.environ.get("DFLOW_TRACE_CHECK") == "1":
    @pytest.fixture(autouse=True)
    def _dflow_trace_check(request, monkeypatch):
        if request.node.get_closest_marker("notracecheck"):
            yield
            return
        from repro.core.check import TraceChecker, TraceRecorder
        from repro.core.dscheduler import InstanceRun
        from repro.core.dstore import DStore
        from repro.core.obs import Tracer

        stress_env = os.environ.get("DFLOW_TRACE_STRESS")
        stress = int(stress_env) if stress_env else None
        own: list[Tracer] = []       # the tracers this fixture attached
        orig_init = DStore.__init__
        orig_attach = DStore.attach_spans
        orig_run_init = InstanceRun.__init__

        def init(self, *args, **kwargs):
            orig_init(self, *args, **kwargs)
            own.append(Tracer(recorder=TraceRecorder(stress=stress)))
            self.attach_spans(own[-1])

        def attach_spans(self, spans):
            # A test's own tracer takes over the store's recorder.
            if spans is not None and spans.recorder is None and \
                    self.spans is not None:
                spans.recorder = self.spans.recorder
            orig_attach(self, spans)

        def run_init(self, *args, **kwargs):
            orig_run_init(self, *args, **kwargs)
            # An engine instruments a store no one else has: this
            # fixture's tracer does not count.
            if self.spans is not None and \
                    any(self.store.spans is t for t in own):
                self.store.attach_spans(self.spans)

        monkeypatch.setattr(DStore, "__init__", init)
        monkeypatch.setattr(DStore, "attach_spans", attach_spans)
        monkeypatch.setattr(InstanceRun, "__init__", run_init)
        yield
        checker = TraceChecker()
        for tracer in own:
            checker.check_or_raise(tracer.recorder.events())


try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ModuleNotFoundError:
    def settings(**kw):
        return lambda fn: fn

    def given(*a, **kw):
        def deco(fn):
            @pytest.mark.skip(reason="hypothesis not installed")
            def wrapper():
                pass                  # pragma: no cover
            wrapper.__name__ = fn.__name__
            return wrapper
        return deco

    class _StStub:
        def __getattr__(self, name):
            return lambda *a, **kw: None

    st = _StStub()
