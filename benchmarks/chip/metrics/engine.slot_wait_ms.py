"""engine.slot_wait_ms: mean duration of the ``slot`` spans, one per
invocation: the wait for one of the node's execution slots
(``ContainerService.slot``), the body excluded.  Program spans (DScope
``slot``).  Moves ``p50_ms``."""

from stats import mean


def read(rec):
    value = mean(end - start for _, kind, _, start, end in rec.spans
                 if kind == "slot")
    return None if value is None else 1e3 * value
