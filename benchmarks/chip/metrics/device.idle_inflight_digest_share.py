"""device.idle_inflight_digest_share: the share, in percent, of the traced
slice's device-idle time with a request in flight (the set that
``device.idle_inflight_share`` measures) during which at least one DStore
``digest`` span was open.  Program spans against the device trace, on one
clock (``time.monotonic``, to which the benchmark ties the trace).  Moves
``p50_ms``."""

from trace_reduce import gaps, intersect, total, union


def read(rec):
    if rec.trace.devices == 0:
        return None
    digests = union((start, end) for _, kind, _, start, end in rec.spans
                    if kind == "digest")
    if not digests:
        return None
    inflight = intersect(rec.in_flight(), [rec.window])
    idle = gaps(rec.trace.busy(), inflight)
    span = total(idle)
    if span <= 0:
        return None
    return 100.0 * total(intersect(idle, digests)) / span
