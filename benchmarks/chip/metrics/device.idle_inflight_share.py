"""device.idle_inflight_share: the share, in percent, of the traced slice's
time with at least one request in flight (launch to completion, the
benchmark's stamps) during which no operation ran on the device.  Leaves
out the wait for arrivals that a rate below the knee makes most of the
window.  Device trace against host stamps, on one clock.  Moves
``p50_ms``."""

from trace_reduce import intersect, total


def read(rec):
    if rec.trace.devices == 0:
        return None
    inflight = intersect(rec.in_flight(), [rec.window])
    span = total(inflight)
    if span <= 0:
        return None
    busy = total(intersect(rec.trace.busy(), inflight))
    return 100.0 * (span - busy) / span
