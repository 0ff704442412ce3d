"""dstore.put_ms_per_req: time in DStore ``put`` spans per request (every
Put of the request, staging included: the copy to the host and the content
digest), mean over the requests that completed in the window.  In a traced
run each body returns once its outputs are ready on the device
(``harness.Stamps``), so a Put does not wait for its producer's device
work.  Program spans (DScope ``put``).  Moves ``p50_ms``."""

from stats import mean


def read(rec):
    per = {}
    for trace, kind, _, start, end in rec.spans:
        if kind == "put":
            per[trace] = per.get(trace, 0.0) + (end - start)
    done = [rec.instances[i] for i, r in enumerate(rec.requests)
            if r["done"] is not None]
    value = mean(per.get(inst, 0.0) for inst in done)
    return None if value is None else 1e3 * value
