"""dstore.d2h_ms_per_req: time in the digest's ``d2h`` spans (``tobytes()``
of each array leaf: for a device array, the copy to the host) per request,
mean over the requests that completed in the window, the same set as
``dstore.put_ms_per_req``.  Program spans (DScope ``d2h``).  Moves
``p50_ms``."""

from stats import mean


def read(rec):
    per = {}
    for trace, kind, _, start, end in rec.spans:
        if kind == "d2h":
            per[trace] = per.get(trace, 0.0) + (end - start)
    if not per:
        return None
    done = [rec.instances[i] for i, r in enumerate(rec.requests)
            if r["done"] is not None]
    value = mean(per.get(inst, 0.0) for inst in done)
    return None if value is None else 1e3 * value
