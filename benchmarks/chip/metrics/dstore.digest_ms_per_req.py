"""dstore.digest_ms_per_req: time in DStore ``digest`` spans (the content
digest of each Put: the copy to the host and the hash) per request, mean
over the requests that completed in the window, the same set as
``dstore.put_ms_per_req``.  Program spans (DScope ``digest``).  Moves
``p50_ms``."""

from stats import mean


def read(rec):
    per = {}
    for trace, kind, _, start, end in rec.spans:
        if kind == "digest":
            per[trace] = per.get(trace, 0.0) + (end - start)
    if not per:
        return None
    done = [rec.instances[i] for i, r in enumerate(rec.requests)
            if r["done"] is not None]
    value = mean(per.get(inst, 0.0) for inst in done)
    return None if value is None else 1e3 * value
