"""engine.dispatch_gap_ms: mean over invocations of the time from the end
of the last ``put`` span of a body's inputs to the body's entry (the
engine's wake-up, container slot and Gets).  A body whose inputs are all
staged from outside counts from its request span's start.  Program spans
(DScope ``put``/``request``) against the benchmark's body-entry stamps, on
one host clock.  Moves ``p50_ms``."""

from stats import mean


def read(rec):
    put_end, req_start = {}, {}
    for trace, kind, name, start, end in rec.spans:
        if kind == "put":
            put_end[name] = end
        elif kind == "request":
            req_start[trace] = start
    gaps = []
    for instance, fn, entered, _ in rec.bodies:
        if instance is None or fn not in rec.functions:
            continue
        inputs = [k for k in rec.functions[fn] if k not in rec.external]
        if inputs:
            ends = [put_end.get(f"{instance}:{k}") for k in inputs]
            if None in ends:
                continue
            ready = max(ends)
        elif instance in req_start:
            ready = req_start[instance]
        else:
            continue
        gaps.append(entered - ready)
    value = mean(gaps)
    return None if value is None else 1e3 * value
