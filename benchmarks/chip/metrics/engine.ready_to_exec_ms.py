"""engine.ready_to_exec_ms: mean over invocations of the time from the end
of the last ``put`` span of a body's inputs to the start of its ``exec``
span (the consumer's wake-up, its other Gets and the wait for an execution
slot).  A body whose inputs are all staged from outside counts from its
request span's start.  The in-program twin of ``engine.dispatch_gap_ms``,
which times the same interval to the benchmark's body-entry stamp.
Program spans (DScope ``put``/``request``/``exec``).  Moves ``p50_ms``."""

from stats import mean


def read(rec):
    put_end, req_start, execs = {}, {}, []
    for trace, kind, name, start, end in rec.spans:
        if kind == "put":
            put_end[name] = end
        elif kind == "request":
            req_start[trace] = start
        elif kind == "exec":
            execs.append((trace, name, start))
    gaps = []
    for instance, fn, start in execs:
        if fn not in rec.functions:
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
        gaps.append(start - ready)
    value = mean(gaps)
    return None if value is None else 1e3 * value
