"""engine.wake_lag_ms: mean over DStore ``wait`` spans that were blocked
when their key's ``put`` span ended (the wait started first) of the wait's
end minus the Put's end: from the producer's publish to the consumer's
thread holding the key's metadata (negative by a few microseconds where
the consumer woke before the Put's span closed).  A Get of a key put
earlier did not wait for it, and is left out.  Program spans (DScope
``wait``/``put``).  Moves ``p50_ms``."""

from stats import mean


def read(rec):
    put_end = {name: end for _, kind, name, _, end in rec.spans
               if kind == "put"}
    value = mean(end - put_end[name]
                 for _, kind, name, start, end in rec.spans
                 if kind == "wait" and name in put_end
                 and start < put_end[name])
    return None if value is None else 1e3 * value
