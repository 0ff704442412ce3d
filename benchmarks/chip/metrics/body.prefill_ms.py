"""body.prefill_ms: mean device time of one prefill step (a module whose
kind is ``prefill``) among the executions in the traced slice.  Device
trace, by module name.  Moves ``p50_ms``."""

from stats import mean


def read(rec):
    lo, hi = rec.window
    value = mean(e - s for name, s, e in rec.trace.modules
                 if lo <= s and e <= hi
                 and rec.modules.get(name, {}).get("kind") == "prefill")
    return None if value is None else 1e3 * value
