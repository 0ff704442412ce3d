"""client.admit_lag_p95_ms: 95th percentile over the window's arrivals of
the ``admit`` span, which DServe's arrival loop opens at a request's due
time and closes when it launches the request.  The in-program twin of
``client.launch_lag_p95_ms``.  Program spans (DScope ``admit``).  Moves
``p95_ms``."""

from stats import percentile


def read(rec):
    lags = [end - start for _, kind, _, start, end in rec.spans
            if kind == "admit"]
    return 1e3 * percentile(lags, 95.0) if lags else None
