"""client.launch_lag_p95_ms: 95th percentile over the window's requests of
how late the arrival loop launched a request after it fell due (the
benchmark's own stamps, host clock).  Moves ``p95_ms``."""

from stats import percentile


def read(rec):
    lags = [r["launch"] - r["due"] for r in rec.requests
            if r["launch"] is not None]
    return 1e3 * percentile(lags, 95.0) if lags else None
