"""End-to-end metrics, from the harness's own stamps (host clock)."""

from __future__ import annotations

import statistics


def rt_factor(rec, args):
    """New samples whose results reached the sinks inside the window, per
    second of window, over the configuration's sample rate."""
    done = sum(s.new_samples for s in rec.completed())
    return done / rec.seconds / rec.sample_rate


def latency_percentile_ms(rec, args):
    """Hand-over to sinks-returned, over every segment handed over in
    the window (those that finished after it closed too)."""
    lat = sorted((s.done - s.handover) * 1e3
                 for s in rec.window() if s.done > 0.0)
    if len(lat) < 2:
        return None
    q = float(args.get("percentile", 95))
    cuts = statistics.quantiles(lat, n=100, method="inclusive")
    return cuts[int(q) - 1]


def setup_s(rec, args):
    return rec.setup_s


REDUCERS = {
    "rt_factor": rt_factor,
    "latency_percentile_ms": latency_percentile_ms,
    "setup_s": setup_s,
}
