"""Per-layer metrics from the program's own journal (``segment_span``
records, ``--telemetry_journal_path``): spans and counters the program
takes today.  A cell whose program writes no journal has no spans, and
these readers return nothing."""

from __future__ import annotations

import statistics


def stage_median_ms(rec, args):
    """Median of one host stage's milliseconds over the window's
    segments (``stages_ms.<stage>``)."""
    vals = [s["stages_ms"][args["stage"]] for s in rec.spans
            if args["stage"] in s.get("stages_ms", {})]
    return statistics.median(vals) if vals else None


def counter_delta_median(rec, args):
    """Median per-segment increase of a cumulative counter, scaled."""
    key = args["counter"]
    spans = rec.warm_spans[-1:] + rec.spans
    deltas = [b[key] - a[key] for a, b in zip(spans, spans[1:])
              if key in a and key in b]
    if not deltas:
        return None
    return statistics.median(deltas) * float(args.get("scale", 1.0))


def first_dispatch_s(rec, args):
    """Seconds of first dispatches (trace + compile or cache load + the
    first run of each program), as the journal's cumulative
    ``compile_ms`` stands after the warm-up."""
    spans = rec.warm_spans or rec.spans
    if not spans or "compile_ms" not in spans[-1]:
        return None
    return spans[-1]["compile_ms"] / 1e3


REDUCERS = {
    "journal_stage_median_ms": stage_median_ms,
    "journal_counter_delta_median": counter_delta_median,
    "journal_first_dispatch_s": first_dispatch_s,
}
