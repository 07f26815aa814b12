"""Host time by the program's own spans, off the profiler's host plane.

Every host stage of the program is a ``jax.profiler.TraceAnnotation``
named ``srtb:<stage>`` (``srtb_tpu/utils/tracing.py``), so it lands in the
traced slice on the device's clock; ``benchmark/trace.py`` already keeps
every such event in ``rec.trace.host``.  This is how a cell whose driver
passes the program no journal path (the grid) gets its host metrics.
"""

from __future__ import annotations


def host_span_ms_per_seg(rec, args):
    """Milliseconds of the ``srtb:<args.name>`` annotations in the slice,
    per segment completed in it; nothing where the program opens no such
    span."""
    tr = rec.trace
    if tr is None or not tr.segments:
        return None
    want = f"srtb:{args['name']}"
    durs = [d for name, _start, d in tr.host if name == want]
    if not durs:
        return None
    return sum(durs) / tr.segments * 1e3


REDUCERS = {"trace_host_span_ms_per_seg": host_span_ms_per_seg}
