"""Per-layer metrics from the profiler trace of the traced slice, and
from the device itself.  Without a trace (``--trace 0``, or a run off the
chip) the trace readers return nothing."""

from __future__ import annotations

from benchmark import counts, peaks


def _segments_in_slice(rec) -> int:
    """Segments whose results reached the sinks during the traced slice
    (the harness's own stamps); 0 without a device trace."""
    tr = rec.trace
    if tr is None or not tr.devices:
        return 0
    return tr.segments


def op_ms_per_seg(rec, args):
    """Device milliseconds of the operations whose name matches
    ``pattern``, per segment of the slice."""
    segs = _segments_in_slice(rec)
    if not segs:
        return None
    return rec.trace.op_seconds(args["pattern"]) / segs * 1e3


def busy_ms_per_seg(rec, args):
    segs = _segments_in_slice(rec)
    return rec.trace.busy_s() / segs * 1e3 if segs else None


def idle_share(rec, args):
    tr = rec.trace
    if tr is None or not tr.devices or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)


def hbm_share(rec, args):
    """Bytes the algorithm's stages must move per segment on one chip
    (``counts.segment_bytes_per_chip``) over the device's busy time per
    segment, as a share of the chip's peak bandwidth.  Bound by bytes:
    the chain is FFTs and elementwise passes, a few operations a byte."""
    segs = _segments_in_slice(rec)
    if not segs:
        return None
    busy = rec.trace.busy_s() / segs
    need = counts.segment_bytes_per_chip(rec.params, rec.chips)
    peak = peaks.of(rec.device_kind)["hbm_bytes_per_s"]
    return 100.0 * need / busy / peak


def peak_hbm_gb(rec, args):
    return rec.peak_bytes / 1e9 if rec.peak_bytes else None


REDUCERS = {
    "trace_op_ms_per_seg": op_ms_per_seg,
    "trace_busy_ms_per_seg": busy_ms_per_seg,
    "trace_idle_share": idle_share,
    "trace_hbm_share": hbm_share,
    "device_peak_hbm_gb": peak_hbm_gb,
}
