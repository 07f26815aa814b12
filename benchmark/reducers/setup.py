"""What ``setup_s`` is made of, as the program names it (ISSUE 42).

Two places.  The program's registry (``srtb_tpu/utils/metrics``), read
in-process as ``harness.loss_counters`` reads it: the spans that lie
outside any segment (``construct``, ``chirp_bank``; a ``StageTimer`` feeds
every span it is handed to ``stage_seconds{stage=...}``) and the first
dispatches by program (``compile_seconds{program=...}``).  So the grid,
whose driver passes the program no journal path, reports them too.  And
two fields of the warm-up record that dumped a candidate, which lie
beside its ``stages_ms``.

A program that opens no such span, books no such label or journals no
such field (every commit before PR 42) gives these readers nothing to
read: they return nothing and raise nothing.

The registry is the process's: one run of ``run.py`` builds one
``Pipeline`` or one ``DMSearchPipeline``, and that is what is read.  A
process that runs several cells one after the other (the selftests) reads
their sum.
"""

from __future__ import annotations


def _registry():
    try:
        from srtb_tpu.utils.metrics import metrics
    except ImportError:
        return None
    return metrics


def stage_total_s(rec, args):
    """Seconds the program spent inside its span ``args.stage`` since the
    process began: ``stage_seconds{stage=...}.sum``."""
    metrics = _registry()
    if metrics is None:
        return None
    hist = metrics.histogram("stage_seconds",
                             labels={"stage": args["stage"]})
    return hist.sum if hist.count else None


def first_dispatch_by_program_s(rec, args):
    """Seconds of the first dispatches of ``args.programs`` (summed):
    ``compile_seconds{program=...}``."""
    metrics = _registry()
    if metrics is None:
        return None
    total = sum(metrics.get("compile_seconds", labels={"program": p})
                for p in args["programs"])
    return total or None


def warmup_dump_field(rec, args):
    """``args.field`` of the first warm-up record that dumped a candidate
    and carries it, times ``args.scale``."""
    for s in rec.warm_spans:
        if s.get("dump") and args["field"] in s:
            return s[args["field"]] * float(args.get("scale", 1.0))
    return None


REDUCERS = {
    "registry_stage_total_s": stage_total_s,
    "registry_first_dispatch_by_program_s": first_dispatch_by_program_s,
    "journal_warmup_dump_field": warmup_dump_field,
}
