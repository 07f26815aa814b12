"""One more reader of the program's journal: the stages of the warm-up
segment that dumped a candidate."""

from __future__ import annotations


def warmup_dump_stage_s(rec, args):
    """Seconds of ``args.stages`` (summed) in the first warm-up segment
    that dumped a candidate and journals them all."""
    for s in rec.warm_spans:
        ms = s.get("stages_ms", {})
        if s.get("dump") and all(k in ms for k in args["stages"]):
            return sum(ms[k] for k in args["stages"]) / 1e3
    return None


REDUCERS = {
    "journal_warmup_dump_stage_s": warmup_dump_stage_s,
}
