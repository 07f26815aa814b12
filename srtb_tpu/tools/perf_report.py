"""Render the perf trajectory from a perf ledger (utils/perf_ledger).

Groups records by (metric unit, shape, plan) and prints each group's
time-ordered trajectory — value, platform, git sha, host fingerprint,
compile time and roofline fraction where recorded — as markdown
tables (default) or one JSON document.  This is the queryable form of
the history PERF.md narrates; legacy per-round bench artifacts can be
backfilled with ``python -m srtb_tpu.tools.perf_ledger LEDGER --import
FILES``.

Usage: python -m srtb_tpu.tools.perf_report LEDGER.jsonl
           [--format md|json] [--source bench,import,...]
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from srtb_tpu.utils import perf_ledger as PL


def _group_key(rec: dict) -> str:
    shape = rec.get("shape") or {}
    log2n = shape.get("log2n", 0)
    plan = rec.get("plan") or "?"
    return f"{rec.get('unit', '?')} @ 2^{log2n} [{plan}]"


def trajectory(records: list[dict]) -> dict:
    """group key -> time-ordered rows.  Failed rounds (value 0) stay
    in the trajectory: an outage is history too."""
    groups: dict[str, list[dict]] = {}
    for rec in sorted(records, key=lambda r: r.get("ts", 0.0)):
        extra = rec.get("extra") or {}
        row = {
            "ts": rec.get("ts", 0.0),
            "when": time.strftime(
                "%Y-%m-%d %H:%M",
                time.localtime(rec.get("ts", 0.0))),
            "value": rec.get("value", 0.0),
            "source": rec.get("source", ""),
            "platform": rec.get("platform", ""),
            "git_sha": rec.get("git_sha", ""),
            "host_fp": rec.get("host_fp", ""),
            "n_samples": len(rec.get("samples_s") or []),
        }
        for k in ("compile_s", "roofline_frac", "overlap", "ring",
                  "import_key", "error", "segments"):
            if k in extra:
                row[k] = extra[k]
        groups.setdefault(_group_key(rec), []).append(row)
    return groups


def report(path: str, sources: list[str] | None = None) -> dict:
    records = PL.load(path)
    if sources:
        records = [r for r in records if r.get("source") in sources]
    groups = trajectory(records)
    out = {"ledger": path, "records": len(records), "groups": {}}
    for key, rows in sorted(groups.items()):
        measured = [r["value"] for r in rows if r["value"] > 0]
        out["groups"][key] = {
            "rows": rows,
            "best": max(measured) if measured else 0.0,
            "latest": measured[-1] if measured else 0.0,
            "failed_rounds": sum(1 for r in rows if r["value"] <= 0),
        }
    return out


def _md(rep: dict) -> str:
    lines = [f"# Perf trajectory — {rep['ledger']}", "",
             f"{rep['records']} perf records."]
    for key, g in rep["groups"].items():
        lines += ["", f"## {key}", "",
                  ("all rounds failed — no measured value yet"
                   if not g["best"] and g["failed_rounds"] else
                   f"best {g['best']}, latest {g['latest']}"
                   + (f", {g['failed_rounds']} failed round(s)"
                      if g["failed_rounds"] else "")),
                  "",
                  "| when | value | source | platform | git | host | "
                  "reps | note |", "|---|---|---|---|---|---|---|---|"]
        for r in g["rows"]:
            note = r.get("error", "")[:40] or (
                f"roofline {r['roofline_frac']}"
                if "roofline_frac" in r else "")
            lines.append(
                f"| {r['when']} | {r['value']} | {r['source']} | "
                f"{r['platform']} | {r['git_sha'][:8]} | "
                f"{r['host_fp'][:6]} | {r['n_samples']} | {note} |")
    return "\n".join(lines) + "\n"


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("ledger")
    p.add_argument("--format", choices=("md", "json"), default="md")
    p.add_argument("--source", default="",
                   help="comma-separated source filter "
                        "(bench,steady,gate,import)")
    args = p.parse_args(argv)
    sources = [s for s in args.source.split(",") if s] or None
    rep = report(args.ledger, sources)
    if not rep["records"]:
        # empty / missing / filtered-to-nothing ledger: a clear note,
        # not a failure — dashboards render before the first record
        # lands (same contract as telemetry_report on a fresh journal)
        note = {"note": f"no perf records in {args.ledger} yet",
                "records": 0}
        print(json.dumps(note) if args.format == "json"
              else f"# Perf trajectory\n\n{note['note']}\n")
        return 0
    if args.format == "json":
        print(json.dumps(rep, sort_keys=True))
    else:
        print(_md(rep))
    return 0


if __name__ == "__main__":
    sys.exit(main())
