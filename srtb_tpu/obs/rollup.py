"""The fleet aggregator: journals + event dumps -> streaming rollups.

Tails every lane's v11 span journal (the active plaintext arm AND the
rotated ``.1.gz`` / legacy ``.1`` generation) plus flight-recorder
event dumps, and maintains:

- per-minute downsampled series per ``(stream, device, plan)`` —
  segments / samples / detections / dumps, loss DELTAS localized from
  the journal's cumulative counters, device-time and batch occupancy
  sums (``rollup_minute`` rows);
- mergeable quantile digests (obs/digest.py) for the stage wall-clock,
  device-time and batch-size distributions (``rollup_digest`` rows,
  cumulative over the aggregator's lifetime);
- the fleet event timeline — migrations, device halts, device drains
  — as identity-keyed ``fleet_event`` rows (event dumps are full
  rewrites, so rows dedup by identity in the store's last-wins merge
  instead of by offset).

Resume is BY OFFSET like the manifest WAL: a ``cursor.json`` in the
store directory records, per journal, the active arm's byte offset +
a first-line signature (a rotation swaps the file under the same
path — the signature detects it and resets the offset), and, per
ROTATED generation, a content signature + consumed-record count — so
re-reading a generation whose earlier read hit a torn gzip tail
ingests only the records beyond the ones already counted.  Kill the
aggregator at any point and restart it: no span is double-counted.

Schema tolerance: mixed v1–v11 journals summarize, never KeyError —
records simply lack the newer fields and drop out of the rollups that
need them (the same reader contract as tools/telemetry_report.py).
"""

from __future__ import annotations

import gzip
import hashlib
import json
import os
import zlib

from srtb_tpu.obs.digest import QuantileDigest
from srtb_tpu.obs.store import RollupStore
from srtb_tpu.utils.telemetry import segment_wall

CURSOR_NAME = "cursor.json"
TMP_SUFFIX = ".srtb_tmp"

# fleet events worth a timeline row in the long-horizon store
FLEET_EVENT_TYPES = ("fleet.migrate", "fleet.device_halt",
                     "fleet.device_drain", "fleet.reinit",
                     "fleet.lane_failed", "incident")

# rotated-generation signatures kept in the cursor: bounds the cursor
# file however many rotations a long observation goes through
MAX_GEN_SIGS = 64


def _sig(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()[:16]


def _first_line_sig(path: str) -> str:
    """Signature of the active arm's first line (bounded read): a
    rotation replaces the file under the same path, and the first
    record of the NEW file differs from the old one's — the cursor's
    rotation detector.  "" while the file is empty or its first line
    is still torn (no newline yet)."""
    try:
        with open(path, "rb") as f:
            head = f.read(65536)
    except OSError:
        return ""
    nl = head.find(b"\n")
    if nl < 0:
        return ""
    return _sig(head[:nl])


def _read_gz_records(path: str) -> list[dict]:
    """Span records from a gzipped generation, tolerating a torn tail
    (crash / copy mid-write): the readable prefix parses, the torn
    remainder is dropped — the cursor's consumed count makes a later
    complete re-read ingest only what this read missed."""
    records = []
    try:
        with gzip.open(path, "rt") as f:
            for line in f:
                line = line.strip()
                if not line.startswith("{"):
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if rec.get("type") == "segment_span":
                    records.append(rec)
    except (OSError, EOFError, zlib.error):
        pass
    return records


class Aggregator:
    """One aggregation pass-holder over N journals + event dumps,
    writing rollups into a :class:`~srtb_tpu.obs.store.RollupStore`.

    Flushes write SNAPSHOTS of every touched rollup row (identity-
    keyed); the store's last-wins merge makes re-flushing an
    still-open minute safe.  The cursor persists at flush, so a
    restarted aggregator resumes from its offsets; the one documented
    gap: counts ingested after the last flush of a crashed aggregator
    re-ingest on restart (the cursor is the flush boundary), which
    last-wins resolves without double-counting.
    """

    def __init__(self, store: RollupStore, journals=(),
                 events_dumps=(), resolution_s: int = 60,
                 digest_alpha: float = 0.01):
        if resolution_s <= 0:
            raise ValueError("resolution_s must be positive")
        self.store = store
        self.journals = list(journals)
        self.events_dumps = list(events_dumps)
        self.resolution_s = int(resolution_s)
        self.digest_alpha = float(digest_alpha)
        self.cursor_path = os.path.join(store.directory, CURSOR_NAME)
        self._cursor = self._load_cursor()
        # rollup state (cumulative over this aggregator's lifetime)
        self._minutes: dict[str, dict] = {}
        self._digests: dict[tuple, QuantileDigest] = {}
        self._events: dict[str, dict] = {}
        self._plans: set[str] = set()
        self._prev: dict[str, dict] = {}  # per-stream previous record
        self._dirty: set = set()
        self.spans = 0

    @classmethod
    def from_config(cls, cfg, journals=(), events_dumps=()):
        """Build store + aggregator from the Config obs knobs; None
        when ``obs_store_dir`` is unset (the zero-cost-off pattern)."""
        d = str(getattr(cfg, "obs_store_dir", "") or "")
        if not d:
            return None
        store = RollupStore(
            d,
            retention_minutes=int(
                getattr(cfg, "obs_retention_minutes", 0) or 0))
        return cls(
            store, journals=journals, events_dumps=events_dumps,
            resolution_s=int(
                getattr(cfg, "obs_rollup_resolution_s", 60) or 60))

    # ------------------------------------------------------- cursor

    def _load_cursor(self) -> dict:
        try:
            with open(self.cursor_path) as f:
                cur = json.load(f)
            if isinstance(cur, dict):
                cur.setdefault("files", {})
                cur.setdefault("gens", {})
                return cur
        except (OSError, ValueError):
            pass
        return {"files": {}, "gens": {}}

    def _save_cursor(self) -> None:
        gens = self._cursor["gens"]
        if len(gens) > MAX_GEN_SIGS:
            # oldest-inserted first (dict order): drop the surplus
            for sig in list(gens)[:len(gens) - MAX_GEN_SIGS]:
                del gens[sig]
        tmp = self.cursor_path + TMP_SUFFIX
        with open(tmp, "w") as f:
            json.dump(self._cursor, f, sort_keys=True)
        os.replace(tmp, self.cursor_path)

    # ------------------------------------------------------ tailing

    def poll(self) -> dict:
        """One tail pass over every journal + event dump.  Returns
        ``{"spans": n, "events": m}`` newly ingested."""
        spans0, n_events = self.spans, 0
        for path in self.journals:
            self._poll_journal(path)
        for path in self.events_dumps:
            n_events += self._poll_events(path)
        return {"spans": self.spans - spans0, "events": n_events}

    def _poll_journal(self, path: str) -> None:
        from srtb_tpu.utils.telemetry import rotated_generation
        gen = rotated_generation(path)
        if gen:
            self._ingest_generation(gen, active_path=path)
        self._tail_active(path)

    def _ingest_generation(self, gen_path: str,
                           active_path: str = "") -> None:
        """A rotated generation, identified by its FIRST record (the
        same generation read torn then complete hashes identically,
        unlike the raw compressed bytes): consume only records beyond
        the cursor's count for that signature."""
        if gen_path.endswith(".gz"):
            records = _read_gz_records(gen_path)
        else:
            from srtb_tpu.tools.telemetry_report import load as _load
            records = _load(gen_path, include_rotated=False)
        if not records:
            return
        sig = _sig(json.dumps(records[0], sort_keys=True).encode())
        seen = int(self._cursor["gens"].get(sig, 0))
        if sig not in self._cursor["gens"] and active_path:
            # a generation seen for the FIRST time may be the old
            # active arm rotated out from under us: its leading spans
            # were already consumed through the offset tail — hand
            # that count off so they aren't ingested twice
            st = self._cursor["files"].get(active_path) or {}
            if st.get("rec_sig") == sig:
                seen = int(st.get("spans", 0))
        for rec in records[seen:]:
            self._ingest_span(rec)
        self._cursor["gens"][sig] = max(len(records), seen)

    def _tail_active(self, path: str) -> None:
        st = self._cursor["files"].setdefault(
            path, {"offset": 0, "sig": ""})
        sig = _first_line_sig(path)
        if not sig:
            return
        if sig != st.get("sig"):
            # rotation swapped a fresh file under this path (its old
            # contents are now the rotated generation, already
            # signature-tracked) — start over from byte 0
            st["offset"] = 0
            st["sig"] = sig
            st["rec_sig"] = ""
            st["spans"] = 0
        try:
            with open(path, "rb") as f:
                f.seek(st["offset"])
                chunk = f.read()
        except OSError:
            return
        # only complete lines: a torn tail stays for the next poll
        end = chunk.rfind(b"\n")
        if end < 0:
            return
        for raw in chunk[:end].split(b"\n"):
            raw = raw.strip()
            if not raw.startswith(b"{"):
                continue
            try:
                rec = json.loads(raw)
            except ValueError:
                continue
            if rec.get("type") == "segment_span":
                if not st.get("rec_sig"):
                    # canonical first-record signature: the identity
                    # this content will carry once rotated into a
                    # generation (see _ingest_generation's handoff)
                    st["rec_sig"] = _sig(
                        json.dumps(rec, sort_keys=True).encode())
                st["spans"] = int(st.get("spans", 0)) + 1
                self._ingest_span(rec)
        st["offset"] += end + 1

    def _poll_events(self, path: str) -> int:
        """Event dumps are FULL REWRITES (EventHub.dump_jsonl opens
        "w"), so offsets can't resume them; fleet events dedup by
        identity key instead — re-reading a dump re-derives the same
        rows and last-wins collapses them."""
        from srtb_tpu.tools.trace_export import load_events
        try:
            events = load_events(path)
        except OSError:
            return 0
        fresh = 0
        for e in events:
            if e.get("type") not in FLEET_EVENT_TYPES:
                continue
            ts = float(e.get("ts", 0.0))
            k = (f"e:{e.get('t', 0.0):.6f}:{e['type']}:"
                 f"{e.get('stream', '')}:{e.get('info', '')}")
            if k in self._events:
                continue
            fresh += 1
            self._events[k] = {
                "k": k, "type": "fleet_event",
                "minute": int(ts // self.resolution_s),
                "ts": round(ts, 3),
                "kind": e["type"],
                "stream": str(e.get("stream") or ""),
                "seg": int(e.get("seg", -1)),
                "info": str(e.get("info") or ""),
            }
            self._dirty.add(k)
        return fresh

    # ----------------------------------------------------- ingest

    def _ingest_span(self, rec: dict) -> None:
        self.spans += 1
        stream = str(rec.get("stream") or "")
        device = str(rec.get("device") or "")
        plan = str(rec.get("active_plan") or "")
        ts = float(rec.get("ts") or 0.0)
        minute = int(ts // self.resolution_s)
        k = f"m:{minute}:{stream}:{device}:{plan}"
        row = self._minutes.get(k)
        if row is None:
            row = self._minutes[k] = {
                "k": k, "type": "rollup_minute", "minute": minute,
                "t_start": minute * self.resolution_s,
                "stream": stream, "device": device, "plan": plan,
                "segments": 0, "samples": 0, "detections": 0,
                "dumps": 0, "loss_delta": 0,
                "packets_lost_delta": 0, "device_ms_sum": 0.0,
                "batch_segments": 0, "batch_waits_ms": 0.0,
            }
        row["segments"] += 1
        row["samples"] += int(rec.get("samples", 0))
        row["detections"] += int(rec.get("detections", 0))
        row["dumps"] += 1 if rec.get("dump") else 0
        # cumulative counters -> per-minute deltas (the journal's own
        # convention: consecutive-record differences localize a burst)
        prev = self._prev.get(stream)
        if prev is not None:
            for cum, delta in (("segments_dropped", "loss_delta"),
                               ("packets_lost", "packets_lost_delta")):
                a, b = prev.get(cum), rec.get(cum)
                if a is not None and b is not None:
                    row[delta] += max(0, int(b) - int(a))
        self._prev[stream] = rec
        dev_ms = rec.get("device_ms")
        if dev_ms is not None:
            row["device_ms_sum"] = round(
                row["device_ms_sum"] + float(dev_ms), 3)
            self._digest(("device_ms", device)).add(float(dev_ms))
        bs = rec.get("batch_size")
        if bs is not None:
            row["batch_segments"] += int(bs)
            self._digest(("batch_size", "")).add(int(bs))
        bw = rec.get("batch_wait_ms")
        if bw is not None:
            row["batch_waits_ms"] = round(
                row["batch_waits_ms"] + float(bw), 3)
        stages = rec.get("stages_ms") or {}
        for name, ms in stages.items():
            self._digest(("stage", str(name))).add(float(ms))
        stage_sum = float(segment_wall(stages))
        if stage_sum > 0.0:
            self._digest(("stage", "segment")).add(stage_sum)
        if plan:
            self._plans.add(plan)
        self._dirty.add(k)

    def _digest(self, key: tuple) -> QuantileDigest:
        d = self._digests.get(key)
        if d is None:
            d = self._digests[key] = QuantileDigest(
                alpha=self.digest_alpha)
        return d

    # ------------------------------------------------------ outputs

    def flush(self) -> int:
        """Write snapshots of every dirty minute/event row + ALL
        digest rows (cumulative, identity-keyed — last-wins keeps the
        newest snapshot), then persist the cursor.  Returns rows
        written."""
        rows = []
        for k in sorted(self._dirty):
            row = self._minutes.get(k) or self._events.get(k)
            if row is not None:
                rows.append(row)
        for (kind, label), dig in sorted(self._digests.items()):
            rows.append({
                "k": f"d:{kind}:{label}", "type": "rollup_digest",
                "kind": kind, "label": label,
                "digest": dig.to_dict(),
            })
        n = self.store.append_many(rows)
        self._save_cursor()
        self._dirty.clear()
        return n

    def plans(self) -> list[str]:
        return sorted(self._plans)


def main(argv=None) -> int:
    """Operator CLI: one aggregation pass (or a follow loop) over the
    given journals/event dumps into a rollup store.  Resumable — the
    store's cursor.json makes re-runs ingest only what's new."""
    import argparse
    import time
    p = argparse.ArgumentParser(
        description="aggregate lane journals into a fleet rollup store")
    p.add_argument("journals", nargs="+",
                   help="v11 span journal paths (one per lane)")
    p.add_argument("--store", required=True,
                   help="rollup store directory (cursor lives here)")
    p.add_argument("--events", action="append", default=[],
                   help="event dump path (repeatable)")
    p.add_argument("--retention-minutes", type=int, default=0)
    p.add_argument("--resolution-s", type=int, default=60)
    p.add_argument("--follow", type=float, default=0.0, metavar="S",
                   help="poll every S seconds until interrupted "
                        "(0 = one pass)")
    p.add_argument("--compact", action="store_true",
                   help="compact the store after aggregating")
    args = p.parse_args(argv)
    store = RollupStore(args.store,
                        retention_minutes=args.retention_minutes)
    agg = Aggregator(store, journals=args.journals,
                     events_dumps=args.events,
                     resolution_s=args.resolution_s)
    spans = events = rows = 0
    try:
        while True:
            got = agg.poll()
            spans += got["spans"]
            events += got["events"]
            rows += agg.flush()
            if not args.follow:
                break
            time.sleep(args.follow)
    except KeyboardInterrupt:
        pass
    out = {"spans": spans, "events": events, "rows": rows,
           "plans": agg.plans(), "store": args.store}
    if args.compact:
        out["compact"] = store.compact()
    print(json.dumps(out, sort_keys=True))
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
