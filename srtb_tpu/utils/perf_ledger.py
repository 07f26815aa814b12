"""Append-only perf ledger: every measurement becomes queryable history.

The repo's perf trajectory lived in two places that don't compose:
hand-written PERF.md rounds and driver-captured per-round bench
artifacts — neither queryable, neither keyed well enough to compare
apples to apples across hosts and commits.  The ledger is one JSONL
file of structured records keyed by the four things that make a perf
number comparable:

- ``plan``/``plan_signature_sha`` — WHAT ran (the SegmentProcessor
  plan id and a short hash of its full trace signature; two records
  with equal hashes executed the same compiled-program family);
- ``shape`` — the measured working set (log2n, channels, nbits);
- ``host_fp`` — WHERE it ran (a stable fingerprint of the host;
  cross-host comparisons must be calibrated, see tools/perf_gate.py);
- ``git_sha`` — WHICH code.

Writers: ``bench.py`` (``SRTB_PERF_LEDGER=path``), steady-state
pipeline runs (``Config.perf_ledger_path`` — one record per run at
drain end), ``tools/perf_gate.py`` captures, and
``tools/perf_ledger.py --import`` (the legacy per-round artifact
backfill).  Reader: ``tools/perf_report.py`` renders the trajectory.

Records carry ``samples_s`` (per-rep seconds) when the producer has
them — that is what makes the regression gate statistical instead of
a two-number diff.
"""

from __future__ import annotations

import hashlib
import json
import os
import time

from srtb_tpu.utils.logging import log

RECORD_TYPE = "perf_record"
RECORD_VERSION = 1


def host_fingerprint() -> str:
    """Short stable id of this host + software stack: records from
    different hosts (or after a jax/python upgrade) must not be
    compared raw.  Deliberately excludes anything run-local (cwd,
    pid, time)."""
    import platform
    parts = {
        "node": platform.node(),
        "machine": platform.machine(),
        "cpus": os.cpu_count(),
        "python": platform.python_version(),
    }
    try:
        import jax
        parts["jax"] = jax.__version__
    except Exception:  # pure-host tools must not require jax
        parts["jax"] = ""
    blob = json.dumps(parts, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:12]


def git_sha(root: str | None = None) -> str:
    """Current commit sha (short), "" outside a git checkout."""
    import subprocess
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short=12", "HEAD"],
            cwd=root or os.getcwd(), capture_output=True, text=True,
            timeout=10)
        return out.stdout.strip() if out.returncode == 0 else ""
    except (OSError, subprocess.SubprocessError):
        # SubprocessError covers TimeoutExpired (a wedged object
        # store) — provenance lookup must never abort the caller
        return ""


def signature_sha(signature: str | None) -> str:
    """Short hash of a full plan signature (the signature itself is a
    multi-KB JSON blob; the ledger needs equality, not contents)."""
    if not signature:
        return ""
    return hashlib.sha256(signature.encode()).hexdigest()[:16]


def make_record(source: str, value: float, unit: str,
                plan: str = "", plan_signature: str | None = None,
                shape: dict | None = None, platform: str = "",
                samples_s: list | None = None,
                extra: dict | None = None,
                ts: float | None = None,
                host_fp: str | None = None,
                git_sha_value: str | None = None) -> dict:
    """One ledger record.  ``source`` names the producer protocol
    ("bench", "steady", "gate", "import").  ``host_fp`` /
    ``git_sha_value`` default to the CURRENT host/commit; producers
    describing measurements they did not run (the legacy importer)
    pass explicit values — usually "" — instead of paying for, then
    discarding, the fingerprint hash and the git subprocess."""
    rec = {
        "type": RECORD_TYPE,
        "v": RECORD_VERSION,
        "ts": time.time() if ts is None else float(ts),
        "source": str(source),
        "value": float(value),
        "unit": str(unit),
        "plan": str(plan),
        "plan_signature_sha": signature_sha(plan_signature),
        "shape": dict(shape or {}),
        "platform": str(platform),
        "host_fp": host_fingerprint() if host_fp is None
        else str(host_fp),
        "git_sha": git_sha() if git_sha_value is None
        else str(git_sha_value),
    }
    if samples_s:
        rec["samples_s"] = [float(s) for s in samples_s]
    if extra:
        rec["extra"] = dict(extra)
    return rec


class PerfLedger:
    """Append-only JSONL; best-effort (a perf record must never abort
    the run it describes)."""

    def __init__(self, path: str):
        self.path = path
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)

    def append(self, record: dict) -> bool:
        try:
            with open(self.path, "a") as f:
                f.write(json.dumps(record, sort_keys=True) + "\n")
            return True
        except OSError as e:
            log.warning(f"[perf_ledger] append to {self.path} failed: "
                        f"{e}")
            return False

    def load(self) -> list[dict]:
        return load(self.path)


def load(path: str) -> list[dict]:
    """Parse perf records, oldest-first by file order, tolerating torn
    tails and foreign lines (the ledger may share a directory with
    journals)."""
    records = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line.startswith("{"):
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if rec.get("type") == RECORD_TYPE:
                    records.append(rec)
    except OSError:
        pass
    return records


def history(records: list[dict], plan: str, host_fp: str | None = None,
            shape: dict | None = None, max_records: int = 3) -> list:
    """Concatenated per-rep samples from the NEWEST records matching
    ``(plan, host_fp, shape)`` — the baseline side of a mid-run
    regression check (srtb_tpu/obs/regression.py).  Records without
    ``samples_s`` carry no statistical weight and are skipped; pass
    ``host_fp=None``/``shape=None`` to not filter on that key."""
    matches = []
    for rec in records:
        if rec.get("plan") != plan or not rec.get("samples_s"):
            continue
        if host_fp is not None and rec.get("host_fp") != host_fp:
            continue
        if shape is not None and rec.get("shape") != dict(shape):
            continue
        matches.append(rec)
    out: list[float] = []
    for rec in matches[-max(1, int(max_records)):]:
        out.extend(float(s) for s in rec["samples_s"])
    return out


def import_keys(records: list[dict]) -> set:
    """The idempotency keys already in the ledger: a re-run of
    ``--import`` must not duplicate history."""
    return {r["extra"]["import_key"] for r in records
            if r.get("extra", {}).get("import_key")}


def record_steady_state(cfg, stats, processor) -> None:
    """One "steady" record for a finished pipeline run (called by the
    runtime when ``Config.perf_ledger_path`` is set and the run
    processed at least one segment).  Value = lifetime Msamples/s over
    the run; per-segment samples live in the telemetry journal, not
    here (the ledger stays one line per run)."""
    path = getattr(cfg, "perf_ledger_path", "")
    if not path or not getattr(stats, "segments", 0):
        return
    try:
        _record_steady_state(cfg, stats, processor, path)
    except Exception as e:  # noqa: BLE001 — the module contract:
        # a perf record must never abort the run it describes (an
        # unwritable ledger dir, a wedged git lookup, a retired
        # processor — all reduce to a warning)
        log.warning(f"[perf_ledger] steady-state record failed: {e}")


def _record_steady_state(cfg, stats, processor, path: str) -> None:
    import math
    sig = None
    plan = getattr(processor, "plan_name", "")
    sig_fn = getattr(processor, "plan_signature", None)
    if sig_fn is not None:
        try:
            sig = sig_fn()
        except Exception:  # a retired/stub processor owes no signature
            sig = None
    try:
        import jax
        platform = jax.default_backend()
    except Exception:
        platform = ""
    n = int(getattr(cfg, "baseband_input_count", 0) or 0)
    shape = {
        "log2n": int(math.log2(n)) if n > 0 else 0,
        "channels": int(getattr(cfg, "spectrum_channel_count", 0) or 0),
        "nbits": int(getattr(cfg, "baseband_input_bits", 0) or 0),
    }
    extra = {
        "segments": int(stats.segments),
        "elapsed_s": round(float(stats.elapsed_s), 4),
        "stream": str(getattr(cfg, "stream_name", "") or ""),
    }
    PerfLedger(path).append(make_record(
        "steady", stats.msamples_per_sec, "Msamples/s", plan=plan,
        plan_signature=sig, shape=shape, platform=platform,
        extra=extra))
