"""Fleet control tower: cross-device telemetry aggregation.

Every observability surface below this package is per-process and
per-lane — v11 span journals (utils/telemetry.py), event rings
(utils/events.py), /metrics (utils/metrics.py) each tell one lane's
story.  This package is the monitoring plane OVER them, the "one view
over composed modules" the FPGA pulsar-search stacks imply (PAPERS.md):

- :mod:`~srtb_tpu.obs.digest` — mergeable quantile digests
  (DDSketch-style relative-accuracy buckets) so distributions from
  many lanes/devices/runs merge without raw samples;
- :mod:`~srtb_tpu.obs.store` — the long-horizon rollup store:
  append-only JSONL segments with retention + idempotent compaction;
- :mod:`~srtb_tpu.obs.rollup` — the aggregator that tails journals
  (plaintext + rotated .gz) and event dumps, resumable by offset like
  the manifest WAL, and maintains the streaming rollups;
- :mod:`~srtb_tpu.obs.trace_join` — the cross-device Perfetto export:
  one trace with a process-track per pool member, where a migrated
  stream's flow arrows cross device tracks;
- :mod:`~srtb_tpu.obs.status` — the ``/fleet`` payload
  (gui/server.py) and the data behind ``tools/console.py``.
"""

from __future__ import annotations

__all__ = ["digest", "store", "rollup", "trace_join", "status"]
