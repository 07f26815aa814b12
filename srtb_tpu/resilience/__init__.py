"""Fault tolerance for the streaming runtime (PR 4).

A transient-search backend is only useful if it survives a night of
observing: the reference keeps its SYCL pipeline alive across packet
loss and slow consumers, and streamed GPU pipelines treat continuity
under stalls as a first-class design constraint (PAPERS.md:
arXiv:2101.00941 CUDA-streams AstroAccelerate; arXiv:1806.01556
always-on FPGA modules).  This package gives the srtb_tpu runtime the
same property, in six composable pieces:

- :mod:`errors` — the typed taxonomy every other piece dispatches on:
  *transient* (retryable), *fatal* (escalate to clean shutdown),
  *data-loss* (retryable, but the occurrence is accounted), and
  *device* (a compute-side OOM / compile failure / device halt —
  never retried verbatim, handed to the self-healing ladder);
- :mod:`retry` — configurable retry with exponential backoff,
  deterministic jitter and deadlines, applied by the pipeline to
  ingest reads, H2D staging, dispatch, fetch, sink writes, and
  checkpoint flushes;
- :mod:`supervisor` — bounded restarts for crashed workers (the sink
  drain Pipe, the GUI server thread) with escalation to clean
  shutdown when the budget is exhausted;
- :mod:`degrade` — the graceful-degradation ladder: under sustained
  sink backlog or accounted loss, shed waterfall dumps first, then
  baseband dumps, then whole segments (the existing
  ``DropOldestSegmentBuffer``), every step counted;
- :mod:`demote` — self-healing compute: the plan-demotion ladder
  (micro_batch -> ring -> skzap -> fused_tail -> staged -> monolithic)
  that survives device OOM and compile faults on a cheaper plan, and
  bounded device-reinit recovery for halt faults — the compute-side
  twin of the supervisor;
- :mod:`faults` — deterministic fault injection (``Config.fault_plan``)
  arming named sites to raise/stall/corrupt — or fail like the
  accelerator runtime (oom / compile_fail / device_halt, with the real
  jax exception strings) — on scheduled segment indices, zero-cost
  when off (the same None-hook pattern as the runtime sanitizer), so
  every recovery path above is testable on CPU CI
  (``tools/chaos_soak.py`` composes them into randomized soaks; an
  optional stream selector ``beam3:dispatch:oom@4`` scopes an entry
  to one fleet lane);
- :mod:`admission` — the multi-tenant fleet's admission gate:
  capacity-bounded concurrent streams with a priority-ordered wait
  queue, every admit/queue/reject decision a stream-labeled counter
  (``pipeline/fleet.py`` consumes it; ``degrade.FleetShedPolicy`` is
  its overload-time twin, shedding the lowest-priority real-time
  stream first under fleet-wide sink pressure).

Everything is surfaced: retries, requeues, restarts, shed dumps, the
degradation level, plan demotions/promotions, device reinits and the
active-plan ladder level are Prometheus counters/gauges and journal
fields (telemetry schema v4).
"""
