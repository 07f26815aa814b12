"""Runtime configuration.

TPU-native re-design of the reference's two-tier config system
(ref: config.hpp:80-249 runtime struct; program_options.hpp:34-309 parsing
with precedence CLI > config file > defaults; arithmetic expressions in
values, e.g. ``2 ** 30``; comma-split lists for multi-receiver options).

Differences from the reference, by design:
- a frozen-ish dataclass passed explicitly instead of a mutable global
  (jit-friendly: derived static quantities hang off this object);
- TPU-specific knobs (`devices`, `dm_list` for multi-chip DM trials).
"""

from __future__ import annotations

import dataclasses
import sys
from dataclasses import dataclass, field

from srtb_tpu.utils.expression import parse_number
from srtb_tpu.utils.logging import log

BITS_PER_BYTE = 8


@dataclass
class Config:
    """Runtime configuration (ref: config.hpp:80-249, same option names)."""

    config_file_name: str = "srtb_config.cfg"

    # count of samples per segment transferred to the device; power of 2
    baseband_input_count: int = 1 << 28
    # bit width of one input sample; negative = signed integer
    baseband_input_bits: int = 8
    # baseband format: simple, interleaved_samples_2 (alias naocpsr_roach2),
    # naocpsr_snap1, gznupsr_a1, gznupsr_a1_v2_1 (ref: io/backend_registry.hpp)
    baseband_format_type: str = "simple"
    # lowest frequency of received baseband signal, MHz
    baseband_freq_low: float = 1000.0
    # bandwidth, MHz (may be negative for inverted bands)
    baseband_bandwidth: float = 500.0
    # samples / second
    baseband_sample_rate: float = 1000e6
    # overlap consecutive segments by nsamps_reserved to mask dedispersion edges
    baseband_reserve_sample: bool = True
    # target dispersion measure, pc cm^-3
    dm: float = 0.0
    # DM trial list for multi-chip DM search (TPU extension; empty = single dm)
    dm_list: list = field(default_factory=list)

    udp_receiver_address: list = field(default_factory=lambda: ["10.0.1.2"])
    udp_receiver_port: list = field(default_factory=lambda: [12004])
    udp_receiver_cpu_preferred: list = field(default_factory=lambda: [0])
    # "block": counter-aligned blocks with reorder tolerance
    # (udp_receive_block_worker, ref: udp_receiver.hpp:180-272);
    # "continuous": strictly sequential gap-free stream, payloads straddle
    # segment boundaries (continuous_udp_receiver_worker, ref: 42-168)
    udp_receiver_mode: str = "block"
    # packet provider for block mode (ref dispatch:
    # udp_receiver_pipe.hpp:158-187): "recvmmsg" = batched syscalls
    # (native, default), "packet_ring" = AF_PACKET TPACKET_V3 mmap ring
    # (native, needs CAP_NET_RAW), "recvfrom" = pure-Python fallback
    udp_packet_provider: str = "recvmmsg"
    # interface the packet_ring provider captures on
    udp_packet_ring_interface: str = "lo"
    # SO_RCVBUF request for the receiver sockets (the reference hardcodes
    # its SO_RCVBUF, recvmmsg_packet_provider.hpp:79; a knob because the
    # right size is deployment-specific: big enough to ride out a
    # compile-time stall, small enough that overload surfaces as prompt
    # accounted loss instead of seconds of silent latency)
    udp_receiver_rcvbuf_bytes: int = 1 << 28

    input_file_path: str = ""
    input_file_offset_bytes: int = 0
    baseband_output_file_prefix: str = "srtb_baseband_output_"
    baseband_write_all: bool = False
    # stamp segment timestamps deterministically from the STREAM
    # OFFSET instead of the wall clock (io/file_input.py
    # DeterministicTimestampReader): the same segment gets the same
    # stamp in every run and every resume, so file-mode artifact names
    # (timestamp-derived when no UDP counter exists) reproduce across
    # runs — what makes an archive replay's output set comparable
    # byte-for-byte against a golden run, and what the crash/archive
    # soaks' exactly-once path+SHA-256 equality gates build on.
    # File sources only; ignored for UDP (real packets carry counters).
    deterministic_timestamps: bool = False

    log_level: int = 3

    mitigate_rfi_average_method_threshold: float = 10.0
    mitigate_rfi_spectral_kurtosis_threshold: float = 1.1
    # "11-12, 15-90" style frequency pairs to zap
    mitigate_rfi_freq_list: str = ""

    spectrum_sum_count: int = 1
    # count of complex channels in spectrum waterfall
    spectrum_channel_count: int = 1 << 15

    signal_detect_signal_noise_threshold: float = 6.0
    signal_detect_channel_threshold: float = 0.9
    signal_detect_max_boxcar_length: int = 1024

    # ---- search mode (pipeline/registry.py registered modes) ----
    # "single_pulse": the reference's boxcar cascade.  "periodicity":
    # single-pulse PLUS a harmonic-summed power-spectrum search over
    # the dedispersed time series with phase-folded profiles at the
    # top candidates (ops/periodicity.py; the FPGA pulsar-search
    # paper's module set), inside the same traced program — every
    # execution plan (fused/staged/ring/micro-batch) carries it.
    # Registered modes land in the plan auditor, the demotion ladder
    # (which sheds the mode FIRST on a device fault) and the fleet
    # automatically.
    search_mode: str = "single_pulse"
    # max harmonics summed incoherently (ladder 1, 2, 4, ... <= this)
    periodicity_harmonics: int = 8
    # top-K candidates folded per stream (static shape)
    periodicity_candidates: int = 4
    # phase bins of each folded pulse profile
    periodicity_fold_bins: int = 64
    # exclude power-spectrum bins below this (DC + red-noise leakage)
    periodicity_min_bin: int = 2
    # a segment is "positive" (candidate files written) when any
    # folded candidate's harmonic-summed score reaches this MARGIN
    # above the trials-expected noise maximum: the per-bin score is
    # ~exponential under noise, so its max over (searched bins x
    # harmonic levels) trials sits near ln(trials) — the gate
    # compares against ln(trials) + this margin (Gumbel scale ~1 per
    # unit; 5 = roughly an e^-5 per-segment false-positive rate).
    # Candidates are always computed and journaled regardless — the
    # gate only decides whether the segment writes candidate files.
    periodicity_snr_threshold: float = 5.0

    thread_query_work_wait_time: int = 1000

    gui_enable: bool = False
    gui_pixmap_width: int = 1920
    gui_pixmap_height: int = 1080
    # serve live waterfall frames over HTTP on this port (0 = disabled;
    # TPU-headless replacement for the reference's Qt windows)
    gui_http_port: int = 0

    # ---- TPU-specific options (no reference equivalent) ----
    # number of devices to use; 0 = all local devices
    n_devices: int = 0
    # use two-float (df64) on-device chirp generation instead of host f64
    use_emulated_fp64: bool = False
    # resume state file for file-mode streaming ("" = disabled)
    checkpoint_path: str = ""
    # durable exactly-once outputs (io/manifest.py): append-only,
    # CRC'd run-manifest WAL recording intent->commit for every sink
    # artifact plus the checkpoint consistency point.  On startup the
    # manifest is recovered (torn tail truncated, uncommitted intents
    # rolled back, committed segments rebuilt into a done-set so a
    # resumed run skips already-written artifacts instead of
    # duplicating them).  Verify/repair offline with
    # `python -m srtb_tpu.tools.fsck`.  "" = disabled.
    run_manifest_path: str = ""
    # arm the WAL's two durability points (io/manifest.py): the
    # publish barrier (pending intents fdatasync'd between an
    # artifact's temp write and its atomic rename — no artifact
    # reaches its final name before the WAL durably holds the intent)
    # and the checkpoint consistency-point record.  0 drops both:
    # process-death (SIGKILL) recovery is unaffected — the page cache
    # survives the process — but power loss may then leak an
    # untracked renamed artifact.
    manifest_fsync: bool = True
    # record a CRC32 of every committed artifact's content in the WAL
    # (fsck's deep bit-rot check).  Costs ~1 ms per dumped MB on the
    # sink path; 0 drops to existence+size verification — worth it
    # only for deployments dumping multi-GB baseband per candidate.
    manifest_hash: bool = True
    # persistent XLA compile cache dir; the FFTW-wisdom analog
    # ("" = $JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache;
    # "off" = disabled)
    fft_fftw_wisdom_path: str = ""
    # AOT executable cache dir ("" = disabled): persists the segment
    # plan's *compiled executables* across process restarts
    # (utils/aot_cache.py) — a warm restart that skips XLA entirely.
    # Off on CPU backends unless SRTB_AOT_ALLOW_CPU=1.
    aot_plan_path: str = ""
    # segment R2C strategy:
    # auto | monolithic | four_step | mxu | pallas | pallas2
    fft_strategy: str = "auto"
    # longest 1-D row length handed to XLA's FFT directly; longer rows
    # recurse into the four-step decomposition (0 = the library default,
    # ops/fft._XLA_FFT_LEN_CAP = 2^16 measured on v5e).  Lowering it
    # forces the recursion at tiny shapes — how the multichip dryrun
    # exercises the production 2^30 in-shard code path without 2^30
    # samples
    fft_len_cap: int = 0
    # use Pallas fused kernels where available (fused RFI-s1 + df64
    # chirp-multiply, VMEM row-FFT waterfall C2C)
    use_pallas: bool = False
    # fused SK-zap + time-series Pallas kernel: separate knob because it
    # measured *slower* than the jnp pair at bench shapes
    # (not re-measured on this JAX) — opt-in for shapes where the 2-read
    # pass wins
    use_pallas_sk: bool = False
    # fused spectrum tail ("auto" | "on" | "off"): fold RFI stage 1 +
    # the dedispersion chirp into the forward FFT's final (Hermitian
    # post-process) pass so the spectrum is written to HBM exactly once,
    # already zapped/normalized/masked/chirped; with use_pallas +
    # use_pallas_sk the SK zap + detection time series additionally fold
    # into the waterfall FFT's write (ops/pallas_fft.fft_rows_skzap_ri)
    # and the detect stage never re-reads the waterfall.  "auto" = on
    # for every plan whose final pass can host the epilogue (four_step /
    # mxu / pallas / pallas2 / staged), off for the monolithic XLA R2C
    # custom call; "on" forces it (errors on monolithic); "off"
    # restores the legacy unfused chain.
    fused_tail: str = "auto"
    # escape hatch: force the exact per-element df64 chirp evaluation
    # (~3 df64 divisions/channel) instead of the anchored-Taylor fast
    # path that is the default everywhere (segment plans, Pallas
    # kernels, DM-grid on-device banks) — a paranoia/A-B knob; the
    # anchored path agrees with the exact one to ~1e-9 turns
    # (ops/dedisperse.anchored_chirp_consts error budget)
    chirp_exact: bool = False
    # incremental H2D overlap-save ring ("auto" | "on" | "off"): keep
    # each segment's reserved tail device-resident as a raw-byte carry
    # so every warm dispatch uploads only the stride's NEW bytes — H2D
    # bytes per segment drop by exactly the reserved fraction,
    # bit-identically (pipeline/segment.py ring plans; the carry
    # donation is a proven input->output alias, checked by the plan
    # audit).  "auto" = on whenever overlap-save reserves a byte-
    # aligned non-empty tail; "on" forces it (errors when nothing is
    # reserved); "off" restores full per-segment uploads and the file
    # reader's legacy seek-back re-reads.  Cold full uploads (first
    # segment, watchdog requeue, dispatch retry, shed, checkpoint
    # resume) re-arm the carry from the retained host buffer.
    ingest_ring: str = "auto"
    # bounded window of segments dispatched to the device before the
    # oldest result is drained (pipeline/runtime.py async engine):
    # ingest + unpack + H2D staging of segment k+1..k+W-1 run while the
    # device computes segment k, and fetch polls device readiness
    # instead of blocking.  1 = fully serial (the A/B reference leg);
    # 2-3 hides host time under device compute (the reference's
    # queue-capacity-2 pipe graph, config.hpp:40-43)
    inflight_segments: int = 2
    # micro-batch: stack B consecutive segments into ONE jit call
    # (vmapped fused plan) to amortize per-dispatch host overhead
    # over B segments.
    # 1 = off; >1 requires the fused plan (not staged)
    micro_batch_segments: int = 1
    # opt-in runtime sanitizer (analysis/sanitizer.py): traps implicit
    # device->host transfers, NaN/Inf at segment-plan boundaries,
    # stage shape/dtype contract breaks, wrong-thread access to engine
    # window state, leaked threads, and makes use-after-donate loud on
    # every backend.  Serializes dispatch — a debugging mode with zero
    # cost when off.  A/B methodology: PERF.md "Sanitizer".
    sanitize: bool = False
    # opt-in runtime concurrency checker (analysis/tsan.py): lockdep
    # acquisition-order graph with live cycle traps, held-too-long
    # stall log, and claim-on-first-use ownership guards on fleet lane
    # state and batch-former group slots.  The fleet holds None when
    # off — zero wrapper indirection on the hot path.  Driven under
    # schedule perturbation by tools/race_soak.py.
    tsan: bool = False
    # fail-fast watchdog on the per-segment device sync (seconds,
    # 0 = disabled): a wedged accelerator runtime otherwise hangs the
    # observation silently — on expiry the process aborts through the
    # termination handler (loud stacktrace), matching the reference's
    # fail-loudly philosophy (ref: util/termination_handler.hpp)
    segment_deadline_s: float = 0.0
    # ---- resilience (srtb_tpu/resilience/) ----
    # retry budget for the pipeline's guarded operations (ingest read,
    # H2D staging, dispatch, fetch, sink write, checkpoint flush);
    # includes the first attempt, <= 1 disables retries entirely
    # (zero-cost-off, like the sanitizer).  Only failures classified
    # transient/data-loss by resilience/errors.py are retried.
    retry_max_attempts: int = 3
    # exponential backoff: base * 2^(attempt-1), capped, with
    # deterministic +/-25% jitter (hash of site+attempt, not random)
    retry_backoff_base_s: float = 0.05
    retry_backoff_max_s: float = 2.0
    # total wall-clock budget of one guarded operation including its
    # backoff sleeps (0 = unbounded): bounds worst-case added latency
    retry_deadline_s: float = 0.0
    # segment watchdog: with segment_deadline_s > 0, an in-flight
    # segment whose fetch never becomes ready within the deadline is
    # cancelled and re-dispatched up to this many times before the
    # run escalates (0 keeps the legacy abort-on-deadline behavior).
    # Scope: the requeue covers the drain-head COMPUTE wedge (results
    # never materialize).  A wedge inside a blocking D2H transfer that
    # already started (the sink's lazy multi-GB waterfall fetch) is
    # uninterruptible from Python and still takes the legacy fail-fast
    # abort after segment_deadline_s — loud exit over a silent hang.
    segment_watchdog_requeues: int = 0
    # bounded restarts for crashed workers (sink drain pipe, GUI
    # server): this many restarts within supervisor_window_s, then
    # escalation to clean shutdown; 0 disables supervision (every
    # crash propagates immediately, the pre-resilience behavior)
    supervisor_max_restarts: int = 3
    supervisor_window_s: float = 60.0
    # graceful-degradation ladder (resilience/degrade.py): under
    # sustained sink backlog or accounted loss, shed waterfall dumps,
    # then baseband dumps, then name whole-segment loss.  Hysteresis:
    # step after degrade_hold_segments consecutive drains above
    # degrade_queue_high occupancy; recover below degrade_queue_low.
    degrade_enable: bool = True
    degrade_queue_high: float = 0.9
    degrade_queue_low: float = 0.25
    degrade_hold_segments: int = 3
    # ---- self-healing compute (resilience/demote.py) ----
    # plan-demotion ladder for device OOM / compile faults: "auto"
    # walks search_mode -> micro_batch -> ring -> skzap -> fused_tail
    # -> staged -> monolithic (the registry's canonical order,
    # cumulatively, skipping rungs the active config
    # doesn't use); an explicit comma list selects a subset in that
    # order; "off" disables demotion (device faults escalate like any
    # fatal).  Each demotion rebuilds the segment plan from the rung's
    # config (the AOT cache misses cleanly via plan_signature) and
    # re-dispatches the faulted segment cold from its retained host
    # buffer.  Every demotion-ladder target is audited: the plan-audit
    # CI gate proves each rung resolves to a carded plan family.
    plan_ladder: str = "auto"
    # promotion probe: after this many consecutively healthy segments
    # on a demoted plan, step one rung back up (the next dispatch
    # probes the richer plan; a recurring fault just demotes again).
    # 0 = stay demoted for the rest of the run.
    promote_after_segments: int = 0
    # device-halt recovery: tear down in-flight device state, clear
    # the jax caches, rebuild the processor (fresh executables on the
    # new backend handle) and re-dispatch in-flight segments from
    # their retained host buffers — at most this many reinits within
    # device_reinit_window_s, then escalation (a flapping device must
    # not flap forever).  0 disables reinit recovery.
    device_reinit_max: int = 2
    device_reinit_window_s: float = 300.0
    # deterministic fault injection (resilience/faults.py):
    # "site:action@index,..." with sites ingest|h2d|dispatch|fetch|
    # sink_write|checkpoint and actions raise|fatal|corrupt|
    # stall=SECONDS, plus the device-fault actions oom|compile_fail|
    # device_halt (h2d/dispatch/fetch sites only — they raise with
    # the real jax exception strings so the self-healing ladder's
    # string classifier is exercised); "" = off (zero cost)
    fault_plan: str = ""
    # bounded join of worker threads at shutdown (pipeline sink pipe,
    # ThreadedPipeline drain): on expiry the wedged thread is reported
    # (name + stack) via utils/termination, still-queued segments are
    # accounted as segments_dropped, and shutdown proceeds WITHOUT
    # flushing the wedged sink's writer pools.  0 (default) waits
    # forever: a slow-but-healthy final flush of a multi-GB waterfall
    # must not be cut short and silently lose dumps — arm this only
    # for real-time deployments that prefer bounded exit over
    # completeness (recommended 120-300 there).
    shutdown_join_timeout_s: float = 0.0
    # ---- multi-tenant stream fleet (pipeline/fleet.py) ----
    # label of THIS stream in a fleet: stamps telemetry spans (v6
    # ``stream`` field), per-stream Prometheus labels, /healthz
    # per-stream staleness, and scopes fault_plan entries carrying a
    # stream selector ("stream0:dispatch:oom@3").  "" = unnamed
    # single-stream run (everything reads exactly as before).
    stream_name: str = ""
    # admission/shedding priority of this stream (higher = more
    # important): when the fleet is over capacity, lower-priority
    # streams are queued/rejected first, and under fleet-wide sink
    # pressure the lowest-priority REAL-TIME stream is shed first
    # (resilience/degrade.FleetShedPolicy).
    stream_priority: int = 0
    # max concurrently admitted streams in a StreamFleet (0 = no
    # admission limit); streams beyond capacity are queued (up to
    # fleet_queue_limit, priority order) or rejected.  Read from the
    # FLEET config (the first spec's cfg), not per stream.
    fleet_max_streams: int = 0
    # queued-stream slots behind the admission gate (0 = reject
    # immediately when over capacity)
    fleet_queue_limit: int = 0
    # cross-tenant continuous batching: max segments from DIFFERENT
    # lanes sharing a plan_cache_key folded into one vmapped device
    # dispatch (pipeline/fleet._BatchFormer).  0 or 1 = off (every
    # lane dispatches solo, bit-identical to the pre-batching fleet).
    # Read from the FLEET config (the first spec's cfg), not per
    # stream.  Batched lanes trade bit-exactness of float artifacts
    # for dispatch amortization: .bin candidates stay bitwise equal,
    # .tim/.npy match solo within the documented vmap tolerance.
    fleet_batch_max: int = 0
    # how long a partially formed batch may wait for co-tenants
    # before it is flushed anyway (milliseconds) — a lone tenant
    # never waits longer than this for neighbors that may not come
    fleet_batch_linger_ms: float = 2.0
    # elastic device pool (pipeline/pool.py): number of pool members
    # the fleet places lanes across.  0/1 = the single-device fleet
    # (bit-identical to the pre-pool engine).  >= 2 on an accelerator
    # host maps onto real jax.devices() (capped at the hardware
    # count); on CPU it builds a deterministic VIRTUAL pool — N
    # logical devices with distinct plan caches / batch families /
    # HALT domains on one physical device (what CI's migration gates
    # run on).  Read from the FLEET config, not per stream.
    fleet_devices: int = 0
    # SLO-driven rebalance: when the burn-rate tracker (utils/slo.py)
    # marks a stream degraded/burning and a strictly less-loaded
    # healthy pool member exists, live-migrate that stream onto it
    # before the error budget is spent.  Needs fleet_devices >= 2 and
    # an armed SLO objective.  Read from the FLEET config.
    migrate_on_burn: bool = False
    # live-migration drain budget (seconds): how long a TRUSTED
    # migration (rebalance / rolling restart — the source device is
    # healthy) may spend draining the lane's in-flight window before
    # the remainder moves via cold re-dispatch instead.  Halted-device
    # migrations never drain (the in-flight results died with the
    # device); cold re-dispatch is lossless either way.
    drain_deadline_s: float = 5.0
    # segment-span telemetry journal: one JSONL record per processed
    # segment (per-stage wall clock, queue depth, loss counters,
    # detection count, dump decision — utils/telemetry.py); "" disables.
    # Summarize with `python -m srtb_tpu.tools.telemetry_report`.
    telemetry_journal_path: str = ""
    # size-rotate the journal when the active file would exceed this
    # (one previous generation kept)
    telemetry_journal_max_bytes: int = 64 << 20
    # gzip the rotated generation (<path>.1.gz instead of <path>.1):
    # a long soak's journal history stays bounded AND small; the
    # reader/report handle both transparently.  0 keeps plaintext.
    telemetry_journal_compress: bool = True
    # ---- causal tracing + flight recorder (utils/events.py) ----
    # arm the process-global event hub: every SegmentWork carries a
    # trace_id and every subsystem that touches it (stage edges,
    # retries, heal/demote decisions, degrade/admission, watchdog,
    # supervisor, ring transitions, manifest records) emits typed
    # monotonic-clocked events onto a bounded per-thread ring — the
    # always-on flight recorder incident bundles and
    # tools/trace_export.py read.  0 disarms (the zero-cost-off
    # None-hook path; PERF.md round 17 A/B).  Process-global, like
    # the metrics registry.
    events_enable: bool = True
    # flight-recorder ring slots PER THREAD (O(ring) memory, no
    # per-event allocation growth)
    events_ring_size: int = 4096
    # write the flight-recorder contents (merged, oldest-first JSONL)
    # here at Pipeline.close() — the input of
    # `python -m srtb_tpu.tools.trace_export`; "" disables
    events_dump_path: str = ""
    # ---- incident bundles (utils/incidents.py) ----
    # on any escalation (LadderExhausted, ReinitBudgetExceeded,
    # WatchdogEscalation, wedged sink, failed fleet lane,
    # manifest-recovery LOSS) dump a self-contained bundle directory
    # here: flight-recorder tail, the offending segment's causal
    # trace, active plan + signature, config + metrics snapshots, last
    # journal spans.  Atomic (temp+rename), rate-limited and bounded
    # in count.  "" disables.
    incident_dir: str = ""
    incident_max_bundles: int = 8
    incident_min_interval_s: float = 30.0
    # ---- SLO burn-rate objectives (utils/slo.py) ----
    # per-stream error-budget burn evaluation over a fast + slow
    # window pair; states ok / degraded (violations within budget) /
    # burning (both windows above slo_burn_threshold) on /healthz and
    # as slo_burn_rate / slo_state gauges on /metrics.  Each objective
    # arms independently: latency (per-segment host wall clock >
    # slo_latency_ms counts against slo_latency_budget), loss
    # (accounted whole-segment drops against slo_loss_budget),
    # staleness (gap beyond slo_staleness_s against
    # slo_staleness_budget as a window fraction).  0 targets = off.
    slo_latency_ms: float = 0.0
    slo_latency_budget: float = 0.01
    slo_loss_budget: float = 0.0
    slo_staleness_s: float = 0.0
    slo_staleness_budget: float = 0.05
    slo_fast_window_s: float = 300.0
    slo_slow_window_s: float = 3600.0
    slo_burn_threshold: float = 1.0
    # sensitivity objective (pulse-injection canary feed): allowed
    # fraction of FAILED canary checks before the burn rate reads 1.0
    # (> 0 arms; needs canary_every_segments > 0 to get observations)
    slo_sensitivity_budget: float = 0.0
    # ---- science observatory (srtb_tpu/quality/) ----
    # on-device per-segment data-quality statistics as a cheap
    # epilogue side-output of the segment plans: zapped-bin fraction,
    # coarse RFI occupancy map, spectral-kurtosis summary, bandpass
    # mean/variance + EWMA drift detector, dead/hot channel flags —
    # exported as quality_* gauges, journaled on segment spans
    # (telemetry v9) and rendered by tools/quality_report.py.  Enters
    # the traced program (trace-relevant: plans with/without the
    # epilogue are different programs and miss the AOT cache cleanly).
    quality_stats: bool = False
    # coarse bins of the occupancy/bandpass maps (trace-relevant:
    # static output shape)
    quality_coarse_bins: int = 64
    # a channel is DEAD below this multiple of the median channel
    # power, HOT above the hot multiple (trace-relevant constants)
    quality_dead_threshold: float = 0.1
    quality_hot_threshold: float = 10.0
    # read every k-th spectrum bin / waterfall sample for the quality
    # statistics (trace-relevant).  Telemetry does not need every bin:
    # subsampling scales the epilogue's read volume — and the producer
    # recompute XLA sometimes chooses for a second consumer — down by
    # k.  1 = exact statistics.
    quality_subsample: int = 8
    # host-side EWMA drift detector on the bandpass mean: alert when
    # an observation sits more than quality_drift_threshold EWMA
    # sigmas from the running mean (alpha = smoothing weight)
    quality_drift_threshold: float = 4.0
    quality_drift_alpha: float = 0.05
    # ---- pulse-injection canary (srtb_tpu/quality/canary.py) ----
    # inject a deterministic synthetic dispersed pulse into the RAW
    # uint8 stream every N segments (0 = off) and check the recovered
    # S/N at the detection stage.  Canary segments are quarantined
    # from science outputs (signals gate + candidate sinks) and
    # flagged in journal + run manifest; non-canary artifacts stay
    # bit-identical to a canary-off run.  8-bit 'simple' format only.
    canary_every_segments: int = 0
    # per-sample pulse amplitude in digitizer counts (the 8-bit
    # digitizer model keeps ~3 sigma full-scale, i.e. noise sigma
    # ~42.5 counts — 25 is a comfortably-detectable burst)
    canary_amp: float = 25.0
    # burst width in raw samples
    canary_width: int = 32
    # dispersion measure of the injected pulse (< 0 = use `dm`, so
    # the search recovers it coherently by default)
    canary_dm: float = -1.0
    # pulse start as a fraction of the segment's non-overlapped span
    canary_position: float = 0.5
    # expected recovered S/N; 0 = auto-calibrate from the first
    # checked canary of the run (the calibration is journaled)
    canary_expected_snr: float = 0.0
    # a canary FAILS when recovered/expected drops below this ratio
    # — drives detection_health_state, /healthz detection section,
    # the SLO sensitivity objective and an incident bundle
    canary_min_ratio: float = 0.5
    # ---- performance observatory ----
    # record a REAL jax.profiler (XLA) trace of the first N drained
    # segments of a run into profile_capture_dir, next to the Perfetto
    # event export; the capture.json sidecar records the covered
    # trace_ids so the device timeline and the causal-event timeline
    # join exactly.  0 = off (zero cost).
    profile_capture_segments: int = 0
    profile_capture_dir: str = "artifacts/profile"
    # ---- fleet control tower (srtb_tpu/obs/) ----
    # long-horizon rollup store directory the aggregator writes
    # (obs/rollup.py tails the lanes' journals + event dumps into
    # per-minute rollups, quantile digests and the fleet event
    # timeline; gui/server.py's /fleet and tools/console.py read it).
    # "" = off (zero cost).
    obs_store_dir: str = ""
    # downsampling resolution of the rollup minute-series (seconds
    # per bucket)
    obs_rollup_resolution_s: int = 60
    # compaction drops rollup rows older than this many minutes
    # behind the newest minute IN THE DATA (0 = keep everything)
    obs_retention_minutes: int = 0
    # /healthz flips to 503 when the last processed segment is older
    # than this many seconds (gui/server.py staleness detection)
    health_stale_after_s: float = 30.0
    # candidate-writer thread count; >0 uses the async writer pool (native
    # C++ when built — the reference's boost thread pools,
    # write_signal_pipe.hpp:159-280), 0 writes synchronously
    writer_thread_count: int = 2
    # scrolling-waterfall GUI mode: lines contributed per segment
    # (0 = simple whole-segment frames, like the reference's live
    # SimpleSpectrumImageProvider vs legacy scrolling provider)
    gui_scroll_lines: int = 0
    # multi-host process group (jax.distributed); the DCN layer the
    # reference lacks. coordinator is "host:port" of process 0
    distributed_coordinator: str = ""
    distributed_num_processes: int = 1
    distributed_process_id: int = 0

    # ------------------------------------------------------------------
    # derived quantities
    # ------------------------------------------------------------------

    @property
    def bytes_per_sample(self) -> float:
        return abs(self.baseband_input_bits) / BITS_PER_BYTE

    @property
    def baseband_freq_high(self) -> float:
        return self.baseband_freq_low + self.baseband_bandwidth

    def segment_bytes(self, data_stream_count: int = 1) -> int:
        """Bytes of one input segment (all interleaved streams)."""
        return int(self.baseband_input_count * self.bytes_per_sample
                   * data_stream_count)

    # ------------------------------------------------------------------
    # loading
    # ------------------------------------------------------------------

    _INT_FIELDS = frozenset({
        "baseband_input_count", "baseband_input_bits",
        "input_file_offset_bytes", "spectrum_sum_count",
        "spectrum_channel_count", "signal_detect_max_boxcar_length",
        "thread_query_work_wait_time", "gui_pixmap_width",
        "gui_pixmap_height", "gui_http_port", "n_devices", "log_level",
        "writer_thread_count", "distributed_num_processes",
        "distributed_process_id", "gui_scroll_lines",
        "telemetry_journal_max_bytes", "inflight_segments",
        "micro_batch_segments", "retry_max_attempts",
        "segment_watchdog_requeues", "supervisor_max_restarts",
        "degrade_hold_segments", "promote_after_segments",
        "device_reinit_max", "stream_priority", "fleet_max_streams",
        "fleet_queue_limit", "fleet_devices", "periodicity_harmonics",
        "periodicity_candidates", "periodicity_fold_bins",
        "periodicity_min_bin", "events_ring_size",
        "incident_max_bundles", "profile_capture_segments",
        "quality_coarse_bins", "quality_subsample",
        "canary_every_segments", "canary_width",
        "obs_rollup_resolution_s", "obs_retention_minutes",
    })
    _FLOAT_FIELDS = frozenset({
        "baseband_freq_low", "baseband_bandwidth", "baseband_sample_rate",
        "dm", "mitigate_rfi_average_method_threshold",
        "mitigate_rfi_spectral_kurtosis_threshold",
        "signal_detect_signal_noise_threshold",
        "signal_detect_channel_threshold", "segment_deadline_s",
        "health_stale_after_s", "retry_backoff_base_s",
        "retry_backoff_max_s", "retry_deadline_s",
        "supervisor_window_s", "degrade_queue_high",
        "degrade_queue_low", "shutdown_join_timeout_s",
        "device_reinit_window_s", "periodicity_snr_threshold",
        "incident_min_interval_s", "slo_latency_ms",
        "slo_latency_budget", "slo_loss_budget", "slo_staleness_s",
        "slo_staleness_budget", "slo_fast_window_s",
        "slo_slow_window_s", "slo_burn_threshold", "drain_deadline_s",
        "slo_sensitivity_budget", "quality_dead_threshold",
        "quality_hot_threshold", "quality_drift_threshold",
        "quality_drift_alpha", "canary_amp", "canary_dm",
        "canary_position", "canary_expected_snr", "canary_min_ratio",
    })
    _BOOL_FIELDS = frozenset({
        "baseband_reserve_sample", "baseband_write_all", "gui_enable",
        "use_emulated_fp64", "use_pallas", "use_pallas_sk", "sanitize",
        "tsan",
        "degrade_enable", "chirp_exact", "manifest_fsync",
        "manifest_hash", "deterministic_timestamps", "events_enable",
        "telemetry_journal_compress", "quality_stats",
        "migrate_on_burn",
    })
    _LIST_FIELDS = frozenset({
        "udp_receiver_address", "udp_receiver_port",
        "udp_receiver_cpu_preferred", "dm_list",
    })

    def set_option(self, key: str, value: str) -> bool:
        """Set one option from its string form, with expression evaluation
        (ref: program_options.hpp:197-263).  Returns False for unknown keys."""
        key = key.strip()
        if not hasattr(self, key):
            return False
        if key in self._INT_FIELDS:
            setattr(self, key, int(parse_number(value)))
        elif key in self._FLOAT_FIELDS:
            setattr(self, key, float(parse_number(value)))
        elif key in self._BOOL_FIELDS:
            setattr(self, key, bool(int(parse_number(value))))
        elif key in self._LIST_FIELDS:
            items = [s.strip() for s in value.split(",") if s.strip()]
            if key == "udp_receiver_address":
                setattr(self, key, items)
            elif key == "dm_list":
                setattr(self, key, [float(parse_number(s)) for s in items])
            else:
                setattr(self, key, [int(parse_number(s)) for s in items])
        else:
            setattr(self, key, value.strip())
        return True

    def load_file(self, path: str) -> None:
        """Load ``key = value`` lines; ``#`` comments; unknown keys warn with
        file/line pointer (ref: program_options.hpp:290-295)."""
        with open(path) as f:
            for lineno, line in enumerate(f, start=1):
                line = line.split("#", 1)[0].strip()
                if not line:
                    continue
                if "=" not in line:
                    log.warning(f"{path}:{lineno}: cannot parse {line!r}")
                    continue
                key, value = line.split("=", 1)
                if not self.set_option(key, value):
                    log.warning(
                        f"{path}:{lineno}: unknown option {key.strip()!r}")

    @classmethod
    def from_args(cls, argv: list[str] | None = None) -> "Config":
        """Build a config with precedence CLI > config file > defaults
        (ref: program_options.hpp:148-179).

        CLI syntax: ``--key=value`` or ``--key value``.
        """
        if argv is None:
            argv = sys.argv[1:]
        cli: dict[str, str] = {}
        i = 0
        while i < len(argv):
            arg = argv[i]
            if not arg.startswith("--"):
                raise SystemExit(f"unexpected argument: {arg}")
            body = arg[2:]
            if "=" in body:
                key, value = body.split("=", 1)
            else:
                key = body
                if i + 1 >= len(argv):
                    raise SystemExit(f"missing value for --{key}")
                i += 1
                value = argv[i]
            cli[key.replace("-", "_")] = value
            i += 1

        cfg = cls()
        config_file = cli.get("config_file_name", cfg.config_file_name)
        import os
        if os.path.exists(config_file):
            cfg.config_file_name = config_file
            cfg.load_file(config_file)
        for key, value in cli.items():
            if not cfg.set_option(key, value):
                log.warning(f"unknown command-line option --{key}")
        log.level = cfg.log_level
        return cfg

    def replace(self, **kwargs) -> "Config":
        return dataclasses.replace(self, **kwargs)
