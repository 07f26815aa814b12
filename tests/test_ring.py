"""Device-resident overlap-save ring tests (ISSUE 8 acceptance).

- incremental (ring on) vs full-upload (ring off) output parity is
  BIT-identical across plan families (monolithic / four_step+ftail /
  staged / micro-batch) and both sources (file + UDP);
- per-segment ``h2d_bytes`` follows the stride model exactly: one cold
  full-segment upload, then stride_bytes per warm dispatch;
- carry invalidation: watchdog requeue, checkpoint resume, and broken
  stream adjacency (a dropped/interleaved segment upstream) all force a
  cold re-arm and stay bit-identical;
- the staging-buffer pool reuses one host block across micro-batches;
- the checked-in plan cards prove the carry donation is a real alias
  (``aliased``, never ``dropped``/``no_candidate``) for every ring-v1
  warm assemble program.
"""

import json
import os
import socket
import struct
import threading
import time

import numpy as np
import pytest

from srtb_tpu.config import Config
from srtb_tpu.io import formats, udp
from srtb_tpu.io.synth import make_dispersed_baseband
from srtb_tpu.pipeline.runtime import Pipeline
from srtb_tpu.pipeline.segment import SegmentProcessor
from srtb_tpu.utils.metrics import metrics

N = 1 << 14  # 16384 samples, 8-bit: segment_bytes == N


@pytest.fixture(scope="module")
def synth_file(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("ring")
    data = make_dispersed_baseband(N * 4, 1405.0, 64.0, 0.05,
                                   pulse_positions=N, nbits=8)
    path = str(tmp / "bb.bin")
    data.tofile(path)
    return path


def _cfg(path, tmp_path, tag, **extra):
    kw = dict(
        baseband_input_count=N,
        baseband_input_bits=8,
        baseband_freq_low=1405.0,
        baseband_bandwidth=64.0,
        baseband_sample_rate=128e6,
        dm=0.05,  # reserves 2304 of 16384 bytes (~14%)
        input_file_path=path,
        baseband_output_file_prefix=str(tmp_path / f"{tag}_"),
        spectrum_channel_count=64,
        mitigate_rfi_average_method_threshold=100.0,
        mitigate_rfi_spectral_kurtosis_threshold=2.0,
        signal_detect_max_boxcar_length=64,
        baseband_reserve_sample=True,
        writer_thread_count=0,
        inflight_segments=3)
    kw.update(extra)
    return Config(**kw)


class _CaptureSink:
    def __init__(self):
        self.out = []

    def push(self, work, positive):
        det = work.detect
        self.out.append((np.asarray(det.signal_counts).copy(),
                         np.asarray(det.zero_count).copy(),
                         np.asarray(det.time_series).copy()))


def _assert_same(a_sink, b_sink):
    assert len(a_sink.out) == len(b_sink.out) > 0
    for a, b in zip(a_sink.out, b_sink.out):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)


def _run(cfg, processor=None, max_segments=None):
    metrics.reset()
    sink = _CaptureSink()
    with Pipeline(cfg, sinks=[sink], processor=processor) as pipe:
        stats = pipe.run(max_segments=max_segments)
    got = (stats, sink, metrics.get("h2d_bytes"),
           metrics.get("ring_cold_dispatches"), pipe.processor)
    metrics.reset()
    return got


# ------------------------------------------------------ ring resolution


def test_ring_resolution():
    base = dict(baseband_input_count=N, baseband_input_bits=8,
                baseband_freq_low=1405.0, baseband_bandwidth=64.0,
                baseband_sample_rate=128e6, spectrum_channel_count=64)
    on = SegmentProcessor(Config(dm=0.05, baseband_reserve_sample=True,
                                 **base))
    assert on.ring and 0 < on.reserved_bytes < on.stride_bytes
    assert on.plan_name.endswith("+ring")
    assert '"ingest": "ring-v1"' in on.plan_signature()
    off = SegmentProcessor(Config(dm=0.05, baseband_reserve_sample=True,
                                  ingest_ring="off", **base))
    assert not off.ring and '"ingest": "direct"' in off.plan_signature()
    # no reserved tail -> auto resolves off; "on" is a loud error
    none = SegmentProcessor(Config(baseband_reserve_sample=False, **base))
    assert not none.ring
    with pytest.raises(ValueError, match="ingest_ring=on"):
        SegmentProcessor(Config(baseband_reserve_sample=False,
                                ingest_ring="on", **base))
    with pytest.raises(ValueError, match="auto/on/off"):
        SegmentProcessor(Config(ingest_ring="maybe", **base))
    # ring methods refuse on a non-ring plan
    with pytest.raises(ValueError, match="ring disabled"):
        none.run_device_cold(np.zeros(N, np.uint8))
    with pytest.raises(ValueError, match="stride_only"):
        none.stage_input(np.zeros(N, np.uint8), stride_only=True)


# ------------------------------------------- incremental-vs-full parity


@pytest.mark.parametrize("plan", ["monolithic", "four_step", "staged",
                                  "micro_batch"])
def test_incremental_vs_full_upload_bit_identical(synth_file, tmp_path,
                                                  plan):
    """Ring on vs off must change H2D bytes only — never one output
    bit — and the h2d_bytes counter must follow the stride model
    exactly (full segment on the one cold dispatch, stride after)."""
    extra = {}
    staged = None
    if plan == "monolithic":
        extra = dict(fft_strategy="monolithic", fused_tail="off")
    elif plan == "four_step":
        extra = dict(fft_strategy="four_step", fused_tail="on")
    elif plan == "staged":
        staged = True
    elif plan == "micro_batch":
        extra = dict(micro_batch_segments=2, inflight_segments=4)
    outs = {}
    for ring in ("auto", "off"):
        cfg = _cfg(synth_file, tmp_path, f"{plan}_{ring}",
                   ingest_ring=ring, **extra)
        proc = None
        if staged:
            proc = SegmentProcessor(cfg, staged=True)
        outs[ring] = _run(cfg, processor=proc)
    stats, sink_on, h_on, cold_on, proc = outs["auto"]
    _, sink_off, h_off, cold_off, _ = outs["off"]
    _assert_same(sink_on, sink_off)
    nseg = stats.segments
    seg_b, stride = proc._segment_bytes, proc.stride_bytes
    assert h_off == nseg * seg_b and cold_off == 0
    if plan == "micro_batch":
        # one cold batch (2 full segments), then strides
        assert h_on == 2 * seg_b + (nseg - 2) * stride
    else:
        assert h_on == seg_b + (nseg - 1) * stride
    assert cold_on == 1
    # the ring saved exactly the reserved fraction on warm dispatches
    assert h_off - h_on == (nseg - (2 if plan == "micro_batch" else 1)) \
        * proc.reserved_bytes


def test_serial_window_and_sanitizer_ring(synth_file, tmp_path):
    """inflight_segments=1 (serial) and Config.sanitize both run the
    ring path unchanged: same outputs, same stride model."""
    ref = _run(_cfg(synth_file, tmp_path, "ref", ingest_ring="off"))
    ser = _run(_cfg(synth_file, tmp_path, "ser", inflight_segments=1))
    san = _run(_cfg(synth_file, tmp_path, "san", inflight_segments=2,
                    sanitize=True))
    _assert_same(ser[1], ref[1])
    _assert_same(san[1], ref[1])
    for stats, _, h2d, cold, proc in (ser, san):
        assert h2d == proc._segment_bytes \
            + (stats.segments - 1) * proc.stride_bytes
        assert cold == 1


# ------------------------------------------------- telemetry accounting


def test_journal_h2d_accounting(synth_file, tmp_path):
    """Journal spans carry cumulative h2d_bytes: consecutive deltas
    localize the stride model per segment."""
    from srtb_tpu.tools import telemetry_report as TR

    cfg = _cfg(synth_file, tmp_path, "jrnl",
               telemetry_journal_path=str(tmp_path / "jrnl.jsonl"))
    stats, _, h2d, _, proc = _run(cfg)
    recs = TR.load(cfg.telemetry_journal_path)
    assert len(recs) == stats.segments
    assert recs[-1]["h2d_bytes"] == h2d
    assert h2d == proc._segment_bytes \
        + (stats.segments - 1) * proc.stride_bytes
    assert all(r["ring_cold_dispatches"] == 1 for r in recs)
    deltas = [b["h2d_bytes"] - a["h2d_bytes"]
              for a, b in zip(recs, recs[1:])]
    # dispatch runs AHEAD of drain inside the window, so a record's
    # delta covers 0..W warm strides — but only whole strides (the one
    # cold full segment is the first record's base), monotonically
    assert all(d >= 0 and d % proc.stride_bytes == 0 for d in deltas)


# ------------------------------------------------------------- sources


def _udp_cfg(port, **extra):
    kw = dict(baseband_input_count=16384, baseband_input_bits=8,
              baseband_format_type="fastmb_roach2",
              baseband_freq_low=1405.0, baseband_bandwidth=64.0,
              baseband_sample_rate=128e6, dm=0.05,
              spectrum_channel_count=2048,
              mitigate_rfi_average_method_threshold=100.0,
              mitigate_rfi_spectral_kurtosis_threshold=2.0,
              udp_receiver_address=["127.0.0.1"],
              udp_receiver_port=[port],
              baseband_reserve_sample=True,
              writer_thread_count=0, inflight_segments=2)
    kw.update(extra)
    return Config(**kw)


def _send_packets(port, count, delay=0.002):
    fmt = formats.FASTMB_ROACH2
    payload = fmt.payload_bytes
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    time.sleep(0.1)
    rng = np.random.default_rng(7)
    blobs = [rng.integers(0, 256, size=payload, dtype=np.uint8).tobytes()
             for _ in range(count)]
    for c in range(count):
        sock.sendto(struct.pack("<Q", c) + blobs[c], ("127.0.0.1", port))
        time.sleep(delay)
    sock.close()


def test_udp_source_overlap_assembly():
    """The real-time source overlaps consecutive segments by the
    reserved tail (stride receives + retained-tail head), with the
    packet counter stamped for the segment's FIRST byte."""
    port = 43310
    cfg = _udp_cfg(port)
    src = udp.UdpReceiverSource(cfg, use_native=False)
    payload = formats.FASTMB_ROACH2.payload_bytes
    assert src.reserved_bytes == payload and src.stride_bytes == 3 * payload
    t = threading.Thread(target=_send_packets, args=(port, 8))
    t.start()
    seg1, seg2 = next(src), next(src)
    t.join()
    src.close()
    np.testing.assert_array_equal(seg2.data[:payload],
                                  seg1.data[-payload:])
    assert seg1.udp_packet_counter == 0 and seg2.udp_packet_counter == 3
    assert (seg1.seq, seg2.seq) == (0, 1)


def test_udp_misaligned_stride_degrades_to_legacy_framing():
    """A reserved tail whose stride is not a payload multiple must NOT
    fail startup: the source keeps the legacy non-overlapping block
    framing (warned) and leaves seq unstamped so the engine's
    adjacency guard keeps the ring cold — never warm-assembles
    non-overlapping blocks against a foreign carry."""
    port = 43340
    # channels=512 -> reserved rounds to 1024-sample tiles: stride is
    # a 1024 multiple but not a 4096 (payload) multiple
    cfg = _udp_cfg(port, spectrum_channel_count=512)
    src = udp.UdpReceiverSource(cfg, use_native=False)
    assert src.reserved_bytes == 0  # overlap disabled, not fatal
    assert src.stride_bytes == src.segment_bytes
    t = threading.Thread(target=_send_packets, args=(port, 8))
    t.start()
    seg1, seg2 = next(src), next(src)
    t.join()
    src.close()
    assert (seg1.seq, seg2.seq) == (-1, -1)  # never warm-assembled
    # legacy framing: consecutive full blocks, no overlap
    assert seg2.udp_packet_counter == 4


def test_staged_ring_sanitize_expires_carry(synth_file, tmp_path):
    """Under Config.sanitize the staged ring's ALWAYS-donated carry is
    expired even with donate_input=False (the CPU-CI stand-in for the
    TPU's donated-buffer invalidation): reusing a consumed carry
    raises instead of silently passing on CPU."""
    cfg = _cfg(synth_file, tmp_path, "sanc", sanitize=True)
    proc = SegmentProcessor(cfg, staged=True)
    raw = np.fromfile(synth_file, dtype=np.uint8, count=N)
    from srtb_tpu.analysis.sanitizer import Sanitizer
    san = Sanitizer()
    with san.run_scope():
        _, c1 = proc.run_device_cold(proc.stage_input(raw))
        new = proc.stage_input(raw, stride_only=True)
        _, c2 = proc.run_device_ring(c1, new)
        with pytest.raises(Exception, match="[Dd]onat|[Dd]elet"):
            proc.run_device_ring(c1, proc.stage_input(
                raw, stride_only=True))  # c1 was consumed


def test_udp_incremental_vs_full_upload_bit_identical(tmp_path):
    """Engine parity on the real-time source: same packet stream, ring
    on vs off, bit-identical detections + the stride H2D model."""
    outs = {}
    for i, ring in enumerate(("auto", "off")):
        port = 43320 + i
        cfg = _udp_cfg(port, ingest_ring=ring,
                       baseband_output_file_prefix=str(
                           tmp_path / f"udp_{ring}_"))
        src = udp.UdpReceiverSource(cfg, use_native=False)
        t = threading.Thread(target=_send_packets, args=(port, 12))
        t.start()
        metrics.reset()
        sink = _CaptureSink()
        with Pipeline(cfg, source=src, sinks=[sink]) as pipe:
            stats = pipe.run(max_segments=3)
        t.join()
        src.close()
        outs[ring] = (stats, sink, metrics.get("h2d_bytes"),
                      metrics.get("ring_cold_dispatches"),
                      pipe.processor)
        metrics.reset()
    _assert_same(outs["auto"][1], outs["off"][1])
    _, _, h_on, cold_on, proc = outs["auto"]
    assert cold_on == 1
    assert h_on == proc._segment_bytes + 2 * proc.stride_bytes
    assert outs["off"][2] == 3 * proc._segment_bytes


# --------------------------------------------------- carry invalidation


class _FlakyReady(Pipeline):
    """Readiness probe that reports the drain head unready until the
    watchdog has requeued once — a deterministic compute wedge."""

    def _result_ready(self, det_res):
        if metrics.get("watchdog_requeues") < 1:
            return False
        return Pipeline._result_ready(det_res)


def test_watchdog_requeue_goes_cold_bit_identical(synth_file, tmp_path):
    """A watchdog requeue re-dispatches cold from the retained host
    buffer AND invalidates the live carry (the wedged device may never
    materialize it); outputs stay bit-identical."""
    ref = _run(_cfg(synth_file, tmp_path, "wd_ref", ingest_ring="off"))
    metrics.reset()
    cfg = _cfg(synth_file, tmp_path, "wd", inflight_segments=2,
               segment_deadline_s=0.15, segment_watchdog_requeues=2,
               retry_backoff_base_s=0.001)
    sink = _CaptureSink()
    with _FlakyReady(cfg, sinks=[sink]) as pipe:
        stats = pipe.run()
    h2d = metrics.get("h2d_bytes")
    cold = metrics.get("ring_cold_dispatches")
    assert metrics.get("watchdog_requeues") == 1
    metrics.reset()
    _assert_same(sink, ref[1])
    proc = pipe.processor
    # cold dispatches: segment 0's initial dispatch, its requeue, and
    # the first fresh dispatch after the invalidation; everything
    # later re-warms off the re-armed carry.  Segment 1 was warm-
    # dispatched BEFORE the wedge (window 2), so warm uploads cover
    # all but two segments — plus the one extra full upload of the
    # requeued segment itself.
    assert cold == 3
    assert h2d == 3 * proc._segment_bytes \
        + (stats.segments - 2) * proc.stride_bytes


def test_checkpoint_resume_goes_cold_bit_identical(synth_file, tmp_path):
    """A resumed run has no device carry: its first dispatch is a cold
    full upload from the checkpointed offset, and the stitched output
    stream is bit-identical to an uninterrupted ring run."""
    ref = _run(_cfg(synth_file, tmp_path, "ck_ref", ingest_ring="off"))
    cfg = _cfg(synth_file, tmp_path, "ck",
               checkpoint_path=str(tmp_path / "ck.json"))
    first = _run(cfg, max_segments=2)
    assert first[0].segments == 2
    resumed = _run(cfg)
    assert resumed[3] == 1  # ONE cold dispatch: the resume re-arm
    stitched = _CaptureSink()
    stitched.out = first[1].out + resumed[1].out
    _assert_same(stitched, ref[1])


class _SeqGapSource:
    """Wraps a source but breaks SegmentWork.seq adjacency — the
    upstream signature of a dropped or interleaved segment."""

    def __init__(self, inner):
        self.inner = inner
        self.pool = getattr(inner, "pool", None)

    def __iter__(self):
        return self

    def __next__(self):
        seg = next(self.inner)
        seg.seq = seg.seq * 2  # gap after the first segment
        return seg

    @property
    def logical_offset(self):
        return getattr(self.inner, "logical_offset", 0)


def test_broken_adjacency_goes_cold_never_wrong(synth_file, tmp_path):
    """Segments that are not stream-adjacent (seq gaps) must NEVER be
    warm-assembled against a foreign carry: every dispatch after a gap
    goes cold, and the outputs match the full-upload reference."""
    ref = _run(_cfg(synth_file, tmp_path, "gap_ref", ingest_ring="off"))
    metrics.reset()
    cfg = _cfg(synth_file, tmp_path, "gap")
    from srtb_tpu.io.file_input import BasebandFileReader
    src = _SeqGapSource(BasebandFileReader(cfg))
    sink = _CaptureSink()
    with Pipeline(cfg, source=src, sinks=[sink]) as pipe:
        stats = pipe.run()
    cold = metrics.get("ring_cold_dispatches")
    h2d = metrics.get("h2d_bytes")
    metrics.reset()
    _assert_same(sink, ref[1])
    # seq 0 anchors seq... 0*2=0; 1->2, 2->4: nothing adjacent after
    # the first pair check, so every dispatch is a full upload
    assert cold == stats.segments
    assert h2d == stats.segments * pipe.processor._segment_bytes


# ------------------------------------------------- staging-buffer pool


def test_staging_pool_reuses_micro_batch_blocks(synth_file, tmp_path):
    """Micro-batch stacking draws from the processor's staging pool
    (one cached block reused per batch shape) instead of allocating a
    fresh np.stack per batch, and drains return every block."""
    cfg = _cfg(synth_file, tmp_path, "pool", micro_batch_segments=2,
               inflight_segments=4)
    stats, _, _, _, proc = _run(cfg)
    assert stats.segments >= 4
    pool = proc._staging_pool.stats()
    assert pool["in_use"] == 0
    # two distinct block sizes at most: [B, seg] (cold) + [B, stride]
    assert 1 <= pool["cached_blocks"] <= 2
    assert not proc._staging_out  # all registrations released


def test_staging_copy_path_and_release():
    """stage_input copies non-contiguous/non-uint8 input into a pooled
    block, registers it against the owner, and release_staging returns
    it; contiguous uint8 input never touches the pool."""
    cfg = Config(baseband_input_count=N, baseband_input_bits=8,
                 baseband_freq_low=1405.0, baseband_bandwidth=64.0,
                 baseband_sample_rate=128e6, dm=0.05,
                 spectrum_channel_count=64, baseband_reserve_sample=True)
    proc = SegmentProcessor(cfg)
    clean = np.zeros(N, np.uint8)
    proc.stage_input(clean)
    assert proc._staging_pool.stats()["in_use"] == 0  # no copy needed
    strided = np.zeros(2 * N, np.uint8)[::2]  # non-contiguous view
    proc.stage_input(strided)
    assert proc._staging_pool.stats()["in_use"] == 1
    proc.release_staging(strided)
    st = proc._staging_pool.stats()
    assert st["in_use"] == 0 and st["cached_blocks"] == 1


def test_staging_overflow_cap_self_heals():
    """Callers that never release (direct API users) are reclaimed by
    the FIFO cap instead of leaking one block per call."""
    cfg = Config(baseband_input_count=N, baseband_input_bits=8,
                 baseband_freq_low=1405.0, baseband_bandwidth=64.0,
                 baseband_sample_rate=128e6, dm=0.05,
                 spectrum_channel_count=64, baseband_reserve_sample=True)
    proc = SegmentProcessor(cfg)
    owners = [np.zeros(2 * N, np.uint8)[::2] for _ in range(20)]
    for o in owners:
        proc.stage_input(o)
    assert len(proc._staging_out) <= proc._staging_cap
    assert proc._staging_pool.stats()["in_use"] <= proc._staging_cap


# ------------------------------------------------- plan-audit coverage


def test_checked_in_cards_prove_carry_alias():
    """The committed plan_cards.json baseline cards every ring-v1
    family with the carry donation PROVEN aliased on each warm
    assemble program (never dropped / no_candidate)."""
    from srtb_tpu.analysis import hlo_audit as HA

    with open(HA.DEFAULT_BASELINE) as f:
        data = json.load(f)
    ring_cards = {k: c for k, c in data["cards"].items()
                  if c.get("ingest") == "ring-v1"}
    assert set(ring_cards) >= {"four_step_ftail_ring", "monolithic_ring",
                               "pallas_skzap_ring", "staged_ring",
                               "four_step_ftail_ring_mb2"}
    for key, card in ring_cards.items():
        warm = {n: p for n, p in card["programs"].items()
                if n in ("ring", "stage_a_ring", "batch_ring")}
        assert warm, key
        for name, prog in warm.items():
            don = prog["donation"]
            assert 0 in don["aliased"], (key, name, don)
            assert 0 not in don["dropped"] + don["no_candidate"]
            assert prog["alias_bytes"] == card["reserved_bytes"] > 0
        assert card["checks"]["ring_alias_ok"], key
    # direct-ingest families are untouched by the ring machinery
    assert data["cards"]["four_step_ftail"]["ingest"] == "direct"


def test_live_audit_proves_alias_and_catches_loss():
    """One live lowering: the ring family audits ring_alias_ok, and a
    non-donating assemble wrapper visibly loses the alias (the
    regression the ci gate guards)."""
    import jax

    from srtb_tpu.analysis import hlo_audit as HA

    cards = HA.audit_families(["four_step_ftail_ring"])
    card = cards["four_step_ftail_ring"]
    assert card["checks"]["ring_alias_ok"]
    spec = next(s for s in HA.PLAN_FAMILIES
                if s.key == "four_step_ftail_ring")
    proc = HA.build_plan(spec)
    (_, _, args, _), = [p for p in proc.lowerables() if p[0] == "ring"]
    lost = HA.audit_program(jax.jit(proc._process_ring), args, (),
                            8 * proc.n_spectrum)
    assert 0 not in lost["donation"]["aliased"]


# --------------------------------------------------------- AOT + reader


def test_aot_cache_covers_ring_programs(synth_file, tmp_path):
    """enable_aot persists the ring programs too: a warm restart loads
    cold+warm executables and produces identical results."""
    cfg = _cfg(synth_file, tmp_path, "aot")
    raw = np.fromfile(synth_file, dtype=np.uint8, count=N)
    proc1 = SegmentProcessor(cfg)
    assert proc1.enable_aot(str(tmp_path / "aot"), allow_cpu=True)
    (wf1, det1), c1 = proc1.run_device_cold(proc1.stage_input(raw))
    proc2 = SegmentProcessor(cfg)
    assert proc2.enable_aot(str(tmp_path / "aot"), allow_cpu=True)
    (wf2, det2), c2 = proc2.run_device_cold(proc2.stage_input(raw))
    np.testing.assert_array_equal(np.asarray(wf1), np.asarray(wf2))
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    names = {p.name for p in (tmp_path / "aot").iterdir()}
    assert any("ring" in n for n in names), names


def test_file_reader_skip_read_bit_identical(synth_file, tmp_path):
    """The skip-read fast path (stride reads + retained tail) emits the
    exact byte stream and logical offsets of the legacy seek-back
    path, while reading only stride bytes from disk per warm segment."""
    from srtb_tpu.io.file_input import BasebandFileReader
    from srtb_tpu.utils.bufferpool import BufferPool

    def harvest(ring):
        cfg = _cfg(synth_file, tmp_path, "rd", ingest_ring=ring)
        metrics.reset()
        r = BasebandFileReader(cfg, buffer_pool=BufferPool("t"))
        segs = [(s.data.copy(), r.logical_offset, s.seq) for s in r]
        bytes_read = metrics.get("file_bytes_read")
        metrics.reset()
        r.close()
        return segs, bytes_read, r

    fast, fast_bytes, r = harvest("auto")
    legacy, legacy_bytes, _ = harvest("off")
    assert len(fast) == len(legacy)
    for (a, oa, sa), (b, ob, sb) in zip(fast, legacy):
        np.testing.assert_array_equal(a, b)
        assert oa == ob and sa == sb
    # the fast path never re-reads the reserved tail from disk
    assert legacy_bytes - fast_bytes == (len(fast) - 1) * r.reserved_bytes
