"""Durable exactly-once outputs (ISSUE 10): run-manifest WAL, crash
recovery, checkpoint hardening, fsck, and the crash windows.

The in-process tests simulate crashes with injected FATAL faults (the
run dies mid-window, Python-level state is abandoned exactly where a
SIGKILL would abandon it for the synchronous-writer paths) and with
hand-built mid-crash filesystem states; the real-SIGKILL subprocess
soak (tools/crash_soak.py) is the slow acceptance gate."""

import json
import os
import zlib

import numpy as np
import pytest

from srtb_tpu.config import Config
from srtb_tpu.io import manifest as M
from srtb_tpu.io.synth import make_dispersed_baseband
from srtb_tpu.pipeline.checkpoint import StreamCheckpoint
from srtb_tpu.pipeline.runtime import Pipeline
from srtb_tpu.pipeline.segment import SegmentProcessor
from srtb_tpu.tools import fsck as F
from srtb_tpu.tools.crash_soak import (make_resumable_source,
                                       snapshot_outputs)
from srtb_tpu.utils.metrics import metrics

KEY = (0, 0, "0:WriteSignalSink")


# ----------------------------------------------------------------
# manifest WAL unit tests
# ----------------------------------------------------------------

def _write_artifact(path, payload=b"artifact-bytes" * 8):
    with open(path, "wb") as f:
        f.write(payload)
    return payload


def test_manifest_roundtrip(tmp_path):
    mpath = str(tmp_path / "m.jsonl")
    m = M.RunManifest.open(mpath)
    p = str(tmp_path / "out_1.bin")
    payload = _write_artifact(p)
    m.intent(KEY, p)
    m.commit(KEY, p, len(payload), zlib.crc32(payload))
    m.sink_done(KEY)
    m.checkpoint(1, 4096)
    assert m.is_done(KEY) and not m.is_done((0, 1, "x"))
    m.close()

    scan = M.scan_manifest(mpath)
    assert not scan.torn and scan.bad_line is None
    assert scan.checkpoint_floor() == 1
    grp = scan.groups[KEY]
    assert M.group_complete(grp)
    art = grp.artifacts[p]
    assert art.committed and art.length == len(payload) \
        and art.crc32 == zlib.crc32(payload)
    # reopen: the done-set survives the process boundary
    m2 = M.RunManifest.open(mpath)
    assert m2.is_done(KEY)
    m2.close()


def test_manifest_torn_tail_truncated(tmp_path):
    mpath = str(tmp_path / "m.jsonl")
    m = M.RunManifest.open(mpath)
    m.sink_done(KEY)
    m.close()
    good = os.path.getsize(mpath)
    with open(mpath, "ab") as f:
        f.write(b'{"t":"done","half-written')  # torn mid-append
    rep = M.recover(mpath, apply=True)
    assert rep.truncated_bytes > 0
    assert os.path.getsize(mpath) == good
    assert KEY in rep.done


def test_manifest_forged_crc_invalidates_tail(tmp_path):
    """Everything after the first bad record is untrusted: later
    groups drop out of the done-set (their segments re-drain on
    resume) while the valid prefix keeps its guarantees.  Artifacts
    the forgotten records had published become untracked files —
    detected by fsck's torn-WAL error, deliberately not deleted
    (recovery only removes files the valid prefix names)."""
    mpath = str(tmp_path / "m.jsonl")
    m = M.RunManifest.open(mpath)
    key2 = (0, 1, "0:WriteSignalSink")
    p1 = str(tmp_path / "out_1.bin")
    p2 = str(tmp_path / "out_2.bin")
    pay1 = _write_artifact(p1)
    m.intent(KEY, p1)
    m.commit(KEY, p1, len(pay1), zlib.crc32(pay1))
    m.sink_done(KEY)
    pay2 = _write_artifact(p2)
    m.intent(key2, p2)
    m.commit(key2, p2, len(pay2), zlib.crc32(pay2))
    m.sink_done(key2)
    m.close()
    # forge a byte inside segment 1's intent record
    with open(mpath, "rb+") as f:
        data = f.read()
        i = data.rindex(b'"intent"')
        f.seek(i)
        f.write(b'"iNtent"')
    rep = M.recover(mpath, apply=True)
    assert KEY in rep.done and key2 not in rep.done
    assert os.path.exists(p1)
    assert rep.truncated_bytes > 0
    # p2 is untracked (its records fell past the corruption): left on
    # disk for the operator, the torn WAL is the loud signal
    assert os.path.exists(p2)


def test_recover_rolls_back_uncommitted_intent(tmp_path):
    metrics.reset()
    mpath = str(tmp_path / "m.jsonl")
    m = M.RunManifest.open(mpath)
    p = str(tmp_path / "out_1.bin")
    m.intent(KEY, p)
    # crash here: temp on disk, and a second flavor where the rename
    # happened but the commit record never landed
    _write_artifact(p + M.TMP_SUFFIX)
    p2 = str(tmp_path / "out_2.npy")
    m.intent(KEY, p2)
    _write_artifact(p2)
    m.close()
    rep = M.recover(mpath, apply=True)
    assert rep.rolled_back_intents == 2
    assert not os.path.exists(p + M.TMP_SUFFIX)
    assert not os.path.exists(p2)
    assert KEY not in rep.done
    # the metric lands when the pipeline reopens the manifest
    metrics.reset()
    M.RunManifest.open(mpath).close()
    assert metrics.get("rolled_back_intents") == 0  # already recovered


def test_recover_truncates_torn_append(tmp_path):
    mpath = str(tmp_path / "m.jsonl")
    m = M.RunManifest.open(mpath)
    p = str(tmp_path / "stream0.bin")
    chunk = b"chunk-one-bytes!"
    m.intent(KEY, p, mode="append", offset=0)
    with open(p, "wb") as f:
        f.write(chunk)
    m.commit(KEY, p, len(chunk), zlib.crc32(chunk), offset=0)
    m.sink_done(KEY)
    key2 = (0, 1, "0:WriteAllSink")
    m.intent(key2, p, mode="append", offset=len(chunk))
    with open(p, "ab") as f:
        f.write(b"torn-append-that-never-committed")
    m.close()
    rep = M.recover(mpath, apply=True)
    assert KEY in rep.done and key2 not in rep.done
    assert os.path.getsize(p) == len(chunk)
    with open(p, "rb") as f:
        assert f.read() == chunk


def test_recover_done_set_and_recovered_counter(tmp_path):
    """A committed group BEYOND the checkpoint is the rescued window:
    counted as recovered and skipped on replay."""
    metrics.reset()
    mpath = str(tmp_path / "m.jsonl")
    m = M.RunManifest.open(mpath)
    p = str(tmp_path / "out_5.bin")
    pay = _write_artifact(p)
    m.checkpoint(5, 1 << 16)
    key5 = (0, 5, "0:WriteSignalSink")
    m.intent(key5, p)
    m.commit(key5, p, len(pay), zlib.crc32(pay))
    m.sink_done(key5)
    m.close()
    m2 = M.RunManifest.open(mpath)
    assert m2.is_done(key5)
    assert metrics.get("recovered_segments") == 1
    m2.close()
    metrics.reset()


def test_recover_honors_checkpoint_floor_hint(tmp_path):
    """A WAL that lost its ckpt records (mid-file corruption) must not
    roll back artifacts in segments the checkpoint FILE says are done
    — the resume would never regenerate them.  The checkpoint floor
    hint raises the effective floor so the gap is flagged, not
    deleted."""
    mpath = str(tmp_path / "m.jsonl")
    m = M.RunManifest.open(mpath)
    p = str(tmp_path / "out_7.bin")
    pay = _write_artifact(p)
    key7 = (0, 7, "0:WriteSignalSink")
    m.intent(key7, p)
    m.close()
    # the commit/done/ckpt records for segment 7 were lost to
    # corruption; the checkpoint file still says 10 segments done
    rep = M.recover(mpath, apply=True, checkpoint_floor_hint=10)
    assert os.path.exists(p)          # NOT rolled back
    assert rep.rolled_back_intents == 0
    assert rep.missing                # flagged as possible loss
    # without the hint the gap segment would be rolled back
    rep2 = M.recover(mpath, apply=True)
    assert not os.path.exists(p)


def test_recover_append_gap_not_truncated(tmp_path):
    """Append flavor of the checkpoint-floor guard: bytes beyond the
    SURVIVING committed prefix that belong to segments the checkpoint
    sealed (but a corrupted WAL forgot) are flagged, never cut."""
    mpath = str(tmp_path / "m.jsonl")
    m = M.RunManifest.open(mpath)
    p = str(tmp_path / "stream0.bin")
    chunk = b"committed-chunk!"
    m.intent(KEY, p, mode="append", offset=0)
    with open(p, "wb") as f:
        f.write(chunk)
    m.commit(KEY, p, len(chunk), zlib.crc32(chunk), offset=0)
    m.sink_done(KEY)
    # segment 1's append happened and WAS sealed, but its commit/done/
    # ckpt records were lost to WAL corruption: only the intent remains
    key1 = (0, 1, "0:WriteAllSink")
    m.intent(key1, p, mode="append", offset=len(chunk))
    with open(p, "ab") as f:
        f.write(b"sealed-but-forgotten")
    m.close()
    size = os.path.getsize(p)
    rep = M.recover(mpath, apply=True, checkpoint_floor_hint=2)
    assert os.path.getsize(p) == size          # untouched
    assert any("forgotten" in s for s in rep.missing)
    # without the hint the overhang is an ordinary torn append
    rep2 = M.recover(mpath, apply=True)
    assert os.path.getsize(p) == len(chunk)


def test_native_drain_commits_verified_per_job(tmp_path, monkeypatch):
    """An errored native drain batch must not drop commits for jobs
    that verifiably landed (temp+rename is all-or-nothing, so a final
    file at the submitted size proves success)."""
    from srtb_tpu.io.native_writer import AsyncWriterPool
    if not __import__("srtb_tpu.io.native_writer",
                      fromlist=["native_available"]).native_available():
        pytest.skip("native writer not built")
    pool = AsyncWriterPool(2, prefer_native=True)
    good = str(tmp_path / "good.bin")
    bad = str(tmp_path / "no_dir" / "bad.bin")
    fired = []
    pool.submit(good, b"payload!", on_done=lambda: fired.append("good"))
    pool.submit(bad, b"payload!", on_done=lambda: fired.append("bad"))
    pool.drain()
    assert fired == ["good"]
    with pytest.raises(RuntimeError):
        pool.raise_new_errors("test")
    # a later clean batch commits normally
    good2 = str(tmp_path / "good2.bin")
    pool.submit(good2, b"x", on_done=lambda: fired.append("good2"))
    pool.drain()
    assert fired == ["good", "good2"]
    pool.close()


def test_recover_flags_missing_below_checkpoint(tmp_path):
    """A committed artifact that vanished UNDER the checkpoint is
    unrecoverable loss: flagged, never silently repaired."""
    mpath = str(tmp_path / "m.jsonl")
    m = M.RunManifest.open(mpath)
    p = str(tmp_path / "out_1.bin")
    pay = _write_artifact(p)
    m.intent(KEY, p)
    m.commit(KEY, p, len(pay), zlib.crc32(pay))
    m.sink_done(KEY)
    m.checkpoint(3, 1 << 16)
    m.close()
    os.unlink(p)
    rep = M.recover(mpath, apply=True)
    assert rep.missing and KEY not in rep.done


# ----------------------------------------------------------------
# checkpoint hardening
# ----------------------------------------------------------------

def test_checkpoint_crc_and_bak_fallback(tmp_path):
    p = str(tmp_path / "ck.json")
    ck = StreamCheckpoint(p)
    ck.update(3, 1000)
    ck.update(4, 2000)
    assert os.path.exists(p + ".bak")
    # corrupt the primary: the previous generation takes over loudly
    with open(p, "w") as f:
        f.write('{"segments_done": 999999, "file_off')
    ck2 = StreamCheckpoint(p)
    assert ck2.segments_done == 3 and ck2.file_offset_bytes == 1000
    # corrupt BOTH: restart from 0, not from garbage
    with open(p + ".bak", "w") as f:
        f.write("not-json")
    ck3 = StreamCheckpoint(p)
    assert ck3.segments_done == 0


def test_checkpoint_crc_rejects_bitrot(tmp_path):
    p = str(tmp_path / "ck.json")
    StreamCheckpoint(p).update(7, 7000)
    with open(p) as f:
        state = json.load(f)
    state["segments_done"] = 9  # forged value, stale CRC
    with open(p, "w") as f:
        json.dump(state, f)
    ck = StreamCheckpoint(p)
    # primary rejected on CRC; .bak does not exist (single update)
    assert ck.segments_done == 0


def test_checkpoint_legacy_without_crc_accepted(tmp_path):
    p = str(tmp_path / "ck.json")
    with open(p, "w") as f:
        json.dump({"segments_done": 5, "file_offset_bytes": 500}, f)
    ck = StreamCheckpoint(p)
    assert ck.segments_done == 5 and ck.file_offset_bytes == 500


def test_checkpoint_seals_manifest_first(tmp_path):
    mpath = str(tmp_path / "m.jsonl")
    m = M.RunManifest.open(mpath)
    ck = StreamCheckpoint(str(tmp_path / "ck.json"), manifest=m)
    ck.update(2, 4096)
    m.close()
    scan = M.scan_manifest(mpath)
    last = scan.last_checkpoint
    assert last["segments_done"] == 2 and last["offset"] == 4096


# ----------------------------------------------------------------
# pipeline crash windows (in-process)
# ----------------------------------------------------------------

def _cfg(tmp_path, tag, n=1 << 12, segments=4, **kw):
    run_dir = tmp_path / tag
    run_dir.mkdir(exist_ok=True)
    return Config(
        baseband_input_count=n, baseband_input_bits=8,
        baseband_freq_low=1405.0, baseband_bandwidth=64.0,
        baseband_sample_rate=128e6, dm=0.05,
        input_file_path=str(tmp_path / "bb.bin"),
        baseband_output_file_prefix=str(run_dir / "out_"),
        spectrum_channel_count=1 << 4,
        mitigate_rfi_average_method_threshold=1000.0,
        mitigate_rfi_spectral_kurtosis_threshold=50.0,
        # below the noise floor: every segment writes (deterministic)
        signal_detect_signal_noise_threshold=2.0,
        signal_detect_max_boxcar_length=8,
        baseband_reserve_sample=False,
        writer_thread_count=0,
        inflight_segments=1,
        retry_max_attempts=1,
        checkpoint_path=str(run_dir / "ck.json"),
        run_manifest_path=str(run_dir / "manifest.jsonl"),
        **kw)


@pytest.fixture(scope="module")
def crash_env(tmp_path_factory):
    """Shared input file + pre-compiled processor + ONE golden output
    snapshot for the crash-window tests (deterministic timestamps make
    every run's artifact names identical, so one golden serves all)."""
    tmp_path = tmp_path_factory.mktemp("durability")
    n = 1 << 12
    segments = 4
    make_dispersed_baseband(
        n * segments, 1405.0, 64.0, 0.05,
        pulse_positions=[n // 2 + i * n for i in range(segments)],
        pulse_amp=30.0, nbits=8, seed=0,
    ).tofile(str(tmp_path / "bb.bin"))
    proc = SegmentProcessor(_cfg(tmp_path, "probe", n=n))
    golden_cfg = _cfg(tmp_path, "golden")
    _run_to_completion(golden_cfg, proc)
    golden = snapshot_outputs(_run_dir(golden_cfg))
    assert golden  # the equality gates must gate something
    return tmp_path, proc, n, segments, golden


def _run_to_completion(cfg, proc):
    metrics.reset()
    with Pipeline(cfg, source=make_resumable_source(cfg),
                  processor=proc) as pipe:
        stats = pipe.run()
    counters = {k: int(metrics.get(k)) for k in
                ("replayed_skips", "recovered_segments",
                 "rolled_back_intents")}
    metrics.reset()
    return stats, counters


def _run_dir(cfg):
    return os.path.dirname(cfg.baseband_output_file_prefix)


def test_crash_between_sink_commit_and_checkpoint(crash_env, tmp_path):
    """THE duplicate window: the run dies after segment 1's artifacts
    committed but before its checkpoint update.  The resume must skip
    the committed push (manifest done-set) and the final output set
    must equal the golden run's exactly."""
    tmp, proc, n, segments, golden = crash_env
    cfg = _cfg(tmp, "crash_a", fault_plan="checkpoint:fatal@1")
    with pytest.raises(Exception):
        with Pipeline(cfg, source=make_resumable_source(cfg),
                      processor=proc) as pipe:
            pipe.run()
    metrics.reset()
    resumed_cfg = cfg.replace(fault_plan="")
    stats, counters = _run_to_completion(resumed_cfg, proc)
    assert counters["replayed_skips"] >= 1
    assert counters["recovered_segments"] >= 1
    assert snapshot_outputs(_run_dir(cfg)) == golden


def test_crash_during_checkpoint_flush(crash_env, tmp_path):
    """The manifest ckpt record lands, then the process dies inside
    the state-file flush (tmp written, rename never happens): the
    resume repeats one segment, idempotently."""
    tmp, proc, n, segments, golden = crash_env
    cfg = _cfg(tmp, "crash_b")

    class Boom(RuntimeError):
        pass

    metrics.reset()
    with Pipeline(cfg, source=make_resumable_source(cfg),
                  processor=proc) as pipe:
        real_update = pipe.checkpoint.update
        calls = [0]

        def dying_update(segments_done, offset):
            calls[0] += 1
            if calls[0] == 2:  # die mid-flush of segment 1's update
                pipe.checkpoint.manifest.checkpoint(segments_done,
                                                    offset)
                with open(pipe.checkpoint.path + ".tmp", "w") as f:
                    f.write('{"segments_done":')  # torn tmp
                raise Boom("simulated death inside checkpoint flush")
            return real_update(segments_done, offset)

        pipe.checkpoint.update = dying_update
        with pytest.raises(Boom):
            pipe.run()
    stats, counters = _run_to_completion(cfg, proc)
    assert counters["replayed_skips"] >= 1
    assert snapshot_outputs(_run_dir(cfg)) == golden


def test_crash_mid_sink_write_rolls_back(crash_env, tmp_path):
    """Death between a temp write and its rename: recovery removes the
    orphan + uncommitted intent and the resume regenerates the
    artifact — exactly once."""
    from srtb_tpu.io import writers
    tmp, proc, n, segments, golden = crash_env
    cfg = _cfg(tmp, "crash_c")

    class Dead(BaseException):
        """Not Exception: nothing may 'handle' the simulated kill."""

    count = [0]

    def hook(path):
        count[0] += 1
        if count[0] == 3:
            raise Dead(path)

    writers._PRE_RENAME_HOOK = hook
    try:
        with pytest.raises(BaseException):
            with Pipeline(cfg, source=make_resumable_source(cfg),
                          processor=proc) as pipe:
                pipe.run()
    finally:
        writers._PRE_RENAME_HOOK = None
    stats, counters = _run_to_completion(cfg, proc)
    assert counters["rolled_back_intents"] >= 1
    assert snapshot_outputs(_run_dir(cfg)) == golden


def test_crash_replay_any_prefix_property(crash_env, tmp_path):
    """Seeded property: crash at ANY (site, segment) point, resume,
    and the final output set equals the golden run exactly once."""
    tmp, proc, n, segments, golden = crash_env
    rng = np.random.default_rng(7)
    sites = ("checkpoint", "sink_write", "dispatch", "fetch")
    for trial in range(3):
        site = sites[int(rng.integers(len(sites)))]
        seg = int(rng.integers(0, segments))
        tag = f"prop_{trial}"
        cfg = _cfg(tmp, tag, fault_plan=f"{site}:fatal@{seg}")
        with pytest.raises(Exception):
            with Pipeline(cfg, source=make_resumable_source(cfg),
                          processor=proc) as pipe:
                pipe.run()
        _run_to_completion(cfg.replace(fault_plan=""), proc)
        assert snapshot_outputs(_run_dir(cfg)) == golden, \
            f"trial {trial}: crash at {site}@{seg} broke exactly-once"


def test_write_all_exactly_once_across_crash(crash_env, tmp_path):
    """The in-place appender: a crash between the append's commit and
    the checkpoint must not double-append on resume."""
    tmp, proc, n, segments, _golden = crash_env
    golden_cfg = _cfg(tmp, "golden_w", baseband_write_all=True)
    _run_to_completion(golden_cfg, proc)
    golden = snapshot_outputs(_run_dir(golden_cfg))
    stream = [k for k in golden if k.startswith("out_stream")]
    assert stream  # the appender actually wrote

    cfg = _cfg(tmp, "crash_w", baseband_write_all=True,
               fault_plan="checkpoint:fatal@2")
    with pytest.raises(Exception):
        with Pipeline(cfg, source=make_resumable_source(cfg),
                      processor=proc) as pipe:
            pipe.run()
    stats, counters = _run_to_completion(cfg.replace(fault_plan=""),
                                         proc)
    assert counters["replayed_skips"] >= 1
    assert snapshot_outputs(_run_dir(cfg)) == golden


# ----------------------------------------------------------------
# fsck
# ----------------------------------------------------------------

def test_fsck_clean_run_and_corruptions(crash_env, tmp_path):
    tmp, proc, n, segments, _golden = crash_env
    cfg = _cfg(tmp, "fsck_run")
    _run_to_completion(cfg, proc)
    mpath = cfg.run_manifest_path
    ckpath = cfg.checkpoint_path
    rep = F.fsck(mpath, ckpath)
    assert rep["clean"], rep

    assert F.main([mpath, "--checkpoint", ckpath]) == F.EXIT_CLEAN

    # delete a committed artifact -> exit 1
    run_dir = _run_dir(cfg)
    victim = next(os.path.join(run_dir, f)
                  for f in sorted(os.listdir(run_dir))
                  if f.endswith(".bin") and "stream" not in f)
    os.rename(victim, victim + ".hidden")
    assert F.main([mpath, "--checkpoint", ckpath]) == F.EXIT_ERRORS
    os.rename(victim + ".hidden", victim)

    # checkpoint ahead of manifest -> exit 1; --repair heals it
    StreamCheckpoint(ckpath).update(10 ** 6, 10 ** 9)
    assert F.main([mpath, "--checkpoint", ckpath]) == F.EXIT_ERRORS
    assert F.main([mpath, "--checkpoint", ckpath, "--repair"]) \
        == F.EXIT_CLEAN
    assert F.main([mpath, "--checkpoint", ckpath]) == F.EXIT_CLEAN

    # missing manifest -> exit 2
    assert F.main([str(tmp_path / "nope.jsonl")]) == F.EXIT_UNVERIFIABLE


def test_fsck_repair_truncates_torn_wal(crash_env, tmp_path):
    tmp, proc, n, segments, _golden = crash_env
    cfg = _cfg(tmp, "fsck_torn")
    _run_to_completion(cfg, proc)
    with open(cfg.run_manifest_path, "ab") as f:
        f.write(b'{"t":"ckpt","half')
    assert F.main([cfg.run_manifest_path]) == F.EXIT_ERRORS
    assert F.main([cfg.run_manifest_path, "--repair"]) == F.EXIT_CLEAN


def test_fsck_selftest_is_sharp():
    assert F.selftest() == []


# ----------------------------------------------------------------
# writer-pool commit hook + telemetry v5
# ----------------------------------------------------------------

def test_py_pool_fires_on_done_after_write(tmp_path):
    from srtb_tpu.io.native_writer import AsyncWriterPool
    pool = AsyncWriterPool(2, prefer_native=False)
    fired = []
    p = str(tmp_path / "x.bin")
    pool.submit(p, b"payload", on_done=lambda: fired.append(p))
    pool.drain()
    assert fired == [p] and os.path.exists(p)
    # a FAILING write must not commit
    bad = str(tmp_path / "no_dir" / "y.bin")
    pool.submit(bad, b"payload", on_done=lambda: fired.append(bad))
    pool.drain()
    assert fired == [p]
    with pytest.raises(RuntimeError):
        pool.raise_new_errors("test")
    pool.close()


def test_telemetry_v5_and_report(crash_env, tmp_path):
    from srtb_tpu.tools import telemetry_report as TR
    from srtb_tpu.utils.telemetry import SPAN_SCHEMA_VERSION
    assert SPAN_SCHEMA_VERSION == 13
    tmp, proc, n, segments, _golden = crash_env
    journal = str(tmp_path / "j.jsonl")
    cfg = _cfg(tmp, "tele", telemetry_journal_path=journal)
    _run_to_completion(cfg, proc)
    recs = TR.load(journal)
    assert recs
    for r in recs:
        assert r["v"] == 13
        for k in ("recovered_segments", "replayed_skips",
                  "rolled_back_intents"):
            assert k in r, (k, r)
    rep = TR.report(journal)
    assert rep["durability"]["replayed_skips"] == 0
    # mixed v4/v5: old records without the fields still summarize
    with open(journal, "a") as f:
        f.write(json.dumps({"type": "segment_span", "v": 4,
                            "ts": recs[-1]["ts"] + 1.0, "segment": 99,
                            "stages_ms": {"sink": 1.0},
                            "degrade_level": 0, "retries": 0}) + "\n")
    rep2 = TR.report(journal)
    assert rep2["records"] == len(recs) + 1
    assert rep2["durability"]["records"] == len(recs)


# ----------------------------------------------------------------
# the real thing (slow): SIGKILL subprocess soak
# ----------------------------------------------------------------

@pytest.mark.slow
def test_sigkill_crash_soak_two_kills():
    from srtb_tpu.tools.crash_soak import run_soak
    report = run_soak(seed=1, segments=5, log2n=12,
                      kill_plan="ckpt_stall@1,rename@1")
    assert report["ok"] and report["sigkills"] == 2
    assert report["replayed_skips"] >= 1
    assert report["rolled_back_intents"] >= 1
