"""Multi-chip tests on the virtual 8-device CPU mesh: DM-trial grid and the
fully sharded ("dm", "seq") segment step, cross-checked against the
single-device SegmentProcessor (self-consistency oracle, the strategy the
reference uses for generic-vs-handwritten kernels)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from srtb_tpu.config import Config
from srtb_tpu.ops import dedisperse as dd
from srtb_tpu.parallel import dm_grid, mesh as M, segment_dist
from srtb_tpu.parallel.segment_dist import DistSegmentProcessor
from srtb_tpu.pipeline.segment import SegmentProcessor
from srtb_tpu.io.synth import make_dispersed_baseband
from srtb_tpu.utils.bufferpool import BufferPool


def _cfg(tmpdir="", n=1 << 14, dm=30.0):
    return Config(
        baseband_input_count=n,
        baseband_input_bits=8,
        baseband_format_type="simple",
        baseband_freq_low=1405.0,
        baseband_bandwidth=64.0,
        baseband_sample_rate=128e6,
        dm=dm,
        spectrum_channel_count=1 << 6,
        signal_detect_signal_noise_threshold=6.0,
        signal_detect_max_boxcar_length=32,
        mitigate_rfi_average_method_threshold=100.0,
        mitigate_rfi_spectral_kurtosis_threshold=2.0,
        baseband_reserve_sample=False,
    )


@pytest.fixture(scope="module")
def raw_segment():
    cfg = _cfg()
    return make_dispersed_baseband(
        cfg.baseband_input_count, cfg.baseband_freq_low,
        cfg.baseband_bandwidth, cfg.dm,
        pulse_positions=cfg.baseband_input_count // 2, pulse_amp=25.0)


def test_dm_grid_finds_true_dm(raw_segment):
    """8 DM trials across 8 chips; the trial nearest the true DM must give
    the highest peak SNR."""
    cfg = _cfg()
    mesh = M.dm_mesh(8)
    proc = SegmentProcessor(cfg.replace(dm=0.0))
    # spectrum before dedispersion: run stage-1 part manually
    from srtb_tpu.ops import fft as F, rfi, unpack as U
    x = U.unpack(jnp.asarray(raw_segment), 8)
    spec = F.segment_rfft(x)
    spec = rfi.mitigate_rfi_average_and_normalize(
        spec, cfg.mitigate_rfi_average_method_threshold, proc.norm_coeff)
    spec = jnp.stack([jnp.real(spec), jnp.imag(spec)])

    dm_list = [0.0, 10.0, 20.0, 30.0, 40.0, 50.0, 60.0, 70.0]
    f_min, f_c, df = dd.spectrum_frequencies(cfg, proc.n_spectrum)
    bank = dm_grid.build_chirp_bank(dm_list, proc.n_spectrum, f_min, df, f_c,
                                    mesh=mesh)
    res = dm_grid.dm_trial_search(
        spec, bank, dm_list, mesh,
        channel_count=proc.channel_count,
        time_reserved_count=0,
        snr_threshold=6.0,
        max_boxcar_length=32,
        sk_threshold=cfg.mitigate_rfi_spectral_kurtosis_threshold)
    idx, snr = dm_grid.best_trial(res)
    assert dm_list[idx] == 30.0, \
        f"best dm {dm_list[idx]} snr {snr}, peaks={np.asarray(res.snr_peaks).max(axis=-1)}"

    # len_cap threads through to the trial waterfalls (Config.fft_len_cap
    # contract): forcing the in-trial four-step recursion must not
    # change any detection outcome
    res_cap = dm_grid.dm_trial_search(
        spec, bank, dm_list, mesh,
        channel_count=proc.channel_count,
        time_reserved_count=0,
        snr_threshold=6.0,
        max_boxcar_length=32,
        sk_threshold=cfg.mitigate_rfi_spectral_kurtosis_threshold,
        len_cap=1 << 4)
    np.testing.assert_allclose(
        np.asarray(res_cap.snr_peaks), np.asarray(res.snr_peaks),
        rtol=2e-4, atol=1e-3)


def test_chirp_bank_on_device_matches_host():
    mesh = M.dm_mesh(8)
    dm_list = np.linspace(10.0, 80.0, 8)
    n = 1 << 10
    host = dm_grid.build_chirp_bank(dm_list, n, 1405.0, 64.0 / n, 1469.0,
                                    mesh=mesh)
    dev = dm_grid.build_chirp_bank(dm_list, n, 1405.0, 64.0 / n, 1469.0,
                                   mesh=mesh, on_device=True)
    err = np.abs(np.angle(np.asarray(dev) * np.conj(np.asarray(host))))
    assert np.max(err) < 5e-3


def test_dist_segment_matches_single_device(raw_segment):
    """The ("dm", "seq")-sharded step must reproduce the single-device
    pipeline's detection outputs for the same DM."""
    cfg = _cfg()
    single = SegmentProcessor(cfg)
    wf, res_single = single.process(raw_segment)

    mesh = M.make_mesh(n_dm=2, n_seq=4)
    dist = DistSegmentProcessor(cfg, mesh, dm_list=[cfg.dm, 0.0])
    res = dist.process(raw_segment)

    counts_single = np.asarray(res_single.signal_counts)[0]
    counts_dist = np.asarray(res.signal_counts)[0, 0]  # dm 0, stream 0
    np.testing.assert_array_equal(counts_dist, counts_single)
    assert int(np.asarray(res.zero_count)[0, 0]) == \
        int(np.asarray(res_single.zero_count)[0])
    np.testing.assert_allclose(np.asarray(res.time_series)[0, 0],
                               np.asarray(res_single.time_series)[0],
                               rtol=2e-3, atol=1e-2)
    # trial at dm=0 must be weaker than the matched trial
    assert np.asarray(res.snr_peaks)[0].max() > \
        np.asarray(res.snr_peaks)[1].max()


def test_dist_segment_seq_only(raw_segment):
    """Pure sequence sharding (seq=8, dm=1)."""
    cfg = _cfg()
    mesh = M.make_mesh(n_dm=1, n_seq=8)
    dist = DistSegmentProcessor(cfg, mesh)
    res = dist.process(raw_segment)
    assert np.asarray(res.signal_counts).shape[0] == 1
    assert np.asarray(res.signal_counts).sum() > 0  # pulse found


def test_dm_search_pipeline(tmp_path):
    """File -> DMSearchPipeline over an 8-trial grid on the 8-device mesh:
    the best trial per segment must be the injected DM."""
    cfg = _cfg().replace(
        dm_list=[0.0, 10.0, 20.0, 30.0, 40.0, 50.0, 60.0, 70.0],
        baseband_output_file_prefix=str(tmp_path / "dm_"),
        signal_detect_signal_noise_threshold=7.0,
    )
    raw = make_dispersed_baseband(
        cfg.baseband_input_count, cfg.baseband_freq_low,
        cfg.baseband_bandwidth, 30.0,
        pulse_positions=cfg.baseband_input_count // 2, pulse_amp=25.0)
    path = str(tmp_path / "in.bin")
    raw.tofile(path)
    cfg = cfg.replace(input_file_path=path)

    from srtb_tpu.pipeline.runtime import DMSearchPipeline
    import json
    pipe = DMSearchPipeline(cfg)
    stats = pipe.run()
    assert stats.segments == 1
    with open(pipe.trials_path) as f:
        rec = json.loads(f.readline())
    assert rec["best_dm"] == 30.0
    assert rec["best_snr"] > 7.0


# ---------------------------------------- the loop and its reader buffers

GRID_SEGMENTS = 6


class _SpyPool(BufferPool):
    """A ``BufferPool`` that writes what happens to it into ``events``
    (shared with the spy around the fetch): ("acquire" | "release",
    address of the block)."""

    def __init__(self, events):
        super().__init__("spy")
        self.events = events

    def acquire(self, nbytes, zero=True):
        buf = super().acquire(nbytes, zero)
        self.events.append(("acquire", buf.ctypes.data))
        return buf

    def release(self, buf):
        self.events.append(("release", buf.ctypes.data))
        super().release(buf)


class _PoolLessSource:
    """What a UDP receiver looks like to the loop: segments in buffers
    of its own, no ``pool`` attribute."""

    def __init__(self, cfg):
        from srtb_tpu.pipeline.work import SegmentWork
        raw = np.fromfile(cfg.input_file_path, dtype=np.uint8)
        self._segs = iter(
            SegmentWork(data=chunk.copy(), timestamp=k)
            for k, chunk in enumerate(
                raw.reshape(-1, cfg.baseband_input_count)))

    def __iter__(self):
        return self._segs


@pytest.fixture(scope="module")
def grid(tmp_path_factory):
    """One compiled ``DMSearchPipeline`` over a file of
    ``GRID_SEGMENTS`` segments (a pulse at DM 30 in each); every test
    gives it a source and an output file of its own through
    ``_grid_run``."""
    from srtb_tpu.pipeline.runtime import DMSearchPipeline
    tmp = tmp_path_factory.mktemp("grid")
    cfg = _cfg()
    n = cfg.baseband_input_count
    raw = make_dispersed_baseband(
        n * GRID_SEGMENTS, cfg.baseband_freq_low, cfg.baseband_bandwidth,
        30.0, pulse_positions=[n * k + n // 2
                               for k in range(GRID_SEGMENTS)],
        pulse_amp=25.0)
    path = str(tmp / "in.bin")
    raw.tofile(path)
    cfg = cfg.replace(
        dm_list=[0.0, 30.0, 60.0, 90.0], n_devices=4,
        input_file_path=path,
        baseband_output_file_prefix=str(tmp / "dm_"),
        signal_detect_signal_noise_threshold=7.0)
    search = DMSearchPipeline(cfg)
    yield search
    search.close()


def _grid_run(search, source, out, **kw):
    """``search.run(**kw)`` on ``source``, records into ``out``; returns
    the records written (also when the run raises: re-raised after)."""
    import json

    from srtb_tpu.pipeline.runtime import PipelineStats
    search.source = source
    search.trials_path = str(out)
    search.stats = PipelineStats()
    try:
        search.run(**kw)
    finally:
        source_close = getattr(source, "close", None)
        if source_close is not None:
            source_close()
    with open(out) as f:
        return [json.loads(ln) for ln in f]


def _pooled_reader(search, pool):
    from srtb_tpu.io.file_input import BasebandFileReader
    return BasebandFileReader(search.cfg, buffer_pool=pool)


def _at_depth(monkeypatch, search, depth):
    """``inflight_segments`` for the runs of one test: ``run()`` reads
    it off ``search.cfg`` when it starts."""
    monkeypatch.setattr(search, "cfg",
                        search.cfg.replace(inflight_segments=depth))


@pytest.mark.parametrize("depth", [1, 2])
def test_dm_search_returns_reader_buffers(grid, tmp_path, monkeypatch,
                                          depth):
    """A file of 6 segments through a pool of its own: the loop hands
    every buffer back, so the reader allocates one block for every step
    of its window and fills the same warm blocks again; the records are
    those of a source with no pool (which runs as it always did),
    timestamps apart."""
    _at_depth(monkeypatch, grid, depth)
    pool = BufferPool("t")
    got = _grid_run(grid, _pooled_reader(grid, pool), tmp_path / "a")
    stats = pool.stats()
    assert grid.stats.segments == GRID_SEGMENTS
    # one per pull and one for the reader's look past the file's end
    assert stats["acquires"] == GRID_SEGMENTS + 1
    # one block a step of the window: the pull comes with one step
    # fewer in flight than the window holds
    assert stats["new_blocks"] == depth
    assert stats["in_use"] == 0 and stats["cached_blocks"] >= 1
    want = _grid_run(grid, _PoolLessSource(grid.cfg), tmp_path / "b")
    assert grid.stats.segments == GRID_SEGMENTS
    assert len(got) == len(want) == GRID_SEGMENTS
    assert any(r["best_dm"] == 30.0 and r["best_snr"] > 7.0 for r in got)
    for a, b in zip(got, want):
        a.pop("timestamp"), b.pop("timestamp")
        assert a == b


def test_dm_search_window_writes_the_serial_loops_records(
        grid, tmp_path, monkeypatch):
    """The records of the 6-segment file with one step in flight are
    those of the serial loop field for field (timestamps apart), in
    hand-over order, one a segment."""
    runs = {}
    for depth in (1, 2):
        _at_depth(monkeypatch, grid, depth)
        runs[depth] = _grid_run(grid, _pooled_reader(grid, BufferPool("t")),
                                tmp_path / f"depth{depth}")
        assert grid.stats.segments == GRID_SEGMENTS
    for recs in runs.values():
        assert [r.pop("segment") for r in recs] == list(
            range(GRID_SEGMENTS))
        stamps = [r.pop("timestamp") for r in recs]
        assert stamps == sorted(stamps)
    assert runs[2] == runs[1]
    assert set(runs[1][0]) == {"best_dm", "best_snr", "dm_list",
                               "peak_snr", "signal_counts", "zero_counts"}


@pytest.mark.parametrize("depth", [1, 2])
def test_dm_search_releases_after_its_own_fetch(grid, tmp_path,
                                                monkeypatch, depth):
    """Order: the uploads ``stage_input`` starts may still be reading
    the buffer until the step's results are back, so each segment's
    release follows the return of its own fetch, and a buffer is never
    handed out again between its pull and that return.  In the serial
    loop nothing of the pool is touched in between; with a step in
    flight the one thing in between is the next segment's pull, into
    another block."""
    from srtb_tpu.pipeline import runtime
    _at_depth(monkeypatch, grid, depth)
    events = []
    real = runtime.sync_with_deadline

    def spy_sync(deadline_s, fn):
        events.append(("fetch", None))
        out = real(deadline_s, fn)
        events.append(("fetched", None))
        return out

    monkeypatch.setattr(runtime, "sync_with_deadline", spy_sync)
    _grid_run(grid, _pooled_reader(grid, _SpyPool(events)),
              tmp_path / "a")
    assert len(events) == 4 * GRID_SEGMENTS + 2
    # the reader's last acquire finds the file read and gives it back:
    # the last two events in the serial loop, ahead of the last step's
    # fetch where that step waits in the window
    look = next(k for k in range(len(events) - 1)
                if events[k][0] == "acquire"
                and events[k + 1] == ("release", events[k][1]))
    assert look == len(events) - 2 - 3 * (depth - 1)
    steps = events[:look] + events[look + 2:]
    names = [e for e, _ in steps]
    if depth == 1:
        assert names == ["acquire", "fetch", "fetched",
                         "release"] * GRID_SEGMENTS
    else:
        assert names == ["acquire"] + ["acquire", "fetch", "fetched",
                                       "release"] * (GRID_SEGMENTS - 1) \
            + ["fetch", "fetched", "release"]
    # segment k's block is the k-th acquired and the k-th released, and
    # it is out (not acquired again) until its own fetch has returned
    acquired = [a for e, a in steps if e == "acquire"]
    released = [a for e, a in steps if e == "release"]
    assert acquired == released
    out = set()
    fetched = 0
    for e, a in steps:
        if e == "acquire":
            assert a not in out, steps
            out.add(a)
        elif e == "fetched":
            fetched += 1
        elif e == "release":
            assert a == acquired[fetched - 1]
            out.discard(a)
    assert not out and len(set(acquired)) == depth


@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("way_out", ["max_segments", "stage_input",
                                     "process", "fetch"])
def test_dm_search_every_way_out_gives_the_buffer_back(
        grid, tmp_path, monkeypatch, way_out, depth):
    """The segment pulled and dropped by ``max_segments`` (with a step
    in flight, which is retired: exactly that many records), and the
    segments in hand when the upload, the step or the fetch raises
    (with a step in flight there are two: both go back): every buffer
    is in the pool again and the exception is the caller's."""
    from srtb_tpu.pipeline import runtime
    _at_depth(monkeypatch, grid, depth)

    class Boom(RuntimeError):
        pass

    def fail_on_second(real):
        calls = []

        def wrapper(*a, **kw):
            calls.append(1)
            if len(calls) == 2:
                raise Boom(way_out)
            return real(*a, **kw)
        return wrapper

    pool = BufferPool("t")
    reader = _pooled_reader(grid, pool)
    if way_out == "max_segments":
        recs = _grid_run(grid, reader, tmp_path / "a", max_segments=2)
        assert len(recs) == 2 and grid.stats.segments == 2
        assert [r["segment"] for r in recs] == [0, 1]
        # the third segment was pulled, dropped and handed back
        assert pool.stats()["acquires"] == 3
    else:
        if way_out == "fetch":
            monkeypatch.setattr(runtime, "sync_with_deadline",
                                fail_on_second(
                                    runtime.sync_with_deadline))
        else:
            monkeypatch.setattr(grid.processor, way_out,
                                fail_on_second(
                                    getattr(grid.processor, way_out)))
        with pytest.raises(Boom, match=way_out):
            _grid_run(grid, reader, tmp_path / "a")
        with open(tmp_path / "a") as f:
            written = len(f.readlines())
        if way_out == "fetch" or depth == 1:
            # the second fetch is segment 1's: segment 0 is on record;
            # with a step in flight segment 2 is in hand beside it
            assert grid.stats.segments == written == 1
            assert pool.stats()["acquires"] == depth + 1
        else:
            # segment 1's upload or step raises with segment 0's step
            # still in flight: abandoned with it, nothing on record
            assert grid.stats.segments == written == 0
            assert pool.stats()["acquires"] == 2
    stats = pool.stats()
    assert stats["in_use"] == 0 and stats["new_blocks"] == depth


class _RecordingProcessor:
    """Stands in for the grid's processor: writes ("process", k) when
    step k is enqueued and ("fetched", k) when something first reads one
    of step k's results, which the loop does in its fetch."""

    class _Handle:
        def __init__(self, events, k, value):
            self._events, self._k, self._value = events, k, value

        def __array__(self, dtype=None, copy=None):
            if ("fetched", self._k) not in self._events:
                self._events.append(("fetched", self._k))
            return self._value

    def __init__(self, events, n_dm):
        self.events, self.n_dm, self._k = events, n_dm, 0

    def stage_input(self, raw):
        return raw

    def process(self, staged):
        from srtb_tpu.parallel.segment_dist import DistSegmentResult
        k, self._k = self._k, self._k + 1
        self.events.append(("process", k))
        zeros = np.zeros((self.n_dm, 1, 1), np.float32)
        return DistSegmentResult(
            zero_count=self._Handle(self.events, k, zeros[..., 0]),
            signal_counts=self._Handle(self.events, k, zeros),
            snr_peaks=self._Handle(self.events, k, zeros + k),
            time_series=None)


@pytest.mark.parametrize("depth", [1, 2])
def test_dm_search_enqueues_ahead_of_the_fetch(grid, tmp_path, monkeypatch,
                                               depth):
    """With one step in flight ``process(k+1)`` is called before step
    k's results are fetched; in the serial loop after.  The counter
    ``grid_steps_ahead`` says how often a step was enqueued behind one
    not yet fetched: segments - 1 a ``run()``, and 0."""
    import io

    from srtb_tpu.utils.logging import LEVEL_INFO, log
    from srtb_tpu.utils.metrics import metrics
    _at_depth(monkeypatch, grid, depth)
    events = []
    monkeypatch.setattr(grid, "processor",
                        _RecordingProcessor(events, len(grid.dm_list)))
    before = metrics.get("grid_steps_ahead")
    lines = io.StringIO()
    monkeypatch.setattr(log, "stream", lines)
    monkeypatch.setattr(log, "level", max(log.level, LEVEL_INFO))
    recs = _grid_run(grid, _PoolLessSource(grid.cfg), tmp_path / "a")
    assert [r["segment"] for r in recs] == list(range(GRID_SEGMENTS))
    assert [r["best_snr"] for r in recs] == list(range(GRID_SEGMENTS))
    for k in range(GRID_SEGMENTS - 1):
        enqueued_first = events.index(("process", k + 1)) \
            < events.index(("fetched", k))
        assert enqueued_first == (depth == 2), (k, events)
        # never two steps ahead: step k's results are back before
        # segment k+2 is enqueued
        if k + 2 < GRID_SEGMENTS:
            assert events.index(("fetched", k)) \
                < events.index(("process", k + 2))
    ahead = (GRID_SEGMENTS - 1) * (depth - 1)
    assert metrics.get("grid_steps_ahead") - before == ahead
    # the loop's closing line carries the run's count
    assert lines.getvalue().splitlines()[-1].endswith(
        f"[dm_search] {GRID_SEGMENTS} segments, {GRID_SEGMENTS} of them "
        f"in this run at window {depth}: grid_steps_ahead {ahead}")


def test_dist_segment_two_streams():
    """Multi-stream (2-pol interleaved) distributed step: both polarization
    streams flow through the sharded FFT/detect chain."""
    cfg = _cfg().replace(baseband_format_type="interleaved_samples_2")
    rng = np.random.default_rng(0)
    raw = rng.integers(0, 256,
                       size=cfg.baseband_input_count * 2,
                       dtype=np.uint8)
    mesh = M.make_mesh(n_dm=2, n_seq=4)
    dist = DistSegmentProcessor(cfg, mesh, dm_list=[0.0, 10.0])
    res = dist.process(raw)
    assert np.asarray(res.signal_counts).shape[:2] == (2, 2)  # [n_dm, S]
    assert np.asarray(res.time_series).shape[:2] == (2, 2)

    # cross-check stream results against the single-device processor
    single = SegmentProcessor(cfg.replace(dm=0.0))
    _, res_single = single.process(raw)
    np.testing.assert_array_equal(
        np.asarray(res.signal_counts)[0],
        np.asarray(res_single.signal_counts))


def test_dist_segment_window_matches_single_device(raw_segment):
    """A configured non-rectangle window must flow through the multi-chip
    step too — applied at unpack on each device's seq-shard and divided
    back out of the waterfall — matching the single-chip windowed run."""
    cfg = _cfg()
    single = SegmentProcessor(cfg, window_name="hamming")
    _, res_single = single.process(raw_segment)

    mesh = M.make_mesh(n_dm=2, n_seq=4)
    dist = DistSegmentProcessor(cfg, mesh, dm_list=[cfg.dm, 0.0],
                                window_name="hamming")
    res = dist.process(raw_segment)

    np.testing.assert_array_equal(
        np.asarray(res.signal_counts)[0, 0],
        np.asarray(res_single.signal_counts)[0])
    np.testing.assert_allclose(np.asarray(res.time_series)[0, 0],
                               np.asarray(res_single.time_series)[0],
                               rtol=2e-3, atol=1e-2)


def _force_chirp_in_step(monkeypatch):
    """A chip so small that no bank is under the rule's share of it:
    the df64 phase is then evaluated inside every step."""
    monkeypatch.setattr(segment_dist, "_device_bytes_limit",
                        lambda mesh: 1)


CHIRP_WAYS = {
    # how the phase is evaluated, and where the planes then live
    "host_f64_bank": dict(chirp_on_device=False),
    "device_df64_bank": dict(chirp_on_device=True),
    "in_step_df64": dict(chirp_on_device=True),
}


@pytest.mark.parametrize("way", sorted(CHIRP_WAYS))
def test_dist_segment_chirp_on_device_matches_bank(raw_segment, way,
                                                   monkeypatch):
    """Each way of making the trials' chirp (float64 on the host, df64
    on the device once, df64 inside every step) must reproduce the
    host-f64 bank's detections."""
    cfg = _cfg()
    mesh = M.make_mesh(n_dm=2, n_seq=4)
    dms = [0.0, 15.0, 30.0, 45.0]
    bank = DistSegmentProcessor(cfg, mesh, dm_list=dms,
                                chirp_on_device=False)
    if way == "in_step_df64":
        _force_chirp_in_step(monkeypatch)
    other = DistSegmentProcessor(cfg, mesh, dm_list=dms, **CHIRP_WAYS[way])
    in_step = np.shape(other.chirp_bank) == (len(dms), 2)
    assert in_step == (way == "in_step_df64"), np.shape(other.chirp_bank)
    res_a = bank.process(raw_segment)
    res_b = other.process(raw_segment)
    np.testing.assert_array_equal(np.asarray(res_a.zero_count),
                                  np.asarray(res_b.zero_count))
    np.testing.assert_allclose(np.asarray(res_a.time_series),
                               np.asarray(res_b.time_series),
                               rtol=2e-3, atol=2e-2)
    np.testing.assert_array_equal(np.asarray(res_a.signal_counts),
                                  np.asarray(res_b.signal_counts))


def test_device_made_bank_is_the_df64_chirp_shard_by_shard():
    """The bank made at construction is ``chirp_factor_df64_ri`` with
    the arguments the in-step arm passes, on every (dm, seq) shard: the
    same planes, bit for bit, each seq shard at its own ``i0``."""
    from srtb_tpu.ops import df64 as ds

    cfg = _cfg()
    mesh = M.make_mesh(n_dm=2, n_seq=4)
    dms = [0.0, 15.0, 30.0, 45.0]
    proc = DistSegmentProcessor(cfg, mesh, dm_list=dms,
                                chirp_on_device=True)
    bank = np.asarray(proc.chirp_bank)
    assert bank.shape == (len(dms), 2, proc.n_spectrum)
    assert bank.dtype == np.float32
    assert proc.chirp_bank.sharding.spec == P("dm", None, "seq")
    n_local = proc.n_spectrum // 4
    one = jax.jit(lambda hi, lo, i0: dd.chirp_factor_df64_ri(
        n_local, proc.f_min, proc.df, proc.f_c, hi, i0=i0, dm_lo=lo,
        anchor_consts=proc.chirp_anchor_consts))
    dm_hi, dm_lo = ds.from_float64(np.asarray(dms, np.float64))
    for t in range(len(dms)):
        for k in range(4):
            want = np.asarray(one(dm_hi[t], dm_lo[t],
                                  jnp.int32(k * n_local)))
            np.testing.assert_array_equal(
                bank[t, :, k * n_local:(k + 1) * n_local], want,
                err_msg=f"trial {t}, seq shard {k}")
    # and the shards differ: an i0 stuck at 0 would repeat shard 0
    assert not np.array_equal(bank[2, :, :n_local],
                              bank[2, :, n_local:2 * n_local])


def _iter_eqns(jaxpr):
    """Every equation of a jaxpr tree, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for item in (v if isinstance(v, (list, tuple)) else [v]):
                if hasattr(item, "jaxpr"):
                    yield from _iter_eqns(item.jaxpr)
                elif hasattr(item, "eqns"):
                    yield from _iter_eqns(item)


@pytest.mark.parametrize("in_step", [False, True],
                         ids=["banked", "in_step"])
def test_banked_step_evaluates_no_phase(in_step, monkeypatch):
    """With the bank resident the step evaluates no chirp phase: no
    ``sin`` / ``cos`` under ``srtb.chirp`` (the R2C's twiddles are the
    only trigonometry left), where the in-step arm holds both."""
    from srtb_tpu.ops import scopes as S

    cfg = _cfg()
    mesh = M.make_mesh(n_dm=2, n_seq=4)
    if in_step:
        _force_chirp_in_step(monkeypatch)
    dist = DistSegmentProcessor(cfg, mesh, dm_list=[cfg.dm, 0.0],
                                chirp_on_device=True)
    raw = jax.device_put(np.zeros(cfg.segment_bytes(1), np.uint8),
                         NamedSharding(mesh, P("seq")))
    jaxpr = jax.make_jaxpr(dist._step)(raw, dist.chirp_bank, dist.rfi_mask)
    trig = [(eqn.primitive.name, str(eqn.source_info.name_stack))
            for eqn in _iter_eqns(jaxpr.jaxpr)
            if eqn.primitive.name in ("sin", "cos")]
    assert trig, "the R2C's twiddles should be here"
    in_chirp = {name for name, stack in trig if S.CHIRP in stack}
    assert in_chirp == ({"sin", "cos"} if in_step else set()), trig
    elsewhere = {stack for _n, stack in trig if S.CHIRP not in stack}
    assert all(S.FFT_R2C in stack for stack in elsewhere), elsewhere


V5E_BYTES_LIMIT = 16_911_433_728     # 15.75 GiB, a v5e's memory_stats


@pytest.mark.parametrize("trials_local,log2n,n_seq,limit,bank", [
    # the grid cell: 2^27 samples, two trials a chip, 1.07 GB of 15.75
    (2, 27, 1, V5E_BYTES_LIMIT, True),
    # one trial a chip (a v5e-8), and a seq axis over 2: smaller still
    (1, 27, 1, V5E_BYTES_LIMIT, True),
    (2, 27, 2, V5E_BYTES_LIMIT, True),
    # 2^28, two and three trials a chip: 2.15 GB, 12.7 %; 3.22 GB, 19 %
    (2, 28, 1, V5E_BYTES_LIMIT, True),
    (3, 28, 1, V5E_BYTES_LIMIT, True),
    # over the share: 4.29 GB, 25 % of the chip, however it is made up
    (4, 28, 1, V5E_BYTES_LIMIT, False),
    (2, 29, 1, V5E_BYTES_LIMIT, False),
    (8, 27, 1, V5E_BYTES_LIMIT, False),
    # a platform that reports no limit (the CPU meshes) holds the bank
    (8, 30, 1, None, True),
    (8, 30, 1, 0, True),
])
def test_chirp_bank_rule(trials_local, log2n, n_seq, limit, bank):
    """The rule reads shapes and the device only: the bank's bytes a
    chip against a fixed share of what the chip may allocate."""
    nbytes = segment_dist.chirp_bank_bytes_per_chip(
        trials_local, (1 << log2n) // 2, n_seq)
    assert nbytes == trials_local * 2 * ((1 << log2n) // 2 // n_seq) * 4
    assert segment_dist.holds_chirp_bank(nbytes, limit) is bank
    if (trials_local, log2n, n_seq) == (2, 27, 1):
        assert nbytes == 1_073_741_824


@pytest.mark.parametrize("way", sorted(CHIRP_WAYS))
def test_chirp_bank_bytes_gauge(way, monkeypatch):
    """``chirp_bank_bytes``: the bank's bytes resident a chip, 0 where
    the phase is generated in the step."""
    from srtb_tpu.utils.metrics import metrics

    cfg = _cfg()
    mesh = M.make_mesh(n_dm=2, n_seq=4)
    dms = [0.0, 15.0, 30.0, 45.0]
    if way == "in_step_df64":
        _force_chirp_in_step(monkeypatch)
    metrics.set("chirp_bank_bytes", -1)
    proc = DistSegmentProcessor(cfg, mesh, dm_list=dms, **CHIRP_WAYS[way])
    got = metrics.get("chirp_bank_bytes")
    if way == "in_step_df64":
        assert got == 0
    else:
        shard = proc.chirp_bank.addressable_shards[0].data
        assert got == shard.nbytes == 2 * 2 * (proc.n_spectrum // 4) * 4


def test_dist_rejects_non_dividing_channel_count():
    """Non-power-of-two channel counts that don't divide the spectrum
    truncate on the single-chip path but would straddle a shard boundary
    distributed — the round-3 sweep caught this as a cryptic reshape
    failure deep inside shard_map; it must be a clear constructor error."""
    cfg = Config(
        baseband_input_count=1 << 14, baseband_input_bits=2,
        baseband_format_type="simple", baseband_freq_low=1405.0,
        baseband_bandwidth=64.0, baseband_sample_rate=128e6, dm=5.0,
        spectrum_channel_count=48, signal_detect_max_boxcar_length=8,
        baseband_reserve_sample=False)
    mesh = M.make_mesh(n_dm=2, n_seq=2, devices=jax.devices()[:4])
    with pytest.raises(ValueError, match="must divide"):
        DistSegmentProcessor(cfg, mesh, dm_list=[1.0, 2.0, 3.0, 4.0])


def test_dist_rows_impl_knob(raw_segment, monkeypatch):
    """SRTB_DIST_ROWS_IMPL=pallas must reach the distributed leg FFTs
    (as pallas_interpret off-TPU), keep the step's outputs on-plan, and
    reject typos loudly."""
    from srtb_tpu.ops import fft as F

    cfg = _cfg()
    mesh = M.make_mesh(n_dm=2, n_seq=4)
    monkeypatch.delenv("SRTB_DIST_ROWS_IMPL", raising=False)
    base = DistSegmentProcessor(cfg, mesh, dm_list=[cfg.dm, 0.0])
    res_base = base.process(raw_segment)

    impls_seen = []
    orig = F._fft_minor

    def spy(x, inverse, rows_impl="xla", len_cap=None):
        impls_seen.append(rows_impl)
        return orig(x, inverse, rows_impl, len_cap)

    monkeypatch.setenv("SRTB_DIST_ROWS_IMPL", "pallas")
    monkeypatch.setattr(F, "_fft_minor", spy)
    try:
        import srtb_tpu.parallel.dist_fft as DF
        monkeypatch.setattr(DF, "_fft_minor", spy)
        dist = DistSegmentProcessor(cfg, mesh, dm_list=[cfg.dm, 0.0])
        res = dist.process(raw_segment)
    finally:
        monkeypatch.setattr(F, "_fft_minor", orig)
    assert "pallas_interpret" in impls_seen, impls_seen
    np.testing.assert_array_equal(np.asarray(res.signal_counts),
                                  np.asarray(res_base.signal_counts))

    monkeypatch.setenv("SRTB_DIST_ROWS_IMPL", "palas")
    with pytest.raises(ValueError, match="SRTB_DIST_ROWS_IMPL"):
        DistSegmentProcessor(cfg, mesh, dm_list=[cfg.dm, 0.0])


def _collect_collectives(jaxpr, out):
    """(primitive name, mesh axes) of every collective in a jaxpr tree."""
    for eqn in _iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name in ("all_to_all", "ppermute", "all_gather",
                    "reduce_scatter") or "psum" in name:
            ax = eqn.params.get("axes") or eqn.params.get("axis_name")
            ax = (ax,) if isinstance(ax, str) else tuple(ax)
            out.append((name.replace("psum_invariant", "psum"), ax))
    return out


def test_dist_step_collective_inventory(raw_segment):
    """The module docstring's collective inventory, enforced: 3 a2a(seq)
    + 2 ppermute(seq) + 3 psum(seq) + 3 psum(dm) per segment.  A change
    that silently adds a collective (an accidental replication, a
    sharding-constraint round trip) must fail here, not surface as an
    unexplained ICI regression on hardware (round-3 verdict #7)."""
    from collections import Counter

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = _cfg()
    mesh = M.make_mesh(n_dm=2, n_seq=4)
    dist = DistSegmentProcessor(cfg, mesh, dm_list=[cfg.dm, 0.0])
    raw = jax.device_put(np.zeros(cfg.segment_bytes(1), np.uint8),
                         NamedSharding(mesh, P("seq")))
    args = [raw, dist.chirp_bank, dist.rfi_mask]
    if dist.window is not None:
        args.append(dist.window)
    jaxpr = jax.make_jaxpr(dist._step)(*args)
    got = Counter(_collect_collectives(jaxpr.jaxpr, []))
    assert got == Counter({
        ("all_to_all", ("seq",)): 3,
        ("ppermute", ("seq",)): 2,
        ("psum", ("seq",)): 3,
        ("psum", ("dm",)): 3,
    }), got
