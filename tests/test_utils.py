"""Utility-layer tests: buffer pool (cached-allocator semantics), metrics,
running-mean quantizer vs oracle, termination handler install."""

import jax.numpy as jnp
import numpy as np

from srtb_tpu.ops import running_mean as rm
from srtb_tpu.utils.bufferpool import BufferPool
from srtb_tpu.utils.metrics import Metrics
from srtb_tpu.utils.termination import install_termination_handler


def test_buffer_pool_reuse():
    pool = BufferPool("test")
    a = pool.acquire(1024)
    assert a.nbytes == 1024 and a.dtype == np.uint8
    base_id = id(a.base if a.base is not None else a)
    pool.release(a)
    b = pool.acquire(1000)  # within the 0.5 threshold -> reuse
    assert id(b.base if b.base is not None else b) == base_id
    pool.release(b)
    c = pool.acquire(256)  # too small a request for the cached 1024 block
    assert id(c.base if c.base is not None else c) != base_id
    pool.release(c)
    assert pool.free_all() == 0


def test_buffer_pool_counts_acquires_and_new_blocks():
    """The two counters that say whether reuse engages: a released
    block serves the next acquire of its size and allocates nothing; a
    second buffer out at once, or a size no cached block fits, does."""
    pool = BufferPool("count")
    assert pool.stats()["acquires"] == pool.stats()["new_blocks"] == 0
    for _ in range(6):
        pool.release(pool.acquire(1024))
    assert pool.stats()["acquires"] == 6
    assert pool.stats()["new_blocks"] == 1
    a = pool.acquire(1024)
    b = pool.acquire(1024)          # the one cached block is out
    c = pool.acquire(64)            # too small for a 1024 block
    stats = pool.stats()
    assert (stats["acquires"], stats["new_blocks"]) == (9, 3)
    assert stats["in_use"] == 3
    for buf in (a, b, c):
        pool.release(buf)
    pool.free_all()                 # the counters are cumulative
    pool.release(pool.acquire(1024))
    stats = pool.stats()
    assert (stats["acquires"], stats["new_blocks"]) == (10, 4)
    assert stats["in_use"] == 0


def test_buffer_pool_leak_detection():
    pool = BufferPool("leak")
    a = pool.acquire(64)
    assert pool.free_all() == 1
    pool.release(a)  # unknown now; warns, no crash


def test_metrics():
    m = Metrics()
    m.add("samples", 1e6)
    m.add("samples", 1e6)
    m.add("packets_total", 100)
    m.add("packets_lost", 3)
    snap = m.snapshot()
    assert snap["samples"] == 2e6
    assert abs(snap["packet_loss_rate"] - 0.03) < 1e-12
    assert "msamples_per_sec" in snap
    assert isinstance(m.to_json(), str)


def test_running_mean_vs_oracle():
    rng = np.random.default_rng(0)
    nsamp, nchan, window = 64, 8, 16
    data = rng.integers(0, 100, size=(nsamp, nchan)).astype(np.float32)
    ave0 = np.asarray(rm.running_mean_init_average(jnp.asarray(data), window))
    expected_ave0 = data[:window].mean(axis=0)
    np.testing.assert_allclose(ave0, expected_ave0, rtol=1e-5)

    out, ave = rm.running_mean(jnp.asarray(data), window,
                               jnp.asarray(ave0))
    out_o, ave_o = rm.running_mean_oracle(data, window, expected_ave0)
    np.testing.assert_array_equal(np.asarray(out), out_o)
    np.testing.assert_allclose(np.asarray(ave), ave_o, rtol=1e-4)


def test_termination_handler_idempotent():
    install_termination_handler()
    install_termination_handler()  # no crash on double install


def test_http_metrics_endpoint(tmp_path):
    """/metrics (Prometheus text) and /metrics.json on the waterfall HTTP
    server expose the runtime counters (beyond the reference's log-only
    observability, SURVEY.md §5.5)."""
    import json
    import urllib.request

    from srtb_tpu.gui.server import WaterfallHTTPServer
    from srtb_tpu.utils.metrics import metrics

    metrics.reset()
    metrics.add("segments", 3)
    metrics.add("samples", 1000)
    server = WaterfallHTTPServer(str(tmp_path), port=0).start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        text = urllib.request.urlopen(base + "/metrics").read().decode()
        assert "srtb_segments 3" in text
        snap = json.loads(
            urllib.request.urlopen(base + "/metrics.json").read())
        assert snap["segments"] == 3
        assert "elapsed_s" in snap
    finally:
        server.stop()
        metrics.reset()  # don't leak counter state into other tests


def test_waterfall_server_interactive_surface(tmp_path):
    """The interactive viewer's JSON frame feed and page controls: the
    QML-window replacement (ref: gui.hpp:34-67, main.qml:14-28) must
    expose the frame history for the scrubber and the control bar."""
    import json
    import urllib.request

    from srtb_tpu.gui.server import WaterfallHTTPServer

    for idx in range(3):
        (tmp_path / f"waterfall_s0_{idx:06d}.png").write_bytes(
            b"\x89PNG\r\n\x1a\nstub")
    (tmp_path / "waterfall_s1_000000.png").write_bytes(
        b"\x89PNG\r\n\x1a\nstub")
    srv = WaterfallHTTPServer(str(tmp_path)).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        feed = json.loads(
            urllib.request.urlopen(base + "/frames.json").read())
        assert feed["streams"]["0"] == [
            f"waterfall_s0_{i:06d}.png" for i in range(3)]
        assert feed["streams"]["1"] == ["waterfall_s1_000000.png"]
        page = urllib.request.urlopen(base + "/").read().decode()
        # latest frame inlined per stream + the interactive controls
        assert "waterfall_s0_000002.png" in page
        assert 'id="pane1"' in page
        for control in ("pause", "zin", "bright", "contrast",
                        "frames.json"):
            assert control in page, control
    finally:
        srv.stop()
