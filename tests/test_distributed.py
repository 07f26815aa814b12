"""Multi-host (DCN-analog) tests.

The reference has no distributed layer to test; this validates the one
the TPU build adds.  Strategy (SURVEY.md §4 implication): a real
two-process ``jax.distributed`` group on CPU — cross-process Gloo
collectives standing in for DCN, intra-process virtual devices standing
in for ICI — plus single-process checks of the hybrid mesh layout.
"""

import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from srtb_tpu.parallel import distributed as D


def test_hybrid_mesh_single_slice_layout():
    # 8 virtual CPU devices, no slice_index -> one slice; n_seq=2 must
    # give a 4x2 ("dm","seq") mesh with seq-contiguous rows
    mesh = D.hybrid_dm_seq_mesh(n_seq=2)
    assert mesh.axis_names == ("dm", "seq")
    assert mesh.devices.shape == (4, 2)
    flat = [d.id for d in mesh.devices.reshape(-1)]
    assert flat == sorted(flat)  # contiguous blocks per dm row


def test_hybrid_mesh_multi_slice_dm_across_dcn():
    # fake two slices by wrapping devices; dm rows must never mix slices
    class FakeDev:
        def __init__(self, d, s):
            self._d, self.slice_index, self.id = d, s, d.id

    devs = jax.devices()
    fake = [FakeDev(d, s) for s, half in
            enumerate((devs[:4], devs[4:])) for d in half]
    mesh_devices = D.hybrid_dm_seq_mesh(n_seq=2, devices=fake).devices
    assert mesh_devices.shape == (4, 2)
    for row in mesh_devices:
        assert len({d.slice_index for d in row}) == 1  # seq stays on ICI
    # dm axis spans both slices
    assert {row[0].slice_index for row in mesh_devices} == {0, 1}


def test_hybrid_mesh_rejects_bad_seq():
    with pytest.raises(ValueError):
        D.hybrid_dm_seq_mesh(n_seq=3)  # 3 does not divide 8


_WORKER = textwrap.dedent("""
    import sys
    import jax
    jax.config.update("jax_platforms", "cpu")
    pid, nproc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
    from srtb_tpu.parallel import distributed as D
    D.initialize(f"127.0.0.1:{port}", nproc, pid)
    assert jax.process_count() == nproc
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = D.hybrid_dm_seq_mesh(n_seq=2)   # 2 procs x 2 devs -> dm=2,seq=2
    assert mesh.devices.shape == (2, 2)
    # seq rows must stay within one process (the "slice"/ICI domain)
    for row in mesh.devices:
        assert len({d.process_index for d in row}) == 1

    # cross-process collective over the full mesh: global psum of a
    # (dm, seq)-sharded array
    from jax import shard_map
    f = jax.jit(shard_map(
        lambda x: jax.lax.psum(jax.lax.psum(x, "seq"), "dm"),
        mesh=mesh, in_specs=P("dm", "seq"), out_specs=P()))
    n_dm, n_seq = mesh.devices.shape
    global_shape = (n_dm * 2, n_seq * 3)
    sharding = NamedSharding(mesh, P("dm", "seq"))

    def shard_value(index):
        # value = global row-major index, so the expected sum is exact
        full = np.arange(np.prod(global_shape), dtype=np.float32)
        return full.reshape(global_shape)[index]

    arr = jax.make_array_from_callback(global_shape, sharding, shard_value)
    out = np.asarray(jax.device_get(f(arr)))
    expected = np.arange(np.prod(global_shape), dtype=np.float32).sum()
    assert out.reshape(-1).sum() == expected, (out, expected)

    # contiguous-block trial ownership, matching P("dm") sharding: with
    # dm=2 rows and 4 trials, row pid owns trials [2*pid, 2*pid+1]
    local = D.process_local_dm_indices(mesh, n_trials=4)
    assert local == [2 * pid, 2 * pid + 1], local

    # full multi-host segment step: DM trials across the process (DCN)
    # boundary, sequence sharding within each process (ICI)
    from srtb_tpu.config import Config
    from srtb_tpu.parallel.segment_dist import DistSegmentProcessor
    cfg = Config(
        baseband_input_count=1 << 14, baseband_input_bits=8,
        baseband_format_type="simple", baseband_freq_low=1405.0,
        baseband_bandwidth=64.0, baseband_sample_rate=128e6, dm=30.0,
        spectrum_channel_count=1 << 6, signal_detect_max_boxcar_length=32,
        mitigate_rfi_average_method_threshold=100.0,
        mitigate_rfi_spectral_kurtosis_threshold=2.0,
        baseband_reserve_sample=False)
    proc = DistSegmentProcessor(cfg, mesh, dm_list=[0.0, 15.0, 30.0, 60.0])
    raw = np.random.default_rng(3).integers(
        0, 256, size=cfg.segment_bytes(1), dtype=np.uint8)
    res = proc.process(raw)
    peaks = np.asarray(res.snr_peaks)     # replicated -> readable anywhere
    counts = np.asarray(res.signal_counts)
    assert peaks.shape[0] == 4 and np.isfinite(peaks).all()
    import hashlib
    digest = hashlib.sha256(
        peaks.tobytes() + counts.tobytes()).hexdigest()[:16]
    print(f"WORKER_DIGEST {digest}", flush=True)

    # the sequence-parallel four-step FFT across the process (DCN)
    # boundary: 4-device seq mesh spanning both processes
    from srtb_tpu.parallel import mesh as M
    from srtb_tpu.parallel.dist_fft import dist_fft
    seq_mesh = M.seq_mesh(4)
    n = 1 << 10
    rng = np.random.default_rng(7)
    host_x = (rng.normal(size=n) + 1j * rng.normal(size=n)
              ).astype(np.complex64)
    seq_sharding = NamedSharding(seq_mesh, P("seq"))
    x = jax.make_array_from_callback(
        (n,), seq_sharding, lambda idx: host_x[idx])
    y = dist_fft(x, seq_mesh)
    expected = np.fft.fft(host_x).astype(np.complex64)
    for shard in y.addressable_shards:
        got = np.asarray(shard.data)
        want = expected[shard.index]
        assert np.allclose(got, want, rtol=2e-3, atol=2e-2 * n ** 0.5), \
            np.abs(got - want).max()
    print(f"WORKER_OK pid={pid}", flush=True)
""")


def test_two_process_group_collectives(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["JAX_PLATFORMS"] = "cpu"
    # the subprocesses are plain CPU jax
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    import socket
    with socket.socket() as s:  # let the OS pick a free port
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    procs = [subprocess.Popen(
        [sys.executable, str(script), str(pid), "2", str(port)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True) for pid in range(2)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=180)
            outs.append(out)
    finally:
        for p in procs:
            p.kill()
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid} failed:\n{out}"
        assert f"WORKER_OK pid={pid}" in out
    # the replicated trial summaries must be identical on every host
    digests = {line.split()[1] for out in outs for line in out.splitlines()
               if line.startswith("WORKER_DIGEST")}
    assert len(digests) == 1, digests
