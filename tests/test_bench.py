"""bench.py emits one valid JSON line naming the device it ran on, and
fails loudly when its platform cannot initialise.  Run it tiny on CPU."""

import json
import os
import subprocess
import sys


def test_bench_emits_one_json_line(tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    env["SRTB_BENCH_LOG2N"] = "16"
    out = subprocess.run(
        [sys.executable, os.path.join(env["PYTHONPATH"], "bench.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [ln for ln in out.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert len(lines) == 1, out.stdout
    rec = json.loads(lines[0])
    assert {"metric", "value", "unit", "vs_baseline"} <= set(rec)
    assert rec["value"] > 0 and rec["vs_baseline"] > 0, rec
    # every line names its device; a per-chip rate and a roofline share
    # are only claimed on a chip whose peak is in the table
    assert rec["platform"] == "cpu" and rec["device_kind"]
    assert rec["device_count"] >= 1
    assert rec["unit"] == "Msamples/s" and "roofline_frac" not in rec
    # roofline fields (PERF.md): fast must be falsifiable.  roofline_frac
    # itself only appears on accelerator runs (no v5e peak to compare a
    # CPU measurement against)
    assert {"achieved_gbps", "model_gflops", "model_hbm_gb"} <= set(rec)
    assert rec["achieved_gbps"] > 0
    # the BASELINE gate field: a CPU run can never pass the chip target
    assert rec["pass"] is False


def test_bench_fails_when_platform_cannot_initialise(tmp_path):
    """No probe, no fallback: a platform that cannot come up is a
    non-zero exit with no result line — never a CPU number under the
    chip's name."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "no_such_platform"
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    env["SRTB_BENCH_LOG2N"] = "16"
    out = subprocess.run(
        [sys.executable, os.path.join(env["PYTHONPATH"], "bench.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode != 0, out.stdout[-2000:]
    assert not [ln for ln in out.stdout.splitlines()
                if ln.startswith("{")], out.stdout


def test_kernel_bench_runs():
    from srtb_tpu.tools import kernel_bench
    import io
    from contextlib import redirect_stdout
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = kernel_bench.main(["--log2n", "16", "--reps", "1",
                                "--pixmap", "64x128"])
    assert rc == 0
    lines = [json.loads(ln) for ln in buf.getvalue().splitlines()]
    assert len(lines) >= 5
    assert all(rec["ms"] > 0 for rec in lines if "ms" in rec)


def test_bench_knob_variants(tmp_path):
    # the A/B knobs must not break the script (four_step + pallas path)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    env["SRTB_BENCH_LOG2N"] = "16"
    env["SRTB_BENCH_FFT_STRATEGY"] = "four_step"
    env["SRTB_BENCH_USE_PALLAS"] = "1"
    out = subprocess.run(
        [sys.executable, os.path.join(env["PYTHONPATH"], "bench.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads([ln for ln in out.stdout.strip().splitlines()
                      if ln.startswith("{")][0])
    assert rec["value"] > 0


def test_baseline_pass_gate():
    """VERDICT r3 #9: the >= 1x real-time gate, both branches — only an
    accelerator platform at >= 1x may report pass."""
    import bench
    assert bench.baseline_pass(True, 1.0) is True
    assert bench.baseline_pass(True, 13.6) is True
    assert bench.baseline_pass(True, 0.99) is False
    assert bench.baseline_pass(False, 5.0) is False
