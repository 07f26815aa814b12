"""chip_smoke.py and the compile cache it shares with tools.main.

The smoke's phases run here in-process on the CPU at a toy size behind
the script's own test-only ``--allow-cpu`` switch (the program has no
such switch): what is pinned is the control flow and the contract of the
last line, never a device number.
"""

import json
import os
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def chip_smoke():
    sys.path.insert(0, REPO)
    try:
        import chip_smoke as mod
        yield mod
    finally:
        sys.path.remove(REPO)


def test_chip_smoke_phases_on_cpu(chip_smoke, tmp_path, capfd):
    rc = chip_smoke.main([
        "--allow-cpu", "--log2n", "16", "--channels", "2**6",
        "--pulse-amp", "8", "--workdir", str(tmp_path / "work")])
    cap = capfd.readouterr()
    out = cap.out.splitlines()
    assert rc == 0, cap.err[-3000:]
    # the last line: exactly this object, nothing after it
    last = json.loads(out[-1])
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}
    body = "\n".join(out[:-1])
    for phase in ("default", "warm", "pallas"):
        assert f"{phase}: plan_demotions=0 device_reinits=0" in body
    assert "default: segment 1 [pulse]" in body
    assert "default: segment 3 [pulse]" in body
    assert "pallas: segment 3 agrees with the default phase" in body
    # nothing large is left behind
    assert not os.path.exists(tmp_path / "work")


def test_chip_smoke_refuses_without_tpu(chip_smoke, capfd):
    """No accelerator, no --allow-cpu: non-zero exit and no result
    line — ``"ok": true`` is never printed."""
    assert chip_smoke.main([]) != 0
    assert capfd.readouterr().out == ""


class _ConfigRecorder:
    def __init__(self):
        self.calls = {}

    def __call__(self, name, value):
        self.calls[name] = value


@pytest.fixture
def on_fake_tpu(monkeypatch):
    """enable_compile_cache as it behaves on a chip, with jax.config
    writes recorded instead of applied (a CPU test process must not
    really turn the persistent cache on)."""
    rec = _ConfigRecorder()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax.config, "update", rec)
    return rec


def test_compile_cache_dir_from_environment(on_fake_tpu, monkeypatch,
                                            tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it itself and repo code
    sets no directory."""
    from srtb_tpu.utils import compile_cache as CC
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert CC.enable_compile_cache() == str(tmp_path)
    assert CC.enable_compile_cache("/elsewhere") == str(tmp_path)
    assert "jax_compilation_cache_dir" not in on_fake_tpu.calls
    # the stage scopes are metadata: a cache keyed without it serves a
    # build that has them the executables of one that had none
    assert on_fake_tpu.calls == {
        "jax_persistent_cache_min_entry_size_bytes": -1,
        "jax_persistent_cache_min_compile_time_secs": 0.0,
        "jax_compilation_cache_include_metadata_in_key": True}


def test_compile_cache_dir_default_is_in_checkout(on_fake_tpu,
                                                  monkeypatch):
    """Unset: the fixed <checkout>/.jax_cache — never a home directory,
    never a temporary name."""
    from srtb_tpu.utils import compile_cache as CC
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert CC.enable_compile_cache() == want
    assert on_fake_tpu.calls["jax_compilation_cache_dir"] == want
    assert os.path.isdir(want)
