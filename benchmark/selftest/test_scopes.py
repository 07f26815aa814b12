"""The reducers that read the program's own names: device time by
``srtb.<stage>`` scope (``reducers/scopes.py``, which reads the
``.xplane.pb`` itself), host time by ``srtb:<stage>`` span
(``reducers/host_spans.py``) and two more journal readers
(``reducers/journal_more.py``).  Held to hand-built messages, to a small
scoped trace recorded on the chip (``data/scoped_slice``, beside the
unscoped ``data/quiet_slice`` of PR 25), and to both tiny rehearsals with
the new entries of ``BENCHMARK.json`` present."""

import gzip
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data")
TINY = os.path.join(HERE, "tiny")

from benchmark.reducers import host_spans, journal_more, scopes  # noqa: E402
from benchmark.trace import Trace  # noqa: E402


# ------------------------------------------------- hand-built messages

def varint(x: int) -> bytes:
    out = b""
    while True:
        b7 = x & 0x7F
        x >>= 7
        out += bytes([b7 | (0x80 if x else 0)])
        if not x:
            return out


def ld(field: int, payload: bytes) -> bytes:
    return varint((field << 3) | 2) + varint(len(payload)) + payload


def vi(field: int, value: int) -> bytes:
    return varint(field << 3) + varint(value)


TF_OP, OTHER = 26, 24


def plane(name: str, ops: dict, events: list, line="XLA Ops") -> bytes:
    """``ops``: {metadata id: (instruction text, op_name or None)};
    ``events``: [(metadata id, offset_ps, duration_ps)]."""
    out = ld(2, name.encode())
    for stat_id, stat_name in ((TF_OP, b"tf_op"), (OTHER, b"hlo_category")):
        out += ld(5, vi(1, stat_id)
                  + ld(2, vi(1, stat_id) + ld(2, stat_name)))
    for meta_id, (text, op_name) in ops.items():
        meta = vi(1, meta_id) + ld(2, text.encode()) \
            + ld(5, vi(1, OTHER) + ld(5, b"loop fusion"))
        if op_name is not None:
            meta += ld(5, vi(1, TF_OP) + ld(5, op_name.encode()))
        out += ld(4, vi(1, meta_id) + ld(2, meta))
    evs = b"".join(ld(4, vi(1, m) + vi(2, off) + vi(3, dur))
                   for m, off, dur in events)
    return out + ld(3, ld(2, line.encode()) + evs)


def write_space(tmp_path, *planes) -> str:
    path = str(tmp_path / "t.xplane.pb")
    with open(path, "wb") as f:
        f.write(b"".join(ld(1, p) for p in planes))
    return path


US = 1_000_000   # picoseconds


def test_innermost_scope_self_time_and_device_average(tmp_path):
    ops = {
        1: ("%fusion.1 = f32[8] fusion(...)",
            "jit(_process_ring)/jit(main)/srtb.fft_r2c/srtb.unpack/mul:"),
        2: ("%fusion.2 = f32[8] fusion(...)",
            "jit(_body)/shard_map/vmap(srtb.chirp)/sin:"),
        3: ("%while.1 = f32[8] while(...)",
            "jit(_body)/shard_map/srtb.detect/while:"),
        4: ("%pad_add_fusion = u8[8] fusion(...)",
            "jit(_process_ring)/concatenate:"),
        5: ("%copy.252 = f32[8] copy(...)", None),
    }
    dev0 = plane("/device:TPU:0", ops, [
        (1, 0, 10 * US), (2, 10 * US, 20 * US),
        (3, 40 * US, 50 * US),          # a while around ...
        (2, 45 * US, 10 * US),          # ... a chirp operation in its body
        (4, 100 * US, 3 * US), (5, 110 * US, 7 * US)])
    dev1 = plane("/device:TPU:1", ops, [(1, 0, 30 * US)])
    other = plane("/device:TPU:0", ops, [(1, 0, 999 * US)],
                  line="Async XLA Ops")          # another line: not read
    host = plane("/host:CPU", ops, [(1, 0, 999 * US)])
    got = scopes.scope_seconds(write_space(tmp_path, dev0, other, host,
                                           dev1))
    want = {"srtb.unpack": (10 + 30) / 2, "srtb.chirp": (20 + 10) / 2,
            "srtb.detect": (50 - 10) / 2, "unscoped": (3 + 7) / 2}
    assert got == pytest.approx({k: v * 1e-6 for k, v in want.items()})
    # every microsecond of the busy union is in exactly one scope
    assert sum(got.values()) == pytest.approx((90 + 30) / 2 * 1e-6)


def test_a_program_without_scopes_reads_as_nothing(tmp_path):
    ops = {1: ("%fusion.1 = f32[8] fusion(...)", "jit(_process_ring)/mul:"),
           2: ("%copy.1 = f32[8] copy(...)", None)}
    path = write_space(tmp_path, plane("/device:TPU:0", ops,
                                       [(1, 0, US), (2, US, US)]))
    assert scopes.scope_seconds(path) == {}


# ------------------------------------------------- the recorded traces

def unzip(tmp_path, name: str) -> str:
    path = str(tmp_path / f"{name}.xplane.pb")
    with gzip.open(os.path.join(DATA, f"{name}.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return path


def known(name: str) -> dict:
    with open(os.path.join(DATA, f"{name}.json")) as f:
        return json.load(f)


class Rec:
    """What the reducers read of a run's record."""

    def __init__(self, trace=None, spans=(), warm_spans=()):
        self.trace = trace
        self.spans = list(spans)
        self.warm_spans = list(warm_spans)


def load_trace(path: str, info: dict) -> Trace:
    from jax.profiler import ProfileData

    tr = Trace.from_profile(ProfileData.from_file(path), info["window_s"])
    tr.segments = info["segments_completed_in_slice"]
    return tr


def test_scoped_slice_by_stage(tmp_path, monkeypatch):
    info = known("scoped_slice")
    path = unzip(tmp_path, "scoped_slice")
    by_scope = scopes.scope_seconds(path)
    assert by_scope == pytest.approx(info["scope_s"], rel=1e-9)
    rec = Rec(load_trace(path, info))
    # the scopes are a partition of what trace.py calls busy (which
    # ProfileData hands out rounded to nanoseconds)
    assert sum(by_scope.values()) == pytest.approx(rec.trace.busy_s(),
                                                   rel=1e-5)
    assert rec.trace.busy_s() == pytest.approx(info["busy_s"], rel=1e-9)
    monkeypatch.setattr(scopes, "slice_path", lambda: path)
    monkeypatch.setattr(scopes, "_CACHE", {})
    segs = info["segments_completed_in_slice"]
    for metric, want_ms in info["metrics_ms_per_seg"].items():
        with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                               f"{metric}.json")) as f:
            reader = json.load(f)
        fn = {**scopes.REDUCERS, **host_spans.REDUCERS}[reader["reducer"]]
        assert fn(rec, reader["args"]) == pytest.approx(want_ms, rel=1e-9), \
            metric
    total = sum(info["metrics_ms_per_seg"][m] for m in info["scope_metrics"])
    assert total == pytest.approx(info["busy_s"] / segs * 1e3, rel=1e-5)
    # the R2C holds the twiddle and transpose passes too: more than its
    # convolution fusions (the DFT stages on the MXU)
    mxu = rec.trace.op_seconds("(?i)fft|^convolution") / segs * 1e3
    assert info["metrics_ms_per_seg"]["ops.fft_r2c_ms_per_seg"] > 2 * mxu


def test_breakdown_names_each_operation_by_its_scope(tmp_path):
    """``breakdown.device_ops`` of a traced run: every row reads
    ``<scope>/<operation>``, and the scope is the one the scope reducer
    gives the same operation."""
    from benchmark.trace import short_name

    info = known("scoped_slice")
    path = unzip(tmp_path, "scoped_slice")
    tr = load_trace(path, info)
    plain = tr.top_ops(40)
    tr.scopes = scopes.op_scopes(path)
    named = tr.top_ops(40)
    # the reducer's own lookup, operation by operation
    by_op = {}
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for number, val in scopes._fields(space):
        if number == 1:
            names = {}
            _plane, scope_of, events = scopes._plane(val, names)
            for _off, _dur, meta in events:
                by_op[short_name(names[meta])] = scope_of[meta]
    assert len(named) == 40 and set(by_op.values()) > {"srtb.fft_r2c",
                                                       scopes.UNSCOPED}
    for (name, sec), (full, sec_named) in zip(plain, named):
        assert full == f"{by_op[name]}/{name}" and sec_named == sec
    assert named[0][0] == "srtb.fft_r2c/fusion.43 f32[128,128,128,64]"
    assert any(row[0].startswith("unscoped/copy.") for row in named)
    # a program that names no stage keeps the plain names
    quiet = unzip(tmp_path, "quiet_slice")
    assert scopes.op_scopes(quiet) == {}


def test_unscoped_trace_and_untraced_run_read_as_nothing(tmp_path,
                                                         monkeypatch):
    """PR 25's slice, recorded before the program had scopes or the new
    spans: what the parent commit gives.  Nothing is read, nothing
    raises."""
    info = known("quiet_slice")
    path = unzip(tmp_path, "quiet_slice")
    rec = Rec(load_trace(path, info))
    monkeypatch.setattr(scopes, "slice_path", lambda: path)
    monkeypatch.setattr(scopes, "_CACHE", {})
    for scope in ("srtb.fft_r2c", "unscoped"):
        assert scopes.scope_ms_per_seg(rec, {"scopes": [scope]}) is None
    assert host_spans.host_span_ms_per_seg(rec, {"name": "h2d"}) is None
    # a span the parent does write is read from the same trace
    assert host_spans.host_span_ms_per_seg(rec, {"name": "fetch"}) > 0
    for rec in (Rec(None), Rec(Trace({}, [], 1.0))):
        assert scopes.scope_ms_per_seg(rec, {"scopes": ["unscoped"]}) is None
        assert host_spans.host_span_ms_per_seg(rec, {"name": "h2d"}) is None
    monkeypatch.setattr(scopes, "slice_path", lambda: None)
    assert scopes.scope_ms_per_seg(Rec(load_trace(path, info)),
                                   {"scopes": ["unscoped"]}) is None


# ---------------------------------------------------- the journal readers

def test_journal_readers():
    warm = [{"dump": False, "stages_ms": {"sink": 1.0}},
            {"dump": True, "stages_ms": {"sink": 4800.0, "d2h": 400.0,
                                         "write": 4300.0, "publish": 2.0}},
            {"dump": True, "stages_ms": {"d2h": 1.0, "write": 1.0,
                                         "publish": 1.0}}]
    rec = Rec(warm_spans=warm)
    assert journal_more.warmup_dump_stage_s(rec, {"stages": ["d2h"]}) == 0.4
    assert journal_more.warmup_dump_stage_s(
        rec, {"stages": ["write", "publish"]}) == pytest.approx(4.302)
    # the parent's journal has no such stage: nothing, no error
    assert journal_more.warmup_dump_stage_s(
        Rec(warm_spans=warm[:1]), {"stages": ["d2h"]}) is None


# ------------------------------------- the rehearsals, new entries present

CELLS = {"j1644_2p27.replay_quiet": "tiny_j1644.replay_quiet",
         "j1644_dmgrid8.replay": "tiny_dmgrid8.replay"}


@pytest.fixture()
def tiny_with_new_entries(tmp_path):
    """A copy of ``selftest/tiny`` whose ``BENCHMARK.json`` also lists the
    per-layer metrics the repo's has and the tiny one lacks, under the
    tiny cells' names."""
    root = str(tmp_path / "tiny")
    shutil.copytree(TINY, root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        full = json.load(f)
    have = {m["name"] for m in bench["per_layer"]}
    for m in full["per_layer"]:
        if m["name"] not in have:
            bench["per_layer"].append(dict(
                m, workloads=[CELLS[w] for w in m["workloads"]]))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def run_cell(capsys, root: str, workload: str) -> dict:
    from benchmark import run
    from srtb_tpu.utils import logging as program_logging

    program_logging.log.stream = sys.stderr
    rc = run.main(["--root", root, "--workload", workload, "--seed", "12",
                   "--seconds", "1", "--trace", "1", "--allow-cpu"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_served_rehearsal_reports_the_new_host_metrics(
        capsys, tiny_with_new_entries):
    out = run_cell(capsys, tiny_with_new_entries, "tiny_j1644.replay_quiet")
    assert out["correct"] and out["failed"] == 0
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert {"io.h2d_ms_per_seg", "runtime.enqueue_ms_per_seg",
            "io.candidate_d2h_s", "io.candidate_write_s"} <= set(m)
    assert m["io.candidate_write_s"] > 0
    # no device trace on the CPU: no device metric, old or new
    assert not any(k.startswith("ops.") for k in m)


def test_grid_rehearsal_reports_its_five_stages(
        capsys, tiny_with_new_entries):
    out = run_cell(capsys, tiny_with_new_entries, "tiny_dmgrid8.replay")
    assert out["correct"] and out["device"]["count"] == 4
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert {"io.grid_ingest_ms_per_seg", "io.h2d_ms_per_seg",
            "runtime.enqueue_ms_per_seg", "runtime.grid_fetch_ms_per_seg",
            "runtime.grid_record_ms_per_seg"} <= set(m)
    assert all(v > 0 for v in m.values())
