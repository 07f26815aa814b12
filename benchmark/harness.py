"""What every cell's run shares: the device check, the work directory,
the data, the reference child, the window, the trace and the result
line.  What differs between the program's entries (``Pipeline``,
``DMSearchPipeline``) is in ``drivers/``.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np

from benchmark import gen, spec as spec_mod
from benchmark.check import Checks
from benchmark.record import RunRecord
from benchmark.reference import chain
from benchmark.reducers.scopes import op_scopes
from benchmark.trace import Trace, Tracer


_T0 = time.perf_counter()


def say(msg: str) -> None:
    """A line of the run's own report, with the seconds since start."""
    print(f"[bench +{time.perf_counter() - _T0:7.2f}s] {msg}", flush=True)


class NoAccelerator(Exception):
    pass


# The reference child makes and drops arrays of 32 MB to 2 GB on a dozen
# threads.  glibc maps and unmaps each one, and a machine that gives freed
# pages back lazily (the one-chip machine: 40 GiB) then counts far more
# than the child ever holds: at 2^28 samples a segment 8.5 GB of arrays
# met that limit (my chip runs, PR 29).  So the child keeps one heap and
# reuses it: no mmap per array, no trim.  It holds ~20 % more at its peak,
# takes the same answers and half the page faults' time.
CHILD_MALLOC = {"MALLOC_MMAP_MAX_": "0", "MALLOC_ARENA_MAX": "1",
                "MALLOC_TRIM_THRESHOLD_": str(1 << 40),
                "MALLOC_TOP_PAD_": str(1 << 28)}


def host_memory() -> str:
    """MemAvailable / Cached / Shmem of /proc/meminfo, for the report: a
    run that reads slow on a machine short of memory should say so."""
    want = ("MemTotal", "MemAvailable", "Cached", "Shmem")
    try:
        with open("/proc/meminfo") as f:
            rows = dict(ln.split(":", 1) for ln in f)
        return ", ".join(f"{k} {int(rows[k].split()[0]) // 1024} MiB"
                         for k in want if k in rows)
    except (OSError, ValueError):
        return "not readable"


def remove_file(path: str) -> None:
    """Give a large file's pages back before unlinking it: on a machine
    whose file system lives in memory, the work directory of every run
    would otherwise add up."""
    try:
        os.truncate(path, 0)
    except OSError:
        pass
    os.remove(path)


def remove_tree(root: str) -> None:
    for folder, _dirs, files in os.walk(root):
        for name in files:
            try:
                remove_file(os.path.join(folder, name))
            except OSError:
                pass
    shutil.rmtree(root, ignore_errors=True)


class CompileEvents:
    """What JAX itself reports (jax.monitoring): persistent-cache hits
    and misses, and every backend compile."""

    def __init__(self):
        import jax

        self.hits = self.misses = self.compiles = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def snapshot(self) -> tuple:
        return (self.hits, self.misses, self.compiles)


class Run:
    def __init__(self, spec: spec_mod.Spec, args, t_start: float):
        self.spec = spec
        self.args = args
        self.seed = int(args.seed)
        self.rec = RunRecord()
        self.rec.t_start = t_start
        global _T0
        _T0 = t_start
        self.rec.seconds = float(args.seconds)
        self.rec.chips = spec.chips
        self.options = dict(spec.config["options"])
        self.params = chain.params_from_config(self.options)
        self.rec.params = self.params
        self.rec.sample_rate = self.params["sample_rate"]
        self.lay = gen.Layout(self.params, spec.workload, self.seed)
        self.checks = Checks(spec.workload["check"]["limits"])
        self.workdir = os.path.join(spec_mod.CHECKOUT, ".bench_work",
                                    spec.name)
        self.data_path = os.path.join(self.workdir, "baseband.bin")
        self.prefix = os.path.join(self.workdir, "out_")
        self.journal = os.path.join(self.workdir, "journal.jsonl")
        self.trace_dir = os.path.join(self.workdir, "trace")
        self.child = None
        self.child_out = os.path.join(self.workdir, "reference.npz")
        self.sampled: list = []
        self.tracer = None
        self.device = {}
        self.events = None
        self._win_events = None
        self._loss0 = None
        self.lost = 0
        self.notes: dict = {}     # a driver's remarks, on the result line

    def argv(self, *extra: str) -> list:
        """The configuration's options as ``tools/main.py`` takes them,
        then the run's own paths."""
        return [f"--{k}={v}" for k, v in self.options.items()] + [
            f"--input_file_path={self.data_path}",
            f"--baseband_output_file_prefix={self.prefix}", *extra]

    # ------------------------------------------------------------ set-up

    def check_devices(self) -> None:
        import jax

        try:
            devs = jax.devices()
        except RuntimeError as e:
            raise NoAccelerator(f"JAX found no device: {e}") from e
        self.device = {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs)}
        if devs[0].platform != "tpu" and not self.args.allow_cpu:
            raise NoAccelerator(f"no TPU: JAX reports {self.device}")
        if len(devs) < self.spec.chips:
            raise NoAccelerator(
                f"the cell needs {self.spec.chips} chip(s), JAX reports "
                f"{len(devs)}")
        self.rec.device_kind = devs[0].device_kind
        say(f"device: {self.device}, jax {jax.__version__}")

    def enable_cache(self) -> None:
        """The program's own call (tools/main.py makes it): the cache is
        where JAX_COMPILATION_CACHE_DIR says, else <checkout>/.jax_cache."""
        from srtb_tpu.utils.compile_cache import enable_compile_cache

        where = enable_compile_cache()
        say(f"compile cache: {where}")
        self.events = CompileEvents()

    def make_workdir(self) -> None:
        remove_tree(self.workdir)
        os.makedirs(self.workdir)
        say(f"host memory at start: {host_memory()}")

    def make_data(self) -> None:
        t0 = time.perf_counter()
        info = gen.write_file(self.data_path, self.params, self.lay,
                              self.seed)
        lay = self.lay
        say(f"data: {info['bytes']} bytes in {info['blocks']} blocks, "
            f"{time.perf_counter() - t0:.2f} s (pulse template "
            f"{info['template_s']:.2f} s); n={lay.n} reserved="
            f"{lay.reserved} stride={lay.stride}; {lay.n_warmup} warm-up + "
            f"{lay.n_replay} replay segments; pulses in file segments "
            f"{sorted(lay.pulse_at)}")

    def choose_sample(self) -> None:
        self.sampled = self.lay.draw_sample(
            self.spec.workload["check"]["sample"], self.seed)

    def reference_segments(self) -> list:
        """File segments the reference computes: the window's sample and,
        unless the workload says ``check.warmup_reference: false`` (a
        cell whose set-up is shorter than the reference), the warm-up
        pulse, which is compared before the window opens."""
        lay = self.lay
        warm_pulse = [k for k in range(lay.n_warmup) if lay.pulsed[k]]
        if not self.spec.workload["check"].get("warmup_reference", True):
            warm_pulse = []
        return warm_pulse[:1] + self.sampled

    def start_reference(self, dms_of) -> None:
        """``dms_of(file_seg)`` -> the DMs wanted for that segment."""
        lay = self.lay
        req = {
            "file": self.data_path, "params": self.params,
            "out": self.child_out,
            "segments": [{"file_seg": k,
                          "offset_bytes": k * lay.stride_bytes,
                          "dms": dms_of(k)}
                         for k in self.reference_segments()],
        }
        path = os.path.join(self.workdir, "reference_request.json")
        with open(path, "w") as f:
            json.dump(req, f)
        child = os.path.join(spec_mod.HERE, "reference", "child.py")
        self.child = subprocess.Popen([sys.executable, child, path],
                                      env=dict(os.environ, **CHILD_MALLOC))
        say(f"reference child started for file segments "
            f"{[s['file_seg'] for s in req['segments']]}")

    def join_reference(self) -> dict:
        """Wait for the child; the seconds spent blocked here are the
        reference's, not the program's, and are kept out of setup_s."""
        t0 = time.perf_counter()
        try:
            rc = self.child.wait(timeout=300)
        finally:
            if self.child.poll() is None:
                self.child.kill()
                self.child.wait()
        self.child = None
        self.rec.reference_wait_s = time.perf_counter() - t0
        if rc != 0:
            raise RuntimeError(f"the reference child exited with {rc}")
        with np.load(self.child_out) as z:
            ref = {k: z[k] for k in z.files}
        secs = {k: float(v) for k, v in ref.items()
                if k.endswith(".seconds")}
        say(f"reference: {secs} s per segment; waited "
            f"{self.rec.reference_wait_s:.2f} s for it (not in setup_s)")
        return ref

    # ------------------------------------------------------------ window

    def loss_counters(self) -> dict:
        from srtb_tpu.utils.metrics import metrics

        return {k: float(metrics.get(k)) for k in (
            "segments_dropped", "shed_waterfalls", "shed_baseband",
            "plan_demotions", "device_reinits")}

    def open_window(self, source) -> None:
        rec = self.rec
        self._loss0 = self.loss_counters()
        self._win_events = self.events.snapshot()
        say(f"before the window: persistent cache hits "
            f"{self._win_events[0]}, misses {self._win_events[1]}, backend "
            f"compiles or cache loads {self._win_events[2]} taking "
            f"{self.events.compile_s:.2f} s")
        rec.t0 = time.perf_counter()
        rec.t1 = rec.t0 + rec.seconds
        rec.setup_s = rec.t0 - rec.t_start - rec.reference_wait_s
        if self.args.trace:
            tr = self.spec.workload.get("trace", {})
            slice_s = min(float(tr.get("slice_s", 3.0)),
                          0.5 * rec.seconds)
            self.tracer = Tracer(self.trace_dir, rec.t0,
                                 0.5 * (rec.seconds - slice_s), slice_s)
            source.tick = self.tracer.tick
        source.begin("window", deadline=rec.t1)

    def close_window(self) -> None:
        if self.tracer is not None:
            self.tracer.stop()
        hits, misses, compiles = (
            b - a for a, b in zip(self._win_events, self.events.snapshot()))
        say(f"compiles inside the window: {compiles} (persistent cache "
            f"hits {hits}, misses {misses})")
        self.checks.require(compiles == 0 and misses == 0,
                            f"{compiles} program(s) compiled inside the "
                            "measured window")
        self.report_pace()
        loss1 = self.loss_counters()
        delta = {k: loss1[k] - self._loss0[k] for k in loss1}
        say(f"loss counters over the window: {delta}")
        self.lost = int(delta["segments_dropped"] + delta["shed_waterfalls"]
                        + delta["shed_baseband"])
        self.checks.require(
            delta["plan_demotions"] == 0 and delta["device_reinits"] == 0,
            f"plan demotions / device reinits in the window: {delta}")

    def report_pace(self) -> None:
        """On an earlier line of every run: the longest waits between two
        completions (a stall shows here, not in a median) and where the
        reader's buffers lie in memory."""
        win = self.rec.window()
        done = sorted(s.done for s in win if s.done > 0.0)
        gaps = sorted(((b - a) * 1e3 for a, b in zip(done, done[1:])),
                      reverse=True)
        period = sorted(gaps)[len(gaps) // 2] if gaps else 0.0
        addrs = sorted({s.buffer_address for s in win})
        say(f"pace: median {period:.2f} ms between completions, longest "
            f"{[round(g, 1) for g in gaps[:5]]}; {len(addrs)} reader "
            f"buffer(s), addresses mod 4096 "
            f"{[a % 4096 for a in addrs[:4]]}, mod 2 MiB "
            f"{[a % (1 << 21) for a in addrs[:4]]}")

    # ------------------------------------------------------------ result

    def read_trace(self) -> None:
        tr = self.tracer
        if tr is None or tr.state != "done":
            return
        t0 = time.perf_counter()
        self.rec.trace = Trace.load(self.trace_dir, tr.t_off - tr.t_on)
        self.rec.trace.scopes = op_scopes(Trace.newest(self.trace_dir))
        self.rec.trace.segments = sum(
            1 for s in self.rec.window() if tr.t_on <= s.done <= tr.t_off)
        n_ops = sum(len(v) for v in self.rec.trace.devices.values())
        say(f"trace: slice of {tr.t_off - tr.t_on:.3f} s, {n_ops} device "
            f"operations on {len(self.rec.trace.devices)} device plane(s), "
            f"{len(self.rec.trace.host)} host annotations, "
            f"{self.rec.trace.segments} segments completed in it; stopping the "
            f"profiler took {tr.stop_cost_s:.2f} s, reading "
            f"{time.perf_counter() - t0:.2f} s")
        keep = os.environ.get("BENCH_KEEP_TRACE")
        if keep:
            shutil.copytree(self.trace_dir, keep, dirs_exist_ok=True)

    def device_memory(self) -> None:
        """Peak HBM on the fullest chip.  On the TPU the allocator keeps
        two books: ``peak_bytes_in_use`` is the buffers JAX holds
        (arguments, results, the chirp bank) and ``peak_bytes_reserved``
        the loaded programs' temporaries, which the first does not show
        (quiet cell: 2.46 + 2.15 GB, the second equal to the compiler's
        own count).  Their sum is the HBM the run needed at most."""
        import jax

        stats = [d.memory_stats() or {} for d in jax.devices()]
        peaks = [s.get("peak_bytes_in_use", 0)
                 + s.get("peak_bytes_reserved", 0) for s in stats]
        self.rec.peak_bytes = int(max(peaks)) if peaks else 0
        if peaks:
            say(f"memory_stats of the fullest chip: "
                f"{stats[peaks.index(max(peaks))]}")

    def result(self, reducers: dict) -> dict:
        rec = self.rec
        kind = "per_layer" if self.args.trace else "end_to_end"
        metrics = {}
        for entry, reader in self.spec.metrics(kind):
            fn = reducers[reader["reducer"]]
            value = fn(rec, reader.get("args", {}))
            if value is None:
                continue          # nothing to read in this cell or run
            metrics[entry["name"]] = {"value": float(value),
                                      "unit": entry["unit"]}
        if self.args.trace:
            for entry, reader in self.spec.metrics("end_to_end"):
                value = reducers[reader["reducer"]](rec,
                                                    reader.get("args", {}))
                say(f"traced run, for the tracing overhead: "
                    f"{entry['name']} = {value!r}")
        window = rec.window()
        failed = len(self.checks.failed_segments) + self.lost
        device = dict(self.device)
        device["memory_peak_bytes"] = rec.peak_bytes
        out = {"correct": bool(self.checks.ok and failed == 0),
               "attempted": len(window), "failed": failed,
               "metrics": metrics, "device": device}
        if self.args.trace and rec.trace is not None:
            device["busy_s"] = rec.trace.busy_s()
            device["window_s"] = rec.trace.window_s
            out["breakdown"] = {"device_ops": rec.trace.top_ops(10),
                                "idle_gaps": rec.trace.idle_gaps(10)}
        if self.notes:
            out["notes"] = self.notes
        # last on the line: where a run is not correct, the end of the
        # line is what the driver's record keeps
        out["checks"] = self.checks.summary()
        return out

    def cleanup(self) -> None:
        if self.child is not None and self.child.poll() is None:
            self.child.kill()
            self.child.wait()
        if not self.args.keep_work:
            remove_tree(self.workdir)
        say(f"host memory at exit: {host_memory()}")
