"""srtb-tsan: concurrency lint rules (lock-order-inversion,
blocking-under-lock, condvar-misuse, check-then-act) fixtures —
positive / negative / pragma / baseline per rule — plus the runtime
checker (analysis/tsan.py): live lockdep cycle trap, condvar wrapper
misuse traps, held-too-long stalls, claim-on-first-use ownership on a
fleet lane, the zero-cost-off contract, and the seeded schedule
perturber's determinism (same seed => same yield schedule => same
journal).
"""

import os
import re
import textwrap
import threading
import time

import pytest

from srtb_tpu.analysis import lint
from srtb_tpu.analysis.tsan import (InstrumentedCondition,
                                    InstrumentedLock,
                                    SchedulePerturber, Tsan, TsanError,
                                    install_perturber,
                                    uninstall_perturber)
from srtb_tpu.tools import SOAK_DM

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write(tmp_path, rel, src):
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(src))
    return str(p)


def _run(tmp_path):
    return lint.run([str(tmp_path)])


def _rules(findings):
    return [f.rule for f in findings]


# ------------------------------------------------ lock-order-inversion


class TestLockOrderInversion:
    def test_inverted_nesting_positive(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import threading

            class Engine:
                def __init__(self):
                    self.a_lock = threading.Lock()
                    self.b_lock = threading.Lock()

                def forward(self):
                    with self.a_lock:
                        with self.b_lock:
                            pass

                def backward(self):
                    with self.b_lock:
                        with self.a_lock:
                            pass
        """)
        fs = _run(tmp_path)
        assert _rules(fs) == ["lock-order-inversion"]
        assert "cycle" in fs[0].message
        assert "a_lock" in fs[0].message and "b_lock" in fs[0].message

    def test_cross_function_positive(self, tmp_path):
        # one half of the cycle hides behind a call: forward holds A
        # and CALLS a helper that takes B
        _write(tmp_path, "mod.py", """
            import threading

            class Engine:
                def __init__(self):
                    self.a_lock = threading.Lock()
                    self.b_lock = threading.Lock()

                def _drain(self):
                    with self.b_lock:
                        pass

                def forward(self):
                    with self.a_lock:
                        self._drain()

                def backward(self):
                    with self.b_lock:
                        with self.a_lock:
                            pass
        """)
        fs = _run(tmp_path)
        assert _rules(fs) == ["lock-order-inversion"]

    def test_reacquire_self_positive(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import threading

            class Engine:
                def __init__(self):
                    self.a_lock = threading.Lock()

                def step(self):
                    with self.a_lock:
                        with self.a_lock:
                            pass
        """)
        fs = _run(tmp_path)
        assert _rules(fs) == ["lock-order-inversion"]
        assert "self-edge" in fs[0].message

    def test_consistent_order_negative(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import threading

            class Engine:
                def __init__(self):
                    self.a_lock = threading.Lock()
                    self.b_lock = threading.Lock()

                def forward(self):
                    with self.a_lock:
                        with self.b_lock:
                            pass

                def also_forward(self):
                    with self.a_lock:
                        with self.b_lock:
                            pass
        """)
        assert _run(tmp_path) == []

    def test_non_lock_with_negative(self, tmp_path):
        # open()/tempfile with-blocks never enter the order graph
        _write(tmp_path, "mod.py", """
            def save(path, other):
                with open(path) as f:
                    with open(other) as g:
                        return f.read() + g.read()
        """)
        assert _run(tmp_path) == []

    def test_pragma_suppresses(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import threading

            class Engine:
                def __init__(self):
                    self.a_lock = threading.Lock()
                    self.b_lock = threading.Lock()

                def forward(self):
                    with self.a_lock:
                        # srtb-lint: disable=lock-order-inversion
                        with self.b_lock:
                            pass

                def backward(self):
                    with self.b_lock:
                        with self.a_lock:
                            pass
        """)
        assert _run(tmp_path) == []


# ------------------------------------------------- blocking-under-lock


class TestBlockingUnderLock:
    def test_fdatasync_positive(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import os
            import threading

            class Wal:
                def __init__(self):
                    self._lock = threading.Lock()

                def flush(self, fd):
                    with self._lock:
                        os.fdatasync(fd)
        """)
        fs = _run(tmp_path)
        assert _rules(fs) == ["blocking-under-lock"]
        assert "fdatasync" in fs[0].message

    def test_untimed_get_and_join_positive(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import threading

            class Sched:
                def __init__(self, q, pipe):
                    self._lock = threading.Lock()
                    self.q = q
                    self.sink_pipe = pipe

                def drain(self):
                    with self._lock:
                        item = self.q.get()
                        self.sink_pipe.join()
                        return item
        """)
        fs = _run(tmp_path)
        assert sorted(_rules(fs)) == ["blocking-under-lock"] * 2

    def test_foreign_wait_positive(self, tmp_path):
        # waiting on cv B while holding lock A deadlocks B's notifier
        # if it ever needs A; waiting on the cv you hold is sanctioned
        _write(tmp_path, "mod.py", """
            import threading

            class Sched:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._cv = threading.Condition()

                def park(self):
                    with self._lock:
                        self._cv.wait(0.1)
        """)
        fs = _run(tmp_path)
        assert _rules(fs) == ["blocking-under-lock"]
        assert "different lock" in fs[0].message

    def test_transitive_through_call_positive(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import os
            import threading

            class Wal:
                def __init__(self):
                    self._lock = threading.Lock()

                def _sync(self, fd):
                    os.fdatasync(fd)

                def commit(self, fd):
                    with self._lock:
                        self._sync(fd)
        """)
        fs = _run(tmp_path)
        assert _rules(fs) == ["blocking-under-lock"]
        assert "_sync" in fs[0].message

    def test_negatives(self, tmp_path):
        # timed get, dict get, os.path.join, str.join, fsync outside
        # the lock: all quiet
        _write(tmp_path, "mod.py", """
            import os
            import threading

            class Sched:
                def __init__(self, q):
                    self._lock = threading.Lock()
                    self.q = q
                    self.d = {}

                def drain(self, fd):
                    with self._lock:
                        item = self.q.get(timeout=0.05)
                        name = self.d.get("key")
                        path = os.path.join("a", name or "b")
                        label = ",".join(["x", path])
                    os.fdatasync(fd)
                    return item, label
        """)
        assert _run(tmp_path) == []

    def test_pragma_suppresses(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import os
            import threading

            class Wal:
                def __init__(self):
                    self._lock = threading.Lock()

                def flush(self, fd):
                    with self._lock:
                        # WAL commit point is lock-serialized by design
                        # srtb-lint: disable=blocking-under-lock
                        os.fdatasync(fd)
        """)
        assert _run(tmp_path) == []


# ----------------------------------------------------- condvar-misuse


class TestCondvarMisuse:
    def test_wait_under_if_positive(self, tmp_path):
        # the fleet scheduler's pre-fix idle wait, reduced
        _write(tmp_path, "mod.py", """
            import threading

            class Sched:
                def __init__(self):
                    self._wake = threading.Condition()
                    self.seq = 0

                def idle(self, seen):
                    with self._wake:
                        if self.seq == seen:
                            self._wake.wait(0.05)
        """)
        fs = _run(tmp_path)
        assert _rules(fs) == ["condvar-misuse"]
        assert "predicate loop" in fs[0].message

    def test_notify_without_lock_positive(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import threading

            class Sched:
                def __init__(self):
                    self._wake = threading.Condition()
                    self.seq = 0

                def poke(self):
                    self.seq += 1
                    self._wake.notify_all()
        """)
        fs = _run(tmp_path)
        assert _rules(fs) == ["condvar-misuse"]
        assert "notify" in fs[0].message

    def test_predicate_loop_and_held_notify_negative(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import threading

            class Sched:
                def __init__(self):
                    self._wake = threading.Condition()
                    self.seq = 0

                def idle(self, seen):
                    with self._wake:
                        while self.seq == seen:
                            self._wake.wait(0.05)

                def idle2(self, pred):
                    with self._wake:
                        self._wake.wait_for(pred, timeout=0.05)

                def poke(self):
                    with self._wake:
                        self.seq += 1
                        self._wake.notify_all()
        """)
        assert _run(tmp_path) == []

    def test_pragma_suppresses(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import threading

            class Sched:
                def __init__(self):
                    self._wake = threading.Condition()
                    self.seq = 0

                def idle(self, seen):
                    with self._wake:
                        if self.seq == seen:
                            # srtb-lint: disable=condvar-misuse
                            self._wake.wait(0.05)
        """)
        assert _run(tmp_path) == []


# ------------------------------------------------------ check-then-act


class TestCheckThenAct:
    SRC = """
        import threading

        class Pump:
            def __init__(self):
                self._lock = threading.Lock()
                self.active = False
                t = threading.Thread(target=self._pump)
                t.start()

            def _pump(self):
                with self._lock:
                    self.active = True

            def stop(self):
                {body}
    """

    def test_test_outside_lock_positive(self, tmp_path):
        # every MUTATION is locked, so unguarded-shared-state stays
        # silent — but the test escaping the lock is the race this
        # rule exists for
        _write(tmp_path, "mod.py", self.SRC.format(body="""if self.active:
                    with self._lock:
                        self.active = False"""))
        fs = _run(tmp_path)
        assert _rules(fs) == ["check-then-act"]
        assert "active" in fs[0].message

    def test_whole_statement_locked_negative(self, tmp_path):
        _write(tmp_path, "mod.py", self.SRC.format(body="""with self._lock:
                    if self.active:
                        self.active = False"""))
        assert _run(tmp_path) == []

    def test_unshared_attr_negative(self, tmp_path):
        # no thread-entry ever touches it: plain single-threaded
        # check-then-set is fine
        _write(tmp_path, "mod.py", """
            class Cache:
                def __init__(self):
                    self.warm = False

                def ensure(self):
                    if not self.warm:
                        self.warm = True
        """)
        assert _run(tmp_path) == []

    def test_pragma_suppresses(self, tmp_path):
        _write(tmp_path, "mod.py", self.SRC.format(
            body="""# lifecycle-exclusive: stop() runs post-join
                # srtb-lint: disable=check-then-act
                if self.active:
                    with self._lock:
                        self.active = False"""))
        assert _run(tmp_path) == []


# ----------------------------------------- baseline workflow per rule


BASELINE_FIXTURES = {
    "lock-order-inversion": """
        import threading

        class E:
            def __init__(self):
                self.a_lock = threading.Lock()
                self.b_lock = threading.Lock()

            def f(self):
                with self.a_lock:
                    with self.b_lock:
                        pass

            def g(self):
                with self.b_lock:
                    with self.a_lock:
                        pass
    """,
    "blocking-under-lock": """
        import os
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()

            def flush(self, fd):
                with self._lock:
                    os.fdatasync(fd)
    """,
    "condvar-misuse": """
        import threading

        class S:
            def __init__(self):
                self._cv = threading.Condition()
                self.seq = 0

            def idle(self, seen):
                with self._cv:
                    if self.seq == seen:
                        self._cv.wait(0.05)
    """,
    "check-then-act": """
        import threading

        class P:
            def __init__(self):
                self._lock = threading.Lock()
                self.active = False
                threading.Thread(target=self._pump).start()

            def _pump(self):
                with self._lock:
                    self.active = True

            def stop(self):
                if self.active:
                    with self._lock:
                        self.active = False
    """,
}


@pytest.mark.parametrize("rule", sorted(BASELINE_FIXTURES))
def test_baseline_accepts_rule(rule, tmp_path):
    _write(tmp_path, "src/mod.py", BASELINE_FIXTURES[rule])
    bl = str(tmp_path / "baseline.json")
    src = str(tmp_path / "src")
    assert lint.main([src, "--baseline", bl]) == 1  # new finding
    assert lint.main([src, "--baseline", bl, "--write-baseline"]) == 0
    assert lint.main([src, "--baseline", bl]) == 0  # accepted


# --------------------------------------------------- runtime: lockdep


class TestLockdepRuntime:
    def test_cycle_trap(self):
        ts = Tsan()
        a, b = ts.lock("A"), ts.lock("B")
        with a:
            with b:
                pass
        with pytest.raises(TsanError, match="inversion"):
            with b:
                with a:
                    pass

    def test_consistent_order_quiet(self):
        ts = Tsan()
        a, b, c = ts.lock("A"), ts.lock("B"), ts.lock("C")
        for _ in range(3):
            with a:
                with b:
                    with c:
                        pass
        assert ts.report()["order_edges"] >= 2

    def test_reacquire_trap(self):
        ts = Tsan()
        a = ts.lock("A")
        with pytest.raises(TsanError, match="re-acquire"):
            with a:
                with a:
                    pass

    def test_transitive_cycle_trap(self):
        # A->B and B->C on record; taking A under C closes the cycle
        # through the path, not a direct edge
        ts = Tsan()
        a, b, c = ts.lock("A"), ts.lock("B"), ts.lock("C")
        with a:
            with b:
                pass
        with b:
            with c:
                pass
        with pytest.raises(TsanError, match="inversion"):
            with c:
                with a:
                    pass

    def test_stall_recorded_not_raised(self):
        ts = Tsan(stall_s=0.01)
        a = ts.lock("slow")
        with a:
            time.sleep(0.05)
        assert ts.stalls and ts.stalls[0][0] == "slow"
        assert ts.stalls[0][1] >= 0.01

    def test_condition_wait_notify_roundtrip(self):
        ts = Tsan()
        cv = ts.condition("cv")
        state = {"ready": False}

        def waker():
            time.sleep(0.02)
            with cv:
                state["ready"] = True
                cv.notify_all()

        t = threading.Thread(target=waker)
        t.start()
        with cv:
            while not state["ready"]:
                assert cv.wait(1.0)
        t.join()
        assert state["ready"]

    def test_condition_misuse_traps(self):
        ts = Tsan()
        cv = ts.condition("cv")
        with pytest.raises(TsanError, match="notify"):
            cv.notify_all()
        with pytest.raises(TsanError, match="wait"):
            cv.wait(0.01)


# ------------------------------------------------- runtime: ownership


class TestOwnership:
    def test_claim_on_first_use_trap(self):
        ts = Tsan()
        ts.assert_owner("lane.s0.step")  # main thread claims
        err = []

        def intruder():
            try:
                ts.assert_owner("lane.s0.step")
            except TsanError as e:
                err.append(e)

        t = threading.Thread(target=intruder, name="intruder")
        t.start()
        t.join()
        assert err and "ownership" in str(err[0])

    def test_release_prefix_allows_reclaim(self):
        ts = Tsan()
        ts.assert_owner("lane.s0.sink")
        ts.assert_owner("former.groups")
        ts.release_owners("lane.s0.sink")
        ok = []

        def successor():
            ts.assert_owner("lane.s0.sink")  # re-claim after restart
            try:
                ts.assert_owner("former.groups")
            except TsanError:
                ok.append(True)

        t = threading.Thread(target=successor)
        t.start()
        t.join()
        assert ok, "unreleased claim must still trap"


# ----------------------------------------- fleet integration + 0-cost


def _tiny_fleet(tmp_path, **cfg_kw):
    from srtb_tpu.config import Config
    from srtb_tpu.io.synth import make_dispersed_baseband
    from srtb_tpu.pipeline.fleet import StreamFleet, StreamSpec
    n = 1 << 12
    specs = []
    for i, name in enumerate(("s0", "s1")):
        bb = os.path.join(str(tmp_path), f"bb_{name}.bin")
        make_dispersed_baseband(
            n * 2, 1405.0, 64.0, SOAK_DM, pulse_positions=[n // 2],
            pulse_amp=30.0, nbits=8, seed=i).tofile(bb)
        cfg = dict(
            baseband_input_count=n, baseband_input_bits=8,
            baseband_freq_low=1405.0, baseband_bandwidth=64.0,
            baseband_sample_rate=128e6, dm=SOAK_DM,
            input_file_path=bb,
            baseband_output_file_prefix=os.path.join(
                str(tmp_path), f"out_{name}_"),
            spectrum_channel_count=64,
            mitigate_rfi_average_method_threshold=100.0,
            mitigate_rfi_spectral_kurtosis_threshold=2.0,
            baseband_reserve_sample=True, writer_thread_count=0,
            fft_strategy="four_step", inflight_segments=2,
            retry_backoff_base_s=0.001)
        cfg.update(cfg_kw)
        specs.append(StreamSpec(name=name, cfg=Config(**cfg)))
    return StreamFleet(specs)


def test_fleet_tsan_on_runs_clean(tmp_path):
    fleet = _tiny_fleet(tmp_path, tsan=True)
    assert fleet._tsan is not None
    assert isinstance(fleet._wake, InstrumentedCondition)
    res = fleet.run()
    try:
        assert all(r.status == "done" for r in res.values())
        for lane in fleet.lanes.values():
            assert isinstance(lane._live_lock, InstrumentedLock)
        rep = fleet._tsan.report()
        # claims were released at run() exit (per-run ownership);
        # the order graph persists across the run
        assert rep["owners"] == {}
        assert "stalls" in rep and "order_edges" in rep
    finally:
        fleet.close()


def test_fleet_tsan_off_is_zero_cost(tmp_path):
    fleet = _tiny_fleet(tmp_path)  # tsan defaults off
    assert fleet._tsan is None
    assert isinstance(fleet._wake, threading.Condition)
    res = fleet.run()
    try:
        assert all(r.status == "done" for r in res.values())
        # lane locks are plain threading primitives — no wrapper
        # indirection anywhere on the hot path when the knob is off
        for lane in fleet.lanes.values():
            assert not isinstance(lane._live_lock, InstrumentedLock)
    finally:
        fleet.close()


# --------------------------------------- seeded schedule perturbation


class TestSchedulePerturber:
    def test_same_seed_same_schedule_same_journal(self):
        # driven with an identical (deterministic, single-threaded)
        # acquisition sequence, two perturbers with the same seed
        # perturb the same occurrences => identical journals
        seq = (["fleet._wake"] * 40 + ["lane.s0._live_lock"] * 40
               + ["fleet._wake", "lane.s1._live_lock"] * 20)
        p1 = SchedulePerturber(42, rate=0.3, sleep_s=0.0)
        p2 = SchedulePerturber(42, rate=0.3, sleep_s=0.0)
        for site in seq:
            p1.perturb(site)
        for site in seq:
            p2.perturb(site)
        assert p1.journal and p1.journal == p2.journal

    def test_different_seed_different_schedule(self):
        sites = [("s", k) for k in range(256)]
        p1 = SchedulePerturber(1, rate=0.3)
        p2 = SchedulePerturber(2, rate=0.3)
        assert [p1.decide(s, k) for s, k in sites] \
            != [p2.decide(s, k) for s, k in sites]

    def test_decide_is_pure(self):
        p = SchedulePerturber(9, rate=0.5)
        before = [p.decide("x", k) for k in range(64)]
        p.perturb("x")  # mutating the counter must not move decide()
        assert [p.decide("x", k) for k in range(64)] == before

    def test_install_uninstall(self):
        from srtb_tpu.analysis.tsan import current_perturber
        p = SchedulePerturber(0, rate=1.0, sleep_s=0.0)
        install_perturber(p)
        try:
            assert current_perturber() is p
            ts = Tsan()
            with ts.lock("L"):
                pass
            assert p.journal == [("L", 0)]
        finally:
            uninstall_perturber()
        assert current_perturber() is None


def test_race_soak_selftest_is_sharp():
    from srtb_tpu.tools.race_soak import selftest
    assert selftest() == []


@pytest.mark.slow
def test_race_soak_smoke(tmp_path):
    from srtb_tpu.tools.race_soak import run_race_soak
    report = run_race_soak(streams=2, segments=3, log2n=12, seed=1,
                           batch=2)
    assert report["ok"] and report["perturbs"] > 0


# ------------------------------------------ thread creation-site tags


def test_tag_thread_reports_creation_site():
    # tag_thread attributes to the first frame OUTSIDE the calling
    # module (the wrapper is not the interesting site), so a direct
    # call from here records OUR caller; the Pipe test below pins the
    # exact-attribution contract.  Here: a site exists and is file:line
    from srtb_tpu.utils import termination
    t = threading.Thread(target=lambda: None)
    termination.tag_thread(t)
    site = termination.created_at(t)
    assert site and re.match(r".+:\d+$", site)
    assert "created at" in termination.describe_threads([t])


def test_pipe_thread_carries_creation_site():
    from srtb_tpu.pipeline import framework as fw
    from srtb_tpu.utils import termination
    stop = fw.StopToken()
    pipe = fw.Pipe(lambda *_: None, None, None, stop)
    site = termination.created_at(pipe.thread)
    # the site is the CALLER of the framework, not framework.py itself
    assert site and "test_tsan.py" in site
    desc = termination.format_thread_stacks([pipe.thread])
    assert "created at" in desc
