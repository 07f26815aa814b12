"""srtb-lint rule fixtures: each rule fires on a minimal positive
snippet, stays quiet on the matching negative, and respects pragma /
baseline suppression — plus the acceptance gate that the real tree
lints clean against the checked-in baseline.
"""

import json
import os
import textwrap

import pytest

from srtb_tpu.analysis import lint
from srtb_tpu.analysis.core import Baseline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write(tmp_path, rel, src):
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(src))
    return str(p)


def _run(tmp_path, *rels):
    return lint.run([str(tmp_path)] if not rels
                    else [str(tmp_path / r) for r in rels])


def _rules(findings):
    return [f.rule for f in findings]


# ------------------------------------------------------ sync-hot-path


class TestSyncHotPath:
    def test_jit_body_positive(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import jax
            import numpy as np

            def g(x):
                return np.asarray(x)

            f = jax.jit(g)
        """)
        fs = _run(tmp_path)
        assert _rules(fs) == ["sync-hot-path"]
        assert "np.asarray" in fs[0].message
        assert fs[0].context == "g"

    def test_dispatch_window_positive(self, tmp_path):
        _write(tmp_path, "pipeline/runtime.py", """
            import numpy as np

            class Pipeline:
                def _dispatch_segment(self, seg):
                    return np.asarray(seg.data)
        """)
        fs = _run(tmp_path)
        assert _rules(fs) == ["sync-hot-path"]
        assert "dispatch window" in fs[0].message

    def test_reaches_through_call_graph(self, tmp_path):
        # the hot root only *calls* the offender; the sync is two hops
        # away in another module imported by alias
        _write(tmp_path, "helpers.py", """
            def fetch(x):
                return x.block_until_ready()
        """)
        _write(tmp_path, "pipeline/runtime.py", """
            import helpers

            def fill_window(pending):
                return helpers.fetch(pending[0])
        """)
        fs = _run(tmp_path)
        assert _rules(fs) == ["sync-hot-path"]
        assert fs[0].rel.endswith("helpers.py")

    def test_item_and_float_in_jit_body(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import jax

            @jax.jit
            def g(x):
                a = x.item()
                return float(x) + a
        """)
        assert _rules(_run(tmp_path)) == ["sync-hot-path"] * 2

    def test_negative_unrooted(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import numpy as np

            def host_helper(x):
                return np.asarray(x)   # never jitted, never hot
        """)
        assert _run(tmp_path) == []

    def test_pragma_suppresses(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import jax
            import numpy as np

            def g(x):
                # host constant, not traced data
                # srtb-lint: disable=sync-hot-path
                return np.asarray(x)

            f = jax.jit(g)
        """)
        assert _run(tmp_path) == []


# --------------------------------------------------- use-after-donate


class TestUseAfterDonate:
    def test_wrapper_positive(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import jax

            def f(x):
                return x + 1

            w = jax.jit(f, donate_argnums=(0,))

            def use(buf):
                y = w(buf)
                return buf.sum()
        """)
        fs = _run(tmp_path)
        assert _rules(fs) == ["use-after-donate"]
        assert "'buf'" in fs[0].message

    def test_api_positive(self, tmp_path):
        _write(tmp_path, "mod.py", """
            def h(proc, buf):
                wf, det = proc.run_device(buf)
                return wf, buf[0]
        """)
        assert _rules(_run(tmp_path)) == ["use-after-donate"]

    def test_negative_reassigned(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import jax

            def f(x):
                return x + 1

            w = jax.jit(f, donate_argnums=(0,))

            def ok(buf):
                buf = w(buf)
                return buf.sum()
        """)
        assert _run(tmp_path) == []

    def test_negative_sibling_branch(self, tmp_path):
        _write(tmp_path, "mod.py", """
            def h(proc, buf, fast):
                if fast:
                    out = proc.run_device(buf)
                else:
                    out = buf[0]
                return out
        """)
        assert _run(tmp_path) == []

    def test_loop_iteration_positive(self, tmp_path):
        _write(tmp_path, "mod.py", """
            def h(proc, buf, n):
                outs = []
                for _ in range(n):
                    outs.append(buf.mean())      # stale on iter 2
                    proc.run_device(buf)
                return outs
        """)
        fs = _run(tmp_path)
        assert _rules(fs) == ["use-after-donate"]
        assert "loop iteration" in fs[0].message


# -------------------------------------------------- recompile-hazard


class TestRecompileHazard:
    def test_jit_in_loop(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import jax

            def sweep(fns, x):
                outs = []
                for f in fns:
                    outs.append(jax.jit(f)(x))
                return outs
        """)
        fs = _run(tmp_path)
        assert "inside a loop" in fs[0].message
        assert all(r == "recompile-hazard" for r in _rules(fs))

    def test_immediate_invoke_in_method(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import jax

            class R:
                def render(self, x):
                    return jax.jit(self._impl)(x)

                def _impl(self, x):
                    return x
        """)
        fs = _run(tmp_path)
        assert _rules(fs) == ["recompile-hazard"]
        assert "immediately invoked" in fs[0].message

    def test_bound_method_uncached(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import jax

            class R:
                def build(self):
                    f = jax.jit(self._impl)
                    return f

                def _impl(self, x):
                    return x
        """)
        fs = _run(tmp_path)
        assert _rules(fs) == ["recompile-hazard"]
        assert "bound method" in fs[0].message

    def test_negative_init_and_cached(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import jax

            class R:
                def __init__(self):
                    self._f = jax.jit(self._impl)
                    self._chirp = jax.jit(lambda: 1.0)()

                def lazy(self):
                    self._g = jax.jit(self._impl)  # cached on self
                    return self._g

                def _impl(self, x):
                    return x

            top = jax.jit(lambda x: x)  # module scope: one-time
        """)
        assert _run(tmp_path) == []


# ------------------------------------------------------- dtype-drift


class TestDtypeDrift:
    def test_jnp_float64_in_ops(self, tmp_path):
        _write(tmp_path, "ops/chirp.py", """
            import jax.numpy as jnp

            def phase(x):
                return x.astype(jnp.float64)
        """)
        fs = _run(tmp_path)
        assert _rules(fs) == ["dtype-drift"]

    def test_np64_inside_jit_body(self, tmp_path):
        _write(tmp_path, "ops/mod.py", """
            import jax
            import numpy as np

            def g(x):
                return x * np.float64(1.5)

            f = jax.jit(g)
        """)
        assert _rules(_run(tmp_path)) == ["dtype-drift"]

    def test_dtype_string_in_jit_body(self, tmp_path):
        _write(tmp_path, "ops/mod.py", """
            import jax
            import jax.numpy as jnp

            @jax.jit
            def g(x):
                return jnp.zeros(4, dtype="float64") + x
        """)
        assert _rules(_run(tmp_path)) == ["dtype-drift"]

    def test_enable_x64_flagged(self, tmp_path):
        _write(tmp_path, "utils/setup.py", """
            import jax

            def enable():
                jax.config.update("jax_enable_x64", True)
        """)
        assert _rules(_run(tmp_path)) == ["dtype-drift"]

    def test_negative_host_precompute(self, tmp_path):
        _write(tmp_path, "ops/window.py", """
            import numpy as np

            def coefficients(n):
                # host-side f64 table, cast before the trace: sanctioned
                x = np.arange(n, dtype=np.float64)
                return np.cos(x).astype(np.float32)
        """)
        assert _run(tmp_path) == []


# ---------------------------------------- unguarded-shared-state


class TestUnguardedSharedState:
    def test_thread_vs_main_positive(self, tmp_path):
        _write(tmp_path, "io/pump.py", """
            import threading

            class Pump:
                def __init__(self):
                    self.count = 0
                    self._thread = threading.Thread(target=self._pump)

                def _pump(self):
                    self.count += 1

                def reset(self):
                    self.count = 0
        """)
        fs = _run(tmp_path)
        assert _rules(fs) == ["unguarded-shared-state"]
        assert "'Pump.count'" in fs[0].message

    def test_negative_locked(self, tmp_path):
        _write(tmp_path, "io/pump.py", """
            import threading

            class Pump:
                def __init__(self):
                    self.count = 0
                    self._lock = threading.Lock()
                    self._thread = threading.Thread(target=self._pump)

                def _pump(self):
                    with self._lock:
                        self.count += 1

                def reset(self):
                    with self._lock:
                        self.count = 0
        """)
        assert _run(tmp_path) == []

    def test_start_pipe_container_mutation(self, tmp_path):
        _write(tmp_path, "pipeline/engine.py", """
            from srtb_tpu.pipeline.framework import start_pipe

            class Engine:
                def run(self, q, stop):
                    done = []

                    def sink_f(_stop, item):
                        done.append(item)

                    pipe = start_pipe(sink_f, q, None, stop, "sink")
                    done.append(None)   # main thread, no lock
                    return pipe
        """)
        fs = _run(tmp_path)
        assert _rules(fs) == ["unguarded-shared-state"]


# ------------------------------------------------ swallowed-except


class TestSwallowedExcept:
    def test_bare_except_pass_positive(self, tmp_path):
        _write(tmp_path, "io/reader.py", """
            def read(f):
                try:
                    return f.read()
                except:
                    pass
        """)
        fs = _run(tmp_path)
        assert _rules(fs) == ["swallowed-except"]
        assert "everything" in fs[0].message
        assert fs[0].context == "read"

    def test_broad_except_dropped_positive(self, tmp_path):
        _write(tmp_path, "pipeline/engine.py", """
            def drain(item):
                try:
                    item.flush()
                except Exception:
                    return None
        """)
        fs = _run(tmp_path)
        assert _rules(fs) == ["swallowed-except"]
        assert "Exception" in fs[0].message

    def test_negative_logged_reraised_or_used(self, tmp_path):
        _write(tmp_path, "pipeline/engine.py", """
            from srtb_tpu.utils.logging import log

            def a(item):
                try:
                    item.flush()
                except Exception:
                    log.warning("flush failed")

            def b(item):
                try:
                    item.flush()
                except Exception:
                    raise RuntimeError("flush failed")

            def c(self, item):
                try:
                    item.flush()
                except BaseException as e:
                    self.exception = e
        """)
        assert _run(tmp_path) == []

    def test_negative_narrow_except(self, tmp_path):
        # a named exception type is a documented decision: out of scope
        _write(tmp_path, "io/reader.py", """
            def read(sock):
                try:
                    return sock.recv(1)
                except OSError:
                    pass
        """)
        assert _run(tmp_path) == []

    def test_negative_outside_pipeline_io_scope(self, tmp_path):
        _write(tmp_path, "gui/tap.py", """
            def tap(frame):
                try:
                    frame.render()
                except Exception:
                    pass
        """)
        assert _run(tmp_path) == []

    def test_pragma_suppresses(self, tmp_path):
        _write(tmp_path, "io/reader.py", """
            def probe(x):
                try:
                    return x.ready()
                except Exception:  # srtb-lint: disable=swallowed-except
                    return True
        """)
        assert _run(tmp_path) == []


# ------------------------------------------- baseline & CLI behavior


class TestBaselineAndCli:
    def _seed(self, tmp_path):
        _write(tmp_path, "src/mod.py", """
            import jax
            import numpy as np

            def g(x):
                return np.asarray(x)

            f = jax.jit(g)
        """)

    def test_baseline_accepts_then_new_fails(self, tmp_path):
        self._seed(tmp_path)
        bl = str(tmp_path / "baseline.json")
        src = str(tmp_path / "src")
        assert lint.main([src, "--baseline", bl]) == 1  # new finding
        assert lint.main([src, "--baseline", bl,
                          "--write-baseline"]) == 0
        assert lint.main([src, "--baseline", bl]) == 0  # accepted
        # notes survive a rewrite
        data = json.load(open(bl))
        key = next(iter(data["entries"]))
        data["entries"][key]["note"] = "accepted: host bytes"
        json.dump(data, open(bl, "w"))
        assert lint.main([src, "--baseline", bl,
                          "--write-baseline"]) == 0
        assert json.load(open(bl))["entries"][key]["note"] \
            == "accepted: host bytes"
        # a NEW finding still fails against the old baseline
        _write(tmp_path, "src/mod2.py", """
            import jax

            @jax.jit
            def h(x):
                return x.item()
        """)
        assert lint.main([src, "--baseline", bl]) == 1

    def test_stale_entries_reported(self, tmp_path):
        self._seed(tmp_path)
        src = str(tmp_path / "src")
        findings = lint.run([src])
        bl = Baseline.from_findings(findings)
        bl.entries["gone::sync-hot-path::f::x"] = {"count": 1}
        new, accepted, stale = bl.filter(findings)
        assert not new and len(accepted) == 1
        assert stale == ["gone::sync-hot-path::f::x"]

    def test_disable_file_pragma(self, tmp_path):
        _write(tmp_path, "src/mod.py", """
            # srtb-lint: disable-file=sync-hot-path
            import jax
            import numpy as np

            def g(x):
                return np.asarray(x)

            f = jax.jit(g)
        """)
        assert lint.run([str(tmp_path / "src")]) == []

    def test_list_rules(self, capsys):
        assert lint.main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rule in ("sync-hot-path", "use-after-donate",
                     "recompile-hazard", "dtype-drift",
                     "unguarded-shared-state"):
            assert rule in out

    def test_json_format(self, tmp_path, capsys):
        self._seed(tmp_path)
        lint.main([str(tmp_path / "src"), "--no-baseline",
                   "--format", "json"])
        data = json.loads(capsys.readouterr().out)
        assert data["new"] and data["new"][0]["rule"] == "sync-hot-path"


# -------------------------------------------- host-callback-in-jit


class TestHostCallbackInJit:
    def test_jit_body_positive(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import jax

            def g(x):
                jax.debug.print("x={x}", x=x)
                return x * 2

            f = jax.jit(g)
        """)
        fs = _run(tmp_path)
        assert "host-callback-in-jit" in _rules(fs)
        f = next(x for x in fs if x.rule == "host-callback-in-jit")
        assert "debug.print" in f.message and f.context == "g"

    def test_pure_callback_in_jit_body(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import jax
            import numpy as np

            def host_fn(x):
                return np.sort(x)

            @jax.jit
            def g(x):
                return jax.pure_callback(
                    host_fn, jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        """)
        fs = _run(tmp_path)
        assert "host-callback-in-jit" in _rules(fs)

    def test_io_callback_via_alias(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import jax
            from jax.experimental import io_callback as iocb

            def log_it(x):
                pass

            @jax.jit
            def g(x):
                iocb(log_it, None, x)
                return x
        """)
        fs = _run(tmp_path)
        assert "host-callback-in-jit" in _rules(fs)

    def test_dispatch_window_positive(self, tmp_path):
        _write(tmp_path, "pipeline/runtime.py", """
            import jax

            class Pipeline:
                def _dispatch_segment(self, seg):
                    jax.debug.callback(print, seg)
                    return seg
        """)
        fs = _run(tmp_path)
        assert "host-callback-in-jit" in _rules(fs)
        f = next(x for x in fs if x.rule == "host-callback-in-jit")
        assert "dispatch window" in f.message

    def test_outside_jit_negative(self, tmp_path):
        # a callback in plain host code (drain side) is sanctioned
        _write(tmp_path, "mod.py", """
            import jax

            def drain(x):
                jax.debug.print("x={x}", x=x)
                return x
        """)
        assert "host-callback-in-jit" not in _rules(_run(tmp_path))

    def test_pragma_suppression(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import jax

            @jax.jit
            def g(x):
                # sanctioned diagnostic
                # srtb-lint: disable=host-callback-in-jit
                jax.debug.print("x={x}", x=x)
                return x
        """)
        assert "host-callback-in-jit" not in _rules(_run(tmp_path))


# --------------------------------------------------- acceptance gate


def test_repo_lints_clean_against_baseline():
    """The acceptance criterion: the real tree, the real baseline,
    exit code 0 — and the baseline has no stale entries (every entry
    still fires, so it documents real accepted findings)."""
    pkg = os.path.join(REPO, "srtb_tpu")
    baseline = os.path.join(pkg, "analysis", "baseline.json")
    findings = lint.run([pkg])
    new, accepted, stale = Baseline.load(baseline).filter(findings)
    assert new == [], "\n".join(f.render() for f in new)
    assert stale == [], stale
    assert accepted, "baseline unexpectedly empty"


def test_repo_baseline_entries_have_notes():
    baseline = os.path.join(REPO, "srtb_tpu", "analysis",
                            "baseline.json")
    data = json.load(open(baseline))
    missing = [k for k, e in data["entries"].items()
               if not e.get("note")]
    assert not missing, missing


def test_no_module_parks_a_kernel_behind_a_constant_false():
    """The rule of PR 50 as a test: a kernel or plan that no supported
    chip compiles is deleted with everything that selects it, not kept
    behind a module-level ``*_MOSAIC_OK = False`` for interpret-mode
    tests to keep alive (three such gates stood from PR 24 to PR 49:
    the sub-byte unpack kernels and the front-fused staged family).  A
    gate that is ``True`` is dead weight too, but harmless."""
    import ast

    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "srtb_tpu")
    parked, seen = [], 0
    for folder, _dirs, files in os.walk(root):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(folder, name)
            with open(path) as f:
                tree = ast.parse(f.read(), path)
            seen += 1
            for node in ast.walk(tree):
                if isinstance(node, ast.Assign):
                    targets, value = node.targets, node.value
                elif isinstance(node, ast.AnnAssign) and node.value:
                    targets, value = [node.target], node.value
                else:
                    continue
                for t in targets:
                    if isinstance(t, ast.Name) \
                            and t.id.endswith("_MOSAIC_OK") \
                            and isinstance(value, ast.Constant) \
                            and value.value is False:
                        parked.append(f"{os.path.relpath(path, root)}:"
                                      f"{node.lineno} {t.id}")
    assert seen > 100       # the walk saw the package
    assert parked == []
