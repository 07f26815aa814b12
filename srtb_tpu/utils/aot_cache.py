"""Ahead-of-time executable persistence — the warm-restart fallback.

The persistent XLA compilation cache (utils/compile_cache.py) is the
first line against the staged 2^30 plan's ~11-minute cold compile; but
if the deployment's remote-compile service bypasses the local cache, a
mid-observation restart is an 11-minute outage.  This module persists
the *compiled executables themselves* via
``jax.experimental.serialize_executable`` so a restarted process loads
and runs them without recompiling — the strong form of the reference's
FFTW-wisdom persistence (ref: fft/fftw_wrapper.hpp:196-238: plans are
re-created per run from wisdom; here the "plan" IS the executable).

Safety model:
- Blobs are keyed by SHA-256 of (jax version, backend platform, device
  kind, program name, plan signature) — a changed config, JAX upgrade,
  or different accelerator generation misses cleanly and recompiles.
  The plan signature (SegmentProcessor.plan_signature) allowlists every
  trace-shaping config field, including the overlap engine's knobs
  (``inflight_segments``, ``micro_batch_segments``) and the input
  donation flag: a restarted process with different overlap settings
  can never load a stale executable whose donation/aliasing or batch
  shape no longer matches.
- CPU backends are OFF by default, same policy and same reason as
  compile_cache.enable_compile_cache: XLA:CPU AOT machine code is keyed
  without host CPU features, and a stale entry after a host swap can
  SIGILL (observed round 4).  Tests opt in with ``allow_cpu=True``
  (save + load on one host is safe); deployments can force it with
  SRTB_AOT_ALLOW_CPU=1.
- Deserialization failures of any kind fall back to a fresh compile —
  the cache can cost a recompile, never correctness.
"""

from __future__ import annotations

import hashlib
import os
import pickle

from srtb_tpu.utils.logging import log


def _device_key() -> str:
    import jax

    dev = jax.devices()[0]
    return f"{jax.__version__}/{dev.platform}/{dev.device_kind}"


def cpu_allowed() -> bool:
    return bool(int(os.environ.get("SRTB_AOT_ALLOW_CPU", "0")))


class AotPlanCache:
    """Directory of serialized compiled executables, one file per
    (program name, plan signature, device key)."""

    def __init__(self, root: str, allow_cpu: bool = False,
                 labels: dict | None = None):
        self.root = root
        self.allow_cpu = allow_cpu or cpu_allowed()
        # per-stream labeled twins for the hit/miss/compile counters
        # (multi-tenant fleet: cache economics must be attributable
        # to the tenant that paid the compile)
        self.labels = dict(labels) if labels else None
        os.makedirs(root, exist_ok=True)

    def _count(self, name: str, value: float = 1.0) -> None:
        from srtb_tpu.utils.metrics import metrics
        metrics.add(name, value)
        if self.labels:
            metrics.add(name, value, labels=self.labels)

    def enabled(self) -> bool:
        import jax

        if jax.default_backend() == "cpu" and not self.allow_cpu:
            log.debug("[aot_cache] skipped on CPU (host-fragile AOT); "
                      "set SRTB_AOT_ALLOW_CPU=1 to force")
            return False
        return True

    def _path(self, name: str, signature: str) -> str:
        h = hashlib.sha256(
            f"{_device_key()}|{name}|{signature}".encode()).hexdigest()
        return os.path.join(self.root, f"{name}.{h[:16]}.aot")

    def load(self, name: str, signature: str):
        """Deserialized compiled executable, or None on miss/any error."""
        if not self.enabled():
            return None
        path = self._path(name, signature)
        if not os.path.exists(path):
            return None
        try:
            import jax
            from jax.experimental.serialize_executable import (
                deserialize_and_load)

            with open(path, "rb") as f:
                blob, in_tree, out_tree = pickle.load(f)
            # pin execution to device 0: the segment plans are
            # single-device programs, and the default (all local
            # devices) makes the loaded executable demand one shard
            # per device on multi-device hosts (e.g. the forced
            # 8-device CPU test platform)
            compiled = deserialize_and_load(
                blob, in_tree, out_tree,
                execution_devices=[jax.devices()[0]])
            log.info(f"[aot_cache] loaded {name} from {path}")
            self._count("aot_cache_hits")
            return compiled
        except Exception as e:  # corrupt blob / jax drift: recompile
            log.warning(f"[aot_cache] load failed for {name}: {e}; "
                        "recompiling")
            return None

    def save(self, name: str, signature: str, compiled) -> str | None:
        if not self.enabled():
            return None
        path = self._path(name, signature)
        try:
            from jax.experimental.serialize_executable import serialize

            payload = serialize(compiled)
            tmp = path + ".tmp"
            with open(tmp, "wb") as f:
                pickle.dump(payload, f)
            os.replace(tmp, path)  # atomic: a crashed save never
            # leaves a truncated blob for the next start to trip on
            log.info(f"[aot_cache] saved {name} -> {path}")
            return path
        except Exception as e:  # pragma: no cover - backend quirk
            log.warning(f"[aot_cache] save failed for {name}: {e}")
            return None

    def get_or_compile(self, name: str, signature: str, jitted, *example):
        """Cached executable for ``jitted`` (a jax.jit wrapper), compiling
        + persisting on miss.  ``example`` entries only need shape/dtype
        (jax.ShapeDtypeStruct works)."""
        compiled = self.load(name, signature)
        if compiled is None:
            # AOT-protocol compile accounting: unlike the lazy-jit
            # first-dispatch timer (pipeline/segment.py), this measures
            # the compile EXACTLY — lower+compile with no execution in
            # the window
            import time
            t0 = time.perf_counter()
            compiled = jitted.lower(*example).compile()
            dt = time.perf_counter() - t0
            self._count("aot_cache_misses")
            self._count("plan_compiles")
            self._count("compile_seconds", dt)
            from srtb_tpu.utils.metrics import metrics
            metrics.set("last_compile_ms", dt * 1e3)
            self.save(name, signature, compiled)
        return compiled
