"""Self-healing compute: the plan-demotion ladder and device reinit.

PR 4's supervisor hardened the *host* side (sinks, watchdog,
degradation); a compute-side failure — an XLA ``RESOURCE_EXHAUSTED``,
a Mosaic compile error, a halted device — still killed the stream even
though the repo has everything needed to recover: 20 audited plan
families (plan_cards.json), retained host buffers that re-dispatch any
segment cold and bit-identically, and checkpoint resume.  This module
closes that gap with two mechanisms, both driven by the typed
device-fault classification in :mod:`srtb_tpu.resilience.errors`:

**Plan demotion** (oom / compile faults).  The ladder is an ordered
list of progressively cheaper execution plans derived from the active
config by switching off features in a fixed order (owned by the plan
registry, ``pipeline/registry.py``)::

    search_mode -> micro_batch -> ring -> skzap -> fused_tail
                -> staged -> monolithic

Each rung is CUMULATIVE (rung k applies every earlier step too) and
rungs that would not change the active config are skipped, so the
ladder a given run walks contains only real alternatives.  On a
device fault at a dispatch/fetch site the engine demotes one rung,
rebuilds the :class:`SegmentProcessor` from the rung's config (the
rung changes trace-relevant knobs, so ``plan_signature()`` differs and
any AOT cache misses cleanly and re-lowers), and re-dispatches the
faulted segment COLD from its already-retained host buffer — the same
recovery path the watchdog requeue proved bit-identical.  The rung
order mirrors cost/fragility: the micro-batch multiplies the program's
footprint by B; the ring adds the carry programs; skzap and the fused
tail are the Pallas-heavy fusions (the likeliest Mosaic compile
surface); the staged plan trades one big program for three small ones
(each program's temporaries freed before the next — the proven answer
to chain OOM at 2^30); monolithic is the minimal-feature floor that
must run anywhere XLA runs.  Every demotion-ladder target must
resolve to a plan family already carded in ``plan_cards.json``
(``analysis/hlo_audit.audit_ladder``, gated in ci.sh): the run never
demotes into an unaudited plan.

**Device reinit** (halt faults).  A halted backend invalidates every
in-flight device buffer and compiled-executable handle.  Recovery:
drop all in-flight device state, ``jax.clear_caches()``, rebuild the
processor at the CURRENT rung (a fresh processor holds no loaded AOT
executables or jit caches bound to the dead backend handle, and the
engine separately invalidates the warm ingest-ring carry), then
re-dispatch every in-flight segment cold from its retained host
buffer — in dispatch order, so journal order and checkpoint resume
offsets are unchanged.  Reinits are budgeted by the same
bounded-restart supervisor the sink pipe uses (``device_reinit_max``
within ``device_reinit_window_s``): a flapping device escalates to a
clean shutdown instead of flapping forever.

**Promotion probe.**  With ``promote_after_segments = N > 0``, N
consecutively healthy drained segments promote one rung back up; the
next dispatch probes the richer plan, and if the fault recurs the
engine simply demotes again (each further promotion needs another N
healthy segments, so a persistent fault settles at the highest rung
that works).  0 (default) sticks with the demoted plan for the rest
of the run.

Every transition is accounted: ``plan_demotions`` /
``plan_promotions`` / ``device_reinits`` counters, the
``plan_ladder_level`` gauge, and the v4 journal's ``active_plan``
field (utils/telemetry.py) — a run that quietly survives on the
monolithic floor must be visible on /metrics.
"""

from __future__ import annotations

from dataclasses import dataclass

from srtb_tpu.pipeline import registry
from srtb_tpu.resilience.errors import classify_device
from srtb_tpu.resilience.supervisor import Supervisor
from srtb_tpu.utils import events
from srtb_tpu.utils.logging import log
from srtb_tpu.utils.metrics import metrics

# canonical rung order, cheapest-to-drop first — read from the ONE
# plan-family registry (pipeline/registry.py), which also owns each
# step's apply rule; this module keeps only the per-run state machine
LADDER_ORDER = registry.ladder_order()


@dataclass(frozen=True)
class Rung:
    """One demotion target: the step that produced it, the demoted
    config, and the explicit ``staged`` constructor override (None =
    let the processor resolve from the segment size)."""

    step: str
    cfg: object
    staged: bool | None

    @property
    def name(self) -> str:
        return self.step


def _apply_step(cfg, step: str, staged: bool | None):
    """(new_cfg, new_staged) after one ladder step, or None when the
    step would not change the active RESOLVED plan (skipped rung —
    demoting onto an identical plan would burn a ladder level while
    recovering nothing).  The apply rules themselves live in the plan
    registry, next to the families they demote between — and they
    delegate to the SegmentProcessor's own pure-config resolvers, so
    no mirrored rule can drift."""
    return registry.ladder_step(step).apply(cfg, staged)


def parse_ladder(text: str) -> tuple[str, ...]:
    """``Config.plan_ladder`` -> ordered step tuple.  "auto" is the
    full canonical order; an explicit comma list selects a subset (in
    the given order); unknown step names raise at startup — a ladder
    with a typo must fail loudly, not silently never demote."""
    text = (text or "auto").strip().lower()
    if text in ("auto", ""):
        return LADDER_ORDER
    if text == "off":
        return ()
    steps = tuple(s.strip() for s in text.split(",") if s.strip())
    for s in steps:
        if s not in LADDER_ORDER:
            raise ValueError(
                f"plan_ladder step {s!r} unknown "
                f"(steps: {', '.join(LADDER_ORDER)}, or auto/off)")
    return steps


def ladder_rungs(cfg, base_staged: bool | None = None,
                 steps: tuple[str, ...] = LADDER_ORDER) -> list[Rung]:
    """The demotion rungs reachable from ``cfg``: cumulative configs in
    ladder order, no-op steps skipped.  ``base_staged`` is the CURRENT
    processor's resolved staged flag (so a run already on the staged
    plan skips that rung)."""
    rungs: list[Rung] = []
    cur, staged = cfg, base_staged
    for step in steps:
        out = _apply_step(cur, step, staged)
        if out is None:
            continue
        cur, staged = out
        rungs.append(Rung(step, cur, staged))
    return rungs


class ComputeHealer:
    """Per-run self-healing state machine: ladder position, promotion
    counter, and the reinit budget.  Owned by the Pipeline; the engine
    calls :meth:`classify` on any dispatch/fetch failure and then one
    of :meth:`demote` / :meth:`reinit`, swapping in the processor each
    returns.  ``factory(cfg, staged)`` builds the replacement
    processor (the pipeline's hook, overridable in tests).

    Zero-cost when healthy: the engine consults this object only from
    exception handlers and one counter bump per drained segment."""

    def __init__(self, cfg, factory, steps: tuple[str, ...] = None,
                 base_staged: bool | None = None,
                 promote_after: int = 0, reinit_max: int = 0,
                 reinit_window_s: float = 300.0):
        if steps is None:
            steps = parse_ladder(getattr(cfg, "plan_ladder", "auto"))
        self._cfg = cfg
        self._factory = factory
        self._steps = steps
        self._rungs = ladder_rungs(cfg, base_staged, steps)
        self._base_staged = base_staged
        self._level = 0  # 0 = the configured (full) plan
        self._healthy = 0
        self.promote_after = int(promote_after)
        self._reinit = None
        if int(reinit_max) > 0:
            # counter=None: reinits are accounted under their OWN
            # device_reinits counter (in reinit()); riding the default
            # worker_restarts would journal phantom worker restarts
            self._reinit = Supervisor(
                "device_reinit", max_restarts=int(reinit_max),
                window_s=float(reinit_window_s), counter=None)
        # per-stream twins (multi-tenant fleet): the flat series stay
        # process-wide; the labeled ones attribute demotions/ladder
        # position to the tenant whose device fault caused them
        stream = str(getattr(cfg, "stream_name", "") or "")
        self._labels = {"stream": stream} if stream else None
        metrics.set("plan_ladder_level", 0)
        if self._labels is not None:
            metrics.set("plan_ladder_level", 0, labels=self._labels)

    def _mark(self, counter: str | None) -> None:
        if counter is not None:
            metrics.add(counter)
        metrics.set("plan_ladder_level", self._level)
        if self._labels is not None:
            if counter is not None:
                metrics.add(counter, labels=self._labels)
            metrics.set("plan_ladder_level", self._level,
                        labels=self._labels)

    @classmethod
    def from_config(cls, cfg, factory) -> "ComputeHealer | None":
        """None (zero-cost off) when both mechanisms are disabled:
        ``plan_ladder = off`` AND ``device_reinit_max = 0``."""
        steps = parse_ladder(getattr(cfg, "plan_ladder", "auto"))
        reinit_max = int(getattr(cfg, "device_reinit_max", 0) or 0)
        if not steps and reinit_max <= 0:
            return None
        return cls(
            cfg, factory, steps=steps,
            promote_after=int(getattr(cfg, "promote_after_segments",
                                      0) or 0),
            reinit_max=reinit_max,
            reinit_window_s=float(getattr(cfg, "device_reinit_window_s",
                                          300.0)))

    # ------------------------------------------------------- state

    @property
    def level(self) -> int:
        return self._level

    @property
    def rungs(self) -> list[Rung]:
        return list(self._rungs)

    @property
    def active_cfg(self):
        """The config of the active rung (the base config at level 0)."""
        if self._level == 0:
            return self._cfg
        return self._rungs[self._level - 1].cfg

    @property
    def active_step(self) -> str:
        return "full" if self._level == 0 \
            else self._rungs[self._level - 1].step

    @property
    def micro_batch(self) -> int:
        """Micro-batch size of the ACTIVE plan — the engine's dispatch
        unit must follow demotions (the micro_batch rung drops it to
        1, and the demoted processor has no batch programs)."""
        return max(1, int(getattr(self.active_cfg,
                                  "micro_batch_segments", 1) or 1))

    def bind_base(self, base_staged: bool | None) -> None:
        """Late-bind the resolved staged flag of the pipeline's actual
        processor (the healer is built before the processor resolves
        on a custom-processor pipeline) and rebuild the rungs."""
        if base_staged != self._base_staged:
            self._base_staged = base_staged
            self._rungs = ladder_rungs(self._cfg, base_staged,
                                       self._steps)

    # -------------------------------------------------- transitions

    def classify(self, exc: BaseException) -> str | None:
        """Device-fault kind of ``exc`` (None = not a device fault).
        Deliberately NOT filtered by remaining budget: the engine must
        learn the kind even when nothing is left, so it can raise the
        typed FATAL escalation (LadderExhausted /
        ReinitBudgetExceeded) instead of letting a DEVICE-classified
        exception escape — an outer supervisor would restart on
        DEVICE, and a permanently OOMing run must escalate, not
        flap."""
        return classify_device(exc)

    def _build(self, rung_level: int):
        if rung_level == 0:
            return self._factory(self._cfg, self._base_staged)
        rung = self._rungs[rung_level - 1]
        return self._factory(rung.cfg, rung.staged)

    def demote(self, exc: BaseException, kind: str):
        """One rung down: returns the replacement processor, or None
        when the ladder is exhausted (the engine then escalates).
        Every demotion resets the promotion counter."""
        if self._level >= len(self._rungs):
            return None
        self._level += 1
        self._healthy = 0
        rung = self._rungs[self._level - 1]
        self._mark("plan_demotions")
        events.emit("heal.demote",
                    stream=(self._labels or {}).get("stream"),
                    info=f"{rung.step}@{self._level} ({kind})")
        log.warning(
            f"[selfheal] device fault ({kind}) — demoting to ladder "
            f"rung {self._level}/{len(self._rungs)} ({rung.step}): "
            f"{exc!r}")
        return self._build(self._level)

    def reinit(self, exc: BaseException):
        """Backend reinit at the current rung: returns the fresh
        processor, or None when the reinit budget is spent within the
        window (the engine then escalates — a flapping device must
        not flap forever).  The caller owns the surrounding teardown
        (jax.clear_caches, ring invalidation, pending re-dispatch)."""
        if self._reinit is None or \
                not self._reinit.should_restart(exc):
            return None
        metrics.add("device_reinits")
        if self._labels is not None:
            metrics.add("device_reinits", labels=self._labels)
        events.emit("heal.reinit",
                    stream=(self._labels or {}).get("stream"),
                    info=f"{self.active_step}@{self._level}")
        log.warning(
            f"[selfheal] device halt — reinitializing backend at "
            f"ladder rung {self._level} ({self.active_step}): {exc!r}")
        return self._build(self._level)

    def rebuild(self, shared=None):
        """Fresh processor at the CURRENT rung, with no budget check
        and no counters: the fleet's SHARED device reinit
        (pipeline/fleet.py) makes one budgeted decision for the whole
        device and then rebuilds every lane — charging each lane's own
        reinit budget for a fault it didn't cause would let one
        flapping neighbor bankrupt the fleet.

        ``shared`` (a zero-arg factory) serves the fleet's LIVE
        migration: a lane at rung 0 re-admits through its target
        device's shared plan cache (rejoining that member's batch
        family and paying a compile only if the family is new there);
        a DEMOTED lane stays on its unshared rung — exactly the
        batch-former's membership rule."""
        if shared is not None and self._level == 0:
            return shared()
        return self._build(self._level)

    # --------------------------------------------- promotion probe

    def note_healthy(self) -> None:
        """One successfully fetched segment on a demoted plan."""
        if self._level > 0 and self.promote_after > 0:
            self._healthy += 1

    def promote_due(self) -> bool:
        return (self._level > 0 and self.promote_after > 0
                and self._healthy >= self.promote_after)

    def promote(self):
        """One rung back up (the promotion probe): returns the richer
        processor; the NEXT dispatch probes it and a recurring fault
        simply demotes again."""
        if self._level <= 0:
            return None
        self._level -= 1
        self._healthy = 0
        self._mark("plan_promotions")
        events.emit("heal.promote",
                    stream=(self._labels or {}).get("stream"),
                    info=f"{self.active_step}@{self._level}")
        log.info(
            f"[selfheal] {self.promote_after} healthy segments — "
            f"promotion probe back to rung {self._level} "
            f"({self.active_step})")
        return self._build(self._level)
