"""Plan-family registry: execution plans as DATA, not if-chains.

Before this module, four subsystems each carried their own enumeration
of the plan zoo and had to be edited in lockstep whenever a family was
added: ``segment.py`` (plan construction + resolvers),
``analysis/hlo_audit.py`` (the auditable family specs), ``demote.py``
(the self-healing ladder's step chain), and ``fleet.py`` (the shared
plan cache's key/build logic).  The FPGA pulsar-search composition
paper (PAPERS.md, *Combining Multiple Optimised FPGA-based Pulsar
Search Modules*) is the target architecture — independent search
modules registered behind one harness — and this registry is the one
table they all consume from, so the enumerations can never drift:

- :class:`PlanFamily` — one auditable plan family: the config
  projection that selects it, its search mode, and whether the
  demotion ladder may land on it
  (``ladder`` eligibility).  ``analysis/hlo_audit.py`` enumerates
  these (``plan_families()``) instead of keeping its own tuple, and
  ``plan_audit --selftest`` proves a family registered here WITHOUT a
  checked-in plan card fails the CI gate (``temp_family``).

- :class:`LadderStep` — one demotion-ladder step: its canonical
  position plus the apply rule (cfg -> cheaper cfg, or None when the
  step would not change the resolved plan).  ``resilience/demote.py``
  walks ``ladder_steps()`` instead of its own if-chain; the apply
  rules delegate to the SAME pure-config predicates the
  SegmentProcessor resolvers use (``pipeline/segment.py``
  ``ring_usable`` / ``fused_tail_resolves``), so a rung is skipped
  exactly when the feature would not resolve ON.

- :class:`SearchMode` — one registered search capability: the
  processor class that implements it and the Config field that selects
  it (``Config.search_mode``).  ``Pipeline``/``ThreadedPipeline``, the
  self-healing plan factory, the fleet's :class:`SharedPlanCache`, the
  archive replay engine and the HLO auditor all build processors
  through :func:`build_processor` / key them through
  :func:`plan_cache_key`, so a new mode lands in every consumer —
  auditor, demotion ladder, chaos soak, fleet — by registering here.

The registry deliberately imports nothing heavy at module level;
processor classes resolve lazily (``module:Class`` paths) so importing
the table costs nothing and no import cycles form (the processor
modules never import this one).
"""

from __future__ import annotations

import contextlib
import importlib
from dataclasses import dataclass, field

# ------------------------------------------------------------------
# search modes


@dataclass(frozen=True)
class SearchMode:
    """One registered search capability (``Config.search_mode``)."""

    name: str
    desc: str
    # lazy "module:Class" path of the SegmentProcessor (sub)class that
    # implements the mode — resolved on first build, never at import
    cls_path: str

    def resolve(self):
        mod, _, cls = self.cls_path.partition(":")
        return getattr(importlib.import_module(mod), cls)


_MODES: dict[str, SearchMode] = {}


def register_mode(mode: SearchMode) -> SearchMode:
    if mode.name in _MODES:
        raise ValueError(f"search mode {mode.name!r} already registered")
    _MODES[mode.name] = mode
    return mode


def search_modes() -> tuple[SearchMode, ...]:
    return tuple(_MODES.values())


def resolve_mode(cfg) -> SearchMode:
    """The registered mode selected by ``cfg.search_mode`` (missing
    attribute = the default single-pulse mode).  Unknown names raise at
    plan-build time — a typo must not silently run the wrong search."""
    name = str(getattr(cfg, "search_mode", "single_pulse")
               or "single_pulse").lower()
    mode = _MODES.get(name)
    if mode is None:
        raise ValueError(
            f"unknown search_mode {name!r} "
            f"(registered: {', '.join(sorted(_MODES))})")
    return mode


def build_processor(cfg, **kwargs):
    """Build the segment processor for ``cfg`` through the registry:
    the ONE constructor every consumer (Pipeline, healer plan factory,
    fleet shared-plan cache, archive engine, HLO auditor) uses,
    so a registered mode reaches all of them.  ``kwargs`` pass through
    to the processor constructor (window_name / staged /
    donate_input)."""
    return resolve_mode(cfg).resolve()(cfg, **kwargs)


def plan_cache_key(cfg, donate_input: bool = False, **kwargs) -> str:
    """Mode-dispatched shared-plan cache key (see
    ``SegmentProcessor.plan_cache_key``): each mode's class projects
    its own trace-relevant config, so two configs share a compiled
    plan only when mode AND projection agree."""
    return resolve_mode(cfg).resolve().plan_cache_key(
        cfg, donate_input=donate_input, **kwargs)


# ------------------------------------------------------------------
# plan families (the auditable zoo)


@dataclass(frozen=True)
class PlanFamily:
    """One auditable plan family: the Config/constructor knobs that
    select it, its search mode, and its demotion-ladder eligibility
    (``ladder=False`` families — e.g. the periodicity mode, which the
    ladder demotes OUT of, never INTO — may not be landed on by a
    demotion; ``analysis/hlo_audit.audit_ladder`` enforces it)."""

    key: str
    desc: str
    cfg: dict = field(default_factory=dict)
    donate: bool = False
    staged: bool | None = None
    env: dict = field(default_factory=dict)
    mode: str = "single_pulse"
    ladder: bool = True


_FAMILIES: dict[str, PlanFamily] = {}


def register_family(fam: PlanFamily) -> PlanFamily:
    if fam.key in _FAMILIES:
        raise ValueError(f"plan family {fam.key!r} already registered")
    if fam.mode not in _MODES:
        raise ValueError(
            f"plan family {fam.key!r}: unregistered mode {fam.mode!r}")
    _FAMILIES[fam.key] = fam
    return fam


def plan_families() -> tuple[PlanFamily, ...]:
    return tuple(_FAMILIES.values())


def plan_keys() -> tuple[str, ...]:
    return tuple(_FAMILIES)


def family(key: str) -> PlanFamily | None:
    return _FAMILIES.get(key)


@contextlib.contextmanager
def temp_family(fam: PlanFamily):
    """Scoped registration for tests and the plan-audit selftest: the
    family exists (and is enumerated by every consumer) only inside
    the ``with`` block."""
    register_family(fam)
    try:
        yield fam
    finally:
        _FAMILIES.pop(fam.key, None)


# ------------------------------------------------------------------
# demotion-ladder steps


@dataclass(frozen=True)
class LadderStep:
    """One demotion step: canonical name + the apply rule.  ``apply``
    returns ``(cheaper_cfg, staged_override)`` or None when the step
    would not change the active RESOLVED plan (skipped rung — demoting
    onto an identical plan would burn a ladder level recovering
    nothing).  ``staged`` in/out is the explicit SegmentProcessor
    constructor override (None = resolve from segment size)."""

    name: str
    desc: str
    apply: object  # callable (cfg, staged) -> (cfg, staged) | None


_STEPS: dict[str, LadderStep] = {}


def register_step(step: LadderStep) -> LadderStep:
    if step.name in _STEPS:
        raise ValueError(f"ladder step {step.name!r} already registered")
    _STEPS[step.name] = step
    return step


def ladder_steps() -> tuple[LadderStep, ...]:
    return tuple(_STEPS.values())


def ladder_order() -> tuple[str, ...]:
    return tuple(_STEPS)


def ladder_step(name: str) -> LadderStep:
    step = _STEPS.get(name)
    if step is None:
        raise ValueError(
            f"unknown ladder step {name!r} "
            f"(steps: {', '.join(_STEPS)})")
    return step


# ------------------------------------------------------------------
# built-in registrations
# ------------------------------------------------------------------

register_mode(SearchMode(
    "single_pulse",
    "single-pulse search: boxcar cascade over the dedispersed "
    "time series (the reference pipeline's mode)",
    "srtb_tpu.pipeline.segment:SegmentProcessor"))

register_mode(SearchMode(
    "periodicity",
    "periodicity search: harmonic-summed power spectrum over the "
    "dedispersed time series + phase folding at detected candidates "
    "(the FPGA pulsar-search paper's module set), on top of the "
    "single-pulse chain",
    "srtb_tpu.pipeline.periodicity:PeriodicitySegmentProcessor"))


# ---- ladder steps, cheapest-to-drop first.  The apply rules import
# the shared pure-config predicates lazily: the SegmentProcessor
# resolvers and these rules are the same functions, so a rung can
# never demote onto an identical plan by rule drift.

def _resolved_staged(cfg, staged):
    from srtb_tpu.pipeline.segment import staged_resolves
    return staged_resolves(cfg, staged)


def _apply_quality(cfg, staged):
    if not getattr(cfg, "quality_stats", False):
        return None
    return cfg.replace(quality_stats=False), staged


def _apply_search_mode(cfg, staged):
    if str(getattr(cfg, "search_mode", "single_pulse")
           or "single_pulse").lower() == "single_pulse":
        return None
    return cfg.replace(search_mode="single_pulse"), staged


def _apply_micro_batch(cfg, staged):
    if int(getattr(cfg, "micro_batch_segments", 1) or 1) <= 1:
        return None
    return cfg.replace(micro_batch_segments=1), staged


def _apply_ring(cfg, staged):
    if str(getattr(cfg, "ingest_ring", "auto")).lower() == "off":
        return None
    from srtb_tpu.pipeline.segment import ring_usable
    if not ring_usable(cfg):
        return None
    return cfg.replace(ingest_ring="off"), staged


def _apply_skzap(cfg, staged):
    if not (getattr(cfg, "use_pallas_sk", False)
            and getattr(cfg, "use_pallas", False)):
        return None
    return cfg.replace(use_pallas_sk=False), staged


def _apply_fused_tail(cfg, staged):
    # drops the fused epilogue AND the Pallas kernels hosting it:
    # this rung is the Mosaic-free fallback, so a kernel compile
    # fault cannot survive it
    from srtb_tpu.pipeline.segment import fused_tail_resolves
    if not (fused_tail_resolves(cfg, _resolved_staged(cfg, staged))
            or getattr(cfg, "use_pallas", False)):
        return None
    return cfg.replace(fused_tail="off", use_pallas=False), staged


def _apply_staged(cfg, staged):
    if _resolved_staged(cfg, staged):
        return None
    # staged forbids micro-batching; force it off even when an
    # explicit plan_ladder subset skipped the micro_batch rung
    if int(getattr(cfg, "micro_batch_segments", 1) or 1) > 1:
        cfg = cfg.replace(micro_batch_segments=1)
    return cfg, True


def _apply_monolithic(cfg, staged):
    from srtb_tpu.pipeline.segment import segment_strategy
    n = int(getattr(cfg, "baseband_input_count", 0) or 0)
    already = (not _resolved_staged(cfg, staged) and n > 0
               and segment_strategy(cfg, False) == "monolithic")
    if already:
        return None
    return cfg.replace(fft_strategy="monolithic"), False


register_step(LadderStep(
    "quality", "drop the data-quality epilogue (telemetry, not "
    "science) — the very cheapest thing to shed",
    _apply_quality))
register_step(LadderStep(
    "search_mode", "drop the extra search mode (periodicity folding) "
    "back to single-pulse — the cheapest science to shed",
    _apply_search_mode))
register_step(LadderStep(
    "micro_batch", "drop micro-batching (B x program footprint)",
    _apply_micro_batch))
register_step(LadderStep(
    "ring", "drop the ingest ring's carry programs",
    _apply_ring))
register_step(LadderStep(
    "skzap", "drop the one-kernel SK-zap fusion",
    _apply_skzap))
register_step(LadderStep(
    "fused_tail", "drop the fused epilogue + every Pallas kernel "
    "(the Mosaic-free rung)", _apply_fused_tail))
register_step(LadderStep(
    "staged", "three small programs instead of one big one "
    "(the proven chain-OOM answer)", _apply_staged))
register_step(LadderStep(
    "monolithic", "the minimal-feature floor that must run anywhere "
    "XLA runs", _apply_monolithic))


# ---- plan families.  The audit shape (analysis/hlo_audit.py,
# default 2^16 samples / 8 channels) keeps every family lowerable in
# ~a second on CPU; the cfg dicts are overrides on that audit config.

_RING_CFG = {"baseband_reserve_sample": True, "dm": 0.1}

for _fam in (
    PlanFamily("monolithic", "one XLA R2C custom call, unfused tail",
               {"fft_strategy": "monolithic", "fused_tail": "off"}),
    PlanFamily("monolithic_donate", "monolithic with the donated raw "
               "input",
               {"fft_strategy": "monolithic", "fused_tail": "off"},
               donate=True),
    PlanFamily("four_step", "Bailey four-step R2C, unfused tail",
               {"fft_strategy": "four_step", "fused_tail": "off"}),
    PlanFamily("four_step_ftail", "four-step with the fused RFI+chirp "
               "tail",
               {"fft_strategy": "four_step", "fused_tail": "on"}),
    PlanFamily("four_step_ftail_donate", "fused tail + donated raw "
               "input",
               {"fft_strategy": "four_step", "fused_tail": "on"},
               donate=True),
    PlanFamily("four_step_ftail_mb2", "fused tail, micro-batch of 2",
               {"fft_strategy": "four_step", "fused_tail": "on",
                "micro_batch_segments": 2},
               donate=True),
    PlanFamily("mxu_ftail", "radix-128 MXU matmul FFT, fused tail",
               {"fft_strategy": "mxu", "fused_tail": "on"}),
    PlanFamily("pallas_ftail", "Pallas unpack/chirp kernels, fused tail",
               {"fft_strategy": "four_step", "fused_tail": "on",
                "use_pallas": True}),
    PlanFamily("pallas_fft_ftail", "Pallas VMEM row-FFT legs, fused "
               "tail",
               {"fft_strategy": "pallas", "fused_tail": "on",
                "use_pallas": True}),
    PlanFamily("pallas_skzap", "fully fused: one-kernel "
               "watfft+SK+detect",
               {"fft_strategy": "four_step", "fused_tail": "on",
                "use_pallas": True, "use_pallas_sk": True}),
    PlanFamily("pallas_skzap_donate", "skzap plan + donated raw input",
               {"fft_strategy": "four_step", "fused_tail": "on",
                "use_pallas": True, "use_pallas_sk": True},
               donate=True),
    PlanFamily("staged", "three-program staged plan, fused tail, "
               "donation",
               {"fft_strategy": "four_step", "fused_tail": "on"},
               donate=True, staged=True),
    PlanFamily("staged_unfused", "the plain staged plan (what a 2^30 "
               "segment resolves to): every stage in blocks of the "
               "boundary, unfused tail",
               {"fft_strategy": "four_step", "fused_tail": "off"},
               donate=True, staged=True),
    PlanFamily("staged_pallas", "staged with Pallas row-FFT legs",
               {"fft_strategy": "four_step", "fused_tail": "on"},
               donate=True, staged=True,
               env={"SRTB_STAGED_ROWS_IMPL": "pallas"}),
    # ---- ingest-ring (ring-v1) families: overlap-save reserves a
    # tail (baseband_reserve_sample + a small dm keeps 0 < reserved
    # < n at the audit shape), so the two-input carry ++ new assemble
    # programs exist and their carry donation must audit as a PROVEN
    # alias (checks.ring_alias_ok).
    PlanFamily("four_step_ftail_ring", "fused tail + ingest ring: "
               "carry donation proven aliased on the warm assemble "
               "program",
               {"fft_strategy": "four_step", "fused_tail": "on",
                **_RING_CFG},
               donate=True),
    PlanFamily("monolithic_ring", "ring on the unfused monolithic "
               "fallback plan",
               {"fft_strategy": "monolithic", "fused_tail": "off",
                **_RING_CFG},
               donate=True),
    PlanFamily("pallas_skzap_ring", "fully fused 4-pass plan + ring",
               {"fft_strategy": "four_step", "fused_tail": "on",
                "use_pallas": True, "use_pallas_sk": True,
                **_RING_CFG},
               donate=True),
    PlanFamily("four_step_ftail_ring_mb2", "ring micro-batch: ONE "
               "carry + B stride uploads assemble B overlapped "
               "segments",
               {"fft_strategy": "four_step", "fused_tail": "on",
                "micro_batch_segments": 2, **_RING_CFG},
               donate=True),
    PlanFamily("pallas_skzap_ring_mb2", "the fully-featured single-"
               "pulse plan: skzap + ring + micro-batch of 2 — the "
               "search_mode demotion rung's landing target",
               {"fft_strategy": "four_step", "fused_tail": "on",
                "use_pallas": True, "use_pallas_sk": True,
                "micro_batch_segments": 2, **_RING_CFG},
               donate=True),
    PlanFamily("staged_ring", "staged plan + ring: stage_a_ring emits "
               "the carry alongside the canonical boundary",
               {"fft_strategy": "four_step", "fused_tail": "on",
                **_RING_CFG},
               donate=True, staged=True),
    PlanFamily("staged_unfused_ring", "the plain staged plan + ring "
               "(what a 2^30 segment with a reserve resolves to): stage "
               "(a) takes the carry and the new bytes as rows of its "
               "view and joins them a strip at a time",
               {"fft_strategy": "four_step", "fused_tail": "off",
                **_RING_CFG},
               donate=True, staged=True),
    # ---- data-quality epilogue (srtb_tpu/quality/): cheap jnp
    # reductions over the spectrum + waterfall ride the detect tail
    # as a side output (coarse-bin-sized extra traffic);
    # ladder=False because the quality rung (FIRST in the order)
    # sheds the epilogue and must never demote INTO it.
    PlanFamily("four_step_ftail_quality", "fused-tail four-step plan "
               "with the data-quality epilogue side output",
               {"fft_strategy": "four_step", "fused_tail": "on",
                "quality_stats": True},
               donate=True, ladder=False),
    # ---- periodicity search mode: the single-pulse chain PLUS the
    # harmonic-summed power spectrum + phase folding over the
    # dedispersed time series (pipeline/periodicity.py).  The extra
    # passes are time-series-sized (spectrum / channel_count); ladder=False
    # because the demotion ladder sheds the mode (search_mode rung,
    # FIRST in the order) and must never demote INTO it.
    PlanFamily("periodicity_ftail", "periodicity mode on the fused-"
               "tail four-step plan: harmonic sum + fold over the "
               "detection time series",
               {"fft_strategy": "four_step", "fused_tail": "on",
                "search_mode": "periodicity"},
               donate=True, mode="periodicity",
               ladder=False),
    PlanFamily("periodicity_ring_mb2", "the archive-replay shape: "
               "periodicity mode + ingest ring + micro-batch of 2",
               {"fft_strategy": "four_step", "fused_tail": "on",
                "micro_batch_segments": 2, "search_mode": "periodicity",
                **_RING_CFG},
               donate=True, mode="periodicity",
               ladder=False),
):
    register_family(_fam)
del _fam
