"""Plan-family registry invariants (pipeline/registry.py).

The contract: plan families are DATA in one table, and every consumer
— ``segment.py`` plan construction, ``demote.py``'s ladder,
``hlo_audit.py``'s auditable specs, ``fleet.py``'s shared plan cache —
enumerates from that table alone.  A family added to only one consumer
must fail here; the four source files must contain no independent
family lists (grep-provable, pinned below)."""

import json
import os
import re

import pytest

from srtb_tpu.analysis import hlo_audit as HA
from srtb_tpu.config import Config
from srtb_tpu.pipeline import registry
from srtb_tpu.resilience.demote import (LADDER_ORDER, ladder_rungs,
                                        parse_ladder)

SRC = os.path.join(os.path.dirname(__file__), "..", "srtb_tpu")


def _read(rel):
    with open(os.path.join(SRC, rel)) as f:
        return f.read()


# ------------------------------------------------------------------
# round-trip: registry <-> plan cards <-> ladder, no orphans


def test_every_family_has_a_checked_in_card_and_vice_versa():
    """registry -> plan_cards.json and back, no orphans in either
    direction: a family registered but never carded (or a card whose
    family was dropped) fails CI before a human ever greps."""
    baseline = HA.CardBaseline.load(HA.DEFAULT_BASELINE)
    assert baseline.cards, "checked-in plan_cards.json missing/empty"
    keys = set(registry.plan_keys())
    carded = set(baseline.cards)
    assert keys - carded == set(), \
        f"registered families without a plan card: {keys - carded}"
    assert carded - keys == set(), \
        f"plan cards without a registered family: {carded - keys}"


def test_card_mode_matches_registered_mode():
    baseline = HA.CardBaseline.load(HA.DEFAULT_BASELINE)
    for key, card in baseline.cards.items():
        fam = registry.family(key)
        assert fam is not None
        assert card.get("mode") == fam.mode, (key, card.get("mode"))


def test_family_roundtrip_key_signature_consistency():
    """Equal plan_cache_keys + equal constructor overrides imply
    equal plan_signatures across the WHOLE registered zoo.  The
    cache key is a config-only projection; the ``staged`` audit
    override is a constructor input the fleet never passes
    (SharedPlanCache builds with staged=None), so the fleet-safety
    claim is keyed on (cache_key, staged override) here — families
    differing ONLY in the override (e.g. four_step_ftail_donate vs
    staged) legitimately share a config key while the fleet can only
    ever reach the staged=None member."""
    by_key = {}
    for spec in registry.plan_families():
        cfg = HA._audit_config(HA.DEFAULT_LOG2N, HA.DEFAULT_CHANNELS,
                               dict(spec.cfg))
        with HA._env(spec.env):
            cache_key = registry.plan_cache_key(
                cfg, donate_input=spec.donate)
            proc = registry.build_processor(
                cfg, staged=spec.staged, donate_input=spec.donate)
            sig = proc.plan_signature()
        seen = by_key.setdefault((cache_key, spec.staged),
                                 (spec.key, sig))
        assert seen[1] == sig, \
            (f"families {seen[0]} and {spec.key} share a cache key "
             "but resolve different plan signatures")
        # the mode's processor class really implements the mode
        assert proc.MODE == spec.mode, spec.key


def test_ladder_order_comes_from_registry():
    assert LADDER_ORDER == registry.ladder_order()
    assert parse_ladder("auto") == registry.ladder_order()
    with pytest.raises(ValueError):
        parse_ladder("warp_drive")


def test_every_ladder_rung_lands_on_an_eligible_carded_family():
    """The full ladder walk from the fully-featured audit config:
    every rung fingerprint-matches a checked-in card whose registered
    family is ladder-ELIGIBLE (audit_ladder is the CI gate; this
    pins it in the suite too)."""
    baseline = HA.CardBaseline.load(HA.DEFAULT_BASELINE)
    assert HA.audit_ladder(baseline) == []


def test_ladder_sheds_periodicity_first_and_never_enters_it():
    cfg = HA._audit_config(HA.DEFAULT_LOG2N, HA.DEFAULT_CHANNELS,
                           dict(HA.LADDER_AUDIT_CFG))
    rungs = ladder_rungs(cfg)
    assert rungs[0].step == "search_mode"
    assert rungs[0].cfg.search_mode == "single_pulse"
    # every subsequent rung stays single-pulse
    for rung in rungs[1:]:
        assert rung.cfg.search_mode == "single_pulse", rung.step
    # the periodicity families are registered ladder-INELIGIBLE
    for key in ("periodicity_ftail", "periodicity_ring_mb2"):
        assert registry.family(key).ladder is False


def test_family_added_to_only_one_consumer_fails():
    """A temp family registered WITHOUT a card surfaces as
    unbaselined in the audit diff (the plan_audit CI gate) — adding a
    family is not done until its card is accepted."""
    baseline = HA.CardBaseline.load(HA.DEFAULT_BASELINE)
    with registry.temp_family(registry.PlanFamily(
            key="__test_orphan", desc="t",
            cfg={"fft_strategy": "four_step", "fused_tail": "on"},
            donate=True)):
        assert "__test_orphan" in registry.plan_keys()
        assert "__test_orphan" in tuple(s.key for s in HA.PLAN_FAMILIES)
        cards = HA.audit_families(["__test_orphan"])
        _, new_plans, _ = HA.diff_cards(cards, baseline)
        assert new_plans == ["__test_orphan"]
    assert "__test_orphan" not in registry.plan_keys()


# ------------------------------------------------------------------
# search modes


def test_mode_dispatch_and_unknown_mode():
    cfg = HA._audit_config(HA.DEFAULT_LOG2N, HA.DEFAULT_CHANNELS, {})
    assert registry.resolve_mode(cfg).name == "single_pulse"
    p = registry.build_processor(cfg)
    assert p.MODE == "single_pulse"
    cfg_p = cfg.replace(search_mode="periodicity")
    assert registry.build_processor(cfg_p).MODE == "periodicity"
    with pytest.raises(ValueError, match="unknown search_mode"):
        registry.build_processor(cfg.replace(search_mode="nope"))


def test_cache_key_distinguishes_modes_and_keys_are_json():
    cfg = HA._audit_config(HA.DEFAULT_LOG2N, HA.DEFAULT_CHANNELS, {})
    k1 = registry.plan_cache_key(cfg)
    k2 = registry.plan_cache_key(cfg.replace(search_mode="periodicity"))
    assert k1 != k2
    assert json.loads(k1)["mode"] == "single_pulse"
    assert json.loads(k2)["mode"] == "periodicity"
    # tenancy stays outside the key (the fleet claim, both modes)
    k3 = registry.plan_cache_key(cfg.replace(
        search_mode="periodicity", stream_name="s7",
        stream_priority=3))
    assert k2 == k3


def test_periodicity_knobs_split_the_cache_key():
    cfg = HA._audit_config(HA.DEFAULT_LOG2N, HA.DEFAULT_CHANNELS,
                           {"search_mode": "periodicity"})
    k1 = registry.plan_cache_key(cfg)
    k2 = registry.plan_cache_key(
        cfg.replace(periodicity_candidates=8))
    assert k1 != k2
    # ...but NOT the single-pulse key (the knobs are dead there)
    s1 = registry.plan_cache_key(
        cfg.replace(search_mode="single_pulse"))
    s2 = registry.plan_cache_key(
        cfg.replace(search_mode="single_pulse",
                    periodicity_candidates=8))
    assert s1 == s2


def test_duplicate_registration_rejected():
    with pytest.raises(ValueError, match="already registered"):
        registry.register_mode(registry.SearchMode(
            "single_pulse", "dup", "x:y"))
    with pytest.raises(ValueError, match="already registered"):
        registry.register_step(registry.LadderStep(
            "ring", "dup", lambda c, s: None))
    with pytest.raises(ValueError, match="already registered"):
        with registry.temp_family(registry.PlanFamily(
                key="monolithic", desc="dup")):
            pass


def test_family_with_unregistered_mode_rejected():
    with pytest.raises(ValueError, match="unregistered mode"):
        registry.register_family(registry.PlanFamily(
            key="__bad_mode", desc="t", mode="nope"))


# ------------------------------------------------------------------
# grep-provable: no independent family lists in the consumers


def test_consumers_hold_no_independent_family_lists():
    """The four consumers enumerate from the registry alone.  Pinned
    by source inspection: the old literal tables and mirrored rule
    chains must not reappear."""
    hlo = _read("analysis/hlo_audit.py")
    assert "PLAN_FAMILIES = (" not in hlo
    assert "PlanSpec(\"" not in hlo and "PlanSpec('" not in hlo
    assert "registry.plan_families()" in hlo
    assert "registry.plan_keys()" in hlo

    demote = _read("resilience/demote.py")
    # the canonical order is READ from the registry, never restated
    assert re.search(r"LADDER_ORDER\s*=\s*\(", demote) is None
    assert "registry.ladder_order()" in demote
    # no per-step rule chain left behind
    assert '== "micro_batch"' not in demote
    assert '== "monolithic"' not in demote
    assert "registry.ladder_step(" in demote

    fleet = _read("pipeline/fleet.py")
    assert "registry.plan_cache_key(" in fleet
    assert "registry.build_processor(" in fleet
    assert "SegmentProcessor.plan_cache_key(" not in fleet
    assert re.search(r"SegmentProcessor\(\s*cfg", fleet) is None

    runtime = _read("pipeline/runtime.py")
    assert "registry.build_processor(" in runtime


def test_tools_enumerate_from_registry():
    # the plan_audit CLI lists families through hlo_audit's live view
    src = _read("tools/plan_audit.py")
    assert "PLAN_FAMILIES = (" not in src


def test_config_knobs_registered_in_field_sets():
    """The new knobs parse from config files / CLI like every other
    option (a field missing from the typed sets silently becomes a
    string)."""
    cfg = Config()
    assert cfg.set_option("search_mode", "periodicity")
    assert cfg.search_mode == "periodicity"
    assert cfg.set_option("periodicity_harmonics", "2 ** 3")
    assert cfg.periodicity_harmonics == 8
    assert cfg.set_option("periodicity_snr_threshold", "7.5")
    assert cfg.periodicity_snr_threshold == 7.5
    assert cfg.set_option("deterministic_timestamps", "1")
    assert cfg.deterministic_timestamps is True
    assert cfg.set_option("periodicity_fold_bins", "32")
    assert cfg.periodicity_fold_bins == 32


def test_the_ladder_walks_these_steps_in_this_order():
    """The rungs by name (the `front_fuse` rung went in PR 50 with the
    family it dropped): what `plan_ladder auto` walks, and what an
    operator's explicit list is checked against."""
    assert registry.ladder_order() == (
        "quality", "search_mode", "micro_batch", "ring", "skzap",
        "fused_tail", "staged", "monolithic")
    with pytest.raises(ValueError, match="front_fuse"):
        registry.ladder_step("front_fuse")
    with pytest.raises(ValueError):
        parse_ladder("micro_batch,front_fuse")


def test_every_family_has_a_card_and_every_card_a_family():
    """The registry and the checked-in plan cards name the same plans
    (24 since PR 50): a family without a card is unaudited, a card
    without a family pins a plan nothing can build."""
    baseline = HA.CardBaseline.load(HA.DEFAULT_BASELINE)
    assert sorted(baseline.cards) == sorted(registry.plan_keys())
    assert len(baseline.cards) == 24
    retired = {"staged_ffuse", "staged_ffuse_ring", "staged_pallas2",
               "staged_pallas2_unfused"}
    assert not retired & set(registry.plan_keys())
    # a family's environment chooses among what is left
    assert {spec.env.get("SRTB_STAGED_ROWS_IMPL", "xla")
            for spec in registry.plan_families()} == {"xla", "pallas"}
