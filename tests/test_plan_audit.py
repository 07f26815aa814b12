"""Compile-time HLO plan auditor (srtb_tpu/analysis/hlo_audit.py +
python -m srtb_tpu.tools.plan_audit): donation proven honored vs
visibly dropped, audited spectrum passes, dtype/transfer flags,
baseline accept/reject, CLI exit codes.

Everything here lowers + compiles on the CPU backend; no program is
ever executed (the auditor's contract: no device required).
"""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from srtb_tpu.analysis import hlo_audit as HA
from srtb_tpu.tools import plan_audit as CLI

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKED_IN = os.path.join(REPO, "srtb_tpu", "analysis",
                          "plan_cards.json")


def _spec(key):
    return next(s for s in HA.PLAN_FAMILIES if s.key == key)


# cards are compile-derived and deterministic — build the expensive
# ones once per module
@pytest.fixture(scope="module")
def staged_proc():
    return HA.build_plan(_spec("staged"))


@pytest.fixture(scope="module")
def family_cards():
    return HA.audit_families(
        ["monolithic", "four_step_ftail", "pallas_skzap"])


# ---------------------------------------------------------- donation


class TestDonation:
    def test_staged_donation_proven_aliased(self, staged_proc):
        """The canonical [2, S, F, T] boundary makes stage_b/stage_c
        donation a REAL XLA input->output alias, visible in the
        compiled artifact's input_output_alias table."""
        card = HA.audit_processor(staged_proc)
        for name in ("stage_b", "stage_c"):
            prog = card["programs"][name]
            assert prog["donation"]["aliased"] == [0], (name, prog)
            assert prog["donation"]["dropped"] == []
            boundary_bytes = 8 * staged_proc.n_spectrum
            assert prog["alias_bytes"] >= boundary_bytes, (name, prog)
        assert card["checks"]["donation_ok"]

    def test_raw_input_donation_is_classed_never_dropped(self):
        """The fused plan's donated raw uint8 buffer: whether XLA finds
        an output to alias it to is the compiler's call — the audit
        classes it aliased or no_candidate, never dropped, and does not
        fail the plan."""
        proc = HA.build_plan(_spec("four_step_ftail_donate"))
        card = HA.audit_processor(proc)
        don = card["programs"]["fused"]["donation"]
        assert don["declared"] == [0]
        assert don["declared"] == sorted(don["aliased"]
                                         + don["no_candidate"])
        assert don["dropped"] == []
        assert card["checks"]["donation_ok"]  # no_candidate != dropped

    def test_dropped_donation_is_visible(self, staged_proc):
        """Deliberately disabling donation (a non-donating wrapper of
        the same stage) must visibly change the audited card — the
        regression the CI diff exists to catch."""
        progs = {p[0]: p for p in staged_proc.lowerables()}
        _, fn, args, donated = progs["stage_b"]
        sbytes = 8 * staged_proc.n_spectrum
        honored = HA.audit_program(fn, args, donated, sbytes)
        undonated = HA.audit_program(jax.jit(staged_proc._stage_b),
                                     args, (), sbytes)
        assert honored["donation"]["aliased"] == [0]
        assert undonated["donation"]["declared"] == []
        assert undonated["alias_bytes"] == 0
        assert honored["donation"] != undonated["donation"]

    def test_selftest_catches_both_injections(self):
        assert HA.selftest() == []

    def test_aot_active_processor_still_audits(self, tmp_path):
        """enable_aot swaps the _jit_* attributes for Compiled
        executables (no .lower()); lowerables() must keep handing the
        auditor lowerable wrappers."""
        proc = HA.build_plan(_spec("four_step_ftail"))
        assert proc.enable_aot(str(tmp_path), allow_cpu=True)
        card = HA.audit_processor(proc)
        assert card["total_spectrum_passes"] > 0
        assert all(card["checks"].values()), card["checks"]

    def test_non_dividing_channel_count_staged(self):
        """channel_count that does not divide n_spectrum (waterfall
        truncates the spectrum tail): the staged boundary falls back to
        the flat canonical [2, S, m] — the chain still runs, stage_b
        still aliases its donation, stage_c's is an honest
        no_candidate."""
        import numpy as np

        from srtb_tpu.pipeline.segment import SegmentProcessor
        cfg = HA._audit_config(14, 12, {"fft_strategy": "four_step",
                                        "fused_tail": "on"})
        proc = SegmentProcessor(cfg, staged=True, donate_input=False)
        assert proc.channel_count * proc.watfft_len != proc.n_spectrum
        card = HA.audit_processor(proc)
        b = card["programs"]["stage_b"]["donation"]
        c = card["programs"]["stage_c"]["donation"]
        assert b["aliased"] == [0] and b["dropped"] == []
        assert c["no_candidate"] == [0] and c["dropped"] == []
        raw = np.random.default_rng(0).integers(
            0, 256, cfg.segment_bytes(1), dtype=np.uint8)
        wf, res = proc.process(raw)
        assert wf.shape[2] == 12  # truncated waterfall, F=12


# ------------------------------------------- audited spectrum passes


class TestSpectrumPasses:
    def test_audited_count_is_the_sum_of_its_programs(self, family_cards):
        """Every check holds and a plan's count is its programs' sum.
        No order between families is asserted: the compiler decides it
        (the numbers themselves are pinned by the baseline diff)."""
        for key, card in family_cards.items():
            assert all(card["checks"].values()), (key, card["checks"])
            assert card["total_spectrum_passes"] == sum(
                p["spectrum_passes"]
                for p in card["programs"].values()) > 0, (key, card)

    def test_extra_pass_moves_the_count(self):
        proc = HA.build_plan(_spec("four_step_ftail"))
        (_, fn, args, don), = [p for p in proc.lowerables()
                               if p[0] == "fused"]
        sbytes = 8 * proc.n_spectrum
        clean = HA.audit_program(fn, args, don, sbytes)
        dirty = HA.audit_program(HA.extra_pass_jit(proc), args, don,
                                 sbytes)
        assert dirty["spectrum_passes"] >= clean["spectrum_passes"] + 2

    def test_transfer_and_dtype_clean(self, family_cards):
        for key, card in family_cards.items():
            assert card["checks"]["transfer_free"], (key, card)
            assert card["checks"]["dtype_clean"], (key, card)


# --------------------------------------------------------- HLO flags


class TestFlags:
    def test_f64_flag_positive(self):
        """A program that genuinely lowers f64 ops must be flagged (the
        drift the dtype-drift lint rule guards at source level, proven
        at artifact level here)."""
        with jax.enable_x64():
            f = jax.jit(lambda x: x * 2.0 + 1.0)
            aval = jax.ShapeDtypeStruct((4096,), jnp.float64)
            prog = HA.audit_program(f, (aval,), (), 8 * 4096)
        assert prog["f64_ops"] > 0

    def test_host_callback_flagged(self):
        """A debug.print smuggled into a jitted program shows up as a
        host callback custom-call -> transfer_free would fail."""
        def g(x):
            jax.debug.print("x0={v}", v=x[0])
            return x * 2

        aval = jax.ShapeDtypeStruct((1024,), jnp.float32)
        prog = HA.audit_program(jax.jit(g), (aval,), (), 8 * 1024)
        assert prog["host_callbacks"], prog["custom_calls"]

    def test_analyze_hlo_counts_copies_and_collectives(self):
        txt = """\
HloModule m, input_output_alias={ {0}: (0, {}, may-alias) }

ENTRY %main (p0: f32[4096]) -> f32[4096] {
  %p0 = f32[4096]{0} parameter(0)
  %c = f32[4096]{0} copy(f32[4096]{0} %p0)
  %ag = f32[4096]{0} all-gather(f32[4096]{0} %c), dimensions={0}
  ROOT %t = f32[4096]{0} transpose(f32[4096]{0} %ag), dimensions={0}
}
"""
        a = HA.analyze_hlo(txt, 4096 * 4)
        assert a["entry_copies"] == 1
        assert a["entry_transposes"] == 1
        assert a["collectives"] == ["all-gather"]
        assert a["aliased_params"] == [0]
        # copy r+w, all-gather r+w, transpose r+w = 6 unit sweeps
        assert a["spectrum_passes"] == 6

    def test_alias_table_with_multiple_entries(self):
        """Every entry of a multi-donation alias table must parse — a
        lazy regex used to stop at the first entry's inner '{}' and
        misclassify later aliased params as dropped."""
        txt = ("HloModule m, input_output_alias={ {0}: (0, {}, "
               "may-alias), {1}: (2, {}, must-alias) }, "
               "entry_computation_layout={(f32[8])->f32[8]}\n")
        assert HA.analyze_hlo(txt, 1 << 30)["aliased_params"] == [0, 2]


# -------------------------------------------------- baseline + diff


class TestBaseline:
    def test_accept_then_clean_diff(self, family_cards, tmp_path):
        path = str(tmp_path / "cards.json")
        HA.CardBaseline.from_cards(family_cards).save(path)
        regs, new, stale = HA.diff_cards(family_cards,
                                         HA.CardBaseline.load(path))
        assert regs == [] and new == [] and stale == []

    def test_reject_on_mutated_count(self, family_cards, tmp_path):
        path = str(tmp_path / "cards.json")
        HA.CardBaseline.from_cards(family_cards).save(path)
        data = json.load(open(path))
        card = data["cards"]["four_step_ftail"]
        card["programs"]["fused"]["spectrum_passes"] -= 1
        json.dump(data, open(path, "w"))
        regs, _, _ = HA.diff_cards(family_cards,
                                   HA.CardBaseline.load(path))
        assert regs and "spectrum_passes" in regs[0]

    def test_reject_on_donation_change(self, family_cards, tmp_path):
        path = str(tmp_path / "cards.json")
        HA.CardBaseline.from_cards(family_cards).save(path)
        data = json.load(open(path))
        don = data["cards"]["monolithic"]["programs"]["fused"]["donation"]
        don["declared"] = [0]
        json.dump(data, open(path, "w"))
        regs, _, _ = HA.diff_cards(family_cards,
                                   HA.CardBaseline.load(path))
        assert any("donation" in r for r in regs), regs

    def test_new_and_stale_plans_reported(self, family_cards, tmp_path):
        path = str(tmp_path / "cards.json")
        subset = {"monolithic": family_cards["monolithic"]}
        HA.CardBaseline.from_cards(subset).save(path)
        regs, new, stale = HA.diff_cards(family_cards,
                                         HA.CardBaseline.load(path))
        assert set(new) == {"four_step_ftail", "pallas_skzap"}
        b2 = HA.CardBaseline.from_cards(family_cards)
        _, _, stale2 = HA.diff_cards(subset, b2)
        assert set(stale2) == {"four_step_ftail", "pallas_skzap"}

    def test_notes_carried_forward(self, family_cards, tmp_path):
        path = str(tmp_path / "cards.json")
        b = HA.CardBaseline.from_cards(family_cards)
        b.notes["monolithic"] = "why this card is accepted"
        b.save(path)
        old = HA.CardBaseline.load(path)
        HA.CardBaseline.from_cards(family_cards, old=old).save(path)
        assert HA.CardBaseline.load(path).notes["monolithic"] \
            == "why this card is accepted"

    def test_checked_in_baseline_matches_reality(self):
        """Acceptance gate: the real tree's plan cards match the
        checked-in baseline and every invariant check passes — the
        exact invocation ci.sh gates on (subset keeps it fast; the CI
        stage audits all families)."""
        keys = ["monolithic", "four_step_ftail", "staged"]
        cards = HA.audit_families(keys)
        assert HA.failed_checks(cards) == []
        regs, new, _ = HA.diff_cards(cards,
                                     HA.CardBaseline.load(CHECKED_IN))
        assert regs == [], "\n".join(regs)
        assert new == []


# --------------------------------------------------------------- CLI


class TestCli:
    def test_list_plans(self, capsys):
        assert CLI.main(["--list-plans"]) == 0
        out = capsys.readouterr().out
        for key in ("monolithic", "staged", "pallas_skzap"):
            assert key in out

    def test_unknown_plan_is_usage_error(self):
        assert CLI.main(["--plans", "definitely_not_a_plan"]) == 2

    def test_clean_run_exit_zero_and_json(self, capsys):
        rc = CLI.main(["--plans", "monolithic", "--format", "json"])
        data = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert data["regressions"] == [] and data["failed_checks"] == []
        assert data["cards"]["monolithic"]["total_spectrum_passes"] > 0

    def test_regression_exit_one(self, tmp_path, capsys):
        src = json.load(open(CHECKED_IN))
        src["cards"]["monolithic"]["programs"]["fused"][
            "spectrum_passes"] += 1  # "an extra spectrum-sized pass"
        path = str(tmp_path / "cards.json")
        json.dump(src, open(path, "w"))
        rc = CLI.main(["--plans", "monolithic", "--baseline", path])
        assert rc == 1
        assert "spectrum_passes" in capsys.readouterr().out

    def test_unbaselined_plan_exit_one(self, tmp_path, capsys):
        path = str(tmp_path / "empty.json")
        json.dump({"version": 1, "cards": {}, "notes": {}},
                  open(path, "w"))
        rc = CLI.main(["--plans", "monolithic", "--baseline", path])
        assert rc == 1
        assert "not in baseline" in capsys.readouterr().out

    def test_write_baseline_roundtrip(self, tmp_path, capsys):
        path = str(tmp_path / "cards.json")
        assert CLI.main(["--plans", "monolithic",
                         "--write-baseline", "--baseline", path]) == 0
        capsys.readouterr()
        assert CLI.main(["--plans", "monolithic",
                         "--baseline", path]) == 0
