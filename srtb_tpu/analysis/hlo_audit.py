"""Compile-time HLO plan auditor: count spectrum-sized passes and prove
donation and transfer-freedom per execution plan, without a device.

The pipeline is HBM-bandwidth bound.  srtb-lint (analysis/core.py)
checks the Python source; this module checks one level down, at the
**lowered-HLO / compiled-artifact** level, so a regression in bytes
moved, aliasing, or dtype is caught on CPU CI before a TPU run ever
happens (cf. the bandwidth-accounting discipline of arXiv:2506.15437
and the stream/overlap audit methodology of arXiv:2101.00941).

For every plan family reachable from ``plan_signature()`` the auditor
AOT-lowers the plan's jitted programs (``SegmentProcessor.lowerables``
— abstract avals only, nothing runs) and statically audits the
compiled artifact:

- ``compiled.memory_analysis()`` / ``cost_analysis()`` for bytes
  accessed, argument/output/temp footprints and aliased bytes;
- the ``input_output_alias`` table, to prove ``donate_argnums`` was
  **honored** by XLA and not silently dropped — jax only aliases a
  donated input to an output with an *identical aval*, so a donated
  buffer with no shape-matching output is a structural no-op (the
  silent failure mode the canonical staged boundary in
  pipeline/segment.py exists to eliminate);
- an HLO-text walk flagging f64/c128 ops, host callbacks
  (``custom-call`` to callback targets), collectives, infeed/outfeed,
  and entry-level ``copy``/``transpose`` ops;
- a structural count of **spectrum-sized HBM round trips**: every
  entry-computation instruction's operand and result buffers, in units
  of one spectrum (``8 * n_spectrum`` bytes).  Buffers inside a fusion
  stay in registers/VMEM, so entry-level granularity approximates what
  actually crosses HBM; the count is pinned exactly in the baseline so
  *any* newly materialized spectrum-sized pass fails CI.

Each plan emits a JSON "plan card"; cards diff against the checked-in
``srtb_tpu/analysis/plan_cards.json`` with the same re-baseline
workflow as srtb-lint (``--write-baseline`` keeps notes).  Driver:
``python -m srtb_tpu.tools.plan_audit`` (new ci.sh stage).

Counts are deterministic for a fixed jax/XLA version and audit shape;
the baseline records both.  The audit runs the CPU backend's pipeline
— TPU fusion differs in *degree* (it fuses more, never less at entry
level), so the CPU count is itself an upper-ish floor check, and the
regression gate is the exact pinned value, not a cross-backend truth.
"""

from __future__ import annotations

import contextlib
import json
import os
import re

from srtb_tpu.pipeline import registry

# ------------------------------------------------------------------
# plan families: enumerated from the ONE plan-family registry
# (pipeline/registry.py) — this module keeps NO family list of its
# own, so the auditable zoo, the demotion ladder and the fleet's plan
# cache can never drift apart.  ``PlanSpec`` is the registry's
# dataclass (the pre-registry name, kept for importers), and the
# module attributes PLAN_FAMILIES / PLAN_KEYS are LIVE views so a
# ``registry.temp_family`` registration (tests, the selftest) is
# visible here too.

PlanSpec = registry.PlanFamily


def __getattr__(name: str):
    if name == "PLAN_FAMILIES":
        return registry.plan_families()
    if name == "PLAN_KEYS":
        return registry.plan_keys()
    raise AttributeError(name)

DEFAULT_BASELINE = os.path.join(os.path.dirname(__file__),
                                "plan_cards.json")

# audit shape defaults: ci.sh stage-7's fused-parity shape — every
# family (incl. skzap's VMEM row window) is live and lowers in ~1 s
DEFAULT_LOG2N = 16
DEFAULT_CHANNELS = 8


def _audit_config(log2n: int, channels: int, overrides: dict):
    from srtb_tpu.config import Config
    base = dict(
        baseband_input_count=1 << log2n, baseband_input_bits=2,
        baseband_format_type="simple", baseband_freq_low=1405.0,
        baseband_bandwidth=64.0, baseband_sample_rate=128e6, dm=30.0,
        spectrum_channel_count=channels,
        mitigate_rfi_average_method_threshold=25.0,
        mitigate_rfi_spectral_kurtosis_threshold=1.05,
        signal_detect_signal_noise_threshold=5.0,
        signal_detect_max_boxcar_length=8,
        mitigate_rfi_freq_list="1410-1412",
        baseband_reserve_sample=False)
    base.update(overrides)
    return Config(**base)


@contextlib.contextmanager
def _env(overrides: dict):
    saved = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def build_plan(spec: PlanSpec, log2n: int = DEFAULT_LOG2N,
               channels: int = DEFAULT_CHANNELS):
    """Construct the segment processor for one plan family at the
    audit shape (device constants are built, but no plan program
    runs).  Built through the registry, so a family whose config
    selects a registered search mode (``search_mode``) audits that
    mode's actual processor class."""
    cfg = _audit_config(log2n, channels, spec.cfg)
    with _env(spec.env):
        return registry.build_processor(cfg, staged=spec.staged,
                                        donate_input=spec.donate)


# ------------------------------------------------------------------
# HLO-text structural analysis

_SHAPE_RE = re.compile(r"\b([a-z]+[0-9]+|pred)\[([0-9,]*)\]")
_INSTR_RE = re.compile(r"(?:ROOT )?%?[\w.\-]+ = (.*)")
_OP_RE = re.compile(r"\)?\}?\s*([a-z][a-z0-9\-]*)\(")
_ENTRY_RE = re.compile(r"^ENTRY [^\n]*\{$(.*?)^\}", re.M | re.S)
# the alias table nests one brace level per entry ("{0}: (0, {},
# may-alias), {1}: ..."), so the body match must admit inner braces — a
# lazy .*? would stop at the first entry's "{}" and silently drop every
# later aliased parameter
_ALIAS_RE = re.compile(
    r"input_output_alias=\{((?:[^{}]|\{[^{}]*\})*)\}")
_ALIAS_ENTRY_RE = re.compile(r"\{[0-9, ]*\}:\s*\((\d+)")
_CC_RE = re.compile(r'custom_call_target="([^"]+)"')

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1,
                "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
                "s32": 4, "u32": 4, "f32": 4,
                "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16}

# ops that move no HBM bytes of their own (aliases, metadata, scalars)
_NO_TRAFFIC_OPS = frozenset((
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "iota", "opt-barrier", "after-all", "partition-id", "replica-id"))

_COLLECTIVE_OPS = frozenset((
    "all-gather", "all-reduce", "all-to-all", "collective-permute",
    "collective-broadcast", "reduce-scatter", "all-gather-start",
    "all-reduce-start"))

_HOST_TRANSFER_OPS = frozenset((
    "infeed", "outfeed", "send", "recv", "send-done", "recv-done"))

# custom-call targets that re-enter Python / the host mid-program
_CALLBACK_MARKERS = ("callback", "py_func", "host")


def _shape_units(text: str, unit: int) -> int:
    """Total buffer traffic of one instruction line, in spectrum units
    (integer floor per buffer: sub-spectrum buffers count 0)."""
    units = 0
    for dt, dims in _SHAPE_RE.findall(text):
        nelem = 1
        for d in dims.split(","):
            if d:
                nelem *= int(d)
        units += (nelem * _DTYPE_BYTES.get(dt, 4)) // unit
    return units


def analyze_hlo(txt: str, spectrum_bytes: int) -> dict:
    """Structural audit of one compiled module's HLO text."""
    m = _ENTRY_RE.search(txt)
    body = m.group(1) if m else txt
    passes = copies = transposes = 0
    collectives: list[str] = []
    host_transfers: list[str] = []
    for line in body.splitlines():
        im = _INSTR_RE.match(line.strip())
        if not im:
            continue
        rest = im.group(1)
        om = _OP_RE.search(rest)
        op = om.group(1) if om else ""
        if op in _NO_TRAFFIC_OPS:
            continue
        if op == "copy":
            copies += 1
        elif op == "transpose":
            transposes += 1
        if op in _COLLECTIVE_OPS:
            collectives.append(op)
        if op in _HOST_TRANSFER_OPS:
            host_transfers.append(op)
        passes += _shape_units(rest, spectrum_bytes)
    custom_calls = sorted(set(_CC_RE.findall(txt)))
    callbacks = [c for c in custom_calls
                 if any(s in c.lower() for s in _CALLBACK_MARKERS)]
    # whole-module dtype scan: f64/c128 anywhere (incl. fusion bodies)
    # means a 64-bit op survived lowering — the drift srtb-lint's
    # dtype-drift rule guards at source level, proven here at HLO level
    f64_ops = len(re.findall(r"\bf64\[", txt))
    c128_ops = len(re.findall(r"\bc128\[", txt))
    am = _ALIAS_RE.search(txt)
    aliased_params = (sorted({int(p) for p in
                              _ALIAS_ENTRY_RE.findall(am.group(1))})
                      if am else [])
    return {
        "spectrum_passes": passes,
        "entry_copies": copies,
        "entry_transposes": transposes,
        "collectives": sorted(set(collectives)),
        "host_transfer_ops": sorted(set(host_transfers)),
        "custom_calls": custom_calls,
        "host_callbacks": callbacks,
        "f64_ops": f64_ops,
        "c128_ops": c128_ops,
        "aliased_params": aliased_params,
    }


# ------------------------------------------------------------------
# program + plan audits


def _flat_param_index(args, pos: int) -> int | None:
    """Flattened HLO parameter number of positional python arg ``pos``
    (None args contribute no leaves)."""
    import jax
    idx = 0
    for i, a in enumerate(args):
        leaves = len(jax.tree_util.tree_leaves(a))
        if i == pos:
            return idx if leaves else None
        idx += leaves
    return None


def audit_program(jit_fn, args, donated: tuple, spectrum_bytes: int,
                  keep_text: bool = False) -> dict:
    """AOT-lower + compile one jitted program and audit the artifact.
    Nothing executes; ``args`` are ShapeDtypeStructs (or None)."""
    import jax

    lowered = jit_fn.lower(*args)
    compiled = lowered.compile()
    txt = compiled.as_text()
    audit = analyze_hlo(txt, spectrum_bytes)

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    ca = ca or {}

    out_avals = [(tuple(a.shape), str(a.dtype)) for a in
                 jax.tree_util.tree_leaves(jax.eval_shape(jit_fn, *args))]
    declared, aliased, dropped, no_candidate = [], [], [], []
    for pos in donated:
        p = _flat_param_index(args, pos)
        if p is None:
            continue
        declared.append(p)
        leaf = jax.tree_util.tree_leaves(args[pos])[0]
        in_aval = (tuple(leaf.shape), str(leaf.dtype))
        if p in audit["aliased_params"]:
            aliased.append(p)
        elif in_aval in out_avals:
            # an identically-shaped output existed and XLA still did
            # not alias it — a genuinely dropped donation (regression)
            dropped.append(p)
        else:
            # structurally unusable: no output shares the donated aval,
            # so jax warns "donated buffers were not usable" and the
            # donation is a no-op by construction.  Recorded, not
            # failed: the raw uint8 input can never alias f32 outputs.
            no_candidate.append(p)

    card = {
        "spectrum_passes": audit["spectrum_passes"],
        "entry_copies": audit["entry_copies"],
        "entry_transposes": audit["entry_transposes"],
        "collectives": audit["collectives"],
        "host_transfer_ops": audit["host_transfer_ops"],
        "custom_calls": audit["custom_calls"],
        "host_callbacks": audit["host_callbacks"],
        "f64_ops": audit["f64_ops"],
        "c128_ops": audit["c128_ops"],
        "donation": {"declared": declared, "aliased": aliased,
                     "dropped": dropped, "no_candidate": no_candidate},
        "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0)),
        # informational (vary with jax/XLA build; excluded from diff)
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
        "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
        "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
    }
    if keep_text:
        card["hlo_text"] = txt
    return card


def audit_processor(proc, keep_text: bool = False) -> dict:
    """Plan card for one constructed SegmentProcessor: per-program
    audits + plan-level invariant checks."""
    spectrum_bytes = 8 * proc.n_spectrum
    programs = {}
    for name, fn, args, donated in proc.lowerables():
        programs[name] = audit_program(fn, args, donated, spectrum_bytes,
                                       keep_text=keep_text)
    total_passes = sum(p["spectrum_passes"] for p in programs.values())
    ring = bool(getattr(proc, "ring", False))
    # the warm assemble programs whose carry (flat param 0) MUST alias:
    # a dropped/no_candidate carry donation means every warm dispatch
    # allocates a fresh reserved-tail buffer — the exact silent
    # regression the ring-v1 gate exists to catch
    warm_names = ("ring", "stage_a_ring", "batch_ring")
    warm_progs = {n: p for n, p in programs.items() if n in warm_names}
    checks = {
        # no donation may be dropped while a matching output existed
        "donation_ok": all(not p["donation"]["dropped"]
                           for p in programs.values()),
        # single-chip plans must be free of host round trips and
        # cross-chip transfers
        "transfer_free": all(
            not p["host_callbacks"] and not p["collectives"]
            and not p["host_transfer_ops"] for p in programs.values()),
        "dtype_clean": all(p["f64_ops"] == 0 and p["c128_ops"] == 0
                           for p in programs.values()),
        # ring-v1: the carry donation is a proven alias on EVERY warm
        # assemble program (and those programs exist when the ring is
        # resolved on); vacuously true for direct-ingest plans
        "ring_alias_ok": (not ring or (
            bool(warm_progs) and all(
                0 in p["donation"]["aliased"] and p["alias_bytes"] > 0
                for p in warm_progs.values()))),
    }
    return {
        "plan_name": proc.plan_name,
        "fused_tail": bool(proc.fused_tail),
        "staged": bool(proc.staged),
        "ingest": "ring-v1" if ring else "direct",
        "reserved_bytes": int(getattr(proc, "reserved_bytes", 0)),
        "n_spectrum": proc.n_spectrum,
        "programs": programs,
        "total_spectrum_passes": total_passes,
        "checks": checks,
    }


def audit_families(keys=None, log2n: int = DEFAULT_LOG2N,
                   channels: int = DEFAULT_CHANNELS) -> dict:
    """Cards for the requested plan families (default: every family
    in the registry)."""
    specs = {s.key: s for s in registry.plan_families()}
    keys = list(keys) if keys else list(registry.plan_keys())
    cards = {}
    for k in keys:
        if k not in specs:
            raise KeyError(
                f"unknown plan family {k!r} "
                f"(known: {', '.join(registry.plan_keys())})")
        spec = specs[k]
        with _env(spec.env):
            proc = build_plan(spec, log2n=log2n, channels=channels)
            card = audit_processor(proc)
        card["audit_shape"] = {"log2n": log2n, "channels": channels}
        card["mode"] = spec.mode
        cards[k] = card
    return cards


# ------------------------------------------------------------------
# baseline + diff (same accept/re-baseline workflow as srtb-lint)

# per-program fields whose exact values are pinned; everything else in
# the card is informational context
_DIFF_PROGRAM_KEYS = (
    "spectrum_passes", "entry_copies", "entry_transposes", "collectives",
    "host_transfer_ops", "custom_calls", "host_callbacks", "f64_ops",
    "c128_ops", "donation", "alias_bytes")
_DIFF_PLAN_KEYS = ("plan_name", "fused_tail", "staged", "ingest",
                   "reserved_bytes", "mode", "total_spectrum_passes",
                   "checks")


def stable_view(card: dict) -> dict:
    """The baseline-pinned subset of one plan card."""
    view = {k: card[k] for k in _DIFF_PLAN_KEYS if k in card}
    view["programs"] = {
        name: {k: prog[k] for k in _DIFF_PROGRAM_KEYS if k in prog}
        for name, prog in card.get("programs", {}).items()}
    return view


class CardBaseline:
    """Checked-in plan cards + per-plan acceptance notes."""

    def __init__(self, data: dict | None = None):
        data = data or {}
        self.cards: dict = data.get("cards", {})
        self.notes: dict = data.get("notes", {})

    @classmethod
    def load(cls, path: str) -> "CardBaseline":
        if not path or not os.path.exists(path):
            return cls()
        with open(path) as f:
            return cls(json.load(f))

    def save(self, path: str) -> None:
        import jax
        out = {"version": 1, "jax": jax.__version__,
               "cards": self.cards, "notes": self.notes}
        with open(path, "w") as f:
            json.dump(out, f, indent=2, sort_keys=True)
            f.write("\n")

    @classmethod
    def from_cards(cls, cards: dict,
                   old: "CardBaseline | None" = None) -> "CardBaseline":
        b = cls()
        b.cards = {k: stable_view(c) for k, c in cards.items()}
        if old is not None:  # carry notes forward across rewrites
            b.notes = {k: n for k, n in old.notes.items() if k in b.cards}
        return b


def _walk_diff(path: str, base, cur, out: list) -> None:
    if isinstance(base, dict) and isinstance(cur, dict):
        for k in sorted(set(base) | set(cur)):
            _walk_diff(f"{path}.{k}" if path else k,
                       base.get(k), cur.get(k), out)
    elif base != cur:
        out.append(f"{path}: baseline {base!r} -> audited {cur!r}")


def diff_cards(cards: dict, baseline: CardBaseline):
    """(regressions, new_plans, stale_plans): exact-match diff of the
    stable card subset against the baseline."""
    regressions: list[str] = []
    new_plans: list[str] = []
    for key, card in cards.items():
        cur = stable_view(card)
        if key not in baseline.cards:
            new_plans.append(key)
            continue
        plan_diffs: list[str] = []
        _walk_diff("", baseline.cards[key], cur, plan_diffs)
        regressions.extend(f"{key}: {d}" for d in plan_diffs)
    stale = sorted(k for k in baseline.cards if k not in cards)
    return regressions, new_plans, stale


def failed_checks(cards: dict) -> list:
    """Invariant violations (independent of any baseline)."""
    out = []
    for key, card in cards.items():
        for name, ok in sorted(card.get("checks", {}).items()):
            if not ok:
                out.append(f"{key}: check {name} failed")
    return out


# ------------------------------------------------------------------
# demotion-ladder target audit: the self-healing ladder must never
# demote into an unaudited plan family

# the fully-featured ladder base: every canonical demotion rung is
# live from here (search mode, micro-batch, ring, skzap, fused tail,
# staged, monolithic), so walking it exercises the ladder's whole
# range — including the periodicity mode's shed-the-mode-first rung
LADDER_AUDIT_CFG = {
    "fft_strategy": "four_step", "fused_tail": "on",
    "use_pallas": True, "use_pallas_sk": True,
    "micro_batch_segments": 2, "search_mode": "periodicity",
    "baseband_reserve_sample": True, "dm": 0.1,
}

def _plan_fingerprint(plan_name: str, ingest: str, staged: bool,
                      micro_batch: bool) -> tuple:
    return (str(plan_name), str(ingest), bool(staged),
            bool(micro_batch))


def _card_fingerprints(baseline: "CardBaseline") -> dict:
    """fingerprint -> [family keys] over the checked-in cards.  The
    fingerprint is (plan_name, ingest, staged, has-micro-batch):
    plan_name already encodes strategy + fused_tail + skzap + ring,
    and a micro-batching plan carries a "batch" program."""
    out: dict[tuple, list] = {}
    for key, card in baseline.cards.items():
        fp = _plan_fingerprint(
            card.get("plan_name", ""), card.get("ingest", "direct"),
            card.get("staged", False),
            "batch" in card.get("programs", {}))
        out.setdefault(fp, []).append(key)
    return out


def audit_ladder(baseline: "CardBaseline",
                 log2n: int = DEFAULT_LOG2N,
                 channels: int = DEFAULT_CHANNELS) -> list:
    """Check that EVERY demotion-ladder rung reachable from the
    fully-featured audit config resolves to a plan family already
    carded in the baseline AND registered as ladder-ELIGIBLE — the
    self-healing ladder (resilience/demote.py) must never land the
    run on an unaudited plan, nor on a family the registry declared
    off-limits as a demotion target (``PlanFamily.ladder=False``,
    e.g. the periodicity mode the ladder sheds, never enters).
    Returns failure strings (empty = every target is carded).

    Builds each rung's processor at the audit shape (constants only —
    nothing lowers or runs) and matches its resolved fingerprint
    against the baseline cards."""
    from srtb_tpu.resilience.demote import ladder_rungs

    cfg = _audit_config(log2n, channels, dict(LADDER_AUDIT_CFG))
    rungs = ladder_rungs(cfg)
    failures = []
    if not rungs:
        return ["ladder: no demotion rungs resolved from the "
                "fully-featured audit config (ladder dead?)"]
    fps = _card_fingerprints(baseline)
    for rung in rungs:
        proc = registry.build_processor(rung.cfg, staged=rung.staged,
                                        donate_input=True)
        mb = int(getattr(rung.cfg, "micro_batch_segments", 1) or 1)
        fp = _plan_fingerprint(proc.plan_name,
                               "ring-v1" if proc.ring else "direct",
                               proc.staged, mb > 1)
        keys = fps.get(fp, [])
        if not keys:
            failures.append(
                f"ladder: rung {rung.step!r} resolves to an UNAUDITED "
                f"plan (plan={fp[0]} ingest={fp[1]} staged={fp[2]} "
                f"micro_batch={fp[3]}) — card the family in "
                "plan_cards.json before the ladder may demote into it")
            continue
        fams = {k: registry.family(k) for k in keys}
        unregistered = sorted(k for k, f in fams.items() if f is None)
        if unregistered and not any(fams.values()):
            failures.append(
                f"ladder: rung {rung.step!r} lands on "
                f"{'/'.join(unregistered)}, carded but NOT in the "
                "registry — stale plan_cards.json entry (re-run "
                "--write-baseline)")
            continue
        if not any(f is not None and f.ladder for f in fams.values()):
            failures.append(
                f"ladder: rung {rung.step!r} lands on "
                f"{'/'.join(keys)}, registered ladder-INELIGIBLE "
                "(PlanFamily.ladder=False) — the ladder may shed such "
                "a family but never demote into it")
    return failures


# ------------------------------------------------------------------
# selftest: prove the auditor catches the regressions it exists for


def extra_pass_jit(proc):
    """The fused plan with a deliberately un-fusable extra
    spectrum-sized round trip appended: a cumulative sum along the time
    axis is a sequential scan XLA cannot fold into the producing
    kernel's elementwise epilogue, so the waterfall is re-read and a
    same-sized result re-written (a plain ``+ eps`` behind an
    optimization_barrier is NOT enough — XLA re-fuses it after the
    barrier is dropped).  Audit-only — never executed."""
    import jax
    import jax.numpy as jnp

    def f(raw, chirp_ri, chirp_w_ri=None):
        wf, res = proc._process(raw, chirp_ri, chirp_w_ri)
        return jnp.cumsum(wf, axis=-1), res
    return jax.jit(f)


def selftest(log2n: int = DEFAULT_LOG2N,
             channels: int = DEFAULT_CHANNELS) -> list:
    """Inject the two regression classes the CI gate must catch and
    verify each one moves the audited card.  Returns a list of failure
    strings (empty = the auditor is sharp)."""
    import jax

    failures = []
    spec = registry.family("four_step_ftail")
    proc = build_plan(spec, log2n=log2n, channels=channels)
    spectrum_bytes = 8 * proc.n_spectrum
    (name, fn, args, donated), = [p for p in proc.lowerables()
                                  if p[0] == "fused"]
    clean = audit_program(fn, args, donated, spectrum_bytes)
    dirty = audit_program(extra_pass_jit(proc), args, donated,
                          spectrum_bytes)
    gained = dirty["spectrum_passes"] - clean["spectrum_passes"]
    if gained < 2:
        failures.append(
            "extra-pass injection not caught: audited passes moved by "
            f"{gained} (expected >= 2: one read + one write)")

    sspec = registry.family("staged")
    sproc = build_plan(sspec, log2n=log2n, channels=channels)
    sbytes = 8 * sproc.n_spectrum
    progs = {p[0]: p for p in sproc.lowerables()}
    _, bfn, bargs, bdon = progs["stage_b"]
    honored = audit_program(bfn, bargs, bdon, sbytes)
    if not honored["donation"]["aliased"] or not honored["alias_bytes"]:
        failures.append(
            "staged stage_b donation NOT proven aliased in the clean "
            f"artifact: {honored['donation']} "
            f"alias_bytes={honored['alias_bytes']}")
    # deliberately disable donation via a non-donating wrapper: the
    # audited donation table must visibly lose the alias
    undonated = audit_program(jax.jit(sproc._stage_b), bargs, (), sbytes)
    if undonated["donation"]["declared"] or undonated["alias_bytes"]:
        failures.append(
            "donation-disabled injection not caught: non-donating "
            f"wrapper still audits as aliased: {undonated['donation']} "
            f"alias_bytes={undonated['alias_bytes']}")

    # ring-v1: the carry alias must be proven on the warm assemble
    # program, and a plan that loses it (non-donating wrapper again)
    # must fail the ring_alias_ok check
    rspec = registry.family("four_step_ftail_ring")
    rproc = build_plan(rspec, log2n=log2n, channels=channels)
    if not rproc.ring:
        failures.append("ring family resolved with the ring OFF "
                        "(audit shape reserves no tail?)")
        return failures
    rcard = audit_processor(rproc)
    if not rcard["checks"]["ring_alias_ok"]:
        failures.append(
            "clean ring plan fails ring_alias_ok: "
            f"{rcard['programs'].get('ring', {}).get('donation')}")
    rbytes = 8 * rproc.n_spectrum
    (_, _, rargs, _), = [p for p in rproc.lowerables()
                         if p[0] == "ring"]
    lost = audit_program(jax.jit(rproc._process_ring), rargs, (), rbytes)
    if lost["donation"]["declared"] or 0 in lost["donation"]["aliased"]:
        failures.append(
            "carry-donation-disabled injection not caught: the "
            f"non-donating assemble still audits aliased: "
            f"{lost['donation']}")

    # demotion-ladder gate: every rung must match the checked-in
    # baseline, and the gate must visibly fail against a baseline
    # with no cards (= every rung unaudited)
    checked_in = CardBaseline.load(DEFAULT_BASELINE)
    if checked_in.cards:
        ladder_problems = audit_ladder(checked_in, log2n=log2n,
                                       channels=channels)
        if ladder_problems:
            failures.append(
                "demotion-ladder targets do not all resolve to "
                "checked-in plan cards: " + "; ".join(ladder_problems))
    missing = audit_ladder(CardBaseline(), log2n=log2n,
                           channels=channels)
    if not missing:
        failures.append(
            "ladder-gate injection not caught: an EMPTY baseline "
            "still passes audit_ladder (the gate would never fire)")

    # registry gate: a plan family REGISTERED without a checked-in
    # plan card must fail the CI diff as unbaselined — registering a
    # new capability (a search mode, a plan variant) in
    # pipeline/registry.py is not done until its card is accepted
    with registry.temp_family(registry.PlanFamily(
            key="__selftest_uncarded",
            desc="selftest: registered but never carded",
            cfg={"fft_strategy": "four_step", "fused_tail": "on"},
            donate=True)):
        cards = audit_families(["__selftest_uncarded"], log2n=log2n,
                               channels=channels)
        _, new_plans, _ = diff_cards(cards, checked_in)
        if "__selftest_uncarded" not in new_plans:
            failures.append(
                "uncarded-family injection not caught: a family "
                "registered without a plan card did not surface as "
                "unbaselined (the registry gate would never fire)")
    return failures
