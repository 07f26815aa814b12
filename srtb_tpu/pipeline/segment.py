"""The fused segment processor.

The reference runs one OS thread per pipeline stage with bounded queues so
GPU kernels of consecutive segments overlap (ref: pipeline/framework/
pipe.hpp, src/main.cpp:125-272).  On TPU the idiomatic equivalent is a
**single jitted function for the whole device chain** — XLA fuses the
elementwise stages into the FFTs' epilogues and overlaps host transfers
with compute via async dispatch; the host-side stage structure survives
only around the device (reader -> processor -> writers).

Device chain (ref call stack: SURVEY.md §3.2):

  unpack (+window) -> R2C FFT (drop Nyquist) -> RFI s1 (avg-zap +
  normalize + manual zap) -> chirp multiply -> waterfall backward C2C ->
  RFI s2 (spectral kurtosis) -> signal detect (boxcar cascade)

Everything is batched over data streams (polarizations): shape [S, ...].
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from srtb_tpu.config import Config
from srtb_tpu.io import formats
from srtb_tpu.ops import dedisperse as dd
from srtb_tpu.ops import detect as det
from srtb_tpu.ops import fft as F
from srtb_tpu.ops import rfi
from srtb_tpu.ops import scopes as S
from srtb_tpu.ops import unpack as U
from srtb_tpu.ops import window as W
from srtb_tpu.utils import tracing
from srtb_tpu.utils.logging import log


def unpack_streams(raw: jnp.ndarray, variant: str, nbits: int,
                   window: jnp.ndarray | None) -> jnp.ndarray:
    """Dispatch to the right unpack kernel and stack the resulting data
    streams into [S, n] (ref dispatch: unpack_pipe.hpp:46-136, 392-413)."""
    streams = [U.unpack_stream(own, variant, nbits, window)
               for own in U.stream_bytes(raw, variant)]
    if len(streams) == 1:
        return streams[0][None, :]
    with jax.named_scope(S.UNPACK):
        return jnp.stack(streams)


# Segments at or above this sample count execute as three XLA programs
# instead of one fused program: a 2^30-sample segment's fused graph needs
# > 16 GB of HBM scratch on a v5e even with the four-step FFT (the two
# transposes + batched FFTs + Hermitian combine all overlap in one
# program's lifetime), while the staged plan frees each program's
# temporaries before the next starts and never materializes a chirp bank.
STAGED_MIN_N = 1 << 30

# Largest n_spectrum at which fused_tail="auto" turns fusion on for the
# BANKLESS plans (staged / use_pallas), whose epilogue generates the
# df64 chirp in-trace.  The anchored-Taylor evaluation is per-anchor
# cheap, but its per-element update still runs through ops/df64's
# EFT optimization_barriers, which block XLA fusion — a handful of
# spectrum-sized f32 intermediates materialize (~2 GB each at
# n_spectrum = 2^29).  Harmless through 2^27 (n = 2^28); past it the
# staged plan keeps the tail unfused and makes the chirp a block of
# channels at a time inside stage (c) (_stage_c_rows: the spelling a
# v5e runs at 2^30; fused_tail="on" overrides this gate and was never
# compiled for one at that size).  Bank plans are exempt: their chirp
# rides the precombined (c, cw) banks, no in-trace df64.
FUSED_TAIL_DF64_MAX_SPECTRUM = 1 << 27


# ---- pure-config plan-resolution predicates.  Single home shared by
# the SegmentProcessor resolvers below AND the demotion ladder's
# no-op-rung detection (resilience/demote.py): the ladder must skip a
# rung exactly when the feature would not resolve ON, and a hand-
# maintained mirror of these rules would silently drift.


def staged_resolves(cfg, staged: bool | None = None) -> bool:
    """Resolution of the staged-plan flag from config alone (the
    constructor's default when no explicit override is given) — the
    single home of the size rule, shared by the demotion ladder's
    rung predicates (pipeline/registry.py) and the fleet's pre-build
    lane validation."""
    if staged is not None:
        return staged
    return int(getattr(cfg, "baseband_input_count", 0) or 0) \
        >= STAGED_MIN_N


def ring_usable(cfg) -> bool:
    """Whether overlap-save reserves a non-empty, byte-aligned tail
    strictly smaller than the segment — the structural precondition of
    the ingest ring, independent of the ``ingest_ring`` mode knob."""
    from srtb_tpu.io import formats as _formats
    fmt = _formats.resolve(cfg.baseband_format_type)
    bits = abs(int(cfg.baseband_input_bits))
    nres = int(dd.nsamps_reserved(cfg))
    reserved = nres * bits // 8 * fmt.data_stream_count
    seg = cfg.segment_bytes(fmt.data_stream_count)
    return nres > 0 and (nres * bits) % 8 == 0 and 0 < reserved < seg


def refuse_overlong_reserve(cfg) -> None:
    """The detector trims ``nsamps_reserved // channel_count`` time
    samples off the waterfall's ``n / 2 / channel_count``; once that is
    all of them ``ops/detect.trimmed_length`` trims nothing and the
    dedispersion-corrupted tail is searched (every segment near a pulse
    fires).  ``SegmentProcessor`` refuses such a configuration here,
    before it makes the chirp bank."""
    n = int(cfg.baseband_input_count)
    channels = min(int(cfg.spectrum_channel_count), n // 2)
    reserved = int(dd.nsamps_reserved(cfg))
    time_samples = n // 2 // channels
    if reserved // channels < time_samples:
        return
    # the reserve is twice the sweep, rounded up to whole waterfall
    # columns: it stays under half the segment up to this DM
    sweep_per_dm = abs(dd.max_delay_time(
        cfg.baseband_freq_low, cfg.baseband_bandwidth, 1.0)
        * cfg.baseband_sample_rate)
    dm_max = (n // 2 - 2 * channels) / 2 / sweep_per_dm
    raise ValueError(
        f"dm {cfg.dm} reserves {reserved} of the {n} samples of a "
        f"segment (baseband_input_count): the detector would trim "
        f"{reserved // channels} of {time_samples} time samples, so it "
        f"trims none and searches the corrupted tail.  A segment of "
        f"this size serves |dm| up to {dm_max:.4g} at this band; use a "
        f"longer segment or baseband_reserve_sample 0")


def _stream_bytes_subbyte(cfg) -> bool:
    """Whether each stream's own bytes are sub-byte samples MSB first
    (``ops/unpack.stream_bytes``): what the blocked field planes of
    ``ops/fft.rfft_subbyte`` are made of."""
    fmt = formats.resolve(cfg.baseband_format_type)
    return (cfg.baseband_input_bits in (1, 2, 4) and fmt.unpack_variant
            in ("simple", "interleaved_samples_2"))


def _r2c_sample_bits(cfg) -> int:
    """The bits ``ops/fft.own_tail_shape`` is asked with: the samples'
    own where a stream's bytes go to the own transform as they lie
    (sub-byte samples MSB first as blocked field planes, a byte a
    sample dealt out as bytes), and 0, which it refuses, for anything
    else: wider samples, and sub-byte samples of a word-interleaved
    format, would be dealt out as floats, which a v5e read 2.6x dearer
    than the bytes and no plan runs (PERF.md section 6, PR 48)."""
    if _stream_bytes_subbyte(cfg):
        return cfg.baseband_input_bits
    fmt = formats.resolve(cfg.baseband_format_type)
    one_byte = U.one_byte_cast(fmt.unpack_variant, cfg.baseband_input_bits)
    return 8 if one_byte is not None else 0


def own_r2c_hostable(cfg, staged: bool) -> bool:
    """Whether this configuration's plan, given the strategy "pallas2",
    runs the repo's own transform whole (``ops/fft.own_spectrum``: two
    kernel passes and the post pass that carries RFI s1 and the chirp):
    a fused plan and not the staged one, a tail that may fold in
    (``fused_tail`` not off), a chirp bank to fold (``use_pallas`` plans
    keep none), one segment a program (the micro-batch and the fleet
    ``vmap`` it, which no chip run has measured), samples of a byte or
    less (``_r2c_sample_bits``) and a shape the kernels take
    (``ops/fft.own_tail_shape``).  Any other plan given
    "pallas2" runs the two passes with XLA's Hermitian post and tail,
    which a v5e read slower than XLA's R2C (PERF.md section 6, PR 43)."""
    batched = (int(getattr(cfg, "micro_batch_segments", 1) or 1) > 1
               or int(getattr(cfg, "fleet_batch_max", 0) or 0) > 1)
    return bool(
        not staged and not batched
        and not getattr(cfg, "use_pallas", False)
        and str(getattr(cfg, "fused_tail", "auto")).lower() != "off"
        and F.own_tail_shape(int(cfg.baseband_input_count),
                             _r2c_sample_bits(cfg)) is not None)


def segment_strategy(cfg, staged: bool) -> str:
    """The segment R2C's strategy for a configuration and its resolved
    ``staged`` flag: ``fft_strategy`` with "auto" resolved by
    ``ops/fft.resolve_strategy`` from what the configuration and the
    platform show (the segment's length, its samples' bits and streams,
    whether the plan would run the own transform whole, the backend, the
    chip's ``bytes_limit``).  The single home: the fused tail, the own
    transform, the plan's name and signature, the ladder's
    ``monolithic`` rung and ``_process`` all ask here, so "auto" names
    "pallas2" only for the plan that was measured."""
    strategy = getattr(cfg, "fft_strategy", "auto")
    if strategy != "auto":
        return strategy
    from srtb_tpu.utils import platform
    fmt = formats.resolve(cfg.baseband_format_type)
    return F.resolve_strategy(
        int(cfg.baseband_input_count), strategy,
        bits=_r2c_sample_bits(cfg), streams=fmt.data_stream_count,
        on_tpu=platform.on_accelerator(),
        bytes_limit=platform.device_bytes_limit(),
        own_plan=own_r2c_hostable(cfg, staged))


def fused_tail_resolves(cfg, staged: bool) -> bool:
    """Resolution of ``Config.fused_tail`` ("auto"/"on"/"off") for a
    plan with the given resolved ``staged`` flag (see
    SegmentProcessor._resolve_fused_tail for the rationale of each
    branch).  Raises on "on" with a monolithic, non-staged plan."""
    mode = str(getattr(cfg, "fused_tail", "auto")).lower()
    if mode not in ("auto", "on", "off"):
        raise ValueError(f"fused_tail must be auto/on/off, got {mode!r}")
    if mode == "off":
        return False
    n = int(cfg.baseband_input_count)
    hostable = staged or segment_strategy(cfg, staged) != "monolithic"
    if mode == "on":
        if not hostable:
            raise ValueError(
                "fused_tail=on requires a non-monolithic "
                "fft_strategy (the XLA R2C custom call cannot host "
                "the RFI/chirp epilogue)")
        return True
    if not hostable:
        return False
    bankless = staged or getattr(cfg, "use_pallas", False)
    return not (bankless and n // 2 > FUSED_TAIL_DF64_MAX_SPECTRUM)


class SegmentProcessor:
    """Builds and owns the jitted per-segment device function plus its
    precomputed constants (chirp, window, RFI mask, normalization).

    Execution plans:
    - **fused** (default): the whole device chain is one jitted program.
    - **staged** (n >= STAGED_MIN_N, or ``staged=True``): three jitted
      programs — (a) unpack + pack + four-step first half, (b) four-step
      second half + Hermitian post-process, (c) RFI + in-step df64 chirp
      + waterfall + detect.  The plain spelling (``+rows`` in the plan
      name: what a 2^30-sample segment resolves to) walks the boundary
      in blocks inside each program, see ``_stage_a_rows`` and below;
      the Pallas / fused-tail variants keep whole-plane stages.
      Boundaries are stacked (re, im) float32 in
      the CANONICAL shape [2, S, channel_count, watfft_len]: XLA only
      honors ``donate_argnums`` when an output aval exactly matches the
      donated input's aval, so every stage boundary (and the waterfall
      output) shares one aval — stage (b) and (c) genuinely alias their
      donated inputs instead of silently dropping the donation (the
      pre-canonical shapes [2, S, n2, n1] -> [2, S, m] never matched
      and XLA warned "donated buffers were not usable" on every staged
      dispatch).  The reshapes ride the producing/consuming kernels.
      ``python -m srtb_tpu.tools.plan_audit`` proves the aliasing
      statically per plan.
    """

    # registered search mode this class implements
    # (pipeline/registry.py): subclasses adding a search capability
    # override it, and it stamps plan_signature/plan_cache_key so
    # plans of different modes can never share an AOT entry or a
    # fleet plan-cache slot
    MODE = "single_pulse"

    def __init__(self, cfg: Config, window_name: str = W.DEFAULT_WINDOW,
                 compute_chirp_on_device: bool | None = None,
                 staged: bool | None = None,
                 donate_input: bool = False,
                 stage_timer: "tracing.StageTimer | None" = None):
        self.cfg = cfg
        # the owning pipeline's timer (None standing alone): the spans
        # this processor opens outside any segment go to it,
        # ``chirp_bank`` below and each program's ``first_dispatch``
        self.stage_timer = stage_timer
        self.fmt = formats.resolve(cfg.baseband_format_type)
        n = cfg.baseband_input_count
        if n & (n - 1):
            raise ValueError("baseband_input_count must be a power of 2")
        self.n = n
        self.n_spectrum = n // 2  # after R2C + drop-Nyquist
        self.channel_count = min(cfg.spectrum_channel_count, self.n_spectrum)
        self.watfft_len = self.n_spectrum // self.channel_count
        refuse_overlong_reserve(cfg)

        # ---- precomputed constants ----
        self._window_name = window_name  # enters plan_signature: the
        # window is a captured constant of the traced programs
        win = W.window_coefficients(window_name, n)
        self.window = None if win is None else jnp.asarray(win)
        # Simple-format sub-byte segments take the fused blocked-plane
        # R2C (ops/fft.rfft_subbyte) on the non-monolithic strategies:
        # unpack + pack + FFT with no sample-order interleave anywhere —
        # the sample-order composition materializes a [bytes, count]
        # layout that pads 32x on TPU.  Independent of use_pallas: the
        # unpack is XLA's on every plan.
        self._blocked_subbyte = (
            self.fmt.unpack_variant == "simple"
            and cfg.baseband_input_bits in (1, 2, 4))
        self.window_planes = None
        if self._blocked_subbyte and win is not None:
            self.window_planes = jnp.asarray(F.subbyte_window_planes(
                win, cfg.baseband_input_bits))
        # watfft-length window to divide out of the dynamic spectrum after
        # the backward C2C (ref: fft_pipe.hpp:346-359); zero edges already
        # sanitized to 1 by dewindow_coefficients
        wat_win = W.dewindow_coefficients(window_name, self.watfft_len)
        self.watfft_dewindow = None if wat_win is None \
            else jnp.asarray(wat_win)

        f_min, f_c, df = dd.spectrum_frequencies(cfg, self.n_spectrum)
        self.f_min, self.f_c, self.df = f_min, f_c, df
        self.staged = (self.n >= STAGED_MIN_N) if staged is None else staged
        # the segment R2C's strategy, "auto" resolved once: the plan's
        # name and what it traces cannot disagree
        self.strategy = segment_strategy(cfg, self.staged)
        # fused spectrum tail (Config.fused_tail): RFI s1 + chirp fold
        # into the forward FFT's final pass; resolved once so the plan
        # and its signature can never disagree
        self.fused_tail = self._resolve_fused_tail()
        # ... and where the segment R2C is the repo's own transform
        # whole (ops/fft.own_spectrum): the same question "auto" was
        # resolved with, so a plan "auto" names pallas2 always runs it
        self.own_tail = (self.strategy == "pallas2"
                         and own_r2c_hostable(cfg, self.staged))
        assert self.fused_tail or not self.own_tail
        if self.own_tail and win is not None and self.window_planes is None:
            # every stream goes to the own transform as 2p planes (a
            # sub-byte stream's blocked field planes, whole bytes dealt
            # out), and the window with them
            self.window_planes = jnp.asarray(F.window_planes(
                win, 2 * F.own_tail_shape(self.n, _r2c_sample_bits(cfg))[0]))
        from srtb_tpu.utils.metrics import metrics
        metrics.set("segment_r2c_own", int(self.own_tail))
        # the staged plan's default spelling walks its boundary in this
        # many blocks of rows (0: the whole-plane spellings)
        self.staged_rows = self._resolve_staged_rows()
        # the chirp crosses the host->device boundary as stacked (re, im)
        # float32 [2, n]: some TPU runtimes can't transfer complex buffers,
        # and split re/im is the natural VPU layout anyway; complex exists
        # only inside jit.  The staged plan never materializes a bank —
        # at n = 2^30 it would occupy 4 GB of HBM for the segment's whole
        # lifetime — and instead computes the df64 chirp inside stage (c).
        self.chirp_w = None  # chirp·twiddle precombined bank (fused tail)
        if self.staged or cfg.use_pallas:
            # staged and Pallas plans compute the chirp in-step; a
            # precomputed bank would sit dead in HBM (2 GB at n = 2^29)
            self.chirp = None
        else:
            if compute_chirp_on_device is None:
                compute_chirp_on_device = cfg.use_emulated_fp64
            # one span around what makes the bank, whichever arm: the
            # float64 phase on one host thread and its upload (14-29 s
            # at the cells' sizes) or the df64 program, then the fused
            # tail's precombination.  It ends when the bank is on the
            # device, so the uploads' and the programs' time is in it
            # and not in whatever first waits for them
            with tracing.span("chirp_bank", stage_timer):
                if compute_chirp_on_device:
                    self.chirp = jax.jit(
                        lambda: dd.chirp_factor_df64_ri(
                            self.n_spectrum, f_min, df, f_c, cfg.dm,
                            exact=getattr(cfg, "chirp_exact", False)))()
                else:
                    self.chirp = jnp.asarray(dd.chirp_factor_host_ri(
                        self.n_spectrum, f_min, df, f_c, cfg.dm))
                if self.fused_tail:
                    # chirp·twiddle precombination: cw = chirp · w folds
                    # the Hermitian twiddle into the bank once, so the
                    # fused final pass costs one complex mul per bin and
                    # zero in-trace trig (explicit arg, not a closure
                    # capture — a captured 2 GB bank would bake into the
                    # program)
                    self.chirp_w = jax.jit(self._premul_bank)(self.chirp)
                if self.own_tail:
                    from srtb_tpu.ops import pallas_fft2 as pf2
                    self.chirp_w = jax.jit(pf2.post_bank)(
                        self.chirp, self.chirp_w)
                jax.block_until_ready((self.chirp, self.chirp_w))

        zap_ranges = rfi.eval_rfi_ranges(cfg.mitigate_rfi_freq_list)
        if self.staged_rows or self.own_tail:
            # a block compares the zap ranges with its own bin indices:
            # no mask the spectrum's size (0.5 GB at 2^29 bins) is made
            self.rfi_bins = rfi.rfi_ranges_to_bins(
                zap_ranges, self.n_spectrum, cfg.baseband_freq_low,
                cfg.baseband_bandwidth)
            mask = None
        else:
            mask = rfi.rfi_ranges_to_mask(
                zap_ranges, self.n_spectrum,
                cfg.baseband_freq_low, cfg.baseband_bandwidth)
        self.rfi_mask = None if mask is None else jnp.asarray(mask)

        self.norm_coeff = rfi.normalization_coefficient(
            self.n_spectrum, self.channel_count)

        self.nsamps_reserved = dd.nsamps_reserved(cfg)
        # trim of the waterfall time axis (ref: signal_detect_pipe.hpp:289-299)
        self.time_reserved_count = self.nsamps_reserved // self.channel_count

        # ---- incremental H2D overlap-save ring (Config.ingest_ring) ----
        # Overlap-save re-processes the reserved tail of every segment,
        # so a full-segment upload re-transmits bytes that are already
        # device-resident from one segment ago.  The ring keeps that
        # tail on the device as a raw-byte CARRY: each warm dispatch
        # uploads only the stride's new bytes and a jitted assemble step
        # concatenates carry ++ new into the full segment while emitting
        # the next carry with an IDENTICAL aval (uint8[reserved_bytes]
        # in -> uint8[reserved_bytes] out) — XLA only honors donation on
        # an exact aval match (the PR 7 lesson), so the carry donation
        # is a *proven* input->output alias, checked per plan by the
        # plan-audit gate (analysis/hlo_audit.py ring families).
        self._segment_bytes = cfg.segment_bytes(self.fmt.data_stream_count)
        self.reserved_bytes = int(
            self.nsamps_reserved * abs(cfg.baseband_input_bits) // 8
            * self.fmt.data_stream_count)
        self.stride_bytes = self._segment_bytes - self.reserved_bytes
        self.ring = self._resolve_ring()
        # the staged ring hands stage (a) the bytes as whole rows of its
        # ``[T, bytes a row]`` view (0: flat bytes, joined in the program)
        self.ring_row_bytes = self._resolve_ring_rows()

        # Pallas kernels need interpret mode off-TPU (CPU CI)
        from srtb_tpu.utils.platform import on_accelerator
        self._pallas_interpret = not on_accelerator()
        # fully-fused waterfall tail (pf.fft_rows_skzap_ri): C2C +
        # de-window + SK decision + zap + time series in ONE kernel —
        # requires the fused tail, both Pallas knobs, and rows that fit
        # the VMEM row-FFT window
        from srtb_tpu.ops import pallas_fft as _pf
        self._skzap = bool(
            self.fused_tail and cfg.use_pallas and cfg.use_pallas_sk
            and _pf.supported(self.watfft_len, self.channel_count))
        # XLA FFT row-length cap override (Config.fft_len_cap; None =
        # the ops/fft default), threaded through every FFT entry point
        self._len_cap = cfg.fft_len_cap or None
        # Input donation (async engine): every segment's raw byte array
        # is a fresh device_put the caller never reuses, so donating it
        # lets XLA recycle that HBM as program scratch — steady-state
        # streaming does no net fresh device allocation per segment.
        # Off by default: external callers (A/B tests) legally
        # reuse one device-resident input across calls, which donation
        # would invalidate.
        self._donate_input = bool(donate_input)
        # runtime sanitizer (Config.sanitize): per-stage NaN tripwires
        # + boundary contracts + explicit expiry of donated inputs.
        # Not part of plan_signature: it changes call sequencing only,
        # never the traced programs.
        self._sanitize = bool(getattr(cfg, "sanitize", False))
        in_donate = (0,) if self._donate_input else ()
        self._jit_process = jax.jit(self._process, donate_argnums=in_donate)
        self._jit_process_batch = None  # built lazily (micro-batch mode)
        if self.staged:
            # natural (pre-canonicalization) shape of the stage (a)
            # intermediate, recovered inside stage (b) by a fused
            # metadata reshape (abstract trace only — no compile, no run)
            expected = cfg.segment_bytes(self.fmt.data_stream_count)
            self._a_nat_shape = jax.eval_shape(
                self._stage_a_nat,
                jax.ShapeDtypeStruct((expected,), jnp.uint8)).shape
        self._jit_stage_a = jax.jit(self._stage_a, donate_argnums=in_donate)
        # the staged intermediates are consumed exactly once, so stages
        # donate their inputs — and because every boundary shares the
        # canonical aval (see class docstring) the donation is a REAL
        # input->output alias, not a dropped request: the 4 GB boundary
        # array of a 2^30 segment is reused in place instead of staying
        # live across the next program's entire temp footprint (the
        # chain ResourceExhausted at runtime without it even though each
        # program compiled within budget)
        self._jit_stage_b = jax.jit(self._stage_b, donate_argnums=(0,))
        self._jit_stage_c = jax.jit(self._stage_c, donate_argnums=(0,))
        # ring plan variants.  The carry (arg 0) is ALWAYS donated: it
        # is a ring-owned intermediate consumed exactly once per step
        # (callers receive the next carry in exchange), and its output
        # twin shares the exact aval so the donation is a real alias —
        # the reserved-bytes buffer is rewritten in place every segment
        # instead of accreting one fresh HBM allocation per dispatch.
        # The stride input rides the caller's donate_input policy (it
        # can never alias an output — recorded as no_candidate).
        self._jit_ring = None
        self._jit_cold = None
        self._jit_stage_a_ring = None
        self._jit_stage_a_cold = None
        self._jit_batch_ring = None
        self._jit_batch_cold = None
        if self.ring:
            ring_donate = (0,) + ((1,) if self._donate_input else ())
            if self.staged:
                # the carry alone: stage (a) returns the boundary
                # (float32, four times the segment's bytes) and the
                # next carry, so the stride's bytes can alias nothing
                # and their donation was dropped at every compile
                # ("Some donated buffers were not usable")
                self._jit_stage_a_ring = jax.jit(
                    self._stage_a_ring, donate_argnums=(0,))
                self._jit_stage_a_cold = jax.jit(
                    self._stage_a_cold, donate_argnums=in_donate)
            else:
                self._jit_ring = jax.jit(self._process_ring,
                                         donate_argnums=ring_donate)
                self._jit_cold = jax.jit(self._process_cold,
                                         donate_argnums=in_donate)
        # host staging-buffer pool: when stage_input/stack_batch must
        # materialize a contiguous uint8 copy (non-contiguous or
        # non-uint8 input, micro-batch stacking), the bytes land in a
        # pooled buffer sized by the plan's segment/stride byte counts
        # instead of a fresh allocation per segment.  Buffers register
        # against the owning segment's host buffer and return to the
        # pool when the segment drains (Pipeline calls release_staging);
        # the FIFO cap self-heals callers that never release.
        from srtb_tpu.utils.bufferpool import BufferPool
        self._staging_pool = BufferPool("staging")
        self._staging_out: "dict[int, tuple]" = {}
        self._staging_cap = 2 * max(
            1, int(getattr(cfg, "inflight_segments", 2) or 1)) + 4
        # performance-observatory compile accounting (always-on): the
        # lazy-jit protocol traces+compiles inside the FIRST dispatch
        # of each program, so that call's wall clock is the live
        # compile measurement (an upper bound — it includes the first
        # execution's dispatch; the AOT protocol measures exactly in
        # aot_cache.get_or_compile instead).  Per-stream labeled twins
        # when this processor serves a named fleet lane.
        # program family -> the seconds of its first dispatch (0.0
        # where the AOT cache had compiled it: marked, not counted)
        self.first_dispatch_s: dict[str, float] = {}
        # host seconds of the staged plan's three jit calls, by span
        self._stage_spans: dict[str, float] = {}
        self._metric_labels = ({"stream": cfg.stream_name}
                               if getattr(cfg, "stream_name", "")
                               else None)
        self.aot_active = False
        if cfg.aot_plan_path:
            if not self.enable_aot(cfg.aot_plan_path):
                # visible, not debug: the config requested warm-restart
                # protection and it did NOT activate
                log.warning(
                    "[segment] aot_plan_path set but the AOT cache is "
                    "inactive (CPU backend without SRTB_AOT_ALLOW_CPU=1)"
                    " — restarts will recompile")
        log.debug(f"[segment] n={n} spectrum={self.n_spectrum} "
                  f"channels={self.channel_count} watfft={self.watfft_len} "
                  f"reserved={self.nsamps_reserved} plan={self.plan_name}")

    # ------------------------------------------------------------------
    # fused spectrum tail: plan resolution + the epilogue itself

    def _resolve_fused_tail(self) -> bool:
        """Resolve Config.fused_tail ("auto"/"on"/"off") against the
        plan: the staged plan and every non-monolithic strategy end in
        the Hermitian post-process, which can host the RFI-s1 + chirp
        epilogue; the monolithic XLA R2C custom call cannot and stays
        the unfused fallback under "auto".  Under "auto", bankless
        plans (staged / use_pallas, in-trace df64 chirp) additionally
        gate on the proven size range
        (FUSED_TAIL_DF64_MAX_SPECTRUM); "on" overrides for the
        hardware experiments.  The rule itself lives in the module-
        level :func:`fused_tail_resolves` (shared with the demotion
        ladder)."""
        return fused_tail_resolves(self.cfg, self.staged)

    def _resolve_staged_rows(self) -> int:
        """How many blocks of rows the staged plan's stages (b) and (c)
        walk the canonical boundary ``[2, S, F, T]`` in
        (``_stage_b_rows``, ``_stage_c_rows``); 0 for the whole-plane
        spellings.  The plain staged plan (XLA legs, no fused tail, no
        Pallas kernel, no quality epilogue: what a 2^30-sample segment
        resolves to by itself) takes the blocks at every size: at 2^30
        the whole-plane Hermitian post and the whole-spectrum RFI s1 /
        df64 chirp / waterfall are each refused by one v5e (16.00 and
        19.00 GB of 15.75), and nothing else chooses between them, so
        the small forced-staged shapes the tests run are the same
        programs.  The variants that bring their own kernels or fold the
        tail into stage (b) keep their spellings."""
        cfg = self.cfg
        plain = (self.staged and not self.fused_tail
                 and not cfg.use_pallas and not cfg.use_pallas_sk
                 and not getattr(cfg, "quality_stats", False)
                 and self._staged_rows_impl == "xla"
                 and not self._staged_blocked
                 and self.channel_count * self.watfft_len
                 == self.n_spectrum)
        return F.block_count(self.channel_count, self.n_spectrum,
                             pairs=True) if plain else 0

    def _resolve_ring(self) -> bool:
        """Resolve Config.ingest_ring ("auto"/"on"/"off") against the
        plan: the ring needs a non-empty, byte-aligned reserved tail
        strictly smaller than the segment.  "auto" turns it on whenever
        overlap-save is active; "on" forces it (and errors when the
        config has nothing to carry); "off" restores full re-uploads."""
        mode = str(getattr(self.cfg, "ingest_ring", "auto")).lower()
        if mode not in ("auto", "on", "off"):
            raise ValueError(
                f"ingest_ring must be auto/on/off, got {mode!r}")
        if mode == "off":
            return False
        # the structural test is the shared module-level predicate
        # (the demotion ladder consults the same rule)
        usable = ring_usable(self.cfg)
        if mode == "on" and not usable:
            raise ValueError(
                "ingest_ring=on requires overlap-save with a byte-"
                "aligned reserved tail (baseband_reserve_sample with "
                f"0 < reserved_bytes < segment_bytes; got reserved="
                f"{self.reserved_bytes} of {self._segment_bytes})")
        return usable

    # ---- ring plan variants: carry ++ new assemble + carry emission.
    # The warm variants take (carry uint8[R], new uint8[stride]) and
    # return the plan outputs PLUS the next carry uint8[R] — the last
    # reserved_bytes of the assembled segment, emitted with the exact
    # aval of the donated carry input so XLA aliases the two buffers.
    # The cold variants take the full uint8[segment_bytes] upload and
    # also emit the carry, so a cold dispatch needs no extra H2D bytes
    # and no separate slice program to re-arm the ring.

    # Both halves of the ring's own work run under ``srtb.ring``
    # (ops/scopes.py): metadata only, no operation is added or moved.

    @staticmethod
    @S.scoped(S.RING)
    def _assemble(carry: jnp.ndarray, new: jnp.ndarray) -> jnp.ndarray:
        return jnp.concatenate([carry, new])

    @S.scoped(S.RING)
    def _next_carry(self, raw: jnp.ndarray) -> jnp.ndarray:
        return raw[self.stride_bytes:]

    def _process_ring(self, carry: jnp.ndarray, new: jnp.ndarray,
                      chirp_ri: jnp.ndarray, chirp_w_ri=None):
        raw = self._assemble(carry, new)
        out = self._process(raw, chirp_ri, chirp_w_ri)
        return out, self._next_carry(raw)

    def _process_cold(self, raw: jnp.ndarray, chirp_ri: jnp.ndarray,
                      chirp_w_ri=None):
        return (self._process(raw, chirp_ri, chirp_w_ri),
                self._next_carry(raw))

    def _resolve_ring_rows(self) -> int:
        """Bytes in a row of stage (a)'s ``[T, bytes a row]`` view of a
        segment where the staged ring assembles by strips, else 0.  The
        plain staged plan's stage (a) makes a block of boundary rows
        from a strip of columns of that view (``_stage_a_rows``), and
        the reserve is whole rows of it (``ops/dedisperse.nsamps_
        reserved`` keeps the stride a multiple of ``2 * channels``
        samples: 3994 rows of carry and 12390 of new bytes, 65536 bytes
        each, at 2^30 / 2^15 / DM 56.77).  So the carry and the new
        bytes cross to the device as ``[rows, bytes a row]`` and a block
        reads its strip from each: no ``u8[segment_bytes]`` join and no
        relayout of a whole segment's bytes (2 x 1.07 GB at 2^30 8-bit
        samples).  0 where stage (a) walks no blocks (several streams in
        one byte stream, the fused-tail and Pallas variants): those
        rings keep flat bytes and the whole-segment join."""
        if not (self.ring and self.staged and self._stage_a_block_rows()):
            return 0
        row = self.channel_count * 2 * abs(
            int(self.cfg.baseband_input_bits)) // 8
        if self.reserved_bytes % row:
            raise ValueError(
                f"the staged ring's reserve of {self.reserved_bytes} "
                f"bytes is no whole number of {row}-byte rows")
        return row

    def _ring_shape(self, nbytes: int) -> tuple:
        """``nbytes`` of a segment as the staged ring's programs take
        them: whole rows by strips, else flat."""
        row = self.ring_row_bytes
        return (nbytes // row, row) if row else (nbytes,)

    def _ring_aval(self, nbytes: int) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(self._ring_shape(nbytes), jnp.uint8)

    def _ring_rows(self, raw):
        """``raw`` in :meth:`_ring_shape` (host bytes are viewed; a
        device array already in that shape is itself)."""
        return raw.reshape(self._ring_shape(raw.size))

    def _stage_a_with_carry(self, *parts: jnp.ndarray):
        """Shared body of the staged ring variants: stage (a) — in
        whichever spelling the plan resolved — plus the next carry,
        from the segment's bytes in ``parts``: the carry and the new
        bytes (warm) or the whole upload (cold).  One home, so the
        warm/cold twins (and any future variant) cannot drift apart.
        By strips (``ring_row_bytes``) the parts are rows of stage
        (a)'s view, joined a strip at a time inside its loop, and the
        next carry is the last rows of the last part (the reserve is
        under half a segment: ``refuse_overlong_reserve``); else they
        are flat bytes, joined whole."""
        if self.ring_row_bytes:
            with jax.named_scope(S.RING):
                carry = parts[-1][-(self.reserved_bytes
                                    // self.ring_row_bytes):]
            return self._stage_a_rows(parts), carry
        raw = parts[0] if len(parts) == 1 else self._assemble(*parts)
        return self._stage_a(raw), self._next_carry(raw)

    def _stage_a_ring(self, carry: jnp.ndarray, new: jnp.ndarray):
        return self._stage_a_with_carry(carry, new)

    def _stage_a_cold(self, raw: jnp.ndarray):
        return self._stage_a_with_carry(raw)

    def _process_batch_ring(self, carry: jnp.ndarray, new_b: jnp.ndarray,
                            chirp_ri: jnp.ndarray, chirp_w_ri=None):
        """Micro-batch warm step: ONE carry plus B stride uploads
        reassemble B overlapped segments (raw_i starts at i*stride of
        carry ++ new_0 ++ ... ++ new_{B-1}); the next carry is the tail
        of the whole window, aliased onto the donated carry."""
        b = new_b.shape[0]
        seg = self._segment_bytes
        with jax.named_scope(S.RING):
            full = jnp.concatenate([carry, new_b.reshape(-1)])
            raws = jnp.stack([full[i * self.stride_bytes:
                                   i * self.stride_bytes + seg]
                              for i in range(b)])
        out = jax.vmap(self._process, in_axes=(0, None, None))(
            raws, chirp_ri, chirp_w_ri)
        with jax.named_scope(S.RING):
            return out, full[full.shape[0] - self.reserved_bytes:]

    def _process_batch_cold(self, raws: jnp.ndarray,
                            chirp_ri: jnp.ndarray, chirp_w_ri=None):
        out = jax.vmap(self._process, in_axes=(0, None, None))(
            raws, chirp_ri, chirp_w_ri)
        with jax.named_scope(S.RING):
            return out, raws[-1, self.stride_bytes:]

    @property
    def plan_name(self) -> str:
        """Human-readable plan id: base plan + resolved strategy
        + which fusions are live."""
        name = ("staged" if self.staged else "fused") + f":{self.strategy}"
        if self.staged_rows:
            name += "+rows"
        if self.fused_tail:
            name += "+ftail"
        if self._skzap:
            name += "+skzap"
        if self.ring:
            name += "+ring"
        return name

    @staticmethod
    def _premul_bank(c_ri: jnp.ndarray) -> jnp.ndarray:
        """cw = chirp · w with w the drop-Nyquist Hermitian twiddle
        exp(-2πik/n) — the chirp·twiddle precombination consumed by
        ops.fft.hermitian_rfft_post(premul=...)."""
        m = c_ri.shape[-1]
        w = F._iota_phase(m, 2 * m, -1.0)
        # (c_re + i c_im)(w_re + i w_im) on the stacked planes as they
        # lie, with no stack of two results: at 2^26 bins the chip's
        # compiler ends on a check failure there (fusion_emitter.cc,
        # IsFusibleUnalignedDUS; PERF.md section 6, PR 43)
        sign = jnp.asarray([[-1.0], [1.0]], c_ri.dtype)
        return c_ri * jnp.real(w) + (c_ri[::-1] * sign) * jnp.imag(w)

    def _tail_epilogue(self, chirp_ri):
        """The elementwise epilogue folded into the forward FFT's final
        pass: RFI stage-1 zap (mean power via the Parseval identity over
        the FFT's own input, rfi.mean_power_packed — no spectrum
        re-read) + normalize + manual mask, then the chirp.  With a bank
        (``chirp_ri`` given) the chirp was already applied through the
        precombined (c, cw) pair inside the Hermitian assembly — the
        zap/normalize commute with the unit-modulus multiply — so only
        the zap runs here; without one the df64 chirp (anchored-Taylor
        unless Config.chirp_exact) is generated in-trace and fuses into
        the same write."""
        cfg = self.cfg

        def epilogue(zf, spec):
            mean_power = rfi.mean_power_packed(zf)
            if chirp_ri is not None:
                # the pass that applied the chirp ends in ONE select,
                # under the chirp's name (a fusion reads as its root's):
                # the values of the two selects below, bit for bit
                zap = rfi.s1_zap(
                    spec, mean_power,
                    cfg.mitigate_rfi_average_method_threshold,
                    self.rfi_mask)
                with jax.named_scope(S.CHIRP):
                    return jnp.where(zap, jnp.zeros((), spec.dtype),
                                     spec * self.norm_coeff)
            spec = rfi.mitigate_rfi_s1_given_mean(
                spec, mean_power,
                cfg.mitigate_rfi_average_method_threshold,
                self.norm_coeff)
            spec = rfi.mitigate_rfi_manual(spec, self.rfi_mask)
            if chirp_ri is None:
                c_ri = dd.chirp_factor_df64_ri(
                    spec.shape[-1], self.f_min, self.df, self.f_c,
                    cfg.dm, exact=getattr(cfg, "chirp_exact", False))
                with jax.named_scope(S.CHIRP):
                    spec = spec * jax.lax.complex(c_ri[0], c_ri[1])
            return spec
        return epilogue

    # ------------------------------------------------------------------

    def _unpack(self, raw: jnp.ndarray) -> jnp.ndarray:
        """raw bytes -> windowed float32 samples [S, n]."""
        return unpack_streams(raw, self.fmt.unpack_variant,
                              self.cfg.baseband_input_bits, self.window)

    def _resolve_rows_impl(self, impl: str) -> str:
        """Single home of the off-TPU downgrade rule: 'pallas' runs the
        kernels in interpret mode on CPU backends.  Unknown names raise:
        a typo in ``fft_strategy`` must not silently fall back to XLA."""
        if impl not in ("xla", "four_step", "mxu", "monolithic", "auto",
                        "pallas", "pallas_interpret",
                        "pallas2", "pallas2_interpret"):
            raise ValueError(f"unknown rows impl / fft strategy {impl!r}")
        if impl in ("pallas", "pallas2") \
                and getattr(self, "_pallas_interpret", False):
            return impl + "_interpret"
        return impl

    def _process(self, raw: jnp.ndarray, chirp_ri: jnp.ndarray,
                 chirp_w_ri: jnp.ndarray = None):
        if self.own_tail:
            return self._process_own(raw, chirp_w_ri)
        strategy = self._resolve_rows_impl(self.strategy)
        epilogue = premul = None
        if self.fused_tail:
            epilogue = self._tail_epilogue(chirp_ri)
            if chirp_ri is not None:
                # bank plan: chirp·twiddle precombination inside the
                # Hermitian assembly (see _premul_bank)
                premul = (jax.lax.complex(chirp_ri[0], chirp_ri[1]),
                          jax.lax.complex(chirp_w_ri[0], chirp_w_ri[1]))
        if self._blocked_subbyte and strategy in ("four_step", "mxu",
                                                  "pallas",
                                                  "pallas_interpret",
                                                  "pallas2",
                                                  "pallas2_interpret"):
            spec = F.rfft_subbyte(raw, self.cfg.baseband_input_bits,
                                  strategy, self.window_planes,
                                  len_cap=self._len_cap,
                                  epilogue=epilogue,
                                  premul=premul)[None, :]
            return self._spectrum_to_results(spec, chirp_ri)

        def chain(x):                                   # x [1, n]
            return self._spectrum_to_results(F.segment_rfft(
                x, strategy, len_cap=self._len_cap,
                epilogue=epilogue, premul=premul), chirp_ri)
        own = U.stream_bytes(raw, self.fmt.unpack_variant)
        if len(own) == 1:
            return chain(self._unpack(raw))
        with jax.named_scope(S.UNPACK):
            own = jnp.stack(own)                        # [S, bytes]
        return self._stream_after_stream(
            lambda b: chain(U.unpack_stream(
                b, self.fmt.unpack_variant, self.cfg.baseband_input_bits,
                self.window)[None, :]), own)

    def _process_own(self, raw: jnp.ndarray, bank: jnp.ndarray):
        """The segment through the repo's own transform
        (``ops/fft.own_spectrum``: two kernel passes and the post pass
        with RFI s1, the manual zap and the chirp in it; ``bank`` is
        ``chirp_w``), a stream at a time.  A stream's sub-byte samples
        go to the kernels as the blocked field planes they unpack to,
        a byte a sample dealt out as bytes to 2p planes and cast plane
        by plane (``ops/fft.deal_planes``): the even/odd pack at p = 1,
        every fourth sample a plane at p = 2 (2^28 samples: the 1 GSa/s
        segment).  A window goes in as planes of the same deal
        (``window_planes``).  Wider samples never come here
        (``_r2c_sample_bits``)."""
        cfg = self.cfg
        bits = cfg.baseband_input_bits
        p, n1, n2 = F.own_tail_shape(self.n, _r2c_sample_bits(cfg))

        def results(planes):                            # [2p, M]
            spec = F.own_spectrum(
                planes, (n1, n2), bank,
                threshold=cfg.mitigate_rfi_average_method_threshold,
                norm=self.norm_coeff, bins=self.rfi_bins,
                interpret=self._pallas_interpret)
            return self._waterfall_detect(spec[None, :])

        def from_bytes(b):
            if _stream_bytes_subbyte(cfg):
                planes = U.unpack_subbyte_planes(b, bits)
            else:
                # a byte a sample: dealt out as bytes and cast plane by
                # plane, a quarter of the bytes the floats would move;
                # the cast under the R2C's name, where XLA's plan has it
                cast = U.one_byte_cast(self.fmt.unpack_variant, bits)
                with jax.named_scope(S.FFT_R2C):
                    planes = jnp.stack(
                        [cast(q) for q in F.deal_planes(b, 2 * p)])
            if self.window_planes is not None:
                with jax.named_scope(S.FFT_R2C):
                    planes = planes * self.window_planes
            return results(planes)
        own = U.stream_bytes(raw, self.fmt.unpack_variant)
        if len(own) == 1:
            return from_bytes(raw)
        with jax.named_scope(S.UNPACK):
            own = jnp.stack(own)                        # [S, bytes]
        return self._stream_after_stream(from_bytes, own)

    def _spectrum_to_results(self, spec: jnp.ndarray, chirp_ri):
        """From the R2C's spectrum ``[S, n/2]`` to the program's
        outputs."""
        if self.fused_tail:
            # the spectrum left the FFT already zapped/normalized/
            # masked/chirped — straight to the waterfall tail
            return self._waterfall_detect(spec)
        return self._spectrum_tail(spec, chirp_ri)

    @staticmethod
    def _stream_after_stream(chain, streams: jnp.ndarray):
        """A segment of several streams as one-stream chains one after
        the other inside the program: ONE loop over the leading axis of
        ``streams`` whose body is ``chain`` on one stream's row (the
        very shapes, and so the very transforms, of a one-stream
        deployment), the outputs joined once at the end — the
        waterfalls along their stream axis, every array of the detect
        result along its leading one.

        No transform receives the stream axis as a batch dimension:
        handed ``f32[2, 2^27]`` the chip's compiler puts the 2 into the
        minor tile and relays the stack out twice, and one batched
        waterfall C2C of ``[2 * 2048, 32768]`` costs 3.05x one stream's
        and 139 s of compile.  And a loop, not the chains traced one
        behind the other: with two segment-sized FFTs in one
        computation the compiler no longer carries the transform's
        layout back into a sub-byte unpack (it leaves ``f32[2^25, 4]``
        padded to 16 GB), and an unpack made to stand alone costs each
        R2C a relayout of its input: 144.3 ms a two-stream segment
        where the loop takes 135.4 and the batch took 155.4 (PERF.md
        section 6, PRs 36 and 38).  A caller that brings its batch by
        ``vmap`` (the micro-batch's segments, the fleet's beams) maps
        the loop as a whole."""
        static = []

        def body(x):
            wf_ri, result = chain(x)
            # the static fields (the boxcar lengths; ``quality`` when
            # it is off) are every stream's alike and stay outside
            static[:] = [type(result), *(
                None if isinstance(f, jax.Array) else (f,) for f in result)]
            return wf_ri, [f for f in result if isinstance(f, jax.Array)]
        # the loop's own work (a stream's row sliced out, each output
        # written into its stack) and the join read as the waterfall's;
        # every operation of the body keeps its stage's inner scope
        with jax.named_scope(S.WATERFALL):
            wf_ri, arrays = jax.lax.map(body, streams)   # [S, 2, 1, F, T]
            wf_ri = jnp.moveaxis(wf_ri[:, :, 0], 0, 1)
            arrays = iter(f.reshape(-1, *f.shape[2:]) for f in arrays)
            kind, *fields = static
            result = kind(*(next(arrays) if f is None else f[0]
                            for f in fields))
        return wf_ri, result

    # ---- staged plan: three programs with (re, im) f32 boundaries ----

    # The blocked-plane form inside the *staged* plan reproducibly
    # SIGSEGVs the XLA TPU compiler at the 2^30 production shape (the
    # fused blocked form through 2^28 and the classic staged form are
    # both fine) — keep the staged plan on the proven unpack+pack path
    # until that compiler crash is root-caused.  Flip for experiments
    # with SRTB_STAGED_BLOCKED=1.
    @property
    def _staged_blocked(self) -> bool:
        return self._blocked_subbyte and bool(
            int(os.environ.get("SRTB_STAGED_BLOCKED", "0")))

    @property
    def _staged_rows_impl(self) -> str:
        """Who runs the whole-plane staged stages' batched leg FFTs.
        Default XLA; SRTB_STAGED_ROWS_IMPL=pallas moves the legs to the
        VMEM row-FFT kernel (ops/pallas_fft; in interpret mode off the
        chip).  Any other value is an error that names the two."""
        impl = os.environ.get("SRTB_STAGED_ROWS_IMPL", "xla")
        if impl not in ("xla", "pallas"):
            raise ValueError(
                f"SRTB_STAGED_ROWS_IMPL={impl!r}: the staged plan's legs "
                f"are 'xla' or 'pallas'")
        return self._resolve_rows_impl(impl)

    @S.scoped(S.FFT_R2C)
    def _staged_pack(self, raw: jnp.ndarray) -> jnp.ndarray:
        """unpack + pack for the staged plan: blocked field-plane pairs
        [S, p, M] (sub-byte, lane-dense by construction) or even/odd
        packed [S, m]."""
        if self._staged_blocked:
            planes = U.unpack_subbyte_planes(
                raw, self.cfg.baseband_input_bits)
            if self.window_planes is not None:
                planes = planes * self.window_planes
            return F.subbyte_planes_to_packed(planes)[None]
        return F.pack_even_odd(self._unpack(raw))

    # The staged boundary CANONICAL aval: [2, S, channel_count,
    # watfft_len] float32.  Every stage consumes and produces this exact
    # shape so XLA's aval-matching donation rule can alias each donated
    # boundary to the stage's output (see the class docstring); the
    # reshapes to/from the stages' natural working shapes are metadata
    # remappings fused into the adjacent kernels' reads/writes — the
    # plan auditor's entry-level copy count is the regression tripwire
    # should a relayout ever materialize one as a real pass.

    def _boundary_canon(self, x: jnp.ndarray) -> jnp.ndarray:
        if self.channel_count * self.watfft_len == self.n_spectrum:
            return x.reshape(2, -1, self.channel_count, self.watfft_len)
        # non-dividing channel count: the waterfall row view truncates
        # the spectrum tail (spec[..., :F*T]), so [2, S, F, T] cannot
        # hold the full boundary — fall back to the flat canonical
        # [2, S, m].  stage (b) in==out still aliases; stage (c)'s
        # donation becomes a structural no_candidate (wf is smaller),
        # which the plan card records honestly.
        return x.reshape(2, -1, self.n_spectrum)

    def _stage_a(self, raw: jnp.ndarray):
        if self.staged_rows:
            return self._stage_a_rows((raw.reshape(self.watfft_len, -1),))
        return self._boundary_canon(self._stage_a_nat(raw))

    def _stage_b(self, a_ri):
        if self.staged_rows:
            return self._stage_b_rows(a_ri)
        return self._boundary_canon(
            self._stage_b_nat(a_ri.reshape(self._a_nat_shape)))

    def _enqueue_stage(self, key: str, fn, *args):
        """One of the staged plan's three jit calls, under a host span of
        its own: ``srtb:enqueue_a`` / ``_b`` / ``_c`` on the profiler's
        host plane, and the seconds kept for the segment's journal
        record (``take_stage_spans``: children of ``enqueue`` in
        ``stages_ms``), so a trace and a journal tell the three
        dispatches apart as the device plane's ``jit(_stage_a)`` ...
        tell the three programs apart."""
        with tracing.span(f"enqueue_{key}") as sp:
            out = fn(*args)
        self._stage_spans[sp.name] = \
            self._stage_spans.get(sp.name, 0.0) + sp.seconds
        return out

    def take_stage_spans(self) -> dict:
        """Seconds per staged dispatch since the last call ({} for a
        one-program plan)."""
        spans, self._stage_spans = self._stage_spans, {}
        return spans

    def _run_stage_b(self, a):
        """Dispatch the stage-(a) boundary into the jitted stage (b)."""
        return self._enqueue_stage("b", self._jit_stage_b, a)

    def _stage_c(self, spec_ri: jnp.ndarray):
        if self.staged_rows:
            return self._stage_c_rows(spec_ri)
        return self._stage_c_nat(
            spec_ri.reshape(2, spec_ri.shape[1], -1))

    @S.scoped(S.FFT_R2C)
    def _stage_a_nat(self, raw: jnp.ndarray):
        """unpack + even/odd pack + segment-FFT first half."""
        a = F.four_step_stage1(self._staged_pack(raw),
                               rows_impl=self._staged_rows_impl,
                               len_cap=self._len_cap)  # [..., n2, n1]
        return jnp.stack([jnp.real(a), jnp.imag(a)])

    @S.scoped(S.FFT_R2C)
    def _stage_b_nat(self, a_ri: jnp.ndarray):
        """segment-FFT second half + Hermitian post -> spectrum [S, n/2].
        With the fused tail the RFI-s1 + df64-chirp epilogue folds into
        the Hermitian post's single write here, so stage (c) starts from
        an already-dedispersed spectrum."""
        zf = F.four_step_stage2(jax.lax.complex(a_ri[0], a_ri[1]),
                                rows_impl=self._staged_rows_impl,
                                len_cap=self._len_cap)
        epilogue = self._tail_epilogue(None) if self.fused_tail else None
        if self._staged_blocked:
            spec = F.finish_rfft_subbyte(zf[0], epilogue=epilogue)[None, :]
        else:
            spec = F.hermitian_rfft_post(zf, drop_nyquist=True,
                                         epilogue=epilogue)
        return jnp.stack([jnp.real(spec), jnp.imag(spec)])

    def _stage_c_nat(self, spec_ri: jnp.ndarray):
        """RFI s1 + in-step chirp + waterfall + RFI s2 + detect (the s1
        + chirp front half lives in stage (b) when the tail is fused)."""
        spec = jax.lax.complex(spec_ri[0], spec_ri[1])       # [S, n/2]
        if spec.shape[0] == 1:
            return self._spectrum_to_results(spec, None)
        return self._stream_after_stream(
            lambda row: self._spectrum_to_results(row[None, :], None), spec)

    # ---- the plain staged plan, in blocks of the boundary's rows ----
    #
    # The segment's half-size C2C is split n/2 = F x T, the boundary's
    # own two axes (a four-step's split is free; the whole-plane stages
    # take sqrt(n/2) squared): stage (a)'s A[j2, k1] IS the boundary
    # [2, S, F, T], row by row, stage (b) transforms its columns and
    # pairs its rows, stage (c) takes its rows as channels.  Each stage
    # carries the boundary through loops whose body reads a block,
    # computes and writes it back in place, so beside the 4 GB boundary
    # of a 2^30 segment a program holds a few blocks (16 channels or
    # 2048 columns: ops/fft.BLOCK_POINTS = 2^22 points, 32 MB) and never
    # a plane of the spectrum's size, and no stage reshapes the boundary
    # across its lanes.

    def _stage_a_block_rows(self) -> int:
        """Rows of the boundary a block of ``_stage_a_rows`` makes: twice
        ``ops/fft.BLOCK_POINTS`` points (32 rows of 2^18 at 2^30 / 2^11:
        191.0 ms a segment on a v5e where 64 rows take 207.9 and 16 take
        229.5), or as many more rows as it takes for their packed points
        to fill whole bytes in a row of the bytes' view (two 2-bit points
        a byte); 0 where the whole-plane stage (a) stays (several streams
        in one byte stream, or fewer channels than that)."""
        if not self.staged_rows or self.fmt.unpack_variant != "simple":
            return 0
        blocks = F.block_count(self.channel_count, self.n_spectrum,
                               2 * F.BLOCK_POINTS)
        rows = self.channel_count // blocks
        bits = abs(int(self.cfg.baseband_input_bits))
        while (rows * 2 * bits) % 8:
            rows *= 2
        return rows if self.channel_count % rows == 0 else 0

    @S.scoped(S.FFT_R2C)
    def _stage_a_rows(self, parts: tuple):
        """unpack + even/odd pack + segment-FFT first half over blocks of
        the boundary's rows.  Row ``j2`` of ``A[j2, k1]`` transforms the
        points ``x[j1*F + j2]``: a block of rows reads one strip of
        columns of the bytes' ``[T, bytes a row]`` view, unpacks and
        packs it, transforms it and writes its rows, so the unpacked
        float32 samples (4 GB at 2^30) and the packed plane exist a
        block at a time.  ``parts`` are that view's rows: all of them in
        one array or, from the staged ring, the carry's and then the
        new bytes'; a strip is read from each part and the strips are
        joined under ``srtb.ring``, so the ring never makes the view."""
        cfg = self.cfg
        n2, n1 = self.channel_count, self.watfft_len
        jw = self._stage_a_block_rows()
        if not jw:
            (raw,) = parts          # no ring by strips without blocks
            z = self._staged_pack(raw.reshape(-1))            # [S, n/2]
            # the rows are a plane here: XLA's own cap, not a block's
            a = F.four_step_stage1_cols(
                z.reshape(z.shape[0], n1, n2),
                len_cap=self._len_cap or F._XLA_FFT_LEN_CAP)
            return jnp.stack([jnp.real(a), jnp.imag(a)])
        bits = abs(int(cfg.baseband_input_bits))
        wb = jw * 2 * bits // 8              # a block's bytes in a row
        win2 = None if self.window is None \
            else self.window.reshape(n1, 2 * n2)

        def body(b, out):
            strips = [jax.lax.dynamic_slice_in_dim(p, b * wb, wb, 1)
                      for p in parts]
            if len(strips) == 1:
                x = strips[0]
            else:
                with jax.named_scope(S.RING):
                    x = jnp.concatenate(strips)               # [n1, wb]
            win = None if win2 is None else jax.lax.dynamic_slice_in_dim(
                win2, b * 2 * jw, 2 * jw, 1).reshape(-1)
            x = unpack_streams(x.reshape(-1), self.fmt.unpack_variant,
                               cfg.baseband_input_bits, win)  # [1, 2*n1*jw]
            z = F.pack_even_odd(x).reshape(1, n1, jw)
            a = F.four_step_stage1_cols(z, len_cap=self._len_cap)
            return jax.lax.dynamic_update_slice_in_dim(
                out, jnp.stack([jnp.real(a), jnp.imag(a)]), b * jw, 2)

        return jax.lax.fori_loop(
            0, n2 // jw, body, jnp.zeros((2, 1, n2, n1), jnp.float32))

    @S.scoped(S.FFT_R2C)
    def _stage_b_rows(self, a_ri: jnp.ndarray):
        """segment-FFT second half over blocks of columns, then the
        Hermitian post over mirrored pairs of row blocks, both in place
        on the boundary (ops/fft.four_step_stage2_cols,
        hermitian_rfft_post_rows)."""
        # blocks of whole vector lanes of columns
        col_blocks = F.block_count(self.watfft_len, self.n_spectrum,
                                   unit=min(128, self.watfft_len))
        z_ri = F.four_step_stage2_cols(a_ri, col_blocks,
                                       len_cap=self._len_cap)
        return F.hermitian_rfft_post_rows(z_ri, self.staged_rows)

    def _stage_c_rows(self, spec_ri: jnp.ndarray):
        """RFI s1 + in-step df64 chirp + waterfall + RFI s2 + detect over
        blocks of channels.  RFI s1's mean power is the one reduction
        over the whole spectrum; after it the zap, the manual mask, the
        chirp, the backward C2C, the de-window and the SK zap are each
        channel's own, and the detector's series is a sum over channels:
        a block's share of it is kept, and the blocks' shares are summed
        by the same pairwise tree (ops/detect.tree_sum_freq over the
        blocks continues the tree inside each), so the series is the
        whole-plane spelling's to the last bit on the same waterfall."""
        cfg = self.cfg
        n_streams, f_cnt, t_len = spec_ri.shape[1:]
        blocks = self.staged_rows
        cb = f_cnt // blocks
        t = det.trimmed_length(t_len, self.time_reserved_count)
        with jax.named_scope(S.RFI_S1):
            mean_power = jnp.mean(spec_ri[0] ** 2 + spec_ri[1] ** 2,
                                  axis=(-2, -1))                  # [S]

        def body(i, carry):
            buf, ts_parts, zero_count = carry
            s, b = i // blocks, i % blocks
            c0 = b * cb
            x = jax.lax.dynamic_slice(buf, (0, s, c0, 0),
                                      (2, 1, cb, t_len))
            spec = jax.lax.complex(x[0], x[1]).reshape(1, cb * t_len)
            spec = rfi.mitigate_rfi_s1_given_mean(
                spec, mean_power[s],
                cfg.mitigate_rfi_average_method_threshold, self.norm_coeff)
            k0 = c0 * t_len                  # the block's first bin
            spec = rfi.mitigate_rfi_manual_bins(spec, self.rfi_bins, k0)
            c_ri = dd.chirp_block_df64_ri(
                cb * t_len, self.n_spectrum, self.f_min, self.df,
                self.f_c, cfg.dm, k0,
                exact=getattr(cfg, "chirp_exact", False))
            spec = dd.dedisperse(spec, jax.lax.complex(c_ri[0], c_ri[1]))
            wf = F.waterfall_c2c(spec, cb, self.watfft_dewindow,
                                 len_cap=self._len_cap)       # [1, cb, T]
            wf = rfi.mitigate_rfi_spectral_kurtosis(
                wf, cfg.mitigate_rfi_spectral_kurtosis_threshold)
            with jax.named_scope(S.DETECT):
                # ops/detect.detect's two reductions, a block's share
                power = jnp.real(wf) ** 2 + jnp.imag(wf) ** 2
                zero_count = zero_count.at[s].add(jnp.sum(
                    (power[0, :, 0] == 0).astype(jnp.int32)))
                ts = det.tree_sum_freq(power[..., :t])            # [1, t]
            buf = jax.lax.dynamic_update_slice(
                buf, jnp.stack([jnp.real(wf), jnp.imag(wf)]),
                (0, s, c0, 0))
            ts_parts = jax.lax.dynamic_update_slice(
                ts_parts, ts[:, None], (s, b, 0))
            return buf, ts_parts, zero_count

        # the loop's own work (a block sliced out and written back)
        # reads as the waterfall's; the body's keep their stages' scopes
        with jax.named_scope(S.WATERFALL):
            wf_ri, ts_parts, zero_count = jax.lax.fori_loop(
                0, n_streams * blocks, body,
                (spec_ri, jnp.zeros((n_streams, blocks, t), jnp.float32),
                 jnp.zeros((n_streams,), jnp.int32)))
        result = det.detect_from_time_series(
            det.tree_sum_freq(ts_parts), zero_count,
            cfg.signal_detect_signal_noise_threshold,
            cfg.signal_detect_max_boxcar_length)
        return wf_ri, result

    def _spectrum_tail(self, spec: jnp.ndarray, chirp_ri):
        """Legacy (unfused-tail) device chain from the raw spectrum
        onward: RFI s1 + chirp as their own sweeps, then the waterfall
        tail.  With ``chirp_ri=None`` the df64 chirp is generated inside
        the trace (fuses into the multiply; nothing bank-sized is
        materialized)."""
        chirped, qtap = self._apply_s1_chirp(spec, chirp_ri)
        return self._waterfall_detect(chirped, qspec=qtap)

    @S.scoped(S.CHIRP)
    def _apply_s1_chirp(self, spec: jnp.ndarray, chirp_ri):
        """RFI stage 1 + manual mask + chirp multiply as standalone
        spectrum sweeps (the passes the fused tail folds into the FFT's
        final write).  Returns ``(chirped, qtap)`` where ``qtap`` is
        the spectrum the quality epilogue should read bin powers from:
        the chirp is unit-modulus, so the PRE-chirp zapped/normalized
        spectrum has bin-identical power and zeros — and reading it
        keeps the (expensive, error-free-transform) df64 chirp chain
        out of the epilogue's fusion producers."""
        cfg = self.cfg
        interp = getattr(self, "_pallas_interpret", False)
        from srtb_tpu.ops import pallas_kernels as pk
        n_streams = spec.shape[0]
        if cfg.use_pallas:
            # Fully fused front half: RFI s1 zap + normalize + manual
            # mask + df64 in-register chirp in ONE HBM pass per stream
            # (the mean-power reduce stays a jnp pass).  Phase computed
            # in-register; no chirp bank exists.
            outs = []
            for s in range(n_streams):
                spec_ri = jnp.stack([jnp.real(spec[s]), jnp.imag(spec[s])])
                out_ri = pk.rfi_s1_dedisperse_df64(
                    spec_ri, cfg.mitigate_rfi_average_method_threshold,
                    self.norm_coeff, self.f_min, self.df, self.f_c,
                    cfg.dm, mask=self.rfi_mask, interpret=interp,
                    exact=getattr(cfg, "chirp_exact", False))
                outs.append(jax.lax.complex(out_ri[0], out_ri[1]))
            out = jnp.stack(outs)
            # the Pallas kernel materializes its output: reading it
            # again is one cheap pass, no producer duplication
            return out, out
        spec = rfi.mitigate_rfi_average_and_normalize(
            spec, cfg.mitigate_rfi_average_method_threshold,
            self.norm_coeff)
        spec = rfi.mitigate_rfi_manual(spec, self.rfi_mask)
        qtap = spec  # pre-chirp: bin powers/zeros identical post-chirp
        if chirp_ri is None:
            # In-step df64 chirp of a WHOLE-PLANE staged stage (c): the
            # Pallas kernel over the whole spectrum.  At 2^30 its
            # program was refused by a v5e (19.00G of 15.75G with the
            # whole-spectrum RFI s1 beside it); the plain staged plan
            # makes the chirp in XLA a block of channels at a time
            # instead (_stage_c_rows), so this branch serves the
            # forced-staged variants at small sizes and the CPU tests.
            outs = []
            for s in range(n_streams):
                spec_ri = jnp.stack([jnp.real(spec[s]),
                                     jnp.imag(spec[s])])
                out_ri = pk.dedisperse_df64(
                    spec_ri, self.f_min, self.df, self.f_c,
                    cfg.dm, interpret=interp,
                    exact=getattr(cfg, "chirp_exact", False))
                outs.append(jax.lax.complex(out_ri[0], out_ri[1]))
            return jnp.stack(outs), qtap
        chirp = jax.lax.complex(chirp_ri[0], chirp_ri[1])
        return dd.dedisperse(spec, chirp), qtap

    @S.scoped(S.WATERFALL)
    def _waterfall_detect(self, spec: jnp.ndarray, qspec=None):
        """Waterfall backward C2C + RFI stage 2 + detection from an
        already-dedispersed spectrum.  With the fully-fused skzap plan
        (fused tail + use_pallas + use_pallas_sk + VMEM-resident rows)
        the whole tail is ONE kernel per stream — the detect stage never
        re-reads the waterfall from HBM.

        ``qspec`` is the spectrum the quality epilogue reads bin powers
        from when it differs from ``spec`` (the unfused jnp path hands
        the PRE-chirp zapped/normalized spectrum — power-identical,
        and it keeps the df64 chirp chain out of the epilogue's XLA
        fusion producers, which otherwise duplicates it at ~40%
        per-segment cost on the CPU path)."""
        cfg = self.cfg
        if qspec is None:
            qspec = spec
        use_pallas = cfg.use_pallas
        interp = getattr(self, "_pallas_interpret", False)
        from srtb_tpu.ops import pallas_kernels as pk
        n_streams = spec.shape[0]
        if self._skzap:
            from srtb_tpu.ops import pallas_fft as pf
            t_len = self.watfft_len
            x = spec[..., :self.channel_count * t_len].reshape(
                n_streams, self.channel_count, t_len)
            zapped, zero_counts, ts_rows = [], [], []
            for s in range(n_streams):
                wr, wi, zapf, fs0, ts = pf.fft_rows_skzap_ri(
                    jnp.real(x[s]), jnp.imag(x[s]),
                    cfg.mitigate_rfi_spectral_kurtosis_threshold,
                    inverse=True, dewindow=self.watfft_dewindow,
                    interpret=interp)
                zapped.append(jax.lax.complex(wr, wi))
                zero_counts.append(jnp.sum(
                    ((zapf[:, 0] != 0) | (fs0[:, 0] == 0))
                    .astype(jnp.int32)))
                ts_rows.append(ts)
            wf = jnp.stack(zapped)
            t = det.trimmed_length(wf.shape[-1], self.time_reserved_count)
            result = det.detect_from_time_series(
                jnp.stack(ts_rows)[:, :t], jnp.stack(zero_counts),
                cfg.signal_detect_signal_noise_threshold,
                cfg.signal_detect_max_boxcar_length)
            result = self._quality_epilogue(qspec, wf, result)
            wf_ri = jnp.stack([jnp.real(wf), jnp.imag(wf)])
            return wf_ri, result
        from srtb_tpu.ops import pallas_fft as pf
        pallas_wf = use_pallas and pf.supported(
            self.watfft_len, spec.shape[0] * self.channel_count)
        pallas_sk = cfg.use_pallas_sk and pk.sk_tiling_ok(
            self.channel_count, self.watfft_len)
        if pallas_sk and pallas_wf:
            # Fully fused waterfall post-chain: ONE batched VMEM row-FFT
            # kernel computes the backward C2C for all streams,
            # de-applies the window and collects the SK power moments
            # while each row is still in VMEM
            # (ops/pallas_fft.fft_rows_stats_ri) — the waterfall is never
            # re-read for statistics; the zap verdict + time series then
            # cost exactly one more read+write (pk.sk_apply_timeseries).
            # 2 HBM round trips total where the jnp chain takes ~5.
            t_len = self.watfft_len
            x = spec[..., :self.channel_count * t_len].reshape(
                n_streams, self.channel_count, t_len)
            wr, wi, s2p, s4p = pf.fft_rows_stats_ri(
                jnp.real(x), jnp.imag(x), inverse=True,
                dewindow=self.watfft_dewindow, interpret=interp)
            zap_all = pk.sk_zap_decision(            # [S, F]
                s2p.sum(-1), s4p.sum(-1), t_len,
                cfg.mitigate_rfi_spectral_kurtosis_threshold)
            fs0 = wr[..., 0] ** 2 + wi[..., 0] ** 2
            zc_all = jnp.sum((zap_all | (fs0 == 0)).astype(jnp.int32),
                             axis=-1)
            zapped, zero_counts, ts_rows = [], [], []
            for s in range(n_streams):
                wf_ri1, ts = pk.sk_apply_timeseries(
                    jnp.stack([wr[s], wi[s]]), zap_all[s],
                    interpret=interp)
                zapped.append(jax.lax.complex(wf_ri1[0], wf_ri1[1]))
                zero_counts.append(zc_all[s])
                ts_rows.append(ts)
        elif pallas_sk:
            wf = F.waterfall_c2c(spec, self.channel_count,
                                 self.watfft_dewindow,
                                 len_cap=self._len_cap)  # [S, F, T]
            zapped, zero_counts, ts_rows = [], [], []
            for s in range(n_streams):
                wf_ri1 = jnp.stack([jnp.real(wf[s]), jnp.imag(wf[s])])
                wf_ri1, zc, ts = pk.sk_zap_timeseries(
                    wf_ri1, cfg.mitigate_rfi_spectral_kurtosis_threshold,
                    interpret=interp)
                zapped.append(jax.lax.complex(wf_ri1[0], wf_ri1[1]))
                zero_counts.append(zc)
                ts_rows.append(ts)
        if pallas_sk:
            wf = jnp.stack(zapped)
            t = det.trimmed_length(wf.shape[-1], self.time_reserved_count)
            result = det.detect_from_time_series(
                jnp.stack(ts_rows)[:, :t], jnp.stack(zero_counts),
                cfg.signal_detect_signal_noise_threshold,
                cfg.signal_detect_max_boxcar_length)
        else:
            if pallas_wf:
                # one-HBM-pass Pallas waterfall C2C (ops/pallas_fft):
                # rows in VMEM, DFT-matmul stages on the MXU
                x = spec[..., :self.channel_count
                         * self.watfft_len].reshape(
                    *spec.shape[:-1], self.channel_count, self.watfft_len)
                wr, wi = pf.fft_rows_ri(jnp.real(x), jnp.imag(x),
                                        inverse=True, interpret=interp)
                wf = jax.lax.complex(wr, wi)
                if self.watfft_dewindow is not None:
                    wf = wf / self.watfft_dewindow
            else:
                wf = F.waterfall_c2c(spec, self.channel_count,
                                     self.watfft_dewindow,
                                     len_cap=self._len_cap)  # [S, F, T]
            wf = rfi.mitigate_rfi_spectral_kurtosis(
                wf, cfg.mitigate_rfi_spectral_kurtosis_threshold)
            result = det.detect(wf, self.time_reserved_count,
                                cfg.signal_detect_signal_noise_threshold,
                                cfg.signal_detect_max_boxcar_length)
        result = self._quality_epilogue(qspec, wf, result)
        # boundary representation: waterfall leaves jit as stacked (re, im)
        wf_ri = jnp.stack([jnp.real(wf), jnp.imag(wf)])  # [2, S, F, T]
        return wf_ri, result

    def _quality_epilogue(self, spec: jnp.ndarray, wf: jnp.ndarray,
                          result):
        """Data-quality statistics rider (srtb_tpu/quality/stats.py):
        with ``Config.quality_stats`` armed, pack the per-stream
        quality vector from the spectrum and waterfall ALREADY
        resident in this trace and attach it to the detect result —
        two cheap extra reads inside every plan family, no new plan.
        Off (the default) this is an exact no-op: existing plans trace
        byte-identically."""
        cfg = self.cfg
        if not getattr(cfg, "quality_stats", False):
            return result
        from srtb_tpu.quality import stats as Q
        qvec = Q.quality_stats_device(
            spec, wf,
            int(getattr(cfg, "quality_coarse_bins", 64) or 64),
            float(getattr(cfg, "quality_dead_threshold", 0.1)),
            float(getattr(cfg, "quality_hot_threshold", 10.0)),
            subsample=int(getattr(cfg, "quality_subsample", 1) or 1))
        return result._replace(quality=qvec)

    # ------------------------------------------------------------------
    # AOT warm restart (utils/aot_cache.py): replace the jit wrappers
    # with persisted compiled executables so a restarted observation
    # skips the (minutes-long at 2^30) XLA compile entirely.

    # Config fields that enter the traced programs.  An ALLOWLIST, not a
    # denylist: IO/GUI/paths knobs added later can't silently start
    # keying the AOT cache and turning a deployment-local tweak (e.g.
    # udp_receiver_rcvbuf_bytes) into an 11-minute 2^30 recompile.
    _TRACE_CFG_KEYS = (
        "baseband_input_count", "baseband_input_bits",
        "baseband_format_type", "baseband_freq_low",
        "baseband_bandwidth", "baseband_sample_rate", "dm", "dm_list",
        "spectrum_channel_count", "signal_detect_signal_noise_threshold",
        "signal_detect_max_boxcar_length", "signal_detect_channel_threshold",
        "mitigate_rfi_average_method_threshold",
        "mitigate_rfi_spectral_kurtosis_threshold",
        "mitigate_rfi_freq_list", "baseband_reserve_sample",
        "fft_strategy", "fft_len_cap", "use_pallas", "use_pallas_sk",
        "use_emulated_fp64", "fused_tail", "chirp_exact",
        # overlap-engine trace shapers: micro_batch_segments changes the
        # traced program (vmapped batch plan) outright;
        # inflight_segments shapes the runtime's donation/aliasing
        # pattern around the executables — a restarted process with
        # different overlap settings must miss the cache cleanly, not
        # load a stale executable
        "inflight_segments", "micro_batch_segments",
        # the ingest ring adds the two-input assemble programs and
        # changes which program the engine dispatches per segment
        "ingest_ring",
        # quality epilogue: armed/off changes the traced program (the
        # detect result grows the packed stats output), and the bin
        # count / channel thresholds are trace-time constants shaping
        # it — host-side quality knobs (drift detector) and the
        # canary (raw-byte injection upstream of the trace) are
        # deliberately NOT here
        "quality_stats", "quality_coarse_bins",
        "quality_dead_threshold", "quality_hot_threshold",
        "quality_subsample",
    )

    @classmethod
    def _trace_projection(cls, cfg) -> tuple[dict, dict]:
        """The (config fields, env knobs) that shape the traced
        programs — the ONE projection both :meth:`plan_signature` and
        :meth:`plan_cache_key` are built from, so the fleet's shared-
        plan safety claim ("equal cache keys imply equal signatures")
        can never drift apart by a one-sided edit.  Only SRTB_* env
        prefixes that shape traces are swept: keying on run-local
        paths (SRTB_WATCH_LOG, the cache dir itself)
        would silently miss on every deployment-environment
        difference — the exact outage the AOT cache exists to
        prevent."""
        cfg_d = {k: getattr(cfg, k) for k in cls._TRACE_CFG_KEYS
                 if hasattr(cfg, k)}
        trace_prefixes = ("SRTB_STAGED", "SRTB_PALLAS", "SRTB_DIST",
                          "SRTB_MXU")
        knobs = {k: v for k, v in os.environ.items()
                 if k.startswith(trace_prefixes)}
        return cfg_d, knobs

    @classmethod
    def plan_cache_key(cls, cfg, window_name: str = W.DEFAULT_WINDOW,
                       donate_input: bool = False) -> str:
        """Conservative shared-plan cache key WITHOUT constructing a
        processor: the trace projection + the constructor inputs.
        Equal keys imply equal :meth:`plan_signature` — every derived
        plan flag (staged, fused_tail, ring, skzap)
        resolves as a pure function of exactly these inputs and the
        local platform — so the fleet's SharedPlanCache
        (pipeline/fleet.py) can serve one compiled plan family to
        every stream whose config projects identically, probing
        nothing.  (The key is *finer* than the family only in the
        degenerate sense that two DIFFERENT projections could resolve
        to the same plan; those compile twice — correct, merely
        unshared.)  Per-stream identity (stream_name, priority,
        paths) is deliberately outside the projection: tenancy must
        never split the plan cache."""
        import json

        cfg_d, knobs = cls._trace_projection(cfg)
        return json.dumps(
            {"cfg": cfg_d, "env": knobs, "window": window_name,
             "mode": cls.MODE,
             "donate_input": bool(donate_input)},
            sort_keys=True, default=str)

    def plan_signature(self) -> str:
        """Stable string identifying everything that shapes the compiled
        programs: the trace-relevant config fields, the trace-shaping
        SRTB_* env knobs, and the plan flags.  Any drift misses the AOT
        cache cleanly and recompiles."""
        import json

        cfg_d, knobs = self._trace_projection(self.cfg)
        # the stream plan: a several-stream segment runs as one-stream
        # chains one after the other (_stream_after_stream: the fused
        # plan's whole chain, the staged plan's stage (c)); before, the
        # transforms took the stream axis as a batch, in programs of
        # the same avals.  A one-stream plan has no such entry: its
        # signature and its cache keys stay what they were
        streams = ({"streams": "looped-v1"}
                   if self.fmt.data_stream_count > 1 else {})
        return json.dumps(
            {**streams,
             "cfg": cfg_d, "env": knobs, "mode": self.MODE,
             "staged": self.staged,
             "interp": self._pallas_interpret,
             "window": self._window_name,
             "has_chirp": self.chirp is not None,
             "donate_input": self._donate_input,
             # resolved fusion state, not just the "auto" request: a
             # restarted process whose plan resolves differently (e.g.
             # strategy flips monolithic <-> four_step across the
             # threshold) must miss the AOT cache cleanly
             "fused_tail": self.fused_tail,
             # the repo's own transform with the tail in its post pass:
             # other programs and another bank than the fused tail's
             # XLA spelling; a plan without it keeps its signature
             **({"r2c": "own-v1"} if self.own_tail else {}),
             "skzap": self._skzap,
             # resolved ingest plan: the ring's two-input assemble
             # programs (and their carry avals) exist only when it is
             # live, so a restart that resolves differently (e.g. a
             # dm change flips reserved_bytes to 0) must miss cleanly
             "ingest": "ring-v1" if self.ring else "direct",
             # staged-boundary schema version: the canonical
             # donation-aliasable [2, S, F, T] boundary changed the
             # staged programs' avals — a warm AOT cache written before
             # it must miss cleanly, not feed the new chain executables
             # with the old boundary shapes
             "boundary": "canonical-v2"},
            sort_keys=True, default=str)

    def lowerables(self):
        """Every jitted program of this plan as ``(name, jit_fn,
        abstract_args, donated_argnums)`` — lowerable via
        ``jit_fn.lower(*abstract_args)`` without touching a device or
        running anything.  The plan-enumeration hook the compile-time
        HLO plan auditor (``srtb_tpu/analysis/hlo_audit.py``) and the
        AOT cache both build on: abstract avals only, boundary shapes
        chained by ``jax.eval_shape`` exactly as ``enable_aot`` chains
        them, so the audited artifacts ARE the executed artifacts."""
        expected = self.cfg.segment_bytes(self.fmt.data_stream_count)
        raw_s = jax.ShapeDtypeStruct((expected,), jnp.uint8)
        in_donate = (0,) if self._donate_input else ()
        ring_donate = (0,) + ((1,) if self._donate_input else ())
        carry_s = jax.ShapeDtypeStruct((self.reserved_bytes,), jnp.uint8)
        new_s = jax.ShapeDtypeStruct((self.stride_bytes,), jnp.uint8)
        # Fresh jit wrappers of the underlying plan functions, NOT the
        # self._jit_* attributes: enable_aot swaps those for loaded
        # Compiled executables, which cannot .lower() again — the
        # audit must stay lowerable on an AOT-active processor.  The
        # per-call wrappers are sanctioned here: this is the audit-only
        # cold path (never the per-segment dispatch), and a cached
        # wrapper would defeat the AOT independence above.
        if self.staged:
            a_out = jax.eval_shape(self._stage_a, raw_s)
            b_out = jax.eval_shape(self._stage_b, a_out)
            progs = [
                ("stage_a",
                 # srtb-lint: disable=recompile-hazard
                 jax.jit(self._stage_a, donate_argnums=in_donate),
                 (raw_s,), in_donate),
                # srtb-lint: disable=recompile-hazard
                ("stage_b", jax.jit(self._stage_b, donate_argnums=(0,)),
                 (a_out,), (0,)),
                # srtb-lint: disable=recompile-hazard
                ("stage_c", jax.jit(self._stage_c, donate_argnums=(0,)),
                 (b_out,), (0,)),
            ]
            if self.ring:
                # the bytes as the staged ring takes them (rows where it
                # assembles by strips), the carry alone donated
                progs += [
                    ("stage_a_ring",
                     # srtb-lint: disable=recompile-hazard
                     jax.jit(self._stage_a_ring, donate_argnums=(0,)),
                     (self._ring_aval(self.reserved_bytes),
                      self._ring_aval(self.stride_bytes)), (0,)),
                    ("stage_a_cold",
                     # srtb-lint: disable=recompile-hazard
                     jax.jit(self._stage_a_cold,
                             donate_argnums=in_donate),
                     (self._ring_aval(expected),), in_donate),
                ]
            return progs

        def aval(x):
            return None if x is None else jax.ShapeDtypeStruct(
                x.shape, x.dtype)

        chirps = (aval(self.chirp), aval(self.chirp_w))
        progs = [("fused",
                  # srtb-lint: disable=recompile-hazard
                  jax.jit(self._process, donate_argnums=in_donate),
                  (raw_s,) + chirps, in_donate)]
        if self.ring:
            progs += [
                ("ring",
                 # srtb-lint: disable=recompile-hazard
                 jax.jit(self._process_ring, donate_argnums=ring_donate),
                 (carry_s, new_s) + chirps, ring_donate),
                ("ring_cold",
                 # srtb-lint: disable=recompile-hazard
                 jax.jit(self._process_cold, donate_argnums=in_donate),
                 (raw_s,) + chirps, in_donate),
            ]
        mb = int(getattr(self.cfg, "micro_batch_segments", 1) or 1)
        if mb > 1:
            batch_s = jax.ShapeDtypeStruct((mb, expected), jnp.uint8)
            progs.append(("batch",
                          jax.jit(jax.vmap(self._process,
                                           in_axes=(0, None, None)),
                                  donate_argnums=in_donate),
                          (batch_s,) + chirps, in_donate))
            if self.ring:
                news_s = jax.ShapeDtypeStruct((mb, self.stride_bytes),
                                              jnp.uint8)
                progs += [
                    ("batch_ring",
                     # srtb-lint: disable=recompile-hazard
                     jax.jit(self._process_batch_ring,
                             donate_argnums=ring_donate),
                     (carry_s, news_s) + chirps, ring_donate),
                    ("batch_cold",
                     # srtb-lint: disable=recompile-hazard
                     jax.jit(self._process_batch_cold,
                             donate_argnums=in_donate),
                     (batch_s,) + chirps, in_donate),
                ]
        return progs

    def enable_aot(self, path: str, allow_cpu: bool = False) -> bool:
        """Swap the jitted plan programs for cached compiled executables
        (compiling + persisting on miss).  Returns False when the cache
        is unavailable (CPU backend without the opt-in) — the jit
        wrappers stay in place and behavior is unchanged."""
        from srtb_tpu.utils.aot_cache import AotPlanCache

        cache = AotPlanCache(path, allow_cpu=allow_cpu,
                             labels=self._metric_labels)
        if not cache.enabled():
            return False
        sig = self.plan_signature()
        expected = self.cfg.segment_bytes(self.fmt.data_stream_count)
        raw_s = jax.ShapeDtypeStruct((expected,), jnp.uint8)
        carry_s = jax.ShapeDtypeStruct((self.reserved_bytes,), jnp.uint8)
        new_s = jax.ShapeDtypeStruct((self.stride_bytes,), jnp.uint8)
        if not self.staged:
            self._jit_process = cache.get_or_compile(
                "fused", sig, self._jit_process, raw_s, self.chirp,
                self.chirp_w)
            if self.ring:
                self._jit_ring = cache.get_or_compile(
                    "ring", sig, self._jit_ring, carry_s, new_s,
                    self.chirp, self.chirp_w)
                self._jit_cold = cache.get_or_compile(
                    "ring_cold", sig, self._jit_cold, raw_s,
                    self.chirp, self.chirp_w)
        else:
            # chain the boundary avals by abstract evaluation (free:
            # trace only, no compile)
            a_out = jax.eval_shape(self._stage_a, raw_s)
            b_out = jax.eval_shape(self._stage_b, a_out)
            self._jit_stage_a = cache.get_or_compile(
                "stage_a", sig, self._jit_stage_a, raw_s)
            self._jit_stage_b = cache.get_or_compile(
                "stage_b", sig, self._jit_stage_b, a_out)
            self._jit_stage_c = cache.get_or_compile(
                "stage_c", sig, self._jit_stage_c, b_out)
            if self.ring:
                self._jit_stage_a_ring = cache.get_or_compile(
                    "stage_a_ring", sig, self._jit_stage_a_ring,
                    self._ring_aval(self.reserved_bytes),
                    self._ring_aval(self.stride_bytes))
                self._jit_stage_a_cold = cache.get_or_compile(
                    "stage_a_cold", sig, self._jit_stage_a_cold,
                    self._ring_aval(expected))
        self.aot_active = True
        return True

    @staticmethod
    def _count_h2d(nbytes: int) -> None:
        """Account one host->device transfer (the ring's falsifiable
        payoff: warm dispatches move exactly stride_bytes, cold ones
        exactly segment_bytes — tests and the ci smoke assert the
        counter against that stride model)."""
        from srtb_tpu.utils.metrics import metrics
        metrics.add("h2d_bytes", nbytes)

    def _as_device_bytes(self, raw) -> jnp.ndarray:
        """Host bytes -> device uint8 via *explicit* ``device_put``
        (``jnp.asarray`` on host data is an implicit H2D transfer; the
        explicit spelling keeps every pipeline transfer visible to
        ``jax.transfer_guard`` and the runtime sanitizer)."""
        if isinstance(raw, jax.Array):
            return raw if raw.dtype == jnp.uint8 \
                else jnp.asarray(raw, dtype=jnp.uint8)
        arr = np.ascontiguousarray(np.asarray(raw), dtype=np.uint8)
        self._count_h2d(arr.nbytes)
        return jax.device_put(arr)

    # ---------------------------- host staging buffers (pooled copies)

    def _staged_host(self, raw, owner=None) -> np.ndarray:
        """A contiguous uint8 host view of ``raw``, copying into a
        pooled staging buffer only when a copy is unavoidable (wrong
        dtype / non-contiguous input).  ``owner`` keys the buffer's
        lifetime: it returns to the pool at release_staging(owner)
        (the pipeline calls that when the segment drains), or via the
        FIFO overflow cap for callers that never release."""
        arr = raw if isinstance(raw, np.ndarray) \
            else np.ascontiguousarray(raw)  # host data, never a device fetch
        if arr.dtype == np.uint8 and arr.flags["C_CONTIGUOUS"]:
            return arr
        buf = self._staging_pool.acquire(arr.size, zero=False)
        np.copyto(buf, arr.reshape(-1), casting="unsafe")
        self._register_staging(owner if owner is not None else raw, buf)
        return buf

    def _register_staging(self, owner, buf: np.ndarray) -> None:
        entry = self._staging_out.get(id(owner))
        if entry is None:
            # the owner rides in the entry so its id stays pinned
            # until release (no reuse-after-GC key collisions)
            self._staging_out[id(owner)] = (owner, [buf])
        else:
            entry[1].append(buf)
        while len(self._staging_out) > self._staging_cap:
            # overflow: the oldest registration's transfer completed
            # long ago (the in-flight window bounds concurrency), so
            # reclaiming it is safe even for a caller that never
            # releases explicitly
            _, (_owner, bufs) = next(iter(self._staging_out.items()))
            self._staging_out.pop(id(_owner))
            for b in bufs:
                self._staging_pool.release(b)

    def release_staging(self, owner) -> None:
        """Return the staging buffers registered against ``owner``
        (one segment's host byte buffer) to the pool.  Called by the
        pipeline when the segment drains; a no-op for segments that
        never needed a staging copy."""
        entry = self._staging_out.pop(id(owner), None)
        if entry is not None:
            for b in entry[1]:
                self._staging_pool.release(b)

    def stack_batch(self, datas, stride_only: bool = False) -> np.ndarray:
        """Stack B segments' host bytes into one pooled, contiguous
        [B, segment_bytes] (or [B, stride_bytes] with ``stride_only``)
        uint8 array for a micro-batch dispatch — reusing a staging
        buffer instead of a fresh ``np.stack`` allocation per batch.
        Registered against the FIRST segment's buffer: the batch is one
        device program, so its first drain implies the whole transfer
        completed."""
        width = self.stride_bytes if stride_only else self._segment_bytes
        buf = self._staging_pool.acquire(len(datas) * width, zero=False)
        out = buf.reshape(len(datas), width)
        for i, d in enumerate(datas):
            src = d if isinstance(d, np.ndarray) \
                else np.ascontiguousarray(d)
            out[i] = src[src.shape[0] - width:] if stride_only else src
        self._register_staging(datas[0], buf)
        return out

    # ------------------------------------------------- H2D staging

    def stage_input(self, raw, stride_only: bool = False) -> jnp.ndarray:
        """Start the async host->device transfer of one segment's raw
        bytes and return the device handle immediately (H2D staging).
        The overlap engine calls this right after ingest, so the
        transfer runs under the *previous* segment's device compute
        instead of serializing into the next dispatch.

        With ``stride_only`` (the live ring's warm path) only the
        stride's NEW bytes — ``raw[reserved_bytes:]`` — cross the PCIe
        link; the reserved head is already device-resident as
        the carry.  ``raw`` stays the FULL segment either way: the
        retained host buffer is what watchdog requeues and dispatch
        retries re-stage cold, bit-identically."""
        expected = self.cfg.segment_bytes(self.fmt.data_stream_count)
        if raw.shape != (expected,):
            raise ValueError(
                f"segment must be {expected} bytes, got {raw.shape}")
        staged = self._staged_host(raw, owner=raw)
        from srtb_tpu.utils.metrics import metrics
        if stride_only:
            if not self.ring:
                raise ValueError("stride_only staging requires the "
                                 "ingest ring (Config.ingest_ring)")
            staged = staged[self.reserved_bytes:]
            # what the ring saved this dispatch: with h2d_bytes it adds
            # up to segment_bytes a dispatch (a cold one adds nothing)
            metrics.add("ring_carry_bytes", self.reserved_bytes)
        elif self.ring:
            # counted HERE, not in the engine, so the count stays one-
            # per-full-upload under retries (a retried dispatch
            # re-stages and re-counts) — the invariant telemetry
            # consumers rely on: h2d_bytes == ring_cold_dispatches *
            # segment_bytes + warm_count * stride_bytes
            metrics.add("ring_cold_dispatches")
        self._count_h2d(staged.nbytes)
        # by strips the bytes land on the device as the rows stage (a)
        # reads (a view on the host: the same bytes in the same order)
        return jax.device_put(self._ring_rows(staged))

    def _batch_jit(self):
        """The lazily-built micro-batch program: the fused plan vmapped
        over the leading batch axis (one jit object, shared by
        :meth:`process_batch` and :meth:`lowerables`)."""
        if self._jit_process_batch is None:
            in_donate = (0,) if self._donate_input else ()
            self._jit_process_batch = jax.jit(
                jax.vmap(self._process, in_axes=(0, None, None)),
                donate_argnums=in_donate)
        return self._jit_process_batch

    def process_batch(self, raws) -> tuple[jnp.ndarray, det.DetectResult]:
        """Micro-batch mode: run B stacked segments ``raws`` [B, bytes]
        in ONE jit call (the fused plan vmapped over the batch axis),
        amortizing per-dispatch host overhead over B segments.  Returns ``(waterfall_ri, detect)`` with a leading
        batch axis on every array; slice per segment with
        ``jax.tree_util.tree_map(lambda x: x[i], ...)``."""
        if self.staged:
            raise ValueError(
                "micro_batch_segments > 1 requires the fused plan "
                "(staged segments are already dispatch-amortized)")
        raw = self._as_device_bytes(raws)
        expected = self.cfg.segment_bytes(self.fmt.data_stream_count)
        if raw.ndim != 2 or raw.shape[1] != expected:
            raise ValueError(
                f"batch must be [B, {expected}] bytes, got {raw.shape}")
        out = self._timed_first(
            "batch",
            lambda: self._batch_jit()(raw, self.chirp, self.chirp_w))
        if self._sanitize and self._donate_input:
            from srtb_tpu.analysis import sanitizer as S
            # the sanitizer is the sanctioned holder of the donated
            # buffer (it deletes it)  # srtb-lint: disable=use-after-donate
            S.expire_donated(raw, out)
        return out

    def process(self, raw) -> tuple[jnp.ndarray, det.DetectResult]:
        """Run one segment. ``raw`` is the uint8 byte array of the segment
        (all streams interleaved, as read from file or UDP).

        Returns ``(waterfall_ri, detect_result)`` where waterfall_ri is
        [2, S, F, T] float32 (re, im); use :func:`waterfall_to_numpy` to
        assemble a complex host array.
        """
        raw = self._as_device_bytes(raw)
        expected = self.cfg.segment_bytes(self.fmt.data_stream_count)
        if raw.shape != (expected,):
            raise ValueError(
                f"segment must be {expected} bytes, got {raw.shape}")
        return self.run_device(raw)

    def _timed_first(self, name: str, fn):
        """Dispatch ``fn`` with first-call compile accounting: the
        first dispatch of program family ``name`` on this processor is
        where lazy jit traces+compiles, so its wall clock feeds the
        ``compile_seconds`` / ``plan_compiles`` / ``last_compile_ms``
        metrics (per-stream twins when labeled) and, with the family
        kept, ``compile_seconds{program="<name>"}``
        (utils/tracing.first_dispatch).  An AOT-active
        processor compiled in ``enable_aot`` (counted exactly there by
        the cache), so its first dispatch is marked but not counted.
        Steady-state dispatches pay one set-membership check."""
        if self.aot_active and name not in self.first_dispatch_s:
            self.first_dispatch_s[name] = 0.0
        return tracing.first_dispatch(
            self.first_dispatch_s, name, fn, self.stage_timer,
            self._metric_labels)

    def run_device(self, raw: jnp.ndarray):
        """Run one segment on an already-device-resident byte array,
        dispatching between the fused and staged execution plans.

        Under ``Config.sanitize`` every plan boundary gets a NaN/Inf
        tripwire + a stacked-(re, im) float32 contract assert, and the
        donated input buffer is explicitly expired once consumed so a
        use-after-donate raises on CPU CI too (donation there is a
        no-op and the bug would otherwise only corrupt on the TPU).
        This serializes dispatch — sanitize is a debugging mode."""
        if not self.staged:
            out = self._timed_first(
                "fused",
                lambda: self._jit_process(raw, self.chirp,
                                          self.chirp_w))
            if self._sanitize and self._donate_input:
                from srtb_tpu.analysis import sanitizer as S
                # sanctioned holder: expiry deletes the donated
                # buffer  # srtb-lint: disable=use-after-donate
                S.expire_donated(raw, out)
            return out
        if not self._sanitize:
            def _run_staged():
                # the fused branch above returned, so its donation
                # can never reach this chain's read
                a = self._enqueue_stage(
                    "a", self._jit_stage_a,
                    raw)  # srtb-lint: disable=use-after-donate
                return self._enqueue_stage(
                    "c", self._jit_stage_c, self._run_stage_b(a))

            return self._timed_first("staged", _run_staged)

        def _run_checked():
            # the sanitizer is the sanctioned holder of the donated
            # input (it expires it); the fused branch above returned,
            # so its lambda-wrapped donation never reaches this read
            a = self._staged_a_checks(
                self._enqueue_stage("a", self._jit_stage_a, raw),
                raw)  # srtb-lint: disable=use-after-donate
            return self._staged_tail(a)

        # the WHOLE three-stage chain under one first-dispatch timer:
        # stage_b/stage_c compile on the first call too, and counting
        # only stage_a would report a third of the staged plan's cost
        # (the fused branch times its entire program — uniform books)
        return self._timed_first("staged", _run_checked)

    def _staged_a_checks(self, a, consumed, donated: bool | None = None):
        """Sanitizer hooks at the stage (a) boundary: contract + NaN
        tripwires, and explicit expiry of the consumed (donated)
        input so a use-after-donate raises on CPU CI too.  ``donated``
        overrides the donate_input default — the ring carry is ALWAYS
        donated regardless of the raw-input policy, so its expiry must
        not be gated on ``self._donate_input``."""
        from srtb_tpu.analysis import sanitizer as S
        S.check_contract("stage_a boundary", a, lead=2,
                         dtype=jnp.float32)
        S.check_finite("stage_a boundary", a)
        if self._donate_input if donated is None else donated:
            # sanctioned holder: expiry deletes the donated
            # buffer  # srtb-lint: disable=use-after-donate
            S.expire_donated(consumed, a)
        return a

    def _staged_tail(self, a):
        """Stages (b) + (c) under the sanitizer (the shared back half
        of run_device and the ring variants)."""
        from srtb_tpu.analysis import sanitizer as S
        b = self._run_stage_b(a)  # donates a (checked above, by value)
        S.check_contract("stage_b boundary", b, lead=2,
                         dtype=jnp.float32)
        S.check_finite("stage_b boundary", b)
        return self._enqueue_stage("c", self._jit_stage_c, b)

    # ------------------------------------------- ring execution paths

    def run_device_ring(self, carry: jnp.ndarray, new: jnp.ndarray):
        """Warm ring step: run one segment from the device-resident
        ``carry`` (the previous segment's reserved tail) plus the
        stride's freshly uploaded ``new`` bytes.  Returns
        ``((waterfall_ri, detect), next_carry)``.

        The carry is DONATED (a proven alias — see the ring comment in
        ``__init__``): callers must treat it as consumed and thread the
        returned next_carry into the following step instead."""
        if not self.ring:
            raise ValueError("ingest ring disabled for this plan "
                             "(Config.ingest_ring / no reserved tail)")
        if self.staged:
            def _run_ring():
                # whole chain under one timer (see run_device): the
                # b/c stages compile on first dispatch too
                a, nc = self._enqueue_stage(
                    "a", self._jit_stage_a_ring, self._ring_rows(carry),
                    self._ring_rows(new))
                if not self._sanitize:
                    return self._enqueue_stage(
                        "c", self._jit_stage_c,
                        self._run_stage_b(a)), nc
                # sanctioned holder: _staged_a_checks expires the
                # carry, which is donated UNCONDITIONALLY (unlike the
                # raw input)
                return self._staged_tail(self._staged_a_checks(
                    a, carry,  # srtb-lint: disable=use-after-donate
                    donated=True)), nc

            out, next_carry = self._timed_first("staged_ring",
                                                _run_ring)
        else:
            out, next_carry = self._timed_first(
                "ring",
                lambda: self._jit_ring(carry, new, self.chirp,
                                       self.chirp_w))
            if self._sanitize:
                from srtb_tpu.analysis import sanitizer as S
                # sanctioned holder: the donated carry is expired
                # here  # srtb-lint: disable=use-after-donate
                S.expire_donated(carry, out)
        return out, next_carry

    def run_device_cold(self, raw: jnp.ndarray):
        """Cold ring step: run one segment from a FULL device-resident
        upload and (re-)arm the ring — the carry is emitted by the same
        program, so a cold dispatch costs exactly segment_bytes of H2D
        and no extra slice pass.  Used for the first segment and after
        any event that breaks carry continuity (watchdog requeue,
        dispatch retry, shed segment, checkpoint resume)."""
        if not self.ring:
            raise ValueError("ingest ring disabled for this plan "
                             "(Config.ingest_ring / no reserved tail)")
        if self.staged:
            def _run_cold():
                # whole chain under one timer (see run_device)
                a, nc = self._enqueue_stage(
                    "a", self._jit_stage_a_cold, self._ring_rows(raw))
                if not self._sanitize:
                    return self._enqueue_stage(
                        "c", self._jit_stage_c,
                        self._run_stage_b(a)), nc
                # sanctioned holder: _staged_a_checks expires the
                # donated input
                return self._staged_tail(self._staged_a_checks(
                    a,
                    raw)), nc  # srtb-lint: disable=use-after-donate

            out, next_carry = self._timed_first("staged_ring_cold",
                                                _run_cold)
        else:
            out, next_carry = self._timed_first(
                "ring_cold",
                lambda: self._jit_cold(raw, self.chirp, self.chirp_w))
            if self._sanitize and self._donate_input:
                from srtb_tpu.analysis import sanitizer as S
                # sanctioned holder  # srtb-lint: disable=use-after-donate
                S.expire_donated(raw, out)
        return out, next_carry

    def _batch_ring_jit(self):
        if self._jit_batch_ring is None:
            donate = (0,) + ((1,) if self._donate_input else ())
            self._jit_batch_ring = jax.jit(self._process_batch_ring,
                                           donate_argnums=donate)
        return self._jit_batch_ring

    def _batch_cold_jit(self):
        if self._jit_batch_cold is None:
            in_donate = (0,) if self._donate_input else ()
            self._jit_batch_cold = jax.jit(self._process_batch_cold,
                                           donate_argnums=in_donate)
        return self._jit_batch_cold

    def _check_batch(self, raw, width: int):
        if self.staged:
            raise ValueError(
                "micro_batch_segments > 1 requires the fused plan "
                "(staged segments are already dispatch-amortized)")
        if raw.ndim != 2 or raw.shape[1] != width:
            raise ValueError(
                f"batch must be [B, {width}] bytes, got {raw.shape}")

    def process_batch_ring(self, carry, news):
        """Micro-batch warm ring step: B stride uploads ``news``
        [B, stride_bytes] plus the device carry run B overlapped
        segments in ONE vmapped jit call.  Returns
        ``((waterfall_ri, detect), next_carry)`` batched like
        :meth:`process_batch`; the carry is donated (consumed)."""
        if not self.ring:
            raise ValueError("ingest ring disabled for this plan "
                             "(Config.ingest_ring / no reserved tail)")
        news = self._as_device_bytes(news)
        self._check_batch(news, self.stride_bytes)
        from srtb_tpu.utils.metrics import metrics
        metrics.add("ring_carry_bytes", self.reserved_bytes)
        out, next_carry = self._timed_first(
            "batch_ring",
            lambda: self._batch_ring_jit()(carry, news, self.chirp,
                                           self.chirp_w))
        if self._sanitize:
            from srtb_tpu.analysis import sanitizer as S
            # sanctioned holder  # srtb-lint: disable=use-after-donate
            S.expire_donated(carry, out)
        return out, next_carry

    def process_batch_cold(self, raws):
        """Micro-batch cold ring step: B full-segment uploads, plan
        outputs plus the re-armed carry in one jit call."""
        if not self.ring:
            raise ValueError("ingest ring disabled for this plan "
                             "(Config.ingest_ring / no reserved tail)")
        from srtb_tpu.utils.metrics import metrics
        metrics.add("ring_cold_dispatches")  # one per full-batch upload
        raws = self._as_device_bytes(raws)
        self._check_batch(raws, self._segment_bytes)
        out, next_carry = self._timed_first(
            "batch_cold",
            lambda: self._batch_cold_jit()(raws, self.chirp,
                                           self.chirp_w))
        if self._sanitize and self._donate_input:
            from srtb_tpu.analysis import sanitizer as S
            # sanctioned holder  # srtb-lint: disable=use-after-donate
            S.expire_donated(raws, out)
        return out, next_carry

    # ---------------------------------------- self-healing retirement

    _RETIRED_PROGRAMS = (
        "_jit_process", "_jit_process_batch", "_jit_stage_a",
        "_jit_stage_b", "_jit_stage_c", "_jit_ring", "_jit_cold",
        "_jit_stage_a_ring", "_jit_stage_a_cold", "_jit_batch_ring",
        "_jit_batch_cold")

    # set by SharedPlanCache.mark_shared(): this processor serves
    # SEVERAL fleet streams at once, so one stream's plan demotion
    # must not retire the programs its neighbors are still
    # dispatching through (the bulkhead contract).  A fleet-wide
    # device reinit retires shared processors too, via force=True.
    _fleet_shared = False

    def mark_shared(self) -> "SegmentProcessor":
        """Flag this processor as fleet-shared (see retire)."""
        self._fleet_shared = True
        return self

    def retire(self, force: bool = False) -> None:
        """Disarm a processor the pipeline has replaced (plan demotion,
        promotion probe, or device reinit — resilience/demote.py).

        Every compiled-program handle is swapped for a loud failure:
        after a device reinit the old handles (in-memory AOT
        executables, jit caches) are bound to the dead backend, and a
        stray dispatch through a stale reference must raise instead of
        feeding a dead handle — or silently racing the replacement
        plan.  Host-side state (the staging pool, retained buffers) is
        left to the garbage collector: in-flight transfers may still
        reference those buffers, and a fresh processor owns fresh
        pools.

        A fleet-SHARED processor (mark_shared) is a no-op here unless
        ``force=True``: one stream swapping it out (demotion) leaves
        the other tenants' dispatch path alive; only the fleet itself
        retires the shared plan (device reinit, fleet close)."""
        if self._fleet_shared and not force:
            return
        def _dead(*_args, **_kwargs):
            raise RuntimeError(
                "SegmentProcessor retired (plan demotion / device "
                "reinit replaced it) — dispatch through the "
                "pipeline's active processor")
        for name in self._RETIRED_PROGRAMS:
            if getattr(self, name, None) is not None:
                setattr(self, name, _dead)
        self.aot_active = False

    @property
    def data_stream_count(self) -> int:
        return self.fmt.data_stream_count


def waterfall_to_numpy(wf_ri) -> np.ndarray:
    """[2, S, F, T] float32 (re, im) -> [S, F, T] complex64 on host.

    Uses the explicit D2H spelling (utils/platform.to_host) so sinks
    fetching a still-device waterfall stay visible to the transfer
    guard / sanitizer."""
    from srtb_tpu.utils.platform import to_host
    a = to_host(wf_ri)
    return (a[0] + 1j * a[1]).astype(np.complex64)
