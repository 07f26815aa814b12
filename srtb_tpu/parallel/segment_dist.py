"""The full segment step sharded over a ("dm", "seq") mesh.

This is the multi-chip version of pipeline.segment.SegmentProcessor: one
``shard_map`` program covering unpack -> distributed R2C FFT -> RFI s1 ->
DM-trial chirp multiply -> waterfall FFT -> RFI s2 -> detection, with

- ``seq``: the segment's samples/channels sharded over chips (sequence /
  context parallelism; all_to_all transposes inside the distributed FFT,
  psum reductions for the global statistics), and
- ``dm``:  independent DM trials replicating the sequence work (data
  parallelism; the cleaned spectrum is computed once per seq-shard and
  reused by every local trial).

The trials' chirps depend on the DM and the channel, never on the
segment: they are a bank [n_dm, 2, n_spectrum] sharded (dm, -, seq),
made once at construction (on the device with df64, or on the host in
float64) and read by every step.  Only a grid whose bank would crowd
the chip's memory (``holds_chirp_bank``) evaluates the df64 phase
inside the step instead.

Collective inventory per segment: 3 all_to_all (FFT transposes, seq) +
2 ppermute (Hermitian mirror, seq) + 3 psum over seq (mean power, zero
count, time series) + 3 psum over dm (the replicated trial summaries) —
all riding ICI.  Pinned by jaxpr inspection in
tests/test_parallel.py::test_dist_step_collective_inventory so a
silently-added collective fails CI.
"""

from __future__ import annotations

import os
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from srtb_tpu.config import Config
from srtb_tpu.io import formats
from srtb_tpu.ops import dedisperse as dd
from srtb_tpu.ops import detect as det
from srtb_tpu.ops import df64 as ds
from srtb_tpu.ops import fft as F
from srtb_tpu.ops import rfi
from srtb_tpu.ops import scopes as S
from srtb_tpu.ops import unpack as U
from srtb_tpu.ops import window as W
from srtb_tpu.parallel import dist_fft as DF
from srtb_tpu.parallel import dm_grid
from srtb_tpu.utils import tracing
from srtb_tpu.utils.logging import log
from srtb_tpu.utils.metrics import metrics


class DistSegmentResult(NamedTuple):
    zero_count: jnp.ndarray      # [n_dm, S]           (replicated)
    signal_counts: jnp.ndarray   # [n_dm, S, n_boxcars] (replicated)
    snr_peaks: jnp.ndarray       # [n_dm, S, n_boxcars] (replicated)
    time_series: jnp.ndarray     # [n_dm, S, T]         (dm-sharded)


def _put_sharded(host_array: np.ndarray, sharding: NamedSharding):
    """Host array -> sharded jax.Array; works in multi-controller runs
    (every process supplies its local shards by slicing the same host
    data), unlike a plain ``jax.device_put``."""
    return jax.make_array_from_callback(
        host_array.shape, sharding, lambda idx: host_array[idx])


# The largest share of a chip's memory the resident df64 chirp bank may
# take.  Compiled for a described v5e, the step with its bank is 4.0x
# (eight trials a chip) to 4.6x (two) the bank's bytes, so the two fit
# while the bank is under ~1/4.6 of the chip; 0.2 leaves the step a
# twelfth of room.  The in-step evaluation needs as much at two trials
# a chip and half a bank less at eight: past the share it is the arm
# that may still fit.  PERF.md section 4 has the reckoning.
CHIRP_BANK_HBM_SHARE = 0.2


def chirp_bank_bytes_per_chip(trials_local: int, n_spectrum: int,
                              n_seq: int) -> int:
    """Bytes one chip holds of a [n_dm, 2, n_spectrum] float32 bank
    sharded (dm, -, seq)."""
    return trials_local * 2 * (n_spectrum // n_seq) * 4


def holds_chirp_bank(bank_bytes: int, bytes_limit: int | None) -> bool:
    """Whether the df64 chirp is kept as a resident bank (made once) or
    evaluated inside every step: the bank, unless it would take more
    than ``CHIRP_BANK_HBM_SHARE`` of the chip's memory.  A platform that
    reports no limit (the CPU) holds the bank."""
    return not bytes_limit \
        or bank_bytes <= CHIRP_BANK_HBM_SHARE * bytes_limit


def _device_bytes_limit(mesh: Mesh) -> int | None:
    """What one chip of the mesh may allocate, where the platform says."""
    stats = mesh.local_devices[0].memory_stats()
    return (stats or {}).get("bytes_limit")


def _trial_chirp(dm_pair, *, n_local, f_min, df, f_c, anchor_consts):
    """One trial's df64 chirp planes [2, n_local] for this chip's seq
    shard, from the trial's (dm_hi, dm_lo) pair.  Inside shard_map."""
    return dd.chirp_factor_df64_ri(
        n_local, f_min, df, f_c, dm_pair[0],
        i0=jax.lax.axis_index("seq") * n_local, dm_lo=dm_pair[1],
        anchor_consts=anchor_consts)


class DistSegmentProcessor:
    """Builds the jitted multi-chip step for one baseband segment and a DM
    trial list."""

    def __init__(self, cfg: Config, mesh: Mesh, dm_list=None,
                 chirp_on_device: bool | None = None,
                 window_name: str = W.DEFAULT_WINDOW,
                 stage_timer: "tracing.StageTimer | None" = None):
        self.cfg = cfg
        self.mesh = mesh
        # the loop's timer (None standing alone): ``chirp_bank`` and
        # the two programs' ``first_dispatch`` spans go to it
        self.stage_timer = stage_timer
        # program -> seconds of its first dispatch, as the served
        # processor keeps them (``grid_bank``, ``grid_step``): the
        # books of utils/tracing.first_dispatch
        self.first_dispatch_s: dict[str, float] = {}
        self.fmt = formats.resolve(cfg.baseband_format_type)
        self.n_seq = mesh.shape["seq"]
        self.n_dm_devices = mesh.shape["dm"]
        if dm_list is None:
            dm_list = cfg.dm_list or [cfg.dm]
        if len(dm_list) % self.n_dm_devices:
            raise ValueError("len(dm_list) must divide by dm-axis size")
        self.dm_list = np.asarray(dm_list, dtype=np.float64)

        n = cfg.baseband_input_count
        self.n = n
        self.n_spectrum = n // 2
        self.channel_count = min(cfg.spectrum_channel_count, self.n_spectrum)
        self.watfft_len = self.n_spectrum // self.channel_count
        if self.channel_count % self.n_seq:
            raise ValueError("spectrum_channel_count must divide by seq axis")
        if self.n_spectrum % self.channel_count:
            # the single-chip path truncates the spectrum tail to a
            # whole number of waterfall rows; sharded, that truncation
            # would straddle a shard boundary (channel rows are
            # contiguous wlen-blocks of the seq-sharded spectrum), so
            # non-dividing channel counts must be rejected loudly here
            # rather than fail as a reshape deep inside shard_map
            raise ValueError(
                f"spectrum_channel_count {self.channel_count} must divide "
                f"the {self.n_spectrum}-channel spectrum for the "
                "distributed plan (power-of-two counts always do); the "
                "single-chip pipeline handles non-dividing counts by "
                "truncation")

        f_min, f_c, df = dd.spectrum_frequencies(cfg, self.n_spectrum)
        self.f_min, self.f_c, self.df = f_min, f_c, df
        # how the trials' chirp phase is evaluated: df64 on the device
        # (default follows use_emulated_fp64) or float64 on the host.
        # Either way the step reads a bank [n_dm, 2, n_spec] sharded
        # (dm, -, seq) made here, once; only a df64 bank that would
        # crowd the chip (holds_chirp_bank) is left out and the phase
        # evaluated per trial inside every step
        if chirp_on_device is None:
            chirp_on_device = cfg.use_emulated_fp64
        self.chirp_on_device = chirp_on_device
        bank_sharding = NamedSharding(mesh, P("dm", None, "seq"))
        bank_bytes = chirp_bank_bytes_per_chip(
            len(self.dm_list) // self.n_dm_devices, self.n_spectrum,
            self.n_seq)
        chirp_in_step = None
        if chirp_on_device:
            dm_hi, dm_lo = ds.from_float64(self.dm_list)
            dm_pairs = _put_sharded(
                np.stack([dm_hi, dm_lo], axis=1),    # [n_dm, 2]
                NamedSharding(mesh, P("dm", None)))
            # dm-linear anchored-Taylor coefficients (validated at the
            # grid's max |dm|): turns the per-trial chirp from ~3 df64
            # divisions/channel into one anchored update — None (exact
            # path) when the bound can't be proven or the
            # Config.chirp_exact escape hatch is set
            dm_absmax = max((abs(float(d)) for d in self.dm_list),
                            default=0.0) or 1.0
            self.chirp_anchor_consts = None \
                if getattr(cfg, "chirp_exact", False) \
                else dd.anchored_chirp_consts(
                    self.n_spectrum, f_min, df, f_c, dm_absmax,
                    unit_dm=True)
            trial_chirp = partial(
                _trial_chirp, n_local=self.n_spectrum // self.n_seq,
                f_min=f_min, df=df, f_c=f_c,
                anchor_consts=self.chirp_anchor_consts)
            if holds_chirp_bank(bank_bytes, _device_bytes_limit(mesh)):
                # a program of its own, run once: the same function
                # with the same arguments the in-step arm evaluates on
                # every segment.  One local trial after another
                # (lax.map): vmapped, this program alone compiles for
                # the chip in 200 s at 2^27 where the loop takes 3.
                # Waited for inside its spans, so ``chirp_bank`` and
                # ``compile_seconds{program="grid_bank"}`` hold its
                # compile AND its run
                bank_program = jax.jit(shard_map(
                    lambda pairs: jax.lax.map(trial_chirp, pairs),
                    mesh=mesh, in_specs=P("dm", None),
                    out_specs=bank_sharding.spec))
                with tracing.span("chirp_bank", stage_timer):
                    self.chirp_bank = tracing.first_dispatch(
                        self.first_dispatch_s, "grid_bank",
                        lambda: jax.block_until_ready(
                            bank_program(dm_pairs)), stage_timer)
            else:
                self.chirp_bank, chirp_in_step = dm_pairs, trial_chirp
                bank_bytes = 0
        else:
            with tracing.span("chirp_bank", stage_timer):
                self.chirp_bank = jax.block_until_ready(_put_sharded(
                    np.asarray(dm_grid.build_chirp_bank(
                        self.dm_list, self.n_spectrum, f_min, df, f_c)),
                    bank_sharding))
        metrics.set("chirp_bank_bytes", bank_bytes)
        how = "df64 on the device" if chirp_on_device \
            else "float64 on the host"
        where = "generated in the step" if chirp_in_step \
            else "a resident bank"
        log.info(f"[dist] mesh dm={self.n_dm_devices} seq={self.n_seq}, "
                 f"{len(self.dm_list)} trials; chirp phase {how}, "
                 f"{where}: chirp_bank_bytes {bank_bytes} a chip")

        mask = rfi.rfi_ranges_to_mask(
            rfi.eval_rfi_ranges(cfg.mitigate_rfi_freq_list), self.n_spectrum,
            cfg.baseband_freq_low, cfg.baseband_bandwidth)
        if mask is None:
            mask = np.zeros(self.n_spectrum, dtype=bool)
        self.rfi_mask = _put_sharded(mask, NamedSharding(mesh, P("seq")))

        # unpack window, sharded over seq (each device windows its own
        # contiguous sample block); watfft-length de-window divided out of
        # the dynamic spectrum after the per-row backward C2C, same as the
        # single-chip path (ref: fft_pipe.hpp:346-359)
        win = W.window_coefficients(window_name, n)
        self.window = None if win is None \
            else _put_sharded(win, NamedSharding(mesh, P("seq")))
        watfft_dewindow = W.dewindow_coefficients(window_name,
                                                  self.watfft_len)

        self.norm_coeff = rfi.normalization_coefficient(
            self.n_spectrum, self.channel_count)
        self.nsamps_reserved = dd.nsamps_reserved(cfg)
        self.time_reserved_count = self.nsamps_reserved // self.channel_count

        # who runs the local FFT legs under the a2a transposes: the env
        # knob mirrors SRTB_STAGED_ROWS_IMPL; Pallas kernels need
        # interpret mode off-TPU (CPU-mesh CI)
        from srtb_tpu.parallel.dist_fft import resolve_rows_impl
        rows_impl = resolve_rows_impl(
            os.environ.get("SRTB_DIST_ROWS_IMPL", "xla"))
        body = partial(
            self._body,
            rows_impl=rows_impl,
            len_cap=cfg.fft_len_cap or None,
            variant=self.fmt.unpack_variant,
            nbits=cfg.baseband_input_bits,
            n=self.n, n_seq=self.n_seq, n_dm_dev=self.n_dm_devices,
            chirp_in_step=chirp_in_step,
            has_window=self.window is not None,
            watfft_dewindow=watfft_dewindow,
            n_spectrum=self.n_spectrum,
            channel_count=self.channel_count,
            norm_coeff=self.norm_coeff,
            avg_threshold=cfg.mitigate_rfi_average_method_threshold,
            sk_threshold=cfg.mitigate_rfi_spectral_kurtosis_threshold,
            time_reserved_count=self.time_reserved_count,
            snr_threshold=cfg.signal_detect_signal_noise_threshold,
            max_boxcar_length=cfg.signal_detect_max_boxcar_length,
        )
        # trial summaries leave the step replicated (all_gather over dm in
        # the body) so every controller process can read them; the bulky
        # time series stays dm-sharded
        chirp_spec = P("dm", None) if chirp_in_step \
            else bank_sharding.spec
        in_specs = [P("seq"), chirp_spec, P("seq")]
        if self.window is not None:
            in_specs.append(P("seq"))
        self._step = jax.jit(shard_map(
            body, mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=(P(), P(), P(), P("dm")),
            # whole-body vma opt-out for Pallas legs: accepted scope
            # (see parallel/dist_fft.py — interpret-mode kernels trace
            # under shard_map and trip the checker on unvarying kernel
            # consts); the same collectives run checker-ON in the
            # default-xla tests
            check_vma=rows_impl == "xla"))

    # ------------------------------------------------------------------

    @staticmethod
    def _body(raw_block, chirp_block, mask_block, *rest, variant, nbits, n,
              rows_impl, len_cap, n_seq, n_dm_dev, chirp_in_step,
              n_spectrum, channel_count, norm_coeff,
              avg_threshold, sk_threshold, time_reserved_count,
              snr_threshold, max_boxcar_length,
              has_window=False, watfft_dewindow=None):
        from srtb_tpu.pipeline.segment import unpack_streams

        # ---- unpack (local; each device windows its own contiguous
        # sample block with its seq-shard of the global window) ----
        window_block = rest[0] if has_window else None
        xs = unpack_streams(raw_block, variant, nbits,
                            window_block)             # [S, n/n_seq]
        n_streams = xs.shape[0]

        # ---- distributed R2C FFT per stream, drop Nyquist ----
        m = n // 2
        log2m = m.bit_length() - 1
        n1 = 1 << (log2m // 2)
        n2 = m // n1
        specs = []
        for s in range(n_streams):  # S is tiny (1-4); loop, don't vmap
            # lane-dense even/odd pack — a [m, 2] reshape pads its minor
            # dim 2 -> 128 lanes on real TPU (64x HBM, ops/fft.py)
            with jax.named_scope(S.FFT_R2C):
                z = F.pack_even_odd(xs[s])
                zf = DF._dist_fft_block(z, axis_name="seq", n1=n1, n2=n2,
                                        n_dev=n_seq, inverse=False,
                                        rows_impl=rows_impl,
                                        len_cap=len_cap)
                spec = DF._dist_rfft_post_block(zf, axis_name="seq", m=m,
                                                n_dev=n_seq)  # [m/n_seq]
            # RFI stage 1: global mean power via psum, zap + normalize
            with jax.named_scope(S.RFI_S1):
                power = jnp.real(spec) ** 2 + jnp.imag(spec) ** 2
                mean_power = jax.lax.psum(jnp.sum(power),
                                          "seq") / n_spectrum
                zap = power > avg_threshold * mean_power
                spec = jnp.where(zap, 0.0 + 0.0j, spec * norm_coeff)
                spec = jnp.where(mask_block, 0.0 + 0.0j, spec)
            specs.append(spec)
        spec_all = jnp.stack(specs)                    # [S, m/n_seq]

        # ---- per-DM-trial: chirp, waterfall, SK, detect ----
        wlen = n_spectrum // channel_count
        ch_local = channel_count // n_seq
        t = wlen - time_reserved_count \
            if wlen > time_reserved_count else wlen

        @S.scoped(S.DETECT)
        def detect_trial(wf):
            # global zapped-channel count per stream
            zero_count = jax.lax.psum(
                jnp.sum((jnp.abs(wf[:, :, 0]) == 0).astype(jnp.int32),
                        axis=-1), "seq")               # [S]
            # global time series: sum power over all channels — local
            # pairwise tree (det.tree_sum_freq: deterministic O(log K)
            # rounding) + psum's own log2(n_seq)-level tree across shards
            ts = jax.lax.psum(
                det.tree_sum_freq(
                    jnp.real(wf[:, :, :t]) ** 2
                    + jnp.imag(wf[:, :, :t]) ** 2),
                "seq")                                  # [S, t]
            # tree-sum the time mean too (same discipline as the local
            # channel sum above; shared spelling with the single-chip
            # detect tail)
            ts = ts - det.tree_mean(ts)
            # boxcar cascade on the (replicated) time series
            lengths = det.boxcar_lengths(max_boxcar_length, t)
            acc = jnp.cumsum(ts, axis=-1)
            counts, peaks = [], []
            for b in lengths:
                series = ts if b == 1 \
                    else acc[..., b:] - acc[..., :-b]
                c, p = det.count_signal(series, snr_threshold)
                counts.append(c)
                peaks.append(p)
            return (zero_count, jnp.stack(counts, axis=-1),
                    jnp.stack(peaks, axis=-1), ts)

        def one_trial(chirp_ri):
            with jax.named_scope(S.CHIRP):
                s = spec_all * jax.lax.complex(chirp_ri[0], chirp_ri[1])
            # local channels are complete contiguous sub-bands
            with jax.named_scope(S.WATERFALL):
                wf = s.reshape(n_streams, ch_local, wlen)
                wf = jnp.fft.ifft(wf, axis=-1, norm="forward")
                if watfft_dewindow is not None:
                    wf = wf / watfft_dewindow
            return detect_trial(
                rfi.mitigate_rfi_spectral_kurtosis(wf, sk_threshold))

        if chirp_in_step is not None:
            # the grid's bank was too large to keep: chirp_block holds
            # the trials' (dm_hi, dm_lo) pairs and the planes are made
            # here, on every segment
            chirp_block = jax.vmap(chirp_in_step)(chirp_block)
        zc, counts, peaks, ts = jax.vmap(one_trial)(chirp_block)

        # replicate the small per-trial summaries across the dm axis
        # (multi-host: every controller must be able to materialize them).
        # scatter-into-zeros + psum is replication the VMA checker can
        # prove invariant, unlike all_gather
        dm_idx = jax.lax.axis_index("dm")
        trials_local = chirp_block.shape[0]

        def replicate_trials(x):
            full = jnp.zeros((trials_local * n_dm_dev,) + x.shape[1:],
                             x.dtype)
            full = jax.lax.dynamic_update_slice_in_dim(
                full, x, dm_idx * trials_local, axis=0)
            return jax.lax.psum(full, "dm")

        return (replicate_trials(zc), replicate_trials(counts),
                replicate_trials(peaks), ts)

    # ------------------------------------------------------------------

    def stage_input(self, raw) -> jax.Array:
        """Host bytes -> the mesh, sharded over "seq" (so every dm-row
        of chips gets its copy), counted in ``h2d_bytes`` like the
        single-chip path's upload."""
        staged = _put_sharded(np.asarray(raw, dtype=np.uint8),
                              NamedSharding(self.mesh, P("seq")))
        metrics.add("h2d_bytes", sum(s.data.nbytes
                                     for s in staged.addressable_shards))
        return staged

    def process(self, raw) -> DistSegmentResult:
        """One segment through the sharded step.  ``raw``: host bytes,
        or a segment ``stage_input`` already uploaded (a loop that
        times the upload and the step's call apart,
        DMSearchPipeline.run)."""
        if not isinstance(raw, jax.Array):
            raw = self.stage_input(raw)
        args = [raw, self.chirp_bank, self.rfi_mask]
        if self.window is not None:
            args.append(self.window)
        return DistSegmentResult(*tracing.first_dispatch(
            self.first_dispatch_s, "grid_step",
            lambda: self._step(*args), self.stage_timer))
