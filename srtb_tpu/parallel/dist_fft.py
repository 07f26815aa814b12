"""Sequence-sharded large 1-D FFT over a device mesh.

The reference's hardest resource constraint is the single 2^30-point R2C
FFT (SURVEY.md §7 hard part #1); one chip's HBM bounds the segment size.
This module removes that bound: the four-step factorization
(ops.fft.four_step_fft) distributed over the ``seq`` mesh axis with
``shard_map`` + ``all_to_all`` transposes — the TPU-native analog of
sequence/context parallelism.  Layout (n = n1 * n2, D devices):

  x flat, sharded in j1-blocks        [n1/D, n2]   per device
  all_to_all transpose             -> [n2/D, n1]
  local FFT (length n1, columns of A) + twiddle exp(-2*pi*i*k1*j2/n)
  all_to_all transpose back        -> [n1/D, n2]   rows now B[k1, j2]
  local FFT (length n2)            -> C[k1, k2]
  all_to_all transpose             -> natural order X[k2*n1+k1]

The R2C variant packs 2m reals as m complex, runs the distributed C2C,
and applies the Hermitian post-process (ref: fft/fft_1d_r2c_post_process.
hpp:33-82) with the conjugate-mirrored spectrum materialized via a global
flip (local flip + ppermute device reversal + edge-roll).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from srtb_tpu.ops.fft import _fft_minor, _phase_exp, pack_even_odd


def _local_transpose_a2a(x_block, axis_name, n_dev):
    """Global [R, C] -> [C, R] transpose of a row-sharded matrix:
    split local rows' columns into n_dev chunks, all_to_all, reassemble."""
    r_loc, c = x_block.shape
    c_loc = c // n_dev
    # [r_loc, n_dev, c_loc] -> a2a over chunk axis -> [n_dev, r_loc, c_loc]
    t = x_block.reshape(r_loc, n_dev, c_loc)
    t = jax.lax.all_to_all(t, axis_name, split_axis=1, concat_axis=0,
                           tiled=False)
    # t: [n_dev, r_loc, c_loc] where first axis = source device (row block)
    # global columns of this device: [c_loc rows] x [R = n_dev*r_loc]
    t = jnp.transpose(t, (2, 0, 1)).reshape(c_loc, n_dev * r_loc)
    return t


def _dist_fft_block(x_block, *, axis_name, n1, n2, n_dev, inverse,
                    rows_impl="xla", len_cap=None):
    """shard_map body: x_block [n_local] = this device's j1-block rows,
    viewed as [n1/D, n2].  ``rows_impl`` selects who runs the local leg
    FFTs (ops.fft._fft_minor dispatch): "xla", or "pallas"/
    "pallas_interpret" for the VMEM row kernel — the same per-chip
    kernels the single-chip plans use, now under the a2a transposes.
    ``len_cap`` threads ops.fft._fft_minor's XLA length cap through the
    in-shard legs (tiny-shape dryruns force the four-step recursion a
    production 2^30 shard takes by lowering it)."""
    a = x_block.reshape(n1 // n_dev, n2)

    # transpose so columns (j1 axis) become local rows
    at = _local_transpose_a2a(a, axis_name, n_dev)          # [n2/D, n1]
    bt = _fft_minor(at, inverse, rows_impl, len_cap)
    # twiddle: row j2 (global), column k1: exp(sign*2*pi*i*k1*j2/n).
    # The residue k1*j2 < n1*n2 = n fits int32 exactly for n <= 2^30, and
    # _phase_exp splits it hi/lo so the f32 phase stays exact at large n
    # (same precision discipline as ops/fft.py:_twiddle; a plain f32
    # ratio product here diverges for shards >= 2^24).
    idx = jax.lax.axis_index(axis_name)
    j2 = (idx * (n2 // n_dev)
          + jax.lax.iota(jnp.int32, n2 // n_dev)).astype(jnp.int32)
    k1 = jax.lax.iota(jnp.int32, n1)
    r = j2[:, None] * k1[None, :]
    tw = _phase_exp(r, n1 * n2, 1.0 if inverse else -1.0)
    bt = bt * tw

    # transpose back: rows k1 local again
    b = _local_transpose_a2a(bt, axis_name, n_dev)          # [n1/D, n2]
    c = _fft_minor(b, inverse, rows_impl, len_cap)
    # natural order: X[k2*n1 + k1] = C[k1, k2] -> global transpose
    ct = _local_transpose_a2a(c, axis_name, n_dev)          # [n2/D, n1]
    return ct.reshape(-1)


def resolve_rows_impl(impl: str) -> str:
    """Validate + resolve a distributed leg implementation: typos must
    fail loudly (the segment.py:_resolve_rows_impl rule), and "pallas"
    downgrades to interpret mode off-TPU (utils.platform.on_accelerator
    is the single home of the backend set)."""
    if impl not in ("xla", "pallas", "pallas_interpret"):
        raise ValueError(
            f"unknown SRTB_DIST_ROWS_IMPL / rows_impl {impl!r}")
    from srtb_tpu.utils.platform import on_accelerator
    if impl == "pallas" and not on_accelerator():
        return "pallas_interpret"
    return impl


def dist_fft(x, mesh: Mesh, axis_name: str = "seq",
             inverse: bool = False, rows_impl: str = "xla",
             len_cap: int | None = None):
    """Distributed unnormalized C2C FFT of a 1-D power-of-two array sharded
    (or shardable) over ``axis_name``.  Returns the spectrum in natural
    order with the same sharding."""
    n = x.shape[-1]
    n_dev = mesh.shape[axis_name]
    rows_impl = resolve_rows_impl(rows_impl)
    if n > 1 << 30:
        # the twiddle residue j2*k1 is int32; products stay < n, so 2^30
        # is a safe static ceiling (2^31 would need int64 residues)
        raise ValueError(f"n={n} exceeds the int32 twiddle-residue ceiling "
                         "of 2^30; split the segment or use int64 residues")
    log2n = n.bit_length() - 1
    n1 = 1 << (log2n // 2)
    n2 = n // n1
    if n1 % n_dev or n2 % n_dev:
        raise ValueError(f"n1={n1}, n2={n2} must divide by {n_dev} devices")
    # With Pallas legs the vma checker is off for the WHOLE body — an
    # accepted scope, not an oversight: jax 0.9 can annotate a
    # pallas_call's outputs (ShapeDtypeStruct(vma=...)), but in
    # interpret mode (all CPU CI) the kernel body is traced under
    # shard_map, where unvarying kernel consts meet varying refs and
    # the checker itself rejects the mul ("requires varying manual
    # axes to match").  Every collective here is identical across
    # rows_impls and covered with the checker ON by the default-xla
    # tests (tests/test_dist_fft.py).
    fn = shard_map(
        partial(_dist_fft_block, axis_name=axis_name, n1=n1, n2=n2,
                n_dev=n_dev, inverse=inverse, rows_impl=rows_impl,
                len_cap=len_cap),
        mesh=mesh, in_specs=P(axis_name), out_specs=P(axis_name),
        check_vma=rows_impl == "xla")
    return fn(x.astype(jnp.complex64))


# ----------------------------------------------------------------
# distributed R2C with Hermitian post-process
# ----------------------------------------------------------------

def _global_conj_mirror(f_block, axis_name, n_dev):
    """Given F sharded in blocks, return G with G[k] = conj(F[(m-k) % m]),
    same sharding.  Global flip = local flip + device-order reversal; the
    ``% m`` index shift is a global roll right by one element."""
    rev = jnp.flip(f_block, axis=-1)
    perm = [(d, n_dev - 1 - d) for d in range(n_dev)]
    rev = jax.lax.ppermute(rev, axis_name, perm)   # global flip(F)
    # roll right by 1: each device receives the last element of the
    # previous device's block (cyclic)
    shift_perm = [(d, (d + 1) % n_dev) for d in range(n_dev)]
    prev_last = jax.lax.ppermute(rev[..., -1:], axis_name, shift_perm)
    rolled = jnp.concatenate([prev_last, rev[..., :-1]], axis=-1)
    return jnp.conj(rolled)


def _dist_rfft_post_block(zf_block, *, axis_name, m, n_dev):
    """Hermitian reconstruction on the m-point C2C spectrum of packed
    reals; emits m bins (Nyquist dropped, matching segment_rfft)."""
    f_k = zf_block
    f_mk = _global_conj_mirror(zf_block, axis_name, n_dev)
    even = 0.5 * (f_k + f_mk)
    odd = -0.5j * (f_k - f_mk)
    idx = jax.lax.axis_index(axis_name)
    k = (idx * (m // n_dev)
         + jax.lax.iota(jnp.int32, m // n_dev)).astype(jnp.int32)
    # w[k] = exp(-i*pi*k/m) = exp(-2*pi*i*k/(2m)) via the exact hi/lo
    # phase split (a raw f32 k/m loses bits of phase for m >= 2^24).
    w = _phase_exp(k, 2 * m, -1.0)
    return even + w * odd


def dist_rfft_drop_nyquist(x, mesh: Mesh, axis_name: str = "seq",
                           rows_impl: str = "xla",
                           len_cap: int | None = None):
    """Distributed R2C of 2m reals -> m complex bins (drop-Nyquist
    convention of the segment FFT, ref: fft_pipe.hpp:75-77)."""
    n = x.shape[-1]
    m = n // 2
    n_dev = mesh.shape[axis_name]

    def pack(blk):
        # lane-dense even/odd pack — a [m, 2] reshape pads its minor dim
        # 2 -> 128 lanes on real TPU (64x HBM), see ops/fft.pack_even_odd.
        # Known future work: for sub-byte input the single-chip path now
        # skips sample order entirely (ops/fft.rfft_subbyte blocked
        # planes); the distributed analog would hold each shard as field
        # planes and absorb the cross-plane butterfly after dist_fft,
        # but that changes the output sharding layout (k = k2*M + k1
        # interleaves device blocks) and with it every downstream
        # index computation in segment_dist — deferred until real
        # multi-chip hardware is available to measure on.
        return pack_even_odd(blk)

    z = shard_map(pack, mesh=mesh, in_specs=P(axis_name),
                  out_specs=P(axis_name))(x.astype(jnp.float32))
    zf = dist_fft(z, mesh, axis_name, rows_impl=rows_impl,
                  len_cap=len_cap)
    post = shard_map(
        partial(_dist_rfft_post_block, axis_name=axis_name, m=m,
                n_dev=n_dev),
        mesh=mesh, in_specs=P(axis_name), out_specs=P(axis_name))
    return post(zf)
