"""Bytes the algorithm has to move per segment, from the shapes alone.

One function, kept with the yardstick: the count is what the chain's
stages exchange when each runs once over device memory — the packed
input read once, each FFT's input and output once, one read of the
waterfall for detection.  A program whose stages fuse (an unpack folded
into the FFT's first pass, a detection that never writes the waterfall)
moves less, so a share of the peak computed from this count can pass
100 % only by such fusion; it is named a share of bandwidth, not a
kernel's roofline.
"""

from __future__ import annotations


def segment_bytes_per_chip(p: dict, chips: int = 1) -> float:
    """``p``: reference.chain.params_from_config's dict.  With several
    chips the trials divide among them and every chip transforms the
    whole segment (the DM grid's ("dm", "seq") mesh with seq = 1)."""
    n = p["n"]
    streams = p["streams"]              # every stream runs the chain
    trials = max(1, len(p["dm_list"]))
    per_chip = -(-trials // chips)
    packed = n * abs(p["bits"]) / 8     # uint8 in
    r2c = 4 * n + 8 * (n // 2)          # f32 in, complex64 out
    c2c = 2 * 8 * (n // 2)              # complex64 in and out, per trial
    detect = 8 * (n // 2)               # one read of the waterfall
    return streams * (packed + r2c + per_chip * (c2c + detect))
