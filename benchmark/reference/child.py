"""The reference as a child process: ``python child.py REQUEST.json``.

Started during set-up (as soon as the data are written and, in the served
cell, the pipeline is constructed) and joined before the window, so most
of its seconds hide under the warm-up and the rest are kept out of
``setup_s``; it imports NumPy (and SciPy's FFT) only, never JAX, so it never
touches a chip.  The request names the replay file, the configuration's
numbers, the segments to compute (offset in bytes, the DMs wanted) and
where the answers go (one ``.npz``).  A file of several streams is
de-interleaved here, in plain NumPy, and the chain runs on each stream.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.reference import chain  # noqa: E402


def compute(req: dict) -> dict:
    p = req["params"]
    workers = chain.default_workers()
    seg_bytes = chain.segment_bytes(p)
    out = {}
    for item in req["segments"]:
        t0 = time.perf_counter()
        raw = np.fromfile(req["file"], dtype=np.uint8, count=seg_bytes,
                          offset=int(item["offset_bytes"]))
        if raw.size != seg_bytes:
            raise RuntimeError(f"short read at {item['offset_bytes']}")
        for s, stream in enumerate(chain.deinterleave(raw, p)):
            res = chain.segment(stream, p, dms=item["dms"], workers=workers,
                                low=req.get("low", ""))
            tag = chain.stream_tag(item["file_seg"], s)
            for i, r in enumerate(res):
                out[f"{tag}.t{i}.series"] = r["time_series"]
                out[f"{tag}.t{i}.snr_peaks"] = np.asarray(r["snr_peaks"])
                out[f"{tag}.t{i}.counts"] = np.asarray(r["signal_counts"])
                out[f"{tag}.t{i}.peak_bins"] = np.asarray(r["peak_bins"])
                out[f"{tag}.t{i}.zero_count"] = np.asarray(r["zero_count"])
        out[f"s{item['file_seg']}.seconds"] = np.asarray(
            time.perf_counter() - t0)
    return out


def main(argv) -> int:
    with open(argv[1]) as f:
        req = json.load(f)
    out = compute(req)
    tmp = req["out"] + ".tmp.npz"
    np.savez(tmp, **out)
    os.replace(tmp, req["out"])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
