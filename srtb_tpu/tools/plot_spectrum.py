"""Offline spectrum plotting helper (ref: src/plot_spectrum.py).

Reads the ``<prefix><counter>.<i>.npy`` complex waterfalls written by
WriteSignalSink and renders dynamic-spectrum images (matplotlib if
available, else the built-in PNG writer).
"""

from __future__ import annotations

import glob
import sys

import numpy as np


def plot_one(path: str) -> str:
    wf = np.load(path)
    power = np.abs(wf) ** 2
    out_path = path + ".png"
    try:
        import matplotlib
        matplotlib.use("Agg")
        import matplotlib.pyplot as plt
        fig, ax = plt.subplots(figsize=(12, 7))
        ax.imshow(power, aspect="auto", origin="lower",
                  interpolation="nearest")
        ax.set_xlabel("time sample")
        ax.set_ylabel("frequency channel")
        fig.savefig(out_path, dpi=120)
        plt.close(fig)
    except ImportError:
        from srtb_tpu.gui.waterfall import write_png
        from srtb_tpu.ops import spectrum as sp
        import jax.numpy as jnp
        img = power / (2 * max(power.mean(), 1e-30))
        pix = np.asarray(sp.generate_pixmap(jnp.asarray(
            img.astype(np.float32))))
        write_png(out_path, pix)
    return out_path


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    paths = []
    for pattern in (argv or ["*.npy"]):
        paths.extend(glob.glob(pattern))
    for p in sorted(paths):
        print(plot_one(p))
    return 0


if __name__ == "__main__":
    sys.exit(main())
