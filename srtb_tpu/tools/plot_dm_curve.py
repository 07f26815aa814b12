"""Plot the DM-search SNR curve from a ``*dm_trials.jsonl`` record.

The classic pulsar-search acceptance artifact: peak S/N per DM trial,
peaking at the true dispersion measure.  The reference searches a single
configured DM in production (ref: srtb_config_1644-4559.cfg:22); the DM
grid (`--dm_list`) is this repo's scale-out addition, and this plot is
its visual proof — the curve must peak at the injected DM and fall off
to the sides (decoherence from the DM error, ref dispersion math:
coherent_dedispersion.hpp:87-128).

Usage: python -m srtb_tpu.tools.plot_dm_curve TRIALS.jsonl [OUT.png]
"""

from __future__ import annotations

import json
import sys



def plot(trials_path: str, out_path: str | None = None) -> str:
    records = []
    with open(trials_path) as f:
        for line in f:
            line = line.strip()
            if line:
                records.append(json.loads(line))
    if not records:
        raise SystemExit(f"no trial records in {trials_path}")
    out_path = out_path or trials_path + ".png"

    import matplotlib
    matplotlib.use("Agg")
    import matplotlib.pyplot as plt

    fig, ax = plt.subplots(figsize=(9, 5.5))
    for rec in records:
        ax.plot(rec["dm_list"], rec["peak_snr"], marker="o",
                label=f"segment {rec['segment']}")
        ax.axvline(rec["best_dm"], color="0.7", lw=0.8, zorder=0)
    ax.set_xlabel("trial DM (pc cm$^{-3}$)")
    ax.set_ylabel("peak S/N")
    best = max(records, key=lambda r: r["best_snr"])
    ax.set_title(f"DM search: best {best['best_dm']} "
                 f"(S/N {best['best_snr']:.1f})")
    ax.legend(loc="best", fontsize=8)
    ax.grid(alpha=0.3)
    fig.tight_layout()
    fig.savefig(out_path, dpi=120)
    plt.close(fig)
    return out_path


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if not argv:
        print(__doc__, file=sys.stderr)
        return 2
    print(plot(argv[0], argv[1] if len(argv) > 1 else None))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
