"""The comparison that decides ``correct``.

Every number compared is printed beside its limit, in every run.  The
limits are data (the workload file's ``check.limits``, each with the
readings it was set from in PERF.md); this file only says what is
compared:

- ``series_gap``: the widest gap between the program's detection time
  series and the float64 reference's, in units of the reference series'
  noise (1.4826 x its median absolute deviation) — on the warm-up pulsed
  segment and on the sampled segments of the window;
- ``snr_gap``: the widest relative gap between the program's peak S/N
  and the reference's, over the boxcar lengths (served path) or over the
  compared trials (DM grid);
- ``bin_gap``: samples between the two peaks (exact: limit 0);
- the schedule: a pulsed segment fires and leaves its candidate files,
  a quiet one does not; the DM curve peaks at the injected DM.  These
  count ``failed`` segments.
"""

from __future__ import annotations

import numpy as np


class Checks:
    """Prints each number beside its limit, keeps the verdict and what
    the result line carries: per limit the widest reading, and the text
    of every requirement that failed."""

    MAX_TEXT, MAX_FAILURES = 200, 16

    def __init__(self, limits: dict):
        self.limits = limits
        self.ok = True
        self.failed_segments: set = set()
        self.widest: dict = {}       # limit key -> widest value read
        self.failures: list = []     # texts of failed requirements

    def number(self, name: str, value: float, limit_key: str) -> None:
        limit = float(self.limits[limit_key])
        value = float(value)
        good = bool(np.isfinite(value)) and value <= limit
        self.ok = self.ok and good
        old = self.widest.get(limit_key)
        if old is None or not value <= old:     # a NaN is the widest
            self.widest[limit_key] = value
        print(f"[check] {name} = {value!r} (limit {limit!r}) "
              f"{'ok' if good else 'FAIL'}", flush=True)

    def require(self, cond: bool, what: str, segment=None) -> None:
        if not cond:
            self.ok = False
            if segment is not None:
                self.failed_segments.add(segment)
            self.failures.append(what[:self.MAX_TEXT])
            print(f"[check] FAIL: {what}", flush=True)

    def _failed_texts(self) -> list:
        more = len(self.failures) - self.MAX_FAILURES
        return self.failures[:self.MAX_FAILURES] + (
            [f"... and {more} more"] if more > 0 else [])

    def summary(self) -> dict:
        """``{"<limit key>": [widest value, limit], ..., "failed":
        [texts]}``: the result line's ``checks``.  A reading that is not
        finite is written as text (JSON has no such number)."""
        out = {key: [value if np.isfinite(value) else repr(value),
                     float(self.limits[key])]
               for key, value in self.widest.items()}
        out["failed"] = self._failed_texts()
        return out

    def lines(self) -> list:
        """The same as ``[check]`` lines, for the end of stderr."""
        rows = []
        for key, value in self.widest.items():
            limit = float(self.limits[key])
            rows.append(f"[check] widest {key} = {value!r} (limit "
                        f"{limit!r}) {'ok' if value <= limit else 'FAIL'}")
        return rows + [f"[check] FAIL: {t}" for t in self._failed_texts()]


def noise_scale(series: np.ndarray) -> float:
    """1.4826 x the median absolute deviation: the series' noise level,
    whatever pulse stands in it."""
    s = np.asarray(series, dtype=np.float64)
    return 1.4826 * float(np.median(np.abs(s - np.median(s))))


def series_gap(got: np.ndarray, ref: np.ndarray) -> float:
    got = np.asarray(got, dtype=np.float64).reshape(-1)
    ref = np.asarray(ref, dtype=np.float64).reshape(-1)
    if got.shape != ref.shape:
        return float("inf")
    return float(np.max(np.abs(got - ref)) / noise_scale(ref))


def relative_gap(got, ref) -> float:
    got = np.asarray(got, dtype=np.float64).reshape(-1)
    ref = np.asarray(ref, dtype=np.float64).reshape(-1)
    if got.shape != ref.shape:
        return float("inf")
    return float(np.max(np.abs(got - ref) / np.abs(ref)))
