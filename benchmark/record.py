"""What one run collects, and what the reducers read."""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class SegRec:
    """One segment handed to the program."""
    index: int                 # hand-over order within its phase
    phase: str                 # "warmup" or "window"
    file_seg: int              # which segment of the replay file it is
    pulsed: bool               # the schedule holds a pulse in it
    new_samples: int           # un-overlapped samples it brings
    segment: object = None     # the program's SegmentWork (identity)
    handover: float = 0.0      # perf_counter: bytes in host memory
    next_pull: float = 0.0     # perf_counter: the program's next pull
    done: float = 0.0          # perf_counter: sinks returned
    fired: bool | None = None  # the program's verdict: candidate or not
    detections: int = 0
    series: object = None      # the detection series, where captured
    snr_peaks: object = None
    trials: dict | None = None  # the grid's record for this segment
    buffer_address: int = 0     # of the host buffer the reader filled


class RunRecord:
    """Everything a run measured; reducers take their metric from it."""

    def __init__(self):
        self.segs: list[SegRec] = []
        self.t_start = 0.0       # process start (perf_counter)
        self.t0 = 0.0            # window opens
        self.t1 = 0.0            # window closes (t0 + seconds)
        self.seconds = 0.0
        self.setup_s = 0.0
        self.reference_wait_s = 0.0
        self.sample_rate = 0.0
        self.spans: list[dict] = []     # journal spans of the window
        self.warm_spans: list[dict] = []
        self.trace = None               # benchmark.trace.Trace or None
        self.params: dict = {}
        self.chips = 1
        self.device_kind = ""
        self.peak_bytes = 0

    def window(self) -> list[SegRec]:
        return [s for s in self.segs if s.phase == "window"]

    def completed(self) -> list[SegRec]:
        """Window segments whose results reached the sinks before the
        window closed."""
        return [s for s in self.window() if 0.0 < s.done <= self.t1]
