"""Stub stages for the served loop's reader (pipeline/runtime.py): a
source slower than the device, which makes the loop ask its reader for
the segment one ahead, and a device slower than the source, which never
does."""

import threading
import time


class SlowFile:
    """The program's own file reader behind a pull that takes
    ``pull_s``.  ``raise_at`` makes the pull of that segment raise;
    ``threads`` names the thread that made each pull."""

    def __init__(self, cfg, pull_s=0.05, raise_at=None, pool=None,
                 start=None):
        from srtb_tpu.io.file_input import make_file_source

        self.reader = make_file_source(cfg, buffer_pool=pool,
                                       start_offset_bytes=start)
        self.pull_s = pull_s
        self.raise_at = raise_at
        self.pulled = 0
        self.threads = []

    pool = property(lambda self: self.reader.pool)
    logical_offset = property(lambda self: self.reader.logical_offset)

    def __iter__(self):
        return self

    def __next__(self):
        time.sleep(self.pull_s)
        if self.raise_at == self.pulled:
            raise RuntimeError("disk gone")
        seg = next(self.reader)
        self.pulled += 1
        self.threads.append(threading.current_thread().name)
        return seg

    def close(self):
        self.reader.close()


class SlowDevice:
    """A processor whose every dispatch blocks ``step_s`` first."""

    def __init__(self, inner, step_s):
        self.inner = inner
        self.step_s = step_s

    def process(self, raw):
        time.sleep(self.step_s)
        return self.inner.process(raw)
