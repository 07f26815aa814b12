"""The benchmark's sample formats, held in tier-1 (ISSUE 29, PERF.md Open
question 1a): a file of each format the queued deployments need is
unpacked alike by the program's ``unpack_streams`` and by the float64
reference, and a segment is as many bytes as the program's reader takes.
The case itself lives with the benchmark
(``benchmark/selftest/test_gen.py``); this file runs it where the driver
counts."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from benchmark.selftest.test_gen import (  # noqa: E402
    test_program_and_reference_unpack_the_file_alike as _unpacked_alike)

CASES = [(2, "simple"), (8, "simple"), (-8, "naocpsr_snap1"),
         (2, "interleaved_samples_2"), (8, "interleaved_samples_2")]


@pytest.mark.parametrize("bits, fmt", CASES)
def test_program_and_reference_unpack_the_file_alike(tmp_path, bits, fmt):
    _unpacked_alike(tmp_path, bits, fmt)
