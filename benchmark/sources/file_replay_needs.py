"""``file_replay_needs``: ``file_replay``, for a cell that a program can
run only with something the cell's own PR brought to it.

The cell's file says what (``source.needs``: dotted names under
``srtb_tpu``) and what happens without it (``source.needs_why``).  Where
the program has them all this is ``file_replay`` and nothing else: the
same reader, the same segments, the same stamps.  Where one is missing
the run ends here BY ITSELF, before the program is built and before the
reference's child is started: the reason on the run's own report, a
traceback on stderr, no result line and exit code 1 (``run.py``).

Why a source kind checks the program: the drivers, ``spec.py`` and
``harness.py`` are the accepted benchmark's and a cell's PR may not edit
them; a source kind is the one thing a cell names that is built before
the program is (``drivers/served.py``).  The alternative it replaces is
the HOST ending the whole command: a program before PR 44 holds the
4.29 GB waterfall of a 2^30-sample segment's candidate three times over,
and beside the reference's 19.5 GB child the one-chip machine's 40 GiB
killed it (exit 137) in four runs of six (PERF.md section 6, PR 44),
which the driver's check of a new cell on the parent commit takes for a
fault of the cell.  A program that cannot run a cell says so and exits;
it is not left to be killed.
"""

from __future__ import annotations

import importlib

from benchmark.harness import say
from benchmark.sources.file_replay import FileReplay


class CannotRunCell(RuntimeError):
    pass


def missing(names) -> list:
    """Those of the dotted ``names`` that do not resolve: the module does
    not import or lacks the attribute."""
    out = []
    for dotted in names:
        module, _, attr = dotted.rpartition(".")
        try:
            getattr(importlib.import_module(module), attr)
        except (ImportError, AttributeError):
            out.append(dotted)
    return out


class FileReplayNeeds(FileReplay):
    def __init__(self, cfg, layout, record, params: dict):
        lacks = missing(params["needs"])
        if lacks:
            say(f"CANNOT RUN THIS CELL: the program lacks {lacks}.  "
                f"{params['needs_why']}")
            raise CannotRunCell(
                f"the program lacks {lacks}, which this cell needs; the "
                "run ends here by itself")
        say(f"the program has what the cell needs: {list(params['needs'])}")
        super().__init__(cfg, layout, record, params)


KINDS = {"file_replay_needs": FileReplayNeeds}
