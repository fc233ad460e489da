#!/usr/bin/env python3
"""For whoever sets a limit or records a trace; never part of a benchmark run.

    python3 -m perfbench.tools [--control float32] [--keep-trace PATH] -- \\
        --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs the cell as ``perfbench.run`` does (what follows ``--`` is its command
line) and, with ``--control``, puts each reference's answers in the named
lower precision in the engine's place on the window's own requests, through
the same comparison: the ``control`` line has to say ``"within": false``.
``--keep-trace`` keeps the ``.xplane.pb`` of a ``--trace 1`` run.
"""
from __future__ import annotations

import argparse
import sys

from perfbench import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--control", default=None)
    ap.add_argument("--keep-trace", default=None)
    ap.add_argument("run_args", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    return run.main([a for a in args.run_args if a != "--"],
                    control=args.control, keep_trace=args.keep_trace)


if __name__ == "__main__":
    sys.exit(main())
