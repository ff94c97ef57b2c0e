#!/usr/bin/env python3
"""Runs one benchmark cell of ``BENCHMARK.json`` on the chips of this machine.

    python3 bench/run.py --workload hpl.n32768.1chip --seed 7 --seconds 10 \
        --trace 0
    JAX_PLATFORMS=cpu python3 bench/run.py --workload hpl.n32768.2x2 \
        --seed 7 --seconds 1 --tiny     # CPU rehearsal at toy sizes

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (end-to-end with ``--trace 0``,
per-layer with ``--trace 1``), ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``, each compared number with its limit.
Without a TPU listed in ``bench/peaks.py``, or with fewer chips than the
cell asks for, it exits nonzero and prints no result.
"""
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
