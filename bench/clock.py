"""Phase times of one run, each logged to standard error as it ends, as
``bench phase <name>_s=<seconds>``. The harness times the window, the
trace's reduction and the check with it; a driver times the parts of its
check (the copy off the devices into the global layout, each compared
number)."""
from __future__ import annotations

import sys
import time
from contextlib import contextmanager


@contextmanager
def phase(name: str):
    t0 = time.perf_counter()
    try:
        yield
    finally:
        print(f"bench phase {name}_s={time.perf_counter() - t0!r}",
              file=sys.stderr, flush=True)
