#!/usr/bin/env python3
"""Runs ``bench/run.py`` as a child process and times it from outside: the
wall time from its start to its result line and to its exit, which no line
of its own can give (the runtime's teardown and the chips' release come
after its last line).

    python3 bench/wall.py --workload hpl.n32768.1chip --seed 7 \
        --seconds 30 --trace 0

Passes the child's standard error through as it comes, then its standard
output, then one line of its own to standard output:
``wall {"rc": ..., "result_s": ..., "exit_s": ..., "tail_s": ...,
"phases": {...}}``, where ``phases`` sums the child's ``bench phase``
times by name and adds the set-up split of its ``bench setup_s`` line. It
does not import JAX, so the child holds the chips.
"""
import json
import re
import subprocess
import sys
import threading
import time
from pathlib import Path

RUN = Path(__file__).resolve().parent / "run.py"
TIMES = re.compile(r"(\w[\w.]*)_s=([0-9.e+-]+)")


def phases(stderr_lines) -> dict:
    out = {}
    for line in stderr_lines:
        if line.startswith(("bench phase ", "bench setup_s=",
                            "bench scopes_s=")):
            for k, v in TIMES.findall(line):
                out[k] = out.get(k, 0.0) + float(v)
    return out


def main(argv) -> int:
    t0 = time.perf_counter()
    child = subprocess.Popen([sys.executable, str(RUN), *argv],
                             stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                             text=True)
    err = []

    def pump():
        for line in child.stderr:
            err.append(line)
            sys.stderr.write(line)
            sys.stderr.flush()

    reader = threading.Thread(target=pump)
    reader.start()
    out, result_s = [], None
    for line in child.stdout:
        out.append(line)
        if line.startswith("{"):
            result_s = time.perf_counter() - t0
    rc = child.wait()
    exit_s = time.perf_counter() - t0
    reader.join()
    sys.stdout.write("".join(out))
    print("wall " + json.dumps({
        "rc": rc, "result_s": result_s, "exit_s": exit_s,
        "tail_s": None if result_s is None else exit_s - result_s,
        "phases": phases(err)}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
