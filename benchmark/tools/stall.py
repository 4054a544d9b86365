#!/usr/bin/env python3
"""Run one cell with its process kept off the processor for a while
inside the window: what a stalled host costs the cell's number.

    python3 benchmark/tools/stall.py --after 5 --for 2 -- \
        python3 benchmark/run.py --workload <cell> --seed 7 \
        --seconds 20 --trace 0

The command runs as a child in a process group of its own. Once it
prints ``window opens`` the tool waits ``--after`` seconds, stops the
whole group (SIGSTOP: every thread, the runtime's too) for ``--for``
seconds and lets it go on. The child's output is passed through; the
exit code is the child's. Not part of any run of the benchmark.
"""

from __future__ import annotations

import argparse
import contextlib
import os
import signal
import subprocess
import sys
import threading
import time

MARK = "window opens"


def hold(pgid: int, after: float, length: float) -> None:
    time.sleep(after)
    with contextlib.suppress(ProcessLookupError):   # it may have ended
        os.killpg(pgid, signal.SIGSTOP)
        print(f"[stall] stopped for {length} s", flush=True)
        time.sleep(length)
        os.killpg(pgid, signal.SIGCONT)
        print("[stall] continued", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--after", type=float, default=5.0)
    ap.add_argument("--for", dest="length", type=float, default=2.0)
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    cmd = [c for c in args.command if c != "--"]
    child = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True,
                             start_new_session=True)
    holder = None
    try:
        for line in child.stdout:
            sys.stdout.write(line)
            sys.stdout.flush()
            if holder is None and MARK in line and args.length > 0:
                holder = threading.Thread(
                    target=hold, args=(child.pid, args.after, args.length))
                holder.start()
        return child.wait()
    finally:
        if holder is not None:
            holder.join()
        if child.poll() is None:
            with contextlib.suppress(ProcessLookupError):
                os.killpg(child.pid, signal.SIGKILL)
            child.wait()


if __name__ == "__main__":
    raise SystemExit(main())
