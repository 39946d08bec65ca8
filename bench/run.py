"""Run one benchmark cell on the TPU and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs from the root of a checkout, on the machine that holds the chips the
cell asks for.  With ``--trace 0`` the result's metrics are the cell's
end-to-end metrics; with ``--trace 1`` the window runs under the profiler
and they are its per-layer metrics.  A machine without a TPU, or with a
TPU that ``bench/peaks.json`` does not list, or with fewer chips than the
cell needs, exits non-zero and prints no result.  The last line of
standard output is one JSON object; the numbers that decided ``correct``
are the last lines of standard error and the result's last key.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import ctypes  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def steady_malloc() -> None:
    """Fix glibc's malloc thresholds for this process (32 MiB mmap, 256 MiB
    trim) before anything allocates much.  With glibc's adaptive defaults a
    process falls, by its early allocation pattern, into one of several
    speeds of the Flower client path, whose ~10 MB batch stack per ``fit``
    either reuses heap pages or page-faults fresh ones: 87, 118 or 142 ms a
    round from one run to the next on a TPU v5e host.  Fixed thresholds
    keep every run on heap pages.  A libc without ``mallopt`` is left as
    it is."""
    try:
        libc = ctypes.CDLL("libc.so.6")
        libc.mallopt(-3, 32 << 20)    # M_MMAP_THRESHOLD
        libc.mallopt(-1, 256 << 20)   # M_TRIM_THRESHOLD
    except (OSError, AttributeError):
        pass


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    steady_malloc()

    from bench import harness

    cell = harness.resolve(args.workload)
    devices = harness.check_device(cell.chips)
    harness.enable_cache()
    result = harness.measure(cell, args.seed, args.seconds, bool(args.trace),
                             devices, T_PROCESS)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
