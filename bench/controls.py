"""Readings that set a cell's correctness limits, at the cell's own size.

    python3 bench/controls.py --workload <cell> --seeds 12 --control-seeds 3

For each seed, in one process: the program's first rounds as a run's
set-up drives them (no window), and the plain float32 reference of the
same rounds; the numbers of ``bench/reference/compare.py`` between them
are the sound readings.  On the first ``--control-seeds`` seeds also:
the control (the reference computed in bfloat16, put in the program's
place) and each fault the cell can have, planted in the program
(``bench/cells/common.py``), each against the same float32 reference.
Prints one line per reading and a JSON summary with the largest sound
reading and the smallest control and fault reading of each number.

    python3 bench/controls.py --workload <cell> --seeds 3 --witness-highest

looks for the cause of a sound gap instead: for each seed, the program's
first rounds at the configuration's precision and again under
``default_matmul_precision("highest")``, each against the one float32
reference, with the five leaves that read the largest gaps and, for a
top-k uplink, the share of moved entries that only one side moved.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

# the faults each runner's cells can have (a state left unchanged reads 1
# by construction and is planted only in the CPU test)
FAULTS = {"scanned": ("half_batch",), "flower": ("half_batch", "unweighted")}


def readings(cell, seeds, control_seeds, devices, log=print) -> dict:
    import jax
    import jax.numpy as jnp

    from bench import harness
    from bench.reference import compare

    out = {"sound": [], "control": [], "faults": {}}
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        prog = harness.make_runner(cell, seed, devices)
        prog.setup()
        prog.release()
        p0, ref = prog.reference_rounds()
        nums = compare.training_numbers(p0, prog.readings, ref)
        out["sound"].append(nums)
        log(f"[sound] seed {seed}: {json.dumps(nums)} ({time.perf_counter() - t0:.1f} s)")
        if i >= control_seeds:
            continue
        _, low = prog.reference_rounds(dtype=jnp.bfloat16)
        nums = compare.training_numbers(p0, low, ref)
        out["control"].append(nums)
        log(f"[control bf16] seed {seed}: {json.dumps(nums)}")
        for fault in FAULTS[cell.traffic["entry"]]:
            bad = harness.make_runner(cell, seed, devices, faults=(fault,))
            bad.setup()
            bad.release()
            nums = compare.training_numbers(p0, bad.readings, ref)
            out["faults"].setdefault(fault, []).append(nums)
            log(f"[fault {fault}] seed {seed}: {json.dumps(nums)}")
        del prog
        jax.clear_caches()
    keys = out["sound"][0].keys()
    out["lower"] = {k: max(r[k] for r in out["sound"]) for k in keys}
    out["control_min"] = {k: min(r[k] for r in out["control"]) for k in keys} if out["control"] else {}
    out["fault_min"] = {f: {k: min(r[k] for r in rs) for k in keys}
                        for f, rs in out["faults"].items()}
    return out


def witness(cell, seeds, devices, log=print) -> list[dict]:
    """For each seed, the gaps of the program at the cell's precision and at
    HIGHEST against one reference, with the five worst leaves."""
    import jax

    from bench import harness
    from bench.reference import compare

    topk = cell.traffic.get("codec", {}).get("name") == "topk"
    out = []
    for seed in seeds:
        t0 = time.perf_counter()
        row = {"seed": seed}
        runs = {}
        for precision in ("default", "highest"):
            prog = harness.make_runner(cell, seed, devices)
            try:
                with jax.default_matmul_precision(precision):
                    prog.setup()
            except Exception as e:  # a program that fails here gives no reading
                log(f"[witness] seed {seed} {precision}: {type(e).__name__}: {e}"[:2000])
                continue
            prog.release()
            runs[precision] = prog
        p0, ref = runs["default"].reference_rounds()
        for precision, prog in runs.items():
            nums = compare.training_numbers(p0, prog.readings, ref)
            nums["worst"] = compare.worst_leaves(p0, prog.readings, ref, top=5)
            if topk:
                nums["support_mismatch"] = compare.support_mismatch(
                    p0, prog.readings["last"], ref["last"])
            row[precision] = nums
        if topk and len(runs) == 2:
            row["support_mismatch_default_vs_highest"] = compare.support_mismatch(
                p0, runs["default"].readings["last"], runs["highest"].readings["last"])
        out.append(row)
        log(f"[witness] {json.dumps(row)} ({time.perf_counter() - t0:.1f} s)")
        del runs
        jax.clear_caches()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_000_000_011)
    ap.add_argument("--witness-highest", action="store_true")
    args = ap.parse_args(argv)

    from bench import harness

    cell = harness.resolve(args.workload)
    devices = harness.check_device(cell.chips)
    harness.enable_cache()
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    if args.witness_highest:
        witness(cell, seeds, devices, log=lambda s: print(s, flush=True))
        return 0
    out = readings(cell, seeds, args.control_seeds, devices,
                   log=lambda s: print(s, flush=True))
    print(json.dumps({k: out[k] for k in ("lower", "control_min", "fault_min")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
