"""Readings behind a cell's limits: the program's numbers on many seeds and
the control's on a few, at the cell's own size, in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,... \\
        --control-seeds 101,102,103 [--out readings.json]

For each seed the program runs the first ``check_jobs`` jobs of a window
of that seed through the timed path, after the run's own set-up, and the
numbers are compared with the plain reference. For each control seed the
reference computed in bfloat16 stands in for the program on the jobs the
program ran first: the precision below the float32 the configuration
states for its meters. A limit lies above every program reading and below
every control reading. The benchmark's own runs never run this.
"""
from __future__ import annotations

import contextlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    import argparse
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default="")
    p.add_argument("--out")
    a = p.parse_args(argv)
    import harness
    jax = bench_run.start_jax()
    if jax.devices()[0].platform != bench_run.PLATFORM:
        print("control: needs a TPU", file=sys.stderr)
        return 2
    sys.path.insert(0, str(bench_run.ROOT / "src"))
    import ml_dtypes
    from repro.core import pim
    spec = harness.Spec.load(a.workload)
    m = int(spec.traffic["check_jobs"])
    span = (lambda name: contextlib.nullcontext())
    rows = []

    def first_jobs(seed):
        cell = spec.make_cell(pim, seed)
        kept = harness.first_jobs(cell, m, span)
        cell.release()
        return cell, kept

    for seed in [int(s) for s in a.seeds.split(",") if s]:
        t0 = time.perf_counter()
        cell, kept = first_jobs(seed)
        nums = harness.check(cell, kept, spec.traffic["limits"])
        rows.append({"kind": "program", "seed": seed, "numbers": nums,
                     "seconds": time.perf_counter() - t0})
        print(json.dumps(rows[-1]), flush=True)
    for seed in [int(s) for s in a.control_seeds.split(",") if s]:
        cell, kept = first_jobs(seed)
        nums = harness.check(cell, kept, spec.traffic["limits"],
                             control=ml_dtypes.bfloat16)
        rows.append({"kind": "control_bf16", "seed": seed, "numbers": nums})
        print(json.dumps(rows[-1]), flush=True)
    if a.out:
        Path(a.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
