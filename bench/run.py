"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic and its metrics are found by name
from ``BENCHMARK.json`` (see ``harness.py``). The run needs a TPU: on any
other platform, or with fewer chips than the cell asks for, it exits 2 and
prints no result. JAX's persistent compilation cache lives in
``.jax_cache/`` at the root of the checkout, so only a checkout's first run
of a cell compiles.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` profiles
the window and reports its per-layer metrics, the device's busy seconds and
the top device operations and idle gaps.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
PLATFORM = "tpu"

sys.path.insert(0, str(BENCH))


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def start_jax():
    """Point JAX's persistent cache at the checkout, caching every program
    however small or quick to compile."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def main(argv=None) -> int:
    args = parse(argv)
    import harness
    import tracered
    spec = harness.Spec.load(args.workload)
    jax = start_jax()
    devs = jax.devices()
    chips = int(spec.cell["chips"])
    if devs[0].platform != PLATFORM or len(devs) < chips:
        print(f"bench: {args.workload} needs {chips} {PLATFORM} chip(s); "
              f"JAX found {len(devs)} {devs[0].platform!r} device(s) "
              f"({devs[0].device_kind})", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core import pim
    dev = devs[0]
    harness.log(f"device {dev.platform} {dev.device_kind!r} x{len(devs)}; "
                f"cell {args.workload}: config {spec.cell['config']}, "
                f"traffic {spec.cell['traffic']}, family {spec.family}, "
                f"seed {args.seed}, {args.seconds} s, trace {args.trace}")

    with tempfile.TemporaryDirectory() as tdir:
        res = harness.measure(spec, pim, args.seed, args.seconds,
                              bool(args.trace), T_START, trace_dir=tdir)
        run = res["run"]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devs), "memory_peak_bytes": res["peak"]}
        line = {"correct": res["correct"], "attempted": res["attempted"],
                "failed": res["failed"]}
        if args.trace:
            t0 = time.perf_counter()
            run.trace = tracered.reduce(
                tracered.load(tracered.find_xplane(tdir)))
            harness.log(f"trace reduced in {time.perf_counter() - t0:.3f} s: "
                        f"busy {run.trace.busy_s:.6f} s of "
                        f"{run.trace.window_s:.6f} s")
            line["metrics"] = harness.metric_values(run, spec.per_layer,
                                                     spec.root)
            device.update(busy_s=run.trace.busy_s,
                          window_s=run.trace.window_s)
            line["device"] = device
            line["breakdown"] = run.trace.breakdown()
        else:
            line["metrics"] = harness.metric_values(run, spec.end_to_end,
                                                     spec.root)
            line["device"] = device
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    share = (f" = {100 * res['peak'] / limit:.3f}% of the {limit} B limit"
             if limit else "")
    harness.log(f"memory peak {res['peak']} B{share}")
    line["checks"] = res["checks"]
    for text in harness.check_lines(res["checks"]):
        print(text, file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
