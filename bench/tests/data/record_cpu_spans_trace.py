"""Records ``cpu_spans_trace.xplane.pb``: three jobs of a small
``schedule_pipeline`` step on the CPU, each inside the benchmark's host
spans, so that the program's own spans (``pim.sched.*``) nest inside
``bench.entry`` and ``bench.readback``; and ``cpu_spans_trace.hlo.txt``,
the compiled scan driver's HLO text, whose ``op_name`` metadata names the
program's scopes (``pim.runner.*``, ``pim.step.*``). The Python tracer is
off, which keeps the file small.

    JAX_PLATFORMS=cpu PYTHONPATH=src python bench/tests/data/record_cpu_spans_trace.py
"""
import glob
import importlib
import shutil
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pim

HERE = Path(__file__).parent
ROWS, WORDS, K = 16, 8, 33       # k = 33 fuses into one shift run


def job(device, rows):
    steps = []
    for row in rows:
        b = pim.ProgramBuilder(ROWS, WORDS)
        b.issue()
        b.write_row(1, row)
        b.shift_k(1, 2, K)
        b.read_row(2)
        steps.append(b.build())
    with jax.profiler.TraceAnnotation("bench.entry"):
        pr = pim.schedule_pipeline(device, [steps])
    with jax.profiler.TraceAnnotation("bench.block"):
        pr.state.banks.meter.time_ns.block_until_ready()
    with jax.profiler.TraceAnnotation("bench.readback"):
        reads = pr.reads
        jax.device_get(pr.state.banks.meter)
    return reads


def main():
    # source files by base name only in the HLO metadata both files keep
    jax.config.update("jax_hlo_source_file_canonicalization_regex", ".*/")
    sched = importlib.import_module("repro.core.pim.schedule")
    cfg = pim.DeviceConfig(channels=1, ranks=1, banks_per_rank=2,
                           subarrays=1, num_rows=ROWS, words=WORDS)
    device = pim.make_device(cfg)
    rng = np.random.default_rng(0)
    fresh = lambda: rng.integers(0, 2**32, (cfg.n_slots, WORDS),  # noqa
                                 dtype=np.uint32)
    job(device, fresh())                        # compiles
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        for _ in range(3):
            job(device, fresh())
            time.sleep(0.005)
        jax.profiler.stop_trace()
        src = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0]
        shutil.copy(src, HERE / "cpu_spans_trace.xplane.pb")
    (_, n_steps, _), (fn, plan) = next(iter(sched._pipeline_cache.items()))
    xs = tuple(jnp.zeros((n_steps, len(slots), n, WORDS), jnp.uint32)
               for slots, n in zip(plan.group_slots, plan.group_n_payloads))
    text = fn.lower(device.banks, jnp.float32(0), xs).compile().as_text()
    (HERE / "cpu_spans_trace.hlo.txt").write_text(text)


if __name__ == "__main__":
    main()
