"""A recurring shift step through ``schedule_pipeline``.

One job: ``steps`` recurring steps in which every slot of the device runs
``ISSUE; HOSTW row src; shift_k(src, dst, k); HOSTR row dst`` on a fresh
row, as one ``schedule_pipeline`` call from the device made at set-up.
The rows come from a pool drawn from the seed; each job reads its own
window of the pool, at an offset drawn from the seed and the job's index.
The job builds its programs from those rows (``with_payloads``, which
copies each row) inside its timed span, as every user of the scheduler
does: made ahead, the programs of a window's jobs would take gigabytes.
"""
from __future__ import annotations

import numpy as np

import refsim
from harness import JobCell


class Cell(JobCell):
    def __init__(self, pim, config, traffic, seed):
        super().__init__(pim, config, traffic, seed)
        d = self.dev
        tr = traffic
        self.K, self.k = tr["steps"], tr["shift_k"]
        self.src, self.dst = tr["src_row"], tr["dst_row"]
        b = pim.ProgramBuilder(d["num_rows"], d["words"])
        b.issue()
        b.write_row(self.src, np.zeros(d["words"], np.uint32))
        b.shift_k(self.src, self.dst, self.k)
        b.read_row(self.dst)
        self.base = b.build()
        self.per_step = self.K * self.n_slots
        self.pool = np.random.default_rng([seed, 1]).integers(
            0, 2**32, (tr["pool_rows"], d["words"]), dtype=np.uint32)
        self.commands = [(refsim.ISSUE, 0, 0, 0),
                         (refsim.WRITE, self.src, 0, 0),
                         (refsim.SHIFT, self.src, self.dst, self.k),
                         (refsim.READ, self.dst, 0, 0)]
        self.commands_per_job = (refsim.n_commands(self.commands)
                                 * self.n_slots * self.K)

    def inputs(self, i: int):
        """The job's rows, ``(steps, slots, words)``: a view of the pool."""
        rng = np.random.default_rng([self.seed, 2, i])
        off = int(rng.integers(0, len(self.pool) - self.per_step + 1))
        return self.pool[off:off + self.per_step].reshape(
            self.K, self.n_slots, -1)

    def run(self, inp, span):
        with span("bench.build"):
            steps = [[self.base.with_payloads([row]) for row in step]
                     for step in inp]
        with span("bench.entry"):
            pr = self.pim.schedule_pipeline(self.device0, steps)
        with span("bench.block"):
            pr.state.banks.meter.time_ns.block_until_ready()
        with span("bench.readback"):
            reads = pr.reads
            meter, wall, energy = self.jax.device_get(
                (pr.state.banks.meter, pr.wall_ns, pr.energy_nj))
        return {"reads": [[list(r) for r in step] for step in reads],
                "meter": self.meter_dict(meter), "wall": wall,
                "energy": energy, "state": pr.state.banks}

    def reference(self, data, dtype):
        dev = refsim.Device(self.dev, self.timing, dtype)
        slots = list(range(self.n_slots))
        reads, walls, energies = [], [], []
        for s in range(self.K):
            got, wall, energy = dev.step(
                [(slots, self.commands, data[s][:, None, :])])
            reads.append(got)
            walls.append(wall)
            energies.append(energy)
        return {"reads": reads, "wall": walls, "energy": energies,
                "dev": dev}

    def paper_line(self, out) -> str:
        """Time and energy per shift in the program's meters, with the
        reference's ISSUE and host-transfer charges taken out and one ISSUE
        added back (the paper's single shift is issued once), against the
        paper's Tables 2/3."""
        dev = refsim.Device(self.dev, self.timing)
        dev.step([([0], self.commands[:2] + self.commands[3:],
                    np.zeros((1, 1, self.dev["words"]), np.uint32))])
        other_t = float(dev.f["time_ns"][0]) * self.K
        other_e = float(dev.energy()[0]) * self.K
        t_issue = self.timing["t_issue"]
        e_issue = t_issue * self.timing["p_background"]
        m = out["meter"]
        n = self.K * self.k
        ns = (float(m["time_ns"][0]) - other_t) / n + t_issue
        e_tot = sum(float(m[f][0]) for f in refsim.FLOAT_FIELDS
                    if f.startswith("e_"))
        nj = (e_tot - other_e) / n + e_issue
        p_ns, p_nj = self.traffic["paper_shift_ns"], self.traffic[
            "paper_shift_nj"]
        return (f"per shift (one ISSUE + one 4-AAP shift, host transfers "
                f"out): {ns:.4f} ns, {nj:.4f} nJ; paper Tables 2/3 {p_ns} ns, "
                f"{p_nj} nJ; error {100 * (ns / p_ns - 1):+.2f}% time, "
                f"{100 * (nj / p_nj - 1):+.2f}% energy")
