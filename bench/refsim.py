"""Plain reference of the simulated DRAM device, in numpy.

It executes per-slot command lists with the semantics the paper and the
repository's DESIGN.md state for each command, and charges each command to
a per-slot meter from the DDR3-1333 constants that a configuration file
lists under ``timing``. Nothing here imports the program under test.

Rows are packed little-endian: column ``c`` of a row is bit ``c % 32`` of
word ``c // 32``. Row ``R-1`` is C0 (all zeros), ``R-2`` is C1 (all ones).

Commands are tuples ``(op, a, b, c)``:

  ISSUE                   command-burst issue overhead
  WRITE  row, payload     host write of the job's payload row
  READ   row              host read
  SHIFT  src, dst, k      k chained 1-bit migration-cell shifts toward
                          higher columns, the first src -> dst, the rest
                          dst -> dst; charged as k SHIFTs of 4 AAPs each

A step is a list of ``(slots, commands, payloads)`` groups: every slot of a
group runs the same commands, ``payloads[j]`` holding slot ``j``'s rows.
"""
from __future__ import annotations

import math

import numpy as np

ISSUE, WRITE, READ, SHIFT = range(4)

FLOAT_FIELDS = ("time_ns", "e_act", "e_pre", "e_refresh", "e_burst",
                "e_background")
INT_FIELDS = ("n_act", "n_pre", "n_aap", "n_shift", "n_tra", "n_refresh")

EVEN = np.uint32(0x5555_5555)
ODD = np.uint32(0xAAAA_AAAA)


def n_commands(commands) -> int:
    """DRAM commands in a list, a k-shift counting as k."""
    return sum(cmd[3] if cmd[0] == SHIFT else 1 for cmd in commands)


# -- Row math ---------------------------------------------------------------

def shift_cols(rows: np.ndarray, k: int) -> np.ndarray:
    """Shift packed rows by ``k`` >= 0 columns toward higher columns; zeros
    enter at column 0 and columns pushed past the edge are lost."""
    if k == 0:
        return rows.copy()
    bits = np.unpackbits(rows.astype("<u4").view(np.uint8), axis=-1,
                         bitorder="little")
    out = np.zeros_like(bits)
    if k < bits.shape[-1]:
        out[..., k:] = bits[..., :-k]
    return np.packbits(out, axis=-1, bitorder="little").view("<u4")


def migration_hop(row: np.ndarray):
    """One 1-bit right shift through the migration rows (paper Fig. 3): the
    top row captures the even columns, the bottom row the odd ones, and
    both re-emerge one column over. Returns ``(out, mig_top, mig_bot)``."""
    top, bot = row & EVEN, row & ODD
    return shift_cols(top, 1) | shift_cols(bot, 1), top, bot


# -- The device -------------------------------------------------------------

class Device:
    """Every slot's rows, side rows and meter. ``dtype`` is the precision
    of the meter's float fields: float64 for the reference; a lower one
    makes the control."""

    def __init__(self, dev: dict, timing: dict, dtype=np.float64):
        self.ch, self.rk = dev["channels"], dev["ranks"]
        self.bpr, self.S = dev["banks_per_rank"], dev["subarrays"]
        self.R, self.W = dev["num_rows"], dev["words"]
        self.t = timing
        self.dtype = dtype
        self.n_slots = self.ch * self.rk * self.bpr * self.S
        n = self.n_slots
        self.bits = np.zeros((n, self.R, self.W), np.uint32)
        self.bits[:, self.R - 2] = 0xFFFF_FFFF
        self.mig_top = np.zeros((n, self.W), np.uint32)
        self.mig_bot = np.zeros((n, self.W), np.uint32)
        self.dcc = np.zeros((n, self.W), np.uint32)
        self.f = {k: np.zeros(n, dtype) for k in FLOAT_FIELDS}
        self.i = {k: np.zeros(n, np.int64) for k in INT_FIELDS}
        t = timing
        self.t_aap = t["tRAS"] + t["tRP"]
        self.burst_n = -(-self.W * 4 // 64)
        self.t_burst = t["tRC"] + self.burst_n * 6.0

    # -- meter ---------------------------------------------------------------
    def _charge(self, idx, dt, e_act=0.0, e_pre=0.0, e_burst=0.0, **counts):
        d = self.dtype
        f = self.f
        f["time_ns"][idx] = (f["time_ns"][idx] + d(dt)).astype(d)
        if e_act:
            f["e_act"][idx] = (f["e_act"][idx] + d(e_act)).astype(d)
        if e_pre:
            f["e_pre"][idx] = (f["e_pre"][idx] + d(e_pre)).astype(d)
        if e_burst:
            f["e_burst"][idx] = (f["e_burst"][idx] + d(e_burst)).astype(d)
        bg = d(d(dt) * d(self.t["p_background"]))
        f["e_background"][idx] = (f["e_background"][idx] + bg).astype(d)
        for k, v in counts.items():
            self.i[k][idx] += v

    def _aap(self, idx, times=1):
        t = self.t
        for _ in range(times):
            self._charge(idx, self.t_aap, 2 * t["e_act"], t["e_pre"],
                         n_act=2, n_pre=1, n_aap=1)

    def energy(self) -> np.ndarray:
        return sum(self.f[k].astype(np.float64)
                   for k in FLOAT_FIELDS if k.startswith("e_"))

    # -- one group of slots --------------------------------------------------
    def _run_group(self, idx, commands, payloads, reads):
        t = self.t
        bits = self.bits
        for op, a, b, c in commands:
            if op == ISSUE:
                self._charge(idx, t["t_issue"])
            elif op in (WRITE, READ):
                if op == WRITE:
                    bits[idx, a] = payloads[:, b]
                else:
                    reads.append((idx, bits[idx, a].copy()))
                self._charge(idx, self.t_burst, t["e_act"], t["e_pre"],
                             self.burst_n * t["e_burst_per_64b"],
                             n_act=1, n_pre=1)
            elif op == SHIFT:
                # k - 1 hops carry the row k - 1 columns; the last hop runs
                # through the migration rows, which keep what it captured.
                y = shift_cols(bits[idx, a], c - 1)
                out, top, bot = migration_hop(y)
                bits[idx, b] = out
                self.mig_top[idx] = top
                self.mig_bot[idx] = bot
                for _ in range(c):
                    self._aap(idx, 4)
                    self.i["n_shift"][idx] += 1
            else:
                raise ValueError(op)

    # -- the device timing model ---------------------------------------------
    def _coords(self, slot):
        """(channel, rank) of a slot."""
        ch, rest = divmod(slot // self.S, self.rk * self.bpr)
        return ch, rest // self.bpr

    def _bus(self, commands) -> float:
        n_issue = sum(cmd[0] == ISSUE for cmd in commands)
        n_host = sum(cmd[0] in (WRITE, READ) for cmd in commands)
        return n_issue * self.t["t_issue"] + n_host * self.t_burst

    def _channels(self, bus) -> float:
        """Bus-active slots served first come, first served, in slot order
        on their channel; a switch of rank between consecutive bursts on a
        channel costs tRTRS."""
        busy = [0.0] * self.ch
        last = [None] * self.ch
        for k in range(self.n_slots):
            if bus[k] <= 0.0:
                continue
            ch, rk = self._coords(k)
            busy[ch] += bus[k]
            if last[ch] is not None and last[ch] != rk:
                busy[ch] += self.t["tRTRS"]
            last[ch] = rk
        return max(busy)

    def step(self, groups):
        """Run one step: every group's commands. Returns ``(reads, wall,
        energy)``: reads per slot in command order, the step's simulated
        wall clock (host bus of the busiest channel plus the slowest slot's
        compute) and energy (float64)."""
        t_before = self.f["time_ns"].astype(np.float64)
        e_before = self.energy()
        bus = np.zeros(self.n_slots)
        reads = [[] for _ in range(self.n_slots)]
        for slots, commands, payloads in groups:
            idx = np.asarray(slots)
            got = []
            self._run_group(idx, commands, payloads, got)
            for sl, rows in got:
                for j, s in enumerate(sl):
                    reads[s].append(rows[j])
            bus[idx] = self._bus(commands)
        exec_ns = self.f["time_ns"].astype(np.float64) - t_before - bus
        wall = self._channels(bus) + max(float(exec_ns.max()), 0.0)
        return reads, wall, float((self.energy() - e_before).sum())

    def meter(self) -> dict:
        out = {k: v.copy() for k, v in self.f.items()}
        out.update({k: v.copy() for k, v in self.i.items()})
        return out


def rel_err(got, want) -> float:
    """Largest ``|got - want| / |want|``; where ``want`` is 0, ``got`` must
    be 0 as well (any other value reads as infinite error)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return math.inf
    diff = np.abs(got - want)
    with np.errstate(divide="ignore", invalid="ignore"):
        r = np.where(want != 0, diff / np.abs(want),
                     np.where(diff == 0, 0.0, np.inf))
    return float(r.max()) if r.size else 0.0


def words_differ(got, want) -> int:
    """uint32 words that differ; a shape mismatch counts every word."""
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        return int(max(got.size, want.size, 1))
    return int(np.count_nonzero(got != want))
