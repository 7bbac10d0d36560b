"""Workload scheduler for device-level (multi-bank, multi-subarray) PIM
execution.

Takes *heterogeneous* per-slot :class:`~.ir.PimProgram`s (slot = one
``(bank, subarray)`` pair) and executes them against a
:class:`~.device.DeviceState` with as few compiled artifacts as possible:
slots whose command streams are identical (same ops, shape and payload
count — payload *data* may differ) form one group, and each group runs as
ONE compiled runner vmapped over the group's slot states with the HOSTW
payloads passed as a batched argument (``exec.make_runner``'s
``payload_arg`` mode). This is SIMDRAM's framework split — program →
allocation → execution — with Shared-PIM-style concurrent bank scheduling.

In-DRAM row movement (``COPY``, LISA-style): a slot's stream may carry
``COPY`` ops whose destination is *another* slot — an adjacent subarray
(row-buffer-movement hops) or another bank (the chip's shared internal
bus). The scheduler strips those ops out of the compiled streams and
drains them **after the step's in-bank compute**, DMA-engine style: a
cross-slot COPY reads its source row's *post-compute* value, copies apply
in (slot, stream-position) order (later copies observe earlier ones), and
the moved rows are visible to the *next* ``schedule`` step. Each copy
charges ``timing.copy_cost`` onto the **source** slot's meter — no HOSTR/
HOSTW, no off-chip burst energy. Same-slot COPYs stay in-stream (they are
ordinary distance-0 LISA copies the executor runs directly).

The drain itself is *link-contended*: every inter-subarray RBM link
(``(bank, i)`` joins subarrays ``i``/``i+1``) and every channel's shared
internal bus is a FCFS resource. Copies are served in drain order; a copy
holds every link it crosses (plus the internal bus(es) for inter-bank
moves) for its full duration, so massive gathers queue instead of
draining for free. An inter-bank copy pays real RBM hops too: source
subarray → bank edge (subarray 0, where the internal bus taps the bank)
and edge → destination subarray.

Device accounting (see ``device.py``): per-slot meters accumulate each
slot's own busy time; the schedule-level wall clock is channel-aware:

    wall = max_ch chan_busy_ch + max_k (Δt_k − bus_k) + copy drain makespan
    energy = Σ_k Δenergy_k

where ``bus_k`` is slot k's bus occupancy (ISSUE bursts AND off-chip
HOSTW/HOSTR burst windows) and ``chan_busy_ch`` serializes the occupancy
of channel ``ch``'s slots FCFS, charging ``tRTRS`` between bursts that
switch rank. With ``async_host=True`` (Shared-PIM-style double buffering)
each channel's HOST traffic first overlaps the *previous* step's
compute+copy window (``DeviceState.host_credit_ns``), so multi-step
pipelines pay ``max(transfer, compute)`` instead of the sum — bits,
reads, and energy are identical to the sync schedule.

``shard_rows`` / ``shard_lanes`` partition one large host buffer into
per-slot programs (row-wise or lane-wise, optionally across the subarray
axis), and ``gather_rows`` / ``xor_reduce_program`` are the in-DRAM
movement/reduction building blocks the benchmarks use to exchange rows
between slots without host round-trips (RS syndrome sums across banks,
cross-lane reductions).

Host-side performance model (DESIGN.md §10): one ``schedule`` call is ONE
XLA dispatch. Grouping hashes the programs' cached columnar digests (O(1)
per slot), the per-call layout resolves to a cached :class:`_StepPlan`
whose jitted step function folds every stream group, the COPY drain, and
the channel-bus model into a single compiled computation, and all returned
timing values stay lazy (device/numpy) until read. ``schedule_pipeline``
runs K recurring steps under one ``jax.lax.scan`` — steady-state per-step
cost is one scan iteration, not a Python round-trip.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import exec as pim_exec
from . import ir
from .compile import CompiledProgram, compile_program
from .device import (DeviceConfig, DeviceState, channel_occupancy,
                     host_bus_ns, issue_bus_ns)
from .ir import PimProgram, ProgramBuilder
from .state import NUM_ROWS
from .timing import DDR3Timing, DEFAULT_TIMING, copy_cost


def _unbatch_reads(group_reads, read_layout, n_steps=None):
    """Shared lazy read unbatching: ONE device->host transfer per group
    read array, then plain numpy slicing into the per-slot layout. With
    ``n_steps`` the arrays carry a leading step axis and a per-step list
    is returned. Runs inside the ``pim.sched.reads`` span."""
    n_slots, group_slots = read_layout

    def one_step(host, pick):
        out: list = [()] * n_slots
        for g, slots in enumerate(group_slots):
            for j, k in enumerate(slots):
                out[k] = tuple(pick(r, j) for r in host[g])
        return tuple(out)

    with jax.profiler.TraceAnnotation("pim.sched.reads"):
        host = [tuple(np.asarray(r) for r in g) for g in group_reads]
        if n_steps is None:
            return one_step(host, lambda r, j: r[j])
        return [one_step(host, lambda r, j, k=k: r[k, j])
                for k in range(n_steps)]


@dataclasses.dataclass
class ScheduleResult:
    """Outcome of one device-level schedule step.

    Timing metrics that may live on-device (async mode makes the channel
    occupancy depend on the previous step's lazy compute window) are stored
    raw in underscored fields and converted on *access* — reading
    ``host_overlap_ns`` etc. yields plain floats exactly as before, but
    constructing the result never blocks on the device, so back-to-back
    ``schedule`` calls dispatch asynchronously."""

    state: DeviceState
    wall_ns: jax.Array          # max-channel bus + max in-slot exec + copies
    bus_ns: float               # total bus occupancy, summed over slots
    energy_nj: jax.Array        # summed across slots (this step only)
    copy_ns: float = 0.0        # COPY drain *makespan* (link-contended wall)
    host_bytes: int = 0         # off-chip bytes this step's streams moved
    rank_switch_ns: float = 0.0  # total tRTRS penalty charged this step
    copy_total_ns: float = 0.0  # Σ per-copy duration (old copy_ns meaning)
    copy_queue_ns: float = 0.0  # Σ FCFS waiting behind busy links/buses
    link_busy_ns: dict = dataclasses.field(default_factory=dict)
    # per-resource occupancy: ("link", bank, i) RBM link between subarrays
    # i/i+1, ("ibus", channel) the channel's shared internal bus.
    _host_bus_ns: float = 0.0   # HOSTW/HOSTR burst occupancy, Σ over slots
    _channel_bus_ns: object = ()  # per-channel occupancy (may be on-device)
    _host_overlap_ns: object = 0.0  # host time hidden under prev step
    _group_reads: tuple = ()    # per group: per-read (n_group, words) arrays
    _read_layout: tuple = (0, ())  # (n_slots, group slot-id tuples)

    @property
    def reads(self) -> tuple:
        """Per slot: host-read rows in ``read_row`` slot order. The jitted
        step returns reads batched per stream group; the per-slot view is
        sliced out lazily here (and memoized) so the hot scheduling path
        never pays per-slot unbatching."""
        cached = getattr(self, "_reads_cache", None)
        if cached is None:
            cached = _unbatch_reads(self._group_reads, self._read_layout)
            self._reads_cache = cached
        return cached

    @property
    def host_bus_ns(self) -> float:
        return float(self._host_bus_ns)

    @property
    def host_overlap_ns_lazy(self):
        """The raw (possibly on-device) hidden-host-time value — for
        accumulators that must not block (``host_overlap_ns`` converts)."""
        return self._host_overlap_ns

    @property
    def channel_bus_ns(self) -> tuple:
        """Per-channel serialized occupancy (+tRTRS), as floats."""
        return tuple(float(x) for x in self._channel_bus_ns)

    @property
    def host_overlap_ns(self) -> float:
        return float(self._host_overlap_ns)


def stream_key(p: PimProgram):
    """Slots with equal keys share one compiled vmapped runner: identical
    command stream and shape; HOSTW payload *data* is excluded (it is passed
    per-slot at run time). O(1): the stream itself is represented by the
    program's cached 128-bit columnar digest, not re-hashed per call."""
    return (p.digest, p.num_rows, p.words, len(p.payloads))


# Host-orchestration counters, reset-able by tests/benchmarks:
#   dispatches     — XLA dispatches issued by schedule()/schedule_pipeline()
#                    (the acceptance bar is <= 1 per steady-state step)
#   plan_misses    — step-plan cache misses (a new schedule layout)
#   compile_misses — _compiled_for cache misses (a new program stream)
#   upload_bytes   — bytes of HOSTW payload stacks put on the device
#                    (payload-cache misses only)
#   payload_hits   — payload-cache lookups that hit, one per stream group
#   payload_misses — ... and that missed (each uploads its group's rows)
SCHED_STATS = {"dispatches": 0, "plan_misses": 0, "compile_misses": 0,
               "upload_bytes": 0, "payload_hits": 0, "payload_misses": 0}


# Host spans, written into the profiler's trace beside the device events
# (so an idle gap of the device can be put down to what the host did):
#   pim.sched.{schedule,pipeline,workload}   one entry-point call
#     pim.sched.lower      normalize, strip copies, group by stream key
#     pim.sched.plan       the step-plan lookup (and build, on a miss)
#     pim.sched.payloads   payload stacks: the cache, the upload, stacking
#     pim.sched.dispatch   the jitted driver's lookup and its call
#   pim.sched.reads        a result's reads brought to the host
# A span costs about a microsecond while no trace runs, so spans mark
# layer boundaries only, never a loop over slots or ops.
def _span(name: str):
    """A profiler span tagged ``call=`` with the ordinal of the dispatch the
    current entry-point call makes: the entry's span and its children share
    it."""
    return jax.profiler.TraceAnnotation(name, call=SCHED_STATS["dispatches"])


def _spanned(name: str):
    """Decorator: run the function inside ``_span(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with _span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


# One compiled artifact per distinct (stream, timing): groups recur across
# schedule() calls (e.g. PimVM flushes), so keep the jitted runners warm.
# LRU-bounded — long sessions stream many one-off programs through here,
# and insertion-order (FIFO) eviction would let them push out hot
# recurring streams.
_compile_cache: dict = {}
_COMPILE_CACHE_MAX = 512


def _compiled_for(program: PimProgram, timing: DDR3Timing) -> CompiledProgram:
    key = (stream_key(program), timing)
    hit = _compile_cache.pop(key, None)
    if hit is None:
        SCHED_STATS["compile_misses"] += 1
        if len(_compile_cache) >= _COMPILE_CACHE_MAX:
            _compile_cache.pop(next(iter(_compile_cache)))
        hit = compile_program(program, timing)
    _compile_cache[key] = hit           # (re)insert at the MRU end
    return hit


def compiled_for(program: PimProgram,
                 timing: DDR3Timing = DEFAULT_TIMING) -> CompiledProgram:
    """Public entry to the scheduler's LRU compile cache: equal streams
    (by columnar digest) share one :class:`CompiledProgram` — and thereby
    one set of jitted runners — across calls. Use this instead of
    ``compile_program`` for recurring streams (``PimVM`` does)."""
    return _compiled_for(program, timing)


# Stacked payload batches keyed on the *identity* of the payload arrays:
# recurring flushes (PimVM pipelines) schedule the same PimProgram objects
# over and over, and re-stacking identical host data plus re-uploading it
# to the device every step was pure waste. Entries pin the source arrays
# their keys name (so a recycled id can never alias a dead key) and carry
# their byte count, taken once at put. Bounded by entry count AND by
# pinned bytes: pipeline entries hold K-step device arrays, and a
# long-running serving loop with churning payloads would otherwise grow
# device memory without bound.
class _PayloadEntry(NamedTuple):
    array: jax.Array        # what a lookup of the key returns
    refs: tuple             # the source arrays the key names, pinned
    nbytes: int             # array and refs, counted at put


_payload_cache: dict = {}
_PAYLOAD_CACHE_MAX = 256
_PAYLOAD_CACHE_MAX_BYTES = 256 << 20        # pinned stacked-array budget
_payload_cache_bytes = 0


def _entry_nbytes(hit) -> int:
    """Bytes one cache entry pins: the stacked device array plus the tuple
    of source arrays it keeps alive for id stability. Reads ``nbytes``
    attributes only: iterating a device array would slice it row by row on
    the device."""
    stacked, refs = hit
    return int(stacked.nbytes) + sum([int(a.nbytes) for a in refs])


def _payload_cache_get(key):
    """LRU hit: pop + reinsert at the MRU end (byte total unchanged)."""
    hit = _payload_cache.pop(key, None)
    if hit is not None:
        _payload_cache[key] = hit
    return hit


def _payload_cache_put(key, array, refs: tuple) -> None:
    """Insert at the MRU end, then evict LRU entries until both the entry
    count and the pinned-byte budget hold. The newest entry itself is never
    evicted — one oversized batch must still be cacheable or recurring
    pipelines would re-upload it every call. O(1) per eviction: an entry's
    byte count is stored with it."""
    global _payload_cache_bytes
    entry = _PayloadEntry(array, refs, _entry_nbytes((array, refs)))
    _payload_cache[key] = entry
    _payload_cache_bytes += entry.nbytes
    while (len(_payload_cache) > _PAYLOAD_CACHE_MAX
           or _payload_cache_bytes > _PAYLOAD_CACHE_MAX_BYTES):
        if len(_payload_cache) <= 1:
            break
        _payload_cache_bytes -= _payload_cache.pop(
            next(iter(_payload_cache))).nbytes


def _payload_cache_clear() -> None:
    """Drop every pinned payload batch (test hygiene)."""
    global _payload_cache_bytes
    _payload_cache.clear()
    _payload_cache_bytes = 0


def _payload_lookup(key, refs: tuple,
                    build: Callable[[], jax.Array]) -> jax.Array:
    """The device array cached under ``key``; on a miss ``build()``'s,
    cached pinning ``refs``. Counts one payload-cache hit or miss."""
    hit = _payload_cache_get(key)
    if hit is not None:
        SCHED_STATS["payload_hits"] += 1
        return hit.array
    SCHED_STATS["payload_misses"] += 1
    array = build()
    _payload_cache_put(key, array, refs)
    return array


def _upload(host: np.ndarray) -> jax.Array:
    """One host-to-device copy, counted in ``upload_bytes``."""
    SCHED_STATS["upload_bytes"] += host.nbytes
    return jax.device_put(host)


def _group_payloads(batches, words: int, k_axis: bool) -> jax.Array:
    """One stream group's HOSTW payloads on the device; ``batches`` holds
    each step's programs of the group. ``(K, n_group, n_payloads, words)``
    uint32 with ``k_axis``, else the one batch's ``(n_group, n_payloads,
    words)``. A miss stacks every row in one host pass and uploads the
    result once; a batch that all K > 1 steps share is uploaded once and
    replicated on the device."""
    K, n, n_pay = len(batches), len(batches[0]), len(batches[0][0].payloads)
    shape = ((K,) if k_axis else ()) + (n, n_pay, words)
    if n_pay == 0:
        return _payload_lookup(("zeros",) + shape, (),
                               lambda: jnp.zeros(shape, jnp.uint32))
    refs = tuple([a for progs in batches for p in progs for a in p.payloads])
    ids = tuple(map(id, refs))
    per = n * n_pay                     # rows a step
    replicated = K > 1 and all(ids[k * per:(k + 1) * per] == ids[:per]
                               for k in range(1, K))
    if replicated:
        refs, ids = refs[:per], ids[:per]
    # the shape prefix disambiguates the partitioning: the same id sequence
    # could otherwise alias e.g. 2 programs x 2 payloads vs 4 x 1
    tag = "steps" if replicated else "multi" if K > 1 else "batch"
    key = (tag,) + shape + ids

    def build():
        host = np.asarray(np.array(refs), np.uint32)   # no copy if uint32
        if replicated:
            return jnp.stack([_upload(host.reshape(shape[1:]))] * K)
        return _upload(host.reshape(shape))
    return _payload_lookup(key, refs, build)


def _payload_stack(programs: Sequence[PimProgram], words: int) -> jax.Array:
    """(n_slots_in_group, n_payloads, words) uint32 HOSTW payload batch."""
    return _group_payloads([programs], words, k_axis=False)


def _normalize_programs(cfg: DeviceConfig, programs) -> list:
    """Accept per-bank (len ``n_banks``, entries optionally nested per
    subarray) or flat per-slot (len ``n_slots``) program sequences and
    return a flat per-slot list (``None`` = idle)."""
    programs = list(programs)
    flat: list = [None] * cfg.n_slots
    S = cfg.subarrays

    def put(slot, p):
        flat[slot] = p

    if len(programs) == cfg.n_slots and not any(
            isinstance(p, (list, tuple)) for p in programs):
        for k, p in enumerate(programs):
            put(k, p)
        return flat
    if len(programs) != cfg.n_banks:
        raise ValueError(
            f"got {len(programs)} programs for {cfg.n_banks} banks "
            f"({cfg.n_slots} slots)")
    for b, entry in enumerate(programs):
        if isinstance(entry, (list, tuple)):
            if len(entry) != S:
                raise ValueError(
                    f"bank {b}: {len(entry)} subarray programs for "
                    f"{S} subarrays")
            for s, p in enumerate(entry):
                put(b * S + s, p)
        else:
            put(b * S, entry)       # bare program → the bank's subarray 0
    return flat


def _split_copies(cfg: DeviceConfig, slot: int, program: PimProgram):
    """Partition one slot's stream into (compiled-stream program, deferred
    cross-slot copies). Same-slot COPYs are normalized to the executor's
    local ``COPY_SELF`` encoding and stay in-stream.

    The no-copy common case is detected vectorized on the columnar
    encoding (no per-op Python walk); only streams that actually carry
    cross-slot or explicitly-self-addressed COPYs take the op loop."""
    cols = program.columns
    is_copy = cols.code == ir.OP_CODE[ir.OP_COPY]
    b, s = cfg.slot_coords(slot)
    if not is_copy.any():
        return program, []              # no COPYs at all: nothing to strip
    self_like = (cols.delta == ir.COPY_SELF) & (cols.c == ir.COPY_SELF)
    if not (is_copy & ~self_like).any():
        return program, []              # every COPY already local-encoded
    self_dst = (ir.COPY_SELF, ir.COPY_SELF)
    kept, deferred = [], []
    changed = False
    for op in program.ops:
        # On the device, local means self-addressed or "destination IS the
        # carrying slot" — explicit (0, 0) on any other carrier is a real
        # transfer to bank 0, so ir.copy_is_local only applies at (0, 0).
        is_local = (op.op == ir.OP_COPY
                    and ((op.delta, op.c) == self_dst
                         or (op.delta, op.c) == (b, s)))
        if op.op != ir.OP_COPY or is_local:
            if is_local and (op.delta, op.c) != self_dst:
                op = dataclasses.replace(op, delta=ir.COPY_SELF,
                                         c=ir.COPY_SELF)
                changed = True
            kept.append(op)
            continue
        dst_slot = cfg.slot_index(op.delta, op.c)   # validates coordinates
        if not (0 <= op.a < cfg.num_rows and 0 <= op.b < cfg.num_rows):
            raise ValueError(
                f"slot {(b, s)}: COPY rows {(op.a, op.b)} out of range "
                f"[0, {cfg.num_rows})")
        deferred.append((slot, dst_slot, op))
        changed = True
    if not changed:
        return program, deferred
    return PimProgram(ops=tuple(kept), num_rows=program.num_rows,
                      words=program.words,
                      payloads=program.payloads), deferred


@dataclasses.dataclass
class CopyDrainStats:
    """Link-contention accounting of one step's COPY drain phase."""

    makespan_ns: float = 0.0    # FCFS queue-model wall of the drain
    total_ns: float = 0.0       # Σ per-copy duration (contention-free sum)
    queue_ns: float = 0.0       # Σ time copies waited behind busy resources
    link_busy_ns: dict = dataclasses.field(default_factory=dict)


def _copy_route(cfg: DeviceConfig, src_slot: int, dst_slot: int):
    """(hops, inter_bank, resources) of one cross-slot copy.

    Intra-bank: RBM hops between the two subarrays, crossing links
    ``(bank, i)`` for i in [min, max). Inter-bank: the row rides RBM links
    from the source subarray to the bank edge (subarray 0, where the
    chip's internal bus taps the bank), crosses the channel's shared
    internal bus, and rides links from the destination's edge inward —
    so an S-1 → S-1 move costs 2(S-1) hops on top of ``t_copy_bank``.
    """
    S = cfg.subarrays
    sb, ss = divmod(src_slot, S)
    db, ds = divmod(dst_slot, S)
    if sb == db:
        hops = abs(ds - ss)
        res = [("link", sb, i) for i in range(min(ss, ds), max(ss, ds))]
        return hops, False, res
    hops = ss + ds
    res = [("link", sb, i) for i in range(ss)]
    res += [("link", db, i) for i in range(ds)]
    s_ch = cfg.bank_coords(sb)[0]
    d_ch = cfg.bank_coords(db)[0]
    res.append(("ibus", s_ch))
    if d_ch != s_ch:
        res.append(("ibus", d_ch))
    return hops, True, res


@dataclasses.dataclass(frozen=True)
class _CopyDrainPlan:
    """Route-table + FCFS outcome of one copy *pattern* (the (src, dst)
    slot pairs, in drain order). Rows are not part of the pattern — the
    same gather shape recurs step after step with different rows, and
    everything here depends only on the slots, so it is computed once and
    cached."""

    dt_slot: np.ndarray         # (n_slots,) float32 Σ copy time per source
    e_act_slot: np.ndarray      # (n_slots,) float32
    e_pre_slot: np.ndarray      # (n_slots,) float32
    n_act_slot: np.ndarray      # (n_slots,) int32
    n_pre_slot: np.ndarray      # (n_slots,) int32
    n_aap_slot: np.ndarray      # (n_slots,) int32
    stats: CopyDrainStats


@functools.lru_cache(maxsize=256)
def _copy_drain_plan(cfg: DeviceConfig, pairs: tuple) -> _CopyDrainPlan:
    """Per-copy route tables and ``timing.copy_cost`` charges (computed
    once per pair in the FCFS walk), per-source meter increments (one
    ``np.add.at`` scatter per field), and the FCFS link/bus serialization
    — all keyed on (device, copy pattern) so recurring steps skip the
    whole computation."""
    t = cfg.timing
    n = cfg.n_slots
    src = np.fromiter((p[0] for p in pairs), np.int64, len(pairs))
    dt = np.zeros(len(pairs))
    e_act = np.zeros(len(pairs))
    stats = CopyDrainStats()
    ready: dict = {}                    # resource -> busy-until (drain clock)
    for i, (src_slot, dst_slot) in enumerate(pairs):
        hops, inter_bank, resources = _copy_route(cfg, src_slot, dst_slot)
        c_dt, c_ea, _, _, _, _ = copy_cost(hops, inter_bank, t)
        dt[i] = c_dt
        e_act[i] = c_ea
        start = max((ready.get(r, 0.0) for r in resources), default=0.0)
        end = start + c_dt
        for r in resources:
            ready[r] = end
            stats.link_busy_ns[r] = stats.link_busy_ns.get(r, 0.0) + c_dt
        stats.queue_ns += start
        stats.total_ns += c_dt
        stats.makespan_ns = max(stats.makespan_ns, end)
    dt_slot = np.zeros(n, np.float32)
    e_act_slot = np.zeros(n, np.float32)
    e_pre_slot = np.zeros(n, np.float32)
    n_act_slot = np.zeros(n, np.int32)
    n_pre_slot = np.zeros(n, np.int32)
    n_aap_slot = np.zeros(n, np.int32)
    np.add.at(dt_slot, src, dt.astype(np.float32))
    np.add.at(e_act_slot, src, e_act.astype(np.float32))
    np.add.at(e_pre_slot, src, np.float32(t.e_pre))
    np.add.at(n_act_slot, src, np.int32(2))
    np.add.at(n_pre_slot, src, np.int32(1))
    np.add.at(n_aap_slot, src, np.int32(1))
    return _CopyDrainPlan(dt_slot=dt_slot, e_act_slot=e_act_slot,
                          e_pre_slot=e_pre_slot, n_act_slot=n_act_slot,
                          n_pre_slot=n_pre_slot, n_aap_slot=n_aap_slot,
                          stats=stats)


@dataclasses.dataclass
class _StepPlan:
    """One schedule layout, fully lowered: the jitted single-dispatch step
    function plus every static (trace-time) quantity of the step. Cached
    per (device config, flags, group signature, copy signature) so a
    recurring step pays ONE dict lookup + one XLA dispatch."""

    fn: object                  # jitted (banks, credit, payloads) -> ...
    raw_fn: object              # same, unjitted (inlined into pipelines)
    group_slots: tuple          # tuple of slot-id tuples, plan group order
    bus_total: float            # Σ per-slot bus occupancy
    host_bus_total: float       # Σ per-slot host-burst occupancy
    chan_busy: tuple            # per-channel occupancy at credit=0 (+tRTRS)
    switch_ns: float
    host_bytes: int
    copy: "_CopyDrainPlan | None"
    group_n_reads: tuple = ()   # per group: HOSTR count of the rep stream
    group_n_payloads: tuple = ()  # per group: HOSTW payload count
    # Static diagnostics of this layout (lint._plan_diagnostics), computed
    # ONCE at plan build: the verify=True gates of schedule()/
    # schedule_pipeline()/schedule_workload() only scan this cached tuple,
    # so warm paths pay zero extra work.
    lint: tuple = ()


# Each cached plan keeps its compiled step program alive. On the XLA CPU
# backend one such program holds ~290 memory mappings (measured on the
# 4-slot differential-harness layouts), and a process may hold at most
# vm.max_map_count (65,530 by default): past it, XLA's code allocator
# fails and the next compile crashes the process. 64 plans stay far below.
_plan_cache: dict = {}
_PLAN_CACHE_MAX = 64


def _plan_key(cfg: DeviceConfig, groups, deferred, *,
              use_kernels, interpret, refresh, async_host):
    """The step-plan cache key: everything trace-relevant about one
    schedule layout (streams via digests, grouping, copy pattern, flags).
    Shared by ``_plan_for`` and the multi-phase workload signature."""
    return (cfg, use_kernels, interpret, refresh, async_host,
            tuple((key, tuple(slots)) for key, slots in groups.items()),
            tuple((s, d, op.a, op.b) for s, d, op in deferred))


def _make_step_fn(cfg: DeviceConfig, runners, group_slots, bus_j,
                  chan_busy0, host_ch, copy_plan, copy_moves,
                  copy_independent, async_host):
    """Build the single-dispatch jitted step: every stream group's vmapped
    run, the COPY drain (bits scatter + meter bump), and the channel-bus
    fold — one traced computation, one XLA dispatch per call. The drain and
    the fold carry the named scopes ``pim.step.copy_drain`` and
    ``pim.step.bus_fold`` (the runners carry ``pim.runner.*``), which name
    their operations in the compiled program and the device trace."""
    n_slots = cfg.n_slots
    bus_j_c = jnp.asarray(bus_j)
    busy0_c = jnp.asarray(chan_busy0, jnp.float32)
    host_ch_c = jnp.asarray(host_ch, jnp.float32)
    p_bg = jnp.float32(cfg.timing.p_background)
    idx_arrays = [jnp.asarray(np.asarray(slots)) for slots in group_slots]
    makespan = jnp.float32(copy_plan.stats.makespan_ns if copy_plan else 0.0)

    def drain(banks):
        """The COPY drain: the moved rows' scatter and the copies' meter
        charges."""
        bits = banks.bits
        si, sr, di, dr = copy_moves
        if copy_independent:
            # Independent copies (the common gather pattern: distinct
            # destinations, none feeding a later copy) — ONE batched
            # scatter instead of a row-at-a-time chain.
            bits = bits.at[jnp.asarray(di), jnp.asarray(dr)].set(
                bits[jnp.asarray(si), jnp.asarray(sr)])
        else:
            for s_slot, s_row, d_slot, d_row in zip(si, sr, di, dr):
                bits = bits.at[d_slot, d_row].set(bits[s_slot, s_row])
        m = banks.meter
        meter = dataclasses.replace(
            m,
            time_ns=m.time_ns + jnp.asarray(copy_plan.dt_slot),
            e_act=m.e_act + jnp.asarray(copy_plan.e_act_slot),
            e_pre=m.e_pre + jnp.asarray(copy_plan.e_pre_slot),
            e_background=m.e_background
            + jnp.asarray(copy_plan.dt_slot) * p_bg,
            n_act=m.n_act + jnp.asarray(copy_plan.n_act_slot),
            n_pre=m.n_pre + jnp.asarray(copy_plan.n_pre_slot),
            n_aap=m.n_aap + jnp.asarray(copy_plan.n_aap_slot))
        return dataclasses.replace(banks, bits=bits, meter=meter)

    def step(banks, credit, payloads):
        t0 = jnp.asarray(banks.meter.time_ns)
        e0 = jnp.asarray(banks.meter.total_energy_nj)
        new_banks = banks
        reads = []
        for g, runner in enumerate(runners):
            if group_slots[g] == tuple(range(n_slots)):
                # group covers every slot: no gather/scatter round-trip
                # (the homogeneous fast path — one vmap over the banks)
                out, group_reads = jax.vmap(runner.traced)(banks,
                                                           payloads[g])
                new_banks = out
            else:
                idx = idx_arrays[g]
                sub = jax.tree_util.tree_map(lambda x: x[idx], banks)
                out, group_reads = jax.vmap(runner.traced)(sub, payloads[g])
                new_banks = jax.tree_util.tree_map(
                    lambda full, upd: full.at[idx].set(upd), new_banks, out)
            reads.append(group_reads)   # batched: per-slot view sliced lazily
        t1 = jnp.asarray(new_banks.meter.time_ns)      # before the drain
        if copy_plan is not None:
            with jax.named_scope("pim.step.copy_drain"):
                new_banks = drain(new_banks)
        with jax.named_scope("pim.step.bus_fold"):
            # In-slot execution excludes each slot's own bus occupancy and
            # the drained copies (accounted by the contention model below).
            exec_ns = t1 - t0 - bus_j_c
            e1 = jnp.asarray(new_banks.meter.total_energy_nj)
            compute_ns = jnp.max(exec_ns) + makespan
            if async_host:
                hidden = jnp.minimum(
                    host_ch_c,
                    jnp.maximum(jnp.asarray(credit, jnp.float32), 0.0))
            else:
                hidden = jnp.zeros_like(host_ch_c)
            busy = busy0_c - hidden
            wall = jnp.max(busy) + compute_ns
            energy = jnp.sum(e1 - e0)
            # The outgoing double-buffer credit: only an ASYNC step
            # prefetches the next step's transfers under its compute
            # window. A sync step resets the leaf to zero — its host engine
            # ran synchronously, so there is nothing buffered for a later
            # async step to hide behind.
            credit_out = compute_ns if async_host else jnp.float32(0.0)
            hidden_sum = jnp.sum(hidden)
        return (new_banks, tuple(reads), wall, energy, credit_out, busy,
                hidden_sum)

    return jax.jit(step), step


@_spanned("pim.sched.plan")
def _plan_for(cfg: DeviceConfig, stripped, groups, deferred, *,
              use_kernels, interpret, refresh, async_host) -> _StepPlan:
    """Resolve (and cache) the step plan of one schedule layout."""
    plan_key = _plan_key(cfg, groups, deferred, use_kernels=use_kernels,
                         interpret=interpret, refresh=refresh,
                         async_host=async_host)
    plan = _plan_cache.pop(plan_key, None)
    if plan is not None:
        _plan_cache[plan_key] = plan    # (re)insert at the MRU end
        return plan
    SCHED_STATS["plan_misses"] += 1

    runners, group_slots = [], []
    group_n_reads, group_n_pay = [], []
    issue_bus = np.zeros(cfg.n_slots, np.float32)
    host_bus = np.zeros(cfg.n_slots, np.float32)
    for key, slot_ids in groups.items():
        rep = stripped[slot_ids[0]]
        compiled = _compiled_for(rep, cfg.timing)
        runners.append(pim_exec.make_runner(
            compiled, cfg.timing, use_kernels=use_kernels,
            interpret=interpret, refresh=refresh, payload_arg=True))
        group_slots.append(tuple(slot_ids))
        group_n_reads.append(rep.n_reads)
        group_n_pay.append(len(rep.payloads))
        g_issue = issue_bus_ns(rep, cfg.timing)
        g_host = host_bus_ns(rep, cfg.timing)
        for k in slot_ids:
            issue_bus[k] = g_issue
            host_bus[k] = g_host

    issue_ch, host_ch, switch_ch = channel_occupancy(cfg, issue_bus,
                                                     host_bus)
    chan_busy0 = issue_ch + host_ch + switch_ch
    switch_ns = float(switch_ch.sum())

    copy_plan = None
    copy_moves = None
    copy_independent = False
    if deferred:
        copy_plan = _copy_drain_plan(
            cfg, tuple((s, d) for s, d, _ in deferred))
        srcs = [(k, op.a) for k, _, op in deferred]
        dsts = [(d, op.b) for _, d, op in deferred]
        copy_independent = (len(set(dsts)) == len(dsts)
                            and not set(dsts) & set(srcs))
        copy_moves = (tuple(x[0] for x in srcs), tuple(x[1] for x in srcs),
                      tuple(x[0] for x in dsts), tuple(x[1] for x in dsts))

    host_bytes = sum(
        len(slots) * stripped[slots[0]].host_bytes
        for slots in group_slots)

    fn, raw_fn = _make_step_fn(cfg, tuple(runners), tuple(group_slots),
                               issue_bus + host_bus, chan_busy0, host_ch,
                               copy_plan, copy_moves, copy_independent,
                               async_host)
    from . import lint as pim_lint      # lazy: lint imports this module
    plan_lint = pim_lint._plan_diagnostics(cfg, stripped, groups, deferred,
                                           async_host)
    plan = _StepPlan(
        fn=fn,
        raw_fn=raw_fn,
        group_slots=tuple(group_slots),
        bus_total=float((issue_bus + host_bus).sum(dtype=np.float64)),
        host_bus_total=float(host_bus.sum(dtype=np.float64)),
        chan_busy=tuple(float(x) for x in chan_busy0),
        switch_ns=switch_ns,
        host_bytes=host_bytes,
        copy=copy_plan,
        group_n_reads=tuple(group_n_reads),
        group_n_payloads=tuple(group_n_pay),
        lint=plan_lint)
    if len(_plan_cache) >= _PLAN_CACHE_MAX:
        _plan_cache.pop(next(iter(_plan_cache)))
    _plan_cache[plan_key] = plan
    return plan


def _verify_plans(plans, what: str) -> None:
    """The ``verify=True`` gate: raise LintError when any plan in ``plans``
    carries error-severity diagnostics. Scans cached tuples only — no
    analysis runs here."""
    if all(not plan.lint for plan in plans):
        return
    from . import lint as pim_lint
    diags = tuple(d for plan in plans for d in plan.lint)
    if any(d.severity == pim_lint.ERROR for d in diags):
        raise pim_lint.LintError(pim_lint.LintReport(diags), what)


def _lower_step(cfg: DeviceConfig, programs):
    """Shared front half of schedule()/schedule_pipeline(): normalize the
    layout, strip cross-slot copies, group by stream digest. Returns
    ``(flat, stripped, groups, deferred)``."""
    flat = _normalize_programs(cfg, programs)
    for k, p in enumerate(flat):
        if p is not None and (p.num_rows, p.words) != (cfg.num_rows,
                                                       cfg.words):
            raise ValueError(
                f"slot {cfg.slot_coords(k)}: program shape "
                f"{(p.num_rows, p.words)} != device "
                f"shape {(cfg.num_rows, cfg.words)}")

    deferred: list = []
    stripped: list = [None] * cfg.n_slots
    for k, p in enumerate(flat):
        if p is None:
            continue
        stripped[k], slot_copies = _split_copies(cfg, k, p)
        deferred.extend(slot_copies)

    groups: dict = {}
    for k, p in enumerate(stripped):
        if p is not None and len(p.ops):
            groups.setdefault(stream_key(p), []).append(k)
    return flat, stripped, groups, deferred


@_spanned("pim.sched.lower")
def _lower_recurring(cfg: DeviceConfig, step_list, *, what: str, hint: str):
    """Lower a K-step RECURRING layout: step 0 fully, later steps only an
    O(slots) digest check — identical command streams imply identical copy
    stripping and grouping, and stripping preserves HOSTW payloads, so the
    original (pre-strip) programs serve for per-step payload extraction.
    Returns ``(flats, stripped0, groups0, deferred0)``."""
    flat0, stripped0, groups0, deferred0 = _lower_step(cfg, step_list[0])
    flats = [flat0]
    for k, programs in enumerate(step_list[1:], 1):
        if programs is step_list[0]:
            flats.append(flat0)         # replicated layout: nothing to check
            continue
        flat_k = _normalize_programs(cfg, programs)
        for s in range(cfg.n_slots):
            a, b = flat0[s], flat_k[s]
            if ((a is None) != (b is None)
                    or (a is not None and stream_key(a) != stream_key(b))):
                raise ValueError(
                    f"{what} step {k} does not recur: slot "
                    f"{cfg.slot_coords(s)}'s command stream differs from "
                    f"step 0 — {hint}")
        flats.append(flat_k)
    return flats, stripped0, groups0, deferred0


@_spanned("pim.sched.schedule")
def schedule(device: DeviceState,
             programs, *,
             use_kernels: bool | None = None,
             interpret: bool | None = None,
             refresh: bool = False,
             async_host: bool = False,
             verify: bool = False) -> ScheduleResult:
    """Run one program per slot (``None`` = idle slot) and fold the device
    timing model over the per-slot meters.

    ``programs`` may be per-bank (len ``n_banks``; entries are a program for
    the bank's subarray 0 or a nested per-subarray sequence) or flat
    per-slot (len ``n_slots``). Cross-slot ``COPY`` ops are stripped from
    the compiled streams and drained after the in-bank compute (see module
    docstring).

    ``refresh`` folds periodic-refresh stalls/energy into each slot's meter
    (``timing.apply_refresh``); the fold is incremental against the meter's
    ``n_refresh`` history, so repeated refreshed schedules on one device
    charge every event exactly once.

    ``async_host=True`` models a Shared-PIM-style asynchronous host-transfer
    engine: this step's HOSTW/HOSTR bursts overlap the *previous* step's
    compute+copy window (``device.host_credit_ns``), double-buffered, so a
    multi-step pipeline pays ``max(transfer, compute)`` per step instead of
    the sum. Only the wall clock changes — states, reads, and energy are
    identical to the synchronous schedule.

    The whole step — every stream group, the COPY drain, and the
    channel-bus fold — executes as ONE jitted dispatch (the step plan is
    cached per layout), and the result's timing values stay lazy; no
    blocking device sync happens inside this call.
    """
    cfg = device.config
    with _span("pim.sched.lower"):
        _, stripped, groups, deferred = _lower_step(cfg, programs)
    plan = _plan_for(cfg, stripped, groups, deferred,
                     use_kernels=use_kernels, interpret=interpret,
                     refresh=refresh, async_host=async_host)
    if verify:
        _verify_plans((plan,), "schedule layout")
    with _span("pim.sched.payloads"):
        payloads = tuple(
            _payload_stack([stripped[k] for k in slots], cfg.words)
            for slots in plan.group_slots)
    credit = device.host_credit_ns
    if not isinstance(credit, jax.Array):
        credit = jnp.float32(credit)
    with _span("pim.sched.dispatch"):
        new_banks, greads, wall, energy, credit_out, busy, hidden_sum = \
            plan.fn(device.banks, credit, payloads)
    SCHED_STATS["dispatches"] += 1
    stats = plan.copy.stats if plan.copy is not None else CopyDrainStats()
    return ScheduleResult(
        state=device.with_banks(new_banks, host_credit_ns=credit_out),
        wall_ns=wall,
        bus_ns=plan.bus_total,
        energy_nj=energy,
        _group_reads=greads,
        _read_layout=(cfg.n_slots, plan.group_slots),
        copy_ns=stats.makespan_ns,
        host_bytes=plan.host_bytes,
        rank_switch_ns=plan.switch_ns,
        copy_total_ns=stats.total_ns,
        copy_queue_ns=stats.queue_ns,
        link_busy_ns=dict(stats.link_busy_ns),
        _host_bus_ns=plan.host_bus_total,
        _channel_bus_ns=busy if async_host else plan.chan_busy,
        _host_overlap_ns=hidden_sum if async_host else 0.0)


# ---------------------------------------------------------------------------
# Multi-step pipelines: K recurring steps under one lax.scan
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PipelineResult:
    """Outcome of ``schedule_pipeline``: K steps of one recurring layout.

    Per-step timing arrays carry a leading step axis and stay lazy until
    read; the static per-step quantities (bus occupancy, copy drain stats,
    host bytes) are identical every step — the layout recurs by
    construction."""

    state: DeviceState          # final device (credit = last step's compute)
    wall_ns: jax.Array          # (K,) per-step wall clock
    energy_nj: jax.Array        # (K,) per-step energy
    n_steps: int
    bus_ns: float               # per-step bus occupancy (Σ slots)
    host_bytes: int             # per-step off-chip bytes
    copy_ns: float = 0.0        # per-step COPY drain makespan
    copy_total_ns: float = 0.0
    copy_queue_ns: float = 0.0
    rank_switch_ns: float = 0.0
    link_busy_ns: dict = dataclasses.field(default_factory=dict)
    _group_reads: tuple = ()    # per group: per-read (K, n_group, words)
    _read_layout: tuple = (0, ())  # (n_slots, group slot-id tuples)
    _host_overlap_ns: object = 0.0  # (K,) in async mode, else 0.0

    @property
    def reads(self) -> list:
        """Per-step reads, same nesting as ``ScheduleResult.reads``:
        ``reads[k][slot]`` is the slot's host-read rows of step ``k``.
        Sliced out of the group-batched scan output lazily (memoized)."""
        cached = getattr(self, "_reads_cache", None)
        if cached is None:
            cached = _unbatch_reads(self._group_reads, self._read_layout,
                                    self.n_steps)
            self._reads_cache = cached
        return cached

    @property
    def host_overlap_ns_lazy(self):
        """Raw per-step hidden-host-time values (see
        ``ScheduleResult.host_overlap_ns_lazy``)."""
        return self._host_overlap_ns

    @property
    def total_wall_ns(self) -> float:
        return float(jnp.sum(self.wall_ns))

    @property
    def host_overlap_ns(self) -> float:
        """Total host-transfer time hidden across the pipeline (async)."""
        return float(jnp.sum(jnp.asarray(self._host_overlap_ns)))


@_spanned("pim.sched.payloads")
def _step_xs(plan: _StepPlan, flats, words: int) -> tuple:
    """The scan's payload xs of one recurring layout: per stream group, the
    ``(K, n_group, n_payloads, words)`` stack of every step's HOSTW rows
    (``flats`` holds each step's flat per-slot programs)."""
    return tuple(
        _group_payloads([[flat[s] for s in slots] for flat in flats], words,
                        k_axis=True)
        for slots in plan.group_slots)


_pipeline_cache: dict = {}
_PIPELINE_CACHE_MAX = 64


def _pipeline_fn(plan: _StepPlan, n_steps: int, donate: bool):
    """One jitted scan over the plan's step function. With ``donate`` the
    input device buffers are donated to the scan (the caller's state is
    consumed in place); CPU ignores donation, so it is skipped there to
    avoid warnings."""
    key = (id(plan), n_steps, donate)
    hit = _pipeline_cache.pop(key, None)
    if hit is None:
        def pipe(banks, credit, xs):
            def body(carry, x):
                b, c = carry
                nb, reads, wall, energy, credit_out, _busy, hidden = \
                    plan.raw_fn(b, c, x)
                return (nb, credit_out), (reads, wall, energy, hidden)

            # explicit length: a copy-only step layout has no stream
            # groups, so its xs pytree carries no leaves to infer K from
            (nb, credit_out), ys = jax.lax.scan(body, (banks, credit), xs,
                                                length=n_steps)
            return nb, credit_out, ys

        argnums = ((0, 1) if donate and jax.default_backend() != "cpu"
                   else ())
        # the cache entry holds the plan too, pinning id(plan) to this plan
        hit = (jax.jit(pipe, donate_argnums=argnums), plan)
        if len(_pipeline_cache) >= _PIPELINE_CACHE_MAX:
            _pipeline_cache.pop(next(iter(_pipeline_cache)))
    _pipeline_cache[key] = hit
    return hit[0]


@_spanned("pim.sched.pipeline")
def schedule_pipeline(device: DeviceState, steps, *,
                      n_steps: int | None = None,
                      use_kernels: bool | None = None,
                      interpret: bool | None = None,
                      refresh: bool = False,
                      async_host: bool = False,
                      donate: bool = False,
                      verify: bool = False) -> PipelineResult:
    """Run K recurring schedule steps as ONE ``jax.lax.scan`` dispatch.

    ``steps`` is either a sequence of K per-step program layouts (anything
    ``schedule`` accepts — all steps must lower to the SAME layout:
    identical command streams per slot and copy pattern; HOSTW payload
    *data* may differ per step), or — with ``n_steps=K`` — a single layout
    replayed K times. Equivalent to calling ``schedule`` K times in a
    Python loop (bit-exact states, reads, and meters; the async host
    credit chains identically), but the steady-state per-step cost is one
    scan iteration instead of a full host round-trip.

    ``donate=True`` donates the input device's buffers to the scan on
    accelerator backends — fastest for long-lived pipelines, but the
    passed-in ``device`` is CONSUMED (using it afterwards raises a
    donated-buffer error); leave the default to keep ``schedule``'s
    input-preserving contract.
    """
    cfg = device.config
    if n_steps is not None:
        step_list = [steps] * int(n_steps)
    else:
        step_list = list(steps)
    if not step_list:
        raise ValueError("schedule_pipeline needs at least one step")

    flats, stripped0, groups0, deferred0 = _lower_recurring(
        cfg, step_list, what="pipeline",
        hint="schedule_pipeline runs ONE recurring step; use "
             "schedule_workload() for multi-phase sequences or schedule() "
             "for fully heterogeneous ones")

    plan = _plan_for(cfg, stripped0, groups0, deferred0,
                     use_kernels=use_kernels, interpret=interpret,
                     refresh=refresh, async_host=async_host)
    if verify:
        _verify_plans((plan,), "pipeline layout")
    xs = _step_xs(plan, flats, cfg.words)
    credit = device.host_credit_ns
    if not isinstance(credit, jax.Array):
        credit = jnp.float32(credit)
    with _span("pim.sched.dispatch"):
        fn = _pipeline_fn(plan, len(step_list), donate)
        new_banks, credit_out, (reads, walls, energies, hidden) = fn(
            device.banks, credit, xs)
    SCHED_STATS["dispatches"] += 1
    stats = plan.copy.stats if plan.copy is not None else CopyDrainStats()
    return PipelineResult(
        state=device.with_banks(new_banks, host_credit_ns=credit_out),
        wall_ns=walls,
        energy_nj=energies,
        n_steps=len(step_list),
        bus_ns=plan.bus_total,
        host_bytes=plan.host_bytes,
        copy_ns=stats.makespan_ns,
        copy_total_ns=stats.total_ns,
        copy_queue_ns=stats.queue_ns,
        rank_switch_ns=plan.switch_ns,
        link_busy_ns=dict(stats.link_busy_ns),
        _group_reads=reads,
        _read_layout=(cfg.n_slots, plan.group_slots),
        _host_overlap_ns=hidden if async_host else 0.0)


# ---------------------------------------------------------------------------
# Multi-phase workloads: heterogeneous phase sequences under ONE dispatch
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class Phase:
    """One phase of a multi-phase workload: a RECURRING step layout (the
    ``schedule_pipeline`` contract) replayed once per entry of ``steps``.
    Payload data may differ per step; the command streams may not.

    ``async_host=None`` inherits the workload-level flag; an explicit
    ``True``/``False`` overrides it per phase (e.g. an async HOSTW load
    phase feeding a sync compute phase)."""

    steps: tuple
    async_host: bool | None = None

    @classmethod
    def repeat(cls, layout, n_steps: int, **kw) -> "Phase":
        """A phase that replays ONE layout ``n_steps`` times (payloads
        included — use explicit ``steps`` for per-step data)."""
        return cls(steps=(layout,) * int(n_steps), **kw)


def _as_phase(d) -> Phase:
    """Phase descriptors: a :class:`Phase`, a ``(layout, n_steps)`` pair,
    or a sequence of per-step layouts."""
    if isinstance(d, Phase):
        return d
    if (isinstance(d, tuple) and len(d) == 2
            and isinstance(d[1], (int, np.integer))):
        return Phase.repeat(d[0], int(d[1]))
    return Phase(steps=tuple(d))


@dataclasses.dataclass(frozen=True, eq=False)
class PipelinePlan:
    """A fully-lowered multi-phase workload: one cached :class:`_StepPlan`
    per phase plus the sequence signature the plan cache is keyed on.
    Identity-stable across warm ``schedule_workload`` calls, so the jitted
    segmented/switch drivers (keyed on ``id(plan)``) stay warm too."""

    phases: tuple               # per-phase _StepPlan
    n_steps: tuple              # per-phase step count
    async_host: tuple           # per-phase resolved async-host flag
    signature: bytes            # 128-bit digest of the phase sequence


@dataclasses.dataclass
class PhaseResult:
    """One phase's slice of a :class:`WorkloadResult` — the
    :class:`PipelineResult` metrics minus the device state (state is only
    meaningful at the end of the whole workload) plus the async credit
    observed at the phase boundary."""

    wall_ns: jax.Array          # (K,) per-step wall clock
    energy_nj: jax.Array        # (K,) per-step energy
    n_steps: int
    bus_ns: float               # per-step bus occupancy (Σ slots)
    host_bytes: int             # per-step off-chip bytes
    copy_ns: float = 0.0
    copy_total_ns: float = 0.0
    copy_queue_ns: float = 0.0
    rank_switch_ns: float = 0.0
    link_busy_ns: dict = dataclasses.field(default_factory=dict)
    _boundary_credit_ns: object = 0.0   # credit leaving the phase's last step
    _group_reads: tuple = ()
    _read_layout: tuple = (0, ())
    _host_overlap_ns: object = 0.0      # (K,) in async mode, else 0.0

    @property
    def reads(self) -> list:
        """Per-step reads: ``reads[k][slot]``, as in
        :attr:`PipelineResult.reads` (lazy, memoized)."""
        cached = getattr(self, "_reads_cache", None)
        if cached is None:
            cached = _unbatch_reads(self._group_reads, self._read_layout,
                                    self.n_steps)
            self._reads_cache = cached
        return cached

    @property
    def boundary_credit_ns(self) -> float:
        """The ``host_credit_ns`` leaf as it left this phase's last step:
        the next phase's first step overlaps (at most) this much host
        traffic. Zero after a sync phase — see the credit-reset contract.
        Stored as a lazy ``(per-boundary array, phase index)`` pair so a
        warm ``schedule_workload`` call issues no per-phase host
        dispatches; the slice happens here, on first read."""
        b = self._boundary_credit_ns
        if isinstance(b, tuple):
            arr, i = b
            return float(arr[i])
        return float(b)

    @property
    def host_overlap_ns_lazy(self):
        """Raw per-step hidden-host-time values (see
        ``ScheduleResult.host_overlap_ns_lazy``)."""
        return self._host_overlap_ns

    @property
    def total_wall_ns(self) -> float:
        return float(jnp.sum(self.wall_ns))

    @property
    def host_overlap_ns(self) -> float:
        return float(jnp.sum(jnp.asarray(self._host_overlap_ns)))


@dataclasses.dataclass
class WorkloadResult:
    """Outcome of ``schedule_workload``: the final device state plus one
    :class:`PhaseResult` per phase. ``order`` echoes the switch-mode step
    order (``None`` for the segmented lowering)."""

    state: DeviceState
    phases: tuple
    order: tuple | None = None

    @property
    def n_steps(self) -> int:
        return sum(p.n_steps for p in self.phases)

    @property
    def total_wall_ns(self) -> float:
        return sum(p.total_wall_ns for p in self.phases)

    @property
    def total_energy_nj(self) -> float:
        return float(sum(float(jnp.sum(p.energy_nj)) for p in self.phases))

    @property
    def host_overlap_ns(self) -> float:
        return sum(p.host_overlap_ns for p in self.phases)


_workload_plan_cache: dict = {}
_WORKLOAD_PLAN_CACHE_MAX = 64

_workload_fn_cache: dict = {}
_WORKLOAD_FN_CACHE_MAX = 64

# Per-phase lowering memo: a warm re-dispatch of a workload whose phase
# objects are unchanged (the steady-state shape — fresh payloads arrive as
# NEW with_payloads programs and therefore miss) skips the O(steps x
# slots) recurrence re-check and the plan-key tuple rebuild entirely.
_phase_lower_cache: dict = {}
_PHASE_LOWER_CACHE_MAX = 256

# Whole-workload identity memo: re-submitting the SAME Phase objects (the
# steady-state loop shape — state threads through, descriptors don't
# change) skips even the O(phases x steps) id walks and goes straight to
# the cached driver + xs. Entries pin the steps tuples they key on, so a
# recycled id can never alias a dead layout.
_workload_fast_cache: dict = {}
_WORKLOAD_FAST_CACHE_MAX = 32


def clear_caches() -> None:
    """Drop every scheduler cache — compiled streams, step plans, pipeline
    and workload drivers, lowering memos and pinned payload batches — and
    with them the compiled programs they keep alive. The next call of each
    layout lowers and compiles again; results are unchanged."""
    for cache in (_compile_cache, _plan_cache, _pipeline_cache,
                  _workload_plan_cache, _workload_fn_cache,
                  _phase_lower_cache, _workload_fast_cache):
        cache.clear()
    _payload_cache_clear()
    _copy_drain_plan.cache_clear()


def _layout_ids(step):
    """Identity fingerprint of one step layout (programs by id, nesting
    preserved). Mutating a layout in place swaps the contained program
    ids, so the fingerprint-keyed cache can never serve stale lowerings.
    Returns None for containers it does not recognize (uncacheable)."""
    if step is None or isinstance(step, PimProgram):
        return id(step)
    if isinstance(step, (list, tuple)):
        parts = tuple(_layout_ids(x) for x in step)
        return None if any(p is None for p in parts) else parts
    return None


def _workload_fn(wplan: PipelinePlan, donate: bool):
    """The segmented-scan driver: one ``lax.scan`` per phase, chained
    under ONE jit with the banks pytree and the async credit threaded
    through — a whole multi-phase workload is one XLA dispatch."""
    key = ("seg", id(wplan), donate)
    hit = _workload_fn_cache.pop(key, None)
    if hit is None:
        plans = wplan.phases

        def drive(banks, credit, xs_phases):
            outs, boundary = [], []
            b, c = banks, credit
            for plan, n, xs in zip(plans, wplan.n_steps, xs_phases):
                def body(carry, x, plan=plan):
                    bb, cc = carry
                    nb, reads, wall, energy, credit_out, _busy, hidden = \
                        plan.raw_fn(bb, cc, x)
                    return (nb, credit_out), (reads, wall, energy, hidden)

                # explicit length: a copy-only phase has no stream groups,
                # so its xs pytree carries no leaves to infer K from
                (b, c), ys = jax.lax.scan(body, (b, c), xs, length=n)
                outs.append(ys)
                boundary.append(c)
            return b, c, tuple(outs), jnp.stack(boundary)

        argnums = ((0, 1) if donate and jax.default_backend() != "cpu"
                   else ())
        # the cache entry holds the wplan too, pinning id(wplan)
        hit = (jax.jit(drive, donate_argnums=argnums), wplan)
        if len(_workload_fn_cache) >= _WORKLOAD_FN_CACHE_MAX:
            _workload_fn_cache.pop(next(iter(_workload_fn_cache)))
    _workload_fn_cache[key] = hit
    return hit[0]


def _switch_fn(wplan: PipelinePlan, words: int, donate: bool):
    """The plan-switching driver: one ``lax.scan`` over a phase-index
    sequence, ``lax.switch``-ing across the per-phase step fns. Branches
    must return identical pytrees, so each branch flattens its reads to a
    zero-padded ``(R_max, words)`` block and slices its payloads out of a
    common ``(G_max, S_max, P_max, words)`` xs leaf; the per-phase views
    are recovered statically by the caller."""
    key = ("switch", id(wplan), donate)
    hit = _workload_fn_cache.pop(key, None)
    if hit is None:
        plans = wplan.phases
        r_tot = [sum(nr * len(slots) for nr, slots in
                     zip(p.group_n_reads, p.group_slots)) for p in plans]
        r_max = max(r_tot)
        branches = []
        for plan, r_p in zip(plans, r_tot):
            def branch(banks, credit, pay, plan=plan, r_p=r_p):
                payloads = tuple(
                    pay[g, :len(slots), :n_pay]
                    for g, (slots, n_pay) in enumerate(
                        zip(plan.group_slots, plan.group_n_payloads)))
                nb, reads, wall, energy, credit_out, _busy, hidden = \
                    plan.raw_fn(banks, credit, payloads)
                if r_p:
                    fr = jnp.concatenate(
                        [r for group in reads for r in group], axis=0)
                    fr = jnp.zeros((r_max, words),
                                   jnp.uint32).at[:r_p].set(fr)
                else:
                    fr = jnp.zeros((r_max, words), jnp.uint32)
                return nb, credit_out, (fr, wall, energy, hidden,
                                        credit_out)

            branches.append(branch)

        def drive(banks, credit, idx, pay):
            def body(carry, x):
                b, c = carry
                i, p = x
                nb, cc, ys = jax.lax.switch(i, branches, b, c, p)
                return (nb, cc), ys

            (nb, cc), ys = jax.lax.scan(body, (banks, credit), (idx, pay))
            return nb, cc, ys

        argnums = ((0, 1) if donate and jax.default_backend() != "cpu"
                   else ())
        hit = (jax.jit(drive, donate_argnums=argnums), wplan)
        if len(_workload_fn_cache) >= _WORKLOAD_FN_CACHE_MAX:
            _workload_fn_cache.pop(next(iter(_workload_fn_cache)))
    _workload_fn_cache[key] = hit
    return hit[0]


def _phase_result(cfg, plan: _StepPlan, n_steps: int, walls, energies,
                  greads, hidden, boundary) -> PhaseResult:
    stats = plan.copy.stats if plan.copy is not None else CopyDrainStats()
    return PhaseResult(
        wall_ns=walls,
        energy_nj=energies,
        n_steps=n_steps,
        bus_ns=plan.bus_total,
        host_bytes=plan.host_bytes,
        copy_ns=stats.makespan_ns,
        copy_total_ns=stats.total_ns,
        copy_queue_ns=stats.queue_ns,
        rank_switch_ns=plan.switch_ns,
        link_busy_ns=dict(stats.link_busy_ns),
        _boundary_credit_ns=boundary,
        _group_reads=greads,
        _read_layout=(cfg.n_slots, plan.group_slots),
        _host_overlap_ns=hidden)


def _run_segmented(device: DeviceState, wplan: PipelinePlan, xs_phases,
                   fn) -> WorkloadResult:
    """Dispatch a prepared segmented-scan workload and wrap the outputs.
    Shared by the cold path and the whole-workload identity fast path."""
    cfg = device.config
    credit = device.host_credit_ns
    if not isinstance(credit, jax.Array):
        credit = jnp.float32(credit)
    with _span("pim.sched.dispatch"):
        new_banks, credit_out, outs, boundary = fn(
            device.banks, credit, xs_phases)
    SCHED_STATS["dispatches"] += 1
    phase_results = tuple(
        _phase_result(cfg, plan, wplan.n_steps[p], walls, energies,
                      greads,
                      hidden if wplan.async_host[p] else 0.0,
                      (boundary, p))
        for p, (plan, (greads, walls, energies, hidden)) in enumerate(
            zip(wplan.phases, outs)))
    return WorkloadResult(
        state=device.with_banks(new_banks, host_credit_ns=credit_out),
        phases=phase_results,
        order=None)


@_spanned("pim.sched.workload")
def schedule_workload(device: DeviceState, phases, *,
                      order: Sequence[int] | None = None,
                      use_kernels: bool | None = None,
                      interpret: bool | None = None,
                      refresh: bool = False,
                      async_host: bool = False,
                      donate: bool = False,
                      verify: bool = False) -> WorkloadResult:
    """Run a HETEROGENEOUS multi-phase workload as ONE XLA dispatch.

    ``phases`` is a sequence of phase descriptors (:class:`Phase`, a
    ``(layout, n_steps)`` pair, or a sequence of per-step layouts); each
    phase is one recurring step layout in the ``schedule_pipeline`` sense
    — per-step HOSTW data may differ, command streams may not. Phases may
    differ arbitrarily from each other (different streams, grouping, copy
    patterns, async flags).

    With ``order=None`` (the static, hot path) the phases execute
    back-to-back — one ``lax.scan`` per contiguous phase segment, chained
    under a single jitted driver. With ``order=[phase_idx, ...]`` (the
    data-dependent path) the steps execute in exactly that interleaved
    order under one ``lax.scan`` over the phase index, ``lax.switch``-ing
    across the per-phase step fns; each phase's steps are consumed FIFO,
    so ``order`` must name phase ``p`` exactly ``len(phases[p].steps)``
    times. Switch mode pads every step's reads/payloads to the workload
    maximum — prefer the segmented lowering when the order is static.

    Equivalent to per-phase ``schedule_pipeline`` / per-step ``schedule``
    loops: bit-exact states, reads, and meters, with the async host credit
    and the refresh-history meter threaded through the scan carry across
    every phase boundary (a sync phase RESETS the credit — see the step-fn
    contract). Timing/energy outputs stay lazy per phase.
    """
    cfg = device.config
    phase_list = [_as_phase(d) for d in phases]
    if not phase_list:
        raise ValueError("schedule_workload needs at least one phase")

    fkey = (cfg, use_kernels, interpret, refresh, async_host, donate)
    if order is None:
        entry = _workload_fast_cache.pop(fkey, None)
        if entry is not None:
            _workload_fast_cache[fkey] = entry   # MRU touch
            steps_refs, wplan_c, xs_c, fn_c = entry
            if len(phase_list) == len(steps_refs) and all(
                    ph.steps is st and
                    (async_host if ph.async_host is None
                     else bool(ph.async_host)) == ah
                    for ph, (st, ah) in zip(phase_list, steps_refs)):
                if verify:
                    _verify_plans(wplan_c.phases, "workload layout")
                return _run_segmented(device, wplan_c, xs_c, fn_c)

    plans, flats_p, keys, a_hs = [], [], [], []
    for p, ph in enumerate(phase_list):
        step_list = list(ph.steps)
        if not step_list:
            raise ValueError(f"workload phase {p} has no steps")
        a_h = async_host if ph.async_host is None else bool(ph.async_host)
        ids = _layout_ids(tuple(step_list))
        lkey = (None if ids is None else
                (cfg, use_kernels, interpret, refresh, a_h, ids))
        hit = _phase_lower_cache.pop(lkey, None) if lkey else None
        if hit is None:
            flats, stripped0, groups0, deferred0 = _lower_recurring(
                cfg, step_list, what=f"workload phase {p}",
                hint="each phase of schedule_workload is ONE recurring "
                     "step layout; split heterogeneous steps into "
                     "separate phases")
            plan = _plan_for(cfg, stripped0, groups0, deferred0,
                             use_kernels=use_kernels, interpret=interpret,
                             refresh=refresh, async_host=a_h)
            pk = _plan_key(cfg, groups0, deferred0,
                           use_kernels=use_kernels, interpret=interpret,
                           refresh=refresh, async_host=a_h)
            # flats hold every layout program, pinning the ids in lkey
            hit = (flats, plan, pk)
        if lkey:
            if len(_phase_lower_cache) >= _PHASE_LOWER_CACHE_MAX:
                _phase_lower_cache.pop(next(iter(_phase_lower_cache)))
            _phase_lower_cache[lkey] = hit
        flats, plan, pk = hit
        plans.append(plan)
        flats_p.append(flats)
        keys.append((pk, len(step_list)))
        a_hs.append(a_h)

    # The phase-sequence signature keys the workload plan cache, keeping
    # PipelinePlan identity (and thereby the jitted drivers) stable across
    # warm calls with fresh payload data.
    wkey = tuple(keys)
    wplan = _workload_plan_cache.pop(wkey, None)
    if wplan is None:
        if len(_workload_plan_cache) >= _WORKLOAD_PLAN_CACHE_MAX:
            _workload_plan_cache.pop(next(iter(_workload_plan_cache)))
        wplan = PipelinePlan(
            phases=tuple(plans),
            n_steps=tuple(len(ph.steps) for ph in phase_list),
            async_host=tuple(a_hs),
            signature=ir.sequence_digest(
                hashlib.blake2b(repr(k).encode(), digest_size=16).digest()
                for k in keys))
    _workload_plan_cache[wkey] = wplan
    if verify:
        _verify_plans(wplan.phases, "workload layout")

    if order is None:
        xs_phases = tuple(_step_xs(plan, flats, cfg.words)
                          for plan, flats in zip(wplan.phases, flats_p))
        with _span("pim.sched.dispatch"):
            fn = _workload_fn(wplan, donate)
        if len(_workload_fast_cache) >= _WORKLOAD_FAST_CACHE_MAX:
            _workload_fast_cache.pop(next(iter(_workload_fast_cache)))
        _workload_fast_cache[fkey] = (
            tuple((ph.steps, ah) for ph, ah in zip(phase_list, a_hs)),
            wplan, xs_phases, fn)
        return _run_segmented(device, wplan, xs_phases, fn)

    credit = device.host_credit_ns
    if not isinstance(credit, jax.Array):
        credit = jnp.float32(credit)

    order = tuple(int(i) for i in order)
    n_ph = len(wplan.phases)
    counts = [0] * n_ph
    for i in order:
        if not 0 <= i < n_ph:
            raise ValueError(
                f"order index {i} out of range for {n_ph} phases")
        counts[i] += 1
    for p, (got, want) in enumerate(zip(counts, wplan.n_steps)):
        if got != want:
            raise ValueError(
                f"order names phase {p} {got} times but the phase has "
                f"{want} steps — each phase's steps are consumed FIFO")

    g_max = max(len(p.group_slots) for p in wplan.phases)
    s_max = max((len(s) for p in wplan.phases for s in p.group_slots),
                default=0)
    p_max = max((n for p in wplan.phases for n in p.group_n_payloads),
                default=0)
    with _span("pim.sched.payloads"):
        pay = np.zeros((len(order), g_max, s_max, p_max, cfg.words),
                       np.uint32)
        cursor = [0] * n_ph
        for t, pi in enumerate(order):
            plan = wplan.phases[pi]
            flat = flats_p[pi][cursor[pi]]
            cursor[pi] += 1
            for g, slots in enumerate(plan.group_slots):
                for j, s in enumerate(slots):
                    for q, arr in enumerate(flat[s].payloads):
                        pay[t, g, j, q] = np.asarray(arr, np.uint32)

    with _span("pim.sched.dispatch"):
        fn = _switch_fn(wplan, cfg.words, donate)
        new_banks, credit_out, (fr, walls, energies, hidden, credits) = fn(
            device.banks, credit,
            jnp.asarray(np.asarray(order, np.int32)), jnp.asarray(pay))
    SCHED_STATS["dispatches"] += 1
    phase_results = []
    for p, plan in enumerate(wplan.phases):
        ks = [t for t, o in enumerate(order) if o == p]
        sel = jnp.asarray(np.asarray(ks, np.int32))
        fr_p = fr[sel]
        greads, off = [], 0
        for g, slots in enumerate(plan.group_slots):
            n_g = len(slots)
            rds = []
            for _ in range(plan.group_n_reads[g]):
                rds.append(fr_p[:, off:off + n_g])
                off += n_g
            greads.append(tuple(rds))
        phase_results.append(_phase_result(
            cfg, plan, wplan.n_steps[p], walls[sel], energies[sel],
            tuple(greads),
            hidden[sel] if wplan.async_host[p] else 0.0,
            (credits, ks[-1])))
    phase_results = tuple(phase_results)
    order_out = order

    return WorkloadResult(
        state=device.with_banks(new_banks, host_credit_ns=credit_out),
        phases=phase_results,
        order=order_out)


# ---------------------------------------------------------------------------
# In-DRAM movement / reduction primitives
# ---------------------------------------------------------------------------

def gather_rows(cfg: DeviceConfig, moves, programs=None) -> list:
    """Per-slot COPY streams for in-DRAM row movement (zero host bytes).

    ``moves``: iterable of ``((src_bank, src_sub, src_row),
    (dst_bank, dst_sub, dst_row))``. Each move records one ``COPY`` in the
    *source* slot's stream; the scheduler drains them after the step's
    compute, so gathered rows hold post-compute values and are readable by
    the next step. ``programs`` (optional, any layout ``schedule`` accepts)
    is appended to — pass the step's compute programs to fuse compute +
    gather into one ``schedule`` call. Returns a flat per-slot list.
    """
    base = (_normalize_programs(cfg, programs) if programs is not None
            else [None] * cfg.n_slots)
    builders: dict[int, ProgramBuilder] = {}
    for (sb, ss, sr), (db, ds, dr) in moves:
        slot = cfg.slot_index(sb, ss)
        cfg.slot_index(db, ds)          # validate destination coordinates
        builders.setdefault(
            slot, ProgramBuilder(cfg.num_rows, cfg.words)).copy_row(
                sr, dr, db, ds)
    out = list(base)
    for slot, b in builders.items():
        copies = b.build()
        out[slot] = (copies if out[slot] is None
                     else ir.concat([out[slot], copies]))
    return out


def xor_reduce_program(num_rows: int, words: int, rows: Sequence[int],
                       dst: int) -> PimProgram:
    """One slot's in-place XOR fold: ``dst <- rows[0] ^ rows[1] ^ ...`` via
    Ambit XOR (rows must avoid the T0..T3 scratch). The reduction half of a
    gather/reduce step — all row traffic stays inside the subarray."""
    b = ProgramBuilder(num_rows, words)
    rows = list(rows)
    assert rows, "need at least one row to reduce"
    if rows[0] != dst:
        b.rowclone(rows[0], dst)
    for r in rows[1:]:
        b.ambit_xor(dst, r, dst)
    return b.build()


# ---------------------------------------------------------------------------
# Host-buffer partitioners: one large buffer → per-slot programs
# ---------------------------------------------------------------------------

BuildFn = Callable[[ProgramBuilder, list[int]], None]


def _chunk_program(chunk: np.ndarray, num_rows: int, words: int,
                   build: BuildFn | None, read_back: bool) -> PimProgram:
    b = ProgramBuilder(num_rows, words)
    b.issue()
    rows = list(range(chunk.shape[0]))
    for r in rows:
        b.write_row(r, chunk[r])
    if build is not None:
        build(b, rows)
    if read_back:
        for r in rows:
            b.read_row(r)
    return b.build()


def _regroup(programs: list, subarrays: int):
    """Flat chunk list → nested [bank][sub] when placing across the
    subarray axis; flat per-bank list otherwise (back-compat)."""
    if subarrays == 1:
        return programs
    return [programs[b * subarrays:(b + 1) * subarrays]
            for b in range(len(programs) // subarrays)]


def shard_rows(data: np.ndarray, n_banks: int, num_rows: int = NUM_ROWS, *,
               subarrays: int = 1, build: BuildFn | None = None,
               read_back: bool = False) -> list:
    """Split a ``(R, words)`` row buffer row-wise across ``n_banks`` banks
    (× ``subarrays`` slots per bank).

    Each slot receives a contiguous chunk of rows, HOSTW-written to its rows
    ``0..k-1`` after one ISSUE burst; ``build(builder, local_rows)`` then
    appends the per-slot compute. Chunks are ``np.array_split``-balanced, so
    R need not divide evenly (trailing slots may be one row short or idle).
    Returns a flat per-bank list, or nested ``[bank][sub]`` when
    ``subarrays > 1`` — both layouts feed ``schedule`` directly.
    """
    data = np.asarray(data, dtype=np.uint32)
    assert data.ndim == 2, data.shape
    chunks = np.array_split(data, n_banks * subarrays, axis=0)
    return _regroup(
        [_chunk_program(c, num_rows, data.shape[1], build, read_back)
         for c in chunks], subarrays)


def shard_lanes(data: np.ndarray, n_banks: int, num_rows: int = NUM_ROWS, *,
                subarrays: int = 1, build: BuildFn | None = None,
                read_back: bool = False) -> list:
    """Split a ``(R, words)`` row buffer lane-wise across ``n_banks`` banks
    (× ``subarrays`` slots per bank).

    Slot ``k`` receives the word-slice ``[:, k*w:(k+1)*w]`` of every row
    (``w = words // n_slots``) — all slots then run the SAME command stream
    over different columns, the natural SIMD split for element-parallel
    workloads (element width must divide 32 so lanes never straddle the
    word-slice boundary). Layout as in ``shard_rows``.
    """
    data = np.asarray(data, dtype=np.uint32)
    assert data.ndim == 2, data.shape
    words = data.shape[1]
    n_slots = n_banks * subarrays
    if words % n_slots:
        raise ValueError(f"words={words} not divisible by n_banks*subarrays="
                         f"{n_slots}")
    w = words // n_slots
    chunks = [data[:, k * w:(k + 1) * w] for k in range(n_slots)]
    return _regroup(
        [_chunk_program(c, num_rows, w, build, read_back) for c in chunks],
        subarrays)
