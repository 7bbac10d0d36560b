"""Breakdown of a traced window by the program's own spans and scopes.

``tracered`` reduces a profiler trace to the benchmark's device numbers:
the window, the jobs and the idle gaps there are defined by the
benchmark's own host spans (``bench.*``). This module reads the same trace
one level deeper and leaves those definitions as they are:

  spans    host spans of the benchmark and of the program (``bench.*``,
           ``pim.*``), nested per host thread: each name's count, total
           seconds and self seconds (a span's duration less what its
           children cover)
  gaps     idle device seconds by the innermost span covering them, or
           ``host.other`` where none does: the buckets add up to the idle
           time of the window
  scopes   busy device seconds by the innermost ``pim.*`` named scope of
           the operation that ran, or ``unscoped``: the buckets add up to
           the busy time of the window

An operation's scope comes from its own stats where they carry its
``op_name`` path (read in full), and otherwise from the ``op_name``
metadata of its instruction in the compiled program's HLO text: the CPU's
events name the instruction and module in their stats, the TPU's name the
instruction in the event's name, and the module is the "XLA Modules"
event running at the time. Where operations nest on one device line (a
loop and the operations of its body), the innermost owns the time.

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s> --out <dir>

profiles one window of a cell as ``run.py --trace 1`` does, keeps the
trace under ``<dir>``, logs self times and device time by scope to stderr
and prints the breakdown as JSON.
"""
from __future__ import annotations

import argparse
import bisect
import dataclasses
import heapq
import json
import re
import sys
import time
from pathlib import Path

import tracered

PREFIXES = (tracered.SPAN_PREFIX, "pim.")
UNSCOPED = "unscoped"
# Stats of a device event that may hold its op_name path, in the order
# they are read; any other string stat is read after them.
SCOPE_STATS = ("tf_op", "op_name", "long_name")
_SCOPE = re.compile(r"(?:^|/)(pim\.[A-Za-z0-9_.]+)")


def scope_of(op_name: str) -> str | None:
    """The innermost ``pim.*`` component of an ``op_name`` path."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else None


# -- compiled HLO text --------------------------------------------------------

_HEADER = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$")
_INSTR = re.compile(r"^\s*(ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(
    r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)"
    r"|branch_computations=\{([^}]*)\}")


def hlo_scopes(text: str) -> dict:
    """``(module, instruction) -> scope`` from a compiled program's HLO
    text. An instruction without a scope of its own (a fusion, a wrapped
    call) takes the scope of the computations it calls: their root's, or
    else the first that any of their instructions has."""
    module = re.search(r"^HloModule ([^\s,]+)", text, re.M).group(1)
    own, calls, roots, members = {}, {}, {}, {}
    comp = None
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m and comp is not None:
            name = m.group(2)
            op = _OP_NAME.search(line)
            own[name] = scope_of(op.group(1)) if op else None
            called = []
            for a, b in _CALLS.findall(line):
                called += [a] if a else [c.strip().lstrip("%")
                                         for c in b.split(",")]
            calls[name] = called
            members[comp].append(name)
            if m.group(1):
                roots[comp] = name
            continue
        h = _HEADER.match(line)
        if h:
            comp = h.group(1)
            members[comp] = []

    def resolve(name, seen=()):
        if own.get(name):
            return own[name]
        for c in calls.get(name, ()):
            if c in seen:
                continue
            for inner in [roots.get(c)] + members.get(c, []):
                if inner is not None:
                    s = resolve(inner, seen + (c,))
                    if s:
                        return s
        return None

    return {(module, n): resolve(n) for n in own}


# -- loading ------------------------------------------------------------------

@dataclasses.dataclass
class Trace:
    devices: dict     # device key -> list of (start_ns, end_ns, op, scope)
    threads: dict     # host line -> list of (start_ns, end_ns, span name)


_OP = re.compile(r"%?([\w.\-]+) = ")
_MODULE_ID = re.compile(r"\(\d+\)$")


def _event_scope(name: str, stats: dict, module, hlo: dict) -> str | None:
    """The scope in an event's stats or name, else its instruction's in the
    HLO text: on the CPU the stats name the instruction and its module; on
    the TPU the event's name starts with the instruction (``%copy.3 =
    ...``) and the module is the one running at the time."""
    texts = [stats[k] for k in SCOPE_STATS if k in stats] + [
        v for k, v in stats.items() if k not in SCOPE_STATS] + [name]
    for text in texts:
        found = isinstance(text, str) and scope_of(text)
        if found:
            return found
    op = stats.get("hlo_op")
    if op is None:
        m = _OP.match(name)
        op = m.group(1) if m else name
    return hlo.get((stats.get("hlo_module", module), op))


def _modules(line) -> list:
    """``(start_ns, end_ns, module)`` of an "XLA Modules" line."""
    return sorted((e.start_ns, e.start_ns + e.duration_ns,
                   _MODULE_ID.sub("", e.name)) for e in line.events)


def load(path: str, is_device=tracered.tpu_ops, hlo_texts=()) -> Trace:
    """The device operations with their scopes, and every ``bench.*`` and
    ``pim.*`` host span by host thread line. ``hlo_texts`` are compiled
    programs' HLO texts, for operations whose stats name no scope."""
    from jax.profiler import ProfileData
    hlo: dict = {}
    for text in hlo_texts:
        hlo.update(hlo_scopes(text))
    devices: dict = {}
    threads: dict = {}
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        by_name = {line.name: line for line in lines}
        modules = []
        if "XLA Ops" in by_name and "XLA Modules" in by_name:
            modules = _modules(by_name["XLA Modules"])
        starts = [m[0] for m in modules]
        for line in lines:
            if line.name == "XLA Modules" and modules:
                continue
            if is_device(plane.name, line.name):
                out = devices.setdefault(plane.name, [])
                seen: dict = {}
                for e in line.events:
                    i = bisect.bisect_right(starts, e.start_ns) - 1
                    module = modules[i][2] if i >= 0 else None
                    if (e.name, module) not in seen:
                        seen[e.name, module] = _event_scope(
                            e.name, dict(e.stats), module, hlo)
                    out.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name, seen[e.name, module]))
            elif plane.name == "/host:CPU":
                spans = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                         for e in line.events
                         if e.name.startswith(PREFIXES)]
                if spans:
                    threads[f"{plane.name}/{line.name}"] = sorted(spans)
    return Trace(devices=devices, threads=threads)


# -- reduction ----------------------------------------------------------------

def innermost(intervals) -> list:
    """Cut ``(start, end, key)`` intervals into ``(start, end, key)``
    pieces that do not overlap, each owned by the interval covering it that
    started last (of two that start together, the one that ends first):
    where intervals nest, the innermost. The pieces cover the union."""
    ordered = sorted(intervals, key=lambda t: (t[0], -t[1]))
    bounds = sorted({x for s, e, _ in intervals for x in (s, e)})
    out: list = []
    active: list = []
    j = 0
    for lo, hi in zip(bounds, bounds[1:]):
        while j < len(ordered) and ordered[j][0] <= lo:
            s, e, key = ordered[j]
            heapq.heappush(active, (-s, e, j, key))
            j += 1
        while active and active[0][1] <= lo:
            heapq.heappop(active)
        if active:
            key = active[0][3]
            if out and out[-1][2] == key and out[-1][1] == lo:
                out[-1] = (out[-1][0], hi, key)
            else:
                out.append((lo, hi, key))
    return out


def _clip(pieces, lo, hi):
    return [(max(s, lo), min(e, hi), k) for s, e, k in pieces
            if e > lo and s < hi]


@dataclasses.dataclass
class Breakdown:
    window_s: float
    busy_s: float
    jobs: int
    spans: dict       # name -> {"n", "total_s", "self_s"}
    gaps: dict        # innermost span name (or host.other) -> idle seconds
    scopes: dict      # scope (or unscoped) -> busy device seconds
    scope_ops: dict   # scope -> {op: busy device seconds}

    @property
    def idle_s(self) -> float:
        return self.window_s - self.busy_s

    def as_dict(self, n_ops: int = 5) -> dict:
        top = {sc: sorted(ops.items(), key=lambda kv: -kv[1])[:n_ops]
               for sc, ops in self.scope_ops.items()}
        return {"window_s": self.window_s, "busy_s": self.busy_s,
                "idle_s": self.idle_s, "jobs": self.jobs,
                "spans": self.spans, "gaps": self.gaps,
                "scopes": self.scopes, "scope_ops": top}


def reduce(tr: Trace) -> Breakdown:
    """The window and jobs exactly as ``tracered.reduce`` defines them
    (``bench.*`` spans alone); span self times, idle gaps by innermost
    span and busy time by scope inside that window."""
    bench = [s for spans in tr.threads.values() for s in spans
             if s[2].startswith(tracered.SPAN_PREFIX)]
    if not bench:
        raise ValueError("the trace holds no bench.* host spans")
    lo = min(s for s, _, _ in bench)
    hi = max(e for _, e, _ in bench)

    spans: dict = {}
    every = []
    for line in tr.threads.values():
        inside = [t for t in line if t[1] > lo and t[0] < hi]
        every.extend(inside)
        for s, e, n in inside:
            rec = spans.setdefault(n, {"n": 0, "total_s": 0.0,
                                       "self_s": 0.0})
            rec["n"] += 1
            rec["total_s"] += (min(e, hi) - max(s, lo)) * 1e-9
        for s, e, n in _clip(innermost(inside), lo, hi):
            spans[n]["self_s"] += (e - s) * 1e-9
    # idle gaps go to the innermost span; where spans of two threads
    # overlap, to the one that started last
    owners = innermost(every)

    gaps: dict = {}
    scopes: dict = {}
    scope_ops: dict = {}
    busy = []
    starts = [s for s, _, _ in owners]
    for events in tr.devices.values():
        inside = [(s, e, (n, sc)) for s, e, n, sc in events
                  if e > lo and s < hi]
        if not inside:
            continue
        pieces = _clip(innermost(inside), lo, hi)
        busy.append(sum(e - s for s, e, _ in pieces))
        for s, e, (n, sc) in pieces:
            sc = sc or UNSCOPED
            scopes[sc] = scopes.get(sc, 0.0) + (e - s) * 1e-9
            ops = scope_ops.setdefault(sc, {})
            ops[n] = ops.get(n, 0.0) + (e - s) * 1e-9
        merged = tracered.union([(s, e) for s, e, _ in pieces], lo, hi)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            covered = 0.0
            first = max(bisect.bisect_right(starts, g0) - 1, 0)
            for s, e, n in owners[first:]:
                if s >= g1:
                    break
                o = max(0, min(e, g1) - max(s, g0))
                if o:
                    gaps[n] = gaps.get(n, 0.0) + o * 1e-9
                    covered += o
            gaps[tracered.OTHER] = (gaps.get(tracered.OTHER, 0.0)
                                    + (g1 - g0 - covered) * 1e-9)
    if not busy:
        raise ValueError("no device operation ran inside the traced window")
    k = len(busy)
    avg = (lambda d: {n: v / k for n, v in d.items()})
    return Breakdown(
        window_s=(hi - lo) * 1e-9, busy_s=sum(busy) / k * 1e-9,
        jobs=sum(n == tracered.ENTRY for _, _, n in bench),
        spans=spans, gaps=avg(gaps), scopes=avg(scopes),
        scope_ops={sc: avg(ops) for sc, ops in scope_ops.items()})


def log(b: Breakdown) -> None:
    """Per-span self time and per-scope device time, one line each."""
    for n, s in sorted(b.spans.items(), key=lambda kv: -kv[1]["self_s"]):
        print(f"[spans] span {n}: n {s['n']}, total {s['total_s']:.6f} s, "
              f"self {s['self_s']:.6f} s, idle under it "
              f"{b.gaps.get(n, 0.0):.6f} s", file=sys.stderr)
    for sc, v in sorted(b.scopes.items(), key=lambda kv: -kv[1]):
        ops = sorted(b.scope_ops[sc].items(), key=lambda kv: -kv[1])[:3]
        print(f"[spans] scope {sc}: {v:.6f} s of {b.busy_s:.6f} s busy; "
              f"top ops {ops}", file=sys.stderr)


def program_hlo(pim, cfg) -> list:
    """HLO text of every scan driver the scheduler's pipeline cache holds,
    compiled again from shapes for the default device: the join for
    operations whose stats name no scope (all of them, on the CPU and on
    the TPU)."""
    import jax
    import jax.numpy as jnp
    sched = sys.modules["repro.core.pim.schedule"]
    banks = jax.eval_shape(lambda: pim.make_device(cfg).banks)
    credit = jax.ShapeDtypeStruct((), jnp.float32)
    texts = []
    for (_, n_steps, _), (fn, plan) in list(sched._pipeline_cache.items()):
        xs = tuple(jax.ShapeDtypeStruct((n_steps, len(slots), n_pay,
                                         cfg.words), jnp.uint32)
                   for slots, n_pay in zip(plan.group_slots,
                                           plan.group_n_payloads))
        texts.append(fn.lower(banks, credit, xs).compile().as_text())
    return texts


def profile(spec, pim, seed: int, seconds: float, out: str,
            is_device=tracered.tpu_ops) -> tuple:
    """One traced window of a cell (``harness.measure``), its trace kept
    under ``out``. Returns the measurement and the breakdown."""
    import harness
    res = harness.measure(spec, pim, seed, seconds, True,
                          time.perf_counter(), trace_dir=out)
    hlo = program_hlo(pim, res["cell"].cfg)
    b = reduce(load(tracered.find_xplane(out), is_device, hlo))
    return res, b


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)
    import harness
    import run
    spec = harness.Spec.load(args.workload)
    jax = run.start_jax()
    dev = jax.devices()[0]
    if dev.platform != run.PLATFORM:
        print(f"spans: {args.workload} needs a {run.PLATFORM} chip; JAX "
              f"found {dev.platform!r}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(run.ROOT / "src"))
    from repro.core import pim
    res, b = profile(spec, pim, args.seed, args.seconds,
                     str(Path(args.out).resolve()))
    log(b)
    print(json.dumps(dict(b.as_dict(), correct=res["correct"],
                          device=dev.device_kind)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
