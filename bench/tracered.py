"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's
device numbers.

  busy_s     union of the intervals in which an operation ran on a device,
             inside the traced window, averaged over the devices that ran
  window_s   first start to last end of the benchmark's own host spans
             (``bench.*``), i.e. the jobs of the traced window
  jobs       jobs in the traced window (``bench.entry`` spans)
  ops        device seconds by operation name
  gaps       idle device seconds by the host span that covered them, or
             ``host.other`` where none did

Device and host events come from the same trace, on the profiler's clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

import numpy as np

SPAN_PREFIX = "bench."
ENTRY = "bench.entry"          # one per job
OTHER = "host.other"


def tpu_ops(plane: str, line: str) -> bool:
    """The operations of each TPU chip ("XLA Ops"; where a plane has no
    such line, its "XLA Modules")."""
    return (bool(re.fullmatch(r"/device:TPU:\d+", plane))
            and line in ("XLA Ops", "XLA Modules"))


def cpu_ops(plane: str, line: str) -> bool:
    """XLA's CPU client threads: the operations of a trace taken on the
    CPU, where the host is the device (used by the tests)."""
    return plane == "/host:CPU" and line.startswith("tf_XLA")


# Stats of a device event that may name what the operation computes, such
# as the kernel behind a custom call; kept, cut short, once per op name.
LABEL_STATS = ("long_name", "tf_op", "kernel_name", "hlo_category", "source")


@dataclasses.dataclass
class Events:
    devices: dict           # device key -> list of (start_ns, end_ns, name)
    spans: list             # (start_ns, end_ns, name) of bench.* host spans
    labels: dict = dataclasses.field(default_factory=dict)  # name -> text


def _label(event) -> str:
    return " ".join(str(v)[:160] for k, v in event.stats
                    if k in LABEL_STATS)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str, is_device=tpu_ops) -> Events:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: dict = {}
    spans = []
    labels: dict = {}
    for plane in pd.planes:
        lines = list(plane.lines)
        names = {line.name for line in lines}
        for line in lines:
            if line.name == "XLA Modules" and "XLA Ops" in names:
                continue
            if is_device(plane.name, line.name):
                out = devices.setdefault(plane.name, [])
                for e in line.events:
                    out.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name))
                    if e.name not in labels:
                        labels[e.name] = _label(e)
            elif plane.name == "/host:CPU":
                spans.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return Events(devices=devices, spans=sorted(spans), labels=labels)


def union(intervals, lo: float, hi: float) -> list:
    """Merged ``[start, end)`` intervals clipped to ``[lo, hi]``."""
    out: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float
    ops: dict
    gaps: dict
    n_devices: int
    jobs: int
    labels: dict = dataclasses.field(default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, pattern: str) -> float | None:
        """Device seconds of the operations whose name, or the label its
        stats give it, matches ``pattern`` (a regular expression); None
        where none does."""
        hit = [s for n, s in self.ops.items()
               if re.search(pattern, f"{n} {self.labels.get(n, '')}")]
        return sum(hit) if hit else None

    def breakdown(self, n: int = 10) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:n]
        gaps = sorted(self.gaps.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in gaps]}


def reduce(ev: Events) -> Reduction:
    if not ev.spans:
        raise ValueError("the trace holds no bench.* host spans")
    lo = min(s for s, _, _ in ev.spans)
    hi = max(e for _, e, _ in ev.spans)
    ops: dict = {}
    busy = []
    gaps: dict = {}
    starts = [s for s, _, _ in ev.spans]
    for events in ev.devices.values():
        inside = [(s, e, n) for s, e, n in events if e > lo and s < hi]
        if not inside:
            continue
        for s, e, n in inside:
            ops[n] = ops.get(n, 0.0) + (min(e, hi) - max(s, lo)) * 1e-9
        merged = union([(s, e) for s, e, _ in inside], lo, hi)
        busy.append(sum(e - s for s, e in merged))
        # idle gaps of this device, each split over the host spans
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            covered = 0.0
            first = max(bisect.bisect_right(starts, g0) - 1, 0)
            for s, e, n in ev.spans[first:]:
                if e <= g0:
                    continue
                if s >= g1:
                    break
                o = _overlap(g0, g1, s, e)
                gaps[n] = gaps.get(n, 0.0) + o * 1e-9
                covered += o
            gaps[OTHER] = gaps.get(OTHER, 0.0) + (g1 - g0 - covered) * 1e-9
    if not busy:
        raise ValueError("no device operation ran inside the traced window")
    k = len(busy)
    return Reduction(window_s=(hi - lo) * 1e-9,
                     busy_s=float(np.mean(busy)) * 1e-9,
                     ops={n: s / k for n, s in ops.items()},
                     gaps={n: s / k for n, s in gaps.items()},
                     n_devices=k,
                     jobs=sum(n == ENTRY for _, _, n in ev.spans),
                     labels=ev.labels)
