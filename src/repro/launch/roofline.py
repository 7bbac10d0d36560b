"""Roofline-term extraction from compiled dry-run artifacts.

Three terms per (arch × shape × mesh), from the per-chip peaks of the
target device kind (``PEAKS``; TPU v5e numbers shown):

    T_compute    = HLO_FLOPs / (chips · 197e12)          [bf16 peak]
    T_memory     = HLO_bytes / (chips · 819e9)           [HBM BW]
    T_collective = link_bytes / (chips · 50e9)           [ICI per-link BW]

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()``. Collective
bytes are parsed out of the post-SPMD HLO text: for every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute we resolve the
result (and, via a symbol table, operand) shapes and convert to *per-chip
link traffic* with ring-algorithm factors:

    all-reduce       2 · bytes · (n−1)/n      (reduce-scatter + all-gather)
    all-gather       bytes · (n−1)/n          (bytes = full result)
    reduce-scatter   bytes · (n−1)/n          (bytes = full operand)
    all-to-all       bytes · (n−1)/n
    collective-permute  bytes

Since cost_analysis on the CPU backend reflects XLA:CPU fusion choices, an
*analytic* FLOP model per cell (from the config) is reported alongside —
MODEL_FLOPS = 6·N·D for training, 2·N·D for inference (N = active params).
"""
from __future__ import annotations

import dataclasses
import re


@dataclasses.dataclass(frozen=True)
class Peaks:
    """Published per-chip peaks of one accelerator kind."""

    flops: float                 # bf16 FLOP/s
    hbm_bw: float                # HBM bytes/s
    link_bw: float               # ICI bytes/s per link
    source: str


# Keyed by ``jax.Device.device_kind``.
PEAKS = {
    "TPU v5 lite": Peaks(
        flops=197e12, hbm_bw=819e9, link_bw=50e9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               '819 GB/s HBM, 1,600 Gbit/s ICI per chip over 4 links'),
}


def peaks(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; a kind without published peaks is an
    error, never a silent default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (known: {sorted(PEAKS)})") from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2,
    "f32": 4, "f64": 8, "c64": 8, "c128": 16, "token": 0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(?[^=]*?\)?)\s+"
                     r"([\w\-]+)\(")
_GROUPS_RE = re.compile(r"replica_groups=\{([^}]*)\}")
_GROUPS_SIZE_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def shape_bytes(type_str: str) -> int:
    """Total bytes of an HLO type string (handles tuples)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_SIZE_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_RE.search(line)
    if m and m.group(1).strip():
        first = m.group(1).split("}")[0].strip("{ ")
        n = len([t for t in first.split(",") if t.strip() != ""])
        if n > 0:
            return n
    return default


def collective_bytes(hlo_text: str, n_devices: int) -> dict:
    """Per-chip link-traffic bytes by collective kind (ring model)."""
    out = {k: 0.0 for k in COLLECTIVES}
    counts = {k: 0 for k in COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = _DEF_RE.match(stripped)
        if not m:
            continue
        op = m.group(3)
        kind = None
        for k in COLLECTIVES:
            if op == k or op.startswith(k + "-"):   # e.g. all-reduce-start
                kind = k
                break
        if kind is None or op.endswith("-done"):
            continue
        n = _group_size(stripped, n_devices)
        if n <= 1:
            continue
        result_bytes = shape_bytes(m.group(2))
        ring = (n - 1) / n
        if kind == "all-reduce":
            traffic = 2.0 * result_bytes * ring
        elif kind == "all-gather":
            traffic = result_bytes * ring          # result = gathered size
        elif kind == "reduce-scatter":
            traffic = result_bytes * (n - 1)       # operand = result × n
        elif kind == "all-to-all":
            traffic = result_bytes * ring
        else:                                      # collective-permute
            traffic = result_bytes
        out[kind] += traffic
        counts[kind] += 1
    out["total"] = sum(out[k] for k in COLLECTIVES)
    out["counts"] = counts
    return out


@dataclasses.dataclass
class Roofline:
    flops: float                 # per-chip HLO flops
    hbm_bytes: float             # per-chip HLO bytes accessed
    link_bytes: float            # per-chip collective link traffic
    chips: int
    model_flops: float           # analytic global model flops
    peaks: Peaks

    @property
    def t_compute(self) -> float:
        return self.flops / self.peaks.flops

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / self.peaks.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.link_bytes / self.peaks.link_bw

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def bound_time(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def roofline_fraction(self) -> float:
        """Useful-compute fraction of the bound: (MODEL_FLOPS/chips/peak) /
        max-term — the score-carrying number (1.0 = perfect)."""
        ideal = self.model_flops / self.chips / self.peaks.flops
        return ideal / max(self.bound_time, 1e-30)

    @property
    def flops_utilization(self) -> float:
        """MODEL_FLOPS / HLO_FLOPS — remat/redundancy waste detector."""
        return self.model_flops / max(self.flops * self.chips, 1e-30)


def from_compiled(compiled, n_devices: int, model_flops: float,
                  device_kind: str,
                  hlo_text: str | None = None) -> tuple[Roofline, dict]:
    """Terms via the loop-aware HLO analyzer (hlo_analysis.py). The SPMD
    module is already per-device, so no /n_devices normalization is applied
    to flops/bytes; only model_flops (global) is divided where needed."""
    from . import hlo_analysis
    text = hlo_text if hlo_text is not None else compiled.as_text()
    cost = hlo_analysis.analyze(text, n_devices)
    coll = dict(cost.coll)
    coll["total"] = cost.link
    # raw XLA numbers as a cross-check column (loops counted once there)
    try:
        xla = compiled.cost_analysis()
        if isinstance(xla, (list, tuple)):
            xla = xla[0]
        coll["xla_flops_raw"] = float(xla.get("flops", 0.0))
        coll["xla_bytes_raw"] = float(xla.get("bytes accessed", 0.0))
    except Exception:                                     # noqa: BLE001
        pass
    rl = Roofline(flops=cost.flops, hbm_bytes=cost.hbm,
                  link_bytes=cost.link, chips=n_devices,
                  model_flops=model_flops, peaks=peaks(device_kind))
    return rl, coll


def analytic_model_flops(cfg, shape_kind: str, seq: int, batch: int) -> float:
    tokens = batch * (seq if shape_kind in ("train", "prefill") else 1)
    n = cfg.n_active_params()
    per_tok = 6 * n if shape_kind == "train" else 2 * n
    return float(per_tok) * tokens
