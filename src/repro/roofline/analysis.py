"""Roofline analysis from compiled dry-run artifacts (DESIGN.md §6).

This container is CPU-only; TPU v5e is the *target*.  The three roofline terms
are therefore derived structurally from the AOT-compiled artifact:

  compute term    = HLO_FLOPs        / (chips * PEAK_FLOPS)
  memory term     = HLO_bytes        / (chips * HBM_BW)
  collective term = collective_bytes / (chips * ICI_BW)

`cost_analysis()` supplies FLOPs / bytes.  Collective bytes are NOT in
cost_analysis: we parse the post-SPMD optimized HLO (`compiled.as_text()`) and
sum operand sizes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute.

`lax.scan` bodies are counted ONCE by cost_analysis (verified in-container), so
full-depth numbers from a scanned graph undercount.  The dry-run therefore
probes each cell at two reduced *unrolled* depths and extrapolates linearly to
the full depth (`extrapolate`); ops outside the per-layer body (embedding,
logits, loss, optimizer) are captured by the intercept.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Tuple

# --- TPU v5e hardware constants (per chip) ---------------------------------
PEAK_FLOPS = 197e12          # bf16 FLOP/s
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s per link (~aggregate per-chip budget used
                             # for the collective term, per the assignment)

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")


def shape_bytes(shape_str: str) -> int:
    """Bytes of one HLO shape string, e.g. 'bf16[128,4096]{1,0}'.
    Tuple shapes: sum of components."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


# An HLO instruction line: `  %name = <shape> opcode(...operands...)`.
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*((?:\([^=]*?\)|[a-z0-9]+\[[^\]]*\](?:\{[^}]*\})?))\s+"
    r"([\w\-]+)(?:\.\d+)?\(", re.M)


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_op: Dict[str, int]
    count_by_op: Dict[str, int]

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_op.values())

    @property
    def total_count(self) -> int:
        return sum(self.count_by_op.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum operand bytes of every collective op in (optimized) HLO text.

    Works on both `lowered.as_text()` (StableHLO has no collectives pre-SPMD —
    returns 0) and `compiled.as_text()` (post-partitioning HLO — the real
    schedule).  Operand sizes are resolved through a name->shape map built from
    the whole module, falling back to the result shape when an operand is not
    found (all-reduce: result size == operand size).
    """
    name_shape: Dict[str, str] = {}
    for m in _INSTR_RE.finditer(hlo_text):
        name_shape[m.group(1)] = m.group(2)
    # Also catch parameters: `%param.1 = f32[...]{...} parameter(0)` handled
    # above; constants etc. too.

    bytes_by_op: Dict[str, int] = {}
    count_by_op: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.match(
            r"^(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*((?:\([^=]*?\)|[a-z0-9]+\[[^\]]*\](?:\{[^}]*\})?))\s+([\w\-]+?)(?:\.\d+)?\(([^)]*)\)",
            stripped)
        if not m:
            continue
        opcode = m.group(3)
        base = None
        for c in COLLECTIVE_OPS:
            if opcode == c or opcode.startswith(c + "-start"):
                base = c
                break
        if base is None:
            continue
        if opcode.endswith("-done"):
            continue                       # avoid double counting async pairs
        operands = [o.strip().lstrip("%") for o in m.group(4).split(",")
                    if o.strip()]
        b = 0
        for o in operands:
            o = o.split(" ")[-1].lstrip("%")       # 'f32[..] %x' or '%x'
            if o in name_shape:
                b += shape_bytes(name_shape[o])
        if b == 0:                                  # fallback: result shape
            b = shape_bytes(m.group(2))
        bytes_by_op[base] = bytes_by_op.get(base, 0) + b
        count_by_op[base] = count_by_op.get(base, 0) + 1
    return CollectiveStats(bytes_by_op, count_by_op)


# ---------------------------------------------------------------------------
# Terms
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RooflineTerms:
    flops: float                 # total HLO FLOPs for the step (all chips)
    hbm_bytes: float             # total HLO bytes accessed (all chips)
    collective_bytes: float      # total collective operand bytes (all chips)
    chips: int
    model_flops: float = 0.0     # 6*N*D analytic useful FLOPs

    @property
    def t_compute(self) -> float:
        return self.flops / (self.chips * PEAK_FLOPS)

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / (self.chips * HBM_BW)

    @property
    def t_collective(self) -> float:
        return self.collective_bytes / (self.chips * ICI_BW)

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        """Roofline lower bound on step time: max of the three terms (perfect
        overlap assumption)."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_fraction(self) -> float:
        return self.model_flops / self.flops if self.flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of peak at the roofline bound: useful FLOPs per second at
        t_bound over peak FLOPs (the MFU the roofline permits)."""
        if self.t_bound == 0:
            return 0.0
        return (self.model_flops / self.t_bound) / (self.chips * PEAK_FLOPS)

    def to_dict(self) -> Dict:
        return {
            "flops": self.flops, "hbm_bytes": self.hbm_bytes,
            "collective_bytes": self.collective_bytes, "chips": self.chips,
            "model_flops": self.model_flops,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck, "t_bound_s": self.t_bound,
            "useful_fraction": self.useful_fraction,
            "roofline_fraction": self.roofline_fraction,
        }


def extrapolate(v1: float, v2: float, l1: int, l2: int, l_full: int) -> float:
    """Two-point linear depth extrapolation: per-layer slope + intercept."""
    if l2 == l1:
        return v2
    slope = (v2 - v1) / (l2 - l1)
    intercept = v1 - slope * l1
    return max(intercept + slope * l_full, 0.0)


def cost_flops(cost: Dict) -> float:
    return float(cost.get("flops", 0.0))


def cost_bytes(cost: Dict) -> float:
    """Total bytes accessed from a cost_analysis dict ('bytes accessed')."""
    return float(cost.get("bytes accessed", 0.0))


def model_flops_train(n_params: int, tokens: int) -> float:
    """6*N*D: fwd 2ND + bwd 4ND."""
    return 6.0 * n_params * tokens


def model_flops_decode(n_params: int, tokens: int) -> float:
    """Decode forward only: 2*N per token."""
    return 2.0 * n_params * tokens


def format_table(rows: List[Dict], keys: List[str]) -> str:
    widths = {k: max(len(k), *(len(str(r.get(k, ""))) for r in rows))
              for k in keys}
    line = " | ".join(k.ljust(widths[k]) for k in keys)
    sep = "-+-".join("-" * widths[k] for k in keys)
    body = "\n".join(" | ".join(str(r.get(k, "")).ljust(widths[k])
                                for k in keys) for r in rows)
    return f"{line}\n{sep}\n{body}"
