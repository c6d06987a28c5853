"""Device operations with the program's name scopes, and the host spans of
`SketchBoost.fit`, read from a JAX profiler trace.

`trace.load` reads a trace through `jax.profiler.ProfileData`, which gives
each device operation its own stats but not those of its event metadata,
where the trace keeps the operation's name-scope path (the ``tf_op`` stat,
``jit(boost_scan)/while/body/closed_call/jit(grow_tree)/hist/
jit(histogram_splits_level)/gather/...``).  `load` reads the ``.xplane.pb``
itself, with the standard library alone: a walk of the protobuf wire format
over the few messages and fields it needs,

    XSpace          planes=1
    XPlane          name=2, lines=3, event_metadata=4 (map), stat_metadata=5 (map)
    XLine           name=2, timestamp_ns=3, events=4
    XEvent          metadata_id=1, offset_ps=2, duration_ps=3
    XEventMetadata  id=1, name=2, stats=5
    XStat           metadata_id=1, str_value=5, ref_value=7
    XStatMetadata   id=1, name=2

and gives every operation of the ``XLA Ops`` line of each ``/device:TPU:<i>``
plane as a `ScopedOp`: its interval and name exactly as `trace.load` gives
them, its ``tf_op`` (``""`` where it has none, as a loop container has
none) and the program it ran in (the ``XLA Modules`` event holding it,
``jit_boost_scan(<fingerprint>)``).

The scopes themselves are named in `repro.runtime.tracing`.  The functions
below are arithmetic on such lists; times are own times (`trace.self_ns`),
clipped to the window:

* scope time: operations whose ``tf_op`` holds the scope's path as
  components in order (``("hist", "gather")``), not counting the last
  component, which names the operation itself (a ``gather`` primitive is
  not the ``gather`` scope), and not counting operations under a
  ``pallas_call`` component (the kernels, timed in `kernels`);
* program time: operations that ran in a program whose name starts with
  the given prefix (``jit_boost_scan``, ``jit_apply_quantizer``);
* span time: host events named ``sketchboost.<span>``;
* owner of an idle gap: the innermost ``sketchboost.*`` host span covering
  its midpoint (the innermost ``bench.*`` span where none does).
"""
from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, Iterable, List, NamedTuple, Sequence, Tuple

from metrics import kernels as K
from metrics import trace as TR

SCAN_PROGRAM = "jit_boost_scan"
BIN_PROGRAM = "jit_apply_quantizer"
SPAN_PREFIX = "sketchboost."
KERNEL_COMPONENT = "pallas_call"
MODULES_LINE = "XLA Modules"

# The per-round scopes whose times add up, with the kernels and the
# unscoped rest, to the scan program's device time (names of
# `repro.runtime.tracing`).
ROUND_SCOPES = {
    "round_grad_ms": ("grad",),
    "hist_gather_ms": ("hist", "gather"),
    "hist_epilogue_ms": ("hist", "epilogue"),
    "hist_subtract_ms": ("hist", "subtract"),
    "round_route_ms": ("route",),
    "round_leaf_ms": ("leaf",),
    "round_eval_ms": ("eval",),
}


class ScopedOp(NamedTuple):
    start_ns: float
    end_ns: float
    name: str          # ``%hist_tiles_pallas.72``, as `trace.op_name` gives
    tf_op: str         # name-scope path, "" where the trace holds none
    program: str       # the XLA module the operation ran in, "" if none


# -- protobuf wire format -----------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, lo: int, hi: int):
    """``(field number, value)`` of each field of the message in
    ``buf[lo:hi]``: an int for a varint, ``(start, end)`` for a
    length-delimited field; fixed-width fields are skipped."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield num, v
        elif wire == 2:
            n, i = _varint(buf, i)
            yield num, (i, i + n)
            i += n
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"unsupported wire type {wire} at {i}")


def _text(buf, span: Tuple[int, int]) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entries(buf, spans: Iterable[Tuple[int, int]]):
    """``(key, value span)`` of protobuf map entries (key=1, value=2)."""
    for lo, hi in spans:
        key, val = 0, (lo, lo)
        for num, v in _fields(buf, lo, hi):
            if num == 1:
                key = v
            elif num == 2:
                val = v
        yield key, val


def _plane(buf, lo: int, hi: int) -> Dict[int, list]:
    out: Dict[int, list] = {2: [], 3: [], 4: [], 5: []}
    for num, v in _fields(buf, lo, hi):
        if num in out:
            out[num].append(v)
    return out


def _stat_names(buf, spans) -> Dict[int, str]:
    """stat metadata id -> stat name."""
    out = {}
    for sid, (lo, hi) in _map_entries(buf, spans):
        for num, v in _fields(buf, lo, hi):
            if num == 2:
                out[sid] = _text(buf, v)
    return out


def _event_metadata(buf, spans, stat_names: Dict[int, str]
                    ) -> Dict[int, Tuple[str, str]]:
    """metadata id -> (operation name, tf_op)."""
    tf_op_ids = {k for k, n in stat_names.items() if n == "tf_op"}
    out = {}
    for mid, (lo, hi) in _map_entries(buf, spans):
        name, tf_op = "", ""
        for num, v in _fields(buf, lo, hi):
            if num == 2:
                name = _text(buf, v)
            elif num == 5:
                sid, val = None, ""
                for snum, sv in _fields(buf, *v):
                    if snum == 1:
                        sid = sv
                    elif snum == 5:
                        val = _text(buf, sv)
                    elif snum == 7:
                        val = stat_names.get(sv, "")
                if sid in tf_op_ids:
                    tf_op = val
        # the stat reads "<op_name>:<op_type>"; the type is empty here
        out[mid] = (name, tf_op.rsplit(":", 1)[0] if ":" in tf_op else tf_op)
    return out


def _line_events(buf, lo: int, hi: int):
    """``(name, [(start_ns, end_ns, metadata_id)])`` of one XLine, in whole
    nanoseconds as `jax.profiler.ProfileData` rounds them."""
    name, ts, events = "", 0, []
    for num, v in _fields(buf, lo, hi):
        if num == 2:
            name = _text(buf, v)
        elif num == 3:
            ts = v
        elif num == 4:
            events.append(v)
    out = []
    for elo, ehi in events:
        mid = off = dur = 0
        for num, v in _fields(buf, elo, ehi):
            if num == 1:
                mid = v
            elif num == 2:
                off = v
            elif num == 3:
                dur = v
        start = ts + off // 1000
        out.append((float(start), float(start + dur // 1000), mid))
    return name, out


def parse(data: bytes) -> Dict[int, List[ScopedOp]]:
    """Chip id -> its ``XLA Ops`` operations, sorted, from a serialized
    XSpace."""
    buf = memoryview(data)
    chips: Dict[int, List[ScopedOp]] = {}
    for num, span in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        p = _plane(buf, *span)
        name = _text(buf, p[2][0]) if p[2] else ""
        m = TR.DEVICE_PLANE.match(name)
        if not m:
            continue
        stat_names = _stat_names(buf, p[5])
        meta = _event_metadata(buf, p[4], stat_names)
        ops_line, modules = [], []
        for lo, hi in p[3]:
            lname, events = _line_events(buf, lo, hi)
            if lname == TR.OPS_LINE:
                ops_line = events
            elif lname == MODULES_LINE:
                modules = sorted((s, e, meta.get(mid, ("", ""))[0])
                                 for s, e, mid in events)
        starts = [s for s, _, _ in modules]
        ops = []
        for s, e, mid in ops_line:
            op, tf_op = meta.get(mid, ("", ""))
            j = bisect.bisect_right(starts, s) - 1
            prog = modules[j][2] if j >= 0 and s < modules[j][1] else ""
            ops.append(ScopedOp(s, e, TR.op_name(op), tf_op, prog))
        chips[int(m.group(1))] = sorted(ops)
    return chips


def load(trace_dir: str) -> Dict[int, List[ScopedOp]]:
    """`parse` of the newest ``*.xplane.pb`` under ``trace_dir``."""
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    with open(paths[-1], "rb") as f:
        return parse(f.read())


# -- reductions ----------------------------------------------------------------

def in_scope(tf_op: str, path: Sequence[str]) -> bool:
    """Whether ``tf_op`` runs under the scope ``path`` (see module doc)."""
    parts = tf_op.split("/")
    if KERNEL_COMPONENT in parts:
        return False
    i = 0
    for p in parts[:-1]:
        if i < len(path) and p == path[i]:
            i += 1
    return i == len(path)


def is_kernel(op: ScopedOp) -> bool:
    return (TR.matches(op.name, K.PALLAS)
            or KERNEL_COMPONENT in op.tf_op.split("/"))


def own_times(ops: Sequence[ScopedOp], lo: float, hi: float
              ) -> List[Tuple[ScopedOp, float]]:
    """Each operation with its own time in ns (`trace.self_ns`), clipped to
    ``[lo, hi]``."""
    clipped = TR.clip([(o.start_ns, o.end_ns, i) for i, o in enumerate(ops)],
                      lo, hi)
    return [(ops[i], e - s) for s, e, i in TR.self_ns(clipped)]


def scope_ns(owned, path: Sequence[str]) -> float:
    return sum(t for op, t in owned if in_scope(op.tf_op, path))


def program_ns(owned, prefix: str, kernels: bool = False) -> float:
    """Own time of the operations of the programs named ``prefix...``,
    without the kernels unless ``kernels``."""
    return sum(t for op, t in owned if op.program.startswith(prefix)
               and (kernels or not is_kernel(op)))


def span_ns(host: Iterable[TR.Interval], name: str, lo: float, hi: float
            ) -> float:
    """Summed duration of the host spans ``sketchboost.<name>`` in
    ``[lo, hi]``."""
    return sum(e - s for s, e, _ in
               TR.clip([x for x in host if x[2] == SPAN_PREFIX + name],
                       lo, hi))


def owner(host: Sequence[TR.Interval], t: float) -> str:
    """The innermost ``sketchboost.*`` span covering ``t``, else the
    innermost ``bench.*`` one."""
    for prefix in (SPAN_PREFIX, "bench."):
        cover = sorted((e - s, n) for s, e, n in host
                       if s <= t <= e and n.startswith(prefix))
        if cover:
            return cover[0][1]
    return "none"


def owned_gaps(ops: Iterable[TR.Interval], host: Sequence[TR.Interval],
               lo: float, hi: float, k: int = 10) -> List[Tuple[str, float]]:
    """The ``k`` longest idle gaps in seconds, each with its `owner`."""
    gs = sorted(TR.gaps(ops, lo, hi), key=lambda g: g[0] - g[1])[:k]
    return [(owner(host, (s + e) / 2), (e - s) * 1e-9) for s, e in gs]


def round_split(ops: Sequence[ScopedOp], lo: float, hi: float, rounds: int,
                fits: int) -> Dict[str, float]:
    """The per-layer split of a traced training window: each scope of
    `ROUND_SCOPES` and the scan program's unscoped rest in ms per round,
    the binning program in ms per fit, and the totals they add up to."""
    owned = own_times(ops, lo, hi)
    per_round = 1e-6 / rounds
    out = {k: scope_ns(owned, p) * per_round for k, p in ROUND_SCOPES.items()}
    scan_xla = program_ns(owned, SCAN_PROGRAM) * per_round
    out["round_unscoped_ms"] = scan_xla - sum(out.values())
    out["scan_xla_ms"] = scan_xla
    out["scan_kernels_ms"] = (program_ns(owned, SCAN_PROGRAM, kernels=True)
                              * per_round - scan_xla)
    out["bin_ms_per_fit"] = program_ns(owned, BIN_PROGRAM) * 1e-6 / fits
    out["other_programs_xla_ms"] = (
        sum(t for op, t in owned if not is_kernel(op)) * per_round
        - scan_xla - out["bin_ms_per_fit"] * fits / rounds)
    return out
