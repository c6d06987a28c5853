"""The program's own measurement: device scopes, host spans, a compile counter.

Everything the trainer records about itself goes through this module, so a
reader of a profiler trace finds one list of names here.

Device scopes (`jax.named_scope`) name the parts of a boosting round in the
compiled program's op metadata.  They add no device work: XLA carries them
only in each instruction's ``metadata={op_name=...}``, which a device trace
shows as the ``tf_op`` of every operation (``jit(boost_scan)/while/body/
closed_call/jit(grow_tree)/hist/jit(histogram_splits_level)/gather/...``).
Nested scopes read as a path (``hist`` then ``gather``).  The name under
which `bench/metrics/scopes.py` reports each one, in ms per round:

=============  =================================================  ==========================
scope          code                                               metric
=============  =================================================  ==========================
``grad``       loss gradients / Hessians, guards, RNG split,      ``round_grad_ms``
               sample weights, feature mask (`boosting`)
``sketch``     the sketch projection, count channel and stats     none yet
               guard (`boosting`)
``hist``       each level's histogram and split search, kernel    the three below
               and jnp modes (`tree`)
``gather``     in ``hist``: smaller-child choice, tile plan, row  ``hist_gather_ms``
               gathers of codes and stats, lane pad (`ops`)
``epilogue``   in ``hist``: bin slice, feature pad and params     ``hist_epilogue_ms``
               for the split kernel (`ops`)
``subtract``   in ``hist``: parent - built, sibling pick,         ``hist_subtract_ms``
               interleave (`ops`)
``route``      splits from the scan's flat result, heap writes,   ``round_route_ms``
               routing bits, partition advance (`tree`)
``leaf``       leaf sums, values, cover, skip-round scale and     ``round_leaf_ms``
               the score update (`tree`, `boosting`)
``eval``       the in-scan eval: tree applied to the eval set,    ``round_eval_ms``
               eval loss (`boosting`)
=============  =================================================  ==========================

The Pallas kernels sit inside these scopes but are timed apart
(``hist_kernel_ms``, ``split_kernel_ms``); each ``pallas_call`` carries
its own ``name=``.  The leaf-wise grower uses the same scope names where
it does the same work.

Host spans (`span`) are ``jax.profiler.TraceAnnotation`` events named
``sketchboost.<name>`` on the profiler's host clock, so every stretch in
which the chip idles during a fit lies under one of them.  With no
profiler running a span costs about a microsecond.  Names, with what
reads them (the benchmark's ``quantile_ms.train``; every span names the
idle gaps it covers):

=========================  ===============================================  ===================
span                       code                                             metric
=========================  ===============================================  ===================
``sketchboost.fit``        the whole of `SketchBoost.fit`, parent of the    idle-gap owner
                           rest
``sketchboost.validate``   input validation of the train and eval sets      idle-gap owner
``sketchboost.quantiles``  the host quantile fit (`quantize.fit_quantizer`) ``quantile_ms``
``sketchboost.bin``        binning of both sets, targets, base score,       idle-gap owner
                           initial scores
``sketchboost.segment``    each dispatch of a ``boost_scan`` segment        idle-gap owner
``sketchboost.sync``       the host's wait for a segment's eval losses      idle-gap owner
``sketchboost.replay``     history and early-stop replay, chunk slicing,    idle-gap owner
                           concatenation
``sketchboost.pack``       `forest.pack_forest`                             idle-gap owner
=========================  ===============================================  ===================

The histogram kernel's fold is a count made while a program is traced:
`HistFold` notes, for each shape of ``hist_tiles_pallas`` traced while it
is on, its grid steps (``m x n_tiles``, one tile's dot each), the node
blocks it writes to HBM (``m x n_nodes``), the bf16 parts per statistic
(3: the exact float32 split, 1: bfloat16 statistics) and the MXU operand
lanes of each step's one pass.  Steps over blocks is how many tile dots
fold into each node histogram inside the kernel.  Shapes are static, so it
adds no device work; nothing in the benchmark reads it yet.

`CompileCounter` counts the executables JAX compiles or loads from the
persistent cache (``window_programs``, ``setup_compile_s`` in
`bench/tools/scope_split.py`).
"""
from __future__ import annotations

from typing import Dict, List, NamedTuple, Tuple

import jax

SPAN_PREFIX = "sketchboost."

# device scopes
GRAD = "grad"
SKETCH = "sketch"
HIST = "hist"
GATHER = "gather"            # inside HIST
EPILOGUE = "epilogue"        # inside HIST
SUBTRACT = "subtract"        # inside HIST
ROUTE = "route"
LEAF = "leaf"
EVAL = "eval"
SCOPES = (GRAD, SKETCH, HIST, GATHER, EPILOGUE, SUBTRACT, ROUTE, LEAF, EVAL)

# host spans (without the prefix)
FIT = "fit"
VALIDATE = "validate"
QUANTILES = "quantiles"
BIN = "bin"
SEGMENT = "segment"
SYNC = "sync"
REPLAY = "replay"
PACK = "pack"
SPANS = (FIT, VALIDATE, QUANTILES, BIN, SEGMENT, SYNC, REPLAY, PACK)


class HistLevel(NamedTuple):
    """One traced shape of the histogram tiles kernel."""
    steps: int          # grid steps, m x n_tiles: one tile's MXU pass each
    blocks: int         # node blocks written, m x n_nodes
    parts: int          # bf16 parts per statistic: 3 exact, 1 rounded
    lanes: int          # MXU operand lanes per step (P)


class HistFold:
    """How many tile steps of the histogram kernel fold into each node
    block it writes, for every kernel shape traced while it is on.

    `record_hist_level`, called by ``hist_tiles_pallas`` as it is traced,
    puts ``(m, n_tiles, n_nodes) -> HistLevel(m x n_tiles grid steps,
    m x n_nodes blocks written, bf16 parts per statistic, operand lanes)``
    into ``levels`` of every `HistFold` whose ``with`` block is open.
    Shapes are static, so this adds no device work; a program JAX has
    already traced in the process records nothing again, so open it
    before the first fit.
    """

    def __init__(self):
        self.levels: Dict[Tuple[int, int, int], HistLevel] = {}

    def __enter__(self) -> "HistFold":
        _HIST_FOLDS.append(self)
        return self

    def __exit__(self, *exc) -> None:
        _HIST_FOLDS.remove(self)


_HIST_FOLDS: List[HistFold] = []


def record_hist_level(m: int, n_tiles: int, n_nodes: int, parts: int,
                      lanes: int) -> None:
    """Note one traced shape of the histogram tiles kernel in every open
    `HistFold`."""
    for fold in _HIST_FOLDS:
        fold.levels[(m, n_tiles, n_nodes)] = HistLevel(
            m * n_tiles, m * n_nodes, parts, lanes)


def span(name: str) -> jax.profiler.TraceAnnotation:
    """Host span ``sketchboost.<name>`` on the profiler's clock."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


class CompileCounter:
    """How many programs the process compiled or loaded, and how long that
    took, read from JAX's own monitoring events.

    An operator uses it to see what a fit costs before its first round: each
    executable JAX builds, whether the backend compiled it or loaded it from
    the persistent compile cache, counts once in ``programs`` with its time
    in ``compile_s`` (``cache_hits`` says how many of them were loads);
    tracing Python to jaxprs counts in ``traces`` / ``trace_s``.  A warm
    process that re-runs a fit it has run before adds nothing.

    Listeners are registered only between `start` and `stop`, so a process
    that never starts a counter pays nothing.  `snapshot` reads the totals
    at any point, for differences between two moments.
    """

    def __init__(self):
        self.programs = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.traces = 0
        self.trace_s = 0.0
        self.names: List[str] = []       # fun_name of each program, in order
        self._on = False

    def _duration(self, event: str, secs: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.programs += 1
            self.compile_s += secs
            self.names.append(str(kw.get("fun_name", "")))
        elif event == TRACE_EVENT:
            self.traces += 1
            self.trace_s += secs

    def _event(self, event: str, **kw) -> None:
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def start(self) -> "CompileCounter":
        if not self._on:
            jax.monitoring.register_event_duration_secs_listener(
                self._duration)
            jax.monitoring.register_event_listener(self._event)
            self._on = True
        return self

    def stop(self) -> "CompileCounter":
        if self._on:
            jax.monitoring.unregister_event_duration_listener(self._duration)
            jax.monitoring.unregister_event_listener(self._event)
            self._on = False
        return self

    def snapshot(self) -> Dict[str, float]:
        return {"programs": self.programs, "compile_s": self.compile_s,
                "cache_hits": self.cache_hits, "traces": self.traces,
                "trace_s": self.trace_s}

    def __enter__(self) -> "CompileCounter":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

