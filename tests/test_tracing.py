"""The program's own measurement (`repro.runtime.tracing`).

* every device scope reaches the compiled scan program's op metadata, as a
  path, in the kernel (interpreted) and jnp modes;
* a fit under the profiler records every host span inside its
  ``sketchboost.fit`` span;
* `CompileCounter` counts a program once, for a new shape only, and
  leaves no listener behind.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import boosting as B
from repro.data.pipeline import make_tabular
from repro.runtime import tracing as TR

N, NV, M, D = 256, 64, 4, 3

KERNEL_ONLY = {TR.GATHER, TR.EPILOGUE, TR.SUBTRACT}


def scan_program(mode: str, **cfg_kw):
    cfg = B.GBDTConfig(n_trees=2, depth=2, n_bins=16, sketch_k=2,
                       use_kernel=mode, **cfg_kw).resolve(D).strip_io()
    S = jax.ShapeDtypeStruct
    return B.boost_scan.lower(
        S((N, D), jnp.float32), S((N, M), jnp.uint8), S((N,), jnp.int32),
        S((NV, D), jnp.float32), S((NV, M), jnp.uint8), S((NV,), jnp.int32),
        jax.random.key(0), cfg=cfg, n_steps=2, has_eval=True)


def has_path(op_names, path) -> bool:
    """Some op name holds ``path`` as components, in order, before its
    last component (the operation itself)."""
    for name in op_names:
        parts, i = name.split("/")[:-1], 0
        for p in parts:
            if i < len(path) and p == path[i]:
                i += 1
        if i == len(path):
            return True
    return False


@pytest.mark.parametrize("mode", ["interpret", "jnp"])
def test_scan_program_metadata_names_every_scope(mode):
    lowered = scan_program(mode)
    text = lowered.as_text(debug_info=True)
    locs = set(re.findall(r'loc\("([^"]*)"', text))
    want = [s for s in TR.SCOPES if mode != "jnp" or s not in KERNEL_ONLY]
    for scope in want:
        assert any(scope in loc.split("/")[:-1] for loc in locs), scope
    # After inlining, nested scopes read as one path from the scan down.
    op_names = set(re.findall(r'op_name="([^"]*)"',
                              lowered.compile().as_text()))
    assert all(n.startswith("jit(boost_scan)") for n in op_names
               if TR.HIST in n.split("/"))
    paths = [(s,) for s in want if s not in KERNEL_ONLY]
    if mode != "jnp":
        paths += [(TR.HIST, s) for s in KERNEL_ONLY]
    for path in paths:
        assert has_path(op_names, path), path


def test_leafwise_grower_uses_the_same_scopes():
    op_names = set(re.findall(
        r'op_name="([^"]*)"',
        scan_program("jnp", growth="leafwise",
                     max_leaves=3).compile().as_text()))
    for path in [(TR.HIST,), (TR.HIST, TR.GATHER), (TR.HIST, TR.SUBTRACT),
                 (TR.ROUTE,), (TR.LEAF,), (TR.GRAD,), (TR.EVAL,)]:
        assert has_path(op_names, path), path


def test_fit_records_every_span_inside_the_fit_span(tmp_path):
    from jax.profiler import ProfileData
    X, y = make_tabular("multiclass", 300, 5, 3, seed=4)
    Xv, yv = make_tabular("multiclass", 80, 5, 3, seed=5)
    cfg = B.GBDTConfig(n_trees=3, depth=2, n_bins=16, scan_chunk=2)
    B.SketchBoost(cfg).fit(X, y, eval_set=(Xv, yv))       # compile first
    with jax.profiler.trace(str(tmp_path)):
        B.SketchBoost(cfg).fit(X, y, eval_set=(Xv, yv))
    path = next(tmp_path.rglob("*.xplane.pb"))
    spans = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
             for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for e in line.events
             if e.name.startswith(TR.SPAN_PREFIX)]
    fits = [(s, e) for s, e, n in spans if n == TR.SPAN_PREFIX + TR.FIT]
    assert len(fits) == 1
    lo, hi = fits[0]
    names = {n[len(TR.SPAN_PREFIX):] for s, e, n in spans
             if lo <= s and e <= hi}
    assert names == set(TR.SPANS)
    assert all(lo <= s and e <= hi for s, e, _ in spans)
    # two segments (chunks of 2 rounds), each dispatched and synced once
    n_seg = sum(1 for _, _, n in spans if n == TR.SPAN_PREFIX + TR.SEGMENT)
    n_sync = sum(1 for _, _, n in spans if n == TR.SPAN_PREFIX + TR.SYNC)
    assert n_seg == n_sync == 2


def test_compile_counter_counts_a_new_shape_once():
    f = jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)
    a, b = jnp.ones((3, 7)), jnp.ones((5, 7))  # built before counting
    with TR.CompileCounter() as counter:
        f(a)
        first = counter.snapshot()
        f(a)                                   # same shape: nothing built
        again = counter.snapshot()
        f(b)                                   # new shape: one program
        third = counter.snapshot()
    assert first["programs"] == 1 and first["compile_s"] > 0
    assert first["traces"] >= 1
    assert again == first
    assert third["programs"] == 2
    assert all("<lambda>" in n for n in counter.names)


def test_compile_counter_leaves_no_listener():
    from jax._src import monitoring
    before = (monitoring.get_event_duration_listeners(),
              monitoring.get_event_listeners())
    counter = TR.CompileCounter().start()
    counter.start()                            # a second start adds nothing
    assert len(monitoring.get_event_duration_listeners()) \
        == len(before[0]) + 1
    counter.stop()
    counter.stop()
    assert (monitoring.get_event_duration_listeners(),
            monitoring.get_event_listeners()) == before
    jax.jit(lambda x: x * np.float32(7.0))(jnp.ones((2, 9)))
    assert counter.snapshot()["programs"] == 0
