"""Spec loading, the device check, host spans and the result line."""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
from typing import Any, Dict, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class NoAccelerator(SystemExit):
    """Raised (exit code 3) when JAX finds no TPU or too few chips."""

    def __init__(self, msg: str):
        print(f"bench: {msg}", file=sys.stderr, flush=True)
        super().__init__(3)


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> Dict[str, Any]:
    """Everything one cell needs, found by names in ``BENCHMARK.json``:
    the configuration file, the traffic file, the limits of the check and
    the per-layer metrics whose ``workloads`` list the cell."""
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    conf_entry = configs[cell["config"]]
    bench = os.path.join(root, "bench")
    return {
        "name": name,
        "chips": int(cell["chips"]),
        "config": load_json(os.path.join(root, conf_entry["file"])),
        "traffic": load_json(os.path.join(bench, "traffic",
                                          cell["traffic"] + ".json")),
        "limits": load_json(os.path.join(bench, "limits", name + ".json")),
        "end_to_end": [m for m in spec["end_to_end"]
                       if name in m.get("workloads", [name])],
        "per_layer": [m for m in spec["per_layer"]
                      if name in m.get("workloads", [name])],
    }


def load_reader(metric: str, root: str = ROOT):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    path = os.path.join(root, "bench", "metrics", metric + ".py")
    mod_name = "bench_metric_" + metric.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def device_info(chips: int, require_tpu: bool = True) -> Dict[str, Any]:
    """Platform, kind and count as JAX reports them; refuses a run with
    no TPU or with fewer chips than the cell asks for."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:                    # no backend at all
        raise NoAccelerator(f"JAX found no devices: {e}")
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"bench: platform={info['platform']} device_kind={info['kind']} "
          f"count={info['count']}", file=sys.stderr, flush=True)
    if require_tpu:
        if info["platform"] != "tpu":
            raise NoAccelerator(f"no TPU: JAX found {info['platform']!r}")
        if info["count"] < chips:
            raise NoAccelerator(f"the cell needs {chips} chips, JAX sees "
                                f"{info['count']}")
    return info


def memory_peak_bytes(chips: int) -> Optional[int]:
    """Peak device memory on the fullest of the cell's chips: the peak of
    buffers in use plus the peak the runtime reserved for the programs'
    temporaries, which ``peak_bytes_in_use`` leaves out (a dionis fit
    reads 0.81 GB in use and 11.03 GB reserved on a v5e)."""
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"])
                         + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks) if peaks else None


def enable_caches() -> str:
    """The program's persistent compile cache (`repro.runtime.
    compile_cache`: ``JAX_COMPILATION_CACHE_DIR`` or the checkout's
    ``.jax_cache/``), with every program cached however fast it compiled,
    so that a second run of a cell finds all of them."""
    import jax
    from repro.runtime.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


@contextlib.contextmanager
def span(name: str):
    """The benchmark's own host span around a call into the program,
    written into the profiler's trace (when one runs) as a
    ``TraceAnnotation`` named ``bench.<name>``, which the trace reduction
    uses to find the window and to label idle gaps."""
    import jax
    with jax.profiler.TraceAnnotation("bench." + name):
        yield


def start_trace() -> str:
    """Start the profiler (no Python tracer: it would slow the host path
    being measured) into a fresh directory under ``TMPDIR``."""
    import jax
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    return trace_dir


def stop_trace(trace_dir: str):
    """Stop the profiler and reduce its trace (`metrics.trace.reduce`);
    the trace itself is deleted."""
    import jax
    from metrics import trace as TR
    jax.profiler.stop_trace()
    try:
        return TR.reduce(TR.load(trace_dir))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def fill_metrics(cell: Dict[str, Any], result: Dict[str, Any], red,
                 run, values: Dict[str, float]) -> None:
    """The cell's per-layer metrics (traced run, ``red`` from
    `stop_trace`; each read by its ``bench/metrics/<name>.py``, which
    returns None when it finds nothing to read) or its end-to-end metrics
    (``values``) into ``result``."""
    if red is None and run is None:
        for m in cell["end_to_end"]:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
        return
    for m in cell["per_layer"]:
        v = load_reader(m["name"])(run)
        if v is not None:
            result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    if red is not None:
        result["device"].update(busy_s=red.busy_s, window_s=red.window_s)
        result["breakdown"] = red.breakdown()


def seed_parts(seed: int):
    """``--seed`` as the program's 31-bit seed and a JAX key (all bits)."""
    import jax
    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                             (seed >> 32) & 0x7FFFFFFF)
    return seed % (2 ** 31), key


def emit(result: Dict[str, Any], checks: Dict[str, Dict[str, float]]) -> None:
    """Each compared number beside its limit as the last lines of standard
    error, then the result as the last line of standard output with the
    same numbers under ``checks``, its last key."""
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    out = dict(result)
    out["checks"] = checks
    print(json.dumps(out), flush=True)


def check_entry(value: float, limit: float) -> Dict[str, Any]:
    return {"value": float(value), "limit": float(limit),
            "ok": bool(value <= limit)}
