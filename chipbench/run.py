#!/usr/bin/env python3
"""Chip benchmark of the SUMMA engine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the TPU chips of this machine and
prints, as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``, the numbers compared beside their
limits.  Without a TPU, or with fewer chips than the cell asks for, it
exits 2 and prints no result.

Everything is found by name: the cell names a configuration
(``configs[].file``) and a traffic mix (``chipbench/traffic/<mix>.json``);
the configuration names its entry, the engine call the cell times
(``chipbench/entries/<entry>.py``, ``"dense"`` where it names none); each
per-layer metric is read by ``chipbench/metrics/<metric>.py``.  A new
cell, a new entry among them, is new files and entries, with no edit
here.  What the harness keeps is the loop: a closed loop with one client,
normal values (``chipbench/generate.py``).  An open loop or several
clients need an edit to this file or to the generator.

The loop is closed, with one client: the caller issues the entry's call
(``DistributedMatmul.__call__`` or ``NonuniformMatmul.__call__``,
``core/api.py``) on fixed operands, waits for its result, and issues the
next, the way an iterative solver does.  Set-up (imports, operands made on
the device from ``--seed``, compiling or loading from the compile cache,
two warm-up calls) runs before the window; ``setup_s`` is process start to
the first timed call.  The window runs for ``--seconds`` and ends with the
call in flight.  After it, the device's memory peak is read, the engine is
dropped, and the last call's C is compared with the plain reference
(``chipbench/reference.py``) by the entry.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "chipbench")
#: JAX's persistent compilation cache, at a fixed path inside the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
#: where a traced run's profile goes; emptied before each traced run
TRACE_DIR = os.path.join(ROOT, ".chipbench", "trace")
WARMUP_CALLS = 2
GIB = 2.0**30


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _paths() -> None:
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)


def load_cell(name: str, root: str = ROOT):
    """``(benchmark, cell, config, traffic)`` of the cell called ``name``."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = _json(os.path.join(root, entry["file"]))
    traffic = _json(os.path.join(root, "chipbench", "traffic", cell["traffic"] + ".json"))
    return bench, cell, config, traffic


def _module(kind: str, name: str, root: str):
    """``chipbench/<kind>/<name>.py``, loaded by its path."""
    path = os.path.join(root, "chipbench", kind, name + ".py")
    if not os.path.isfile(path):
        raise ValueError(f"chipbench: no {kind} module {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"chipbench.{kind}.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metric_reader(name: str, root: str = ROOT):
    """The ``read`` function of ``chipbench/metrics/<name>.py``."""
    return _module("metrics", name, root).read


def entry_module(name: str, root: str = ROOT):
    """The module ``chipbench/entries/<name>.py``: ``check_traffic``,
    ``engine`` and ``build`` of one engine call."""
    return _module("entries", name, root)


def entry_of(config: dict):
    """The entry module a configuration names; ``dense`` where it names none."""
    return entry_module(config.get("entry", "dense"))


def require_devices(chips: int):
    """The first ``chips`` TPU devices; exit 2 without them."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"no TPU: JAX's first device is {devs[0].platform!r}; this benchmark runs only on a TPU")
        raise SystemExit(2)
    if len(devs) < chips:
        log(f"the cell needs {chips} TPU chips, JAX finds {len(devs)}")
        raise SystemExit(2)
    return devs[:chips]


def enable_compile_cache() -> str:
    """Keep every compiled program in the persistent cache:
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``.

    The same directory as ``repro.launch.compile_cache``, kept here so that
    an edit to the program cannot move the benchmark's cache; it also
    caches programs that compile in under a second."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CompileWatch:
    """Sums the seconds of JAX's trace, lowering, compile and compile-cache
    events, by event."""

    def __init__(self):
        import jax.monitoring

        self.events: dict[str, float] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event.startswith(("/jax/core/compile/", "/jax/compilation_cache/")):
            self.events[event] = self.events.get(event, 0.0) + secs

    def close(self) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on)


@dataclasses.dataclass
class RunContext:
    """What a per-layer metric reader gets."""

    cell: dict
    config: dict
    traffic: dict
    chips: int
    device_ids: list
    calls: int
    window_s: float
    host_call_s: list
    peak: dict | None  # chipbench/peaks.json entry of the device kind
    trace: object = None  # xplane.Trace of the window, or None
    # the program's counters, read after the window (Product.counters)
    counters: dict = dataclasses.field(default_factory=dict)
    # the window's (start, end) on the host's clock in the trace; the
    # device's clock in the same trace may be off from it by a millisecond
    window: tuple | None = None

    def device_ops(self) -> list:
        """Per device of the cell: its operations.  The trace starts after
        the warm-up calls have finished and stops after the window, so
        every operation in it belongs to a call of the window."""
        names = {f"/device:TPU:{i}" for i in self.device_ids}
        return [
            ops for name, ops in zip(self.trace.device_names, self.trace.devices)
            if name in names
        ]


def make_mesh(config: dict, devices):
    import numpy as np
    from jax.sharding import AxisType, Mesh

    shape = tuple(config["mesh"])
    if shape[0] * shape[1] != len(devices):
        raise ValueError(f"mesh {shape} needs {shape[0] * shape[1]} devices, given {len(devices)}")
    return Mesh(np.asarray(devices).reshape(shape), ("data", "model"), axis_types=(AxisType.Auto,) * 2)


def _peak_bytes(devices) -> int:
    """Peak device memory of the fullest chip: the allocator's peak of
    live buffers plus its peak reservation for programs' temporaries,
    which the TPU runtime keeps apart and ``peak_bytes_in_use`` leaves out."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use", 0) + stats.get("peak_bytes_reserved", 0))
    return int(max(peaks))


def _strip_suffix(name: str) -> str:
    return re.sub(r"\.\d+$", "", name)


def breakdown(run: RunContext) -> dict:
    """Top device operations by time, and the longest idle gaps labelled
    by the benchmark's host span open in them."""
    from chipbench import xplane

    per_op: dict[str, float] = {}
    gaps = []
    lo, hi = run.window
    spans = [e for e in run.trace.host if e.name in ("chipbench.call", "chipbench.wait")]
    devs = run.device_ops()
    for ops in devs:
        for e in ops:
            key = _strip_suffix(e.op)
            per_op[key] = per_op.get(key, 0.0) + e.duration / len(devs)
        busy = xplane.union([(e.start, e.end) for e in ops])
        span = (min([lo] + [s for s, _ in busy]), max([hi] + [e for _, e in busy]))
        for s, e in xplane.subtract([span], busy):
            mid = 0.5 * (s + e)
            label = next((sp.name for sp in spans if sp.start <= mid < sp.end), "between calls")
            gaps.append((label, e - s))
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps, key=lambda g: -g[1])[:10]
    return {
        "device_ops": [[k, v] for k, v in top_ops],
        "idle_gaps": [[k, v] for k, v in top_gaps],
    }


def run_cell(
    cell: dict,
    config: dict,
    traffic: dict,
    *,
    seed: int,
    seconds: float,
    devices,
    per_layer: list | None = None,
    end_to_end: list | None = None,
    peak: dict | None = None,
    trace_dir: str | None = None,
) -> dict:
    """One run of one cell; returns the result object.

    ``per_layer`` (with ``trace_dir``) or ``end_to_end`` are the metric
    entries of ``BENCHMARK.json`` this run reports.
    """
    _paths()
    import jax

    watch = CompileWatch()
    log(f"{config['name']}: {config['guarantee']}")
    mesh = make_mesh(config, devices)
    product = entry_of(config).build(config, traffic, seed, mesh)
    jax.block_until_ready((product.a, product.b))
    for _ in range(WARMUP_CALLS):
        jax.block_until_ready(product.call())
    stats0 = product.cache_stats()
    setup_peak = _peak_bytes(devices)
    log(f"device 0 memory stats after set-up: {json.dumps(devices[0].memory_stats())}")
    log(f"set-up: {time.perf_counter() - T_START:.3f} s, device memory peak {setup_peak / GIB:.3f} GiB")
    log(f"set-up compile events (s): {json.dumps(watch.events)}")

    tracing = trace_dir is not None
    if tracing:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # host spans only, no Python calls
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    host_call_s = []
    c = None
    watch.events.clear()
    setup_s = time.perf_counter() - T_START
    t0 = time.perf_counter()
    annotate = jax.profiler.TraceAnnotation
    with annotate("chipbench.window"):
        while True:
            c = None
            t_call = time.perf_counter()
            with annotate("chipbench.call"):
                c = product.call()
            host_call_s.append(time.perf_counter() - t_call)
            with annotate("chipbench.wait"):
                c.block_until_ready()
            if time.perf_counter() - t0 >= seconds:
                break
    window_s = time.perf_counter() - t0
    watch.close()
    if tracing:
        jax.profiler.stop_trace()
    calls = len(host_call_s)
    stats1 = product.cache_stats()
    for key, after in (("plan", stats1["plan"]), ("executable", stats1["executable"])):
        before = stats0[key]
        if after.get("misses") != before.get("misses") or after.get("retraces", 0) != before.get("retraces", 0):
            log(f"WINDOW: {key} cache changed inside the window: {before} -> {after}")
    if watch.events:
        log(f"WINDOW: trace/compile events inside the window (s): {json.dumps(watch.events)}")
    log(f"window: {calls} calls in {window_s:.6f} s; cache stats {json.dumps(stats1)}")
    peak_bytes = _peak_bytes(devices)
    counters = product.counters()

    # the program's state goes before the reference runs
    product.drop()
    values = product.compare(c)
    checks = {k: {"value": v, "limit": float(config["limits"][k])} for k, v in values.items()}
    correct = all(ch["value"] < ch["limit"] for ch in checks.values())
    del product, c

    d0 = devices[0]
    device = {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(jax.devices()), "memory_peak_bytes": peak_bytes,
    }
    metrics = {}
    result = {"correct": bool(correct), "attempted": calls, "failed": 0 if correct else 1}
    if tracing:
        from chipbench import xplane

        trace = xplane.load(trace_dir)
        win = trace.spans("chipbench.window")
        if not win:
            raise RuntimeError("the trace holds no chipbench.window span")
        run = RunContext(
            cell=cell, config=config, traffic=traffic, chips=len(devices), device_ids=[d.id for d in devices], calls=calls,
            window_s=window_s, host_call_s=host_call_s, peak=peak,
            trace=trace, window=(win[0].start, win[0].end), counters=counters,
        )
        for m in per_layer or []:
            if "workloads" in m and cell["name"] not in m["workloads"]:
                continue
            value = metric_reader(m["name"])(run)
            if value is None:
                log(f"per-layer {m['name']}: nothing to read")
                continue
            if isinstance(value, tuple):
                value, note = value
                log(f"per-layer {m['name']}: {value} ({note})")
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        busy = [xplane.total([(e.start, e.end) for e in ops]) for ops in run.device_ops()]
        device["busy_s"] = statistics.fmean(busy) if busy else 0.0
        device["window_s"] = window_s
        result["breakdown"] = breakdown(run)
    else:
        measured = {"call_ms": 1e3 * window_s / calls, "peak_hbm_gib": peak_bytes / GIB, "setup_s": setup_s}
        for m in end_to_end or []:
            if "workloads" in m and cell["name"] not in m["workloads"]:
                continue
            metrics[m["name"]] = {"value": measured[m["name"]], "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    _paths()
    import repro.core  # noqa: F401  the system under test, from the checkout's src/

    bench, cell, config, traffic = load_cell(args.workload)
    devices = require_devices(int(cell["chips"]))
    log(f"compile cache {enable_compile_cache()}")
    peaks = _json(os.path.join(HERE, "peaks.json"))
    kind = devices[0].device_kind
    if kind not in peaks:
        log(f"device kind {kind!r} is not in chipbench/peaks.json")
        return 2
    with contextlib.redirect_stdout(sys.stderr):
        result = run_cell(
            cell, config, traffic, seed=args.seed, seconds=args.seconds,
            devices=devices, per_layer=bench["per_layer"] if args.trace else None,
            end_to_end=None if args.trace else bench["end_to_end"],
            peak=peaks[kind], trace_dir=TRACE_DIR if args.trace else None,
        )
    for name, check in result["checks"].items():
        log(f"check {name}: {check['value']!r} limit {check['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
