"""The clock offset between host and chips, and the readers of the engine's
own spans and set-up counters.

``data/*_1x1.xplane.pb`` and ``data/*_2x2.xplane.pb`` are the traced
windows described in ``test_xplane.py``, recorded before the engine had
spans of its own: the offset is bracketed from the runtime's events and
the benchmark's spans alone.  ``data/*_spans.xplane.pb`` are short traced
windows of the cells (two products each) recorded with the engine's
spans; the values expected of them are those the run on the chip logged.
The synthetic traces put every event where a known offset says, so the
bracket and each reader's value are known.
"""
import os

import pytest
from jax.profiler import ProfileData

from chipbench import clock, run, xplane
from chipbench.xplane import Event

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = [
    ("commodity_dense_1x1.xplane.pb", "commodity.dense"),
    ("bgq_dense_2x2.xplane.pb", "bgq.dense.2x2"),
]
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
#: per recorded window with the engine's spans: its cell and what the run
#: on the chip read from it (ms; the offset bracket per chip, in ms)
WITH_SPANS = {
    "commodity_dense_1x1_spans.xplane.pb": (
        "commodity.dense",
        {"frontend_ms": 0.3466755, "dispatch_ms": 0.3982095, "idle_in_engine_ms": 0.7342745},
        [(0.461, 0.936)],
    ),
    "bgq_dense_2x2_spans.xplane.pb": (
        "bgq.dense.2x2",
        {"frontend_ms": 0.37864, "dispatch_ms": 0.788205, "idle_in_engine_ms": 1.1332642},
        [(0.187, 1.121), (0.186, 1.096), (0.186, 1.290), (0.185, 1.075)],
    ),
}
NEW_READERS = ["frontend_ms", "dispatch_ms", "idle_in_engine_ms", "engine_trace_s", "engine_compile_s"]


def _context(trace, cell_name, chips=None):
    _, cell, config, traffic = run.load_cell(cell_name)
    window = trace.spans("chipbench.window")[0]
    chips = chips or cell["chips"]
    return run.RunContext(
        cell=cell, config=config, traffic=traffic, chips=chips,
        device_ids=list(range(chips)),
        calls=len(trace.spans("chipbench.call")), window_s=window.duration,
        host_call_s=[e.duration for e in trace.spans("chipbench.call")], peak=PEAK,
        trace=trace, window=(window.start, window.end),
    )


def _recorded(name, cell):
    return _context(xplane.from_profile(ProfileData.from_file(os.path.join(DATA, name))), cell)


@pytest.mark.parametrize("name, cell", RECORDED)
def test_recorded_bracket(name, cell):
    ctx = _recorded(name, cell)
    offsets = clock.offsets(ctx)
    assert offsets is not None and len(offsets) == ctx.chips
    for o in offsets:
        assert 0.9e-3 <= o.lo <= o.hi <= 2.1e-3, str(o)
        assert (o.launch, o.done) == ("DoEnqueueProgram", "tpu::System::Execute=>Done")


@pytest.mark.parametrize("name, cell", RECORDED)
def test_recorded_bracket_holds_the_looser_pair(name, cell, monkeypatch):
    """The runtime's launch call and the benchmark's wait alone bracket the
    offset too, more loosely, around the tighter bracket."""
    ctx = _recorded(name, cell)
    tight = clock.offsets(ctx)
    monkeypatch.setattr(clock, "LAUNCH", ("PJRT_LoadedExecutable_Execute",))
    monkeypatch.setattr(clock, "DONE", ("no such event", "chipbench.wait"))
    for t, o in zip(tight, clock.offsets(ctx)):
        assert (o.launch, o.done) == ("PJRT_LoadedExecutable_Execute", "chipbench.wait")
        assert o.lo < t.lo <= t.hi < o.hi


@pytest.mark.parametrize("name, cell", RECORDED)
def test_readers_of_the_engine_read_nothing_without_its_spans(name, cell, monkeypatch):
    """A program without the engine's spans and counters (as before they
    were added) gives nothing to read, and nothing raises."""
    from repro.core import summa

    monkeypatch.setattr(summa, "executable_cache_stats", lambda: {"hits": 0, "misses": 0, "retraces": 0, "size": 0})
    ctx = _recorded(name, cell)
    for name in NEW_READERS:
        assert run.metric_reader(name)(ctx) is None, name


DELTA = 1.5e-3  # host = device + DELTA


def _synthetic(products=3, chips=1, delta=DELTA, late_done=None, gather=False, stall=None):
    """A closed loop of ``products`` calls 1 s apart; on each chip a product
    runs 0.6 s from device time p (two ops), every host event placed by
    ``delta``.  ``late_done`` (a product) moves that product's done event
    before its last op ends on the host's clock.  ``gather`` opens each
    call with a padding gather, enqueued before ``repro.execute`` and run
    on the chip before the product, as a nonuniform call does.  ``stall``
    (a product) leaves the chip idle for 0.45 s inside that product, longer
    than the 0.4 s between products, as a host stall between two enqueues
    of one call does."""
    host = [Event("chipbench.window", -0.01, products + 0.0)]
    for p in range(products):
        t = p + delta  # the product's first op, on the host's clock
        done = t + 0.6 + (2e-4 if p != late_done else -1e-3)
        host += [
            Event("chipbench.call", t - 7e-4, t - 5e-5),
            Event("repro.matmul", t - 6e-4, t - 1e-4),
            Event("repro.execute", t - 4e-4, t - 2e-4),
            Event("repro.dispatch", t - 3e-4, t - 2.1e-4),
            Event("PJRT_LoadedExecutable_Execute", t - 2.5e-4, t - 2.2e-4),
            Event("DoEnqueueProgram", t - 2e-4, t - 1.5e-4),
            Event("chipbench.wait", t - 5e-5, t + 0.6 + 3e-4),
            Event("tpu::System::Execute=>Done", done, done + 1e-5),
        ]
        if gather:
            host.append(Event("DoEnqueueProgram", t - 5.8e-4, t - 5.6e-4))
    ops = [
        e for p in range(products)
        for e in (Event("%k.1", p, p + (0.1 if p == stall else 0.5)), Event("%c.1", p + (0.55 if p == stall else 0.5), p + 0.6))
    ]
    if gather:
        ops = sorted(ops + [Event("%g.1", p - 3.5e-4, p) for p in range(products)], key=lambda e: e.start)
    trace = xplane.Trace(
        [f"/device:TPU:{i}" for i in range(chips)], [list(ops) for _ in range(chips)],
        sorted(host, key=lambda e: e.start),
    )
    return _context(trace, "commodity.dense", chips=chips)


def test_synthetic_offset_is_recovered():
    (o,) = clock.offsets(_synthetic())
    assert o.lo == pytest.approx(DELTA - 2e-4)
    assert o.hi == pytest.approx(DELTA + 2e-4)
    assert o.mid == pytest.approx(DELTA)
    assert (o.launch, o.done) == ("DoEnqueueProgram", "tpu::System::Execute=>Done")


def test_synthetic_offset_with_a_gather_before_the_product():
    """The gather's launch, inside ``repro.matmul`` and before
    ``repro.execute``, bounds the offset from below."""
    (o,) = clock.offsets(_synthetic(gather=True))
    assert o.lo == pytest.approx(DELTA - 2.3e-4)
    assert o.hi == pytest.approx(DELTA + 2e-4)
    assert o.lo <= DELTA <= o.hi


def test_synthetic_offset_with_a_stall_inside_a_call():
    """Each call runs the same operations, so they are split by count, not
    at the widest idle gap, which here lies inside a call."""
    (o,) = clock.offsets(_synthetic(stall=1))
    assert (o.lo, o.hi) == pytest.approx((DELTA - 2e-4, DELTA + 2e-4))
    assert clock.products(_synthetic(stall=1).device_ops()[0], 3) == [(0, 0.6), (1, 1.6), (2, 2.6)]


def test_synthetic_offset_without_runtime_events():
    ctx = _synthetic(chips=2)
    ctx.trace.host = [e for e in ctx.trace.host if e.name not in ("DoEnqueueProgram", "PJRT_LoadedExecutable_Execute", "tpu::System::Execute=>Done")]
    for o in clock.offsets(ctx):
        assert o.lo == pytest.approx(DELTA - 3e-4)  # repro.dispatch starts
        assert o.hi == pytest.approx(DELTA + 3e-4)  # chipbench.wait ends
        assert (o.launch, o.done) == ("repro.dispatch", "chipbench.wait")


def test_empty_bracket_gives_none():
    assert clock.offsets(_synthetic(late_done=1)) is None


def test_products_split_at_the_widest_gaps():
    ops = [Event("a", 0, 1), Event("b", 1.1, 2), Event("c", 5, 6), Event("d", 6.01, 7), Event("e", 9, 10)]
    assert clock.products(ops, 3) == [(0, 2), (5, 7), (9, 10)]
    assert clock.products(ops, 1) == [(0, 10)]
    assert clock.products(ops, 6) is None


def test_frontend_and_dispatch_on_synthetic():
    ctx = _synthetic()
    assert run.metric_reader("frontend_ms")(ctx) == pytest.approx(0.5 - 0.09)
    assert run.metric_reader("dispatch_ms")(ctx) == pytest.approx(0.09)


def test_idle_in_engine_on_synthetic():
    """Every ``repro.matmul`` span ends before its product starts on the
    chip, so the whole span is idle time in the engine: 0.5 ms a call.  At
    the bracket's low end the chip starts 0.2 ms earlier on the host's
    clock, inside the span, and 0.4 ms of it is idle."""
    ctx = _synthetic(chips=2)
    value, note = run.metric_reader("idle_in_engine_ms")(ctx)
    assert value == pytest.approx(0.5)
    assert "chip 1: [1.300, 1.700] ms (DoEnqueueProgram / tpu::System::Execute=>Done)" in note
    assert "0.4000 ms at the low ends, 0.5000 ms at the high ends" in note
    device_idle = 1e3 * ctx.window_s * run.metric_reader("device_idle_share")(ctx) / 100 / ctx.calls
    assert value <= device_idle


def test_engine_counters(monkeypatch):
    from repro.core import summa

    stats = {"hits": 3, "misses": 2, "retraces": 2, "build_s": 4.5, "trace_s": 0.75, "size": 2}
    monkeypatch.setattr(summa, "executable_cache_stats", lambda: dict(stats))
    ctx = _synthetic()
    assert run.metric_reader("engine_trace_s")(ctx) == 0.75
    assert run.metric_reader("engine_compile_s")(ctx) == 3.75


@pytest.mark.parametrize("cell", ["commodity.dense", "bgq.dense.2x2"])
def test_traced_run_on_the_cpu_reads_the_engine(cell, tmp_path):
    """A whole traced run of the cell at a tiny size on emulated host
    devices: the engine's spans and counters are read; the readers of chip
    operations find none on the CPU."""
    import jax

    bench, w, config, traffic = run.load_cell(cell)
    config = dict(config, n=512, block=128)
    p_row, p_col = config["mesh"]
    result = run.run_cell(
        w, config, traffic, seed=2**33 + 11, seconds=0.2,
        devices=jax.devices()[: p_row * p_col], per_layer=bench["per_layer"],
        peak=PEAK, trace_dir=str(tmp_path / "trace"),
    )
    m = {k: v["value"] for k, v in result["metrics"].items()}
    for name in ("frontend_ms", "dispatch_ms", "engine_trace_s", "engine_compile_s"):
        assert m[name] > 0, name
    assert "idle_in_engine_ms" not in m  # no chip operations in a CPU trace
    assert m["frontend_ms"] + m["dispatch_ms"] <= m["host_call_ms"]


@pytest.mark.parametrize("name", sorted(WITH_SPANS))
def test_recorded_window_with_spans(name):
    cell, want, brackets = WITH_SPANS[name]
    ctx = _recorded(name, cell)
    assert ctx.calls == 2
    got = {}
    for metric in want:
        value = run.metric_reader(metric)(ctx)
        got[metric] = value[0] if isinstance(value, tuple) else value
        assert got[metric] == pytest.approx(want[metric], rel=1e-6), metric
    offsets = clock.offsets(ctx)
    assert [(round(1e3 * o.lo, 3), round(1e3 * o.hi, 3)) for o in offsets] == brackets

    # the same numbers, pairing each call's spans by order
    spans = {n: ctx.trace.spans(n) for n in ("repro.matmul", "repro.dispatch", "chipbench.call")}
    assert all(len(v) == ctx.calls for v in spans.values())
    own = [m.duration - d.duration for m, d in zip(spans["repro.matmul"], spans["repro.dispatch"])]
    assert got["frontend_ms"] == pytest.approx(1e3 * sum(own) / ctx.calls)
    for m, d, c in zip(spans["repro.matmul"], spans["repro.dispatch"], spans["chipbench.call"]):
        assert c.start <= m.start <= d.start and d.end <= m.end <= c.end
    host_call = run.metric_reader("host_call_ms")(ctx)
    assert abs(got["frontend_ms"] + got["dispatch_ms"] - host_call) < 0.1
    device_idle = 1e3 * ctx.window_s * run.metric_reader("device_idle_share")(ctx) / 100 / ctx.calls
    assert 0 < got["idle_in_engine_ms"] <= device_idle
    assert xplane.matching(ctx.device_ops()[0], "tiled_matmul")
