"""The trace reader and the per-layer readers, against a recorded trace.

``data/commodity_dense_1x1.xplane.pb`` is a traced window of
``commodity.dense`` on one TPU v5e: five products, each one
``tiled_matmul`` kernel and one bf16 conversion.
``data/bgq_dense_2x2.xplane.pb`` is one of ``bgq.dense.2x2`` on four:
nine products, each with four panel broadcasts (``psum``, HLO
``all-reduce``) and two ``tiled_matmul`` kernels per chip.  The expected
numbers are worked out here from the raw profile, independently of
``xplane``.
"""
import dataclasses
import os

import pytest
from jax.profiler import ProfileData

from chipbench import generate, kernel_roofline, run, work, xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
DENSE = os.path.join(DATA, "commodity_dense_1x1.xplane.pb")
MESH = os.path.join(DATA, "bgq_dense_2x2.xplane.pb")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_union_total_subtract():
    assert xplane.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]
    assert xplane.total([(0, 1), (0.5, 2), (5, 5)]) == 2
    assert xplane.subtract([(0, 10)], [(1, 2), (1.5, 3), (9, 12)]) == [(0, 1), (3, 9)]
    assert xplane.subtract([(0, 1), (2, 3)], []) == [(0, 1), (2, 3)]
    assert xplane.subtract([(0, 1)], [(0, 1)]) == []


def test_op_name_is_the_instruction_not_its_operands():
    e = xplane.Event(
        "%convert.1 = bf16[8]{0} convert(f32[8]{0} %tiled_matmul_pallas.1)", 0, 1
    )
    assert e.op == "convert.1"
    assert xplane.matching([e], "tiled_matmul") == []
    assert e.opcode == "convert"
    psum = xplane.Event(
        "%psum.30 = bf16[8,8]{1,0:T(8,128)(2,1)} all-reduce(bf16[8,8]{1,0} %gte.4), "
        "channel_id=1, replica_groups={{0,1},{2,3}}", 0, 1,
    )
    assert psum.op == "psum.30" and xplane.is_collective(psum)
    assert xplane.is_collective(xplane.Event("%x = (bf16[8], bf16[8]) all-reduce-start(y)", 0, 1))
    assert xplane.is_collective(xplane.Event("all-gather-done.1", 0, 1))
    assert not xplane.is_collective(e)
    fused = xplane.Event("%f = bf16[8]{0} fusion(bf16[8]{0} %all-reduce.1), kind=kLoop", 0, 1)
    assert fused.opcode == "fusion" and not xplane.is_collective(fused)


def _raw_device_ops(path, device=0):
    """(instruction text, start_s, duration_s) of a device's op lines, read
    straight from the profile; the text starts at the instruction's name."""
    profile = ProfileData.from_file(path)
    plane = next(p for p in profile.planes if p.name == f"/device:TPU:{device}")
    out = []
    for line in plane.lines:
        if line.name in ("XLA Ops", "Async XLA Ops"):
            for ev in line.events:
                out.append((ev.name[1:], ev.start_ns * 1e-9, ev.duration_ns * 1e-9))
    return out


def _context(path, cell_name):
    trace = xplane.from_profile(ProfileData.from_file(path))
    _, cell, config, traffic = run.load_cell(cell_name)
    window = trace.spans("chipbench.window")[0]
    return run.RunContext(
        cell=cell, config=config, traffic=traffic, chips=cell["chips"],
        device_ids=list(range(cell["chips"])),
        calls=len(trace.spans("chipbench.call")), window_s=window.duration,
        host_call_s=[e.duration for e in trace.spans("chipbench.call")], peak=PEAK,
        trace=trace, window=(window.start, window.end),
    )


def _measure(intervals):
    """Length of a union of intervals, by a sweep over their ends."""
    ends = sorted({t for iv in intervals for t in iv})
    return sum(
        b - a for a, b in zip(ends, ends[1:])
        if any(s <= a and b <= e for s, e in intervals)
    )


@pytest.fixture(scope="module")
def dense_run():
    return _context(DENSE, "commodity.dense")


@pytest.fixture(scope="module")
def mesh_run():
    return _context(MESH, "bgq.dense.2x2")


def test_recorded_trace_shape(dense_run):
    trace = dense_run.trace
    assert trace.device_names == ["/device:TPU:0"]
    assert dense_run.calls == 5
    ops = [e.op for e in trace.devices[0]]
    assert ops.count("tiled_matmul_pallas.1") == 5
    assert ops.count("convert_element_type.1") == 5


def test_kernel_roofline_against_raw_profile(dense_run):
    raw = _raw_device_ops(DENSE)
    kernel = sum(d for text, _, d in raw if text.startswith("tiled_matmul"))
    n = dense_run.config["n"]
    least = max(2.0 * n**3 / PEAK["bf16_flops_per_s"], 3 * 2.0 * n * n / PEAK["hbm_bytes_per_s"])
    value, note = run.metric_reader("tiled_matmul_roofline")(dense_run)
    assert value == pytest.approx(100.0 * 5 * least / kernel, rel=1e-9)
    assert note == "compute-bound"
    assert 0 < value <= 100
    assert kernel_roofline.read(dense_run, "bsmm") is None


def test_idle_share_against_raw_profile(dense_run):
    busy = _measure([(s, s + d) for _, s, d in _raw_device_ops(DENSE)])
    want = 100.0 * (1.0 - busy / dense_run.window_s)
    assert run.metric_reader("device_idle_share")(dense_run) == pytest.approx(want, rel=1e-9)


def test_no_collectives_on_one_chip(dense_run):
    assert run.metric_reader("collective_ms")(dense_run) is None
    assert run.metric_reader("exposed_collective_ms")(dense_run) is None


def test_engine_mfu_and_host_call(dense_run):
    n = dense_run.config["n"]
    want = 100.0 * 5 * 2.0 * n**3 / (dense_run.window_s * 197e12)
    assert run.metric_reader("engine_mfu")(dense_run) == pytest.approx(want, rel=1e-12)
    assert work.useful_flops(dense_run.config) == 2.0 * n**3
    host = run.metric_reader("host_call_ms")(dense_run)
    assert 0 < host < 1e3 * dense_run.window_s / 5


def test_breakdown_lists_kernel_first(dense_run):
    b = run.breakdown(dense_run)
    assert b["device_ops"][0][0] == "tiled_matmul_pallas"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(label in ("chipbench.call", "chipbench.wait", "between calls") for label, _ in b["idle_gaps"])


def test_work_splits_over_the_mesh():
    config = {"n": 1024, "block": 256, "mesh": [2, 2], "dtype": "bfloat16", "out_dtype": "bfloat16"}
    per_device = work.device_work(config)
    assert per_device == [(2.0 * 512 * 1024 * 512, (2 * 512 * 1024 + 512 * 512) * 2.0)] * 4
    assert work.useful_flops(config) == 2.0 * 1024**3


def test_mesh_trace_shape(mesh_run):
    assert mesh_run.trace.device_names == [f"/device:TPU:{i}" for i in range(4)]
    assert mesh_run.calls == 9
    for ops in mesh_run.device_ops():
        assert sum(xplane.is_collective(e) for e in ops) == 4 * 9
        assert len(xplane.matching(ops, "tiled_matmul")) == 2 * 9


def test_collectives_against_raw_profile(mesh_run):
    coll, exposed = [], []
    for dev in range(4):
        raw = _raw_device_ops(MESH, dev)
        c = [(s, s + d) for text, s, d in raw if " all-reduce(" in text]
        other = [(s, s + d) for text, s, d in raw if " all-reduce(" not in text]
        coll.append(_measure(c))
        exposed.append(_measure(c + other) - _measure(other))
    want = 1e3 * sum(coll) / 4 / 9
    assert run.metric_reader("collective_ms")(mesh_run) == pytest.approx(want, rel=1e-9)
    want = 1e3 * sum(exposed) / 4 / 9
    assert run.metric_reader("exposed_collective_ms")(mesh_run) == pytest.approx(want, rel=1e-6)


def test_mesh_kernel_roofline(mesh_run):
    n = mesh_run.config["n"]
    m_loc = n // 2
    least = 2.0 * m_loc * n * m_loc / PEAK["bf16_flops_per_s"]
    kernel = sum(
        d for dev in range(4) for text, _, d in _raw_device_ops(MESH, dev)
        if text.startswith("tiled_matmul")
    )
    value, _ = run.metric_reader("tiled_matmul_roofline")(mesh_run)
    assert value == pytest.approx(100.0 * 4 * 9 * least / kernel, rel=1e-9)
    assert 0 < value <= 100


@pytest.mark.parametrize("path,cell,calls", [(DENSE, "commodity.dense", 5), (MESH, "bgq.dense.2x2", 9)])
def test_layout_ms_against_raw_profile(path, cell, calls):
    """Every op that is neither the kernel nor a panel broadcast, summed
    from the raw profile: the bf16 cast on one chip; on 2x2 the zeroing of
    panels and the add and cast besides."""
    chips = 4 if cell == "bgq.dense.2x2" else 1
    layout = [
        sum(d for text, _, d in _raw_device_ops(path, dev)
            if not text.startswith("tiled_matmul") and " all-reduce(" not in text)
        for dev in range(chips)
    ]
    assert all(layout)
    want = 1e3 * sum(layout) / chips / calls
    assert run.metric_reader("layout_ms")(_context(path, cell)) == pytest.approx(want, rel=1e-9)


def test_useful_flops_share_from_the_programs_counter():
    """``100 prod(1 - padding_waste)``, the logical N^3 over the padded
    extents, with the counter as the nonuniform entry reads it from
    ``NonuniformMatmul``; checked against ``bucketize`` at a small tiling.
    Nothing to read without the counter."""
    import jax
    from repro.core import blocking

    _, cell, config, traffic = run.load_cell("commodity.nonuniform")
    config = dict(config, n=1280, block=128)
    traffic = dict(traffic, mean_block=128)
    entry = run.entry_of(config)
    mesh = run.make_mesh(config, jax.devices()[:1])
    counters = entry.Product(config, entry.engine(config, traffic, mesh), None, None, mesh).counters()
    tiles = blocking.bucketize(blocking.Tiling(generate.block_sizes(traffic, 1280)), 128)
    assert tiles.padded_extent == 1664
    assert counters == {"padding_waste": {d: tiles.padding_waste for d in ("rows", "inner", "cols")}}
    ctx = run.RunContext(
        cell=cell, config=config, traffic=traffic, chips=1, device_ids=[0], calls=1,
        window_s=1.0, host_call_s=[], peak=PEAK, counters=counters,
    )
    read = run.metric_reader("useful_flops_share")
    assert read(ctx) == pytest.approx(100.0 * (1280 / 1664) ** 3, rel=1e-12)
    assert read(dataclasses.replace(ctx, counters={})) is None
