"""The comparison that decides ``correct``, on the CPU at a tiny size.

Each cell's whole run (set-up, window, comparison) is driven past the
look for a chip, on emulated host devices with the Pallas kernels
interpreted: a sound run is correct; the control (int8 operands) fails a
limit; and so does the timed path broken underneath, once for each fault
the cell can have: an answer altered where the kernel produces it, half
of the work left out with the rest scaled up to stand for it, and, on the
2x2 mesh, the exchange between chips left out.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import generate, run

TINY = {"n": 512, "block": 128}
#: the nonuniform cell at a tiny size: ten logical blocks of 112-151 rows,
#: 128-wide tiles, four blocks over one tile
TINY_NONUNIFORM = ({"n": 1280, "block": 128}, {"mean_block": 128})
CELLS = ["commodity.dense", "bgq.dense.2x2", "commodity.nonuniform"]


def _tiny(cell):
    bench, w, config, traffic = run.load_cell(cell)
    if config.get("entry") == "nonuniform":
        config, traffic = dict(config, **TINY_NONUNIFORM[0]), dict(traffic, **TINY_NONUNIFORM[1])
    else:
        config = dict(config, **TINY)
    p_row, p_col = config["mesh"]
    return bench, w, config, traffic, jax.devices()[: p_row * p_col]


def _run(cell, seed=2**33 + 7):
    bench, w, config, traffic, devices = _tiny(cell)
    return run.run_cell(
        w, config, traffic, seed=seed, seconds=0.05, devices=devices,
        end_to_end=bench["end_to_end"],
    )


@pytest.fixture
def fresh_programs():
    """Drop the engine's compiled programs around a test that plants a
    fault, so that neither it nor a later test reuses the other's."""
    from repro.core import summa

    summa.clear_executable_cache()
    yield
    summa.clear_executable_cache()


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, fresh_programs):
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"call_ms", "peak_hbm_gib", "setup_s"}


def test_same_seed_same_operands():
    _, _, config, _, devices = _tiny("commodity.dense")
    mesh = run.make_mesh(config, devices)
    a1, b1 = generate.make_operands(config, 2**40 + 3, mesh)
    a2, b2 = generate.make_operands(config, 2**40 + 3, mesh)
    a3, _ = generate.make_operands(config, 2**40 + 4, mesh)
    assert np.array_equal(np.asarray(a1), np.asarray(a2))
    assert np.array_equal(np.asarray(b1), np.asarray(b2))
    assert not np.array_equal(np.asarray(a1), np.asarray(a3))
    x = np.asarray(a1, np.float32)
    assert abs(x.mean()) < 0.02 and abs(x.std() - 1.0) < 0.02


def test_every_hash_gives_a_finite_normal():
    """The extreme hashes included: an N = 32768 pair of operands holds
    some hundred elements at each end of the 24-bit range."""
    h = jnp.asarray([0, 0xFF, 0x100, 0x7FFFFFFF, 0x80000000, 0xFFFFFF00, 0xFFFFFFFF], jnp.uint32)
    z = np.asarray(generate._to_normal(h))
    assert np.all(np.isfinite(z))
    assert z[0] == -z[-1] and 5.0 < z[-1] < 6.0
    assert np.array_equal(z[:2], z[:1].repeat(2)) and z[2] > z[0]


@pytest.mark.parametrize("cell", CELLS)
def test_int8_control_fails_a_limit(cell):
    _, _, config, traffic, devices = _tiny(cell)
    product = run.entry_of(config).build(config, traffic, 11, run.make_mesh(config, devices))
    product.drop()
    values = product.compare(product.control())
    limits = config["limits"]
    assert any(values[k] >= limits[k] for k in limits), values


def _alter_one_element(out, k):
    return out.at[0, 0].add(jnp.asarray(np.sqrt(k), out.dtype))


def _dense_fault(monkeypatch, fault):
    from repro.kernels import ops

    orig = ops.tiled_matmul

    def broken(a, b, **kw):
        if fault == "altered":
            return _alter_one_element(orig(a, b, **kw), a.shape[1])
        half = a.shape[1] // 2
        return 2 * orig(a[:, :half], b[:half], **kw)

    monkeypatch.setattr(ops, "tiled_matmul", broken)


def _exchange_fault(monkeypatch, fault):
    from repro.core import summa

    monkeypatch.setattr(summa, "_bcast_panel", lambda slab, owner, axis: slab)


def _compaction_fault(monkeypatch, fault):
    """C compacted with the rows of logical blocks 0 and 1 swapped, or
    with the rows of the first remainder tile (a block's second physical
    tile) left zero."""
    from repro.core.api import NonuniformMatmul

    orig = NonuniformMatmul._compact

    def broken(self, c):
        out = orig(self, c)
        tiles = self.row_b
        if fault == "misplaced":
            s0, s1 = self.row_tiling.sizes[:2]
            order = np.r_[s0:s0 + s1, 0:s0, s0 + s1:out.shape[0]]
            return out[jnp.asarray(order)]
        t = next(t for t in range(1, tiles.num_tiles) if tiles.block_id[t] == tiles.block_id[t - 1])
        rows = tiles.gather_indices()[t * tiles.tile: t * tiles.tile + tiles.valid[t]]
        return out.at[jnp.asarray(rows)].set(0)

    monkeypatch.setattr(NonuniformMatmul, "_compact", broken)


FAULTS = [
    ("commodity.dense", "altered", _dense_fault),
    ("commodity.dense", "half", _dense_fault),
    ("bgq.dense.2x2", "altered", _dense_fault),
    ("bgq.dense.2x2", "half", _dense_fault),
    ("bgq.dense.2x2", "no_exchange", _exchange_fault),
    ("commodity.nonuniform", "altered", _dense_fault),
    ("commodity.nonuniform", "half", _dense_fault),
    ("commodity.nonuniform", "misplaced", _compaction_fault),
    ("commodity.nonuniform", "dropped", _compaction_fault),
]


@pytest.mark.parametrize("cell,fault,plant", FAULTS, ids=[f"{c}-{f}" for c, f, _ in FAULTS])
def test_broken_path_is_not_correct(cell, fault, plant, monkeypatch, fresh_programs):
    plant(monkeypatch, fault)
    res = _run(cell)
    assert not res["correct"], res["checks"]
    assert res["failed"] == 1
