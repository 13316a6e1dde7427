"""``BENCHMARK.json`` keeps to the benchmark's contract, and every cell's
configuration, entry, traffic mix and per-layer metric resolves by name."""
import json
import math
import os
import re

import pytest

from chipbench import generate, run

ROOT = run.ROOT
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in BENCH["workloads"]]


def _line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert BENCH["paths"] == ["chipbench"]
    assert len(json.dumps(BENCH)) <= 64 * 1024


def test_names_and_units():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert _line(w["why"])
    for c in BENCH["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
        assert _line(c["source"]) and _line(c["why"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    metric_names = [n for is_metric, n in names if is_metric]
    assert len(metric_names) == len(set(metric_names))
    assert len(CELLS) == len(set(CELLS))


def test_entry_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert _line(m["layer"])
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(CELLS) // 2)


def test_run_seconds_fit_a_full_check():
    rs = BENCH["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    cells = 24
    assert (2 + 14 * cells) * (rs + 60) + cells * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    bench, w, config, traffic = run.load_cell(cell)
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    assert entry["file"].startswith("chipbench/")
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    assert config["mesh"][0] * config["mesh"][1] == w["chips"]
    assert set(config["limits"]) == {"worst_tile_rel_err", "worst_element_err"}
    generate.check_traffic(traffic)
    run.entry_of(config).check_traffic(traffic)
    reported_e2e = [m for m in bench["end_to_end"] if cell in m.get("workloads", CELLS)]
    assert "setup_s" in {m["name"] for m in reported_e2e} and len(reported_e2e) >= 2
    layer = [m for m in bench["per_layer"] if cell in m.get("workloads", CELLS)]
    assert layer
    for m in layer:
        assert callable(run.metric_reader(m["name"]))


@pytest.mark.parametrize("key,value", [("loop", "open"), ("clients", 4), ("values", "uniform"), ("b_fill", 0.5)])
def test_generator_refuses_a_mix_it_cannot_make(key, value):
    _, _, _, traffic = run.load_cell("commodity.dense")
    with pytest.raises(ValueError, match=key):
        generate.check_traffic(dict(traffic, **{key: value}))


def test_entry_refuses_a_mix_it_cannot_run():
    _, _, _, uniform = run.load_cell("commodity.dense")
    _, _, _, blocked = run.load_cell("commodity.nonuniform")
    with pytest.raises(ValueError, match="tiling"):
        run.entry_module("dense").check_traffic(blocked)
    with pytest.raises(ValueError, match="tiling"):
        run.entry_module("nonuniform").check_traffic(uniform)
    with pytest.raises(ValueError, match="loop"):
        run.entry_module("nonuniform").check_traffic(dict(blocked, loop="open"))
    with pytest.raises(ValueError, match="blocks"):
        generate.block_sizes(dict(blocked, blocks="uniform_random"), 1024)


@pytest.mark.parametrize("name", sorted({c.get("entry", "dense") for c in (
    run._json(os.path.join(ROOT, e["file"])) for e in BENCH["configs"])}))
def test_every_entry_resolves_by_name(name):
    module = run.entry_module(name)
    assert all(callable(getattr(module, f)) for f in ("check_traffic", "engine", "build"))


def test_a_new_entry_is_a_new_file(tmp_path):
    """An entry is found by name, as a metric reader is: a module dropped
    into ``chipbench/entries/`` needs no edit to the harness."""
    (tmp_path / "chipbench" / "entries").mkdir(parents=True)
    (tmp_path / "chipbench" / "entries" / "probe.py").write_text(
        "def check_traffic(traffic):\n    return None\n\n"
        "def engine(config, traffic, mesh):\n    return 'probe'\n\n"
        "def build(config, traffic, seed, mesh):\n    return (config, seed)\n"
    )
    module = run.entry_module("probe", root=str(tmp_path))
    assert module.build({"n": 8}, {}, 3, None) == ({"n": 8}, 3)
    assert run.entry_of({}).__name__ == "chipbench.entries.dense"
    with pytest.raises(ValueError, match="no entries module"):
        run.entry_module("absent", root=str(tmp_path))


def test_the_nonuniform_tiling_is_the_papers():
    """The mix's tiling is the program's section 4.1 tiling at the mix's
    parameters, fixed by the mix: 58 blocks of 210-303 rows, of which 28
    take two 256-wide tiles, padded to 22016."""
    from repro.core import blocking

    _, _, config, traffic = run.load_cell("commodity.nonuniform")
    n, mean = config["n"], traffic["mean_block"]
    sizes = generate.block_sizes(traffic, n)
    assert sizes == blocking.nonuniform_tiling(n, n // mean, traffic["tiling_seed"]).sizes
    assert sizes == generate.block_sizes(dict(traffic), n)
    assert (len(sizes), sum(sizes), min(sizes), max(sizes)) == (58, 14848, 210, 303)
    assert sum(s > config["block"] for s in sizes) == 28
    assert blocking.bucketize(blocking.Tiling(sizes), config["block"]).padded_extent == 22016
    for n, mean, seed in ((1280, 128, 0), (4096, 256, 7)):
        t = dict(traffic, mean_block=mean, tiling_seed=seed)
        assert generate.block_sizes(t, n) == blocking.nonuniform_tiling(n, n // mean, seed).sizes


def test_every_config_is_used_and_files_are_distinct():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))


def test_per_layer_cells_exist():
    for m in BENCH["per_layer"]:
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_peaks_name_their_source():
    peaks = json.load(open(os.path.join(ROOT, "chipbench", "peaks.json")))
    for kind, entry in peaks.items():
        assert entry["source"]
        assert math.isfinite(entry["bf16_flops_per_s"]) and entry["bf16_flops_per_s"] > 0
        assert entry["hbm_bytes_per_s"] > 0
