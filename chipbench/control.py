#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: the program over many
seeds, and the control over a few.  Runs on the TPU only.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 --control-seeds 4,5,6

For each of ``--seeds`` it drives a run of the cell (set-up, a short
window at the cell's own size and load, the comparison) and prints the
numbers compared.  For each of ``--control-seeds`` it puts the control in
the program's place, the reference computed from int8 operands
(``reference.int8_control``, through the cell's entry, on the operands
the entry makes), and prints the same numbers.  The lower
reading of a number is the largest the program gives, the upper one the
smallest the control gives.  One JSON line per reading; the benchmark's
own runs never run this.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import run  # noqa: E402


#: a short window at the cell's own load: one product or a few
WINDOW_S = 1.0


def _seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    args = ap.parse_args(argv)

    run._paths()
    bench, cell, config, traffic = run.load_cell(args.workload)
    devices = run.require_devices(int(cell["chips"]))
    run.log(f"compile cache {run.enable_compile_cache()}")

    def emit(kind: str, seed: int, values: dict, **extra) -> None:
        line = {"workload": cell["name"], "kind": kind, "seed": seed, **values, **extra}
        print(json.dumps(line), flush=True)

    for seed in args.seeds:
        with contextlib.redirect_stdout(sys.stderr):
            res = run.run_cell(
                cell, config, traffic, seed=seed, seconds=WINDOW_S,
                devices=devices, end_to_end=bench["end_to_end"],
            )
        emit("program", seed, {k: v["value"] for k, v in res["checks"].items()},
             correct=res["correct"], calls=res["attempted"])
    mesh = run.make_mesh(config, devices)
    entry = run.entry_of(config)
    for seed in args.control_seeds:
        product = entry.build(config, traffic, seed, mesh)
        product.drop()
        c = product.control()
        values = product.compare(c)
        del product, c
        emit("int8_control", seed, values)
    return 0


if __name__ == "__main__":
    sys.exit(main())
