#!/usr/bin/env python3
"""The control of ``correct``: the plain reference in the program's place,
computed in bfloat16, the precision below the configuration's float32.

    python3 benchmarks/chip/control.py --workload <cell> --seeds <n> ...

For each seed it puts the reference's bfloat16 version (on the default JAX
device, the chip where there is one) in the place of the program's entry
(``repro.algorithms.<analytic>``), fed the renumbered graph that the
program gets in that seed's run, and runs the cell through ``run.py``'s
own set-up, window and check. It prints ``run.py``'s result line for each
seed: every one has to read ``correct`` false, and the smallest values of
its ``checks`` are the upper readings of the limits in ``PERF.md``. The
benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import types
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import graphs  # noqa: E402
import run  # noqa: E402
from loader import ROOT, cell, load, read_json  # noqa: E402

# The control's window: one run, the whole comparison of the cell's check.
WINDOW_S = 1.0


def run_control(c: dict, seed: int, seconds: float, devices) -> dict:
    """``run.run_cell`` for cell ``c`` and ``seed`` with the program's
    entry replaced by the bfloat16 reference."""
    from repro import algorithms
    traffic = c["traffic_file"]
    analytic = traffic["analytic"]
    params = dict(traffic.get("params", {}))
    ref = load("references", analytic)
    seen = {}
    real_build = graphs.build

    def build(ds, cfg, seed):
        seen["ds"], seen["view"] = ds, real_build(ds, cfg, seed)
        return seen["view"]

    def entry(pg, *args, **kwargs):
        ds, view = seen["ds"], seen["view"]
        nid = view.new_id
        vals = ref.control(ds.n, nid[ds.src], nid[ds.dst], ds.w, view.root,
                           params)
        fill = np.inf if analytic == "sssp" else 0.0
        out = np.where(pg.vmask, vals[np.maximum(pg.global_id, 0)], fill)
        tele = types.SimpleNamespace(
            local_iters=np.zeros(pg.num_parts, np.int64))
        return out.astype(np.float32), tele

    real_entry = getattr(algorithms, analytic)
    graphs.build = build
    setattr(algorithms, analytic, entry)
    try:
        return run.run_cell(c, seed, seconds, False, devices,
                            time.perf_counter())
    finally:
        graphs.build = real_build
        setattr(algorithms, analytic, real_entry)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    c = cell(read_json(ROOT / "BENCHMARK.json"), args.workload)
    run.use_checkout_cache()
    devices = run.chips_or_none(int(c["chips"]))
    if devices is None:
        return 2
    for seed in args.seeds:
        res = run_control(c, seed, WINDOW_S, devices)
        print(json.dumps(dict(res, workload=args.workload, seed=seed)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
