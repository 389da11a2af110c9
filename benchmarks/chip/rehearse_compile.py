#!/usr/bin/env python3
"""Compile each cell's BSP loop for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse_compile.py [cell ...]

For each cell (all of ``BENCHMARK.json`` by default) this builds the
cell's graph as a run with seed 0 would, lets the program's entry build its
engine, stops it before it runs, and compiles that engine's loop for one
chip of a described ``v5e:2x2`` (the cell's mesh over all four where it
asks for four). It prints ``memory_analysis()`` of each: argument, output
and temporary bytes per device. Nothing runs, so it says nothing of time.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import graphs  # noqa: E402
from loader import ROOT, cell, load, read_json  # noqa: E402


class _Stop(Exception):
    pass


def engine_of(c: dict, mesh):
    """The engine the program's entry builds for the cell's run."""
    from repro.core import engine as engine_mod
    traffic = c["traffic_file"]
    view = graphs.build(graphs.dataset(c["config_file"]), c["config_file"], 0)
    caught = {}

    def stop(self, *a, **k):
        caught["engine"] = self
        raise _Stop

    real = engine_mod.GopherEngine.run
    engine_mod.GopherEngine.run = stop
    try:
        load("analytics", traffic["analytic"]).call(
            view, dict(traffic.get("params", {})), mesh)
    except _Stop:
        pass
    finally:
        engine_mod.GopherEngine.run = real
    return caught["engine"]


def compile_cell(c: dict, topo) -> dict:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding
    from repro.core import device_block, host_graph_block
    k = int(c["traffic_file"].get("mesh_parts", 0))
    if k:
        mesh = jax.sharding.Mesh(list(topo.devices[:k]), ("parts",))
        eng = engine_of(c, mesh)
        gb = device_block(host_graph_block(eng.pg))   # on the host's CPU
        shard = NamedSharding(mesh, PartitionSpec("parts"))
    else:
        eng = engine_of(c, None)
        gb = eng._gb_for_run(eng._graph_block())
        shard = SingleDeviceSharding(topo.devices[0])
    specs = {key: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=shard)
             for key, v in gb.items()}
    ma = eng._runner(gb_example=gb).lower(specs).compile().memory_analysis()
    return {"cell": c["name"], "exchange": eng.exchange, "devices": k or 1,
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "generated_code_bytes": ma.generated_code_size_in_bytes}


def main(argv=None) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    spec = read_json(ROOT / "BENCHMARK.json")
    names = (argv if argv else None) or [w["name"] for w in spec["workloads"]]
    for name in names:
        print(json.dumps(compile_cell(cell(spec, name), topo)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
