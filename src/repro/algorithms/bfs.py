"""BFS levels = SSSP over unit weights (paper §5.4 traversal class)."""
from __future__ import annotations

import numpy as np

from repro.core import GopherEngine, SemiringProgram, make_bfs_init
from repro.gofs.formats import PartitionedGraph


def bfs(pg: PartitionedGraph, source_global: int, mode: str = "subgraph",
        backend: str = "local", mesh=None,
        spmv_backend: str = "jnp"):
    """Returns (levels (P, v_max) float32 — hop counts, inf unreachable, Telemetry).
    Requires the graph to have been built with unit weights."""
    sp_ = int(pg.part_of[source_global])
    sl_ = int(pg.local_of[source_global])
    prog = SemiringProgram(
        semiring="min_plus", init_fn=make_bfs_init(sp_, sl_),
        max_local_iters=None if mode == "subgraph" else 1,
        spmv_backend=spmv_backend)
    eng = GopherEngine(pg, prog, backend=backend, mesh=mesh)
    state, tele = eng.run()
    lvl = np.array(state["x"])
    lvl[~pg.vmask] = np.inf
    return lvl, tele
