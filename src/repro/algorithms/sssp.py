"""Single-Source Shortest Path (paper §5.2, Algorithm 3).

The paper runs Dijkstra inside each sub-graph per superstep; priority queues
do not vectorize, so the TPU adaptation runs the min-plus relaxation to local
fixpoint — identical per-superstep semantics (all intra-sub-graph shortest
paths settle before messages go out), identical superstep count
(meta-graph-diameter-bounded), VPU-friendly inner loop (see DESIGN.md §2.1).
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from repro.core import GopherEngine, SemiringProgram, make_sssp_init
from repro.gofs.formats import PartitionedGraph
from repro.obs import step


def sssp(pg: PartitionedGraph, source_global: int, mode: str = "subgraph",
         backend: str = "local", mesh=None,
         spmv_backend: str = "jnp",
         max_local_iters: Optional[int] = None):
    """Returns (distances (P, v_max) float32, inf = unreachable, Telemetry)."""
    with step("entry"):
        sp_ = int(pg.part_of[source_global])
        sl_ = int(pg.local_of[source_global])
        prog = SemiringProgram(
            semiring="min_plus", init_fn=make_sssp_init(sp_, sl_),
            max_local_iters=(max_local_iters if mode == "subgraph" else 1),
            spmv_backend=spmv_backend)
        eng = GopherEngine(pg, prog, backend=backend, mesh=mesh)
        state, tele = eng.run()
        dist = np.array(state["x"])
        dist[~pg.vmask] = np.inf
        return dist, tele
