"""Max Vertex (paper Algorithm 2) — the didactic example of the abstraction."""
from __future__ import annotations

import numpy as np

from repro.core import GopherEngine, SemiringProgram, init_max_vertex
from repro.gofs.formats import PartitionedGraph


def max_vertex(pg: PartitionedGraph, mode: str = "subgraph",
               backend: str = "local", mesh=None,
               spmv_backend: str = "jnp"):
    """Returns (per-vertex max-reachable-value (P, v_max), Telemetry).

    mode='subgraph' -> Gopher (local fixpoint); mode='vertex' -> Giraph-like
    (one sweep per superstep).
    """
    prog = SemiringProgram(
        semiring="max_first", init_fn=init_max_vertex,
        max_local_iters=None if mode == "subgraph" else 1,
        spmv_backend=spmv_backend)
    eng = GopherEngine(pg, prog, backend=backend, mesh=mesh)
    state, tele = eng.run()
    x = np.array(state["x"])
    x[~pg.vmask] = -np.inf
    return x, tele
