"""PageRank through the program's public entry, and the bytes one sweep
of the whole graph has to move."""
from __future__ import annotations


def call(view, params: dict, mesh):
    """``repro.algorithms.pagerank`` as a user calls it: ranks
    (P, v_max) on the host, and the run's Telemetry."""
    from repro import algorithms
    kw = dict(params)
    if mesh is not None:
        kw.update(backend="shard_map", mesh=mesh)
    return algorithms.pagerank(view.pg, **kw)


def full_sweep_bytes(n: int, arcs: int) -> int:
    """One pull sweep of the unpartitioned graph: each vertex's rank read
    and written (4 + 4 B); per arc its neighbour id and the gathered
    contribution (4 B each). Weights are not read."""
    return 8 * n + 8 * arcs
