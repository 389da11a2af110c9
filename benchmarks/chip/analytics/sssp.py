"""Weighted SSSP through the program's public entry, and the bytes one
sweep of the whole graph has to move."""
from __future__ import annotations


def call(view, params: dict, mesh):
    """``repro.algorithms.sssp`` from the view's root, as a user calls it:
    distances (P, v_max) on the host, and the run's Telemetry."""
    from repro import algorithms
    kw = dict(params)
    if mesh is not None:
        kw.update(backend="shard_map", mesh=mesh)
    return algorithms.sssp(view.pg, view.root, **kw)


def full_sweep_bytes(n: int, arcs: int) -> int:
    """One min-plus sweep of the unpartitioned graph: each vertex's
    distance read and written (4 + 4 B); per arc its neighbour id, its
    weight and the gathered distance (4 B each)."""
    return 8 * n + 12 * arcs
