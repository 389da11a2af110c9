"""The datasets the benchmark runs, made from a configuration and a seed.

A configuration fixes one graph of the 10th DIMACS Challenge's ``delaunay``
family: the Delaunay triangulation of ``vertices`` points drawn uniformly in
the unit square from ``graph_seed``. Its SSSP arc weights are the edges'
Euclidean lengths in float32. The configuration also fixes the
partitioning (the program's partitioner on that graph, from
``partition_seed``) and the SSSP root (drawn with ``root_seed``), as a
published dataset and an LDBC Graphalytics source vertex are fixed.

The run's ``--seed`` draws a renumbering of the vertices: a random
interleaving of the partitions' global ids, and a random order of each
partition's vertices in its local slots, except for the root, which keeps
its slot. The program therefore lays out every partition anew: other rows
of its neighbour tables, other orders of each row's neighbours, remote
edges and mailbox slots. Every partition's vertex set, every padded shape
and the root's partition and slot stay as they are. So each seed gives the
program another input of the same size and the same work, and the compiled
loops of one configuration serve every seed.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np


@dataclasses.dataclass
class Dataset:
    """One undirected graph in its canonical numbering; each edge appears
    once in ``(src, dst, w)``."""
    n: int
    src: np.ndarray
    dst: np.ndarray
    w: np.ndarray
    root: int

    @property
    def arcs(self) -> int:
        """Directed arcs the program stores: every edge both ways."""
        return 2 * int(self.src.size)


def delaunay_edges(n: int, seed: int):
    """The edges of the Delaunay triangulation of ``n`` points uniform in
    the unit square, each once as (lower id, higher id), with their
    Euclidean lengths as float32."""
    from scipy.spatial import Delaunay
    pts = np.random.default_rng(seed).random((n, 2))
    tri = Delaunay(pts).simplices
    e = np.concatenate([tri[:, [0, 1]], tri[:, [1, 2]], tri[:, [0, 2]]])
    e = np.unique(np.sort(e, axis=1), axis=0).astype(np.int64)
    w = np.linalg.norm(pts[e[:, 0]] - pts[e[:, 1]], axis=1).astype(np.float32)
    return e[:, 0], e[:, 1], w


def dataset(cfg: dict) -> Dataset:
    if cfg["graph"] != "delaunay":
        raise ValueError(f"unknown graph kind {cfg['graph']!r}")
    n = int(cfg["vertices"])
    src, dst, w = delaunay_edges(n, int(cfg["graph_seed"]))
    root = int(np.random.default_rng(int(cfg["root_seed"])).integers(n))
    return Dataset(n=n, src=src, dst=dst, w=w, root=root)


def renumber(assign: np.ndarray, root: int, seed: int) -> np.ndarray:
    """``new_id[v]``: the partitions' global ids interleaved at random, and
    each partition's vertices put in its local slots in a random order,
    except ``root``, which keeps the slot it has in the canonical
    numbering (the program's local slot is a vertex's rank by global id
    within its partition)."""
    rng = np.random.default_rng(seed % 2**64)
    slots = rng.permutation(assign)                 # label of each new id
    new_id = np.empty(assign.size, np.int64)
    for p in np.unique(assign):
        members = np.flatnonzero(assign == p)       # canonical rank order
        ids = np.flatnonzero(slots == p)            # local slot order
        order = rng.permutation(members.size)
        if assign[root] == p:
            r = int(np.searchsorted(members, root))
            j = int(np.flatnonzero(order == r)[0])
            order[[j, r]] = order[[r, j]]
        new_id[members] = ids[order]
    return new_id


@dataclasses.dataclass
class View:
    """What the program gets for one run: the partitioned graph in the
    seed's numbering, and the maps back to the canonical numbering."""
    pg: object
    new_id: np.ndarray          # canonical id -> the program's id
    root: int                   # the root in the program's numbering
    build_s: float

    def to_canonical(self, per_part: np.ndarray) -> np.ndarray:
        """(P, v_max) per-partition values -> canonical vertex order."""
        pg = self.pg
        per_part = np.asarray(per_part)
        out = np.empty(pg.n_global, per_part.dtype)
        out[pg.global_id[pg.vmask]] = per_part[pg.vmask]
        return out[self.new_id]


def build(ds: Dataset, cfg: dict, seed: int) -> View:
    """The program's GoFS build of the seed's view, timed on the host:
    the graph, the partitioner on the canonical graph, and the partitioned
    store in the seed's numbering."""
    from repro.gofs import bfs_grow_partition
    from repro.gofs.formats import Graph, partition_graph
    parts = int(cfg["partitions"])
    if cfg["partitioner"] != "bfs_grow":
        raise ValueError(f"unknown partitioner {cfg['partitioner']!r}")
    t0 = time.perf_counter()
    g0 = Graph.from_edges(ds.n, ds.src, ds.dst, weights=ds.w, directed=False)
    assign = np.asarray(bfs_grow_partition(g0, parts,
                                           seed=int(cfg["partition_seed"])))
    new_id = renumber(assign, ds.root, seed)
    g = Graph.from_edges(ds.n, new_id[ds.src], new_id[ds.dst], weights=ds.w,
                         directed=False)
    new_assign = np.empty_like(assign)
    new_assign[new_id] = assign
    pg = partition_graph(g, new_assign, parts)
    return View(pg=pg, new_id=new_id, root=int(new_id[ds.root]),
                build_s=time.perf_counter() - t0)
