"""Incremental re-convergence for the monotone semiring algorithms.

After an ``gofs.temporal.apply_delta``, CC/BFS/SSSP do NOT restart from
scratch: the previous fixpoint is already correct almost everywhere, and the
idempotent-monotone semirings make partial restarts exact.

Insertions (values can only IMPROVE — min distances shrink, max labels grow):
    resume from the previous fixpoint with the frontier seeded at the
    inserted edges' source endpoints. The masked sweeps re-relax exactly the
    affected region; every other partition enters its superstep with an
    empty frontier and runs zero sweeps. The result is bitwise identical to
    a cold run on the new graph: the fixpoint of an idempotent ⊕ is the
    ⊕-reduction over all path values, which is schedule-independent.

    Boundary messaging note: seeding the inserted SOURCES (not destinations)
    is what makes this correct — sources re-announce their converged values
    at superstep 0 (`changed_v` includes the seed frontier there), so a new
    remote edge delivers its first message, and a new local edge's
    destination row re-relaxes because its in-neighbor is in the frontier.

Deletions (values may be stale-OPTIMISTIC — monotone resume can't fix them):
    fall back to recomputing only the AFFECTED SUB-GRAPHS: every sub-graph
    (partition-local WCC, the paper's meta-vertex) reachable in the new
    meta-graph from a deleted edge's destination sub-graph is reset to its
    cold-start values, and the frontier is seeded with the reset vertices
    plus the *boundary* sources — live remote edges entering the reset
    region, whose converged upstream values re-flow in at superstep 0.
    Any vertex whose old value depended on a deleted edge had a dependency
    path through that edge's destination; the path's surviving suffix makes
    it meta-reachable from a seed, so the reset set covers every stale
    vertex. Unaffected sub-graphs never sweep.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np
import scipy.sparse as sp

from repro.core import GopherEngine, SemiringProgram, meta_graph
from repro.gofs.formats import PAD, PartitionedGraph
from repro.gofs.temporal import DeltaResult


def _meta_reachable(pg: PartitionedGraph, seed_vertices: np.ndarray
                    ) -> np.ndarray:
    """(P, v_max) bool: vertices of every sub-graph reachable (along remote
    edge direction) from the sub-graphs containing ``seed_vertices``."""
    num_meta, _, meta_of = meta_graph(pg)
    if num_meta == 0:
        return np.zeros_like(pg.vmask)
    src_m, dst_m = [], []
    for p in range(pg.num_parts):
        m = pg.re_src[p] != PAD
        if not m.any():
            continue
        src_m.append(meta_of[p, pg.re_src[p][m]])
        dst_m.append(meta_of[pg.re_dst_part[p][m], pg.re_dst_local[p][m]])
    if src_m:
        src_m, dst_m = np.concatenate(src_m), np.concatenate(dst_m)
    else:
        src_m = dst_m = np.zeros(0, np.int64)
    adj = sp.csr_matrix((np.ones(src_m.size, np.int8), (src_m, dst_m)),
                        shape=(num_meta, num_meta))
    reach = np.zeros(num_meta, bool)
    seeds = meta_of[seed_vertices & pg.vmask]
    reach[seeds[seeds >= 0]] = True
    frontier = reach.copy()
    while frontier.any():                       # meta-graph BFS (tiny graph)
        nxt = (adj.T @ frontier) > 0
        nxt &= ~reach
        reach |= nxt
        frontier = nxt
    return reach[np.clip(meta_of, 0, num_meta - 1)] & (meta_of >= 0) & pg.vmask


def _boundary_sources(pg: PartitionedGraph, reset: np.ndarray) -> np.ndarray:
    """(P, v_max) bool: sources of live remote edges entering ``reset`` from
    outside it — they must re-announce their converged values."""
    out = np.zeros_like(reset)
    for p in range(pg.num_parts):
        m = pg.re_src[p] != PAD
        if not m.any():
            continue
        srcs = pg.re_src[p][m]
        into_reset = reset[pg.re_dst_part[p][m], pg.re_dst_local[p][m]]
        from_outside = ~reset[p, srcs]
        out[p, srcs[into_reset & from_outside]] = True
    return out


def resume_seed(pg: PartitionedGraph, prev_x: np.ndarray,
                delta: DeltaResult, init_values: np.ndarray):
    """The restart state of an incremental run, ``(x0, frontier0)``: the
    previous fixpoint ``prev_x`` ((P, v_max) or per query lane (P, v_max,
    Q)), with every sub-graph meta-reachable from a removal reset to
    ``init_values``, and the seed frontier (P, v_max): the inserted edges'
    sources, the reset vertices and the boundary sources into them.

    The seed meets the frontier invariant that the masked sweeps rely on
    (kernels.megastep.sweep_flat): every vertex left out of it has already
    relaxed all its local out-neighbours, since a local in-neighbour of a
    reset vertex lies in the same partition-local WCC and so is reset and
    seeded too."""
    x0 = np.array(prev_x, np.float32, copy=True)
    frontier = np.asarray(delta.dirty_insert, bool).copy()
    if delta.dirty_remove.any():
        reset = _meta_reachable(pg, np.asarray(delta.dirty_remove, bool))
        x0[reset] = init_values[reset]
        frontier |= reset | _boundary_sources(pg, reset)
    return x0, frontier & pg.vmask


def _incremental_run(pg: PartitionedGraph, semiring: str, prev_x: np.ndarray,
                     delta: DeltaResult, init_values: np.ndarray,
                     backend: str = "local", mesh=None,
                     spmv_backend: str = "jnp",
                     max_local_iters: Optional[int] = None,
                     gb: Optional[dict] = None, exchange: str = "auto",
                     tier_plan=None):
    x0, frontier = resume_seed(pg, prev_x, delta, init_values)
    prog = SemiringProgram(semiring=semiring, resume=True,
                           spmv_backend=spmv_backend,
                           max_local_iters=max_local_iters)
    # gb: pass the zero-repack-patched device block (DeltaResult.block via
    # core.blocks.device_block) so the restart skips the per-version re-pack;
    # exchange/tier_plan: callers holding a taught profile can route the
    # restart over a tiered/phased wire (Gopher Mesh/Phases)
    eng = GopherEngine(pg, prog, backend=backend, mesh=mesh, gb=gb,
                       exchange=exchange, tier_plan=tier_plan)
    return eng.run(extra={"x0": x0, "frontier0": frontier})


def incremental_sssp(pg: PartitionedGraph, source_global: int,
                     prev_dist: np.ndarray, delta: DeltaResult,
                     backend: str = "local", mesh=None,
                     spmv_backend: str = "jnp",
                     gb: Optional[dict] = None, exchange: str = "auto",
                     tier_plan=None):
    """SSSP on graph version k+1 from version k's distances. Returns
    (distances (P, v_max), Telemetry) — bit-identical to a cold sssp()."""
    init = np.full((pg.num_parts, pg.v_max), np.inf, np.float32)
    init[int(pg.part_of[source_global]),
         int(pg.local_of[source_global])] = 0.0
    prev_x = np.where(pg.vmask, np.asarray(prev_dist, np.float32), np.inf)
    state, tele = _incremental_run(pg, "min_plus", prev_x, delta, init,
                                   backend=backend, mesh=mesh,
                                   spmv_backend=spmv_backend, gb=gb,
                                   exchange=exchange, tier_plan=tier_plan)
    dist = np.array(state["x"])
    dist[~pg.vmask] = np.inf
    return dist, tele


def incremental_bfs(pg: PartitionedGraph, source_global: int,
                    prev_levels: np.ndarray, delta: DeltaResult,
                    backend: str = "local", mesh=None,
                    spmv_backend: str = "jnp",
                    gb: Optional[dict] = None, exchange: str = "auto",
                    tier_plan=None):
    """BFS = SSSP over unit weights (graph must carry unit weights)."""
    return incremental_sssp(pg, source_global, prev_levels, delta,
                            backend=backend, mesh=mesh,
                            spmv_backend=spmv_backend, gb=gb,
                            exchange=exchange, tier_plan=tier_plan)


def incremental_sssp_batched(pg: PartitionedGraph, sources_global,
                             prev_dist: np.ndarray, delta: DeltaResult,
                             backend: str = "local", mesh=None,
                             gb: Optional[dict] = None,
                             exchange: str = "auto", tier_plan=None):
    """Q-source incremental SSSP: resume ALL query lanes from their previous
    fixpoints in ONE batched BSP run (the landmark-maintenance path —
    ROADMAP item 4). ``prev_dist`` is (Q, n_global) in global vertex order
    (LandmarkCache.dist layout); returns (dist (Q, n_global), Telemetry),
    bit-identical to a cold batched run on the new graph.

    The dirty seed is shared across lanes (an inserted edge can improve any
    lane; extra frontier on a converged lane just re-relaxes to the same
    values — idempotent ⊕ makes the overshoot a no-op), while removals
    reset each lane's meta-reachable region to its OWN cold init before the
    restart. ``gb`` lets the caller pass the (possibly zero-repack-patched)
    device graph block so the maintenance run shares the serving fleet's
    device copy; ``exchange``/``tier_plan`` let the serving layer route the
    refresh over its narrow-only phased plan
    (core.tiers.PhasedTierPlan.narrow_resume — this run IS a narrow-frontier
    resume from superstep 0, so it never needs the wide band's geometry)."""
    from repro.serving.batched import (BatchedSemiringProgram,
                                       gather_query_results, sssp_query_init)
    sources_global = np.asarray(sources_global, np.int64).reshape(-1)
    L = int(sources_global.shape[0])
    P, v_max = pg.num_parts, pg.v_max
    prev = np.asarray(prev_dist, np.float32)
    x0 = np.full((P, v_max, L), np.inf, np.float32)
    for p in range(P):
        m = pg.vmask[p]
        x0[p][m] = prev[:, pg.global_id[p][m]].T
    x0, frontier = resume_seed(pg, x0, delta,
                               sssp_query_init(pg, sources_global))
    qf = np.broadcast_to(frontier[..., None], x0.shape)
    prog = BatchedSemiringProgram(semiring="min_plus", num_queries=L,
                                  resume=True)
    eng = GopherEngine(pg, prog, backend=backend, mesh=mesh, gb=gb,
                       exchange=exchange, tier_plan=tier_plan)
    state, tele = eng.run_queries(extra={"qx0": x0, "qfrontier0": qf})
    return gather_query_results(pg, state["x"]), tele


def incremental_connected_components(
        pg: PartitionedGraph, prev_labels: np.ndarray, delta: DeltaResult,
        backend: str = "local", mesh=None,
        spmv_backend: str = "jnp",
        gb: Optional[dict] = None, exchange: str = "auto",
        tier_plan=None) -> Tuple[np.ndarray, int, object]:
    """HCC labels on graph version k+1 from version k's labels. Returns
    (labels, num_components, Telemetry) — bit-identical to a cold run."""
    gid = pg.global_id.astype(np.float32)
    init = np.where(pg.vmask, gid, -np.inf).astype(np.float32)
    prev_x = np.where(pg.vmask, np.asarray(prev_labels, np.float32), -np.inf)
    state, tele = _incremental_run(pg, "max_first", prev_x, delta, init,
                                   backend=backend, mesh=mesh,
                                   spmv_backend=spmv_backend, gb=gb,
                                   exchange=exchange, tier_plan=tier_plan)
    x = np.asarray(state["x"])
    labels = np.where(pg.vmask, x, -1).astype(np.int64)
    ncc = len(np.unique(labels[pg.vmask]))
    return labels, ncc, tele
