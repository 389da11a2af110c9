"""PageRank (classic) and BlockRank (paper §5.3).

Classic PageRank maps to the engine with one Jacobi iteration per superstep —
as the paper notes, the sub-graph abstraction gives no superstep reduction
here (Gopher "simulates" the vertex iterations), so both modes run the same
``num_iters`` supersteps and the interesting comparison is per-superstep cost
and straggler skew (Fig 5).

BlockRank exploits the sub-graph structure the way the paper prescribes:
  phase 1  per-sub-graph LOCAL PageRank to convergence (zero messages —
           pure local fixpoint; one "costlier" superstep);
  phase 2  rank the blocks themselves (meta-graph PageRank — tiny, host-side);
  phase 3  seed classic PageRank with blockrank-weighted local ranks and run
           WITH a convergence tolerance -> far fewer global supersteps.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import GopherEngine, PageRankProgram, meta_graph
from repro.gofs.formats import PAD, PartitionedGraph
from repro.kernels import ops
from repro.obs import step


def pagerank(pg: PartitionedGraph, num_iters: int = 30, damping: float = 0.85,
             tol: Optional[float] = None, backend: str = "local", mesh=None,
             spmv_backend: str = "jnp", init_r: Optional[np.ndarray] = None):
    """Returns (ranks (P, v_max) float32, Telemetry)."""
    with step("entry"):
        init_fn = None
        if init_r is not None:
            r0 = jnp.asarray(init_r)

            def init_fn(gb):  # noqa: E306
                return r0[gb["part_index"]]

        prog = PageRankProgram(n_global=pg.n_global, num_iters=num_iters,
                               damping=damping, tol=tol,
                               spmv_backend=spmv_backend, init_fn=init_fn)
        eng = GopherEngine(pg, prog, backend=backend, mesh=mesh,
                           max_supersteps=max(num_iters + 1, 64))
        state, tele = eng.run()
        r = np.array(state["r"])
        r[~pg.vmask] = 0.0
        return r, tele


def _local_pagerank(pg: PartitionedGraph, num_iters: int = 30,
                    damping: float = 0.85, spmv_backend: str = "jnp"):
    """Phase 1: PageRank of each sub-graph in isolation (local edges only,
    per-sub-graph normalization). Pure local fixpoint — zero messages."""
    nbr = jnp.asarray(pg.nbr)
    wgt = jnp.ones_like(jnp.asarray(pg.wgt))
    vmask = jnp.asarray(pg.vmask)
    sg = jnp.asarray(pg.sg_id)
    v_max = pg.v_max

    # per-vertex LOCAL out-degree = how many local in-lists reference it
    def local_outdeg(nbr_p):
        idx = jnp.where(nbr_p == PAD, v_max, nbr_p).reshape(-1)
        return jax.ops.segment_sum(jnp.ones_like(idx, jnp.float32), idx,
                                   num_segments=v_max + 1)[:v_max]

    # per-sub-graph vertex counts -> per-vertex n_b
    def sg_size(sg_p, vmask_p):
        idx = jnp.where(vmask_p, sg_p, v_max).astype(jnp.int32)
        cnt = jax.ops.segment_sum(jnp.ones_like(idx, jnp.float32), idx,
                                  num_segments=v_max + 1)
        return cnt[jnp.clip(sg_p, 0, v_max - 1)]

    outdeg = jax.vmap(local_outdeg)(nbr)
    n_b = jax.vmap(sg_size)(sg, vmask)
    n_b = jnp.maximum(n_b, 1.0)

    def one_part(nbr_p, wgt_p, vmask_p, od_p, nb_p):
        r = jnp.where(vmask_p, 1.0 / nb_p, 0.0)

        def body(_, r):
            contrib = jnp.where(od_p > 0, r / jnp.maximum(od_p, 1.0), 0.0)
            pull = ops.semiring_spmv(contrib, nbr_p, wgt_p, "plus_times",
                                     backend=spmv_backend)
            return jnp.where(vmask_p, (1 - damping) / nb_p + damping * pull, 0.0)

        return jax.lax.fori_loop(0, num_iters, body, r)

    return np.asarray(jax.jit(jax.vmap(one_part))(nbr, wgt, vmask, outdeg, n_b))


def blockrank(pg: PartitionedGraph, damping: float = 0.85,
              tol: float = 1e-7, max_iters: int = 30,
              local_iters: int = 20, backend: str = "local", mesh=None,
              spmv_backend: str = "jnp"):
    """Returns (ranks, Telemetry-of-phase-3, info dict)."""
    # phase 1: local per-block PageRank
    local_r = _local_pagerank(pg, num_iters=local_iters, damping=damping,
                              spmv_backend=spmv_backend)
    # phase 2: meta-graph PageRank (host-side; meta graph is tiny)
    num_meta, meta_adj, meta_of = meta_graph(pg)
    br = np.full(num_meta, 1.0 / max(num_meta, 1))
    deg = np.asarray(meta_adj.sum(1)).ravel()
    a = meta_adj.T.astype(np.float64)
    for _ in range(50):
        contrib = np.where(deg > 0, br / np.maximum(deg, 1), 0.0)
        br = (1 - damping) / max(num_meta, 1) + damping * (a @ contrib)
    # phase 3: seed classic PageRank with blockrank-weighted local ranks
    valid = pg.sg_id != PAD
    seed = np.zeros((pg.num_parts, pg.v_max), np.float32)
    seed[valid] = (local_r[valid] * br[meta_of[valid]]).astype(np.float32)
    s = seed[pg.vmask].sum()
    seed = seed / max(s, 1e-12)  # normalize to a distribution
    r, tele = pagerank(pg, num_iters=max_iters, damping=damping, tol=tol,
                       backend=backend, mesh=mesh, spmv_backend=spmv_backend,
                       init_r=seed)
    return r, tele, dict(num_meta=num_meta, blockrank=br)
