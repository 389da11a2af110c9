"""End-to-end behaviour tests: the paper's algorithms on the paper's graph
shapes, validated against scipy ground truth, in BOTH execution models
(sub-graph centric Gopher and vertex centric Giraph-baseline)."""
import numpy as np
import pytest
import scipy.sparse.csgraph as csgraph


from repro.gofs import (bfs_grow_partition, hash_partition,
                        powerlaw_social, road_grid, subgraph_balanced_partition,
                        trace_star)
from repro.gofs.formats import partition_graph
from repro.core import meta_diameter, vertex_diameter
from repro.algorithms import blockrank, connected_components, pagerank, sssp


def _gather(pg, per_part):
    """(P, v_max) -> (n,) global order."""
    out = np.zeros(pg.n_global, per_part.dtype)
    for p in range(pg.num_parts):
        m = pg.vmask[p]
        out[pg.global_id[p][m]] = per_part[p][m]
    return out


GRAPHS = {
    "road": lambda: road_grid(16, 16, drop_frac=0.08, seed=1),
    "social": lambda: powerlaw_social(300, m=4, seed=2),
    "trace": lambda: trace_star(300, n_hubs=4, seed=3),
}
PARTITIONERS = {
    "hash": hash_partition,
    "bfs": bfs_grow_partition,
    "balanced": subgraph_balanced_partition,
}


@pytest.mark.parametrize("gname", sorted(GRAPHS))
@pytest.mark.parametrize("pname", ["hash", "bfs"])
def test_connected_components_matches_scipy(gname, pname):
    g = GRAPHS[gname]()
    pg = partition_graph(g, PARTITIONERS[pname](g, 4, seed=0), 4)
    ncc_true, lab_true = csgraph.connected_components(g.undirected_csr(),
                                                      directed=False)
    labels, ncc, tele = connected_components(pg, mode="subgraph")
    assert ncc == ncc_true
    ours = _gather(pg, labels)
    # same partition of vertices into components
    for c in range(ncc_true):
        vals = np.unique(ours[lab_true == c])
        assert len(vals) == 1


@pytest.mark.parametrize("gname", sorted(GRAPHS))
def test_sssp_matches_scipy(gname):
    g = GRAPHS[gname]()
    pg = partition_graph(g, bfs_grow_partition(g, 4, seed=0), 4)
    src = 0
    d_true = csgraph.shortest_path(g.undirected_csr(), unweighted=True,
                                   indices=[src])[0]
    dist, _ = sssp(pg, src, mode="subgraph")
    ours = _gather(pg, dist)
    finite = np.isfinite(d_true)
    np.testing.assert_allclose(ours[finite], d_true[finite], atol=1e-5)
    assert np.array_equal(np.isfinite(ours), finite)


def test_weighted_sssp():
    g = road_grid(10, 10, drop_frac=0.0, seed=4, weighted=True)
    pg = partition_graph(g, bfs_grow_partition(g, 3, seed=0), 3)
    d_true = csgraph.shortest_path(g.csr().T, indices=[5])[0]  # out-edges
    dist, _ = sssp(pg, 5, mode="subgraph")
    ours = _gather(pg, dist)
    finite = np.isfinite(d_true)
    np.testing.assert_allclose(ours[finite], d_true[finite], rtol=1e-5)


def _pagerank_oracle(g, iters, damping=0.85):
    """float64 power iteration WITH dangling-mass redistribution (the
    engine's — correct — formulation: sinks teleport their rank)."""
    A = g.csr()
    outdeg = g.out_degree.astype(np.float64)
    rr = np.full(g.n, 1.0 / g.n)
    for _ in range(iters):
        contrib = np.where(outdeg > 0, rr / np.maximum(outdeg, 1), 0)
        mass = rr[outdeg == 0].sum()
        rr = (1 - damping) / g.n + damping * (A @ contrib + mass / g.n)
    return rr


def test_pagerank_matches_reference():
    g = powerlaw_social(300, m=4, seed=5)   # dust vertices = dangling sinks
    pg = partition_graph(g, bfs_grow_partition(g, 4, seed=0), 4)
    r, tele = pagerank(pg, num_iters=30)
    rr = _pagerank_oracle(g, 30)
    # fp32 segment-sum at powerlaw hubs vs float64 reference: relative check
    np.testing.assert_allclose(_gather(pg, r), rr, rtol=1e-2, atol=1e-5)
    assert tele.supersteps == 30
    np.testing.assert_allclose(_gather(pg, r).sum(), 1.0, rtol=1e-4)


def test_pagerank_dangling_mass_conserved():
    """Bugfix regression: directed graph with sinks — ranks must sum to 1
    (dangling mass redistributes via teleport instead of evaporating), and
    the early-halt tolerance is a GLOBAL criterion, so every partition halts
    at the same superstep."""
    rng = np.random.default_rng(11)
    n, ne = 120, 400
    src = rng.integers(15, n, ne)           # vertices [0, 15) are pure sinks
    dst = rng.integers(0, n, ne)
    keep = src != dst
    from repro.gofs.formats import Graph
    g = Graph.from_edges(n, src[keep], dst[keep], directed=True)
    assert (g.out_degree == 0).any()
    pg = partition_graph(g, hash_partition(g, 4, seed=0), 4)
    r, _ = pagerank(pg, num_iters=50)
    np.testing.assert_allclose(_gather(pg, r).sum(), 1.0, rtol=1e-4)
    np.testing.assert_allclose(_gather(pg, r), _pagerank_oracle(g, 50),
                               rtol=1e-3, atol=1e-7)
    # global tol: converges and conserves mass with early halt too
    r2, tele2 = pagerank(pg, num_iters=200, tol=1e-10)
    assert tele2.supersteps < 200
    np.testing.assert_allclose(_gather(pg, r2).sum(), 1.0, rtol=1e-4)


def test_blockrank_converges_to_pagerank_fixpoint():
    g = road_grid(12, 12, drop_frac=0.05, seed=6)
    pg = partition_graph(g, bfs_grow_partition(g, 4, seed=0), 4)
    rb, tele, info = blockrank(pg, tol=1e-9, max_iters=100)
    rr = _pagerank_oracle(g, 200)
    np.testing.assert_allclose(_gather(pg, rb), rr, atol=1e-4)
    assert info["num_meta"] >= pg.num_parts  # at least one block per partition


def test_superstep_reduction_paper_claim():
    """Paper Fig 4(c): sub-graph centric takes FEWER supersteps than vertex
    centric, and is bounded by the meta-graph diameter (+constant)."""
    g = road_grid(20, 20, drop_frac=0.05, seed=7)  # high-diameter graph (RN)
    pg = partition_graph(g, bfs_grow_partition(g, 4, seed=0), 4)
    _, _, t_sub = connected_components(pg, mode="subgraph")
    _, _, t_vert = connected_components(pg, mode="vertex")
    assert t_sub.supersteps <= t_vert.supersteps
    dm = meta_diameter(pg)
    assert t_sub.supersteps <= dm + 3
    dv = vertex_diameter(g)
    assert t_vert.supersteps <= dv + 3
    assert t_vert.supersteps > t_sub.supersteps  # strict on high-diameter RN


def test_shard_map_backend_matches_local():
    g = road_grid(12, 12, drop_frac=0.06, seed=8)
    pg = partition_graph(g, bfs_grow_partition(g, 4, seed=0), 4)
    from repro.core import compat
    mesh = compat.make_mesh((1,), ("parts",))
    lab0, ncc0, t0 = connected_components(pg, mode="subgraph", backend="local")
    lab1, ncc1, t1 = connected_components(pg, mode="subgraph",
                                          backend="shard_map", mesh=mesh)
    assert np.array_equal(lab0, lab1)
    assert ncc0 == ncc1
    assert t0.supersteps == t1.supersteps
    d0, _ = sssp(pg, 3, backend="local")
    d1, _ = sssp(pg, 3, backend="shard_map", mesh=mesh)
    assert np.allclose(d0[pg.vmask], d1[pg.vmask])


def test_bounded_local_iters_still_correct():
    """Straggler mitigation: capping local sweep iterations trades supersteps
    for tail latency but must stay correct (beyond-paper, DESIGN.md §7)."""
    g = road_grid(14, 14, drop_frac=0.05, seed=9)
    pg = partition_graph(g, bfs_grow_partition(g, 4, seed=0), 4)
    ncc_true, _ = csgraph.connected_components(g.undirected_csr(), directed=False)
    _, ncc_full, t_full = connected_components(pg, mode="subgraph")
    _, ncc_cap, t_cap = connected_components(pg, mode="subgraph",
                                             max_local_iters=3)
    assert ncc_full == ncc_cap == ncc_true
    assert t_cap.supersteps >= t_full.supersteps


def test_bsp_checkpoint_restart(tmp_path):
    """Fault tolerance: kill the BSP run mid-way, restart from the last
    committed superstep snapshot, converge to the identical answer."""
    from repro.core import GopherEngine, SemiringProgram, init_max_vertex
    from repro.resilience.checkpoint import Checkpointer
    g = road_grid(16, 16, drop_frac=0.05, seed=11)
    pg = partition_graph(g, bfs_grow_partition(g, 4, seed=0), 4)

    prog = SemiringProgram(semiring="max_first", init_fn=init_max_vertex)
    ref_state, ref_tele = GopherEngine(pg, prog).run()

    # run with per-2-superstep checkpoints, but cap supersteps to "fail" early
    ck = Checkpointer(str(tmp_path))
    eng_fail = GopherEngine(pg, prog, max_supersteps=3)
    eng_fail.run(checkpointer=ck, checkpoint_every=2)
    assert ck.latest_step() is not None
    assert ck.latest_step() < ref_tele.supersteps  # genuinely mid-run

    # restart and finish
    eng2 = GopherEngine(pg, prog)
    state2, tele2 = eng2.run(checkpointer=ck, checkpoint_every=2, resume=True)
    assert np.array_equal(np.asarray(state2["x"]), np.asarray(ref_state["x"]))
    assert tele2.supersteps == ref_tele.supersteps


def test_checkpointed_run_telemetry_and_block_reuse(tmp_path):
    """Regression: checkpointed runs must reuse the engine's cached device
    graph block (not rebuild a second copy) and report REAL telemetry —
    message counts, per-superstep changed history, and the lockstep and
    chip-wait sweeps of the compact loop they step, like normal runs."""
    from repro.core import GopherEngine, SemiringProgram, init_max_vertex
    from repro.resilience.checkpoint import Checkpointer
    g = road_grid(14, 14, drop_frac=0.05, seed=12)
    pg = partition_graph(g, bfs_grow_partition(g, 4, seed=0), 4)
    prog = SemiringProgram(semiring="max_first", init_fn=init_max_vertex)
    ref_state, ref_tele = GopherEngine(pg, prog).run()

    eng = GopherEngine(pg, prog)
    gb_before = eng._graph_block()
    state, tele = eng.run(checkpointer=Checkpointer(str(tmp_path)),
                          checkpoint_every=3)
    assert eng._graph_block() is gb_before        # cached block reused
    assert np.array_equal(np.asarray(state["x"]), np.asarray(ref_state["x"]))
    assert tele.supersteps == ref_tele.supersteps
    assert tele.messages_sent == ref_tele.messages_sent >= 0
    assert np.array_equal(tele.changed_hist, ref_tele.changed_hist)
    assert np.array_equal(tele.local_iters, ref_tele.local_iters)
    _, compact = GopherEngine(pg, prog, exchange="compact").run()
    assert tele.lockstep_sweeps == compact.lockstep_sweeps is not None
    assert tele.chip_wait_sweeps == compact.chip_wait_sweeps is not None
