"""Gopher Delta: temporal GoFS (edge-delta batches, versioned store),
frontier-driven incremental re-convergence (bit-identical to cold runs on
both backends), frontier-masked kernels, and version-keyed serving caches."""
import numpy as np
import pytest
import scipy.sparse as sp

import jax.numpy as jnp

from repro.algorithms import (bfs, connected_components,
                              incremental_bfs,
                              incremental_connected_components,
                              incremental_sssp, sssp)
from repro.core import GopherEngine, SemiringProgram, compat
from repro.gofs import (EdgeDelta, TemporalStore, apply_delta,
                        bfs_grow_partition, powerlaw_social, road_grid)
from repro.gofs.formats import PAD, Graph, partition_graph
from repro.kernels import ops


def _gather(pg, per_part):
    out = np.zeros(pg.n_global, per_part.dtype)
    for p in range(pg.num_parts):
        m = pg.vmask[p]
        out[pg.global_id[p][m]] = per_part[p][m]
    return out


def _global_csr(pg):
    """Reassemble the global in-edge CSR from the partitioned layout (local
    ELL + remote edges) — the semantic content apply_delta must preserve."""
    rows, cols, vals = [], [], []
    for p in range(pg.num_parts):
        vv, jj = np.nonzero(pg.nbr[p] != PAD)
        keep = pg.vmask[p][vv]
        vv, jj = vv[keep], jj[keep]
        rows.append(pg.global_id[p][vv])
        cols.append(pg.global_id[p][pg.nbr[p][vv, jj]])
        vals.append(pg.wgt[p][vv, jj])
        m = pg.re_src[p] != PAD
        rows.append(pg.global_id[pg.re_dst_part[p][m], pg.re_dst_local[p][m]])
        cols.append(pg.global_id[p][pg.re_src[p][m]])
        vals.append(pg.re_wgt[p][m])
    return sp.csr_matrix((np.concatenate(vals),
                          (np.concatenate(rows), np.concatenate(cols))),
                         shape=(pg.n_global, pg.n_global))


def _edge_list(g):
    a = g.csr().tocoo()           # row v = dst, col = src
    return a.col, a.row, a.data.astype(np.float32)


@pytest.fixture(scope="module")
def road():
    g = road_grid(22, 22, drop_frac=0.08, seed=3)
    pg = partition_graph(g, bfs_grow_partition(g, 4, seed=0), 4)
    return g, pg


# ---------------- apply_delta vs full GoFS rebuild ----------------

def test_apply_delta_matches_full_rebuild(road):
    g, pg0 = road
    rng = np.random.default_rng(0)
    n = g.n
    iu = rng.integers(0, n, 40)
    iv = rng.integers(0, n, 40)
    keep = iu != iv
    iu, iv = iu[keep], iv[keep]
    iw = rng.uniform(1.0, 5.0, iu.size).astype(np.float32)
    res = apply_delta(pg0, EdgeDelta.inserts(iu, iv, iw), directed=False)
    assert res.pg.version == 1
    assert res.stats["inserted"] + res.stats["weight_updated"] == 2 * iu.size

    src0, dst0, w0 = _edge_list(g)
    g1 = Graph.from_edges(n, np.concatenate([src0, iu]),
                          np.concatenate([dst0, iv]),
                          np.concatenate([w0, iw]), directed=False)
    pg1_cold = partition_graph(g1, bfs_grow_partition(g, 4, seed=0), 4)
    assert (_global_csr(res.pg) != _global_csr(pg1_cold)).nnz == 0
    # sub-graph structure rediscovered where topology changed
    assert np.array_equal(np.sort(res.pg.num_subgraphs),
                          np.sort(pg1_cold.num_subgraphs))
    # dirty seeds: exactly the inserted sources (both directions, undirected)
    marked = {int(res.pg.global_id[p][v])
              for p, v in zip(*np.nonzero(res.dirty_insert))}
    assert marked == set(iu.tolist()) | set(iv.tolist())


def test_apply_delta_removals_and_weight_updates(road):
    g, pg0 = road
    src0, dst0, w0 = _edge_list(g)
    und = src0 < dst0
    pick = np.flatnonzero(und)[:17]
    res = apply_delta(pg0, EdgeDelta.removes(src0[pick], dst0[pick]),
                      directed=False)
    assert res.stats["removed"] == 2 * pick.size
    assert res.stats["remove_missed"] == 0
    a1 = _global_csr(res.pg)
    assert a1.nnz == g.nnz - 2 * pick.size
    # re-inserting one removed edge with a higher-then-lower weight applies
    # the MIN duplicate policy and recycles the freed storage
    u, v = int(src0[pick[0]]), int(dst0[pick[0]])
    res2 = apply_delta(res.pg, EdgeDelta.inserts([u], [v], [9.0]))
    res3 = apply_delta(res2.pg, EdgeDelta.inserts([u], [v], [2.0]))
    a3 = _global_csr(res3.pg)
    assert a3[v, u] == 2.0 and a3[u, v] == 2.0
    assert res3.pg.version == 3
    # removing a non-existent edge is counted, not fatal
    res4 = apply_delta(res3.pg, EdgeDelta.removes([u], [u + 1 if u + 1 != v
                                                        else u + 2]))
    assert res4.stats["remove_missed"] >= 1


def test_out_degree_tracks_deltas(road):
    g, pg0 = road
    rng = np.random.default_rng(1)
    iu = rng.integers(0, g.n, 25)
    iv = (iu + 37) % g.n
    res = apply_delta(pg0, EdgeDelta.inserts(iu, iv), directed=False)
    src0, dst0, w0 = _edge_list(g)
    g1 = Graph.from_edges(g.n, np.concatenate([src0, iu]),
                          np.concatenate([dst0, iv]),
                          np.concatenate([w0, np.ones(iu.size, np.float32)]),
                          directed=False)
    assert np.array_equal(_gather(res.pg, res.pg.out_degree),
                          g1.out_degree)


# ---------------- versioned store ----------------

def test_temporal_store_roundtrip(tmp_path, road):
    g, pg0 = road
    st = TemporalStore(str(tmp_path))
    st.build("g", g, bfs_grow_partition(g, 4, seed=0), 4)
    assert st.latest_version("g") == 0
    d1 = EdgeDelta.inserts([0, 5], [99, 200])
    d2 = EdgeDelta.removes([0], [99])
    assert st.append_delta("g", d1) == 1
    assert st.append_delta("g", d2) == 2
    pg2 = st.materialize("g")
    assert pg2.version == 2
    # replay == in-memory chain
    mem = apply_delta(apply_delta(pg0, d1).pg, d2).pg
    assert (_global_csr(pg2) != _global_csr(mem)).nnz == 0
    # historical version still reachable
    pg1 = st.materialize("g", version=1)
    assert pg1.version == 1
    assert (_global_csr(pg1) != _global_csr(apply_delta(pg0, d1).pg)).nnz == 0


# ---------------- incremental == cold, bit-identical, both backends ----------

@pytest.mark.parametrize("backend", ["local", "shard_map"])
def test_incremental_insert_bit_identical(backend, road):
    g, pg0 = road
    mesh = compat.make_mesh((1,), ("parts",)) if backend == "shard_map" else None
    rng = np.random.default_rng(2)
    num = max(1, (g.nnz // 2) // 100)      # the 1% batch of the issue spec
    iu = rng.integers(0, g.n, num)
    iv = rng.integers(0, g.n, num)
    keep = iu != iv
    res = apply_delta(pg0, EdgeDelta.inserts(iu[keep], iv[keep]),
                      directed=False)
    pg1 = res.pg

    lab_prev, _, _ = connected_components(pg0, backend=backend, mesh=mesh)
    lab_cold, ncc_cold, _ = connected_components(pg1, backend=backend,
                                                 mesh=mesh)
    lab_inc, ncc_inc, t_inc = incremental_connected_components(
        pg1, lab_prev, res, backend=backend, mesh=mesh)
    assert np.array_equal(lab_cold, lab_inc) and ncc_cold == ncc_inc

    d_prev, _ = bfs(pg0, 3, backend=backend, mesh=mesh)
    d_cold, t_cold = bfs(pg1, 3, backend=backend, mesh=mesh)
    d_inc, t_inc = incremental_bfs(pg1, 3, d_prev, res, backend=backend,
                                   mesh=mesh)
    assert np.array_equal(d_cold, d_inc)
    # the incremental run did less local work than the cold run
    assert t_inc.local_iters.sum() < t_cold.local_iters.sum()


@pytest.mark.parametrize("backend", ["local", "shard_map"])
def test_incremental_removal_bit_identical(backend):
    g = road_grid(18, 18, drop_frac=0.04, seed=5, weighted=True)
    pg0 = partition_graph(g, bfs_grow_partition(g, 4, seed=0), 4)
    mesh = compat.make_mesh((1,), ("parts",)) if backend == "shard_map" else None
    src0, dst0, _ = _edge_list(g)
    und = np.flatnonzero(src0 < dst0)
    rng = np.random.default_rng(6)
    pick = rng.choice(und, 15, replace=False)
    delta = EdgeDelta.of(insert_src=[1, 2], insert_dst=[200, 250],
                         insert_wgt=[2.5, 4.0],
                         remove_src=src0[pick], remove_dst=dst0[pick])
    res = apply_delta(pg0, delta, directed=False)
    pg1 = res.pg

    d_prev, _ = sssp(pg0, 0)
    d_cold, _ = sssp(pg1, 0, backend=backend, mesh=mesh)
    d_inc, _ = incremental_sssp(pg1, 0, d_prev, res, backend=backend,
                                mesh=mesh)
    assert np.array_equal(d_cold, d_inc)

    lab_prev, _, _ = connected_components(pg0)
    lab_cold, ncc_cold, _ = connected_components(pg1, backend=backend,
                                                 mesh=mesh)
    lab_inc, ncc_inc, _ = incremental_connected_components(
        pg1, lab_prev, res, backend=backend, mesh=mesh)
    assert np.array_equal(lab_cold, lab_inc) and ncc_cold == ncc_inc


def test_incremental_noop_delta_halts_immediately(road):
    """A delta that changes nothing reachable quiesces in one superstep."""
    g, pg0 = road
    src0, dst0, w0 = _edge_list(g)
    # re-insert an existing edge with its existing weight: weight_update no-op
    res = apply_delta(pg0, EdgeDelta.inserts([src0[0]], [dst0[0]],
                                             [float(w0[0])]))
    d_prev, _ = bfs(pg0, 3)
    d_inc, tele = incremental_bfs(res.pg, 3, d_prev, res)
    assert np.array_equal(d_inc, d_prev)
    assert tele.supersteps <= 2
    assert tele.local_iters.sum() <= pg0.num_parts  # no real sweep work


# ---------------- frontier-masked kernels ----------------

@pytest.mark.parametrize("semiring", ["min_plus", "max_first"])
def test_frontier_sweep_matches_full_on_active_rows(semiring):
    rng = np.random.default_rng(0)
    v, d = 64, 8
    nbr = rng.integers(0, v, (v, d)).astype(np.int32)
    nbr[rng.random((v, d)) < 0.3] = PAD
    wgt = rng.uniform(0.1, 2.0, (v, d)).astype(np.float32)
    x = rng.uniform(0.0, 5.0, v).astype(np.float32)
    frontier = rng.random(v) < 0.25
    y_full = ops.semiring_spmv(jnp.asarray(x), jnp.asarray(nbr),
                               jnp.asarray(wgt), semiring, backend="jnp")
    y_m, act = ops.semiring_spmv_frontier(
        jnp.asarray(x), jnp.asarray(frontier), jnp.asarray(nbr),
        jnp.asarray(wgt), semiring, backend="jnp")
    act = np.asarray(act)
    valid = nbr != PAD
    act_ref = np.any(valid & frontier[np.where(valid, nbr, 0)], axis=1)
    assert np.array_equal(act, act_ref)
    ident = np.inf if semiring == "min_plus" else -np.inf
    assert np.array_equal(np.asarray(y_m)[act], np.asarray(y_full)[act])
    assert np.all(np.asarray(y_m)[~act] == ident)
    # pallas interpret path agrees with the jnp oracle
    y_p, act_p = ops.semiring_spmv_frontier(
        jnp.asarray(x), jnp.asarray(frontier), jnp.asarray(nbr),
        jnp.asarray(wgt), semiring, backend="pallas", block_v=16,
        interpret=True)
    assert np.array_equal(np.asarray(y_p), np.asarray(y_m))
    assert np.array_equal(np.asarray(act_p), act)


@pytest.mark.parametrize("semiring", ["min_plus", "max_first"])
def test_binned_frontier_sweep_matches_full(semiring):
    g = powerlaw_social(400, m=4, seed=2)
    pg = partition_graph(g, bfs_grow_partition(g, 4, seed=0), 4)
    from repro.core.engine import graph_block
    gb = graph_block(pg)
    rng = np.random.default_rng(3)
    Q = 3
    x = jnp.asarray(rng.uniform(0, 5, (pg.v_max, Q)).astype(np.float32))
    f = jnp.asarray(rng.random((pg.v_max, Q)) < 0.3)
    for p in range(pg.num_parts):
        y_full = ops.binned_ell_spmv_multi(
            x, gb["nbr_lo"][p], gb["wgt_lo"][p], gb["adj_hub_idx"][p],
            gb["adj_hub_nbr"][p], gb["adj_hub_wgt"][p], semiring)
        y_m = ops.binned_ell_spmv_multi_frontier(
            x, f, gb["nbr_lo"][p], gb["wgt_lo"][p], gb["adj_hub_idx"][p],
            gb["adj_hub_nbr"][p], gb["adj_hub_wgt"][p], semiring)
        valid = np.asarray(gb["nbr"][p]) != PAD
        fq = np.asarray(f)
        act = np.any(valid[:, :, None]
                     & fq[np.where(valid, np.asarray(gb["nbr"][p]), 0), :],
                     axis=1)
        ident = np.inf if semiring == "min_plus" else -np.inf
        assert np.array_equal(np.asarray(y_m)[act], np.asarray(y_full)[act])
        assert np.all(np.asarray(y_m)[~act] == ident)


def test_frontier_quiesced_partition_runs_zero_sweeps(road):
    """Engine-level VoteToHalt: once converged, a re-run seeded with an
    empty frontier must do zero local iterations and halt in one superstep."""
    g, pg = road
    d_prev, _ = bfs(pg, 3)
    prog = SemiringProgram(semiring="min_plus", resume=True)
    eng = GopherEngine(pg, prog)
    x0 = np.where(pg.vmask, d_prev, np.inf).astype(np.float32)
    state, tele = eng.run(extra={
        "x0": x0, "frontier0": np.zeros_like(pg.vmask)})
    assert tele.supersteps == 1
    assert tele.local_iters.sum() == 0
    assert np.array_equal(np.asarray(state["x"]), x0)


# ---------------- serving: version-keyed invalidation ----------------

def test_service_version_keyed_cache_invalidation(road):
    from repro.serving import GraphQueryService
    g, pg = road
    svc = GraphQueryService({"road": pg}, max_batch=8)
    svc.enable_landmarks("road", num_landmarks=4)
    r1 = svc.query("bfs", "road", 0)
    assert svc.query("bfs", "road", 0).cached
    lm_v0 = svc.landmark_caches["road"].graph_version

    svc.apply_delta("road", EdgeDelta.inserts([0], [g.n - 1]),
                    rebuild_landmarks=True)
    assert svc.graphs["road"].version == 1
    # stale entries evicted eagerly; fresh query recomputed on the new graph
    r2 = svc.query("bfs", "road", 0)
    assert not r2.cached
    assert r2.result[g.n - 1] == 1.0
    assert r1.result[g.n - 1] != 1.0
    # landmark tier rebuilt at the new version
    assert svc.landmark_caches["road"].graph_version == 1 > lm_v0 == 0
    assert svc.cache.stats()["invalidations"] >= 1
    # the same query at the new version is cached independently
    assert svc.query("bfs", "road", 0).cached
