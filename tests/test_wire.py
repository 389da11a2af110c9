"""Gopher Wire: frontier-compacted sparse exchange + zero-repack versioned
graph blocks.

Parity contract under test:
  - the compact exchange is BIT-IDENTICAL to the dense mailbox (the packed
    prefix reconstructs the exact dense slot array) for CC / SSSP /
    PageRank on both backends, while shipping fewer slots;
  - a zero-repack-patched graph block produces the same results as a cold
    host_graph_block of the same PartitionedGraph (bit-identical for
    idempotent ⊕; PageRank's float sums may differ in feed-list order, so
    allclose there), across random delta chains (hypothesis);
  - the landmark tier survives deltas per-landmark: provably-untouched
    vectors are kept, stale ones resume from their fixpoints and match a
    cold rebuild exactly.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (GopherEngine, PageRankProgram, SemiringProgram,
                        compat, device_block, host_graph_block,
                        init_max_vertex, make_sssp_init)
from repro.core import messages as msg
from repro.gofs import (EdgeDelta, apply_delta, bfs_grow_partition,
                        powerlaw_social, road_grid)
from repro.gofs.formats import PAD, partition_graph
from repro.gofs.generators import random_graph
from repro.gofs.partition import hash_partition
from repro.kernels import ops


@pytest.fixture(scope="module")
def road():
    g = road_grid(22, 22, drop_frac=0.08, seed=3, weighted=True)
    pg = partition_graph(g, bfs_grow_partition(g, 4, seed=0), 4)
    return g, pg


def _mesh1():
    return compat.make_mesh((1,), ("parts",))


# ---------------- compaction plan: oracle vs Pallas, edge cases ----------------

@pytest.mark.parametrize("shape,density", [((5, 9), 0.3), ((8, 64), 0.05),
                                           ((3, 17), 1.0), ((4, 24), 0.0),
                                           ((1, 1), 0.5)])
def test_compact_plan_pallas_matches_ref(shape, density):
    rng = np.random.default_rng(hash(shape) % 2**31)
    act = jnp.asarray(rng.random(shape) < density)
    ref = ops.outbox_compact_plan(act, backend="jnp")
    pal = ops.outbox_compact_plan(act, backend="pallas", block_r=4,
                                   interpret=True)
    for a, b, name in zip(ref, pal, ["pfwd", "pinv", "counts"]):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name


def test_compact_plan_invariants():
    rng = np.random.default_rng(7)
    act = rng.random((6, 40)) < 0.4
    pfwd, pinv, counts = map(np.asarray,
                             ops.outbox_compact_plan(jnp.asarray(act),
                                                     backend="jnp"))
    assert np.array_equal(counts, act.sum(1))
    for r in range(act.shape[0]):
        c = counts[r]
        # forward: ascending active slot ids in the prefix, PAD after
        assert np.array_equal(pfwd[r, :c], np.flatnonzero(act[r]))
        assert np.all(pfwd[r, c:] == PAD)
        # inverse: active slots point at their prefix position
        assert np.array_equal(np.flatnonzero(pinv[r] != PAD),
                              np.flatnonzero(act[r]))
        assert np.array_equal(pinv[r][act[r]], np.arange(c))


# ---------------- pack/unpack round trip vs the dense outbox ----------------

def test_compact_roundtrip_matches_dense_outbox(road):
    g, pg = road
    gb = host_graph_block(pg)
    rng = np.random.default_rng(0)
    r_max = pg.r_max
    vals = jnp.asarray(rng.uniform(0.0, 9.0, r_max).astype(np.float32))
    send = jnp.asarray(rng.random(r_max) < 0.3)
    for p in range(pg.num_parts):
        ob = jnp.asarray(gb["ob_inv"][p])
        dense = msg.build_outbox_gather(vals, send, ob, pg.num_parts,
                                        pg.mailbox_cap, "min")
        pvals, pinv, counts = msg.build_outbox_compact(
            vals, send, ob, pg.num_parts, pg.mailbox_cap, "min")
        rebuilt = msg.unpack_slots(pvals, pinv, "min")
        assert np.array_equal(np.asarray(rebuilt), np.asarray(dense))
        # payload really is the frontier's slots, prefix-packed
        assert int(jnp.sum(counts)) <= int(jnp.sum(send))
        has = np.asarray(pinv) != PAD
        assert np.array_equal(has.sum(1), np.asarray(counts))


def test_compact_roundtrip_batched(road):
    g, pg = road
    gb = host_graph_block(pg)
    rng = np.random.default_rng(1)
    Q, r_max = 3, pg.r_max
    vals = jnp.asarray(rng.uniform(0.0, 9.0, (r_max, Q)).astype(np.float32))
    send = jnp.asarray(rng.random((r_max, Q)) < 0.3)
    for p in range(pg.num_parts):
        ob = jnp.asarray(gb["ob_inv"][p])
        dense = msg.build_outbox_gather_batched(vals, send, ob, pg.num_parts,
                                                pg.mailbox_cap, "min")
        pvals, pinv, _ = msg.build_outbox_compact_batched(
            vals, send, ob, pg.num_parts, pg.mailbox_cap, "min")
        rebuilt = msg.unpack_slots_batched(pvals, pinv, "min")
        assert np.array_equal(np.asarray(rebuilt), np.asarray(dense))


# ---------------- engine: compact == dense, both backends, 3 programs --------

def _programs(pg, n):
    return [
        ("cc", SemiringProgram(semiring="max_first", init_fn=init_max_vertex),
         "x"),
        ("sssp", SemiringProgram(
            semiring="min_plus",
            init_fn=make_sssp_init(int(pg.part_of[0]), int(pg.local_of[0]))),
         "x"),
        ("pagerank", PageRankProgram(n_global=n, num_iters=12), "r"),
    ]


@pytest.mark.parametrize("backend", ["local", "shard_map"])
def test_compact_exchange_bit_identical_to_dense(backend, road):
    g, pg = road
    mesh = _mesh1() if backend == "shard_map" else None
    for name, prog, key in _programs(pg, g.n):
        sd, td = GopherEngine(pg, prog, backend=backend, mesh=mesh,
                              exchange="dense").run()
        sc, tc = GopherEngine(pg, prog, backend=backend, mesh=mesh,
                              exchange="compact").run()
        assert np.array_equal(np.asarray(sd[key]), np.asarray(sc[key])), name
        assert td.supersteps == tc.supersteps
        # wire telemetry: dense ships P²·cap every round; compact tracks the
        # frontier and can never ship more
        assert tc.wire_slots <= td.wire_slots
        assert tc.bytes_on_wire < td.bytes_on_wire
        # round-indexed: supersteps + 1 entries, slot 0 = the inbox prime,
        # and the histogram fully accounts the run's shipped slots
        assert tc.wire_hist is not None
        assert len(tc.wire_hist) == tc.supersteps + 1
        assert int(np.sum(tc.wire_hist)) == tc.wire_slots
        assert int(np.sum(td.wire_hist)) == td.wire_slots
        P, cap = pg.num_parts, pg.mailbox_cap
        assert np.all(np.asarray(td.wire_hist) == P * P * cap)
        assert np.all(np.asarray(tc.wire_hist) <= P * P * cap)


def test_compact_exchange_query_batched(road):
    """Batched serving programs run the compacted exchange too: Q-lane
    results must match the dense exchange lane-for-lane."""
    from repro.serving.batched import (BatchedSemiringProgram,
                                      gather_query_results, sssp_query_init)
    g, pg = road
    sources = [0, 5, g.n // 2, g.n - 1]
    prog = BatchedSemiringProgram(semiring="min_plus",
                                  num_queries=len(sources))
    extra = {"qinit": sssp_query_init(pg, sources)}
    sd, td = GopherEngine(pg, prog, exchange="dense").run_queries(extra=extra)
    sc, tc = GopherEngine(pg, prog, exchange="compact").run_queries(extra=extra)
    assert np.array_equal(gather_query_results(pg, sd["x"]),
                          gather_query_results(pg, sc["x"]))
    assert np.array_equal(td.query_supersteps, tc.query_supersteps)
    assert tc.wire_slots <= td.wire_slots


def test_quiesced_run_ships_zero_slots(road):
    """VoteToHalt on the wire: resuming a converged fixpoint with an empty
    frontier must ship NOTHING (the whole point of the sparse exchange)."""
    from repro.algorithms import bfs
    g, pg = road
    d_prev, _ = bfs(pg, 3)
    prog = SemiringProgram(semiring="min_plus", resume=True)
    eng = GopherEngine(pg, prog, exchange="compact")
    x0 = np.where(pg.vmask, d_prev, np.inf).astype(np.float32)
    _, tele = eng.run(extra={"x0": x0,
                             "frontier0": np.zeros_like(pg.vmask)})
    assert tele.supersteps == 1
    assert tele.wire_slots == 0
    assert tele.messages_sent == 0


# ---------------- zero-repack blocks: cold == patched ----------------

def _run_all(pg, gb_dev, n):
    out = {}
    for name, prog, key in _programs(pg, n):
        state, _ = GopherEngine(pg, prog, gb=gb_dev).run()
        out[name] = np.asarray(state[key])
    return out


@pytest.mark.parametrize("backend", ["local", "shard_map"])
def test_patched_block_matches_cold_block(backend, road):
    g, pg0 = road
    mesh = _mesh1() if backend == "shard_map" else None
    rng = np.random.default_rng(4)
    iu = rng.integers(0, g.n, 60)
    iv = rng.integers(0, g.n, 60)
    keep = iu != iv
    iw = rng.uniform(0.5, 5.0, keep.sum()).astype(np.float32)
    res = apply_delta(pg0, EdgeDelta.inserts(iu[keep], iv[keep], iw),
                      directed=False, block=host_graph_block(pg0))
    pg1 = res.pg
    assert res.block is not None
    cold = host_graph_block(pg1)
    for name, prog, key in _programs(pg1, g.n):
        s_cold, _ = GopherEngine(pg1, prog, backend=backend, mesh=mesh,
                                 gb=device_block(cold)).run()
        s_pat, _ = GopherEngine(pg1, prog, backend=backend, mesh=mesh,
                                gb=device_block(res.block)).run()
        a, b = np.asarray(s_cold[key]), np.asarray(s_pat[key])
        if name == "pagerank":   # ⊕ = float sum: feed order may differ
            assert np.allclose(a, b, rtol=1e-6, atol=1e-9), name
        else:
            assert np.array_equal(a, b), name


def test_patched_block_chain_with_removals_and_hubs():
    """A powerlaw graph (hub promotion on both block sides) through a chain
    of mixed insert/remove deltas; every version's patched block must agree
    with a cold pack of the same graph."""
    g = powerlaw_social(500, m=4, seed=2)
    pg = partition_graph(g, bfs_grow_partition(g, 4, seed=0), 4)
    hb = host_graph_block(pg)
    rng = np.random.default_rng(5)
    for v in range(1, 5):
        # removals sampled from CURRENT remote+local edges via the pg layout
        srcs, dsts = [], []
        for p in range(pg.num_parts):
            m = pg.re_src[p] != PAD
            if m.any():
                srcs.append(pg.global_id[p][pg.re_src[p][m]])
                dsts.append(pg.global_id[pg.re_dst_part[p][m],
                                         pg.re_dst_local[p][m]])
        el = np.stack([np.concatenate(srcs), np.concatenate(dsts)], 1)
        el = el[el[:, 0] < el[:, 1]]
        pick = rng.choice(el.shape[0], min(8, el.shape[0]), replace=False)
        rs, rd = el[pick, 0], el[pick, 1]
        iu = rng.integers(0, g.n, 20)
        iv = (iu + rng.integers(1, g.n, 20)) % g.n
        # keep the batch well-formed: validate_delta rejects an edge that is
        # both inserted and removed in one delta (rs < rd already canonical)
        ok = ~np.isin(np.minimum(iu, iv) * g.n + np.maximum(iu, iv),
                      rs * g.n + rd)
        delta = EdgeDelta.of(
            insert_src=iu[ok], insert_dst=iv[ok],
            insert_wgt=rng.uniform(0.5, 4.0, 20).astype(np.float32)[ok],
            remove_src=rs, remove_dst=rd)
        res = apply_delta(pg, delta, directed=False, block=hb)
        pg, hb = res.pg, res.block
        assert pg.version == v
        cold = host_graph_block(pg)
        got = _run_all(pg, device_block(hb), g.n)
        want = _run_all(pg, device_block(cold), g.n)
        assert np.array_equal(want["cc"], got["cc"]), v
        assert np.array_equal(want["sssp"], got["sssp"]), v
        assert np.allclose(want["pagerank"], got["pagerank"],
                           rtol=1e-6, atol=1e-9), v


# ---------------- landmark tier: per-landmark survival + exact refresh -------

def test_landmark_stale_filter_and_refresh(road):
    from repro.serving.cache import LandmarkCache
    g, pg0 = road
    lc0 = LandmarkCache.build(pg0, num_landmarks=4)

    # an insert that can't relax any landmark vector: all vectors survive
    hb = host_graph_block(pg0)
    d_noop = EdgeDelta.inserts([0], [5], [1e6])
    res = apply_delta(pg0, d_noop, directed=False, block=hb)
    assert not lc0.stale_landmarks(d_noop).any()
    lc1 = lc0.refresh(res.pg, res, d_noop, gb=device_block(res.block))
    assert lc1.refreshed_landmarks == 0
    assert np.array_equal(lc1.dist, lc0.dist)
    assert lc1.graph_version == 1

    # a shortcut insert: stale subset resumes and matches a cold rebuild
    d_cut = EdgeDelta.inserts([0], [g.n - 1], [0.25])
    res2 = apply_delta(res.pg, d_cut, directed=False, block=res.block)
    lc2 = lc1.refresh(res2.pg, res2, d_cut, gb=device_block(res2.block))
    cold = LandmarkCache.build(res2.pg, landmarks=lc2.landmarks)
    assert np.array_equal(lc2.dist, cold.dist)

    # removals invalidate everything (paths may LENGTHEN) but the resumed
    # vectors still match a cold rebuild bit-for-bit
    # a removal that MISSES (edge not present) applies nothing: with the
    # realized count from the apply, every vector survives untouched
    d_miss = EdgeDelta.removes([0], [g.n - 2])
    res_m = apply_delta(res2.pg, d_miss, directed=False, block=res2.block)
    assert res_m.stats["removed"] == 0 and res_m.stats["remove_missed"] > 0
    lc_m = lc2.refresh(res_m.pg, res_m, d_miss, gb=device_block(res_m.block))
    assert lc_m.refreshed_landmarks == 0
    assert np.array_equal(lc_m.dist, lc2.dist)
    res2, lc2 = res_m, lc_m

    src = int(pg0.global_id[0][pg0.vmask[0]][0])
    j = np.flatnonzero(pg0.nbr[0, int(pg0.local_of[src])] != PAD)
    dst = int(pg0.global_id[0][pg0.nbr[0, int(pg0.local_of[src]), j[0]]])
    d_rm = EdgeDelta.removes([dst], [src])
    assert lc2.stale_landmarks(d_rm).all()
    res3 = apply_delta(res2.pg, d_rm, directed=False, block=res2.block)
    lc3 = lc2.refresh(res3.pg, res3, d_rm, gb=device_block(res3.block))
    assert lc3.refreshed_landmarks == lc3.num_landmarks
    cold3 = LandmarkCache.build(res3.pg, landmarks=lc3.landmarks)
    assert np.array_equal(lc3.dist, cold3.dist)


def test_incremental_sssp_batched_bit_identical(road):
    from repro.algorithms import incremental_sssp_batched
    from repro.serving.cache import LandmarkCache
    g, pg0 = road
    lm = np.asarray([0, 7, g.n // 3, g.n - 2], np.int64)
    prev = LandmarkCache.build(pg0, landmarks=lm).dist
    rng = np.random.default_rng(9)
    iu = rng.integers(0, g.n, 25)
    iv = rng.integers(0, g.n, 25)
    keep = iu != iv
    res = apply_delta(pg0, EdgeDelta.inserts(
        iu[keep], iv[keep],
        rng.uniform(0.2, 3.0, keep.sum()).astype(np.float32)),
        directed=False)
    got, tele = incremental_sssp_batched(res.pg, lm, prev, res)
    want = LandmarkCache.build(res.pg, landmarks=lm).dist
    assert np.array_equal(got, want)
    assert tele.query_supersteps is not None


def test_cold_block_keeps_spilled_entries_after_shrink():
    """Regression: a row that grew past w_lo (entry parked at a column >=
    w_lo) and then shrank back under it by removals must still bin as a hub
    in a COLD build — truncating it to [:w_lo] silently dropped the spilled
    neighbors."""
    g = road_grid(16, 16, drop_frac=0.05, seed=9)
    pg = partition_graph(g, bfs_grow_partition(g, 2, seed=0), 2)
    hb = host_graph_block(pg)
    w_lo = hb["nbr_lo"].shape[2]
    # pick a local-heavy vertex and stuff its in-row past w_lo with
    # same-partition neighbors, then remove early ones so degree <= w_lo
    p, v = 0, int(np.flatnonzero(pg.vmask[0])[0])
    tgt = int(pg.global_id[p][v])
    same = [int(x) for x in pg.global_id[p][pg.vmask[p]]
            if int(x) != tgt][:w_lo + 2]
    cur = apply_delta(pg, EdgeDelta.inserts([tgt] * len(same), same),
                      directed=False)
    old = [int(cur.pg.global_id[p][n]) for n in
           cur.pg.nbr[p, v][:3] if n != PAD]
    cur2 = apply_delta(cur.pg, EdgeDelta.removes([tgt] * len(old), old),
                       directed=False)
    pg2 = cur2.pg
    row = pg2.nbr[p, v]
    assert np.any(row[w_lo:] != PAD), "fixture must spill past w_lo"
    assert (row != PAD).sum() <= w_lo, "fixture must shrink under w_lo"
    cold = host_graph_block(pg2)
    # every live in-edge of the row must appear in exactly one bin
    live = set(row[row != PAD].tolist())
    hrow = np.flatnonzero(cold["adj_hub_idx"][p] == v)
    got = set(cold["adj_hub_nbr"][p, hrow[0]][
        cold["adj_hub_nbr"][p, hrow[0]] != PAD].tolist()) if hrow.size \
        else set(cold["nbr_lo"][p, v][cold["nbr_lo"][p, v] != PAD].tolist())
    assert got == live


def test_patch_hub_promotion_when_feed_widths_equal():
    """Regression: promoting a destination vertex to hub receiver when the
    hub feed width equals m_lo must widen ib_hub instead of writing out of
    bounds (IndexError killed the zero-repack ingest path)."""
    g = random_graph(60, avg_degree=3.0, seed=28, weighted=True)
    pg = partition_graph(g, hash_partition(g, 3, seed=28), 3)
    hb = host_graph_block(pg)
    m_lo, m_hi = hb["ib_lo"].shape[2], hb["ib_hub"].shape[2]
    # drive one vertex's remote in-feed past m_lo: insert edges from
    # other-partition sources (directed so only (u -> tgt) lands remotely)
    tgt = int(pg.global_id[0][np.flatnonzero(pg.vmask[0])[0]])
    others = [int(x) for p in (1, 2)
              for x in pg.global_id[p][pg.vmask[p]]][:m_hi + 3]
    res = apply_delta(pg, EdgeDelta.inserts(others, [tgt] * len(others)),
                      directed=True, block=hb)
    prog = SemiringProgram(semiring="min_plus",
                           init_fn=make_sssp_init(int(res.pg.part_of[tgt]),
                                                  int(res.pg.local_of[tgt])))
    s_cold, _ = GopherEngine(res.pg, prog,
                             gb=device_block(host_graph_block(res.pg))).run()
    s_pat, _ = GopherEngine(res.pg, prog, gb=device_block(res.block)).run()
    assert np.array_equal(np.asarray(s_cold["x"]), np.asarray(s_pat["x"]))


# The hypothesis property over random delta batches lives in
# tests/test_property.py (test_random_delta_patched_block_parity) with the
# repo's importorskip convention — this file must run without hypothesis.
