"""Gopher Hot gates: the fused superstep megakernel (kernels.megastep).

Parity contract under test — the same one the exchange stack already
promises: idempotent-⊕ programs (CC/BFS/SSSP, scalar and query-batched)
are BIT-IDENTICAL across the fused route, its Pallas embodiment
(interpret mode on CPU), the resident narrow-phase schedule, and the
staged dense/compact paths; PageRank (⊕ = sum) is allclose. Telemetry's
logical frontier observation (pair_slots / count_hist / messages_sent)
must match the compact path exactly so the tier-profile EWMAs keep
learning from fused runs, while wire_slots/bytes_on_wire are zero.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (GopherEngine, PageRankProgram, PhasedTierPlan,
                        SemiringProgram, graph_block, init_max_vertex,
                        make_sssp_init)
from repro.gofs import bfs_grow_partition, road_grid
from repro.gofs.formats import partition_graph
from repro.kernels import megastep as mega


@pytest.fixture(scope="module")
def road():
    g = road_grid(10, 11, drop_frac=0.06, seed=3, weighted=True)
    pg = partition_graph(g, bfs_grow_partition(g, 4, seed=0), 4)
    return g, pg


def _source(pg):
    return int(pg.part_of[0]), int(pg.local_of[0])


def _programs(pg):
    sp, sl = _source(pg)
    return {
        "cc": SemiringProgram(semiring="max_first", init_fn=init_max_vertex),
        "sssp": SemiringProgram(semiring="min_plus",
                                init_fn=make_sssp_init(sp, sl)),
    }


# ---------------- auto resolution ----------------

def test_auto_resolves_megastep_on_local(road):
    _, pg = road
    eng = GopherEngine(pg, _programs(pg)["cc"], exchange="auto")
    assert eng.exchange == "megastep"
    # bounded local fixpoints have no fused embodiment: auto keeps dense
    bounded = SemiringProgram(semiring="max_first", init_fn=init_max_vertex,
                              max_local_iters=1)
    assert GopherEngine(pg, bounded, exchange="auto").exchange == "dense"
    # fixed-iteration PageRank fuses; tolerance-halted stays dense
    pr = PageRankProgram(n_global=pg.n_global, num_iters=8)
    assert GopherEngine(pg, pr, exchange="auto").exchange == "megastep"
    pr_tol = PageRankProgram(n_global=pg.n_global, num_iters=8, tol=1e-6)
    assert GopherEngine(pg, pr_tol, exchange="auto").exchange == "dense"


def test_megastep_requires_eligible_program(road):
    _, pg = road
    bounded = SemiringProgram(semiring="max_first", init_fn=init_max_vertex,
                              max_local_iters=1)
    with pytest.raises(AssertionError, match="eligible"):
        GopherEngine(pg, bounded, exchange="megastep")


# ---------------- engine-level parity ----------------

def test_fused_bit_identity_and_telemetry(road):
    _, pg = road
    for name, prog in _programs(pg).items():
        s_ref, t_ref = GopherEngine(pg, prog, exchange="dense").run()
        _, t_cmp = GopherEngine(pg, prog, exchange="compact").run()
        s, t = GopherEngine(pg, prog, exchange="megastep").run()
        assert np.array_equal(np.asarray(s["x"]), np.asarray(s_ref["x"])), name
        assert t.supersteps == t_ref.supersteps, name
        assert np.array_equal(t.local_iters, t_ref.local_iters), name
        assert np.array_equal(t.changed_hist, t_ref.changed_hist), name
        # the LOGICAL frontier observation matches compact exactly ...
        assert np.array_equal(t.pair_slots, t_cmp.pair_slots), name
        assert np.array_equal(t.count_hist, t_cmp.count_hist), name
        assert t.messages_sent == t_cmp.messages_sent, name
        # ... but nothing ships through a routed buffer
        assert t.wire_slots == 0 and t.bytes_on_wire == 0, name


def test_pagerank_fused_allclose(road):
    _, pg = road
    prog = PageRankProgram(n_global=pg.n_global, num_iters=15)
    s_ref, t_ref = GopherEngine(pg, prog, exchange="dense").run()
    s, t = GopherEngine(pg, prog, exchange="megastep").run()
    assert t.supersteps == t_ref.supersteps
    np.testing.assert_allclose(np.asarray(s["r"]), np.asarray(s_ref["r"]),
                               rtol=1e-5, atol=1e-7)
    assert t.wire_slots == 0


def test_batched_queries_fused_parity(road):
    from repro.serving.batched import (QUERY_INIT_KEY, BatchedSemiringProgram,
                                       sssp_query_init)
    _, pg = road
    Q = 3
    prog = BatchedSemiringProgram(semiring="min_plus", num_queries=Q)
    extra = {QUERY_INIT_KEY: sssp_query_init(pg, [0, 7, 19])}
    s_ref, t_ref = GopherEngine(pg, prog,
                                exchange="compact").run_queries(extra=extra)
    s, t = GopherEngine(pg, prog,
                        exchange="megastep").run_queries(extra=extra)
    assert np.array_equal(np.asarray(s["x"]), np.asarray(s_ref["x"]))
    assert np.array_equal(t.query_supersteps, t_ref.query_supersteps)
    assert np.array_equal(t.pair_slots, t_ref.pair_slots)
    assert t.wire_slots == 0


def test_incremental_resume_rides_fused_route(road):
    """resume=True ships x0/frontier0 through ``extra`` — the merge branch
    of _gb_for_run (run-specific entries layered over the pre-composed
    mcm_* block). Parity vs the dense staged resume, and the quiesced
    resume must still halt in one superstep with zero sweeps."""
    _, pg = road
    sp, sl = _source(pg)
    fix, _ = GopherEngine(pg, SemiringProgram(
        semiring="min_plus", init_fn=make_sssp_init(sp, sl)),
        exchange="dense").run()
    x_fix = np.asarray(fix["x"])
    prog = SemiringProgram(semiring="min_plus", resume=True)
    # invalidate a patch of vertices and re-relax from the stale fixpoint
    x0 = np.where(pg.vmask, x_fix, np.inf).astype(np.float32)
    fr0 = np.zeros_like(pg.vmask)
    x0[1, :8] = np.inf
    fr0[1, :8] = True
    extra = {"x0": x0, "frontier0": fr0}
    s_ref, _ = GopherEngine(pg, prog, exchange="dense").run(extra=extra)
    eng = GopherEngine(pg, prog, exchange="megastep")
    s, t = eng.run(extra=extra)
    assert np.array_equal(np.asarray(s["x"]), np.asarray(s_ref["x"]))
    # quiesced resume: one superstep, zero local iterations, state unchanged
    s2, t2 = eng.run(extra={"x0": np.asarray(s["x"]),
                            "frontier0": np.zeros_like(pg.vmask)})
    assert t2.supersteps == 1
    assert t2.local_iters.sum() == 0
    assert np.array_equal(np.asarray(s2["x"]), np.asarray(s["x"]))


def test_checkpointed_run_falls_back_to_staged(road, tmp_path):
    from repro.training.checkpoint import Checkpointer
    _, pg = road
    prog = _programs(pg)["sssp"]
    s_ref, t_ref = GopherEngine(pg, prog, exchange="dense").run()
    eng = GopherEngine(pg, prog, exchange="megastep")
    s, t = eng.run(checkpointer=Checkpointer(str(tmp_path)),
                   checkpoint_every=2)
    assert np.array_equal(np.asarray(s["x"]), np.asarray(s_ref["x"]))
    assert t.supersteps == t_ref.supersteps
    assert eng.exchange == "megastep"   # the fallback must not stick


# ---------------- resident narrow-phase mode ----------------

def test_resident_mode_bit_identity(road):
    _, pg = road
    plan = PhasedTierPlan.from_graph(pg)
    for name, prog in _programs(pg).items():
        s_ref, _ = GopherEngine(pg, prog, exchange="dense").run()
        s, t = GopherEngine(pg, prog, exchange="megastep",
                            tier_plan=plan).run()
        assert np.array_equal(np.asarray(s["x"]), np.asarray(s_ref["x"])), \
            name
        assert t.wire_slots == 0, name


def test_resident_pallas_kernel_quiescence_early_exit(road):
    """The multi-superstep resident launch (interpret mode on CPU) must
    exit on quiescence well before the iteration bound and land on the
    staged fixpoint bit for bit, with the BSP state contract intact."""
    _, pg = road
    sp, sl = _source(pg)
    prog = SemiringProgram(semiring="min_plus",
                           init_fn=make_sssp_init(sp, sl))
    gb = graph_block(pg)
    cm = mega.compose_mailbox(gb)
    st0 = jax.vmap(prog.init)(gb)
    x = st0["x"].reshape(-1)
    ch = st0["changed_v"].reshape(-1)
    fr = st0["frontier"].reshape(-1)
    x2, ch2, fr2, it, li = mega.resident_megastep_pallas(
        x, ch, fr, cm, "min_plus", max_steps=200, interpret=True)
    s_ref, _ = GopherEngine(pg, prog, exchange="dense").run()
    assert np.array_equal(np.asarray(x2).reshape(pg.num_parts, -1),
                          np.asarray(s_ref["x"]))
    assert int(it) < 200              # quiesced, not bound-limited
    assert not np.asarray(ch2).any()  # ... and the exit state shows it
    assert not np.asarray(fr2).any()


def test_resident_enter_round_suffix_rule():
    B = mega.MEGASTEP_VMEM_BUDGET
    # every band fits -> enter at superstep 0
    assert mega.resident_enter_round([B - 1, B // 2], [4]) == 0
    # only the tail band fits -> enter at its boundary
    assert mega.resident_enter_round([B + 1, B // 2], [4]) == 4
    # a non-monotone profile blocks the earlier fitting band
    assert mega.resident_enter_round([B // 2, B + 1, B // 2], [3, 7]) == 7
    # no suffix fits
    assert mega.resident_enter_round([B // 2, B + 1], [5]) is None


# ---------------- kernel-level parity (Pallas interpret vs jnp oracle) ----

def test_pallas_megastep_matches_oracle(road):
    _, pg = road
    gb = graph_block(pg)
    cm = mega.compose_mailbox(gb)
    for name, prog in _programs(pg).items():
        semiring = prog.semiring
        st0 = jax.vmap(prog.init)(gb)
        x = st0["x"].reshape(-1)
        ch = st0["changed_v"].reshape(-1)
        fr = st0["frontier"].reshape(-1)
        for _ in range(3):   # walk a few supersteps, compare each
            xo, cho, fo, lo, so = mega.megastep_semiring(
                x, ch, fr, cm, semiring, backend="jnp")
            xp, chp, fp, lp, sp = mega.megastep_semiring_pallas(
                x, ch, fr, cm, semiring, interpret=True)
            assert np.array_equal(np.asarray(xo), np.asarray(xp)), name
            assert np.array_equal(np.asarray(cho), np.asarray(chp)), name
            assert np.array_equal(np.asarray(fo), np.asarray(fp)), name
            assert np.array_equal(np.asarray(lo), np.asarray(lp)), name
            assert int(so) == int(sp), name
            x, ch, fr = xo, cho, fo


def test_engine_dispatches_pallas_backend(road):
    """Ask for the Pallas backend (interpreted, off the chip) and run the
    whole engine loop through the megakernel embodiment."""
    _, pg = road
    prog = _programs(pg)["cc"]
    s_ref, t_ref = GopherEngine(pg, prog, exchange="dense").run()
    pallas = dataclasses.replace(prog, spmv_backend="pallas", interpret=True)
    s, t = GopherEngine(pg, pallas, exchange="megastep").run()
    assert np.array_equal(np.asarray(s["x"]), np.asarray(s_ref["x"]))
    assert t.supersteps == t_ref.supersteps


# ---------------- composed-mailbox observations ----------------

def test_round_stats_matches_slot_table(road):
    """The einsum contraction must reproduce the slot-table observation
    exactly: pairs[p, j] counts active ob_inv slots p->j (== the compact
    path's active_slots), nsent counts replicated edges in the send set."""
    _, pg = road
    cm = mega.compose_mailbox(graph_block(pg))
    P, cap, n = cm["num_parts"], cm["cap"], cm["n"]
    so = np.asarray(cm["slot_ok"]).reshape(P, P, cap)
    ss = np.asarray(cm["slot_src"]).reshape(P, P, cap)
    eo = np.asarray(cm["edge_ok"])
    es = np.asarray(cm["edge_src"])
    rng = np.random.default_rng(0)
    for changed in [None,
                    rng.random(n) < 0.2,
                    rng.random(n) < 0.8,
                    np.zeros(n, bool),
                    rng.random((n, 3)) < 0.15]:        # batched send set
        pairs, nsent = mega.round_stats(
            None if changed is None else jnp.asarray(changed), cm)
        send_v = (np.ones(n, bool) if changed is None
                  else changed if changed.ndim == 1
                  else changed.any(axis=1))
        ref_pairs = (so & send_v[ss]).sum(axis=2)
        assert np.array_equal(np.asarray(pairs), ref_pairs)
        if changed is None or changed.ndim == 1:
            ref_sent = int((eo & send_v[es]).sum())
        else:   # batched: messages counted per query lane
            ref_sent = int((eo[..., None] & changed[es]).sum())
        assert int(nsent) == ref_sent


def test_service_warm_precompiles_fused_loop(road):
    from repro.serving import GraphQueryService
    _, pg = road
    svc = GraphQueryService({"road": pg}, max_batch=8)
    assert svc.warm("road") >= 1
    r = svc.query("bfs", "road", 0)
    assert r.result[0] == 0.0
