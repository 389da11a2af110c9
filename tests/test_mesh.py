"""Gopher Mesh: capacity-tiered physical exchange.

Contract under test:
  - tier classification is deterministic: structural occupancy excludes
    silent pairs, the EWMA profile demotes quiet pairs to cold/warm, the
    structural prior can never overflow;
  - the schedule covers every non-excluded pair exactly once, with send and
    receive tables aligned, at any device count — and its round_slots is
    the static routed geometry;
  - the fused pack kernel (plan + tier truncation + value pack + spill
    flags) matches the PR 3 plan oracle, on both backends;
  - the tiered exchange is BIT-IDENTICAL to the dense mailbox for
    idempotent ⊕ (CC / SSSP, single and query-batched, both backends) while
    routing strictly less geometry; PageRank (⊕ = float sum) matches to
    allclose — XLA may reassociate sums differently between the two fused
    BSP loops, the same caveat test_wire applies to patched blocks;
  - a pair overflowing its tier width triggers the dense fallback retry
    (results still exact) and escalates the pair for the next run;
  - exchange='auto' resolves to dense on 'local' and tiered on 'shard_map';
  - the traffic profile lives on the host block, folds in observations via
    update_profile, and apply_delta pre-announces the dirty frontier.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (GopherEngine, PageRankProgram, SemiringProgram,
                        TierPlan, compat, device_block, host_graph_block,
                        init_max_vertex, make_sssp_init, update_profile)
from repro.core.tiers import (COLD, EXCLUDED, HOT, WARM,
                              occupancy_from_graph, occupancy_from_ob_inv)
from repro.gofs import EdgeDelta, apply_delta, bfs_grow_partition, road_grid
from repro.gofs.formats import PAD, partition_graph
from repro.kernels import ops


@pytest.fixture(scope="module")
def road():
    g = road_grid(22, 22, drop_frac=0.08, seed=3, weighted=True)
    pg = partition_graph(g, bfs_grow_partition(g, 4, seed=0), 4)
    return g, pg


def _mesh1():
    return compat.make_mesh((1,), ("parts",))


# ---------------- classification ----------------

def test_tier_classification_deterministic():
    occ = np.array([[0, 3, 40, 1],
                    [3, 0, 0, 12],
                    [40, 0, 0, 2],
                    [1, 12, 2, 0]], np.int64)
    ewma = np.array([[0.0, 0.2, 40.0, 1.0],
                     [3.0, 0.0, 5.0, 0.0],
                     [6.0, 9.0, 0.0, 2.0],
                     [0.4, 30.0, 2.0, 0.0]])
    plan = TierPlan.build(ewma, occ, cap=40, warm_div=8)
    assert plan.warm_cap == 5
    t = plan.tiers
    assert t[0, 0] == EXCLUDED                   # occupancy 0
    assert t[1, 2] == EXCLUDED                   # ewma > 0 but occupancy 0
    assert t[0, 1] == COLD                       # quiet (0.2 <= 0.5)
    assert t[3, 0] == COLD
    assert t[0, 2] == HOT                        # 40 > warm_cap
    assert t[2, 0] == HOT                        # min(6, 40) = 6 > 5
    assert t[1, 0] == WARM                       # 3 <= 5
    assert t[3, 1] == HOT                        # min(30, 12) = 12 > 5
    assert t[2, 3] == WARM                       # min(2, 2) in (0.5, 5]
    lim = plan.limits()
    assert lim[0, 0] == 0 and lim[0, 1] == 1
    assert lim[1, 0] == 5 and lim[0, 2] == 40


def test_structural_plan_never_overflows(road):
    """expected == occupancy -> every pair's width covers its maximum
    possible count (the safe default the engine builds with no profile)."""
    g, pg = road
    plan = TierPlan.from_graph(pg)
    occ = occupancy_from_graph(pg)
    lim = plan.limits()
    assert np.all(lim >= occ)
    assert np.all((occ == 0) == (plan.tiers == EXCLUDED))


def test_plan_hashable_and_escalation():
    occ = np.array([[0, 2], [5, 0]], np.int64)
    plan = TierPlan.build(np.zeros((2, 2)), occ, cap=16)
    assert {plan: 1}[TierPlan.build(np.zeros((2, 2)), occ, cap=16)] == 1
    assert plan.tiers[0, 1] == COLD and plan.tiers[1, 0] == COLD
    up = plan.escalate(np.array([[False, True], [False, False]]))
    assert up.tiers[0, 1] == WARM and up.tiers[1, 0] == COLD
    assert up.escalations_from(plan) == 1
    up2 = up.escalate(np.ones((2, 2), bool))
    assert up2.tiers[0, 1] == HOT and up2.tiers[1, 0] == WARM
    # an EXCLUDED pair that somehow overflowed jumps straight to HOT
    assert up2.tiers[0, 0] == HOT
    assert up2.escalate(np.ones((2, 2), bool)).tiers[0, 1] == HOT  # clamps


# ---------------- schedule ----------------

@pytest.mark.parametrize("D", [1, 2, 4])
def test_schedule_covers_every_pair_once(D):
    rng = np.random.default_rng(D)
    P, cap = 8, 24
    occ = rng.integers(0, 10, (P, P))
    np.fill_diagonal(occ, 0)
    ewma = occ * rng.random((P, P))
    plan = TierPlan.build(ewma, occ, cap=cap)
    sched = plan.schedule(D)
    v = P // D
    seen = set()
    # hot: block (i, j) of the all_to_all
    for i in range(sched.D):
        for j in range(sched.D):
            for r in range(sched.hot_send.shape[2]):
                e = sched.hot_send[i, j, r]
                if e == PAD:
                    assert sched.hot_recv[j, i, r] == PAD
                    continue
                s = i * v + e // P
                d = e % P
                assert d // v == j
                assert sched.hot_recv[j, i, r] == (d % v) * P + s
                assert (s, d) not in seen
                seen.add((s, d))
    # hot residual + warm/cold: ppermute shifts
    for shifts in (sched.hot_res_shifts, sched.warm_shifts,
                   sched.cold_shifts):
        for k, gsz, send, recv in shifts:
            assert send.shape == (D, gsz) and recv.shape == (D, gsz)
            for i in range(D):
                j = (i + k) % D
                for r in range(gsz):
                    e = send[i, r]
                    if e == PAD:
                        assert recv[j, r] == PAD
                        continue
                    s = i * v + e // P
                    d = e % P
                    assert d // v == j
                    assert recv[j, r] == (d % v) * P + s
                    assert (s, d) not in seen
                    seen.add((s, d))
    want = {(s, d) for s, d in zip(*np.nonzero(plan.tiers != EXCLUDED))}
    assert seen == want


def test_round_slots_accounting():
    P, cap = 4, 16
    occ = np.array([[0, 9, 1, 0],
                    [9, 0, 0, 1],
                    [1, 0, 0, 0],
                    [0, 1, 0, 0]], np.int64)
    plan = TierPlan.build(occ, occ, cap=cap)     # structural: 2 hot, 4 cold
    assert plan.counts() == {"excluded": 10, "cold": 4, "warm": 0, "hot": 2}
    s1 = plan.schedule(1)
    # D=1: no padding — exactly 2 hot rows at cap + 4 cold rows at width 1
    assert s1.round_slots() == 2 * cap + 4
    assert s1.round_index_slots() == 4
    assert s1.device_round_slots() == s1.round_slots()
    # geometry is always <= the dense exchange's
    assert s1.round_slots() <= P * P * cap
    s2 = plan.schedule(2)
    assert s2.round_slots() >= s1.round_slots()  # residual padding only
    assert s2.device_round_slots() * 2 == s2.round_slots()
    # two-level hot: both hot pairs here live on device pair (0, 0), so the
    # uniform all_to_all block is empty and they ride the residual shift —
    # strictly below the old single-level layout that padded EVERY device
    # pair's block to the max count (2*2*2*cap slots of mostly padding)
    assert s2.hot_h == 0 and len(s2.hot_res_shifts) == 1
    assert s2.round_slots() < 2 * 2 * 2 * cap


# ---------------- fused pack kernel ----------------

@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_fused_pack_matches_plan_oracle(backend):
    rng = np.random.default_rng(11)
    R, cap = 6, 40
    act = rng.random((R, cap)) < 0.35
    vals = rng.uniform(-5, 5, (R, cap)).astype(np.float32)
    vals[0, np.flatnonzero(act[0])[:1]] = np.inf    # ±inf are legal messages
    full = jnp.full((R,), cap, jnp.int32)
    pvals, sids, pinv, counts, over = ops.outbox_pack(
        jnp.asarray(vals), jnp.asarray(act), full, np.inf, backend=backend,
        block_r=4, interpret=True)
    pfwd_o, pinv_o, counts_o = ops.outbox_compact_plan(jnp.asarray(act),
                                                       backend="jnp")
    assert np.array_equal(np.asarray(pinv), np.asarray(pinv_o))
    assert np.array_equal(np.asarray(counts), np.asarray(counts_o))
    assert np.array_equal(np.asarray(sids), np.asarray(pfwd_o))
    assert not np.asarray(over).any()
    # packed values = gather through the oracle's forward permutation
    has = np.asarray(pfwd_o) != PAD
    want = np.where(has, vals[np.arange(R)[:, None],
                              np.where(has, np.asarray(pfwd_o), 0)], np.inf)
    assert np.array_equal(np.asarray(pvals), want)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_fused_pack_truncation_and_overflow(backend):
    rng = np.random.default_rng(12)
    R, cap = 5, 24
    act = rng.random((R, cap)) < 0.5
    vals = rng.uniform(0, 9, (R, cap)).astype(np.float32)
    lim = jnp.asarray(rng.integers(0, 6, R), jnp.int32)
    pvals, sids, pinv, counts, over = ops.outbox_pack(
        jnp.asarray(vals), jnp.asarray(act), lim, 0.0, backend=backend,
        block_r=4, interpret=True)
    counts, over = np.asarray(counts), np.asarray(over)
    assert np.array_equal(counts, act.sum(1))    # counts are PRE-truncation
    assert np.array_equal(over, (act.sum(1) > np.asarray(lim)).astype(np.int32))
    for r in range(R):
        k = min(int(counts[r]), int(lim[r]))
        keep = np.flatnonzero(act[r])[:k]
        assert np.array_equal(np.asarray(sids)[r, :k], keep)
        assert np.all(np.asarray(sids)[r, k:] == PAD)
        assert np.array_equal(np.asarray(pvals)[r, :k], vals[r, keep])
        assert np.all(np.asarray(pvals)[r, k:] == 0.0)
        # pinv maps only the kept slots
        assert np.array_equal(np.flatnonzero(np.asarray(pinv)[r] != PAD), keep)


def test_fused_pack_batched_matches_single():
    rng = np.random.default_rng(13)
    R, cap, Q = 4, 16, 3
    act = rng.random((R, cap)) < 0.4
    vals = rng.uniform(0, 9, (R, cap, Q)).astype(np.float32)
    lim = jnp.asarray(rng.integers(1, 5, R), jnp.int32)
    for backend in ("jnp", "pallas"):
        pv, sids, pinv, counts, over = ops.outbox_pack(
            jnp.asarray(vals), jnp.asarray(act), lim, 0.0, backend=backend,
            interpret=True)
        for q in range(Q):
            pq, sq, iq, cq, oq = ops.outbox_pack(
                jnp.asarray(vals[:, :, q]), jnp.asarray(act), lim, 0.0,
                backend="jnp")
            assert np.array_equal(np.asarray(pv)[:, :, q], np.asarray(pq))
            assert np.array_equal(np.asarray(sids), np.asarray(sq))
            assert np.array_equal(np.asarray(counts), np.asarray(cq))
            assert np.array_equal(np.asarray(over), np.asarray(oq))


# ---------------- engine: tiered == dense, both backends ----------------

def _programs(pg, n):
    return [
        ("cc", SemiringProgram(semiring="max_first", init_fn=init_max_vertex),
         "x", True),
        ("sssp", SemiringProgram(
            semiring="min_plus",
            init_fn=make_sssp_init(int(pg.part_of[0]), int(pg.local_of[0]))),
         "x", True),
        # ⊕ = float sum: the two fused BSP loops may reassociate — allclose
        ("pagerank", PageRankProgram(n_global=n, num_iters=12), "r", False),
    ]


@pytest.mark.parametrize("backend", ["local", "shard_map"])
def test_tiered_exchange_matches_dense(backend, road):
    g, pg = road
    mesh = _mesh1() if backend == "shard_map" else None
    for name, prog, key, exact in _programs(pg, g.n):
        sd, td = GopherEngine(pg, prog, backend=backend, mesh=mesh,
                              exchange="dense").run()
        st, tt = GopherEngine(pg, prog, backend=backend, mesh=mesh,
                              exchange="tiered").run()
        a, b = np.asarray(sd[key]), np.asarray(st[key])
        if exact:
            assert np.array_equal(a, b), name
        else:
            assert np.allclose(a, b, rtol=1e-6, atol=1e-9), name
        assert td.supersteps == tt.supersteps
        assert tt.exchange == "tiered" and not tt.retried
        assert tt.spills == 0
        # physical geometry: static per round, strictly under dense
        P, cap = pg.num_parts, pg.mailbox_cap
        assert np.all(np.asarray(tt.wire_hist)
                      == np.asarray(tt.wire_hist)[0])
        assert tt.wire_slots < td.wire_slots
        assert tt.bytes_on_wire < td.bytes_on_wire
        assert tt.pair_slots is not None and tt.pair_slots.shape == (P, P)
        assert tt.pair_overflow is not None and tt.pair_overflow.sum() == 0


def test_tiered_query_batched_matches_dense(road):
    from repro.serving.batched import (BatchedSemiringProgram,
                                       gather_query_results, sssp_query_init)
    g, pg = road
    sources = [0, 5, g.n // 2, g.n - 1]
    prog = BatchedSemiringProgram(semiring="min_plus",
                                  num_queries=len(sources))
    extra = {"qinit": sssp_query_init(pg, sources)}
    sd, td = GopherEngine(pg, prog, exchange="dense").run_queries(extra=extra)
    st, tt = GopherEngine(pg, prog,
                          exchange="tiered").run_queries(extra=extra)
    assert np.array_equal(gather_query_results(pg, sd["x"]),
                          gather_query_results(pg, st["x"]))
    assert np.array_equal(td.query_supersteps, tt.query_supersteps)
    assert tt.spills == 0 and not tt.retried
    assert tt.wire_slots < td.wire_slots


def test_auto_resolves_per_backend(road):
    g, pg = road
    prog = SemiringProgram(semiring="max_first", init_fn=init_max_vertex)
    local = GopherEngine(pg, prog)
    assert local.exchange_requested == "auto"
    # Gopher Hot: on the local backend an eligible program rides the fused
    # megastep route — one launch per superstep, nothing on the wire
    assert local.exchange == "megastep" and local.tier_plan is None
    # an ineligible program (bounded local fixpoint) stays dense
    capped = GopherEngine(pg, SemiringProgram(semiring="max_first",
                                              init_fn=init_max_vertex,
                                              max_local_iters=1))
    assert capped.exchange == "dense"
    # a DEGENERATE 1-device shard_map mesh is local in every physical sense
    # but the megastep route is vmap-only, so auto picks dense there
    sm = GopherEngine(pg, prog, backend="shard_map", mesh=_mesh1())
    assert sm.exchange == "dense" and sm.tier_plan is None
    # auto results match an explicit dense run on both backends
    sd, _ = GopherEngine(pg, prog, exchange="dense").run()
    sa, ta = local.run()
    assert np.array_equal(np.asarray(sd["x"]), np.asarray(sa["x"]))
    assert ta.exchange == "megastep" and ta.wire_slots == 0
    sm_state, tm = sm.run()
    assert np.array_equal(np.asarray(sd["x"]), np.asarray(sm_state["x"]))
    assert tm.exchange == "dense"
    # an EXPLICIT tiered request on the 1-device mesh is still honored
    st, tt = GopherEngine(pg, prog, backend="shard_map", mesh=_mesh1(),
                          exchange="tiered").run()
    assert tt.exchange == "tiered"
    assert np.array_equal(np.asarray(sd["x"]), np.asarray(st["x"]))


# ---------------- overflow: dense fallback retry + escalation ----------------

def test_overflow_escalates_and_falls_back(road):
    g, pg = road
    prog = SemiringProgram(semiring="min_plus",
                           init_fn=make_sssp_init(int(pg.part_of[0]),
                                                  int(pg.local_of[0])))
    sd, _ = GopherEngine(pg, prog, exchange="dense").run()
    # sabotage the plan: demote the BUSIEST pair to cold (width 1) — a cold
    # SSSP run fires every slot of the pair in the prime round
    plan = TierPlan.from_graph(pg)
    occ = occupancy_from_graph(pg)
    s, d = np.unravel_index(np.argmax(occ), occ.shape)
    assert occ[s, d] > 1
    t = plan.tiers.copy()
    t[s, d] = COLD
    import dataclasses
    bad = dataclasses.replace(plan, tier_bytes=t.tobytes())
    eng = GopherEngine(pg, prog, exchange="tiered", tier_plan=bad)
    st, tt = eng.run()
    # results still exact (dense fallback), spill recorded, pair promoted
    assert np.array_equal(np.asarray(sd["x"]), np.asarray(st["x"]))
    assert tt.retried and tt.spills > 0
    assert tt.exchange == "tiered"
    assert tt.escalations >= 1
    # the profile observation covers the ABORTED tiered attempt's rounds
    assert tt.pair_rounds >= 1
    assert tt.pair_slots.sum() > 0
    assert tt.pair_overflow[s, d] > 0
    assert eng.tier_plan.tiers[s, d] > COLD
    # escalation converges: within the tier ladder the same engine stops
    # spilling and goes back to pure tiered runs
    for _ in range(3):
        st, tt = eng.run()
        if not tt.retried:
            break
    assert not tt.retried and tt.spills == 0
    assert np.array_equal(np.asarray(sd["x"]), np.asarray(st["x"]))


def test_tiered_multi_device_collectives():
    """The real thing: D=4 CPU devices (forced via XLA_FLAGS in a
    subprocess — the flag only takes effect before jax initializes), so
    the hot tier's all_to_all and the warm/cold ppermute round-robin
    actually cross device boundaries. Asserts CC + SSSP bit-parity with
    the dense exchange and a spill-free structural plan."""
    import subprocess
    import sys
    import os
    prog = r"""
import numpy as np
from repro.core import (GopherEngine, SemiringProgram, compat,
                        init_max_vertex, make_sssp_init)
from repro.gofs import bfs_grow_partition, road_grid
from repro.gofs.formats import partition_graph
g = road_grid(14, 14, drop_frac=0.05, seed=1, weighted=True)
pg = partition_graph(g, bfs_grow_partition(g, 8, seed=0), 8)   # v=2/device
mesh = compat.make_mesh((4,), ("parts",))
# auto picks the tiered wire on a REAL multi-device mesh (vs dense at D=1)
assert GopherEngine(pg, SemiringProgram(semiring="max_first",
                                        init_fn=init_max_vertex),
                    backend="shard_map", mesh=mesh).exchange == "tiered"
for prog in (SemiringProgram(semiring="max_first", init_fn=init_max_vertex),
             SemiringProgram(semiring="min_plus",
                             init_fn=make_sssp_init(int(pg.part_of[0]),
                                                    int(pg.local_of[0])))):
    sd, td = GopherEngine(pg, prog, backend="shard_map", mesh=mesh,
                          exchange="dense").run()
    st, tt = GopherEngine(pg, prog, backend="shard_map", mesh=mesh,
                          exchange="tiered").run()
    assert np.array_equal(np.asarray(sd["x"]), np.asarray(st["x"]))
    assert tt.spills == 0 and not tt.retried
    # structural plans on a dense-ish toy mesh can pad up to the dense
    # geometry (h -> v^2); the profile, not structure, buys the big wins
    assert tt.wire_slots <= td.wire_slots
print("OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", env.get("PYTHONPATH", "")) if p)
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


def test_partitioned_sssp_over_four_devices():
    """The partitioned deployment: 16 partitions, 4 on each of D=4 CPU
    devices, through ``repro.algorithms.sssp`` on the shard_map backend
    (exchange 'auto', so tiered). Distances equal a plain float32 min-plus
    relaxation to the bit; supersteps, per-partition sweeps and lockstep
    sweeps equal the one-device megastep route's; the chip wait equals a
    host recount from each superstep's per-partition sweeps; the compiled
    loop names all five staged stages."""
    import subprocess
    import sys
    import os
    prog = r"""
import numpy as np
import jax
from repro import algorithms
from repro.core import SemiringProgram, compat, engine, make_sssp_init
from repro.core.blocks import graph_block
from repro.gofs import bfs_grow_partition, road_grid
from repro.gofs.formats import partition_graph
from repro.kernels import megastep as mega
from repro.obs import op_stages

g = road_grid(24, 24, drop_frac=0.05, seed=3, weighted=True)
pg = partition_graph(g, bfs_grow_partition(g, 16, seed=0), 16)
root = 5
mesh = compat.make_mesh((4,), ("parts",))
dist, t = algorithms.sssp(pg, root, backend="shard_map", mesh=mesh)
assert t.exchange == "tiered" and t.spills == 0 and not t.retried

# plain float32 min-plus relaxation over the in-edge lists, to quiescence
dst = np.repeat(np.arange(g.n), np.diff(g.indptr))
ref = np.full(g.n, np.inf, np.float32)
ref[root] = 0.0
while True:
    nxt = ref.copy()
    np.minimum.at(nxt, dst, ref[g.indices] + g.weights.astype(np.float32))
    if np.array_equal(nxt, ref):
        break
    ref = nxt
got = np.full(g.n, np.nan, np.float32)
got[pg.global_id[pg.vmask]] = dist[pg.vmask]
assert np.array_equal(got, ref)

# the one-device megastep route runs the same supersteps and sweeps
_, t1 = algorithms.sssp(pg, root)
assert t1.exchange == "megastep"
assert t.supersteps == t1.supersteps
assert np.array_equal(t.local_iters, t1.local_iters)
assert t.lockstep_sweeps == t1.lockstep_sweeps

# chip wait recounted on the host: each superstep's per-partition sweeps,
# device d holding partitions 4d .. 4d + 3
p = SemiringProgram(semiring="min_plus",
                    init_fn=make_sssp_init(int(pg.part_of[root]),
                                           int(pg.local_of[root])))
gb = graph_block(pg)
cm = mega.compose_mailbox(gb)
st = jax.vmap(p.init)(gb)
x, ch, fr = (st[k].reshape(-1) for k in ("x", "changed_v", "frontier"))
step = jax.jit(lambda x, ch, fr: mega.megastep_semiring(
    x, ch, fr, cm, "min_plus")[:4])
lock = wait = 0
while True:
    x, ch, fr, li = step(x, ch, fr)
    m = np.asarray(li).reshape(4, 4).max(axis=1)
    lock += int(m.max())
    wait += int((m.max() - m).sum())
    if not np.asarray(ch).any():
        break
assert (lock, wait) == (t.lockstep_sweeps, t.chip_wait_sweeps)
assert wait > 0

loops = {k: v for k, v in engine._RUNNER_CACHE.items() if k[2] == "tiered"}
names = set(op_stages(loops)["jit_gopher_tiered"].values())
assert names == {"gopher.sweep", "gopher.pack", "gopher.route",
                 "gopher.deliver", "gopher.stats"}, names
print("OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", env.get("PYTHONPATH", "")) if p)
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


# ---------------- traffic profile ----------------

def test_profile_update_and_announce(road):
    g, pg = road
    hb = host_graph_block(pg)
    occ = occupancy_from_ob_inv(hb["ob_inv"])
    assert np.array_equal(hb["wire_ewma"], occ.astype(np.float32))
    # a quiet run decays the profile toward zero...
    update_profile(hb, np.zeros_like(occ), rounds=1, decay=0.25)
    assert np.allclose(hb["wire_ewma"], 0.25 * occ)
    update_profile(hb, np.zeros_like(occ), rounds=1, decay=0.25)
    plan = TierPlan.from_block(hb)
    # ...so busy-in-structure but quiet-in-history pairs leave the hot tier
    assert (plan.tiers == HOT).sum() < (TierPlan.from_graph(pg).tiers
                                        == HOT).sum()
    # an insert delta pre-announces its dirty frontier: the touched pair
    # rises to at least its expected prime-round count
    u = int(pg.global_id[0][np.flatnonzero(pg.vmask[0])[0]])
    other = int(pg.global_id[1][np.flatnonzero(pg.vmask[1])[0]])
    res = apply_delta(pg, EdgeDelta.inserts([u], [other], [1.0]),
                      directed=False, block=hb)
    ew = res.block["wire_ewma"]
    pu, pv = int(pg.part_of[u]), int(pg.part_of[other])
    assert ew[pu, pv] >= 1.0 and ew[pv, pu] >= 1.0
    # and an engine run with the rebuilt plan stays spill-free + exact
    plan2 = TierPlan.from_block(res.block)
    prog = SemiringProgram(semiring="min_plus",
                           init_fn=make_sssp_init(int(pg.part_of[0]),
                                                  int(pg.local_of[0])))
    gbd = device_block(res.block)
    sd, _ = GopherEngine(res.pg, prog, gb=gbd, exchange="dense").run()
    st, tt = GopherEngine(res.pg, prog, gb=gbd, exchange="tiered",
                          tier_plan=plan2).run()
    assert np.array_equal(np.asarray(sd["x"]), np.asarray(st["x"]))


def test_tiered_wire_tracks_quiet_profile(road):
    """The acceptance-shape check at test scale: converge, teach the
    profile, apply a small insert delta, and the tiered geometry for the
    incremental run lands well under the dense P²·cap per round."""
    from repro.algorithms import bfs
    g, pg = road
    hb = host_graph_block(pg)
    d_prev, _ = bfs(pg, 3)
    # teach: one converged compact run + one quiesced resume
    prog_cold = SemiringProgram(semiring="min_plus",
                                init_fn=make_sssp_init(int(pg.part_of[3]),
                                                       int(pg.local_of[3])))
    _, tele = GopherEngine(pg, prog_cold, gb=device_block(hb),
                           exchange="compact").run()
    update_profile(hb, tele.pair_slots, tele.supersteps + 1)
    x0 = np.where(pg.vmask, d_prev, np.inf).astype(np.float32)
    prog_res = SemiringProgram(semiring="min_plus", resume=True)
    _, tele_q = GopherEngine(pg, prog_res, gb=device_block(hb),
                             exchange="compact").run(
        extra={"x0": x0, "frontier0": np.zeros_like(pg.vmask)})
    update_profile(hb, tele_q.pair_slots, tele_q.supersteps + 1)
    # version k+1: small insert batch with heavy weights (no shortcuts), so
    # the incremental frontier stays small — the regime the tier profile
    # models; a shortcut-heavy delta would spill and take the dense retry,
    # which test_overflow_escalates_and_falls_back covers
    rng = np.random.default_rng(0)
    iu = rng.integers(0, g.n, 8)
    iv = rng.integers(0, g.n, 8)
    keep = iu != iv
    res = apply_delta(pg, EdgeDelta.inserts(
        iu[keep], iv[keep],
        rng.uniform(50.0, 60.0, int(keep.sum())).astype(np.float32)),
        directed=False, block=hb)
    pg1 = res.pg
    x1 = np.where(pg1.vmask, d_prev, np.inf).astype(np.float32)
    extra = {"x0": x1, "frontier0": res.dirty_insert & pg1.vmask}
    gbd = device_block(res.block)
    outs = {}
    for mode in ("dense", "tiered"):
        eng = GopherEngine(pg1, SemiringProgram(semiring="min_plus",
                                                resume=True),
                           gb=gbd, exchange=mode,
                           tier_plan=(TierPlan.from_block(res.block)
                                      if mode == "tiered" else None))
        state, tele = eng.run(extra=extra)
        outs[mode] = (np.asarray(state["x"]), tele)
    xd, td = outs["dense"]
    xt, tt = outs["tiered"]
    assert np.array_equal(xd, xt)
    assert tt.spills == 0 and not tt.retried
    P, cap = pg1.num_parts, pg1.mailbox_cap
    assert tt.wire_hist[0] <= 0.25 * P * P * cap
    assert tt.wire_slots <= 0.25 * td.wire_slots
