"""Gopher Hot — the fused superstep megakernel for the small-frontier tail.

BENCH_comm's standing embarrassment: the sparse exchange stack
(compact/tiered/phased) ships 100-300x fewer slots than dense yet LOSES
2-3x wall-clock on local small-frontier runs, because every superstep
dispatches separate sweep, pack, route, and halt-vote stages whose launch
overhead dwarfs the tiny frontier's actual work. That regime — 1-3
supersteps, frontiers of a few dozen vertices — is exactly where
incremental serving lives.

This module collapses the whole superstep into ONE dispatch over the flat
(P*v_max,) state:

- :func:`compose_mailbox` folds the graph block's THREE routing hops
  (remote edge -> outbox slot via ``ob_inv``, slot -> wire, wire -> inbox
  feed via ``ib_lo``/``ib_hub``) into direct gather maps from each
  destination vertex's feed lanes straight to the SOURCE vertex's flat
  state index — computed once per run, O(feed-table) work.
- :func:`megastep_semiring` runs one fused superstep: frontier-gated
  mailbox delivery (= the staged exchange's inbox combine, lane for lane),
  inbox ⊕-combine, the masked local-fixpoint sweep, and the changed/halt
  reduction — one traced stage, one kernel launch on the traced driver
  (vs sweep+pack+route = 3+ staged dispatches).
- :func:`megastep_semiring_pallas` / :func:`resident_megastep_pallas` are
  the Pallas TPU embodiments (``grid=(1,)``, whole problem VMEM-resident,
  the mailbox an on-chip buffer), taken only when a program asks for
  ``spmv_backend="pallas"``. The resident kernel runs MULTIPLE
  supersteps of a narrow phase inside a single launch, exiting on
  quiescence or the iteration bound — the on-chip-mailbox mode
  :func:`resident_enter_round` gates on the ``PhasedTierPlan`` band
  geometry fitting :data:`MEGASTEP_VMEM_BUDGET`.

Frontier invariant: every vertex outside the frontier has already
relaxed all its local out-neighbours. Cold starts seed all of ``vmask``,
delivery adds every vertex the inbox lowered, each sweep adds every vertex
it changed, and incremental resumes seed whatever a delta left unrelaxed
(algorithms.incremental.resume_seed). The masked sweeps rely on it: they
gather only the frontier's values (the state with the ⊕-identity off the
frontier), one gather per sweep, since under the invariant a settled
in-neighbour cannot move its row (:func:`sweep_flat`).

Exactness: for idempotent ⊕ (min/max) every value either path produces is
a ⊕-fold of the same multiset of path sums, and float32 min/max are
order-independent bit-for-bit — so the fused superstep, the resident
multi-superstep schedule, and the staged dense exchange all converge to
bitwise-identical fixpoints (the same argument that makes the tiered
dense-retry exact; see analysis.semiring). PageRank's ``sum`` ⊕ folds the
dangling/delta reductions in a different association, so its parity class
is allclose, mirroring the existing cross-mode contract.

Stage names: the fused loops open ``jax.named_scope`` stages, which
reach only the compiled HLO's metadata (``obs.op_stages`` reads them back
per instruction): ``gopher.deliver`` (mailbox delivery and the inbox
⊕-combine), ``gopher.sweep`` (the masked sweeps of the fixpoint),
``gopher.frontier`` (frontier updates and the fixpoint's ``any(f)``),
``gopher.stats`` (round statistics and telemetry) and ``gopher.update``
(PageRank's contributions, dangling mass, rank update and delta).

Delivery-order note: the staged engine exchanges AFTER superstep s and
primes round 0 from the init state. The fused loop instead delivers at
the TOP of superstep s from the previous superstep's ``changed_v`` — the
same messages, one loop-carried dependency shorter (and round 0 falls out
of init's ``changed_v`` seed with no special case). The wasted
final-round exchange the staged loop pays after the halt vote is simply
never launched.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.gofs.formats import PAD

# per-superstep VMEM footprint (predicted per-round wire slots * 4B) under
# which the resident narrow-phase loop may keep the mailbox on chip
MEGASTEP_VMEM_BUDGET = 4 * 2 ** 20

_IDENT = {"min": jnp.inf, "max": -jnp.inf, "sum": 0.0}
_KIDENT = {"min_plus": jnp.inf, "max_first": -jnp.inf}
_REDUCE = {"min": jnp.min, "max": jnp.max, "sum": jnp.sum}
_MAX_IT = 2 ** 30


def _ew(combine: str, a, b):
    if combine == "sum":
        return a + b
    return jnp.minimum(a, b) if combine == "min" else jnp.maximum(a, b)


# ---------------- composed routing maps ----------------

# the python-int entries of a composed mailbox — everything else is a
# device array. Callers that ship a mailbox through a jit boundary (the
# engine's pre-composed ``mcm_*`` graph-block entries) strip these and
# re-derive them from static shapes on the far side.
MAILBOX_STATICS = ("num_parts", "v_max", "cap", "n")


def compose_mailbox(gb: dict, adjacency: str = "full") -> dict:
    """Fold the staged mailbox's three routing hops into direct gather maps.

    For destination vertex (p, v), feed lane m of ``ib_lo[p, v]`` names a
    received slot ``src * cap + slot``; that slot's value on the staged path
    is ``x[src][re_src[src, ob_inv[src, p*cap + slot]]]`` (⊗ the edge
    weight) when the source vertex is in the send set. Composing the three
    maps once per run yields, per feed lane: the source's FLAT state index,
    a validity mask, and the edge weight — delivery becomes one gather +
    one lane reduce, bit-identical to the staged inbox combine because the
    lanes hold the same values in the same order.

    Also composed: the slot-activity map (``slot_src``/``slot_ok``) whose
    per-round counts equal the compact path's ``active_slots`` observation
    exactly (feeds the pair-profile EWMA), the edge-level send map
    (``edge_src``/``edge_ok``) for ``messages_sent``, and the flattened
    adjacency (``adjacency='full'`` for scalar programs, ``'binned'`` for
    the batched two-bin ELL, ``'none'`` for delivery-only callers).
    """
    ob_inv = gb["ob_inv"]
    P = ob_inv.shape[0]
    cap = ob_inv.shape[1] // P
    vmask = gb["vmask"]
    v_max = vmask.shape[1]
    n = P * v_max
    re_src = gb["re_src"]
    re_wgt = gb["re_wgt"]
    p1 = jnp.arange(P, dtype=jnp.int32)[:, None]
    p2 = jnp.arange(P, dtype=jnp.int32)[:, None, None]

    def feed_maps(feeds):
        # feeds (P, ..., m): flat received positions src*cap + slot per
        # destination-partition row; returns (src_flat, ok, w) same shape
        valid = feeds != PAD
        ms = jnp.where(valid, feeds, 0)
        src = ms // cap
        slot = ms % cap
        pidx = jnp.arange(P, dtype=jnp.int32).reshape(
            (P,) + (1,) * (feeds.ndim - 1))
        e = ob_inv[src, pidx * cap + slot]
        ev = e != PAD
        es = jnp.where(ev, e, 0)
        s_local = re_src[src, es]
        sv = s_local != PAD
        ok = valid & ev & sv
        src_flat = jnp.where(ok, src * v_max + jnp.where(sv, s_local, 0), 0)
        return src_flat.astype(jnp.int32), ok, re_wgt[src, es]

    lo_src, lo_ok, lo_w = feed_maps(gb["ib_lo"])            # (P, v_max, m_lo)
    m_lo = lo_src.shape[-1]
    hub_src, hub_ok, hub_w = feed_maps(gb["ib_hub"])        # (P, hr_max, m_hi)
    hr_max, m_hi = hub_src.shape[1], hub_src.shape[2]

    # inverse of ib_hub_idx: flat vertex -> its row in the flattened hub
    # feed table (each vertex receives through EITHER ib_lo or ONE hub row,
    # never both — blocks._mailbox_inverse's ⊕=sum no-double-count
    # invariant — so the hub merge is a pure gather, no scatter)
    hidx = gb["ib_hub_idx"]                                 # (P, hr_max)
    hv = hidx != PAD
    tgt = jnp.where(hv, p1 * v_max + hidx, n).reshape(-1)
    rows = jnp.arange(P * hr_max, dtype=jnp.int32)
    hub_row = jnp.full((n + 1,), PAD, jnp.int32) \
        .at[tgt].set(rows, mode="drop")[:n]
    hub_row_ok = hub_row != PAD
    hub_row = jnp.where(hub_row_ok, hub_row, 0)

    # slot-activity map: ob_inv slot -> source vertex flat id. Per-round
    # counts over it == messages.active_slots of the compact path.
    oe = ob_inv
    ov = oe != PAD
    oes = jnp.where(ov, oe, 0)
    o_local = re_src[p1, oes]
    slot_ok = ov & (o_local != PAD)
    slot_src = jnp.where(slot_ok, p1 * v_max
                         + jnp.where(o_local != PAD, o_local, 0), 0)

    # its vertex-level contraction: vdst[v, j] = 1 iff v occupies a slot to
    # destination j (at most one — the outbox dedupes per pair), so a
    # round's per-pair counts are one einsum over the send set instead of a
    # slot-table gather chain every superstep. Counts stay < 2^24, exact
    # in f32.
    dst_col = jnp.tile(jnp.repeat(jnp.arange(P, dtype=jnp.int32), cap),
                       (P, 1))
    vdst = jnp.zeros((n + 1, P), jnp.float32).at[
        jnp.where(slot_ok, slot_src, n).reshape(-1),
        dst_col.reshape(-1)].add(1.0, mode="drop")[:n]

    # edge-level send map (messages_sent), plus its per-vertex contraction:
    # edge_cnt[v] = how many replicated edges vertex v sources, so a round's
    # message count is one (n,)-reduce over the send set instead of a
    # gather over the padded edge table every superstep
    e_ok = re_src != PAD
    edge_src = jnp.where(e_ok, p1 * v_max + jnp.where(e_ok, re_src, 0), 0)
    n_edges = e_ok.size
    edge_cnt = jnp.zeros((n,), jnp.int32).at[
        jnp.where(e_ok, edge_src, n).reshape(-1)].add(
            jnp.ones((n_edges,), jnp.int32), mode="drop")

    cm = {
        "num_parts": P, "v_max": v_max, "cap": cap, "n": n,
        "vmask": vmask.reshape(-1),
        "lo_src": lo_src.reshape(n, m_lo),
        "lo_ok": lo_ok.reshape(n, m_lo),
        "lo_w": lo_w.reshape(n, m_lo),
        "hub_src": hub_src.reshape(P * hr_max, m_hi),
        "hub_ok": hub_ok.reshape(P * hr_max, m_hi),
        "hub_w": hub_w.reshape(P * hr_max, m_hi),
        "hub_row": hub_row, "hub_row_ok": hub_row_ok,
        "slot_src": slot_src.astype(jnp.int32), "slot_ok": slot_ok,
        "vdst": vdst,
        "edge_src": edge_src.astype(jnp.int32), "edge_ok": e_ok,
        "edge_cnt": edge_cnt.astype(jnp.float32),
    }

    if adjacency == "full":
        nbr = gb["nbr"]
        nok = nbr != PAD
        cm["nbr"] = jnp.where(nok, p2 * v_max + jnp.where(nok, nbr, 0), 0) \
            .reshape(n, -1).astype(jnp.int32)
        cm["nbr_ok"] = nok.reshape(n, -1)
        cm["wgt"] = gb["wgt"].reshape(n, -1)
    elif adjacency == "binned":
        lo = gb["nbr_lo"]
        lov = lo != PAD
        cm["nbr_lo"] = jnp.where(lov, p2 * v_max + jnp.where(lov, lo, 0), 0) \
            .reshape(n, -1).astype(jnp.int32)
        cm["nbr_lo_ok"] = lov.reshape(n, -1)
        cm["wgt_lo"] = gb["wgt_lo"].reshape(n, -1)
        ah = gb["adj_hub_idx"]                              # (P, ah_max)
        ahv = ah != PAD
        cm["ahub_dst"] = jnp.where(ahv, p1 * v_max + jnp.where(ahv, ah, 0),
                                   n).reshape(-1).astype(jnp.int32)
        an = gb["adj_hub_nbr"]
        anv = an != PAD
        cm["ahub_nbr"] = jnp.where(anv, p2 * v_max + jnp.where(anv, an, 0),
                                   0).reshape(an.shape[0] * an.shape[1], -1) \
            .astype(jnp.int32)
        cm["ahub_ok"] = anv.reshape(an.shape[0] * an.shape[1], -1)
        cm["ahub_wgt"] = gb["adj_hub_wgt"] \
            .reshape(an.shape[0] * an.shape[1], -1)
    return cm


@functools.partial(jax.jit, static_argnames="adjacency")
def compose_mailbox_arrays(gb: dict, adjacency: str = "full") -> dict:
    """:func:`compose_mailbox` as one compiled program, returning its device
    arrays only (``MAILBOX_STATICS`` dropped). Run op by op, the composition
    is ~120 small programs, each its own XLA compile on a cold cache."""
    cm = compose_mailbox(gb, adjacency)
    return {k: v for k, v in cm.items() if k not in MAILBOX_STATICS}


# ---------------- fused mailbox delivery ----------------

def deliver_flat(vals, live, cm: dict, combine: str, with_weight: bool):
    """The staged exchange's pack -> route -> inbox-combine pipeline as one
    gather + lane reduce over the composed maps. ``vals`` is the (n,) or
    (n, Q) per-source message value (pre-⊗ except the edge weight); ``live``
    gates sends (None = unconditional, PageRank-style). Lane-for-lane equal
    to messages.combine_inbox_gather over the routed slot array, so the
    reduce is bitwise identical."""
    ident = _IDENT[combine]
    red = _REDUCE[combine]
    batched = vals.ndim == 2

    def pull(src, ok, w):
        g = vals[src]
        if batched:
            ok = ok[..., None]
            if with_weight:
                g = g + w[..., None]
        elif with_weight:
            g = g + w
        if live is not None:
            ok = ok & live[src]
        return jnp.where(ok, g, ident)

    axis = -2 if batched else -1
    y = red(pull(cm["lo_src"], cm["lo_ok"], cm["lo_w"]), axis=axis)
    yh = red(pull(cm["hub_src"], cm["hub_ok"], cm["hub_w"]), axis=axis)
    hro = cm["hub_row_ok"]
    hub = jnp.where(hro[:, None] if batched else hro, yh[cm["hub_row"]],
                    ident)
    return _ew(combine, y, hub)


def round_stats(changed, cm: dict):
    """One round's wire observation from the send set: the per-pair active
    slot counts (== messages.active_slots of the compact path, feeding the
    tier-profile EWMA) and the edge-level message count. ``changed=None``
    counts unconditional sends (PageRank). Batched send sets activate a
    slot when ANY query lane sends (the contiguous Q-vector ships as one
    unit) but count messages per lane."""
    P, v_max = cm["num_parts"], cm["v_max"]
    cnt, vdst = cm["edge_cnt"], cm["vdst"]
    if changed is None:
        pairs = vdst.reshape(P, v_max, P).sum(axis=1)
        return pairs.astype(jnp.int32), jnp.sum(cnt).astype(jnp.int32)
    if changed.ndim == 1:
        chf = changed.astype(jnp.float32)
        nsent = jnp.dot(chf, cnt)
    else:
        chf = jnp.any(changed, axis=1).astype(jnp.float32)
        nsent = jnp.dot(changed.astype(jnp.float32).sum(axis=1), cnt)
    pairs = jnp.einsum("pv,pvj->pj", chf.reshape(P, v_max),
                       vdst.reshape(P, v_max, P))
    return pairs.astype(jnp.int32), nsent.astype(jnp.int32)


# ---------------- flat frontier sweeps ----------------

def sweep_flat(xf, cm: dict, semiring: str):
    """Frontier sweep over the flattened full adjacency: each row's ⊕ over
    its in-neighbours' frontier values, one gather. ``xf`` is the state
    with the ⊕-identity off the frontier (``where(f, x, ident)``), so a
    lane whose source is settled contributes the identity and no separate
    gather of the frontier flags is needed.

    It relies on the frontier invariant: every vertex outside the frontier
    has already relaxed all its local out-neighbours (``x[v] ⊕ (x[u] ⊗ w)
    == x[v]`` for each local edge u -> v with u settled). Under it the
    lanes this sweep drops could not move their row, so the result is not
    row-for-row :func:`kernels.ref.semiring_spmv_frontier_ref` (which
    reduces every lane of a row with an active in-neighbour) but equal to
    it after the ⊕ with the state, bit for bit."""
    ok, idx = cm["nbr_ok"], cm["nbr"]
    g = xf[idx]
    if semiring == "min_plus":
        return jnp.min(jnp.where(ok, g + cm["wgt"], jnp.inf), axis=1)
    return jnp.max(jnp.where(ok, g, -jnp.inf), axis=1)


def sweep_flat_dense(x, cm: dict):
    """Unmasked plus_times sweep with unit weights over the flat adjacency
    (PageRank's pull) — mirrors semiring_spmv_ref lane for lane."""
    ok, idx = cm["nbr_ok"], cm["nbr"]
    g = x[idx]
    ones = jnp.ones_like(cm["wgt"])
    return jnp.sum(jnp.where(ok, g * ones, 0.0), axis=1)


def sweep_flat_batched(xf, cm: dict, semiring: str):
    """Frontier sweep, lane by lane, over the flattened two-bin binned
    adjacency on (n, Q) frontier values — :func:`sweep_flat` per query
    lane, one gather per bin (lo bin + hub scatter merge, as
    ops.binned_ell_spmv_multi_frontier has it, with flat indices). Equal to
    that masked sweep after the ⊕ with the state, under the same frontier
    invariant, lane by lane."""
    assert semiring in ("min_plus", "max_first")

    def sweep(idx, ok, w):
        g = xf[idx]                                         # (rows, D, Q)
        if semiring == "min_plus":
            return jnp.min(jnp.where(ok[..., None], g + w[..., None],
                                     jnp.inf), axis=1)
        return jnp.max(jnp.where(ok[..., None], g, -jnp.inf), axis=1)

    y = sweep(cm["nbr_lo"], cm["nbr_lo_ok"], cm["wgt_lo"])
    yh = sweep(cm["ahub_nbr"], cm["ahub_ok"], cm["ahub_wgt"])
    ref = y.at[cm["ahub_dst"]]
    if semiring == "min_plus":
        return ref.min(yh, mode="drop")
    return ref.max(yh, mode="drop")


# ---------------- fused supersteps (jnp oracles + dispatch) ----------------

def megastep_semiring(x, changed, frontier, cm: dict, semiring: str,
                      unroll: int = 1, backend: str = "jnp",
                      interpret: bool = False):
    """One fused superstep for scalar idempotent-semiring programs on flat
    state: deliver the previous round's messages, ⊕-combine, run the
    masked local fixpoint, emit the new send set. Returns
    ``(x2, changed2, f_left, liters, sweeps)`` with liters per partition
    matching the staged vmapped while_loop's select semantics bit for bit,
    and ``sweeps`` the sweeps the flat fixpoint ran in lockstep.
    Runs the jnp oracle unless ``backend="pallas"`` asks for the
    megakernel (compiled, or interpreted with ``interpret=True``)."""
    if backend == "pallas":
        return megastep_semiring_pallas(
            x, changed, frontier, cm, semiring, unroll=unroll,
            interpret=interpret)
    combine = "min" if semiring == "min_plus" else "max"
    vm = cm["vmask"]
    sweep = functools.partial(sweep_flat, cm=cm, semiring=semiring)
    return _fused_fixpoint(x, changed, frontier, cm, combine, vm, sweep,
                           unroll, semiring == "min_plus")


def _fused_fixpoint(x, changed, frontier, cm: dict, combine: str, vm, sweep,
                    unroll: int, with_weight: bool):
    """The body both fused semiring supersteps share: delivery, the masked
    fixpoint of ``sweep(xf)`` in lockstep over every partition, and the
    new send set, each under its stage name.

    The loop carries the frontier twice: as flags ``f`` (the halt test and
    the per-partition sweep counts read them) and as values ``xf``, the
    state with the ⊕-identity off the frontier, which is all the sweep
    gathers. ``xf`` is built once before the loop and then by each sweep's
    elementwise tail, so the gather reads one carried buffer whatever
    XLA's fusion would make of a mask computed in front of it (which could
    gather the flags again). The masked sweep is exact under the frontier invariant (see
    :func:`sweep_flat`), which every caller's seed meets and every sweep
    keeps: a cold start seeds all of ``vmask``, delivery adds every vertex
    the inbox lowered, each sweep puts every changed vertex into ``f``,
    and an incremental resume seeds the new edges' sources and, for a
    deletion, whole reset sub-graphs with their boundary sources."""
    P = cm["num_parts"]
    ident = _IDENT[combine]
    with jax.named_scope("gopher.deliver"):
        inbox = deliver_flat(x, changed, cm, combine, with_weight)
        x1 = _ew(combine, x, inbox)
    with jax.named_scope("gopher.frontier"):
        f0 = frontier | ((x1 != x) & vm)
        xf0 = jnp.where(f0, x1, ident)

    def cond(c):
        _, _, f, it, _ = c
        with jax.named_scope("gopher.frontier"):
            return jnp.any(f) & (it < jnp.int32(_MAX_IT))

    def body(c):
        xc, xf, f, it, li = c
        with jax.named_scope("gopher.stats"):
            li = li + jnp.int32(unroll) * jnp.any(f.reshape(P, -1), axis=1)
        for _ in range(unroll):
            with jax.named_scope("gopher.sweep"):
                x2 = _ew(combine, xc, sweep(xf))
            with jax.named_scope("gopher.frontier"):
                f = (x2 != xc) & vm
                xf = jnp.where(f, x2, ident)
            xc = x2
        return xc, xf, f, it + jnp.int32(unroll), li

    x2, _, f_left, sweeps, liters = jax.lax.while_loop(
        cond, body,
        (x1, xf0, f0, jnp.int32(0), jnp.zeros((P,), jnp.int32)))
    with jax.named_scope("gopher.frontier"):
        changed2 = (x2 != x) & vm
    return x2, changed2, f_left, liters, sweeps


def megastep_semiring_batched(x, changed, frontier, cm: dict, semiring: str,
                              unroll: int = 2):
    """Q-query fused superstep on flat (n, Q) state — the serving hot path.
    Mirrors serving.batched.BatchedSemiringProgram's superstep + the staged
    batched exchange lane for lane. Returns what
    :func:`megastep_semiring` returns."""
    combine = "min" if semiring == "min_plus" else "max"
    vm = cm["vmask"][:, None]
    sweep = functools.partial(sweep_flat_batched, cm=cm, semiring=semiring)
    return _fused_fixpoint(x, changed, frontier, cm, combine, vm, sweep,
                           unroll, semiring == "min_plus")


def megastep_pagerank(r, cm: dict, deg, tele, n_global: int, damping: float,
                      num_iters: int, step):
    """One fused PageRank superstep on flat state: contributions, pull
    sweep, unconditional mailbox delivery, dangling redistribution, rank
    update. The dangling-mass and delta reductions keep the staged path's
    per-partition-then-global association (sum over v_max, then over P —
    the shape the vmapped psum folds), so local parity is tight; the
    cross-mode contract stays allclose (⊕ = sum is not associative in
    float and collective lowering may re-associate)."""
    vm = cm["vmask"]
    P = cm["num_parts"]
    with jax.named_scope("gopher.update"):
        contrib = jnp.where(deg > 0, r / jnp.maximum(deg, 1.0), 0.0)
    with jax.named_scope("gopher.sweep"):
        pull = sweep_flat_dense(contrib, cm)
    with jax.named_scope("gopher.deliver"):
        inbox = deliver_flat(contrib, None, cm, "sum", False)
    with jax.named_scope("gopher.update"):
        dangling = jnp.sum(jnp.sum(
            jnp.where(vm & (deg == 0), r, 0.0).reshape(P, -1), axis=1))
        r_new = jnp.where(
            vm,
            (1.0 - damping) * tele
            + damping * (pull + inbox + dangling * tele),
            0.0)
        delta = jnp.sum(jnp.sum(jnp.abs(r_new - r).reshape(P, -1), axis=1))
    with jax.named_scope("gopher.frontier"):
        changed = step + 1 < num_iters
    return r_new, delta, changed


def resident_step_semiring(x, changed, frontier, cm: dict, semiring: str):
    """One relaxation round of the resident narrow-phase loop: deliver
    pending news, then a SINGLE masked sweep (local consequences settle
    across rounds instead of per-superstep fixpoints — chaotic relaxation).
    Every improvement is rebroadcast the following round, so the loop
    converges to the same unique ⊕-fixpoint as the BSP schedule, bitwise
    for idempotent ⊕. At exit ``changed2``/``frontier2`` are exactly the
    BSP state contract (pending sends / locally-unsettled rows), so a
    later staged superstep can take over mid-stream."""
    combine = "min" if semiring == "min_plus" else "max"
    vm = cm["vmask"]
    with jax.named_scope("gopher.deliver"):
        inbox = deliver_flat(x, changed, cm, combine, semiring == "min_plus")
        x1 = _ew(combine, x, inbox)
    with jax.named_scope("gopher.frontier"):
        f = frontier | ((x1 != x) & vm)
        xf = jnp.where(f, x1, _IDENT[combine])
    with jax.named_scope("gopher.sweep"):
        x2 = _ew(combine, x1, sweep_flat(xf, cm, semiring))
    with jax.named_scope("gopher.frontier"):
        changed2 = (x2 != x) & vm
        frontier2 = (x2 != x1) & vm
    with jax.named_scope("gopher.stats"):
        active_p = jnp.any(f.reshape(cm["num_parts"], -1), axis=1)
    return x2, changed2, frontier2, active_p


def resident_enter_round(phase_round_bytes, boundaries,
                         budget: int = MEGASTEP_VMEM_BUDGET):
    """Earliest superstep from which the resident narrow-phase mode may
    take over: the start of the first phase band such that EVERY remaining
    band's predicted per-round wire geometry fits the VMEM budget (the
    frontier only contracts across bands by construction, but a
    non-monotone profile keeps the conservative suffix rule honest).
    Returns None when no suffix fits."""
    k0 = None
    for k in range(len(phase_round_bytes) - 1, -1, -1):
        if phase_round_bytes[k] <= budget:
            k0 = k
        else:
            break
    if k0 is None:
        return None
    return 0 if k0 == 0 else int(boundaries[k0 - 1])


# ---------------- Pallas megakernels ----------------
# grid=(1,): the whole flat problem is VMEM-resident for the small-frontier
# tail this path is gated to (resident_enter_round budgets the geometry),
# so block index maps are trivial and every output store is unconditional.


def _take(v, i):
    return jnp.take(v, i.reshape(-1)).reshape(i.shape)


def _deliver_kernel_vals(x0, ch, lsrc, lok, lw, hsrc, hok, hw, hrow, hrok,
                         semiring):
    minp = semiring == "min_plus"
    ident = _KIDENT[semiring]
    lm = (lok > 0.0) & (_take(ch, lsrc) > 0.0)
    lg = _take(x0, lsrc)
    if minp:
        y = jnp.min(jnp.where(lm, lg + lw, ident), axis=1)
    else:
        y = jnp.max(jnp.where(lm, lg, ident), axis=1)
    hm = (hok > 0.0) & (_take(ch, hsrc) > 0.0)
    hg = _take(x0, hsrc)
    if minp:
        yh = jnp.min(jnp.where(hm, hg + hw, ident), axis=1)
    else:
        yh = jnp.max(jnp.where(hm, hg, ident), axis=1)
    hub = jnp.where(hrok > 0.0, jnp.take(yh, hrow), ident)
    return jnp.minimum(y, hub) if minp else jnp.maximum(y, hub)


def _sweep_kernel_vals(xc, f, nbr, nok, wgt, semiring):
    minp = semiring == "min_plus"
    ident = _KIDENT[semiring]
    act = jnp.max(jnp.where(nok, _take(f, nbr), 0.0), axis=1) > 0.0
    if minp:
        y = jnp.min(jnp.where(nok, _take(xc, nbr) + wgt, ident), axis=1)
        ys = jnp.where(act, y, ident)
        return jnp.minimum(xc, ys)
    y = jnp.max(jnp.where(nok, _take(xc, nbr), ident), axis=1)
    ys = jnp.where(act, y, ident)
    return jnp.maximum(xc, ys)


def _megastep_kernel(x_ref, ch_ref, fr_ref, vm_ref, nbr_ref, nok_ref,
                     wgt_ref, lsrc_ref, lok_ref, lw_ref, hsrc_ref, hok_ref,
                     hw_ref, hrow_ref, hrok_ref,
                     xo_ref, cho_ref, fro_ref, lit_ref, it_ref,
                     *, semiring, num_parts, unroll):
    x0 = x_ref[...]
    vmb = vm_ref[...] > 0.0
    inbox = _deliver_kernel_vals(
        x0, ch_ref[...], lsrc_ref[...], lok_ref[...], lw_ref[...],
        hsrc_ref[...], hok_ref[...], hw_ref[...], hrow_ref[...],
        hrok_ref[...], semiring)
    minp = semiring == "min_plus"
    x1 = jnp.minimum(x0, inbox) if minp else jnp.maximum(x0, inbox)
    f0 = jnp.maximum(fr_ref[...], ((x1 != x0) & vmb).astype(jnp.float32))
    nbr = nbr_ref[...]
    nok = nok_ref[...] > 0.0
    wgt = wgt_ref[...]

    def cond(c):
        _, f, it, _ = c
        return jnp.any(f > 0.0) & (it < jnp.int32(_MAX_IT))

    def body(c):
        xc, f, it, li = c
        li = li + jnp.int32(unroll) * jnp.any(
            f.reshape(num_parts, -1) > 0.0, axis=1)
        for _ in range(unroll):
            x2 = _sweep_kernel_vals(xc, f, nbr, nok, wgt, semiring)
            f = ((x2 != xc) & vmb).astype(jnp.float32)
            xc = x2
        return xc, f, it + jnp.int32(unroll), li

    x2, f_left, it, li = jax.lax.while_loop(
        cond, body,
        (x1, f0, jnp.int32(0), jnp.zeros((num_parts,), jnp.int32)))
    xo_ref[...] = x2
    cho_ref[...] = ((x2 != x0) & vmb).astype(jnp.float32)
    fro_ref[...] = f_left
    lit_ref[...] = li
    it_ref[...] = jnp.full((1,), it, jnp.int32)


def _resident_kernel(x_ref, ch_ref, fr_ref, vm_ref, nbr_ref, nok_ref,
                     wgt_ref, lsrc_ref, lok_ref, lw_ref, hsrc_ref, hok_ref,
                     hw_ref, hrow_ref, hrok_ref,
                     xo_ref, cho_ref, fro_ref, it_ref, lit_ref,
                     *, semiring, num_parts, max_steps):
    vmb = vm_ref[...] > 0.0
    minp = semiring == "min_plus"
    lsrc, lok, lw = lsrc_ref[...], lok_ref[...], lw_ref[...]
    hsrc, hok, hw = hsrc_ref[...], hok_ref[...], hw_ref[...]
    hrow, hrok = hrow_ref[...], hrok_ref[...]
    nbr = nbr_ref[...]
    nok = nok_ref[...] > 0.0
    wgt = wgt_ref[...]

    def cond(c):
        _, ch, _, it, _ = c
        return jnp.any(ch > 0.0) & (it < jnp.int32(max_steps))

    def body(c):
        xc, ch, fr, it, li = c
        inbox = _deliver_kernel_vals(xc, ch, lsrc, lok, lw, hsrc, hok, hw,
                                     hrow, hrok, semiring)
        x1 = jnp.minimum(xc, inbox) if minp else jnp.maximum(xc, inbox)
        f = jnp.maximum(fr, ((x1 != xc) & vmb).astype(jnp.float32))
        li = li + jnp.any(f.reshape(num_parts, -1) > 0.0, axis=1)
        x2 = _sweep_kernel_vals(x1, f, nbr, nok, wgt, semiring)
        ch2 = ((x2 != xc) & vmb).astype(jnp.float32)
        fr2 = ((x2 != x1) & vmb).astype(jnp.float32)
        return x2, ch2, fr2, it + jnp.int32(1), li

    x2, ch2, fr2, it, li = jax.lax.while_loop(
        cond, body,
        (x_ref[...], ch_ref[...], fr_ref[...], jnp.int32(0),
         jnp.zeros((num_parts,), jnp.int32)))
    xo_ref[...] = x2
    cho_ref[...] = ch2
    fro_ref[...] = fr2
    it_ref[...] = jnp.full((1,), it, jnp.int32)
    lit_ref[...] = li


def _mega_operands(x, changed, frontier, cm):
    f32 = jnp.float32
    return (
        x, changed.astype(f32), frontier.astype(f32),
        cm["vmask"].astype(f32),
        cm["nbr"], cm["nbr_ok"].astype(f32), cm["wgt"],
        cm["lo_src"], cm["lo_ok"].astype(f32), cm["lo_w"],
        cm["hub_src"], cm["hub_ok"].astype(f32), cm["hub_w"],
        cm["hub_row"], cm["hub_row_ok"].astype(f32),
    )


def _full_specs(operands):
    return [pl.BlockSpec(op.shape, lambda *_, nd=op.ndim: (0,) * nd)
            for op in operands]


def megastep_semiring_pallas(x, changed, frontier, cm: dict, semiring: str,
                             unroll: int = 1, interpret: bool = False):
    """The fused superstep as ONE Pallas launch: mailbox delivery, inbox
    combine, masked local fixpoint, and the changed/halt partial reduction
    all execute against VMEM-resident state. Returns what
    :func:`megastep_semiring` returns."""
    n = x.shape[0]
    P = cm["num_parts"]
    ops = _mega_operands(x, changed, frontier, cm)
    import functools
    kernel = functools.partial(_megastep_kernel, semiring=semiring,
                               num_parts=P, unroll=unroll)
    x2, ch, fr, li, it = pl.pallas_call(
        kernel,
        grid=(1,),
        in_specs=_full_specs(ops),
        out_specs=[pl.BlockSpec((n,), lambda i: (0,)),
                   pl.BlockSpec((n,), lambda i: (0,)),
                   pl.BlockSpec((n,), lambda i: (0,)),
                   pl.BlockSpec((P,), lambda i: (0,)),
                   pl.BlockSpec((1,), lambda i: (0,))],
        out_shape=[jax.ShapeDtypeStruct((n,), x.dtype),
                   jax.ShapeDtypeStruct((n,), jnp.float32),
                   jax.ShapeDtypeStruct((n,), jnp.float32),
                   jax.ShapeDtypeStruct((P,), jnp.int32),
                   jax.ShapeDtypeStruct((1,), jnp.int32)],
        interpret=interpret,
    )(*ops)
    return x2, ch > 0.0, fr > 0.0, li, it[0]


def resident_megastep_pallas(x, changed, frontier, cm: dict, semiring: str,
                             max_steps: int, interpret: bool = False):
    """The resident narrow-phase megakernel: MULTIPLE supersteps run inside
    one launch with the mailbox held on chip, exiting on quiescence or the
    ``max_steps`` bound. Returns ``(x2, changed2, frontier2, iters,
    liters)`` — the exit state keeps the BSP contract, so the caller can
    hand off to a staged superstep at a phase boundary."""
    n = x.shape[0]
    P = cm["num_parts"]
    ops = _mega_operands(x, changed, frontier, cm)
    import functools
    kernel = functools.partial(_resident_kernel, semiring=semiring,
                               num_parts=P, max_steps=max_steps)
    x2, ch, fr, it, li = pl.pallas_call(
        kernel,
        grid=(1,),
        in_specs=_full_specs(ops),
        out_specs=[pl.BlockSpec((n,), lambda i: (0,)),
                   pl.BlockSpec((n,), lambda i: (0,)),
                   pl.BlockSpec((n,), lambda i: (0,)),
                   pl.BlockSpec((1,), lambda i: (0,)),
                   pl.BlockSpec((P,), lambda i: (0,))],
        out_shape=[jax.ShapeDtypeStruct((n,), x.dtype),
                   jax.ShapeDtypeStruct((n,), jnp.float32),
                   jax.ShapeDtypeStruct((n,), jnp.float32),
                   jax.ShapeDtypeStruct((1,), jnp.int32),
                   jax.ShapeDtypeStruct((P,), jnp.int32)],
        interpret=interpret,
    )(*ops)
    return x2, ch > 0.0, fr > 0.0, it[0], li
