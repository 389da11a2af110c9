"""Mailbox message routing — the superstep-boundary exchange.

The paper's Gopher workers aggregate messages per destination host and ship
them over TCP while compute proceeds. The TPU-native analogue is a fixed
capacity mailbox tensor routed with a single ``all_to_all`` per superstep
(or a transpose on the single-device/local backend), then a segment-combine
into each partition's inbox. Capacity = max messages between any partition
pair, precomputed by GoFS at build time — padding slots carry the combine
identity so they are no-ops.

These same primitives back the MoE token-dispatch in repro.models (the
framework's mailbox IS the expert all_to_all), per DESIGN.md §6.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.gofs.formats import PAD

COMBINE_IDENTITY = {"min": jnp.inf, "max": -jnp.inf, "sum": 0.0}
_SEGMENT = {
    "min": jax.ops.segment_min,
    "max": jax.ops.segment_max,
    "sum": jax.ops.segment_sum,
}


def build_outbox(vals: jnp.ndarray, re_src: jnp.ndarray, re_dst_part: jnp.ndarray,
                 re_dst_local: jnp.ndarray, re_slot: jnp.ndarray, send_mask: jnp.ndarray,
                 num_parts: int, cap: int, combine: str
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter per-remote-edge values into the (P_dst, cap) outbox of ONE
    source partition.

    vals: (r_max,) message value per remote edge (already ⊗-combined with the
    edge weight by the program). send_mask masks out pad slots / unchanged
    sources. Returns (out_vals, out_idx) of shape (num_parts, cap).
    """
    ident = COMBINE_IDENTITY[combine]
    valid = (re_src != PAD) & send_mask
    dst_p = jnp.where(valid, re_dst_part, 0)
    slot = jnp.where(valid, re_slot, 0)
    flat = dst_p * cap + slot
    flat = jnp.where(valid, flat, num_parts * cap)  # OOB -> dropped
    out_vals = jnp.full((num_parts * cap,), ident, vals.dtype)
    out_idx = jnp.full((num_parts * cap,), PAD, jnp.int32)
    out_vals = out_vals.at[flat].set(jnp.where(valid, vals, ident), mode="drop")
    out_idx = out_idx.at[flat].set(jnp.where(valid, re_dst_local, PAD), mode="drop")
    return out_vals.reshape(num_parts, cap), out_idx.reshape(num_parts, cap)


def combine_inbox(in_vals: jnp.ndarray, in_idx: jnp.ndarray, v_max: int,
                  combine: str) -> jnp.ndarray:
    """Segment-⊕ received messages into a dense (v_max,) inbox.

    in_vals/in_idx: (num_src, cap) from all source partitions. PAD indices map
    out-of-range and are dropped by the scatter.
    """
    idx = in_idx.reshape(-1)
    idx = jnp.where(idx == PAD, v_max, idx).astype(jnp.int32)
    seg = _SEGMENT[combine](in_vals.reshape(-1), idx, num_segments=v_max + 1)
    inbox = seg[:v_max]
    if combine in ("min", "max"):
        return inbox
    return inbox  # sum: empty segments are already 0


# ---------------- gather-form mailbox (the engine's hot path) ----------------
# The routing plan is fixed at GoFS build time, so both mailbox endpoints can
# be expressed as pure gathers through precomputed INVERSE maps (see
# engine._mailbox_inverse) instead of runtime scatters — scatter is the
# dominant superstep cost on XLA:CPU and serializes badly under a query axis.
# A further win: the destination indices never travel — only values are
# routed, halving mailbox traffic. The scatter forms above are kept as the
# reference oracles the gather forms are tested against.

_REDUCE = {"min": jnp.min, "max": jnp.max, "sum": jnp.sum}


def _at_combine(y, idx, vals, combine: str):
    ref = y.at[idx]
    if combine == "min":
        return ref.min(vals, mode="drop")
    if combine == "max":
        return ref.max(vals, mode="drop")
    return ref.add(vals, mode="drop")


def build_outbox_gather(vals: jnp.ndarray, send_mask: jnp.ndarray,
                        ob_inv: jnp.ndarray, num_parts: int, cap: int,
                        combine: str) -> jnp.ndarray:
    """Gather-form outbox for ONE source partition: each of the num_parts*cap
    slots pulls its remote edge's value (or the identity when empty/masked).
    The send mask is folded into vals BEFORE the slot gather — masking at
    r_max size beats masking at slot size, and only one gather runs."""
    ident = COMBINE_IDENTITY[combine]
    masked = jnp.where(send_mask, vals, ident)
    valid = ob_inv != PAD
    safe = jnp.where(valid, ob_inv, 0)
    return jnp.where(valid, masked[safe], ident).reshape(num_parts, cap)


def build_outbox_gather_batched(vals: jnp.ndarray, send_mask: jnp.ndarray,
                                ob_inv: jnp.ndarray, num_parts: int, cap: int,
                                combine: str) -> jnp.ndarray:
    """Q-query gather-form outbox, QUERY-TRAILING: vals/send are (r_max, Q)
    and each mailbox slot pulls its edge's contiguous Q-vector in one go —
    slot index arithmetic amortizes over the whole query batch. Returns
    (num_parts, cap*Q) with slot-major layout slot*Q + q per pair row."""
    ident = COMBINE_IDENTITY[combine]
    masked = jnp.where(send_mask, vals, ident)      # (r_max, Q)
    valid = ob_inv != PAD
    safe = jnp.where(valid, ob_inv, 0)
    out = jnp.where(valid[:, None], masked[safe, :], ident)
    return out.reshape(num_parts, cap * vals.shape[1])


def combine_inbox_gather(in_vals: jnp.ndarray, ib_lo: jnp.ndarray,
                         ib_hub_idx: jnp.ndarray, ib_hub: jnp.ndarray,
                         v_max: int, combine: str) -> jnp.ndarray:
    """Gather-form inbox combine: (num_src, cap) received values -> (v_max,).
    Each vertex pulls its (two-binned) feed list and reduces it densely; the
    handful of hub receivers merge back via a tiny hr_max-sized scatter."""
    ident = COMBINE_IDENTITY[combine]
    red = _REDUCE[combine]
    flat = in_vals.reshape(-1)

    def pull(m):
        valid = m != PAD
        return jnp.where(valid, flat[jnp.where(valid, m, 0)], ident)

    y = red(pull(ib_lo), axis=-1)                   # (v_max,)
    yh = red(pull(ib_hub), axis=-1)                 # (hr_max,)
    idx = jnp.where(ib_hub_idx != PAD, ib_hub_idx, v_max)
    return _at_combine(y, idx, yh, combine)


def combine_inbox_gather_batched(in_vals: jnp.ndarray, ib_lo: jnp.ndarray,
                                 ib_hub_idx: jnp.ndarray, ib_hub: jnp.ndarray,
                                 v_max: int, cap: int, combine: str
                                 ) -> jnp.ndarray:
    """Q-query gather-form combine, QUERY-TRAILING:
    (num_src, cap*Q) received -> (v_max, Q) inbox. Each vertex's feed slots
    pull contiguous Q-vectors; the reduce runs over the feed axis with Q on
    the lanes."""
    ident = COMBINE_IDENTITY[combine]
    red = _REDUCE[combine]
    num_src = in_vals.shape[0]
    Q = in_vals.shape[1] // cap
    flat = in_vals.reshape(num_src * cap, Q)

    def pull(m):
        valid = m != PAD
        safe = jnp.where(valid, m, 0)
        return jnp.where(valid[..., None], flat[safe, :], ident)

    y = red(pull(ib_lo), axis=1)                    # (v_max, m_lo, Q) -> (v_max, Q)
    yh = red(pull(ib_hub), axis=1)                  # (hr_max, Q)
    idx = jnp.where(ib_hub_idx != PAD, ib_hub_idx, v_max)
    return _at_combine(y, idx, yh, combine)


# ---------------- frontier-compacted sparse exchange (Gopher Wire) ----------
# The dense mailbox above ships every (src, dst) pair's full cap-slot row
# every superstep — identity-filled when the pair is quiescent. The compact
# forms below PACK each pair row to a dense prefix of its active slots
# (source vertex in the send set) plus a per-destination count header, so
# the payload that travels scales with |frontier| instead of P·cap. The
# compaction plan (kernels.ops.outbox_compact_plan: jnp oracle + Pallas
# kernel) yields inverse permutations pfwd/pinv; the sender packs by
# gathering through pfwd and the receiver reconstructs fixed slot positions
# by gathering through pinv — the O(count) dual of scattering the prefix
# back, so neither endpoint runs a runtime scatter. A real transport would
# ship the count-length prefix + its slot ids and rebuild pinv in O(count)
# on arrival; the byte model (core.engine.Telemetry.model_bytes) charges
# exactly that. Reconstruction is exact, so every downstream bit — combine,
# halt, results — is identical to the dense path.


def active_slots(send_mask: jnp.ndarray, ob_inv: jnp.ndarray,
                 num_parts: int, cap: int) -> jnp.ndarray:
    """(num_parts, cap) bool: mailbox slots of ONE source partition whose
    source vertex is in the send set this superstep. Q-batched send masks
    ((r_max, Q)) activate a slot when ANY lane sends — the contiguous
    Q-vector ships (or doesn't) as one unit."""
    valid = ob_inv != PAD
    safe = jnp.where(valid, ob_inv, 0)
    sm = send_mask if send_mask.ndim == 1 else jnp.any(send_mask, axis=-1)
    return (valid & sm[safe]).reshape(num_parts, cap)


def build_outbox_compact(vals: jnp.ndarray, send_mask: jnp.ndarray,
                         ob_inv: jnp.ndarray, num_parts: int, cap: int,
                         combine: str):
    """Frontier-compacted outbox for ONE source partition. Returns
    (pvals (num_parts, cap), pinv (num_parts, cap) int32,
    counts (num_parts,) int32): per destination row, the packed prefix of
    active slot values, the slot->prefix-position map, and the prefix
    length (the wire header — Σ counts is this partition's payload).

    Since Gopher Mesh the compaction plan is FUSED into the pack
    (kernels.ops.outbox_pack): packed positions fall out of the activity
    mask's prefix sum, so no argsort/one-hot plan pass runs."""
    from repro.kernels import ops
    ident = COMBINE_IDENTITY[combine]
    # the dense gather-form outbox IS the slot-value oracle; compaction only
    # adds the activity mask + the fused pack on top of it
    slot_vals = build_outbox_gather(vals, send_mask, ob_inv, num_parts, cap,
                                    combine)
    active = active_slots(send_mask, ob_inv, num_parts, cap)
    full = jnp.full((num_parts,), cap, jnp.int32)
    pvals, _, pinv, counts, _ = ops.outbox_pack(slot_vals, active, full,
                                                ident)
    return pvals, pinv, counts


def build_outbox_compact_batched(vals: jnp.ndarray, send_mask: jnp.ndarray,
                                 ob_inv: jnp.ndarray, num_parts: int,
                                 cap: int, combine: str):
    """Q-query compacted outbox, QUERY-TRAILING: vals/send are (r_max, Q);
    plan fused into the pack as in build_outbox_compact. Returns
    (pvals (num_parts, cap*Q), pinv (num_parts, cap), counts (num_parts,))."""
    from repro.kernels import ops
    ident = COMBINE_IDENTITY[combine]
    Q = vals.shape[1]
    slot_vals = build_outbox_gather_batched(
        vals, send_mask, ob_inv, num_parts, cap,
        combine).reshape(num_parts, cap, Q)
    active = active_slots(send_mask, ob_inv, num_parts, cap)
    full = jnp.full((num_parts,), cap, jnp.int32)
    pvals, _, pinv, counts, _ = ops.outbox_pack(slot_vals, active, full,
                                                ident)
    return pvals.reshape(num_parts, cap * Q), pinv, counts


def unpack_slots(pvals: jnp.ndarray, pinv: jnp.ndarray,
                 combine: str) -> jnp.ndarray:
    """Receiver side: (num_src, cap) packed prefixes + slot->position maps
    -> the dense slot-value array the gather-form inbox combine expects.
    A pure gather (each fixed slot pulls its packed value or the identity);
    bit-identical to what the dense exchange would have delivered."""
    ident = COMBINE_IDENTITY[combine]
    valid = pinv != PAD
    got = jnp.take_along_axis(pvals, jnp.where(valid, pinv, 0), axis=1)
    return jnp.where(valid, got, ident)


def unpack_slots_batched(pvals: jnp.ndarray, pinv: jnp.ndarray,
                         combine: str) -> jnp.ndarray:
    """Q-query receiver reconstruction: (num_src, cap*Q) packed + (num_src,
    cap) maps -> (num_src, cap*Q) dense, each slot pulling its contiguous
    Q-vector."""
    ident = COMBINE_IDENTITY[combine]
    num_src, cap = pinv.shape
    Q = pvals.shape[1] // cap
    pv = pvals.reshape(num_src, cap, Q)
    valid = pinv != PAD
    got = jnp.take_along_axis(pv, jnp.where(valid, pinv, 0)[..., None],
                              axis=1)
    return jnp.where(valid[..., None], got, ident).reshape(num_src, cap * Q)


def route_local(outbox_vals: jnp.ndarray) -> jnp.ndarray:
    """Local backend: outbox (P_src, P_dst, cap) -> inbox-side (P_dst, P_src, cap).
    A transpose IS the all_to_all when every partition lives on one device."""
    return outbox_vals.transpose(1, 0, 2)


def route_shard_map(outbox_vals: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """shard_map backend: per-device block is (v_local_src, P, cap) where
    P = D * v_local. Rearranged so ``all_to_all`` over the device axis delivers
    each device-pair payload, then reassembled as (v_local_dst, P_src, cap)."""
    v, P, cap = outbox_vals.shape
    D = P // v
    # (v_src, D*v_dst, cap) -> (D, v_src, v_dst, cap) -> a2a -> received
    x = outbox_vals.reshape(v, D, v, cap).transpose(1, 0, 2, 3)
    x = jax.lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0, tiled=True)
    # now x[d_src, v_src, v_dst, cap] on each destination device
    return x.reshape(D, v, v, cap).transpose(2, 0, 1, 3).reshape(v, D * v, cap)


# ---------------- capacity-tiered physical exchange (Gopher Mesh) -----------
# The compact exchange above shrinks the modeled PROTOCOL payload but its
# physical buffers keep the dense (P, cap) geometry (static shapes). The
# tiered router below makes the buffers XLA actually routes track the
# frontier: hot pairs ship their full dense cap row through one all_to_all
# over per-device-pair row blocks, warm/cold pairs ship a packed tier-width
# prefix (values + int32 slot ids) through a ppermute round-robin over only
# the nonzero device shifts, and structurally-empty pairs ship NOTHING.
# Every table is a trace-time constant (core.tiers.TierSchedule), so the
# routed shapes — the physical wire — are fixed per tier plan. The receiver
# rebuilds the exact dense slot array (each occupied slot is written once
# with its exact value, everything else holds the ⊕-identity), so as long
# as no pair overflowed its tier width every downstream bit is identical to
# the dense exchange; overflow is detected upstream (ops.outbox_pack) and
# repaired by the engine's dense fallback retry.


def route_tiered(dense_vals: jnp.ndarray, pvals: jnp.ndarray,
                 sids: jnp.ndarray, sched, combine: str,
                 axis_name=None) -> jnp.ndarray:
    """Physically route one superstep's outboxes along the tier schedule.

    dense_vals (v, P, cap, Qg)  gather-form dense slot values (hot rows
                                ship these as-is — no slot ids travel)
    pvals      (v, P, cap, Qg)  packed prefixes (warm/cold rows ship the
                                first tier-width columns)
    sids       (v, P, cap)      packed position -> slot id maps
    sched                       core.tiers.TierSchedule built for this mesh
    axis_name                   mesh axis ('shard_map' backend) or None
                                ('local' backend — D == 1, no collectives)

    Returns the received dense slot array (v, P, cap, Qg), bit-identical to
    what route_local/route_shard_map would have delivered when no pair
    overflowed its tier budget.
    """
    ident = COMBINE_IDENTITY[combine]
    v, P, cap, Qg = dense_vals.shape
    D = sched.D
    me = jax.lax.axis_index(axis_name) if (axis_name and D > 1) else 0
    dflat = dense_vals.reshape(v * P, cap, Qg)
    pflat = pvals.reshape(v * P, cap, Qg)
    iflat = sids.reshape(v * P, cap)
    out = jnp.full((v * P, cap, Qg), ident, dense_vals.dtype)

    # hot tier: one all_to_all over (D, h, cap) row blocks
    if sched.hot_h:
        st = jnp.asarray(sched.hot_send)[me]            # (D, h)
        buf = dflat[jnp.where(st == PAD, 0, st)]        # (D, h, cap, Qg)
        if axis_name is not None and D > 1:
            buf = jax.lax.all_to_all(buf, axis_name, split_axis=0,
                                     concat_axis=0, tiled=True)
        rt = jnp.asarray(sched.hot_recv)[me]            # (D, h)
        tgt = jnp.where(rt == PAD, v * P, rt).reshape(-1)
        out = out.at[tgt].set(buf.reshape(-1, cap, Qg), mode="drop")

    # residual hot rows (pair counts past the uniform all_to_all block):
    # same dense-row geometry — full cap, no slot ids — shipped by one
    # ppermute per device shift, so a skewed mesh pads only the devices
    # that own the excess instead of every all_to_all block
    for k, g, send_tab, recv_tab in sched.hot_res_shifts:
        st = jnp.asarray(send_tab)[me]                  # (g,)
        buf = dflat[jnp.where(st == PAD, 0, st)]        # (g, cap, Qg)
        if axis_name is not None and k % D != 0:
            perm = [(i, (i + k) % D) for i in range(D)]
            buf = jax.lax.ppermute(buf, axis_name, perm)
        rt = jnp.asarray(recv_tab)[me]                  # (g,)
        tgt = jnp.where(rt == PAD, v * P, rt)
        out = out.at[tgt].set(buf, mode="drop")

    # warm/cold tiers: ppermute round-robin over the nonzero device shifts
    flat = out.reshape(v * P * cap, Qg)
    for width, shifts in ((sched.warm_cap, sched.warm_shifts),
                          (1, sched.cold_shifts)):
        for k, g, send_tab, recv_tab in shifts:
            st = jnp.asarray(send_tab)[me]              # (g,)
            rows = jnp.where(st == PAD, 0, st)
            bv = pflat[rows][:, :width]                 # (g, width, Qg)
            bi = iflat[rows][:, :width]                 # (g, width)
            if axis_name is not None and k % D != 0:
                perm = [(i, (i + k) % D) for i in range(D)]
                bv = jax.lax.ppermute(bv, axis_name, perm)
                bi = jax.lax.ppermute(bi, axis_name, perm)
            rt = jnp.asarray(recv_tab)[me]              # (g,)
            ok = (rt != PAD)[:, None] & (bi != PAD)
            pos = jnp.where(ok, rt[:, None] * cap + bi, v * P * cap)
            flat = flat.at[pos.reshape(-1)].set(bv.reshape(-1, Qg),
                                                mode="drop")
    return flat.reshape(v, P, cap, Qg)
