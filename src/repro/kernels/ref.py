"""Pure-jnp oracles for the Pallas kernels.

These are the ground truth the Pallas kernels are validated against
(interpret=True on CPU), and they double as the fast XLA:CPU execution path
for the engine when no TPU is present — same math, fusion left to XLA.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.gofs.formats import PAD

SEMIRINGS = ("min_plus", "max_first", "plus_times")


def semiring_spmv_ref(x: jnp.ndarray, nbr: jnp.ndarray, wgt: jnp.ndarray,
                      semiring: str) -> jnp.ndarray:
    """ELL semiring sweep: y[v] = ⊕_j ( x[nbr[v,j]] ⊗ wgt[v,j] ).

    x: (V,) float32; nbr: (V, D) int32 with PAD fill; wgt: (V, D) float32.
    Semirings: min_plus (SSSP), max_first (CC/MaxVertex — ⊗ ignores wgt),
    plus_times (PageRank).
    """
    valid = nbr != PAD
    safe = jnp.where(valid, nbr, 0)
    g = x[safe]  # (V, D)
    if semiring == "min_plus":
        t = jnp.where(valid, g + wgt, jnp.inf)
        return jnp.min(t, axis=1)
    if semiring == "max_first":
        t = jnp.where(valid, g, -jnp.inf)
        return jnp.max(t, axis=1)
    if semiring == "plus_times":
        t = jnp.where(valid, g * wgt, 0.0)
        return jnp.sum(t, axis=1)
    raise ValueError(f"unknown semiring {semiring}")


def outbox_compact_plan_ref(active: jnp.ndarray):
    """Per-row compaction plan for the frontier-compacted outbox (Gopher
    Wire). ``active``: (R, cap) bool — mailbox slots whose source vertex is
    in the send set this superstep. Returns

      pfwd   (R, cap) int32  packed position j -> slot id (PAD past count):
                             the j-th ACTIVE slot in ascending slot order —
                             the sender gathers values through this to build
                             the dense prefix that travels
      pinv   (R, cap) int32  slot id -> packed position (PAD if inactive):
                             the receiver reconstructs fixed slot positions
                             through this with a pure gather (the O(count)
                             dual of scattering the prefix back)
      counts (R,)   int32    prefix length per destination row — the wire
                             header; Σ counts is the superstep's payload

    pfwd and pinv are inverse permutations restricted to the active set;
    both derive from the same stable order so the Pallas kernel and this
    oracle are bit-identical.
    """
    cap = active.shape[-1]
    counts = jnp.sum(active, axis=-1).astype(jnp.int32)
    # stable sort of ~active: active slots first, ascending slot id
    order = jnp.argsort(~active, axis=-1, stable=True).astype(jnp.int32)
    j = jnp.arange(cap, dtype=jnp.int32)[None, :]
    pfwd = jnp.where(j < counts[:, None], order, PAD)
    csum = jnp.cumsum(active.astype(jnp.int32), axis=-1)
    pinv = jnp.where(active, csum - 1, PAD)
    return pfwd, pinv, counts


def outbox_pack_ref(slot_vals: jnp.ndarray, active: jnp.ndarray,
                    limit: jnp.ndarray, ident: float):
    """Fused compaction plan + value pack (Gopher Mesh). One pass replaces
    PR 3's argsort plan + take_along_axis gather: the packed position of an
    active slot is just its mask prefix-sum minus one, so the pack is a
    single masked scatter — no sort runs at all.

    slot_vals: (R, cap) or (R, cap, Q) dense slot values (the gather-form
    outbox); active: (R, cap) bool; limit: (R,) int32 per-row slot budget
    (the pair's tier width — positions at or past it are TRUNCATED, which
    the tiered exchange detects via ``over`` and repairs with the dense
    fallback retry). Returns

      pvals  like slot_vals   packed prefix, ident-filled past min(count,
                              limit)
      sids   (R, cap) int32   packed position -> slot id (PAD past the
                              prefix) — the receiver's scatter addresses
      pinv   (R, cap) int32   slot id -> packed position (PAD if inactive
                              or truncated) — the compact exchange's
                              receiver gather map
      counts (R,)   int32     UNtruncated active count (the profile /
                              overflow signal)
      over   (R,)   int32     1 where counts > limit (messages were dropped)
    """
    R, cap = active.shape
    act = active.astype(jnp.int32)
    csum = jnp.cumsum(act, axis=-1)
    counts = csum[:, -1]
    pos = csum - 1
    keep = active & (pos < limit[:, None])
    dest = jnp.where(keep, pos, cap)                  # cap -> dropped
    rows = jnp.arange(R, dtype=jnp.int32)[:, None]
    slot = jnp.broadcast_to(jnp.arange(cap, dtype=jnp.int32)[None, :],
                            (R, cap))
    sids = jnp.full((R, cap), PAD, jnp.int32).at[rows, dest].set(
        slot, mode="drop")
    pinv = jnp.where(keep, pos, PAD).astype(jnp.int32)
    pv = jnp.full(slot_vals.shape, ident, slot_vals.dtype)
    pvals = pv.at[rows, dest].set(slot_vals, mode="drop")
    over = (counts > limit).astype(jnp.int32)
    return pvals, sids, pinv, counts, over


def semiring_spmv_frontier_ref(x: jnp.ndarray, frontier: jnp.ndarray,
                               nbr: jnp.ndarray, wgt: jnp.ndarray,
                               semiring: str):
    """Frontier-masked ELL sweep: rows with NO active in-neighbor yield the
    ⊕-identity (the caller's element-wise combine keeps their old state);
    rows WITH one reduce their full neighbor list, exactly like the unmasked
    sweep. Restricted to the idempotent semirings — for those, combine(x,
    identity) == x, so masked and unmasked fixpoints are bitwise identical
    as long as the initial frontier covers every vertex whose value differs
    from the previous fixpoint.

    frontier: (V,) bool. Returns (y, row_active) — row_active is the next
    sweep's candidate set before the caller intersects it with "changed".

    It gathers the state and the frontier flags apart. It remains the
    reference of the Pallas kernel that the programs call on the Pallas
    route (ops.semiring_spmv_frontier); their jnp sweeps gather the
    frontier's values alone (kernels.megastep.sweep_flat,
    core.programs.SemiringProgram._masked_sweep).
    """
    assert semiring in ("min_plus", "max_first"), \
        "frontier masking requires an idempotent ⊕ (min/max)"
    valid = nbr != PAD
    safe = jnp.where(valid, nbr, 0)
    row_active = jnp.any(valid & frontier[safe], axis=1)
    g = x[safe]  # (V, D)
    if semiring == "min_plus":
        t = jnp.where(valid, g + wgt, jnp.inf)
        y = jnp.min(t, axis=1)
        return jnp.where(row_active, y, jnp.inf), row_active
    t = jnp.where(valid, g, -jnp.inf)
    y = jnp.max(t, axis=1)
    return jnp.where(row_active, y, -jnp.inf), row_active
