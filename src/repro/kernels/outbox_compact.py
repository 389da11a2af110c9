"""Pallas TPU kernel: outbox compaction plan — the Gopher Wire pack stage.

Each mailbox pair row (one destination partition's cap slots) is compacted
to a dense prefix of its ACTIVE slots before the superstep exchange, so the
payload that travels scales with the frontier instead of P·cap. The plan is
two inverse permutations plus a count header per row (see
kernels.ref.outbox_compact_plan_ref for the exact contract).

TPU formulation: compaction is a data-dependent permutation, which Mosaic
has no sort primitive for — but the STABLE ascending order over a 0/1 mask
is fully determined by the mask's inclusive prefix sum, and a prefix sum
over the lane axis is one matmul against a triangular ones matrix (MXU
work, no scan). From ``csum``:

    pinv[r, i] = csum[r, i] - 1              (elementwise — slot -> position)
    pfwd[r, j] = Σ_i i · [pinv[r, i] == j]   (one-hot contraction — position
                                              -> slot; ≤1 term survives)

Row blocks are (block_r, cap); the one-hot tensor is (block_r, cap, cap), so
block_r stays small (8) to bound VMEM. The kernel is branch-free.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.gofs.formats import PAD


def _compact_plan_kernel(act_ref, pfwd_ref, pinv_ref, cnt_ref):
    a = act_ref[...]                                    # (BR, C) f32 0/1
    br, c = a.shape
    tri = (jax.lax.broadcasted_iota(jnp.float32, (c, c), 0)
           <= jax.lax.broadcasted_iota(jnp.float32, (c, c), 1)
           ).astype(jnp.float32)
    csum = jnp.dot(a, tri)                              # inclusive prefix sum
    cnt = csum[:, -1]
    act = a > 0
    pos = csum - 1.0                                    # slot -> packed pos
    pinv_ref[...] = jnp.where(act, pos, PAD).astype(jnp.int32)
    # one-hot contraction: match[r, i, j] = active slot i lands at position j
    jgrid = jax.lax.broadcasted_iota(jnp.float32, (br, c, c), 2)
    match = jnp.where(act[:, :, None], (pos[:, :, None] == jgrid)
                      .astype(jnp.float32), 0.0)
    slot = jax.lax.broadcasted_iota(jnp.float32, (br, c, c), 1)
    fwd = jnp.sum(match * slot, axis=1)                 # (BR, C)
    has = jax.lax.broadcasted_iota(jnp.float32, (br, c), 1) < cnt[:, None]
    pfwd_ref[...] = jnp.where(has, fwd, PAD).astype(jnp.int32)
    cnt_ref[...] = cnt.astype(jnp.int32)


def _pack_kernel(act_ref, val_ref, lim_ref, pvals_ref, sids_ref, pinv_ref,
                 cnt_ref, over_ref, *, ident):
    """Fused spill kernel (Gopher Mesh): compaction plan + tier-width
    truncation + value pack + overflow detection in ONE branch-free pass.

    The plan half reuses the triangular-matmul prefix sum of
    ``_compact_plan_kernel``; the pack half replaces the one-hot·slot-id
    contraction with a select-and-reduce over the same (BR, C, C) match
    tensor so packed VALUES come out of the kernel too — a multiply would
    turn an active ±inf message (a legal value under min/max ⊕) into NaN at
    every other position of its row, so the value path selects instead of
    scaling. Positions at or past the row's ``lim`` budget are dropped and
    the row's overflow flag is raised; the engine's dense fallback retry
    makes that loss invisible to results.
    """
    a = act_ref[...]                                    # (BR, C) f32 0/1
    vals = val_ref[...]                                 # (BR, C) f32
    lim = lim_ref[...].astype(jnp.float32)              # (BR,)
    br, c = a.shape
    tri = (jax.lax.broadcasted_iota(jnp.float32, (c, c), 0)
           <= jax.lax.broadcasted_iota(jnp.float32, (c, c), 1)
           ).astype(jnp.float32)
    csum = jnp.dot(a, tri)                              # inclusive prefix sum
    cnt = csum[:, -1]
    act = a > 0
    pos = csum - 1.0                                    # slot -> packed pos
    keep = act & (pos < lim[:, None])
    pinv_ref[...] = jnp.where(keep, pos, PAD).astype(jnp.int32)
    # match[r, i, j] = kept slot i lands at packed position j (<=1 i survives
    # per (r, j), so the reduces below are exact selections)
    jgrid = jax.lax.broadcasted_iota(jnp.float32, (br, c, c), 2)
    match = keep[:, :, None] & (pos[:, :, None] == jgrid)
    slot = jax.lax.broadcasted_iota(jnp.float32, (br, c, c), 1)
    has = (jax.lax.broadcasted_iota(jnp.float32, (br, c), 1)
           < jnp.minimum(cnt, lim)[:, None])
    sids = jnp.sum(jnp.where(match, slot, 0.0), axis=1)
    sids_ref[...] = jnp.where(has, sids, PAD).astype(jnp.int32)
    pv = jnp.sum(jnp.where(match, vals[:, :, None], 0.0), axis=1)
    pvals_ref[...] = jnp.where(has, pv, ident)
    cnt_ref[...] = cnt.astype(jnp.int32)
    over_ref[...] = (cnt > lim).astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("ident", "block_r", "interpret"))
def outbox_pack_pallas(slot_vals: jnp.ndarray, active: jnp.ndarray,
                       limit: jnp.ndarray, ident: float, block_r: int = 8,
                       interpret: bool = False):
    """(R, cap) slot values + active mask + per-row budget ->
    (pvals, sids, pinv, counts, over); bit-identical to
    kernels.ref.outbox_pack_ref (single-query form)."""
    r, cap = active.shape
    br = min(block_r, r)
    r_pad = -(-r // br) * br
    a = active.astype(jnp.float32)
    v = slot_vals.astype(jnp.float32)
    lim = limit.astype(jnp.int32)
    if r_pad != r:
        a = jnp.pad(a, ((0, r_pad - r), (0, 0)))
        v = jnp.pad(v, ((0, r_pad - r), (0, 0)))
        lim = jnp.pad(lim, (0, r_pad - r))
    grid = (r_pad // br,)
    row = pl.BlockSpec((br, cap), lambda i: (i, 0))
    vec = pl.BlockSpec((br,), lambda i: (i,))
    pvals, sids, pinv, cnt, over = pl.pallas_call(
        functools.partial(_pack_kernel, ident=ident),
        grid=grid,
        in_specs=[row, row, vec],
        out_specs=(row, row, row, vec, vec),
        out_shape=(jax.ShapeDtypeStruct((r_pad, cap), jnp.float32),
                   jax.ShapeDtypeStruct((r_pad, cap), jnp.int32),
                   jax.ShapeDtypeStruct((r_pad, cap), jnp.int32),
                   jax.ShapeDtypeStruct((r_pad,), jnp.int32),
                   jax.ShapeDtypeStruct((r_pad,), jnp.int32)),
        interpret=interpret,
    )(a, v, lim)
    return (pvals[:r], sids[:r], pinv[:r], cnt[:r], over[:r])


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def outbox_compact_plan_pallas(active: jnp.ndarray, block_r: int = 8,
                               interpret: bool = False):
    """(R, cap) bool active mask -> (pfwd, pinv, counts); bit-identical to
    kernels.ref.outbox_compact_plan_ref."""
    r, cap = active.shape
    br = min(block_r, r)
    r_pad = -(-r // br) * br
    a = active.astype(jnp.float32)
    if r_pad != r:
        a = jnp.pad(a, ((0, r_pad - r), (0, 0)))
    grid = (r_pad // br,)
    pfwd, pinv, cnt = pl.pallas_call(
        _compact_plan_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((br, cap), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((br, cap), lambda i: (i, 0)),
                   pl.BlockSpec((br, cap), lambda i: (i, 0)),
                   pl.BlockSpec((br,), lambda i: (i,))),
        out_shape=(jax.ShapeDtypeStruct((r_pad, cap), jnp.int32),
                   jax.ShapeDtypeStruct((r_pad, cap), jnp.int32),
                   jax.ShapeDtypeStruct((r_pad,), jnp.int32)),
        interpret=interpret,
    )(a)
    return pfwd[:r], pinv[:r], cnt[:r]
