"""jit'd dispatch wrappers for the kernels.

Every dispatcher runs the pure-jnp route (same math, XLA-fused) unless the
caller asks for ``backend="pallas"``. The Pallas kernels run only on that
request, compiled for the chip, or in interpret mode when the caller also
passes ``interpret=True`` (the kernel parity tests off the chip). Nothing
here looks at the platform: a requested kernel that the chip's compiler
refuses raises instead of falling back.

``multibin_spmv`` is the degree-binned variant for powerlaw graphs (LJ-like):
rows are bucketed by degree into <=3 ELL bins so padding waste stays bounded;
results scatter back by row index.
"""
from __future__ import annotations

from typing import Sequence

import jax.numpy as jnp
import numpy as np

from repro.gofs.formats import PAD
from repro.kernels.outbox_compact import (outbox_compact_plan_pallas,
                                          outbox_pack_pallas)
from repro.kernels.ref import (outbox_compact_plan_ref,
                               outbox_pack_ref, semiring_spmv_frontier_ref,
                               semiring_spmv_ref)
from repro.kernels.semiring_spmv import (semiring_spmv_frontier_pallas,
                                         semiring_spmv_pallas)


def semiring_spmv(x: jnp.ndarray, nbr: jnp.ndarray, wgt: jnp.ndarray,
                  semiring: str, backend: str = "jnp",
                  block_v: int = 256, interpret: bool = False) -> jnp.ndarray:
    if backend == "jnp":
        return semiring_spmv_ref(x, nbr, wgt, semiring)
    if backend == "pallas":
        return semiring_spmv_pallas(x, nbr, wgt, semiring, block_v=block_v,
                                    interpret=interpret)
    raise ValueError(f"unknown backend {backend}")


def semiring_spmv_frontier(x: jnp.ndarray, frontier: jnp.ndarray,
                           nbr: jnp.ndarray, wgt: jnp.ndarray, semiring: str,
                           backend: str = "jnp", block_v: int = 256,
                           interpret: bool = False):
    """Frontier-masked ELL sweep (idempotent ⊕ only): rows with no active
    in-neighbor yield the identity at ~0 cost (the Pallas path predicates the
    gather+combine per row block on the frontier). Returns (y, row_active).

    The programs call it only on the Pallas route
    (``SemiringProgram(spmv_backend="pallas")``); their jnp route gathers
    the frontier's values alone (core.programs.SemiringProgram.
    _masked_sweep). The jnp path here remains the Pallas route's
    reference."""
    if backend == "jnp":
        return semiring_spmv_frontier_ref(x, frontier, nbr, wgt, semiring)
    if backend == "pallas":
        return semiring_spmv_frontier_pallas(
            x, frontier, nbr, wgt, semiring, block_v=block_v,
            interpret=interpret)
    raise ValueError(f"unknown backend {backend}")


def outbox_compact_plan(active: jnp.ndarray, backend: str = "jnp",
                        block_r: int = 8, interpret: bool = False):
    """Frontier-compaction plan for the sparse mailbox exchange (Gopher
    Wire): (R, cap) active-slot mask -> (pfwd, pinv, counts). See
    kernels.ref.outbox_compact_plan_ref for the contract; the Pallas path
    is bit-identical (stable ascending order both ways)."""
    if backend == "jnp":
        return outbox_compact_plan_ref(active)
    if backend == "pallas":
        return outbox_compact_plan_pallas(active, block_r=block_r,
                                          interpret=interpret)
    raise ValueError(f"unknown backend {backend}")


def outbox_pack(slot_vals: jnp.ndarray, active: jnp.ndarray,
                limit: jnp.ndarray, ident: float,
                backend: str = "jnp", block_r: int = 8,
                interpret: bool = False):
    """Fused compaction plan + value pack + spill detection (Gopher Mesh):
    (R, cap[, Q]) slot values + (R, cap) active mask + (R,) tier budget ->
    (pvals, sids, pinv, counts, over). See kernels.ref.outbox_pack_ref for
    the contract. This replaces PR 3's separate argsort/one-hot plan pass:
    the jnp path is one cumsum + one masked scatter, the Pallas path is the
    single fused spill kernel (kernels.outbox_compact.outbox_pack_pallas).

    Q-batched values keep the fused kernel for the plan half (the plan is
    query-independent) and pack the contiguous Q-vectors with the same
    masked scatter the jnp path uses — the per-lane value DMA dominates
    there, not the plan.
    """
    if backend == "jnp":
        return outbox_pack_ref(slot_vals, active, limit, ident)
    if backend == "pallas":
        if slot_vals.ndim == 2:
            return outbox_pack_pallas(slot_vals, active, limit, ident,
                                      block_r=block_r, interpret=interpret)
        # Q-batched: plan (+ per-row truncation/overflow) from the fused
        # kernel, Q-vector pack as a masked scatter through pinv
        _, sids, pinv, counts, over = outbox_pack_pallas(
            jnp.zeros(active.shape, jnp.float32), active, limit, ident,
            block_r=block_r, interpret=interpret)
        r, cap = active.shape
        rows = jnp.arange(r, dtype=jnp.int32)[:, None]
        dest = jnp.where(pinv != PAD, pinv, cap)
        pvals = jnp.full(slot_vals.shape, ident, slot_vals.dtype
                         ).at[rows, dest].set(slot_vals, mode="drop")
        return pvals, sids, pinv, counts, over
    raise ValueError(f"unknown backend {backend}")


def binned_ell_spmv_multi(x: jnp.ndarray, nbr_lo: jnp.ndarray,
                          wgt_lo: jnp.ndarray, hub_idx: jnp.ndarray,
                          hub_nbr: jnp.ndarray, hub_wgt: jnp.ndarray,
                          semiring: str) -> jnp.ndarray:
    """Multi-vector two-bin ELL sweep: x is (V, Q) — Q problem instances over
    one topology, QUERY-TRAILING so every neighbor gather pulls a contiguous
    Q-vector (index arithmetic and bounds checks amortize Q-fold; Q rides the
    SIMD/VPU lane dimension). The serving hot path.
    """
    v_max = x.shape[0]

    def sweep(nbr, wgt):
        valid = nbr != PAD
        g = x[jnp.where(valid, nbr, 0), :]               # (rows, D, Q)
        if semiring == "min_plus":
            t = jnp.where(valid[..., None], g + wgt[..., None], jnp.inf)
            return jnp.min(t, axis=1)
        if semiring == "max_first":
            t = jnp.where(valid[..., None], g, -jnp.inf)
            return jnp.max(t, axis=1)
        if semiring == "plus_times":
            t = jnp.where(valid[..., None], g * wgt[..., None], 0.0)
            return jnp.sum(t, axis=1)
        raise ValueError(f"unknown semiring {semiring}")

    y = sweep(nbr_lo, wgt_lo)                            # (V, Q)
    yh = sweep(hub_nbr, hub_wgt)                         # (H, Q)
    idx = jnp.where(hub_idx != PAD, hub_idx, v_max)
    ref = y.at[idx]
    if semiring == "min_plus":
        return ref.min(yh, mode="drop")
    if semiring == "max_first":
        return ref.max(yh, mode="drop")
    return ref.add(yh, mode="drop")


def binned_ell_spmv_multi_frontier(x: jnp.ndarray, frontier: jnp.ndarray,
                                   nbr_lo: jnp.ndarray, wgt_lo: jnp.ndarray,
                                   hub_idx: jnp.ndarray, hub_nbr: jnp.ndarray,
                                   hub_wgt: jnp.ndarray,
                                   semiring: str) -> jnp.ndarray:
    """Frontier-masked two-bin multi-vector sweep: frontier is (V, Q) bool,
    per query lane. A (row, q) pair with no active in-neighbor in lane q
    yields the ⊕-identity (the caller's combine keeps its old state), so a
    query whose region has quiesced stops paying for that region's rows.
    Idempotent semirings only — see semiring_spmv_frontier_ref."""
    assert semiring in ("min_plus", "max_first")
    v_max = x.shape[0]
    ident = jnp.inf if semiring == "min_plus" else -jnp.inf

    def sweep(nbr, wgt):
        valid = nbr != PAD
        safe = jnp.where(valid, nbr, 0)
        act = jnp.any(valid[..., None] & frontier[safe, :], axis=1)  # (rows, Q)
        g = x[safe, :]                                   # (rows, D, Q)
        if semiring == "min_plus":
            t = jnp.where(valid[..., None], g + wgt[..., None], jnp.inf)
            y = jnp.min(t, axis=1)
        else:
            t = jnp.where(valid[..., None], g, -jnp.inf)
            y = jnp.max(t, axis=1)
        return jnp.where(act, y, ident)

    y = sweep(nbr_lo, wgt_lo)                            # (V, Q)
    yh = sweep(hub_nbr, hub_wgt)                         # (H, Q)
    idx = jnp.where(hub_idx != PAD, hub_idx, v_max)
    ref = y.at[idx]
    if semiring == "min_plus":
        return ref.min(yh, mode="drop")
    return ref.max(yh, mode="drop")


# ---------------- multi-bin ELL (degree-skew mitigation) ----------------

def bin_rows_by_degree(nbr: np.ndarray, wgt: np.ndarray,
                       boundaries: Sequence[int] = (8, 64)) -> list:
    """Host-side: split ELL rows into degree bins [(rows, nbr_b, wgt_b), ...].

    Each bin's width is its own max degree (lane-padded), so a powerlaw graph
    pays mega-hub padding only for the handful of hub rows.
    """
    deg = (nbr != PAD).sum(1)
    edges = [0, *boundaries, nbr.shape[1] + 1]
    bins = []
    for lo, hi in zip(edges[:-1], edges[1:]):
        rows = np.flatnonzero((deg >= lo) & (deg < hi))
        if rows.size == 0:
            continue
        w = max(int(deg[rows].max()), 1)
        w = -(-w // 8) * 8
        bins.append((rows.astype(np.int32),
                     np.ascontiguousarray(nbr[rows, :w]),
                     np.ascontiguousarray(wgt[rows, :w])))
    return bins


def multibin_spmv(x: jnp.ndarray, bins: list, v_out: int, semiring: str,
                  backend: str = "jnp") -> jnp.ndarray:
    """Semiring sweep over degree-binned ELL; scatter bin results to rows."""
    ident = {"min_plus": jnp.inf, "max_first": -jnp.inf, "plus_times": 0.0}[semiring]
    y = jnp.full((v_out,), ident, x.dtype)
    for rows, nbr_b, wgt_b in bins:
        yb = semiring_spmv(x, jnp.asarray(nbr_b), jnp.asarray(wgt_b), semiring,
                           backend=backend)
        y = y.at[rows].set(yb)
    return y
