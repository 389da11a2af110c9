"""Pallas TPU kernel: ELL semiring SpMV — the sub-graph sweep hotispot.

This is the compute kernel of the whole framework: every Gopher superstep is
one or more of these sweeps (min_plus = SSSP relaxation, max_first = connected
components label propagation, plus_times = PageRank pull).

TPU adaptation of the paper's "shared-memory traversal of the sub-graph":
the partition's vertex-state vector x stays resident in VMEM across the sweep
(sub-graphs fit fast memory — the paper's locality insight moved from
RAM-vs-disk down to VMEM-vs-HBM), while the ELL adjacency streams through in
row blocks. Row blocks are multiples of 8 sublanes; D is lane-padded by GoFS.
The gather from x is a dynamic VMEM gather (Mosaic `dynamic_gather` /
jnp.take); pad slots carry the ⊕-identity so no masking divergence exists —
the kernel is branch-free.

Grid: (V // block_v,). Working set per step: block_v*D*(4+4) bytes for
nbr+wgt + V*4 bytes for x, chosen so it stays well under VMEM (~16 MiB class).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.gofs.formats import PAD
from repro.kernels.ref import SEMIRINGS


def _combine(semiring: str, g, w, valid):
    if semiring == "min_plus":
        t = jnp.where(valid, g + w, jnp.inf)
        return jnp.min(t, axis=-1)
    if semiring == "max_first":
        t = jnp.where(valid, g, -jnp.inf)
        return jnp.max(t, axis=-1)
    if semiring == "plus_times":
        t = jnp.where(valid, g * w, 0.0)
        return jnp.sum(t, axis=-1)
    raise ValueError(semiring)


def _spmv_kernel(x_ref, nbr_ref, wgt_ref, y_ref, *, semiring: str):
    x = x_ref[...]                      # (V,) resident VMEM copy of vertex state
    idx = nbr_ref[...]                  # (BV, D) row block of ELL indices
    w = wgt_ref[...]                    # (BV, D)
    valid = idx != PAD
    safe = jnp.where(valid, idx, 0)
    g = jnp.take(x, safe.reshape(-1), axis=0).reshape(idx.shape)
    y_ref[...] = _combine(semiring, g, w, valid).astype(y_ref.dtype)


_IDENT = {"min_plus": float("inf"), "max_first": float("-inf"),
          "plus_times": 0.0}


def _spmv_frontier_kernel(x_ref, f_ref, nbr_ref, wgt_ref, y_ref, act_ref, *,
                          semiring: str):
    """Frontier-masked row block: the cheap frontier gather (f32 0/1) runs
    first; the expensive x-gather + semiring arithmetic is PREDICATED on the
    block containing at least one active row, so a quiesced region's blocks
    cost one small gather and a write — ~0 relative to the full sweep."""
    idx = nbr_ref[...]                  # (BV, D)
    valid = idx != PAD
    safe = jnp.where(valid, idx, 0)
    fg = jnp.take(f_ref[...], safe.reshape(-1), axis=0).reshape(idx.shape)
    row_active = jnp.any(valid & (fg > 0), axis=-1)     # (BV,)
    ident = _IDENT[semiring]

    @pl.when(jnp.any(row_active))
    def _compute():
        g = jnp.take(x_ref[...], safe.reshape(-1), axis=0).reshape(idx.shape)
        y = _combine(semiring, g, wgt_ref[...], valid)
        y_ref[...] = jnp.where(row_active, y, ident).astype(y_ref.dtype)

    @pl.when(~jnp.any(row_active))
    def _skip():
        y_ref[...] = jnp.full(y_ref.shape, ident, y_ref.dtype)

    act_ref[...] = row_active


@functools.partial(jax.jit, static_argnames=("semiring", "block_v", "interpret"))
def semiring_spmv_frontier_pallas(x: jnp.ndarray, frontier: jnp.ndarray,
                                  nbr: jnp.ndarray, wgt: jnp.ndarray,
                                  semiring: str, block_v: int = 256,
                                  interpret: bool = False):
    """Frontier-masked ELL sweep (idempotent semirings only): inactive rows
    return the ⊕-identity without paying the x-gather or the combine.
    Returns (y, row_active); see kernels.ref.semiring_spmv_frontier_ref for
    the exact contract."""
    assert semiring in ("min_plus", "max_first")
    v, d = nbr.shape
    bv = min(block_v, v)
    v_pad = -(-v // bv) * bv
    f = frontier.astype(jnp.float32)    # f32 0/1: TPU-friendly VMEM gather
    if v_pad != v:
        x_p = jnp.pad(x, (0, v_pad - v))
        f = jnp.pad(f, (0, v_pad - v))
        nbr = jnp.pad(nbr, ((0, v_pad - v), (0, 0)), constant_values=PAD)
        wgt = jnp.pad(wgt, ((0, v_pad - v), (0, 0)))
    else:
        x_p = x
    grid = (v_pad // bv,)
    y, act = pl.pallas_call(
        functools.partial(_spmv_frontier_kernel, semiring=semiring),
        grid=grid,
        in_specs=[
            pl.BlockSpec((v_pad,), lambda i: (0,)),        # x: VMEM-resident
            pl.BlockSpec((v_pad,), lambda i: (0,)),        # frontier bits
            pl.BlockSpec((bv, d), lambda i: (i, 0)),
            pl.BlockSpec((bv, d), lambda i: (i, 0)),
        ],
        out_specs=(pl.BlockSpec((bv,), lambda i: (i,)),
                   pl.BlockSpec((bv,), lambda i: (i,))),
        out_shape=(jax.ShapeDtypeStruct((v_pad,), x.dtype),
                   jax.ShapeDtypeStruct((v_pad,), jnp.bool_)),
        interpret=interpret,
    )(x_p, f, nbr, wgt)
    return y[:v], act[:v]


@functools.partial(jax.jit, static_argnames=("semiring", "block_v", "interpret"))
def semiring_spmv_pallas(x: jnp.ndarray, nbr: jnp.ndarray, wgt: jnp.ndarray,
                         semiring: str, block_v: int = 256,
                         interpret: bool = False) -> jnp.ndarray:
    """y[v] = ⊕_j ( x[nbr[v,j]] ⊗ wgt[v,j] ), Pallas ELL kernel.

    x: (V,) f32 — padded so V % block_v == 0 is NOT required (we pad here).
    """
    assert semiring in SEMIRINGS
    v, d = nbr.shape
    bv = min(block_v, v)
    v_pad = -(-v // bv) * bv
    if v_pad != v:
        x_p = jnp.pad(x, (0, v_pad - v))
        nbr = jnp.pad(nbr, ((0, v_pad - v), (0, 0)), constant_values=PAD)
        wgt = jnp.pad(wgt, ((0, v_pad - v), (0, 0)))
    else:
        x_p = x
    grid = (v_pad // bv,)
    y = pl.pallas_call(
        functools.partial(_spmv_kernel, semiring=semiring),
        grid=grid,
        in_specs=[
            pl.BlockSpec((v_pad,), lambda i: (0,)),        # x: full, VMEM-resident
            pl.BlockSpec((bv, d), lambda i: (i, 0)),       # nbr row block
            pl.BlockSpec((bv, d), lambda i: (i, 0)),       # wgt row block
        ],
        out_specs=pl.BlockSpec((bv,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((v_pad,), x.dtype),
        interpret=interpret,
    )(x_p, nbr, wgt)
    return y[:v]
