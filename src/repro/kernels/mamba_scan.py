"""Pallas TPU kernel: fused Mamba1 (S6) selective scan — §Perf F5.

The XLA-level chunked scan (models.layers.mamba1_mixer) must materialize the
(B, Q, di, N) state expansion at fusion boundaries every chunk — measured as
the dominant memory term of falcon-mamba-7b train_4k even after F1–F4
(EXPERIMENTS.md). This kernel keeps the recurrent state h (BD, N) in VMEM for
the whole sequence: HBM traffic collapses to the δ/x/B/C input streams and
the y output stream, ≈ (3·L·BD + 2·L·N + L·BD) elements per block instead of
O(L·BD·N) — a ~2·N ≈ 32× traffic reduction.

Grid: (B, di/BD) — each program instance owns a channel block and loops the
sequence with `lax.fori_loop`, state resident. Forward only: the training
backward needs the reverse-sweep kernel (documented follow-up); the serving
path (prefill/decode) and inference-only deployments use it as-is.

Validated in interpret mode against a step-by-step recurrence oracle
(tests/test_kernels.py::test_mamba_scan_kernel*).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _scan_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, y_ref):
    # blocks: x/dt (1, L, BD); b/c (1, L, N); a (BD, N); y (1, L, BD)
    L = x_ref.shape[1]
    A = a_ref[...].astype(jnp.float32)               # (BD, N)
    BD, N = A.shape

    def step(l, h):
        dt = dt_ref[0, l].astype(jnp.float32)        # (BD,)
        xv = x_ref[0, l].astype(jnp.float32)
        bv = b_ref[0, l].astype(jnp.float32)         # (N,)
        cv = c_ref[0, l].astype(jnp.float32)
        da = jnp.exp(dt[:, None] * A)                # (BD, N)
        h = da * h + (dt * xv)[:, None] * bv[None, :]
        y_ref[0, l] = (h @ cv).astype(y_ref.dtype)   # (BD,)
        return h

    jax.lax.fori_loop(0, L, step, jnp.zeros((BD, N), jnp.float32))


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def mamba1_scan_pallas(x, delta, Bv, Cv, A, block_d: int = 128,
                       interpret: bool = False):
    """y[b,l,d] = Σ_n h[b,l,d,n]·C[b,l,n] with
    h[b,l] = exp(δ[b,l]⊗A)·h[b,l-1] + (δ[b,l]·x[b,l])⊗B[b,l].

    x, delta: (B, L, D); Bv, Cv: (B, L, N); A: (D, N) (negative decays).
    """
    B, L, D = x.shape
    N = A.shape[1]
    bd = min(block_d, D)
    while D % bd:
        bd -= 1
    grid = (B, D // bd)
    return pl.pallas_call(
        _scan_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, L, bd), lambda b, d: (b, 0, d)),   # x
            pl.BlockSpec((1, L, bd), lambda b, d: (b, 0, d)),   # delta
            pl.BlockSpec((1, L, N), lambda b, d: (b, 0, 0)),    # B
            pl.BlockSpec((1, L, N), lambda b, d: (b, 0, 0)),    # C
            pl.BlockSpec((bd, N), lambda b, d: (d, 0)),         # A
        ],
        out_specs=pl.BlockSpec((1, L, bd), lambda b, d: (b, 0, d)),
        out_shape=jax.ShapeDtypeStruct((B, L, D), x.dtype),
        interpret=interpret,
    )(x, delta, Bv, Cv, A)


def mamba1_scan_ref(x, delta, Bv, Cv, A):
    """Step-by-step oracle (pure jnp)."""
    B, L, D = x.shape
    N = A.shape[1]

    def step(h, inp):
        xv, dt, bv, cv = inp
        da = jnp.exp(dt[:, :, None] * A)                        # (B, D, N)
        h = da * h + (dt * xv)[:, :, None] * bv[:, None, :]
        return h, jnp.einsum("bdn,bn->bd", h, cv)

    h0 = jnp.zeros((B, D, N), jnp.float32)
    _, ys = jax.lax.scan(step, h0,
                         (x.transpose(1, 0, 2).astype(jnp.float32),
                          delta.transpose(1, 0, 2).astype(jnp.float32),
                          Bv.transpose(1, 0, 2).astype(jnp.float32),
                          Cv.transpose(1, 0, 2).astype(jnp.float32)))
    return ys.transpose(1, 0, 2).astype(x.dtype)
