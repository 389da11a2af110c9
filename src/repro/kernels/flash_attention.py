"""Pallas TPU flash attention (fwd) — the fused-attention hot-spot kernel.

The dry-run roofline (EXPERIMENTS.md §Perf, llama3 iterations) shows the
XLA-level flash formulation is bound by score-block streaming: every
(qb × kb) f32 score tile crosses the fusion boundary to HBM ~3× (fwd + bwd
recompute + grads) — ~6.5 TB/device/step on llama3-8b train_4k. This kernel
keeps the running-softmax state and score tiles in VMEM: HBM traffic becomes
just Q/K/V/O streams (arithmetic intensity ≈ d_head · intensity of a matmul).

Grid: (batch·kv_heads, nq) — one program instance owns one q block for one
(batch, kv-head) pair and loops the kv blocks with `lax.fori_loop`, exactly
the kernelized version of layers.flash_attention's scan. GQA handled by the
g = H/KV query-group dim riding along in the block.

Validated in interpret mode against layers.flash_attention / the naive oracle
(tests/test_kernels.py::test_flash_kernel_*). On CPU boxes the model code
dispatches to the jnp flash path; on TPU this kernel is selected.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, causal: bool,
                  window: Optional[int], q_offset: int, kb: int,
                  scale: float):
    # q_ref: (1, qb, g, dh); k_ref/v_ref: (1, Sk, dh); o_ref: (1, qb, g, dh)
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale          # (qb, g, dh)
    qb, g, dh = q.shape
    sk = k_ref.shape[1]
    nkb = sk // kb
    qpos = q_offset + qi * qb + jax.lax.broadcasted_iota(jnp.int32, (qb, 1), 0)

    def body(ki, carry):
        m, l, acc = carry
        k = jax.lax.dynamic_slice_in_dim(k_ref[0], ki * kb, kb).astype(jnp.float32)
        v = jax.lax.dynamic_slice_in_dim(v_ref[0], ki * kb, kb).astype(jnp.float32)
        kpos = ki * kb + jax.lax.broadcasted_iota(jnp.int32, (1, kb), 1)
        s = jnp.einsum("qgd,sd->gqs", q, k)           # (g, qb, kb)
        mask = jnp.ones((qb, kb), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask[None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(mask[None], p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = corr * l + jnp.sum(p, axis=-1)
        acc_new = corr[..., None] * acc + jnp.einsum("gqs,sd->gqd", p, v)
        return m_new, l_new, acc_new

    m0 = jnp.full((g, qb), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((g, qb), jnp.float32)
    a0 = jnp.zeros((g, qb, dh), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nkb, body, (m0, l0, a0))
    out = acc / jnp.maximum(l[..., None], 1e-30)      # (g, qb, dh)
    o_ref[0] = out.transpose(1, 0, 2).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_offset",
                                             "q_block", "kv_block", "interpret"))
def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           window: Optional[int] = None, q_offset: int = 0,
                           q_block: int = 256, kv_block: int = 256,
                           interpret: bool = False):
    """q: (B, Sq, H, dh); k, v: (B, Sk, KV, dh). Returns (B, Sq, H, dh)."""
    B, Sq, H, dh = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    g = H // KV
    qb = min(q_block, Sq)
    while Sq % qb:
        qb -= 1
    kb = min(kv_block, Sk)
    while Sk % kb:
        kb -= 1
    nq = Sq // qb
    scale = 1.0 / math.sqrt(dh)

    # layout: fold (B, KV) into the grid's first axis
    qr = q.reshape(B, Sq, KV, g, dh).transpose(0, 2, 1, 3, 4) \
          .reshape(B * KV, Sq, g, dh)
    kr = k.transpose(0, 2, 1, 3).reshape(B * KV, Sk, dh)
    vr = v.transpose(0, 2, 1, 3).reshape(B * KV, Sk, dh)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, causal=causal, window=window,
                          q_offset=q_offset, kb=kb, scale=scale),
        grid=(B * KV, nq),
        in_specs=[
            pl.BlockSpec((1, qb, g, dh), lambda b, i: (b, i, 0, 0)),
            pl.BlockSpec((1, Sk, dh), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Sk, dh), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, qb, g, dh), lambda b, i: (b, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KV, Sq, g, dh), q.dtype),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, KV, Sq, g, dh).transpose(0, 2, 1, 3, 4) \
              .reshape(B, Sq, H, dh)
