"""Model building blocks: norms, RoPE/M-RoPE, flash-style attention, MLP,
MoE (mailbox-dispatch), Mamba1 (S6) and Mamba2 (SSD) mixers.

All blocks are pure functions over explicit param pytrees; layer stacking and
scan live in the per-family model files. Sharding is steered with logical-axis
constraints from repro.models.sharding.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.sharding import shard

# ---------------------------------------------------------------- norms

def rms_norm(x, scale, eps: float = 1e-6):
    # full f32 upcast: measured BETTER than bf16-elementwise scaling (the
    # f32 chain fuses into one kernel; §Perf L2 refuted — see EXPERIMENTS.md)
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layer_norm(x, scale, bias, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


# ---------------------------------------------------------------- RoPE

def rope_freqs(dh: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, dh, 2, dtype=np.float32) / dh))


def apply_rope(x, pos, theta: float):
    """x: (..., S, H, dh); pos: broadcastable to (..., S)."""
    dh = x.shape[-1]
    inv = jnp.asarray(rope_freqs(dh, theta))
    ang = pos[..., None].astype(jnp.float32) * inv          # (..., S, dh/2)
    ang = ang[..., None, :]                                  # add head dim
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, pos3, theta: float, sections=(0.25, 0.375, 0.375)):
    """Qwen2-VL M-RoPE: rotary frequency dims split into (t, h, w) sections,
    each rotated by its own position stream. pos3: (3, ..., S)."""
    dh = x.shape[-1]
    half = dh // 2
    cuts = np.cumsum([int(half * s) for s in sections])[:-1]
    inv = jnp.asarray(rope_freqs(dh, theta))                 # (half,)
    angs = pos3[..., None].astype(jnp.float32) * inv         # (3, ..., S, half)
    pieces = jnp.split(angs, cuts, axis=-1)
    ang = jnp.concatenate([pieces[i][i] for i in range(3)], axis=-1)  # (..., S, half)
    ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- attention

def _pick_block(s: int, pref: int) -> int:
    b = min(pref, s)
    while s % b:
        b -= 1
    return b


def flash_attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
                    q_offset=0, q_block: int = 512, kv_block: int = 1024,
                    use_kernel: bool = False):
    """Blockwise streaming attention (online softmax) — O(S) memory.

    q: (B, Sq, H, dh); k, v: (B, Sk, KV, dh) with H % KV == 0 (GQA).
    ``q_offset`` is the absolute position of q[0] (prefill continuation).
    ``window``: sliding-window size (keys with q_pos - k_pos >= window masked).

    ``use_kernel=True`` dispatches to the fused Pallas kernel
    (repro.kernels.flash_attention), compiled for the chip; the default is
    the XLA-level loop below.
    """
    if use_kernel:
        from repro.kernels.flash_attention import flash_attention_pallas
        return flash_attention_pallas(
            q, k, v, causal=causal, window=window, q_offset=q_offset)
    B, Sq, H, dh = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    g = H // KV
    scale = 1.0 / math.sqrt(dh)
    qb = _pick_block(Sq, q_block)
    kb = _pick_block(Sk, kv_block)
    nq, nk = Sq // qb, Sk // kb

    # K/V stay in the compute dtype (bf16) in HBM; the MXU contracts
    # bf16×bf16 -> f32 natively (preferred_element_type), halving attention
    # HBM traffic and K/V collective bytes (§Perf L1)
    qr = q.reshape(B, nq, qb, KV, g, dh)
    kr = k.reshape(B, nk, kb, KV, dh)
    vr = v.reshape(B, nk, kb, KV, dh)

    def q_step(_, qi):
        qblk = qr[:, qi]                                     # (B, qb, KV, g, dh)
        qpos = q_offset + qi * qb + jnp.arange(qb)

        # checkpoint: flash-bwd semantics — recompute scores/masks per block
        # in the backward instead of stashing (nq, nk, B, ...) residuals
        @jax.checkpoint
        def kv_step(carry, ki):
            m, l, acc = carry
            kblk, vblk = kr[:, ki], vr[:, ki]
            kpos = ki * kb + jnp.arange(kb)
            s = jnp.einsum("bqkgd,bskd->bkgqs", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            mask = jnp.ones((qb, kb), bool)
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            if window is not None:
                mask &= (qpos[:, None] - kpos[None, :]) < window
            s = jnp.where(mask[None, None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            # guard fully-masked rows (m_new = -inf)
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(mask[None, None, None], p, 0.0)
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            l_new = corr * l + jnp.sum(p, axis=-1)
            acc_new = corr[..., None] * acc + jnp.einsum(
                "bkgqs,bskd->bkgqd", p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, g, qb), -jnp.inf)
        l0 = jnp.zeros((B, KV, g, qb))
        a0 = jnp.zeros((B, KV, g, qb, dh))
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nk))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return None, out.transpose(0, 3, 1, 2, 4).reshape(B, qb, H, dh)  # (B,qb,H,dh)

    _, blocks = jax.lax.scan(q_step, None, jnp.arange(nq))   # (nq, B, qb, H, dh)
    out = blocks.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, dh)
    return out.astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cache_len, *, window: Optional[int] = None):
    """Single-token attention against a KV cache.

    q: (B, H, dh); caches: (B, S, KV, dh); cache_len: scalar — #valid entries
    (the new token's k/v must already be written at cache_len - 1).
    """
    B, H, dh = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    g = H // KV
    scale = 1.0 / math.sqrt(dh)
    qr = (q.reshape(B, KV, g, dh).astype(jnp.float32)) * scale
    s = jnp.einsum("bkgd,bskd->bkgs", qr, k_cache.astype(jnp.float32))
    kpos = jnp.arange(S)
    mask = kpos < cache_len
    if window is not None:
        mask &= kpos >= (cache_len - window)
    s = jnp.where(mask[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, H, dh).astype(q.dtype)


# ---------------------------------------------------------------- attention block

def attn_proj_params(key, cfg, cross: bool = False):
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    std = d ** -0.5
    p = {
        "wq": jax.random.normal(k1, (d, h, dh)) * std,
        "wk": jax.random.normal(k2, (d, kv, dh)) * std,
        "wv": jax.random.normal(k3, (d, kv, dh)) * std,
        "wo": jax.random.normal(k4, (h, dh, d)) * (h * dh) ** -0.5,
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, dh))
        p["bk"] = jnp.zeros((kv, dh))
        p["bv"] = jnp.zeros((kv, dh))
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((dh,))
        p["k_norm"] = jnp.zeros((dh,))
    return p


def qkv(x, p, cfg):
    from repro.models.sharding import _state
    tp_sz = getattr(_state, "sizes", {}).get("model", 1)
    n_heads = p["wq"].shape[1]
    fold = (cfg.attn_batch_fold and tp_sz > 1 and n_heads % tp_sz != 0
            and x.shape[1] > 1)
    if fold:
        # heads < TP (gemma3 h=8, whisper h=12): batch-fold the attention
        # block's INPUT over ('pod','data','model') so projections +
        # attention run data-parallel on all chips instead of replicated
        # across the model axis (§Perf W2)
        x = shard(x, "batch_tp", None, None)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if not fold:
        q = shard(q, "batch", "seq", "tp", None)
        k = shard(k, "batch", "seq", "tp", None)
    return q, k, v


def attn_out(o, p, x_dtype):
    y = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))
    return shard(y, "batch", "seq", None).astype(x_dtype)


# ---------------------------------------------------------------- MLP

def mlp_params(key, d: int, d_ff: int):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": jax.random.normal(k1, (d, d_ff)) * d ** -0.5,
        "w_up": jax.random.normal(k2, (d, d_ff)) * d ** -0.5,
        "w_down": jax.random.normal(k3, (d_ff, d)) * d_ff ** -0.5,
    }


def mlp(x, p, act: str = "silu"):
    fn = jax.nn.silu if act == "silu" else jax.nn.gelu
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(x.dtype))
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(x.dtype))
    h = shard(fn(g) * u, "batch", "seq", "tp")
    y = jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(x.dtype))
    return shard(y, "batch", "seq", None)


# ---------------------------------------------------------------- MoE

def moe_params(key, cfg):
    e = cfg.moe
    d = cfg.d_model
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    E = e.n_experts
    p = {
        "router": jax.random.normal(k1, (d, E)) * d ** -0.5,
        "we_gate": jax.random.normal(k2, (E, d, e.d_expert)) * d ** -0.5,
        "we_up": jax.random.normal(k3, (E, d, e.d_expert)) * d ** -0.5,
        "we_down": jax.random.normal(k4, (E, e.d_expert, d)) * e.d_expert ** -0.5,
    }
    if e.n_shared:
        p["shared"] = mlp_params(k5, d, e.d_expert * e.n_shared)
    return p


def _positions_within_expert(flat_e, E):
    """Rank of each (token,k) entry within its expert — the mailbox slot
    assignment (same construction as GoFS's _cumcount, in jnp)."""
    Nk = flat_e.shape[0]
    order = jnp.argsort(flat_e)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(E))
    rank_sorted = jnp.arange(Nk) - starts[sorted_e]
    pos = jnp.zeros(Nk, jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))
    return pos


def moe_block(x, p, cfg, capacity: Optional[int] = None):
    """Top-k routed experts with capacity-bounded mailbox dispatch.

    x: (B, S, d) -> (y, aux_loss). Dispatch is the sorted-scatter version of
    the Gopher mailbox: tokens are messages, experts are partitions, capacity
    is mailbox_cap, overflow drops (standard MoE token dropping).

    Under an active mesh this routes through the shard_map expert-parallel
    mailbox (_moe_block_ep): tokens never leave their data shard, each
    model-rank serves its resident experts, one psum combines — the global
    argsort formulation costs ~3.4 TB/dev of collectives at 256 chips
    (EXPERIMENTS.md §Perf iteration M1).
    """
    from repro.models.sharding import active_mesh
    mesh = active_mesh()
    if mesh is not None and "model" in mesh.axis_names \
            and cfg.moe.n_experts % mesh.shape["model"] == 0:
        return _moe_block_ep(x, p, cfg, mesh, capacity)
    e = cfg.moe
    B, S, d = x.shape
    N = B * S
    E, K = e.n_experts, e.top_k
    # capacity: the usual N*K/E * factor, floored so tiny token counts
    # (decode steps, smoke tests) never drop — keeps decode == forward parity
    C = capacity or max(int(N * K / E * e.capacity_factor), 1, min(N, 32))
    xf = x.reshape(N, d)
    logits = jnp.einsum("nd,de->ne", xf, p["router"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_idx = jax.lax.top_k(probs, K)               # (N, K)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    flat_e = gate_idx.reshape(-1)
    flat_w = gate_w.reshape(-1)
    tok = jnp.repeat(jnp.arange(N), K)
    pos = _positions_within_expert(flat_e, E)
    keep = pos < C
    slot = jnp.where(keep, flat_e * C + pos, E * C)          # OOB -> dropped

    buf = jnp.zeros((E * C, d), x.dtype).at[slot].set(xf[tok], mode="drop")
    buf = shard(buf.reshape(E, C, d), "tp", None, None)
    # expert FFN (E sharded over tp => expert parallelism)
    fn = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    g = jnp.einsum("ecd,edf->ecf", buf, p["we_gate"].astype(x.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, p["we_up"].astype(x.dtype))
    h = fn(g) * u
    yb = jnp.einsum("ecf,efd->ecd", h, p["we_down"].astype(x.dtype)).reshape(E * C, d)

    gathered = yb[jnp.where(keep, slot, 0)] * (keep * flat_w)[:, None].astype(x.dtype)
    y = jnp.zeros((N, d), x.dtype).at[tok].add(gathered)
    if e.n_shared:
        y = y + mlp(xf[None], p["shared"], cfg.act)[0]
    # switch-style load-balance aux loss
    frac_tokens = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], E), axis=0)
    frac_prob = jnp.mean(probs, axis=0)
    aux = jnp.sum(frac_tokens * frac_prob) * E * e.aux_loss_coef
    return shard(y.reshape(B, S, d), "batch", "seq", None), aux


def _moe_block_ep(x, p, cfg, mesh, capacity: Optional[int] = None):
    """Expert-parallel mailbox dispatch under shard_map (§Perf M1).

    Token activations are replicated across 'model' (TP) at block entry, so
    every model-rank already holds the tokens — it routes them to its OWN
    resident experts locally (zero dispatch communication, the degenerate
    all_to_all), runs the expert FFNs, and contributes a partial combine that
    a single psum over 'model' finishes. This is the Gopher mailbox with the
    happy property that the topology makes sends local.
    """
    from jax.sharding import PartitionSpec as P
    from repro.models.sharding import resolve

    e = cfg.moe
    Bb, Sb, d = x.shape
    E, K = e.n_experts, e.top_k
    tp = mesh.shape["model"]
    E_loc = E // tp
    batch_spec = resolve("batch")[0]
    x_spec = P(batch_spec, None, None)
    ew_spec = P("model", None, None)
    fn = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    shared_p = p.get("shared")

    def block(xb, router, wg, wu, wd):
        B_, S_, _ = xb.shape
        N = B_ * S_
        C = capacity or max(int(N * K / E * e.capacity_factor), 1, min(N, 32))
        xf = xb.reshape(N, d)
        logits = jnp.einsum("nd,de->ne", xf, router.astype(xb.dtype)
                            ).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_w, gate_idx = jax.lax.top_k(probs, K)
        gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

        flat_e = gate_idx.reshape(-1)
        flat_w = gate_w.reshape(-1)
        tok = jnp.repeat(jnp.arange(N), K)
        pos = _positions_within_expert(flat_e, E)
        my_lo = jax.lax.axis_index("model") * E_loc
        local_e = flat_e - my_lo
        mine = (local_e >= 0) & (local_e < E_loc) & (pos < C)
        slot = jnp.where(mine, local_e * C + pos, E_loc * C)
        buf = jnp.zeros((E_loc * C, d), xb.dtype).at[slot].set(
            xf[tok], mode="drop").reshape(E_loc, C, d)
        g = jnp.einsum("ecd,edf->ecf", buf, wg.astype(xb.dtype))
        u = jnp.einsum("ecd,edf->ecf", buf, wu.astype(xb.dtype))
        yb = jnp.einsum("ecf,efd->ecd", fn(g) * u, wd.astype(xb.dtype)
                        ).reshape(E_loc * C, d)
        gathered = yb[jnp.where(mine, slot, 0)] * \
            (mine * flat_w)[:, None].astype(xb.dtype)
        y = jnp.zeros((N, d), xb.dtype).at[tok].add(gathered)
        y = jax.lax.psum(y, "model")
        frac_tokens = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], E), axis=0)
        frac_prob = jnp.mean(probs, axis=0)
        aux = jnp.sum(frac_tokens * frac_prob) * E * e.aux_loss_coef
        baxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        if baxes:
            n_sh = 1
            for a in baxes:
                n_sh *= mesh.shape[a]
            aux = jax.lax.psum(aux, baxes) / n_sh
        return y.reshape(B_, S_, d), aux

    from repro.core import compat
    y, aux = compat.shard_map(
        block, mesh=mesh,
        in_specs=(x_spec, P(None, None), ew_spec, ew_spec, ew_spec),
        out_specs=(x_spec, P()))(
        x, p["router"], p["we_gate"], p["we_up"], p["we_down"])
    if e.n_shared:
        y = y + mlp(x.reshape(-1, d)[None], shared_p, cfg.act)[0].reshape(x.shape)
    return shard(y, "batch", "seq", None), aux


# ---------------------------------------------------------------- Mamba1 (S6)

def mamba1_params(key, cfg):
    s = cfg.ssm
    d = cfg.d_model
    di = s.expand * d
    ks = jax.random.split(key, 6)
    dt_rank = max(d // 16, 1)
    return {
        "in_proj": jax.random.normal(ks[0], (d, 2 * di)) * d ** -0.5,
        "conv_w": jax.random.normal(ks[1], (s.d_conv, di)) * s.d_conv ** -0.5,
        "conv_b": jnp.zeros((di,)),
        "x_proj": jax.random.normal(ks[2], (di, dt_rank + 2 * s.d_state)) * di ** -0.5,
        "dt_proj_w": jax.random.normal(ks[3], (dt_rank, di)) * dt_rank ** -0.5,
        "dt_proj_b": jnp.log(jnp.expm1(
            jnp.exp(jax.random.uniform(ks[4], (di,)) *
                    (math.log(0.1) - math.log(0.001)) + math.log(0.001)))),
        "A_log": jnp.log(jnp.tile(jnp.arange(1, s.d_state + 1, dtype=jnp.float32), (di, 1))),
        "D": jnp.ones((di,)),
        "out_proj": jax.random.normal(ks[5], (di, d)) * di ** -0.5,
    }


def _causal_conv(x, w, b, state=None):
    """Depthwise causal conv1d. x: (B, L, C); w: (K, C). state: (B, K-1, C)
    carries context across calls (decode). Returns (y, new_state)."""
    K = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)
    y = sum(xp[:, i:i + x.shape[1]] * w[i].astype(x.dtype) for i in range(K))
    return y + b.astype(x.dtype), xp[:, -(K - 1):] if K > 1 else state


def _ssm_chunk_scan(a, b, h0):
    """Within-chunk linear recurrence h_t = a_t h_{t-1} + b_t via associative
    scan. a, b: (B, Q, D, N); h0: (B, D, N). Returns (h_seq (B,Q,D,N), h_last)."""
    def comb(x, y):
        return (x[0] * y[0], y[0] * x[1] + y[1])
    A_cum, b_cum = jax.lax.associative_scan(comb, (a, b), axis=1)
    h = A_cum * h0[:, None] + b_cum
    return h, h[:, -1]


def mamba1_mixer(x, p, cfg, state=None, chunk: Optional[int] = None):
    """Selective SSM (S6). x: (B, L, d). state: None (train/prefill) or
    dict(conv, ssm) for stepwise decode. Returns (y, new_state)."""
    s = cfg.ssm
    B, L, d = x.shape
    di = s.expand * d
    N = s.d_state
    xz = jnp.einsum("bld,de->ble", x, p["in_proj"].astype(x.dtype))
    xin, z = jnp.split(xz, 2, axis=-1)
    xin = shard(xin, "batch", "seq", "tp")
    conv_state = state["conv"] if state is not None else None
    xc, conv_state = _causal_conv(xin, p["conv_w"], p["conv_b"], conv_state)
    xc = jax.nn.silu(xc)

    dt_rank = p["dt_proj_w"].shape[0]
    proj = jnp.einsum("ble,ef->blf", xc, p["x_proj"].astype(x.dtype))
    dt, Bs, Cs = jnp.split(proj, [dt_rank, dt_rank + N], axis=-1)
    delta = jax.nn.softplus(
        jnp.einsum("blr,re->ble", dt, p["dt_proj_w"].astype(x.dtype))
        + p["dt_proj_b"].astype(x.dtype))                       # (B, L, di)
    A = -jnp.exp(p["A_log"]).astype(jnp.float32)                # (di, N)

    # sequence-length tensors stay in the compute dtype (bf16 on TPU); the
    # f32 upcast happens per-chunk inside the loop (§Perf F3)
    deltaf, xcf = delta, xc
    Bf, Cf = Bs, Cs

    h_prev = (state["ssm"] if state is not None
              else jnp.zeros((B, di, N), jnp.float32))
    if L == 1:  # decode fast path: one recurrence step, no scan
        da = jnp.exp(deltaf[:, 0, :, None] * A)                 # (B, di, N)
        db = (deltaf[:, 0] * xcf[:, 0])[..., None] * Bf[:, 0, :, None].transpose(0, 2, 1)
        h = da * h_prev + db
        y = jnp.einsum("bdn,bn->bd", h, Cf[:, 0])[:, None]
        h_last = h
    else:
        Q = chunk or s.chunk
        Q = _pick_block(L, Q)
        nc = L // Q
        # expand exp(δ⊗A) INSIDE the chunk loop: working set per step is
        # (B, Q, di, N) instead of (B, L, di, N) — nc× less HBM traffic and
        # peak temp (EXPERIMENTS.md §Perf, falcon-mamba iteration F1)
        d_cs = deltaf.reshape(B, nc, Q, di).transpose(1, 0, 2, 3)
        bx_cs = (deltaf * xcf).reshape(B, nc, Q, di).transpose(1, 0, 2, 3)
        B_cs = Bf.reshape(B, nc, Q, N).transpose(1, 0, 2, 3)
        C_cs = Cf.reshape(B, nc, Q, N).transpose(1, 0, 2, 3)

        @jax.checkpoint
        def chunk_step(h0, inp):
            # checkpointed: bwd recomputes the (B,Q,di,N) expansion instead of
            # stashing it per chunk (§Perf F4)
            d_c, bx_c, b_c, c_c = [t.astype(jnp.float32) for t in inp]
            a_c = jnp.exp(d_c[..., None] * A)            # (B,Q,di,N) f32
            rhs = bx_c[..., None] * b_c[:, :, None, :]
            h_seq, h_last = _ssm_chunk_scan(a_c, rhs, h0)
            y_c = jnp.einsum("bqdn,bqn->bqd", h_seq, c_c)
            return h_last, y_c

        h_last, y = jax.lax.scan(chunk_step, h_prev, (d_cs, bx_cs, B_cs, C_cs))
        y = y.transpose(1, 0, 2, 3).reshape(B, L, di)
    y = (y + xcf * p["D"].astype(jnp.float32)).astype(x.dtype)
    y = y * jax.nn.silu(z)
    out = jnp.einsum("ble,ed->bld", y, p["out_proj"].astype(x.dtype))
    new_state = {"conv": conv_state, "ssm": h_last}
    return shard(out, "batch", "seq", None), new_state


# ---------------------------------------------------------------- Mamba2 (SSD)

def mamba2_params(key, cfg):
    s = cfg.ssm
    d = cfg.d_model
    H, Pd, N = s.n_heads, s.head_dim, s.d_state
    di = H * Pd
    ks = jax.random.split(key, 4)
    return {
        "in_proj": jax.random.normal(ks[0], (d, 2 * di + 2 * N + H)) * d ** -0.5,
        "conv_w": jax.random.normal(ks[1], (s.d_conv, di + 2 * N)) * s.d_conv ** -0.5,
        "conv_b": jnp.zeros((di + 2 * N,)),
        "a_log2": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)),
        "dt_bias": jnp.zeros((H,)),
        "D": jnp.ones((H,)),
        "norm": jnp.zeros((di,)),
        "out_proj": jax.random.normal(ks[2], (di, d)) * di ** -0.5,
    }


def mamba2_mixer(x, p, cfg, state=None, chunk: Optional[int] = None):
    """Mamba2 SSD (scalar decay per head, G=1 B/C group). x: (B, L, d)."""
    s = cfg.ssm
    B, L, d = x.shape
    H, Pd, N = s.n_heads, s.head_dim, s.d_state
    di = H * Pd
    z_xBC_dt = jnp.einsum("bld,de->ble", x, p["in_proj"].astype(x.dtype))
    z, xBC, dt = jnp.split(z_xBC_dt, [di, 2 * di + 2 * N], axis=-1)
    # xBC: (B, L, di + 2N) -> conv -> silu
    conv_state = state["conv"] if state is not None else None
    xBC, conv_state = _causal_conv(xBC, p["conv_w"], p["conv_b"], conv_state)
    xBC = jax.nn.silu(xBC)
    xin, Bs, Cs = jnp.split(xBC, [di, di + N], axis=-1)
    xin = shard(xin, "batch", "seq", "tp")
    delta = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B, L, H)
    A = -jnp.exp(p["a_log2"])                                       # (H,)

    # (B, L, ·) tensors stay in compute dtype; per-chunk f32 upcast (§Perf F3)
    Xh = xin.reshape(B, L, H, Pd)
    Bf, Cf = Bs, Cs                                                 # (B, L, N)
    da = (delta * A).astype(x.dtype)                                # (B, L, H)
    dX = Xh * delta.astype(Xh.dtype)[..., None]                     # (B, L, H, P)

    h_prev = (state["ssm"] if state is not None
              else jnp.zeros((B, H, Pd, N), jnp.float32))
    if L == 1:
        a0 = jnp.exp(da[:, 0])                                      # (B, H)
        h = a0[..., None, None] * h_prev + \
            dX[:, 0][..., None] * Bf[:, 0, None, None, :]
        y = jnp.einsum("bhpn,bn->bhp", h, Cf[:, 0])[:, None]        # (B,1,H,P)
        h_last = h
    else:
        Q = chunk or s.chunk
        Q = _pick_block(L, Q)
        nc = L // Q
        # all per-chunk tensors (incl. the (Q,Q) decay matrix) are built
        # INSIDE the chunk loop — peak working set (B,Q,Q,H) not (B,L,Q,H)
        tri = jnp.tril(jnp.ones((Q, Q), bool))
        da_cs = da.reshape(B, nc, Q, H).transpose(1, 0, 2, 3)
        B_cs = Bf.reshape(B, nc, Q, N).transpose(1, 0, 2, 3)
        C_cs = Cf.reshape(B, nc, Q, N).transpose(1, 0, 2, 3)
        dX_cs = dX.reshape(B, nc, Q, H, Pd).transpose(1, 0, 2, 3, 4)

        @jax.checkpoint
        def chunk_step(h0, inp):
            da_c, b_c, c_c, dx_c = [t.astype(jnp.float32) for t in inp]
            cum = jnp.cumsum(da_c, axis=1)                          # (B,Q,H)
            seg = cum[:, :, None, :] - cum[:, None, :, :]           # (B,Q,K,H)
            decay = jnp.where(tri[None, :, :, None], jnp.exp(seg), 0.0)
            scores = jnp.einsum("bqn,bkn->bqk", c_c, b_c)
            y_diag = jnp.einsum("bqk,bqkh,bkhp->bqhp", scores, decay, dx_c)
            decay_to_end = jnp.exp(cum[:, -1:, :] - cum)            # (B,Q,H)
            state_in = jnp.einsum("bqh,bqn,bqhp->bhpn", decay_to_end, b_c, dx_c)
            chunk_decay = jnp.exp(cum[:, -1, :])                    # (B,H)
            decay_from_start = jnp.exp(cum)
            y_inter = jnp.einsum("bqn,bhpn,bqh->bqhp", c_c, h0, decay_from_start)
            h1 = chunk_decay[..., None, None] * h0 + state_in
            return h1, y_diag + y_inter

        h_last, y = jax.lax.scan(chunk_step, h_prev,
                                 (da_cs, B_cs, C_cs, dX_cs))
        y = y.transpose(1, 0, 2, 3, 4).reshape(B, L, H, Pd)
    y = y + Xh * p["D"][None, None, :, None]
    y = y.reshape(B, L, di).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    out = jnp.einsum("ble,ed->bld", y, p["out_proj"].astype(x.dtype))
    new_state = {"conv": conv_state, "ssm": h_last}
    return shard(out, "batch", "seq", None), new_state


# ---------------------------------------------------------------- embedding

def embed_params(key, cfg):
    k1, k2 = jax.random.split(key)
    p = {"tok": jax.random.normal(k1, (cfg.vocab, cfg.d_model)) * 0.02}
    if not cfg.tie_embeddings:
        p["unembed"] = jax.random.normal(k2, (cfg.d_model, cfg.vocab)) * cfg.d_model ** -0.5
    return p


def embed(tokens, p, dtype):
    return shard(p["tok"].astype(dtype)[tokens], "batch", "seq", None)


def unembed(x, p, cfg):
    from repro.models.sharding import _state
    w = p["unembed"] if not cfg.tie_embeddings else p["tok"].T
    logits = jnp.einsum("bsd,dv->bsv", x, w.astype(x.dtype))
    # odd vocabs (whisper 51865) can't shard over TP — shard the SEQ dim
    # instead, or the full per-device logits buffer is V·S·B_loc sized
    sizes = getattr(_state, "sizes", {})
    tp = sizes.get("model", 1)
    if tp > 1 and cfg.vocab % tp != 0 and logits.shape[1] % tp == 0 \
            and logits.shape[1] > 1:
        return shard(logits, "batch", "tp", None)
    return shard(logits, "batch", "seq", "tp")
