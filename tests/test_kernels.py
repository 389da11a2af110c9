"""Pallas kernel validation: interpret=True vs the pure-jnp oracle, swept over
shapes / semirings / block sizes, plus hypothesis property sweeps."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

hypothesis = pytest.importorskip(
    "hypothesis", reason="property sweeps need hypothesis (requirements-dev.txt)")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.gofs.formats import PAD
from repro.kernels import (bin_rows_by_degree, multibin_spmv,
                           semiring_spmv_pallas, semiring_spmv_ref)

SEMIRINGS = ["min_plus", "max_first", "plus_times"]


def _random_ell(rng, v, d, frac_pad=0.3):
    nbr = rng.integers(0, v, (v, d)).astype(np.int32)
    pad = rng.random((v, d)) < frac_pad
    nbr[pad] = PAD
    wgt = rng.uniform(0.1, 2.0, (v, d)).astype(np.float32)
    x = rng.uniform(0.0, 5.0, v).astype(np.float32)
    return x, nbr, wgt


@pytest.mark.parametrize("semiring", SEMIRINGS)
@pytest.mark.parametrize("v,d,bv", [(64, 8, 16), (100, 16, 32), (257, 24, 64),
                                    (33, 8, 256)])
def test_pallas_matches_ref(semiring, v, d, bv):
    rng = np.random.default_rng(hash((semiring, v, d)) % 2**31)
    x, nbr, wgt = _random_ell(rng, v, d)
    got = semiring_spmv_pallas(jnp.asarray(x), jnp.asarray(nbr),
                               jnp.asarray(wgt), semiring, block_v=bv,
                               interpret=True)
    want = semiring_spmv_ref(jnp.asarray(x), jnp.asarray(nbr),
                             jnp.asarray(wgt), semiring)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("semiring", SEMIRINGS)
def test_all_pad_rows(semiring):
    """Rows with zero neighbors must produce the ⊕-identity."""
    v = 16
    nbr = np.full((v, 8), PAD, np.int32)
    wgt = np.zeros((v, 8), np.float32)
    x = np.ones(v, np.float32)
    got = np.asarray(semiring_spmv_pallas(
        jnp.asarray(x), jnp.asarray(nbr), jnp.asarray(wgt), semiring, block_v=8,
        interpret=True))
    ident = {"min_plus": np.inf, "max_first": -np.inf, "plus_times": 0.0}[semiring]
    assert np.all(got == ident)


def test_vmap_over_partitions():
    """The engine vmaps the kernel over the partition axis."""
    rng = np.random.default_rng(0)
    P, v, d = 3, 40, 8
    xs, nbrs, wgts = [], [], []
    for _ in range(P):
        x, nbr, wgt = _random_ell(rng, v, d)
        xs.append(x)
        nbrs.append(nbr)
        wgts.append(wgt)
    xs, nbrs, wgts = map(np.stack, (xs, nbrs, wgts))
    got = jax.vmap(lambda a, b, c: semiring_spmv_pallas(a, b, c, "min_plus",
                                                        block_v=16,
                                                        interpret=True))(
        jnp.asarray(xs), jnp.asarray(nbrs), jnp.asarray(wgts))
    for p in range(P):
        want = semiring_spmv_ref(jnp.asarray(xs[p]), jnp.asarray(nbrs[p]),
                                 jnp.asarray(wgts[p]), "min_plus")
        np.testing.assert_allclose(np.asarray(got[p]), np.asarray(want),
                                   rtol=1e-6)


@pytest.mark.parametrize("semiring", SEMIRINGS)
def test_multibin_matches_single_bin(semiring):
    """Degree-binned ELL (powerlaw mitigation) must equal the flat sweep."""
    rng = np.random.default_rng(7)
    v = 128
    deg = np.minimum(rng.zipf(1.3, v), 64)          # skewed degrees
    d = int(deg.max())
    nbr = np.full((v, d), PAD, np.int32)
    for i in range(v):
        nbr[i, :deg[i]] = rng.integers(0, v, deg[i])
    wgt = rng.uniform(0.1, 1.0, (v, d)).astype(np.float32)
    x = rng.uniform(0, 3, v).astype(np.float32)
    bins = bin_rows_by_degree(nbr, wgt, boundaries=(4, 16))
    got = multibin_spmv(jnp.asarray(x), bins, v, semiring, backend="jnp")
    want = semiring_spmv_ref(jnp.asarray(x), jnp.asarray(nbr),
                             jnp.asarray(wgt), semiring)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)
    # padding waste bound: binned cells < flat ELL cells for skewed degrees
    flat_cells = v * d
    bin_cells = sum(b[1].size for b in bins)
    assert bin_cells < flat_cells


@settings(max_examples=25, deadline=None)
@given(st.integers(2, 80), st.integers(1, 12), st.integers(0, 2),
       st.sampled_from(SEMIRINGS))
def test_property_pallas_equals_ref(v, d, seed, semiring):
    rng = np.random.default_rng(seed)
    x, nbr, wgt = _random_ell(rng, v, d)
    got = semiring_spmv_pallas(jnp.asarray(x), jnp.asarray(nbr),
                               jnp.asarray(wgt), semiring, block_v=32,
                               interpret=True)
    want = semiring_spmv_ref(jnp.asarray(x), jnp.asarray(nbr),
                             jnp.asarray(wgt), semiring)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------- flash kernel

def _naive_attn(q, k, v, causal=True, window=None):
    import math
    B, S, H, dh = q.shape
    KV = k.shape[2]
    g = H // KV
    kr = jnp.repeat(k, g, axis=2)
    vr = jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kr) / math.sqrt(dh)
    qi = jnp.arange(S)[:, None]
    kj = jnp.arange(S)[None, :]
    mask = jnp.ones((S, S), bool)
    if causal:
        mask &= kj <= qi
    if window is not None:
        mask &= (qi - kj) < window
    s = jnp.where(mask[None, None], s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vr)


@pytest.mark.parametrize("H,KV,window", [(4, 4, None), (4, 2, None), (8, 2, 8)])
def test_flash_kernel_matches_naive(H, KV, window):
    from repro.kernels.flash_attention import flash_attention_pallas
    B, S, dh = 2, 32, 16
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, S, H, dh))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, KV, dh))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, KV, dh))
    got = flash_attention_pallas(q, k, v, causal=True, window=window,
                                 q_block=8, kv_block=8, interpret=True)
    want = _naive_attn(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_flash_kernel_matches_layer_impl():
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.models.layers import flash_attention
    B, S, H, KV, dh = 1, 64, 4, 2, 8
    q = jax.random.normal(jax.random.PRNGKey(3), (B, S, H, dh))
    k = jax.random.normal(jax.random.PRNGKey(4), (B, S, KV, dh))
    v = jax.random.normal(jax.random.PRNGKey(5), (B, S, KV, dh))
    got = flash_attention_pallas(q, k, v, q_block=16, kv_block=16,
                                 interpret=True)
    want = flash_attention(q, k, v, q_block=16, kv_block=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------- mamba scan

@pytest.mark.parametrize("B,L,D,N,bd", [(2, 16, 8, 4, 4), (1, 24, 16, 8, 16),
                                        (2, 10, 12, 4, 8)])
def test_mamba_scan_kernel_matches_ref(B, L, D, N, bd):
    from repro.kernels.mamba_scan import mamba1_scan_pallas, mamba1_scan_ref
    rng = np.random.default_rng(B * 100 + L)
    x = jnp.asarray(rng.standard_normal((B, L, D)), jnp.float32) * 0.5
    dt = jnp.asarray(rng.uniform(0.01, 0.5, (B, L, D)), jnp.float32)
    Bv = jnp.asarray(rng.standard_normal((B, L, N)), jnp.float32)
    Cv = jnp.asarray(rng.standard_normal((B, L, N)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 2.0, (D, N)), jnp.float32)
    got = mamba1_scan_pallas(x, dt, Bv, Cv, A, block_d=bd, interpret=True)
    want = mamba1_scan_ref(x, dt, Bv, Cv, A)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_mamba_scan_kernel_matches_mixer_core():
    """The kernel computes the same recurrence the mixer's chunked scan does
    (cross-validated through the step-by-step oracle both are tested against)."""
    from repro.kernels.mamba_scan import mamba1_scan_pallas, mamba1_scan_ref
    rng = np.random.default_rng(0)
    B, L, D, N = 1, 32, 8, 4
    x = jnp.asarray(rng.standard_normal((B, L, D)), jnp.float32) * 0.3
    dt = jnp.asarray(rng.uniform(0.05, 0.3, (B, L, D)), jnp.float32)
    Bv = jnp.asarray(rng.standard_normal((B, L, N)), jnp.float32)
    Cv = jnp.asarray(rng.standard_normal((B, L, N)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 1.5, (D, N)), jnp.float32)
    got = mamba1_scan_pallas(x, dt, Bv, Cv, A, block_d=8, interpret=True)
    want = mamba1_scan_ref(x, dt, Bv, Cv, A)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
