"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles,
all in interpret mode on CPU (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_splitkv
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.moe_gemm import grouped_gemm_padded, sort_by_expert
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas

KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 1e-4


# ---------------------------------------------------------------- flash
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,Hq,Hkv,D,causal,window,bq,bk",
    [
        (2, 64, 4, 2, 32, True, 0, 16, 16),
        (1, 128, 8, 8, 64, True, 0, 32, 64),
        (2, 64, 4, 1, 16, True, 24, 16, 16),     # SWA
        (1, 96, 4, 2, 32, False, 0, 32, 32),     # bidirectional
        (1, 80, 2, 2, 128, True, 0, 16, 32),     # ragged seq
    ])
def test_flash_attention(B, S, Hq, Hkv, D, causal, window, bq, bk, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D)).astype(dtype)
    out = flash_attention_fwd(q, k, v, causal=causal, window=window,
                              block_q=bq, block_k=bk)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=_tol(dtype), rtol=_tol(dtype))


@settings(max_examples=12, deadline=None)
@given(
    s=st.integers(8, 96),
    hq_groups=st.sampled_from([(4, 2), (8, 1), (2, 2), (6, 3)]),
    d=st.sampled_from([16, 32, 64]),
    bq=st.sampled_from([8, 16, 32]),
    bk=st.sampled_from([8, 16, 32]),
)
def test_flash_attention_property(s, hq_groups, d, bq, bk):
    hq, hkv = hq_groups
    ks = jax.random.split(jax.random.PRNGKey(s * 7 + d), 3)
    q = jax.random.normal(ks[0], (1, s, hq, d), jnp.float32)
    k = jax.random.normal(ks[1], (1, s, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (1, s, hkv, d), jnp.float32)
    out = flash_attention_fwd(q, k, v, block_q=bq, block_k=bk)
    want = ref.attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------- decode
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,W,Hq,Hkv,D,bk", [
    (2, 128, 8, 2, 32, 32),
    (1, 100, 4, 4, 64, 64),
    (3, 256, 6, 3, 16, 128),
])
def test_decode_attention(B, W, Hq, Hkv, D, bk, dtype):
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, Hq, D)).astype(dtype)
    kc = jax.random.normal(ks[1], (B, W, Hkv, D)).astype(dtype)
    vc = jax.random.normal(ks[2], (B, W, Hkv, D)).astype(dtype)
    mask = jax.random.bernoulli(ks[3], 0.7, (B, W)).at[:, 0].set(True)
    out = decode_attention_splitkv(q, kc, vc, mask, block_k=bk)
    want = ref.decode_attention_ref(q, kc, vc, mask)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=_tol(dtype), rtol=_tol(dtype))


# ---------------------------------------------------------- paged decode
def _ring_mask(B, n_rows, width, end):
    """Rows valid over a window of ``width`` ending at row ``end`` of a
    ring of ``n_rows``: it wraps, so a slot's first and last pages are
    live and the pages between its two ends are not."""
    r = np.arange(n_rows)[None]
    return (end[:, None] - r) % n_rows < width


def _prefix_mask(lens, n_rows):
    return np.arange(n_rows)[None] < np.asarray(lens)[:, None]


# (Hq, Hkv, D, page_size, table pages, pages per block, mask)
PAGED_CASES = {
    # GQA (starcoder2-3b heads): 1 row, a page boundary, the full table,
    # an empty slot; 8 live pages over blocks of 3
    "gqa-ragged": (24, 2, 128, 16, 8, 3, ("prefix", [1, 16, 128, 0])),
    # MHA (minicpm-2b heads, fewer of them), the same lengths
    "mha-ragged": (4, 4, 64, 8, 8, 3, ("prefix", [1, 8, 64, 0])),
    # pages per block from the page's bytes
    "gqa-derived-block": (24, 2, 128, 16, 8, None, ("prefix", [37, 100])),
    "mha-derived-block": (4, 4, 64, 8, 8, None, ("prefix", [37, 61])),
    # windowed ring: masked pages between live ones
    "gqa-ring": (8, 2, 32, 8, 8, 2, ("ring", 20, [5, 60])),
    "mha-ring": (3, 3, 16, 8, 8, 3, ("ring", 12, [3, 40])),
}


@pytest.mark.parametrize("case", sorted(PAGED_CASES))
def test_paged_decode_attention_matches_xla(case):
    """The live-page kernel against the XLA gather and the dense
    reference over the gathered rows, in float32, through a shuffled
    page table, on a pool stored in the rows the kernel reads (one
    ``Hkv * D`` vector a row for MHA); the XLA gather reads the same
    pages as ``(Hkv, D)`` rows alike. An empty slot's output is only
    finite."""
    from repro.kernels.paged_attention import (page_row,
                                               paged_decode_attention_splitkv)
    from repro.models.attention import paged_decode_attention

    Hq, Hkv, D, ps, NP, pb, (kind, *how) = PAGED_CASES[case]
    if kind == "prefix":
        lens = how[0]
        B = len(lens)
        mask = _prefix_mask(lens, NP * ps)
    else:
        width, ends = how
        B = len(ends)
        mask = _ring_mask(B, NP * ps, width, np.asarray(ends))
    P = B * NP + 1
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Hq, D), jnp.float32)
    k4 = jax.random.normal(ks[1], (P, ps, Hkv, D), jnp.float32)
    v4 = jax.random.normal(ks[2], (P, ps, Hkv, D), jnp.float32)
    row = page_row(Hq, Hkv, D)
    assert (len(row) == 1) == (Hq == Hkv)
    kp, vp = (a.reshape((P, ps) + row) for a in (k4, v4))
    pages = np.random.default_rng(0).permutation(np.arange(1, P))
    pt = jnp.asarray(pages.reshape(B, NP), jnp.int32)
    out = np.asarray(paged_decode_attention_splitkv(
        q, kp, vp, pt, mask, pages_per_block=pb))
    want = np.asarray(paged_decode_attention(q, kp, vp, pt, mask))
    rows = k4[pt].reshape(B, NP * ps, Hkv, D), v4[pt].reshape(
        B, NP * ps, Hkv, D)
    dense = np.asarray(ref.decode_attention_ref(q, *rows, mask))
    live = mask.any(axis=1)
    np.testing.assert_allclose(out[live], want[live], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(out[live], dense[live], atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(
        want, np.asarray(paged_decode_attention(q, k4, v4, pt, mask)))
    assert np.isfinite(out).all()


def test_paged_decode_attention_refuses_rows_it_does_not_read():
    """A pool of one query per kv head stored as ``(Hkv, D)`` rows would
    cost a relayout of the whole pool per call: the kernel refuses it."""
    from repro.kernels.paged_attention import paged_decode_attention_splitkv
    q = jnp.zeros((1, 4, 64), jnp.float32)
    kp = jnp.zeros((3, 8, 4, 64), jnp.float32)
    with pytest.raises(ValueError, match="not the rows"):
        paged_decode_attention_splitkv(q, kp, kp, jnp.ones((1, 2), jnp.int32),
                                       np.ones((1, 16), bool))


@pytest.mark.parametrize("mask,want", [
    (_prefix_mask([1, 8, 9, 32], 32), [1, 1, 2, 4]),
    (_ring_mask(2, 32, 6, np.array([2, 20])), [4, 3]),
    (np.zeros((2, 32), bool), [1, 1]),
])
def test_live_pages_from_mask(mask, want):
    """One past the last page holding a valid row, at least 1."""
    from repro.kernels.paged_attention import live_pages
    assert np.asarray(live_pages(mask, 8)).tolist() == want


# ---------------------------------------------------------------- ssd
@pytest.mark.parametrize("b,S,nh,hp,N,chunk", [
    (2, 64, 4, 16, 8, 16),
    (1, 100, 2, 32, 16, 32),     # ragged chunks
    (2, 128, 8, 8, 32, 64),
    (1, 32, 1, 64, 128, 32),     # mamba2-1.3b head geometry
])
def test_ssd_scan(b, S, nh, hp, N, chunk):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, S, nh, hp), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, S, nh)))
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)) * 0.5)
    B = jax.random.normal(ks[3], (b, S, nh, N), jnp.float32)
    C = jax.random.normal(ks[4], (b, S, nh, N), jnp.float32)
    y, h = ssd_scan_pallas(x, dt, A, B, C, chunk=chunk)
    yr, hr = ref.ssd_ref(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               atol=5e-4, rtol=5e-4)


@settings(max_examples=8, deadline=None)
@given(s=st.integers(4, 80), chunk=st.sampled_from([8, 16, 32]),
       n=st.sampled_from([4, 16]))
def test_ssd_scan_property(s, chunk, n):
    ks = jax.random.split(jax.random.PRNGKey(s + n), 5)
    x = jax.random.normal(ks[0], (1, s, 2, 8), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, s, 2)))
    A = -jnp.exp(jax.random.normal(ks[2], (2,)) * 0.3)
    B = jax.random.normal(ks[3], (1, s, 2, n), jnp.float32)
    C = jax.random.normal(ks[4], (1, s, 2, n), jnp.float32)
    y, h = ssd_scan_pallas(x, dt, A, B, C, chunk=chunk)
    yr, hr = ref.ssd_ref(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=5e-4, rtol=5e-4)


# ---------------------------------------------------------------- moe
@pytest.mark.parametrize("T,d,f,E,bm,bf", [
    (64, 32, 48, 4, 8, 16),
    (100, 16, 64, 3, 16, 32),
    (128, 64, 128, 8, 32, 64),
])
def test_grouped_gemm(T, d, f, E, bm, bf):
    ks = jax.random.split(KEY, 3)
    x = jax.random.normal(ks[0], (T, d), jnp.float32)
    w = jax.random.normal(ks[1], (E, d, f), jnp.float32)
    eor = jax.random.randint(ks[2], (T,), 0, E)
    xs, be, inv, _ = sort_by_expert(x, eor, E, bm)
    out = grouped_gemm_padded(xs, w, be, block_f=bf)[inv]
    want = jnp.einsum("td,tdf->tf", x, w[eor])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


@settings(max_examples=20, deadline=None)
@given(
    T=st.integers(1, 64),
    E=st.integers(1, 6),
    bm=st.sampled_from([4, 8, 16]),
    seed=st.integers(0, 2**16),
)
def test_sort_by_expert_roundtrip_property(T, E, bm, seed):
    """Padding + inverse-permutation round-trip invariants:

    * ``x_pad[inv]`` recovers the original rows exactly;
    * every padded slot NOT addressed by ``inv`` is zero (padding never
      leaks data into an expert's group);
    * each row lands in a block whose ``block_expert`` matches its
      routed expert (the scalar-prefetch contract of the kernel);
    * every block, spare ones included, names a real expert (the chip
      bounds-checks the weight fetch);
    * destination slots are unique (``inv`` is injective).
    """
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    x = jax.random.normal(ks[0], (T, 3), jnp.float32) + 1.0  # no zero rows
    eor = jax.random.randint(ks[1], (T,), 0, E)
    x_pad, block_expert, inv, Tp = sort_by_expert(x, eor, E, bm)
    x_pad, block_expert, inv = (np.asarray(x_pad),
                                np.asarray(block_expert), np.asarray(inv))
    xn, eorn = np.asarray(x), np.asarray(eor)

    assert x_pad.shape[0] == Tp and Tp % bm == 0
    assert block_expert.shape == (Tp // bm,)
    # inverse permutation: padded[inv] == original, injectively
    np.testing.assert_array_equal(x_pad[inv], xn)
    assert len(np.unique(inv)) == T
    # untouched slots carry zeros only
    hit = np.zeros(Tp, bool)
    hit[inv] = True
    assert np.all(x_pad[~hit] == 0.0)
    # each row's destination block streams that row's expert weights
    np.testing.assert_array_equal(block_expert[inv // bm], eorn)
    assert np.all((block_expert >= 0) & (block_expert < E))


def test_grouped_gemm_empty_group():
    """An expert with zero tokens must not corrupt neighbours."""
    x = jax.random.normal(KEY, (32, 16), jnp.float32)
    w = jax.random.normal(KEY, (4, 16, 8), jnp.float32)
    eor = jnp.where(jnp.arange(32) % 2 == 0, 0, 3)     # experts 1,2 empty
    xs, be, inv, _ = sort_by_expert(x, eor, 4, 8)
    out = grouped_gemm_padded(xs, w, be, block_f=8)[inv]
    want = jnp.einsum("td,tdf->tf", x, w[eor])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------- rmsnorm
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("R,d,br", [(64, 32, 16), (100, 128, 32),
                                    (7, 16, 8)])
def test_rmsnorm(R, d, br, dtype):
    x = jax.random.normal(KEY, (R, d)).astype(dtype)
    s = jax.random.normal(jax.random.fold_in(KEY, 1), (d,), jnp.float32)
    out = rmsnorm_pallas(x, s, block_rows=br)
    want = ref.rmsnorm_ref(x, s)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=_tol(dtype), rtol=_tol(dtype))


# ------------------------------------------------------------ ops wrappers
@pytest.mark.parametrize("backend,interpret", [("cpu", True),
                                               ("tpu", False),
                                               ("gpu", None)])
def test_kernels_interpret_only_on_cpu(monkeypatch, backend, interpret):
    """Pallas kernels compile on the TPU, interpret on the CPU, and are
    refused elsewhere rather than interpreted on an accelerator."""
    from repro.kernels import ops
    monkeypatch.setattr(ops.jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="gpu"):
            ops._interpret()
    else:
        assert ops._interpret() is interpret


@pytest.mark.parametrize("n,block,want", [(1408, 512, 128), (2048, 512, 512),
                                          (1536, 512, 512), (768, 512, 384),
                                          (100, 512, 100)])
def test_fit_block_divides_width(n, block, want):
    from repro.kernels.ops import _fit_block
    assert _fit_block(n, block) == want
