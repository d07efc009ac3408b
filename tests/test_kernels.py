"""Per-kernel allclose sweeps (interpret=True) against the pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from numpy.testing import assert_allclose

from repro.kernels.decode_attention.kernel import combine_partials, decode_attention_pallas
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.retrieval_topk.kernel import retrieval_topk_pallas
from repro.kernels.retrieval_topk.ref import retrieval_topk_ref
from repro.kernels.ssd_scan.kernel import ssd_chunk_pallas
from repro.kernels.ssd_scan.ref import ssd_chunk_ref


# ---------------- retrieval_topk ----------------
@pytest.mark.parametrize("q,n,d,k", [(5, 100, 32, 4), (16, 257, 64, 8), (33, 1024, 128, 16), (1, 50, 16, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_retrieval_topk_sweep(q, n, d, k, dtype):
    kk = jax.random.PRNGKey(q * n)
    qs = jax.random.normal(kk, (q, d), dtype)
    cs = jax.random.normal(jax.random.fold_in(kk, 1), (n, d), dtype)
    s_p, i_p = retrieval_topk_pallas(qs, cs, k, bq=8, bn=64)
    s_r, i_r = retrieval_topk_ref(qs, cs, k)
    assert_allclose(np.asarray(s_p), np.asarray(s_r), rtol=2e-2, atol=2e-2)
    # indices may swap under score ties in bf16; check score-equivalence
    gathered = np.take_along_axis(
        np.asarray(qs, np.float32) @ np.asarray(cs, np.float32).T, np.asarray(i_p), axis=1
    )
    assert_allclose(gathered, np.asarray(s_r), rtol=2e-2, atol=2e-2)


@given(
    q=st.integers(1, 12),
    n=st.integers(10, 300),
    k=st.integers(1, 8),
    seed=st.integers(0, 2**16),
)
@settings(max_examples=15, deadline=None)
def test_retrieval_topk_property(q, n, k, seed):
    kk = jax.random.PRNGKey(seed)
    qs = jax.random.normal(kk, (q, 16))
    cs = jax.random.normal(jax.random.fold_in(kk, 1), (n, 16))
    s, i = retrieval_topk_pallas(qs, cs, k, bq=8, bn=32)
    s, i = np.asarray(s), np.asarray(i)
    assert (np.diff(s, axis=1) <= 1e-6).all(), "scores sorted desc"
    assert ((i >= 0) & (i < n)).all(), "indices valid (padding never leaks)"
    full = np.asarray(qs) @ np.asarray(cs).T
    assert_allclose(np.sort(s, 1), np.sort(np.sort(full, 1)[:, -k:], 1), rtol=1e-5, atol=1e-5)


# ---------------- flash attention ----------------
@pytest.mark.parametrize("sq,sk,h,kv,dh", [(32, 32, 4, 4, 16), (64, 64, 8, 2, 32), (128, 128, 4, 1, 64)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(sq, sk, h, kv, dh, causal, dtype):
    kk = jax.random.PRNGKey(sq + h)
    q = jax.random.normal(kk, (2, sq, h, dh), dtype)
    k = jax.random.normal(jax.random.fold_in(kk, 1), (2, sk, kv, dh), dtype)
    v = jax.random.normal(jax.random.fold_in(kk, 2), (2, sk, kv, dh), dtype)
    o_p = flash_attention_pallas(q, k, v, causal=causal, bq=16, bk=16)
    o_r = flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    assert_allclose(np.asarray(o_p, np.float32), np.asarray(o_r, np.float32), rtol=tol, atol=tol)


# ---------------- decode attention ----------------
@pytest.mark.parametrize("b,s,h,kv,dh,bs", [(2, 64, 8, 4, 32, 16), (4, 128, 4, 4, 16, 32), (1, 256, 16, 2, 64, 64)])
def test_decode_attention_sweep(b, s, h, kv, dh, bs):
    kk = jax.random.PRNGKey(b * s)
    q = jax.random.normal(kk, (b, h, dh))
    kc = jax.random.normal(jax.random.fold_in(kk, 1), (b, s, kv, dh))
    vc = jax.random.normal(jax.random.fold_in(kk, 2), (b, s, kv, dh))
    lens = jnp.asarray(np.random.default_rng(0).integers(1, s + 1, size=b))
    o_p = decode_attention_pallas(q, kc, vc, lens, bs=bs)
    o_r = decode_attention_ref(q, kc, vc, lens)
    assert_allclose(np.asarray(o_p), np.asarray(o_r), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize(
    "b,h,kv,dh,bs,n_t,tile_bytes,n_zero",
    [
        pytest.param(2, 8, 4, 32, 16, 4, None, 0, id="2-8-4-32-16-4"),
        pytest.param(3, 4, 4, 16, 32, 2, None, 0, id="3-4-4-16-32-2"),
        pytest.param(1, 16, 2, 64, 8, 8, None, 0, id="1-16-2-64-8-8"),
        # G = 4, rows over up to five tiles of 2 blocks (the last one partial)
        pytest.param(4, 8, 2, 32, 8, 9, 16 << 10, 0, id="g4-tiles"),
        # two rows of length 0 among rows over several tiles
        pytest.param(5, 8, 4, 16, 4, 7, 16 << 10, 2, id="zero-rows-tiles"),
    ],
)
def test_paged_decode_attention_sweep(monkeypatch, b, h, kv, dh, bs, n_t, tile_bytes, n_zero):
    """Paged flash-decode: block-table gather through the live-block walk
    must match (a) the gather reference and (b) the dense kernel run on
    each row's materialized contiguous view; a row of length 0 outputs
    zeros (the reference averages its masked values, which callers
    discard)."""
    from repro.kernels.decode_attention import kernel as da
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref

    if tile_bytes is not None:
        monkeypatch.setattr(da, "TILE_BYTES", tile_bytes)
        assert da._tile_blocks(n_t, kv * bs * 128 * 4) == 2  # f32 blocks, lanes padded
    n_pool = b * n_t + 1  # +1 pool block left dangling (never referenced)
    kk = jax.random.PRNGKey(b * h + n_t)
    q = jax.random.normal(kk, (b, h, dh))
    kp = jax.random.normal(jax.random.fold_in(kk, 1), (n_pool, bs, kv, dh))
    vp = jax.random.normal(jax.random.fold_in(kk, 2), (n_pool, bs, kv, dh))
    rng = np.random.default_rng(0)
    # disjoint, shuffled tables: physical order != logical order
    tables = jnp.asarray(rng.permutation(n_pool - 1)[: b * n_t].reshape(b, n_t), jnp.int32)
    lens = rng.integers(1, n_t * bs + 1, size=b)
    lens[:n_zero] = 0
    lens = jnp.asarray(lens, jnp.int32)
    live = np.asarray(lens) > 0
    o_p = da.paged_decode_attention_pallas(q, kp, vp, tables, lens)
    o_r = paged_decode_attention_ref(q, kp, vp, tables, lens)
    assert_allclose(np.asarray(o_p)[live], np.asarray(o_r, np.float32)[live], rtol=2e-5, atol=2e-5)
    assert (np.asarray(o_p)[~live] == 0).all()
    # dense equivalence: gather each row's blocks into a contiguous cache
    kc = np.asarray(kp)[np.asarray(tables)].reshape(b, n_t * bs, kv, dh)
    vc = np.asarray(vp)[np.asarray(tables)].reshape(b, n_t * bs, kv, dh)
    o_d = decode_attention_ref(q, jnp.asarray(kc), jnp.asarray(vc), lens)
    assert_allclose(np.asarray(o_r), np.asarray(o_d, np.float32), rtol=0, atol=0)


@pytest.mark.parametrize("geometry", ["gqa", "latent"])
def test_paged_decode_kernel_never_reads_past_live_blocks(monkeypatch, geometry):
    """Every pool block the table maps past a row's live blocks (``0 ..
    cdiv(len, bs) - 1``), and every block of a length-0 row, is NaN: a
    kernel that copied one into a tile it computes would carry the NaN
    into the output (``0 * NaN`` in ``p @ v``), so the live rows' outputs
    must be bit-identical to the clean run.  Rows end mid-block, exactly
    on a block edge, at the table's end, and at length 0, over a 7-block
    table walked in tiles of 2 blocks (the last tile partial)."""
    from repro.kernels.decode_attention import kernel as da

    b, bs, n_t = 5, 4, 7
    h, kv, dh, dv, scale = (8, 2, 32, None, None) if geometry == "gqa" else (4, 1, 24, 16, 0.3)
    monkeypatch.setattr(da, "TILE_BYTES", 2 * kv * bs * 128 * 4)
    assert da._tile_blocks(n_t, kv * bs * 128 * 4) == 2
    kk = jax.random.PRNGKey(17)
    n_pool = b * n_t + 1
    q = jax.random.normal(kk, (b, h, dh))
    kp = jax.random.normal(jax.random.fold_in(kk, 1), (n_pool, bs, kv, dh))
    vp = None if dv else jax.random.normal(jax.random.fold_in(kk, 2), (n_pool, bs, kv, dh))
    tb = np.random.default_rng(5).permutation(n_pool)[: b * n_t].reshape(b, n_t)
    tables = jnp.asarray(tb, jnp.int32)
    ln = np.array([3 * bs, n_t * bs, 0, 2 * bs + 3, 1])  # edge, full, empty, mid-block, one key
    lens = jnp.asarray(ln, jnp.int32)
    dead = np.concatenate([tb[i, -(-n // bs):] for i, n in enumerate(ln)])
    assert dead.size == n_t * b - (3 + n_t + 0 + 3 + 1)

    def run(k, v):
        return np.asarray(da.paged_decode_attention_pallas(q, k, v, tables, lens,
                                                            scale=scale, v_dim=dv))

    clean = run(kp, vp)
    poisoned = run(kp.at[dead].set(np.nan), None if vp is None else vp.at[dead].set(np.nan))
    assert np.isfinite(clean).all()
    assert np.array_equal(clean, poisoned)
    assert (clean[2] == 0).all()


def test_paged_decode_trash_blocks_never_leak():
    """Lanes past ``lengths`` (including whole table entries that point at
    a trash block full of garbage) must contribute exactly nothing."""
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref

    b, h, kv, dh, bs, n_t = 2, 4, 2, 16, 8, 3
    kk = jax.random.PRNGKey(3)
    q = jax.random.normal(kk, (b, h, dh))
    kp = jax.random.normal(jax.random.fold_in(kk, 1), (7, bs, kv, dh))
    vp = jax.random.normal(jax.random.fold_in(kk, 2), (7, bs, kv, dh))
    trash = 6
    tables = jnp.asarray([[0, 1, trash], [2, 3, trash]], jnp.int32)
    lens = jnp.asarray([2 * bs, bs + 3], jnp.int32)
    base = paged_decode_attention_ref(q, kp, vp, tables, lens)
    # poison the trash block and every masked lane of a live block
    kp2 = kp.at[trash].set(1e4).at[3, 4:].set(-1e4)
    vp2 = vp.at[trash].set(1e4).at[3, 4:].set(-1e4)
    poisoned = paged_decode_attention_ref(q, kp2, vp2, tables, lens)
    assert_allclose(np.asarray(base), np.asarray(poisoned), rtol=0, atol=0)


def test_decode_partials_combine_equals_monolithic():
    """flash-decode: combining per-shard partials == attention over full cache."""
    kk = jax.random.PRNGKey(7)
    b, s, h, kv, dh, shards = 2, 128, 8, 4, 32, 4
    q = jax.random.normal(kk, (b, h, dh))
    kc = jax.random.normal(jax.random.fold_in(kk, 1), (b, s, kv, dh))
    vc = jax.random.normal(jax.random.fold_in(kk, 2), (b, s, kv, dh))
    lens = jnp.full((b,), s)
    full = decode_attention_ref(q, kc, vc, lens)
    os_, ms_, ls_ = [], [], []
    for i in range(shards):
        sl = slice(i * s // shards, (i + 1) * s // shards)
        o, m, l = decode_attention_pallas(
            q, kc[:, sl], vc[:, sl], jnp.full((b,), s // shards), bs=16, return_partials=True
        )
        os_.append(o), ms_.append(m), ls_.append(l)
    combined = combine_partials(os_, ms_, ls_).reshape(b, h, dh)
    assert_allclose(np.asarray(combined), np.asarray(full, np.float32), rtol=2e-5, atol=2e-5)


# ---------------- chunked prefill (mixed prefill+decode) ----------------
def _mixed_oracle_np(q, kp, vp, tables, desc):
    """Independent float64 numpy oracle for the descriptor contract: lane
    ``j`` of row ``r`` attends positions ``<= q_start + j`` and ``<
    kv_len`` of its slot's gathered pool view; dead lanes are exactly 0."""
    q, kp, vp = (np.asarray(a, np.float64) for a in (q, kp, vp))
    tables = np.asarray(tables)
    r, w, h, dh = q.shape
    bs, kv = kp.shape[1], kp.shape[2]
    g = h // kv
    out = np.zeros_like(q)
    for i in range(r):
        slot, q0, ql, kl = (int(x) for x in np.asarray(desc)[i])
        kview = kp[tables[slot]].reshape(-1, kv, dh)
        vview = vp[tables[slot]].reshape(-1, kv, dh)
        for j in range(ql):
            n = min(q0 + j + 1, kl)
            for hh in range(h):
                s = kview[:n, hh // g] @ q[i, j, hh] / np.sqrt(dh)
                p = np.exp(s - s.max())
                out[i, j, hh] = (p / p.sum()) @ vview[:n, hh // g]
    return out


MIXED_KINDS = ("decode", "cold", "warm", "boundary", "dead")


def _rand_mixed_case(rng, b, w, h, kv, dh, bs, n_t, kinds=MIXED_KINDS):
    """Random pool + disjoint shuffled tables + a descriptor mix covering
    decode rows, cold/warm fill chunks, a COW-style boundary row, and a
    zero-length row when b allows; row i takes ``kinds[i % len(kinds)]``,
    where ``edge`` ends a fill on a block boundary and ``full`` fills the
    whole table."""
    n_pool = b * n_t + 1
    kk = jax.random.PRNGKey(rng.integers(2**31))
    q = jax.random.normal(kk, (b, w, h, dh))
    kp = jax.random.normal(jax.random.fold_in(kk, 1), (n_pool, bs, kv, dh))
    vp = jax.random.normal(jax.random.fold_in(kk, 2), (n_pool, bs, kv, dh))
    tables = jnp.asarray(
        rng.permutation(n_pool - 1)[: b * n_t].reshape(b, n_t), jnp.int32
    )
    cap = n_t * bs
    desc = np.zeros((b, 4), np.int32)
    for i in range(b):
        kind = kinds[i % len(kinds)]
        if kind == "decode":  # 1 fresh token at the tip of a live cache
            q0 = int(rng.integers(0, cap))
            desc[i] = (i, q0, 1, q0 + 1)
        elif kind == "cold":  # prompt chunk from position 0
            ql = int(rng.integers(1, w + 1))
            desc[i] = (i, 0, ql, ql)
        elif kind == "warm":  # suffix chunk riding resident prefix K/V
            q0 = int(rng.integers(1, cap - 1))
            ql = int(rng.integers(1, min(w, cap - q0) + 1))
            desc[i] = (i, q0, ql, q0 + ql)
        elif kind == "boundary":  # full-prefix COW hit: single suffix lane
            kl = int(rng.integers(1, cap + 1))
            desc[i] = (i, kl - 1, 1, kl)
        elif kind in ("edge", "full"):  # kv_len a whole number of blocks
            kl = cap if kind == "full" else bs * int(rng.integers(1, n_t + 1))
            ql = int(rng.integers(1, min(w, kl) + 1))
            desc[i] = (i, kl - ql, ql, kl)
        else:  # zero-length suffix: inert row, must output exact 0
            desc[i] = (i, int(rng.integers(0, cap)), 0, int(rng.integers(1, cap)))
    return q, kp, vp, tables, jnp.asarray(desc)


@pytest.mark.parametrize(
    "b,w,h,kv,dh,bs,n_t", [(5, 6, 8, 4, 32, 16, 4), (6, 4, 4, 4, 16, 4, 3), (3, 8, 16, 2, 64, 8, 2)]
)
def test_mixed_prefill_attention_sweep(b, w, h, kv, dh, bs, n_t):
    """Unified kernel vs the jnp ref vs an independent float64 numpy
    oracle on a batch mixing every descriptor kind the engine emits."""
    from repro.kernels.chunked_prefill.kernel import mixed_prefill_attention_pallas
    from repro.kernels.chunked_prefill.ref import mixed_prefill_attention_ref

    rng = np.random.default_rng(b * w + n_t)
    q, kp, vp, tables, desc = _rand_mixed_case(rng, b, w, h, kv, dh, bs, n_t)
    o_p = mixed_prefill_attention_pallas(q, kp, vp, tables, desc)
    o_r = mixed_prefill_attention_ref(q, kp, vp, tables, desc)
    assert_allclose(np.asarray(o_p), np.asarray(o_r), rtol=2e-5, atol=2e-5)
    o_n = _mixed_oracle_np(q, kp, vp, tables, desc)
    assert_allclose(np.asarray(o_r), o_n, rtol=1e-5, atol=1e-5)
    # dead lanes (j >= q_len) must be exactly zero in both implementations
    lanes = np.arange(w)[None, :] >= np.asarray(desc)[:, 2][:, None]
    assert (np.asarray(o_p)[lanes] == 0).all() and (np.asarray(o_r)[lanes] == 0).all()


@pytest.mark.parametrize("budget", [None, 4096], ids=["one-tile", "query-tiles"])
def test_latent_geometry_kernels_match_the_reference(monkeypatch, budget):
    """Absorbed-MLA geometry in both kernels: one KV head whose first
    ``v_dim`` lanes are the values (no V pool), a scale passed in; the
    chunked-prefill kernel with its lanes in one query block, and cut into
    query tiles on a grid axis of their own (a small fast-memory budget)."""
    from repro.kernels.chunked_prefill import kernel as cp
    from repro.kernels.chunked_prefill.ref import mixed_prefill_attention_ref
    from repro.kernels.decode_attention.kernel import paged_decode_attention_pallas
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref

    b, w, h, dh, dv, bs, n_t, scale = 4, 8, 4, 24, 16, 4, 5, 0.3
    if budget is not None:
        monkeypatch.setattr(cp, "VMEM_BUDGET", budget)
        assert cp._q_tile_lanes(w, h, dh, dv, 4) == 2  # four query tiles
    rng = np.random.default_rng(11)
    q, kp, _, tables, desc = _rand_mixed_case(rng, b, w, h, 1, dh, bs, n_t)
    o_p = cp.mixed_prefill_attention_pallas(q, kp, None, tables, desc, scale=scale, v_dim=dv)
    o_r = mixed_prefill_attention_ref(q, kp, None, tables, desc, scale, dv)
    assert o_p.shape == (b, w, h, dv)
    assert_allclose(np.asarray(o_p), np.asarray(o_r), rtol=2e-5, atol=2e-5)
    lanes = np.arange(w)[None, :] >= np.asarray(desc)[:, 2][:, None]
    assert (np.asarray(o_p)[lanes] == 0).all()
    lens = jnp.asarray(rng.integers(1, n_t * bs + 1, size=b), jnp.int32)
    d_p = paged_decode_attention_pallas(q[:, 0], kp, None, tables, lens, scale=scale, v_dim=dv)
    d_r = paged_decode_attention_ref(q[:, 0], kp, None, tables, lens, scale, dv)
    assert_allclose(np.asarray(d_p), np.asarray(d_r), rtol=2e-5, atol=2e-5)


@given(
    b=st.integers(1, 6),
    w=st.integers(1, 7),
    bs=st.sampled_from([4, 8]),
    n_t=st.integers(1, 4),
    seed=st.integers(0, 2**16),
)
@settings(max_examples=15, deadline=None)
def test_mixed_prefill_attention_property(b, w, bs, n_t, seed):
    """Ragged descriptor mixes under hypothesis: pallas == ref for any
    (decode / cold / warm / boundary / zero-length) row combination."""
    from repro.kernels.chunked_prefill.kernel import mixed_prefill_attention_pallas
    from repro.kernels.chunked_prefill.ref import mixed_prefill_attention_ref

    rng = np.random.default_rng(seed)
    q, kp, vp, tables, desc = _rand_mixed_case(rng, b, w, 4, 2, 16, bs, n_t)
    o_p = mixed_prefill_attention_pallas(q, kp, vp, tables, desc)
    o_r = mixed_prefill_attention_ref(q, kp, vp, tables, desc)
    assert_allclose(np.asarray(o_p), np.asarray(o_r), rtol=2e-5, atol=2e-5)
    lanes = np.arange(w)[None, :] >= np.asarray(desc)[:, 2][:, None]
    assert (np.asarray(o_p)[lanes] == 0).all()


def test_mixed_prefill_trash_blocks_never_leak():
    """Positions past ``kv_len`` — including whole table entries pointing
    at a garbage trash block (how the engine pads dead lanes' K/V
    scatter) — must contribute exactly nothing to any live lane."""
    from repro.kernels.chunked_prefill.ref import mixed_prefill_attention_ref

    b, w, h, kv, dh, bs = 2, 4, 4, 2, 16, 8
    kk = jax.random.PRNGKey(3)
    q = jax.random.normal(kk, (b, w, h, dh))
    kp = jax.random.normal(jax.random.fold_in(kk, 1), (7, bs, kv, dh))
    vp = jax.random.normal(jax.random.fold_in(kk, 2), (7, bs, kv, dh))
    trash = 6
    tables = jnp.asarray([[0, 1, trash], [2, 3, trash]], jnp.int32)
    # row 0: warm fill ending mid-block-1; row 1: decode at the tip
    desc = jnp.asarray([[0, 8, 4, 12], [1, 10, 1, 11]], jnp.int32)
    base = mixed_prefill_attention_ref(q, kp, vp, tables, desc)
    kp2 = kp.at[trash].set(1e4).at[1, 4:].set(-1e4).at[3, 3:].set(-1e4)
    vp2 = vp.at[trash].set(1e4).at[1, 4:].set(-1e4).at[3, 3:].set(-1e4)
    poisoned = mixed_prefill_attention_ref(q, kp2, vp2, tables, desc)
    assert_allclose(np.asarray(base), np.asarray(poisoned), rtol=0, atol=0)


@pytest.mark.parametrize(
    "kinds",
    [MIXED_KINDS] + [(k, "dead") for k in MIXED_KINDS[:4] + ("edge", "full")] + [("dead",)],
    ids=["mixed", "decode", "cold", "warm", "boundary", "block-edge", "full-table", "all-dead"],
)
def test_mixed_prefill_kernel_never_reads_past_kv_len(kinds):
    """Every pool block the table maps past a row's live blocks (``0 ..
    cdiv(kv_len, bs) - 1``), and every block of a ``q_len == 0`` row, is
    NaN: a kernel that still ran its update on one would carry the NaN
    into the output (``0 * NaN`` in ``p @ v``), so the output must be
    bit-identical to the clean run."""
    from repro.kernels.chunked_prefill.kernel import mixed_prefill_attention_pallas

    b, w, h, kv, dh, bs, n_t = 5, 4, 4, 2, 16, 4, 4
    rng = np.random.default_rng(len(kinds) * 7 + sum(map(len, kinds)))
    q, kp, vp, tables, desc = _rand_mixed_case(rng, b, w, h, kv, dh, bs, n_t, kinds)
    d, tb = np.asarray(desc), np.asarray(tables)
    dead = []
    for slot, _, q_len, kv_len in d:
        live = -(-kv_len // bs) if q_len > 0 else 0
        dead.extend(tb[slot, live:])
    dead = np.asarray(dead, np.int32)
    assert dead.size
    kp2, vp2 = kp.at[dead].set(np.nan), vp.at[dead].set(np.nan)
    clean = np.asarray(mixed_prefill_attention_pallas(q, kp, vp, tables, desc))
    poisoned = np.asarray(mixed_prefill_attention_pallas(q, kp2, vp2, tables, desc))
    assert np.isfinite(clean).all()
    assert np.array_equal(clean, poisoned)


def test_mixed_prefill_verify_rows_match_per_lane_decode():
    """Speculative VERIFY descriptors — ``q_len = k + 1`` starting at the
    row's committed position — must be lane-for-lane identical to k+1
    independent decode descriptors over the same resident pool K/V: the
    kernel-level fact that makes draft-k/verify-1 greedy accept-prefix
    bit-identical to plain 1-token decode."""
    from repro.kernels.chunked_prefill.kernel import mixed_prefill_attention_pallas
    from repro.kernels.chunked_prefill.ref import mixed_prefill_attention_ref

    b, w, h, kv, dh, bs, n_t = 3, 5, 4, 2, 16, 8, 3
    rng = np.random.default_rng(17)
    kk = jax.random.PRNGKey(11)
    n_pool = b * n_t + 1
    q = jax.random.normal(kk, (b, w, h, dh))
    kp = jax.random.normal(jax.random.fold_in(kk, 1), (n_pool, bs, kv, dh))
    vp = jax.random.normal(jax.random.fold_in(kk, 2), (n_pool, bs, kv, dh))
    tables = jnp.asarray(
        rng.permutation(n_pool - 1)[: b * n_t].reshape(b, n_t), jnp.int32
    )
    k = w - 1  # draft_k: verify q_len = k + 1 = w lanes
    q0 = [3, 7, 0]  # per-row committed position (q_start)
    desc_v = jnp.asarray(
        [[i, q0[i], k + 1, q0[i] + k + 1] for i in range(b)], jnp.int32
    )
    o_v = mixed_prefill_attention_ref(q, kp, vp, tables, desc_v)
    o_vp = mixed_prefill_attention_pallas(q, kp, vp, tables, desc_v)
    assert_allclose(np.asarray(o_vp), np.asarray(o_v), rtol=2e-5, atol=2e-5)
    assert_allclose(
        np.asarray(o_v), _mixed_oracle_np(q, kp, vp, tables, desc_v),
        rtol=1e-5, atol=1e-5,
    )
    # verify lane j == a plain q_len=1 decode descriptor at q_start + j
    for j in range(k + 1):
        desc_d = jnp.asarray(
            [[i, q0[i] + j, 1, q0[i] + j + 1] for i in range(b)], jnp.int32
        )
        o_d = mixed_prefill_attention_ref(q[:, j : j + 1], kp, vp, tables, desc_d)
        assert_allclose(
            np.asarray(o_v)[:, j], np.asarray(o_d)[:, 0], rtol=1e-6, atol=1e-6
        )


# ---------------- ssd scan ----------------
@pytest.mark.parametrize("b,l,h,hd,ds", [(1, 16, 2, 8, 8), (2, 32, 4, 16, 8), (2, 64, 2, 32, 16)])
def test_ssd_chunk_sweep(b, l, h, hd, ds):
    kk = jax.random.PRNGKey(l)
    x = jax.random.normal(kk, (b, l, h, hd))
    bb = jax.random.normal(jax.random.fold_in(kk, 1), (b, l, h, ds))
    cc = jax.random.normal(jax.random.fold_in(kk, 2), (b, l, h, ds))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(kk, 3), (b, l, h)))
    a = -jnp.exp(jax.random.normal(jax.random.fold_in(kk, 4), (h,)))
    outs_p = ssd_chunk_pallas(x, bb, cc, dt, a)
    outs_r = ssd_chunk_ref(x, bb, cc, dt, a)
    for o_p, o_r in zip(outs_p, outs_r):
        assert_allclose(np.asarray(o_p), np.asarray(o_r), rtol=1e-4, atol=1e-4)
