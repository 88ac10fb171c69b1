"""Serving-tier tests: paged decode kernel, page allocator, and the
continuous-batching engine (CPU, Pallas interpret mode)."""
import numpy as np

import jax
import jax.numpy as jnp
import pytest

from incubator_mxnet_tpu.models import transformer as tfm
from incubator_mxnet_tpu.ops.pallas_kernels import (
    DECODE_BLOCK, dense_decode_attention, flash_decode,
    paged_decode_attention, paged_decode_attention_wide)
from incubator_mxnet_tpu.serving import PageAllocator, ServingEngine


def _small_cfg(**kw):
    base = dict(vocab=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
                max_len=64)
    base.update(kw)
    return tfm.TransformerConfig(**base)


def _pool(k_pages, v_pages, layers=1, layer=0):
    """(P, ps, H, D) test pages -> the kernels' pool,
    (L, H, P, ps, 2 * D): head-major, K|V fused per row, the pages in
    layer `layer` and noise in every other."""
    kv = np.concatenate([k_pages, v_pages], -1).transpose(2, 0, 1, 3)
    pool = np.random.RandomState(99).randn(layers, *kv.shape)
    pool[layer] = kv
    return jnp.asarray(pool.astype(k_pages.dtype))


def _gather_dense(k_pages, v_pages, page_table, page_size):
    """Rebuild the per-sequence dense caches a page table describes
    (pages given token-major, (P, ps, H, D))."""
    B, P_max = page_table.shape
    T = P_max * page_size
    H, D = k_pages.shape[2], k_pages.shape[3]
    kc = np.zeros((B, T, H, D), np.float32)
    vc = np.zeros((B, T, H, D), np.float32)
    for b in range(B):
        for j in range(P_max):
            pg = page_table[b, j]
            kc[b, j * page_size:(j + 1) * page_size] = k_pages[pg]
            vc[b, j * page_size:(j + 1) * page_size] = v_pages[pg]
    return kc, vc


# -- kernel ------------------------------------------------------------------

def test_paged_decode_matches_dense_ragged():
    rng = np.random.RandomState(0)
    B, H, D, ps, P, P_max = 4, 2, 32, 8, 16, 4
    q = rng.randn(B, H, D).astype(np.float32)
    k_pages = rng.randn(P, ps, H, D).astype(np.float32)
    v_pages = rng.randn(P, ps, H, D).astype(np.float32)
    # ragged per-sequence depths, incl. one page-aligned and one dead slot
    n_valid = np.array([13, 1, 16, 0], np.int32)
    page_table = np.array([[1, 2, 3, 0], [4, 0, 0, 0],
                           [5, 6, 0, 0], [0, 0, 0, 0]], np.int32)
    got = np.asarray(paged_decode_attention(
        jnp.asarray(q), _pool(k_pages, v_pages),
        jnp.asarray(page_table), jnp.asarray(n_valid), interpret=True))
    kc, vc = _gather_dense(k_pages, v_pages, page_table, ps)
    want = np.asarray(dense_decode_attention(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
        jnp.asarray(n_valid)))
    live = n_valid > 0
    np.testing.assert_allclose(got[live], want[live], rtol=2e-5, atol=2e-5)
    # the dead slot must still be finite (zero-length softmax guard)
    assert np.all(np.isfinite(got))


def test_paged_decode_pages_reused_after_free():
    """A page freed by one sequence and reallocated to another must read
    the NEW contents — the kernel has no per-page residue."""
    rng = np.random.RandomState(1)
    H, D, ps, P = 2, 16, 4, 8
    alloc = PageAllocator(P, ps)
    pages_a = alloc.alloc(2)
    k_pages = rng.randn(P, ps, H, D).astype(np.float32)
    v_pages = rng.randn(P, ps, H, D).astype(np.float32)
    alloc.free(pages_a)
    pages_b = alloc.alloc(2)  # FIFO recycling reuses a's pages eventually
    # overwrite the reused pages with new K/V (what prefill would do)
    for pg in pages_b:
        k_pages[pg] = rng.randn(ps, H, D)
        v_pages[pg] = rng.randn(ps, H, D)
    table = np.array([alloc.table_row(pages_b, 4)], np.int32)
    n_valid = np.array([2 * ps], np.int32)
    q = rng.randn(1, H, D).astype(np.float32)
    got = np.asarray(paged_decode_attention(
        jnp.asarray(q), _pool(k_pages, v_pages),
        jnp.asarray(table), jnp.asarray(n_valid), interpret=True))
    kc, vc = _gather_dense(k_pages, v_pages, table, ps)
    want = np.asarray(dense_decode_attention(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
        jnp.asarray(n_valid)))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("Q", [1, 4])
def test_paged_decode_wide_matches_dense_per_row(Q):
    """Row i of the wide kernel is single-query attention at depth
    n_base + i + 1 over the same pages (paged prefix + causal within
    the call)."""
    rng = np.random.RandomState(3)
    B, H, D, ps, P, P_max = 3, 2, 32, 8, 16, 4
    q = rng.randn(B, Q, H, D).astype(np.float32)
    k_pages = rng.randn(P, ps, H, D).astype(np.float32)
    v_pages = rng.randn(P, ps, H, D).astype(np.float32)
    n_base = np.array([11, 0, 16], np.int32)
    page_table = np.array([[1, 2, 3, 0], [4, 0, 0, 0], [5, 6, 7, 0]],
                          np.int32)
    got = np.asarray(paged_decode_attention_wide(
        jnp.asarray(q), _pool(k_pages, v_pages),
        jnp.asarray(page_table), jnp.asarray(n_base), interpret=True))
    kc, vc = _gather_dense(k_pages, v_pages, page_table, ps)
    for i in range(Q):
        want = np.asarray(dense_decode_attention(
            jnp.asarray(q[:, i]), jnp.asarray(kc), jnp.asarray(vc),
            jnp.asarray(n_base + i + 1)))
        np.testing.assert_allclose(got[:, i], want, rtol=2e-5, atol=2e-5)


# (page size, table width, heads, VMEM a grid step may take, heads it then
# takes): the last block cut short by the table; a table narrower than one
# block; two head groups; a head count no group divides, down to one head
# a grid step
_WALKS = {"blocks": (16, 20, 3, None, 3), "narrow": (16, 3, 2, None, 2),
          "groups": (8, 20, 6, 160 * 1024, 3),
          "prime": (8, 20, 5, 80 * 1024, 1)}


@pytest.mark.parametrize("Q", [1, 5])
@pytest.mark.parametrize("walk", list(_WALKS))
def test_paged_decode_walks_blocks(walk, Q, monkeypatch):
    """The block walk against the dense oracle: depths of nothing (a dead
    slot), one token, exactly one block, one block and a token, and all
    the table holds, and past it (the wide rows that overrun are
    dropped, the rows before them whole); a permuted table, a layer that
    is not the first."""
    from incubator_mxnet_tpu.ops import pallas_kernels as pk

    ps, W, H, vmem, heads = _WALKS[walk]
    D, B = 16, 6
    block = pk.paged_block_tokens(ps)
    assert block == 128
    if vmem is not None:
        monkeypatch.setattr(pk, "_PAGED_BLOCK_VMEM_BYTES", vmem)
    assert pk._paged_head_group(H, Q, block, 2 * D, jnp.float32) == heads
    rng = np.random.RandomState(17)
    P = B * W + 1
    k_pages = rng.randn(P, ps, H, D).astype(np.float32)
    v_pages = rng.randn(P, ps, H, D).astype(np.float32)
    table = (1 + rng.permutation(B * W)).reshape(B, W).astype(np.int32)
    cap = W * ps
    # what the LAST query row attends, per slot
    depth = np.array([0, Q, min(block, cap), min(block + 1, cap - 1), cap,
                      cap + Q - 2], np.int32)
    table[0] = 0  # the dead slot owns nothing
    pool = _pool(k_pages, v_pages, layers=3, layer=2)
    q = rng.randn(B, Q, H, D).astype(np.float32)
    if Q == 1:
        got = np.asarray(paged_decode_attention(
            jnp.asarray(q[:, 0]), pool, jnp.asarray(table),
            jnp.asarray(depth), layer=2, interpret=True))[:, None]
    else:
        got = np.asarray(paged_decode_attention_wide(
            jnp.asarray(q), pool, jnp.asarray(table),
            jnp.asarray(np.maximum(depth - Q, 0)), layer=2, interpret=True))
    kc, vc = _gather_dense(k_pages, v_pages, table, ps)
    for i in range(Q):
        row_depth = depth - (Q - 1 - i)
        want = np.asarray(dense_decode_attention(
            jnp.asarray(q[:, i]), jnp.asarray(kc), jnp.asarray(vc),
            jnp.asarray(np.maximum(row_depth, 0))))
        held = (row_depth > 0) & (row_depth <= cap)
        assert held[1:5].all()
        np.testing.assert_allclose(got[held, i], want[held], rtol=2e-5,
                                   atol=2e-5)
    assert np.all(np.isfinite(got))
    # a dead slot costs no copy and no loop step, and reads as zeros
    assert np.all(got[0] == 0)


def test_paged_pool_too_large_for_vmem_raises():
    """A pool whose per-head block cannot sit in VMEM is an error naming
    the largest pool that fits — never a silent dense fallback — for the
    kernel that maps it there, paged_diff_attention. paged_decode_attention
    leaves the pool in HBM and takes one of any size."""
    from incubator_mxnet_tpu.ops import pallas_kernels as pk

    H, D, ps = 2, 64, 16
    fits = (pk.PAGED_VMEM_LIMIT_BYTES - pk._PAGED_VMEM_RESERVE_BYTES) \
        // (pk.paged_pool_vmem_bytes(1, ps, D, jnp.bfloat16))
    assert fits == 7680
    pool = jax.ShapeDtypeStruct((1, H, fits + 1, ps, 2 * D), jnp.bfloat16)
    q = jax.ShapeDtypeStruct((1, 1, 4, D), jnp.float32)
    pt = jax.ShapeDtypeStruct((1, 4), jnp.int32)
    nv = jax.ShapeDtypeStruct((1,), jnp.int32)
    with pytest.raises(ValueError, match=f"at most {fits} such pages"):
        jax.eval_shape(pk.paged_diff_attention, q, pool, pt, nv)
    ok = jax.ShapeDtypeStruct((1, H, fits, ps, 2 * D), jnp.bfloat16)
    jax.eval_shape(pk.paged_diff_attention, q, ok, pt, nv)


def test_paged_decode_takes_a_pool_past_the_vmem_limit():
    """8 x 7680 pages a head: the walk gathers a slot's pages from HBM, so
    the pool's size is no concern of the kernel's."""
    H, D, ps = 2, 64, 16
    pool = jax.ShapeDtypeStruct((1, H, 8 * 7680, ps, 2 * D), jnp.bfloat16)
    q = jax.ShapeDtypeStruct((1, H, D), jnp.bfloat16)
    pt = jax.ShapeDtypeStruct((1, 4), jnp.int32)
    nv = jax.ShapeDtypeStruct((1,), jnp.int32)
    out = jax.eval_shape(paged_decode_attention, q, pool, pt, nv)
    assert out.shape == (1, H, D)


# -- the carried pool: paged programs against the dense cache ------------------

_PS, _W = 4, 8  # page size, table width: 32 positions a slot


def _deep_cfg(**kw):
    # three layers: a write or a read in the wrong layer shows
    return _small_cfg(n_layers=3, max_len=_PS * _W, **kw)


def _noise_pool(cfg, slots, seed=7):
    """A pool of noise, not zeros: a row read from where nothing wrote
    it, or written where it should not be, changes a number."""
    paged = tfm.init_paged_kv_cache(cfg, slots * _W + 1, _PS)
    rng = np.random.RandomState(seed)
    return {"kv": jnp.asarray(
        rng.randn(*paged["kv"].shape).astype(np.float32))}


def _tables(slots, live, seed=5):
    """Shuffled page ownership; slots not in `live` keep the all-zero
    row of a dead slot."""
    perm = 1 + np.random.RandomState(seed).permutation(slots * _W)
    table = np.zeros((slots, _W), np.int32)
    for s in live:
        table[s] = perm[s * _W:(s + 1) * _W]
    return table


def _dense_run(params, cfg, prompt, feed):
    """Reference: dense-cache prefill of `prompt`, then decode_step over
    `feed`. Returns (prefill logits (V,), [logits after each fed token],
    cache)."""
    cache = tfm.init_kv_cache(cfg, 1, cfg.max_len)
    cache, lg = tfm.prefill(params, cache, jnp.asarray(prompt)[None], cfg)
    out = []
    for t in feed:
        step, cache = tfm.decode_step(
            params, cache, jnp.asarray([t], jnp.int32), cfg)
        out.append(np.asarray(step[0]))
    return np.asarray(lg[0]), out, cache


def _pool_rows(paged, table_row, n):
    """K and V of positions [0, n) as a page table row maps them:
    (L, n, H, Dh) each."""
    kv = np.asarray(paged["kv"])  # (L, H, P, ps, 2*Dh)
    rows = kv[:, :, table_row].reshape(kv.shape[0], kv.shape[1], -1,
                                       kv.shape[-1])[:, :, :n]
    rows = rows.transpose(0, 2, 1, 3)
    d = rows.shape[-1] // 2
    return rows[..., :d], rows[..., d:]


def _changed_rows(old, new):
    """{(page, offset)} of the rows that differ in any layer or head."""
    diff = np.any(np.asarray(old["kv"]) != np.asarray(new["kv"]),
                  axis=(0, 1, 4))
    return {(int(p), int(o)) for p, o in zip(*np.nonzero(diff))}


def _owned(table_row, positions):
    return {(int(table_row[t // _PS]), t % _PS) for t in positions}


@pytest.fixture(scope="module")
def prefilled():
    """Three slots, the middle one dead, prefilled through one bucket of
    16 with a padding row: what the decode and wide cases start from."""
    cfg = _deep_cfg()
    params = tfm.init_params(cfg, seed=4)
    rng = np.random.RandomState(21)
    lens = np.array([13, 0, 6], np.int32)
    table = _tables(3, live=(0, 2))
    prompts = rng.randint(1, cfg.vocab, (3, 16)).astype(np.int32)
    before = _noise_pool(cfg, 3)
    paged, logits = tfm.prefill_paged(
        params, before, jnp.asarray(prompts), jnp.asarray(lens),
        jnp.asarray(table), cfg)
    return dict(cfg=cfg, params=params, lens=lens, table=table,
                prompts=prompts, before=before, paged=paged,
                logits=np.asarray(logits))


def test_prefill_paged_matches_dense_and_writes_only_its_rows(prefilled):
    f = prefilled
    for s in (0, 2):
        n = int(f["lens"][s])
        want, _, cache = _dense_run(f["params"], f["cfg"],
                                    f["prompts"][s, :n], [])
        np.testing.assert_allclose(f["logits"][s], want, rtol=2e-4,
                                   atol=2e-4)
        # every layer's rows sit where the table says, K and V apart
        k, v = _pool_rows(f["paged"], f["table"][s], n)
        np.testing.assert_allclose(k, np.asarray(cache["k"])[:, 0, :n],
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(v, np.asarray(cache["v"])[:, 0, :n],
                                   rtol=2e-5, atol=2e-5)
    # the bucket's padding (positions 13-15, 6-15, the whole dead row)
    # is stored nowhere: bit for bit, only the real rows changed
    assert _changed_rows(f["before"], f["paged"]) == (
        _owned(f["table"][0], range(13)) | _owned(f["table"][2], range(6)))


def test_decode_step_paged_matches_dense_at_own_depths(prefilled):
    f = prefilled
    cfg, params, table = f["cfg"], f["params"], f["table"]
    feed = np.random.RandomState(8).randint(1, cfg.vocab, (3, 3)).astype(
        np.int32)
    paged, pos, got = f["paged"], f["lens"].copy(), []
    for i in range(3):  # the third step crosses slot 0's page boundary
        before = paged
        lg, paged = tfm.decode_step_paged(
            params, paged, jnp.asarray(feed[:, i]), jnp.asarray(pos),
            jnp.asarray(table), cfg)
        got.append(np.asarray(lg))
        # one row per live slot; the dead slot's lands on the null page
        assert _changed_rows(before, paged) == (
            _owned(table[0], [pos[0]]) | _owned(table[2], [pos[2]])
            | {(0, 0)})
        pos[[0, 2]] += 1
    for s in (0, 2):
        n = int(f["lens"][s])
        _, want, _ = _dense_run(params, cfg, f["prompts"][s, :n], feed[s])
        for i in range(3):
            np.testing.assert_allclose(got[i][s], want[i], rtol=2e-4,
                                       atol=2e-4)


@pytest.mark.parametrize("n_real", [4, 2])
def test_decode_step_paged_wide_matches_dense_rows(prefilled, n_real):
    """Q = 4 rows a slot from an unaligned start: row j is the dense
    cache's step j; rows past n_real (chunk padding) store nothing."""
    f = prefilled
    cfg, params, table = f["cfg"], f["params"], f["table"]
    feed = np.random.RandomState(9).randint(1, cfg.vocab, (3, 4)).astype(
        np.int32)
    n = np.array([n_real, 0, 4], np.int32)
    lg, paged = tfm.decode_step_paged_wide(
        params, f["paged"], jnp.asarray(feed), jnp.asarray(f["lens"]),
        jnp.asarray(n), jnp.asarray(table), cfg)
    assert _changed_rows(f["paged"], paged) == (
        _owned(table[0], range(13, 13 + n_real))
        | _owned(table[2], range(6, 10)))
    for s in (0, 2):
        _, want, _ = _dense_run(params, cfg,
                                f["prompts"][s, :int(f["lens"][s])], feed[s])
        for j in range(n[s]):  # causal within the call: each real row
            np.testing.assert_allclose(np.asarray(lg)[s, j], want[j],
                                       rtol=2e-4, atol=2e-4)


def test_wide_rows_past_capacity_are_dropped(prefilled):
    """Speculative rows running past the table's last position store
    nothing and index nothing out of bounds."""
    f = prefilled
    cfg, table = f["cfg"], f["table"]
    start = np.array([cfg.max_len - 2, 0, 6], np.int32)
    feed = np.ones((3, 4), np.int32)
    lg, paged = tfm.decode_step_paged_wide(
        f["params"], f["paged"], jnp.asarray(feed), jnp.asarray(start),
        jnp.asarray(np.array([4, 0, 1], np.int32)), jnp.asarray(table),
        cfg)
    assert np.all(np.isfinite(np.asarray(lg)))
    assert _changed_rows(f["paged"], paged) == (
        _owned(table[0], [cfg.max_len - 2, cfg.max_len - 1])
        | _owned(table[2], [6]))


def test_paged_pages_reused_after_free_hold_the_new_sequence(prefilled):
    """Slot 0's pages go to a new request in another slot: what it reads
    is its own prefill, not the residue."""
    f = prefilled
    cfg, params = f["cfg"], f["params"]
    table = np.zeros((3, _W), np.int32)
    table[1] = f["table"][0][::-1]  # the freed pages, handed out again
    prompt = np.random.RandomState(13).randint(1, cfg.vocab, (1, 8)).astype(
        np.int32)
    lens = np.array([0, 7, 0], np.int32)
    prompts = np.concatenate([np.zeros_like(prompt), prompt,
                              np.zeros_like(prompt)])
    paged, lg0 = tfm.prefill_paged(
        params, f["paged"], jnp.asarray(prompts), jnp.asarray(lens),
        jnp.asarray(table), cfg)
    feed = np.array([3, 5], np.int32)
    got = []
    for i, t in enumerate(feed):
        lg, paged = tfm.decode_step_paged(
            params, paged, jnp.asarray([0, t, 0], jnp.int32),
            jnp.asarray(lens + np.array([0, i, 0], np.int32)),
            jnp.asarray(table), cfg)
        got.append(np.asarray(lg)[1])
    want0, want, _ = _dense_run(params, cfg, prompt[0, :7], feed)
    np.testing.assert_allclose(np.asarray(lg0)[1], want0, rtol=2e-4,
                               atol=2e-4)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4)


def test_paged_programs_run_the_expert_branch():
    """n_experts sits in the same loop body: full rows through
    prefill_paged and one decode_step_paged against the dense cache
    (same token batch, so the routers' capacity drops agree)."""
    cfg = _deep_cfg(n_experts=2)
    params = tfm.init_params(cfg, seed=6)
    prompts = np.random.RandomState(2).randint(1, cfg.vocab, (2, 8)).astype(
        np.int32)
    table = _tables(2, live=(0, 1))
    lens = np.array([8, 8], np.int32)
    paged, lg = tfm.prefill_paged(
        params, _noise_pool(cfg, 2), jnp.asarray(prompts),
        jnp.asarray(lens), jnp.asarray(table), cfg)
    cache = tfm.init_kv_cache(cfg, 2, cfg.max_len)
    cache, want = tfm.prefill(params, cache, jnp.asarray(prompts), cfg)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(want), rtol=2e-4,
                               atol=2e-4)
    tok = jnp.asarray([9, 11], jnp.int32)
    lg, _ = tfm.decode_step_paged(params, paged, tok, jnp.asarray(lens),
                                  jnp.asarray(table), cfg)
    want, _ = tfm.decode_step(params, cache, tok, cfg)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(want), rtol=2e-4,
                               atol=2e-4)


def test_dense_decode_accepts_per_sequence_vector():
    rng = np.random.RandomState(2)
    B, T, H, D = 3, 24, 2, 8
    q = jnp.asarray(rng.randn(B, H, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    nv = np.array([3, 24, 11], np.int32)
    got = np.asarray(dense_decode_attention(q, k, v, jnp.asarray(nv)))
    for b in range(B):
        ref = np.asarray(dense_decode_attention(
            q[b:b + 1], k[b:b + 1], v[b:b + 1], int(nv[b])))
        np.testing.assert_allclose(got[b:b + 1], ref, rtol=1e-6, atol=1e-6)


def test_flash_decode_accepts_per_sequence_vector():
    rng = np.random.RandomState(3)
    B, T, H, D = 3, 32, 2, 8
    q = jnp.asarray(rng.randn(B, H, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    nv = jnp.asarray(np.array([5, 32, 17], np.int32))
    got = np.asarray(flash_decode(q, k, v, nv, block_k=8, interpret=True))
    want = np.asarray(dense_decode_attention(q, k, v, nv))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_kv_cache_padded_to_decode_block():
    """Satellite: init_kv_cache rounds T_max up so flash_decode always
    tiles (no silent dense fallback on long caches)."""
    cfg = _small_cfg(max_len=512)
    cache = tfm.init_kv_cache(cfg, batch=1, max_len=200)
    T = cache["k"].shape[2]
    assert T == 256 and T % DECODE_BLOCK == 0
    # at or under one block, the kernel tiles as-is: no padding
    assert tfm.init_kv_cache(cfg, 1, 16)["k"].shape[2] == 16
    assert tfm.init_kv_cache(cfg, 1, 128)["k"].shape[2] == 128


def test_no_dense_fallback_on_standard_configs(monkeypatch):
    """The fallback counter stays 0 for caches init_kv_cache produces."""
    from incubator_mxnet_tpu import telemetry
    from incubator_mxnet_tpu.ops.pallas_kernels import (
        DENSE_FALLBACKS_TOTAL)
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    telemetry.refresh_from_env()
    try:
        telemetry.REGISTRY.reset()
        cfg = _small_cfg(max_len=512, use_flash=True)
        for max_len in (64, 130, 200):
            cache = tfm.init_kv_cache(cfg, 2, max_len)
            q = jnp.zeros((2, cfg.n_heads,
                           cfg.d_model // cfg.n_heads), jnp.float32)
            flash_decode(q, cache["k"][0], cache["v"][0], 1,
                         interpret=True)
        assert DENSE_FALLBACKS_TOTAL not in telemetry.prometheus_text()
        # an untiled cache passed directly IS counted
        k = jnp.zeros((1, 130, 2, 8), jnp.float32)
        flash_decode(jnp.zeros((1, 2, 8)), k, k, 1, interpret=True)
        assert DENSE_FALLBACKS_TOTAL in telemetry.prometheus_text()
    finally:
        monkeypatch.delenv("MXNET_TELEMETRY", raising=False)
        telemetry.refresh_from_env()
        telemetry.REGISTRY.reset()


# -- page allocator ----------------------------------------------------------

def test_allocator_alloc_free_reuse():
    a = PageAllocator(num_pages=6, page_size=4)
    assert a.capacity == 5 and a.num_free == 5
    p1 = a.alloc(3)
    assert len(p1) == 3 and 0 not in p1 and a.num_in_use == 3
    a.free(p1)
    assert a.num_free == 5 and a.num_in_use == 0
    # freed pages come back (FIFO order, never the null page)
    p2 = a.alloc(5)
    assert sorted(p2) == [1, 2, 3, 4, 5]


def test_allocator_exhaustion_is_all_or_nothing():
    a = PageAllocator(num_pages=4, page_size=2)
    assert a.alloc(2) is not None
    assert a.alloc(2) is None  # only 1 free: nothing gets allocated
    assert a.num_free == 1


def test_allocator_double_free_raises():
    a = PageAllocator(num_pages=4, page_size=2)
    p = a.alloc(1)
    a.free(p)
    with pytest.raises(ValueError):
        a.free(p)
    with pytest.raises(ValueError):
        a.free([0])  # the null page is never allocatable


def test_allocator_extend():
    a = PageAllocator(num_pages=8, page_size=4)
    p = a.alloc(a.pages_needed(5))  # 2 pages cover 5 tokens
    grown = a.extend(p, 5, 13)  # 13 tokens need 4 pages
    assert len(grown) == 4 and grown[:2] == p
    assert a.extend(grown, 13, 16) == grown  # same page count: no-op
    assert a.extend(grown, 16, 1000) is None  # can't grow: unchanged
    assert a.num_in_use == 4


def test_allocator_pages_needed():
    a = PageAllocator(num_pages=4, page_size=8)
    assert a.pages_needed(0) == 0
    assert a.pages_needed(1) == 1
    assert a.pages_needed(8) == 1
    assert a.pages_needed(9) == 2


# -- engine ------------------------------------------------------------------

def test_engine_token_identical_to_sequential_generate():
    """The continuous-batching acceptance bar: mixed-length requests
    sharing decode steps produce, per request, EXACTLY the tokens
    sequential greedy generate() produces."""
    cfg = _small_cfg()
    params = tfm.init_params(cfg, seed=3)
    rng = np.random.RandomState(11)
    prompts = [rng.randint(1, 64, size=(L,)).astype(np.int32)
               for L in (4, 11, 7, 3, 19, 5)]
    maxnew = [6, 3, 8, 5, 4, 7]
    eng = ServingEngine(params, cfg, slots=3, page_size=8, num_pages=24)
    rids = [eng.submit(p, m) for p, m in zip(prompts, maxnew)]
    res = eng.run()
    assert len(res) == len(prompts)
    # more requests than slots: depths must actually have interleaved
    assert eng.steps < sum(maxnew)
    for rid, p, m in zip(rids, prompts, maxnew):
        ref = np.asarray(
            tfm.generate(params, jnp.asarray(p)[None], m, cfg))[0]
        got = np.array(res[rid].tokens)
        np.testing.assert_array_equal(got, ref)
        assert res[rid].finish_reason == "length"
    # every page recycled after the fleet drains
    assert eng.allocator.num_in_use == 0
    assert eng.slots_in_use == 0


def test_engine_eos_stops_early_and_recycles():
    cfg = _small_cfg()
    params = tfm.init_params(cfg, seed=3)
    rng = np.random.RandomState(5)
    p = rng.randint(1, 64, size=(6,)).astype(np.int32)
    ref = np.asarray(tfm.generate(params, jnp.asarray(p)[None], 8, cfg))[0]
    eos = int(ref[2])
    stop = int(np.argmax(ref == eos))  # first occurrence ends the request
    eng = ServingEngine(params, cfg, slots=2, page_size=8, num_pages=16)
    rid = eng.submit(p, 8, eos_id=eos)
    out = eng.run()[rid]
    assert out.tokens == [int(t) for t in ref[:stop + 1]]
    assert out.finish_reason == "eos"
    assert eng.allocator.num_in_use == 0


def test_engine_backpressure_queues_until_pages_free():
    """Pool smaller than the workload: admission must wait, nothing is
    half-admitted, no page leaks, results stay exact."""
    cfg = _small_cfg()
    params = tfm.init_params(cfg, seed=3)
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, 64, size=(L,)).astype(np.int32)
               for L in (12, 9, 14, 6)]
    # pool fits ~one request at a time
    eng = ServingEngine(params, cfg, slots=4, page_size=8, num_pages=5)
    rids = [eng.submit(p, 4) for p in prompts]
    eng.step()
    assert eng.slots_in_use >= 1 and eng.queue_depth >= 1  # backpressured
    res = eng.run()
    for rid, p in zip(rids, prompts):
        ref = np.asarray(
            tfm.generate(params, jnp.asarray(p)[None], 4, cfg))[0]
        np.testing.assert_array_equal(np.array(res[rid].tokens), ref)
    assert eng.allocator.num_in_use == 0


def test_engine_rejects_unservable_requests():
    cfg = _small_cfg()
    params = tfm.init_params(cfg, seed=0)
    eng = ServingEngine(params, cfg, slots=2, page_size=8, num_pages=16)
    with pytest.raises(ValueError):
        eng.submit([], 4)
    with pytest.raises(ValueError):
        eng.submit([1, 2], 0)
    with pytest.raises(ValueError):
        eng.submit(np.ones(60, np.int32), 10)  # exceeds max_len


def test_engine_steady_state_zero_retraces(tmp_path, monkeypatch):
    """After the first wave compiles every bucket, further mixed-length
    traffic adds ZERO signatures and ZERO retraces (compilereg-gated —
    the property that makes the serving loop TPU-viable)."""
    from incubator_mxnet_tpu import telemetry
    from incubator_mxnet_tpu.telemetry import compilereg
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    monkeypatch.setenv("MXTPU_COMPILE_CACHE_DIR", str(tmp_path / "cc"))
    telemetry.refresh_from_env()
    compilereg.reset()
    try:
        cfg = _small_cfg()
        params = tfm.init_params(cfg, seed=3)
        rng = np.random.RandomState(1)
        eng = ServingEngine(params, cfg, slots=3, page_size=8)

        def totals():
            snap = compilereg.snapshot()
            return (sum(v["signatures"] for v in snap.values()),
                    sum(v["retraces"] for v in snap.values()))

        for _ in range(4):  # warmup wave touches every bucket <= 19
            eng.submit(rng.randint(1, 64, size=(19,)), 3)
            eng.submit(rng.randint(1, 64, size=(3,)), 2)
        eng.run()
        sigs1, re1 = totals()
        assert sigs1 > 0
        for L, m in [(3, 2), (9, 6), (14, 3), (2, 5), (7, 7), (19, 2)]:
            eng.submit(rng.randint(1, 64, size=(L,)), m)
        eng.run()
        sigs2, re2 = totals()
        assert (sigs2 - sigs1, re2 - re1) == (0, 0)
    finally:
        compilereg.reset()
        monkeypatch.delenv("MXNET_TELEMETRY", raising=False)
        telemetry.refresh_from_env()
        telemetry.REGISTRY.reset()


def test_engine_warm_precompiles_all_sites(tmp_path, monkeypatch):
    """warm() populates the compile cache; a second engine (fresh
    process stand-in) warms with ALL HITS — zero compiles at startup."""
    monkeypatch.setenv("MXTPU_COMPILE_CACHE_DIR", str(tmp_path / "cc"))
    cfg = _small_cfg()
    params = tfm.init_params(cfg, seed=0)
    eng = ServingEngine(params, cfg, slots=2, page_size=8)
    first = eng.warm()
    assert first and all(s in ("miss", "hit") for s in first.values())
    eng2 = ServingEngine(params, cfg, slots=2, page_size=8)
    second = eng2.warm()
    assert second.keys() == first.keys()
    assert all(s == "hit" for s in second.values()), second


def test_engine_telemetry_gauges(monkeypatch):
    from incubator_mxnet_tpu import telemetry
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    telemetry.refresh_from_env()
    try:
        telemetry.REGISTRY.reset()
        cfg = _small_cfg()
        params = tfm.init_params(cfg, seed=0)
        eng = ServingEngine(params, cfg, slots=2, page_size=8,
                            num_pages=16)
        eng.submit([1, 2, 3], 3)
        eng.run()
        text = telemetry.prometheus_text()
        for name in ("mxtpu_serving_requests_total",
                     "mxtpu_serving_tokens_total",
                     "mxtpu_serving_request_seconds",
                     "mxtpu_serving_slots_in_use",
                     "mxtpu_serving_pages_in_use"):
            assert name in text, name
    finally:
        monkeypatch.delenv("MXNET_TELEMETRY", raising=False)
        telemetry.refresh_from_env()
        telemetry.REGISTRY.reset()


# -- refcounted allocator + prefix cache -------------------------------------

def test_allocator_share_free_keeps_page_live():
    """share() adds a reference: the first free() only decrements, the
    LAST deref recycles the page into the pool."""
    a = PageAllocator(6, 4)
    pages = a.alloc(2)
    a.share(pages)
    assert all(a.refcount(p) == 2 for p in pages)
    a.free(pages)  # one of two refs: pages stay live
    assert a.num_in_use == 2 and a.num_free == 3
    assert all(a.refcount(p) == 1 for p in pages)
    a.free(pages)  # last deref recycles
    assert a.num_in_use == 0 and a.num_free == 5
    assert all(a.refcount(p) == 0 for p in pages)
    # sharing a dead page would read recycled garbage: must raise
    with pytest.raises(ValueError):
        a.share([pages[0]])


def test_allocator_cow_semantics():
    """cow() copies exactly once: an exclusive page returns itself (no
    copy), a shared page yields a fresh exclusive id and moves one
    reference; an empty pool returns None without touching state."""
    a = PageAllocator(4, 4)
    (p,) = a.alloc(1)
    assert a.cow(p) == p  # refcount 1: no copy needed
    a.share([p])
    fresh = a.cow(p)
    assert fresh not in (None, p)
    assert a.refcount(p) == 1 and a.refcount(fresh) == 1
    # pool now exhausted: a second cow on a re-shared page cannot copy
    a.share([p])
    (last,) = a.alloc(1)
    assert a.cow(p) is None
    assert a.refcount(p) == 2  # unchanged on failure
    a.free([last])
    assert a.cow(p) != p  # retry succeeds once a page frees
    with pytest.raises(ValueError):
        a.cow(99)


def test_allocator_gauges_count_shared_pages_once():
    a = PageAllocator(8, 4)
    pages = a.alloc(3)
    a.share(pages)
    a.share(pages[:1])
    assert a.num_in_use == 3  # 3 physical pages, 7 references
    assert a.occupancy() == 3 / 7
    assert a.refcount_histogram() == {2: 2, 3: 1}


def test_prefix_cache_insert_lookup_roundtrip():
    from incubator_mxnet_tpu.serving import PrefixCache
    a = PageAllocator(12, 4)
    cache = PrefixCache(a)
    prompt = np.arange(1, 11, dtype=np.int32)  # 10 tokens: 2 full + tail 2
    pages = a.alloc(3)
    newly = cache.insert(prompt, pages)
    assert newly == {0, 1, 2}
    assert cache.cached_pages == 3
    assert all(a.refcount(p) == 2 for p in pages)  # owner + cache
    full, partial = cache.lookup(prompt)
    assert full == pages[:2]
    assert partial is not None and partial[0] == pages[2]
    np.testing.assert_array_equal(partial[1], prompt[8:])
    # a prompt sharing only the first chunk matches one page, no partial
    other = np.concatenate([prompt[:4], np.full(6, 63, np.int32)])
    full, partial = cache.lookup(other)
    assert full == pages[:1] and partial is None
    # re-inserting the same prompt shares nothing new
    assert cache.insert(prompt, pages) == set()
    assert all(a.refcount(p) == 2 for p in pages)


def test_prefix_cache_evicts_lru_only_at_refcount_one():
    from incubator_mxnet_tpu.serving import PrefixCache
    a = PageAllocator(12, 4)
    cache = PrefixCache(a)
    p1 = a.alloc(2)
    p2 = a.alloc(2)
    cache.insert(np.arange(1, 9, dtype=np.int32), p1)
    cache.insert(np.arange(20, 28, dtype=np.int32), p2)
    a.free(p2)  # second prompt's owner finished; cache ref only
    # p1 still owner-referenced: eviction may only take p2's pages
    freed = cache.evict(10)
    assert freed == 2
    assert cache.cached_pages == 2
    assert all(a.refcount(p) == 2 for p in p1)
    a.free(p1)
    assert cache.evict(10) == 2  # interior nodes go once leaves do
    assert cache.cached_pages == 0 and a.num_in_use == 0


def test_prefix_cache_release_is_leaf_only():
    from incubator_mxnet_tpu.serving import PrefixCache
    a = PageAllocator(12, 4)
    cache = PrefixCache(a)
    pages = a.alloc(3)
    cache.insert(np.arange(1, 11, dtype=np.int32), pages)
    assert not cache.release(pages[0])  # mid-trie: children key off it
    assert cache.release(pages[2])      # partial leaf: droppable
    assert cache.cached_pages == 2
    assert a.refcount(pages[2]) == 1    # owner ref only now
    assert not cache.release(99)        # unknown page


# -- serving levers: prefix cache, chunked prefill, speculation --------------

def _mixed_trace(rng, n=6, vocab=64, max_len=64):
    """Seeded mixed trace where later prompts reuse earlier heads — the
    workload prefix caching exists for."""
    reqs = []
    for i in range(n):
        p_len = int(rng.randint(2, 40))
        prompt = rng.randint(1, vocab, p_len).astype(np.int32)
        if i >= 2 and rng.rand() < 0.7:
            base = reqs[int(rng.randint(0, len(reqs)))][0]
            keep = min(len(base), int(rng.randint(8, 36)))
            tail = rng.randint(1, vocab, max(1, p_len - keep))
            prompt = np.concatenate([base[:keep], tail.astype(np.int32)])
        m_new = int(rng.randint(1, min(12, max_len - prompt.size)))
        reqs.append((prompt, m_new))
    return reqs


def test_engine_token_identity_all_knob_combos():
    """The hard gate for every lever: greedy decode stays
    token-identical to sequential generate() across all 8 on/off
    combinations of prefix cache x chunked prefill x speculation."""
    import itertools
    cfg = _small_cfg()
    params = tfm.init_params(cfg, seed=3)
    reqs = _mixed_trace(np.random.RandomState(11))
    ref = [np.asarray(tfm.generate(params, jnp.asarray(p)[None], m,
                                   cfg))[0]
           for p, m in reqs]
    for pc, ck, sp in itertools.product([0, 1], repeat=3):
        eng = ServingEngine(params, cfg, slots=3, page_size=8,
                            num_pages=25, prefix_cache=pc,
                            prefill_chunk=6 if ck else 0,
                            spec_ngram=2 if sp else 0, spec_lookahead=3)
        rids = [eng.submit(p, m) for p, m in reqs]
        res = eng.run()
        for rid, want in zip(rids, ref):
            np.testing.assert_array_equal(
                np.array(res[rid].tokens), want,
                err_msg=f"combo prefix={pc} chunk={ck} spec={sp}")
        assert eng.slots_in_use == 0
        # only cache references may outlive the drained fleet
        held = (eng.prefix_cache.cached_pages
                if eng.prefix_cache is not None else 0)
        assert eng.allocator.num_in_use == held


def test_engine_prefix_cache_saves_prefill_and_cows_once():
    """Resubmitting a prompt maps its cached pages: the second prefill
    computes only the (always-recomputed) last token, and each shared
    partial page is copied exactly once per writer."""
    cfg = _small_cfg()
    params = tfm.init_params(cfg, seed=3)
    rng = np.random.RandomState(2)
    p = rng.randint(1, 64, 20).astype(np.int32)  # 2 full pages + tail 4
    ref = np.asarray(tfm.generate(params, jnp.asarray(p)[None], 4, cfg))[0]
    eng = ServingEngine(params, cfg, slots=2, page_size=8, num_pages=16,
                        prefix_cache=1)
    r1 = eng.submit(p, 4)
    res1 = eng.run()
    # first pass: miss, all 20 tokens prefilled, and the slot's own
    # cached partial page copy-on-wrote at its first decode token
    assert eng.prefix_hit_rate == 0.0
    assert eng.goodput()["prefill"] == 20
    assert eng.cow_copies == 1
    r2 = eng.submit(p, 4)
    res2 = eng.run()
    np.testing.assert_array_equal(np.array(res1[r1].tokens), ref)
    np.testing.assert_array_equal(np.array(res2[r2].tokens), ref)
    # second pass: 19 of 20 tokens came from the cache (the last prompt
    # token is always recomputed for its logits), plus one admission
    # copy of the cached partial page
    assert eng.prefix_tokens_saved == 19
    assert eng.prefix_hit_rate == 0.5
    assert eng.goodput()["prefill"] == 21
    assert eng.cow_copies == 2
    # identical tail: insert dedups, so no second decode-time cow
    assert eng.allocator.num_in_use == eng.prefix_cache.cached_pages == 3


def test_engine_all_levers_steady_state_zero_retraces(tmp_path,
                                                      monkeypatch):
    """With every lever on, the second identical trace adds ZERO
    signatures and ZERO retraces — wide programs and the page copy are
    one static shape each."""
    from incubator_mxnet_tpu import telemetry
    from incubator_mxnet_tpu.telemetry import compilereg
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    monkeypatch.setenv("MXTPU_COMPILE_CACHE_DIR", str(tmp_path / "cc"))
    telemetry.refresh_from_env()
    compilereg.reset()
    try:
        cfg = _small_cfg()
        params = tfm.init_params(cfg, seed=3)
        reqs = _mixed_trace(np.random.RandomState(4))
        eng = ServingEngine(params, cfg, slots=3, page_size=8,
                            num_pages=25, prefix_cache=1,
                            prefill_chunk=6, spec_ngram=2,
                            spec_lookahead=3)

        def totals():
            snap = compilereg.snapshot()
            return (sum(v["signatures"] for v in snap.values()),
                    sum(v["retraces"] for v in snap.values()))

        for p_, m_ in reqs:
            eng.submit(p_, m_)
        eng.run()
        sigs1, re1 = totals()
        assert sigs1 > 0
        sites = set(compilereg.snapshot())
        assert any(s.startswith("serving_wide_q") for s in sites)
        for p_, m_ in reqs:
            eng.submit(p_, m_)
        eng.run()
        assert totals() == (sigs1, re1)
    finally:
        compilereg.reset()
        monkeypatch.delenv("MXNET_TELEMETRY", raising=False)
        telemetry.refresh_from_env()
        telemetry.REGISTRY.reset()


def test_engine_knobs_off_builds_only_legacy_sites(tmp_path, monkeypatch):
    """All levers off must be byte-identical to the pre-lever engine:
    the compiled-program set contains exactly the legacy decode +
    prefill-bucket sites (no wide programs, no page copy)."""
    from incubator_mxnet_tpu import telemetry
    from incubator_mxnet_tpu.telemetry import compilereg
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    monkeypatch.setenv("MXTPU_COMPILE_CACHE_DIR", str(tmp_path / "cc"))
    telemetry.refresh_from_env()
    compilereg.reset()
    try:
        cfg = _small_cfg()
        params = tfm.init_params(cfg, seed=3)
        eng = ServingEngine(params, cfg, slots=3, page_size=8,
                            num_pages=25, prefix_cache=0,
                            prefill_chunk=0, spec_ngram=0)
        for p_, m_ in _mixed_trace(np.random.RandomState(4)):
            eng.submit(p_, m_)
        eng.run()
        sites = {s for s in compilereg.snapshot()
                 if s.startswith("serving_")}
        assert sites
        assert all(s == "serving_decode_step"
                   or s.startswith("serving_prefill_b") for s in sites)
        assert not hasattr(eng, "_page_copy")
        assert eng._wides == {}
    finally:
        compilereg.reset()
        monkeypatch.delenv("MXNET_TELEMETRY", raising=False)
        telemetry.refresh_from_env()
        telemetry.REGISTRY.reset()


def test_engine_debug_snapshot_v2_lever_sections():
    cfg = _small_cfg()
    params = tfm.init_params(cfg, seed=3)
    rng = np.random.RandomState(6)
    p = rng.randint(1, 64, 20).astype(np.int32)
    eng = ServingEngine(params, cfg, slots=2, page_size=8, num_pages=16,
                        prefix_cache=1, prefill_chunk=4, spec_ngram=2,
                        spec_lookahead=3)
    eng.submit(p, 4)
    eng.run()
    eng.submit(p, 4)
    eng.run()
    snap = eng.debug_snapshot()
    assert snap["schema"] == "mxtpu-serving-engine-debug-v2"
    prefix = snap["prefix_cache"]
    assert prefix["cached_pages"] == 3
    assert prefix["hits"] == 1 and prefix["lookups"] == 2
    assert prefix["tokens_saved"] == 19
    assert prefix["refcount_histogram"]  # str refcount -> page count
    spec = snap["speculation"]
    assert spec["ngram"] == 2 and spec["lookahead"] == 3
    assert spec["proposed"] >= spec["accepted"] >= 0
    chunked = snap["chunked_prefill"]
    assert chunked["chunk"] == 4 and chunked["chunks_total"] > 0
    assert snap["tokens"]["spec_rejected"] >= 0


def test_cache_stats_count_fetched_tokens_by_the_kernels_block():
    """A decode step attends a slot's depth and fetches it in whole
    blocks of the kernel's own size: the fill share /debug/engine shows."""
    from incubator_mxnet_tpu.ops.pallas_kernels import paged_block_tokens

    cfg = _small_cfg(max_len=192)
    eng = ServingEngine(tfm.init_params(cfg, seed=3), cfg, slots=2,
                        page_size=8)
    block = paged_block_tokens(eng.page_size)
    assert block == 128
    asked = [(120, 12), (5, 6)]  # the first crosses into a second block
    rng = np.random.RandomState(4)
    for n, new in asked:
        eng.submit(rng.randint(1, cfg.vocab, n).astype(np.int32), new)
    eng.run()
    depths = [d for n, new in asked for d in range(n + 1, n + new)]
    stats = eng.cache_stats()
    assert stats["attended_tokens"] == {
        "paged_kv": cfg.n_layers * sum(depths)}
    assert stats["fetched_tokens"] == {
        "paged_kv": cfg.n_layers * sum(-(-d // block) * block
                                       for d in depths)}
    assert max(depths) > block
    assert eng.debug_snapshot()["cache"]["fetched_tokens"] == (
        stats["fetched_tokens"])


# -- cancel/eviction race hardening ------------------------------------------

def test_cancel_after_finish_is_noop_and_waste_counted_once():
    """The cancel/EOS race: a cancel() landing in the same step the
    request finished must not double-free its pages (the PageSanitizer
    MXS010 regression) and eviction waste is counted exactly once."""
    from incubator_mxnet_tpu.analysis import sanitizers

    sanitizers.reset()
    cfg = _small_cfg()
    params = tfm.init_params(cfg, seed=3)
    rng = np.random.RandomState(9)
    eng = ServingEngine(params, cfg, slots=2, page_size=8, num_pages=16)
    san = sanitizers.attach_page_sanitizer(eng.allocator, force=True)
    try:
        # leg 1: cancel mid-stream is an eviction, waste counted once
        p = rng.randint(1, 64, 6).astype(np.int32)
        rid = eng.submit(p, 10)
        eng.step()
        eng.step()
        out_now = len(eng.live_tokens()[rid])
        assert 0 < out_now < 10
        base = eng._wasted_evicted
        assert eng.cancel(rid)
        assert eng.results()[rid].finish_reason == "evicted"
        assert eng._wasted_evicted == base + p.size + out_now
        # the race: a second cancel of the finished id is a clean no-op
        assert not eng.cancel(rid)
        assert eng._wasted_evicted == base + p.size + out_now

        # leg 2: cancel racing a natural EOS-in-the-same-step finish
        rid2 = eng.submit(rng.randint(1, 64, 5).astype(np.int32), 3)
        eng.run()
        assert not eng.cancel(rid2)

        # leg 3: the internal raced path — _finish() twice on one slot
        rid3 = eng.submit(rng.randint(1, 64, 5).astype(np.int32), 8)
        eng.step()
        (slot,) = [s for s, r in enumerate(eng._slot_req)
                   if r is not None and r.request_id == rid3]
        out3 = len(eng._slot_out[slot])
        base = eng._wasted_evicted
        eng._finish(slot, reason="evicted")
        eng._finish(slot, reason="evicted")  # idempotence guard
        assert eng._wasted_evicted == base + 5 + out3

        # nothing above double-freed a page or leaked a reference
        eng.run()
        san.check()
        assert not sanitizers.findings("MXS010")
        assert not sanitizers.report()
    finally:
        sanitizers.reset()
