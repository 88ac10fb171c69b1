"""Serving-tier tests: the paged decode kernel and the transformer's
programs over the paged pool and the dense cache (CPU, Pallas interpret
mode). The allocator and the engine are tests/test_serving_engine.py's: two
files, so that neither is tier 1's wall on one xdist worker (ROADMAP.md D13)."""
import numpy as np

import jax
import jax.numpy as jnp
import pytest

from incubator_mxnet_tpu.models import transformer as tfm
from incubator_mxnet_tpu.ops.pallas_kernels import (
    DECODE_BLOCK, dense_decode_attention, flash_decode,
    paged_decode_attention, paged_decode_attention_wide)
from incubator_mxnet_tpu.serving import PageAllocator


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


def test_paged_diff_takes_a_pool_past_the_vmem_limit():
    """The same for paged_diff_attention, which mapped a pair of heads'
    whole pool into VMEM until PR 31 and refused more than 7680 pages."""
    from incubator_mxnet_tpu.ops.pallas_kernels import paged_diff_attention

    H, D, ps = 2, 64, 16
    pool = jax.ShapeDtypeStruct((1, H, 8 * 7680, ps, 2 * D), jnp.bfloat16)
    q = jax.ShapeDtypeStruct((1, H // 2, 4, D), jnp.float32)
    pt = jax.ShapeDtypeStruct((1, 4), jnp.int32)
    nv = jax.ShapeDtypeStruct((1,), jnp.int32)
    out = jax.eval_shape(paged_diff_attention, q, pool, pt, nv)
    assert out.shape == (1, H // 2, 4, 2 * D)


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


def _program_calls(cfg):
    """The five programs of models.transformer over one token batch, each
    as a no-argument call."""
    params = tfm.init_params(cfg, seed=6)
    toks = jnp.asarray(np.random.RandomState(2).randint(
        1, cfg.vocab, (2, 8)).astype(np.int32))
    lens = jnp.asarray([8, 8], jnp.int32)
    table = jnp.asarray(_tables(2, live=(0, 1)))
    cache = tfm.init_kv_cache(cfg, 2, cfg.max_len)
    return {
        "apply": lambda: tfm.apply(params, toks, cfg),
        "decode_step": lambda: tfm.decode_step(params, cache, toks[:, 0],
                                               cfg),
        "prefill": lambda: tfm.prefill(params, cache, toks, cfg),
        "prefill_paged": lambda: tfm.prefill_paged(
            params, _noise_pool(cfg, 2), toks, lens, table, cfg),
        "decode_step_paged_wide": lambda: tfm.decode_step_paged_wide(
            params, _noise_pool(cfg, 2), toks[:, :3], lens,
            jnp.full((2,), 3, jnp.int32), table, cfg),
    }


@pytest.mark.parametrize("n_experts", [0, 2], ids=["dense", "experts"])
@pytest.mark.parametrize("program", ["apply", "decode_step", "prefill",
                                     "prefill_paged",
                                     "decode_step_paged_wide"])
def test_every_program_runs_the_one_block(program, n_experts):
    """The layer and the head are written once: every program's trace
    goes through _block (once: its layer loop is a scan) and _logits."""
    from unittest import mock

    call = _program_calls(_deep_cfg(n_experts=n_experts))[program]
    with mock.patch.object(tfm, "_block", wraps=tfm._block) as block, \
            mock.patch.object(tfm, "_logits", wraps=tfm._logits) as logits:
        jax.make_jaxpr(call)()
    assert (block.call_count, logits.call_count) == (1, 1)


# apply(...)[1] of _program_calls(_deep_cfg(n_experts=2)) on the tree of
# commit 4d3881e, this installation, CPU
AUX_AT_PARENT = 1.0220247507095337


def test_apply_aux_loss_is_the_parents():
    """apply moved onto the paged programs' block (PR 30: the barrier, the
    expert branch written once): the router's auxiliary loss of a fixed
    seed is what models.transformer._layer gave at commit 4d3881e, to the
    bit."""
    _, aux = _program_calls(_deep_cfg(n_experts=2))["apply"]()
    assert np.float32(aux) == np.float32(AUX_AT_PARENT), float(aux)


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
