"""models.sambay through its programs and through ServingEngine, against the
plain reference (benchmark/references/phi4_mini_flash.py), logits and not
tokens, at a small size: 8 layers = one self-decoder of 4 (Mamba, window,
Mamba, window), the memory layer, the full layer, one GMU, one cross layer;
d 64, 8 query / 4 K/V heads, window 8, vocab 128. And the grouped
differential kernel against dense attention, feature by feature."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from benchmark.harness import loader
from incubator_mxnet_tpu import config as knobs
from incubator_mxnet_tpu.models import sambay, transformer
from incubator_mxnet_tpu.ops import pallas_kernels as pk
from incubator_mxnet_tpu.serving import ServingEngine

REFERENCE = loader.load_callable("references", "phi4_mini_flash.py:logits")
CFG = sambay.SambaYConfig(vocab=128, d_model=64, n_layers=8, n_heads=8,
                          n_kv_heads=4, d_ff=128, window=8, dt_rank=4,
                          prefill_block=8)
CONFIG = {"num_hidden_layers": 8, "num_attention_heads": 8,
          "sliding_window": 8, "layer_norm_eps": 1e-5,
          "mamba": {"d_state": 16, "d_conv": 4, "dt_rank": 4}}
PAGE, WIDTH = 4, 16   # a ring of 3 pages of 4: 64 tokens wrap it 5 times
TOL = 2e-5


@pytest.fixture(scope="module")
def params():
    return sambay.init_params(CFG, 3)


@pytest.fixture(scope="module")
def programs():
    prog = CFG.paged_programs()
    return jax.jit(prog.prefill), jax.jit(prog.decode)


def _reference_rows(params, tokens, first, count):
    toks = np.zeros((64,), np.int32)  # one length: one compile
    toks[: len(tokens)] = tokens
    return np.asarray(REFERENCE(params, jnp.asarray(toks), CONFIG)
                      )[first: first + count]


def _serve(params, programs, prompts, buckets, steps, slots=None, cache=None):
    """Prefills each prompt into its slot, then `steps` greedy decode
    steps for all of them in one batch. Returns per prompt (tokens,
    logits of the last prompt row and of every decoded row) and the
    cache."""
    prefill, decode = programs
    S = 3
    slots = list(range(len(prompts))) if slots is None else slots
    if cache is None:
        cache = sambay.init_cache(CFG, S, S * WIDTH + 1, PAGE)
    tables = np.zeros((S, WIDTH), np.int32)
    positions = np.zeros((S,), np.int32)
    nxt = np.zeros((S,), np.int32)
    seqs, rows = {}, {}
    for s, prompt, bucket in zip(slots, prompts, buckets):
        padded = np.zeros((1, bucket), np.int32)
        padded[0, : prompt.size] = prompt
        row = 1 + s * WIDTH + np.arange(WIDTH, dtype=np.int32)
        cache, lg = prefill(params, cache, jnp.asarray(padded),
                            jnp.asarray([prompt.size], jnp.int32),
                            jnp.asarray(row[None]),
                            jnp.asarray([s], jnp.int32))
        tables[s], positions[s] = row, prompt.size
        seqs[s], rows[s] = list(prompt), [np.asarray(lg[0])]
        nxt[s] = int(np.argmax(lg[0]))
    for _ in range(steps):
        lg, cache = decode(params, cache, jnp.asarray(nxt),
                           jnp.asarray(positions), jnp.asarray(tables))
        # fetched before the host arrays change under the running call
        lg = np.asarray(lg)
        for s in slots:
            seqs[s].append(int(nxt[s]))
            positions[s] += 1
            rows[s].append(lg[s])
            nxt[s] = int(np.argmax(lg[s]))
    return [(np.asarray(seqs[s], np.int32), np.stack(rows[s]))
            for s in slots], cache


def _prompt(rng, n):
    return rng.integers(1, CFG.vocab, size=n).astype(np.int32)


@pytest.mark.parametrize("lengths,buckets,steps", [
    # shorter than, equal to and longer than the window, each under its
    # bucket's length: the scan state is the one after the last real row
    ((5, 8, 13), (8, 16, 16), 6),
    # true length == bucket; three depths in one decode batch; 40 steps
    # wrap the 12-token ring several times
    ((8, 16, 3), (8, 16, 8), 40),
], ids=["under_bucket", "ring_wraps"])
def test_programs_match_the_reference(params, programs, lengths, buckets,
                                      steps):
    rng = np.random.default_rng(sum(lengths))
    prompts = [_prompt(rng, n) for n in lengths]
    served, _ = _serve(params, programs, prompts, buckets, steps)
    for prompt, (tokens, got) in zip(prompts, served):
        want = _reference_rows(params, tokens, prompt.size - 1, steps + 1)
        assert np.abs(got - want).max() < TOL


def test_a_reused_slot_starts_from_an_empty_state(params, programs):
    rng = np.random.default_rng(7)
    first = _prompt(rng, 15)
    _, cache = _serve(params, programs, [first], [16], 30, slots=[1])
    again = _prompt(rng, 6)
    (tokens, got), = _serve(params, programs, [again], [8], 12, slots=[1],
                            cache=cache)[0]
    want = _reference_rows(params, tokens, again.size - 1, 13)
    assert np.abs(got - want).max() < TOL


# -- through the engine ---------------------------------------------------------


def _engine(params, **kw):
    return ServingEngine(params, CFG, slots=3, page_size=PAGE, max_len=64,
                         **{"prefix_cache": 0, "prefill_chunk": 0,
                            "spec_ngram": 0, **kw})


def test_engine_serves_it_by_the_same_entry_points(params):
    """Five requests through three slots: admission, buckets, decode
    batches of mixed depth, slots reused after a request ended. Every
    token the engine gave is the reference's own choice for its row."""
    rng = np.random.default_rng(11)
    eng = _engine(params)
    asked = [(_prompt(rng, n), new) for n, new in
             ((5, 9), (16, 30), (14, 12), (9, 40), (3, 17))]
    rids = [eng.submit(p, new) for p, new in asked]
    results = eng.run()
    for rid, (prompt, new) in zip(rids, asked):
        out = np.asarray(results[rid].tokens, np.int32)
        assert out.size == new and results[rid].finish_reason == "length"
        rows = _reference_rows(params, np.concatenate([prompt, out[:-1]]),
                               prompt.size - 1, new)
        margin = rows.max(-1) - rows[np.arange(new), out]
        assert margin.max() < TOL
    stats = eng.cache_stats()
    assert stats["pool"]["pages_reserved"] == 0
    assert stats["kinds"]["window_kv"]["ring_pages_per_slot"] == 3
    assert stats["kinds"]["recurrent"]["state_bytes_per_slot"] == (
        4 * 3 * (16 + 3) * 128)
    decoded = eng.goodput()["decode"]
    assert decoded == sum(new - 1 for _, new in asked)
    # every decode step read the shared cache twice (full + one cross
    # layer) and two window layers no deeper than the window
    shared, window = (stats["attended_tokens"][k]
                      for k in ("shared_kv", "window_kv"))
    assert shared == 2 * sum(sum(range(p.size + 1, p.size + new))
                             for p, new in asked)
    assert window == 2 * sum(sum(min(8, n) for n in
                                 range(p.size + 1, p.size + new))
                             for p, new in asked)
    assert eng.debug_snapshot()["cache"]["attended_tokens"] == (
        stats["attended_tokens"])
    # and fetched them in whole blocks (32 pages of 4 here: no slot gets
    # past its first), so the fill share is depth / 128 and window / 128
    steps = 2 * sum(new - 1 for _, new in asked)
    assert stats["fetched_tokens"] == {"shared_kv": 128 * steps,
                                       "window_kv": 128 * steps}
    assert eng.debug_snapshot()["cache"]["fetched_tokens"] == (
        stats["fetched_tokens"])


def test_fetched_tokens_are_the_walks_whole_blocks():
    """What cache_stats() adds per decode step, against hand-made depths
    at the benchmark's geometry (pages of 16, a block of 8, window 512,
    eight layers reading each kind): the shared cache from page 0, a ring
    from the first page its window reaches."""
    cfg = sambay.SambaYConfig(window=512, n_layers=32)
    prog = cfg.paged_programs()
    assert (1 + cfg.n_cross_pairs, cfg.n_self_pairs) == (8, 8)
    for depth, shared, ring in (
            (1, 1, 1), (128, 1, 1), (129, 2, 2), (512, 4, 4),
            (513, 5, 5),      # tokens 1..512: pages 0..32, 33 pages
            (528, 5, 4),      # tokens 16..527: pages 1..32, 32 pages
            (3500, 28, 5), (7168, 56, 4)):
        assert prog.fetched([depth], 16) == {
            "shared_kv": 8 * 128 * shared, "window_kv": 8 * 128 * ring}
    assert prog.fetched([1, 513, 3500], 16) == {
        "shared_kv": 8 * 128 * (1 + 5 + 28), "window_kv": 8 * 128 * 11}
    assert prog.fetched((), 16) == {"shared_kv": 0, "window_kv": 0}
    # a page as long as a block is a block by itself
    assert prog.fetched([300], 256)["shared_kv"] == 8 * 256 * 2


def test_engine_programs_leave_each_tokens_row_on_the_device(params):
    """The benchmark's check reads logits, not tokens: the engine's own
    compiled prefill and decode programs leave the row each token was
    chosen from in the cache, and `served_rows` collects them with every
    slot live, a ring that wraps while decoding, and more requests than
    slots (a row read after its slot was handed on would be another's)."""
    served_rows = loader.load_callable("jobs", "serve_hybrid.py:served_rows")
    rng = np.random.default_rng(13)
    prompts = [_prompt(rng, n) for n in (15, 9, 12, 5)]
    new = 14
    eng = _engine(params)
    served = served_rows(eng, prompts, new, set(range(new)))
    assert len(served) == 4
    for prompt, (out, rows) in zip(prompts, served.values()):
        assert out.size == new and sorted(rows) == list(range(new))
        got = np.stack([np.asarray(rows[i]) for i in range(new)])
        assert (got.argmax(-1) == out).all()
        tokens = np.concatenate([prompt, out[:-1]])
        want = _reference_rows(params, tokens, prompt.size - 1, new)
        assert np.abs(got - want).max() < TOL


def test_an_eos_and_a_cancel_leave_the_neighbours_tokens(params):
    """A request that ends early (EOS) and one cancelled between two
    steps free their slot, ring and state rows; the neighbours decoding
    beside them, and the request that takes the slot next, read none of
    what they left."""
    rng = np.random.default_rng(5)
    a, b = _prompt(rng, 11), _prompt(rng, 6)
    eng = _engine(params)
    rids = [eng.submit(a, 20), eng.submit(b, 25)]
    alone = eng.run()
    want_a, want_b = (alone[r].tokens for r in rids)
    assert eng.goodput()["decode"] == 19 + 24  # no row booked twice

    stop = want_a[7]
    first = want_a.index(stop)
    ra = eng.submit(a, 20, eos_id=stop)
    rb = eng.submit(b, 25)
    eng.step()
    # a token is readable when the step() that made it returns
    assert [len(t) for t in eng.live_tokens().values()] == [2, 2]
    rc = eng.submit(a, 20)
    for _ in range(4):
        eng.step()
    assert eng.cancel(rc)
    out = eng.run()
    assert out[ra].tokens == want_a[: first + 1]
    assert out[ra].finish_reason == "eos"
    assert out[rb].tokens == want_b and out[rb].finish_reason == "length"
    assert out[rc].finish_reason == "evicted"
    assert out[rc].tokens == want_a[: len(out[rc].tokens)]
    assert eng.cache_stats()["pool"]["pages_reserved"] == 0


@pytest.mark.parametrize("lever", ["prefix_cache", "prefill_chunk",
                                   "spec_ngram"])
def test_engine_refuses_a_lever_without_state_snapshots(params, lever):
    with pytest.raises(ValueError, match="state snapshots") as e:
        _engine(params, **{lever: 4})
    assert lever in str(e.value)


def test_the_transformer_keeps_its_levers():
    cfg = transformer.TransformerConfig(vocab=64, d_model=32, n_heads=2,
                                        n_layers=2, d_ff=64, max_len=64)
    eng = ServingEngine(transformer.init_params(cfg, 0), cfg, slots=2,
                        page_size=4, prefix_cache=1, prefill_chunk=8,
                        spec_ngram=2)
    rid = eng.submit(np.arange(1, 20, dtype=np.int32), 5)
    assert len(eng.run()[rid].tokens) == 5
    assert eng.cache_stats()["attended_tokens"].keys() == {"paged_kv"}


def test_no_new_knob():
    assert len(knobs.KNOBS) == 93


# -- the kernel, feature by feature --------------------------------------------


def _dense(q, pool, table, n_valid, layer, window, ring):
    B, G, _, D = q.shape
    page = pool.shape[3]
    width = table.shape[1]
    out = np.zeros((B, G, 4, 2 * D), np.float32)
    for b in range(B):
        n = int(n_valid[b])
        pos = np.arange(max(n - window, 0) if window else 0, n)
        cols = (pos // page) % width if ring else pos // page
        for g in range(G):
            rows = [pool[layer, 2 * g + e, table[b, cols], pos % page]
                    for e in (0, 1)]
            value = np.concatenate([rows[0][:, D:], rows[1][:, D:]], -1)
            for r in range(4):
                s = rows[r // 2][:, :D] @ q[b, g, r] / np.sqrt(D)
                p = np.exp(s - s.max())
                out[b, g, r] = (p / p.sum()) @ value
    return out


# at page 4 a block is 32 pages, 128 tokens: the wide tables span three
# blocks and more
@pytest.mark.parametrize("groups,window,ring,width,depths,dtype", [
    (1, 0, False, 6, (1, 7, 23), "float32"),   # the pair: two keys, one value
    (3, 0, False, 6, (4, 5, 24), "float32"),   # groups of query heads per pair
    (2, 5, False, 6, (3, 5, 22), "float32"),   # a window: the first page
    (2, 8, True, 3, (9, 13, 100), "float32"),  # the ring: pages modulo width
    (2, 6, True, 3, (1, 12, 57), "float32"),   # the ring from its first token
    (2, 0, False, 100, (128, 256, 384), "float32"),  # whole blocks exactly
    (2, 0, False, 100, (129, 300, 0, 399), "float32"),  # a dead slot beside
    # a window whose first token lies mid-block and mid-page
    (2, 150, False, 100, (151, 215, 398), "float32"),
    # a ring of 40 pages under a window of 150: the walk wraps inside a
    # block (pages 38, 39, 0, 1, ...), and far past the first wrap
    (2, 150, True, 40, (158, 407, 0, 1201), "float32"),
    # as the engine sends them: a bfloat16 pool, float32 queries
    (2, 0, False, 100, (130, 257, 399), "bfloat16"),
    (2, 150, True, 40, (158, 407, 1201), "bfloat16"),
], ids=["pair", "group", "window", "ring", "ring_short", "block_multiple",
        "dead_slot", "window_mid_block", "ring_wraps_in_block", "bf16_pool",
        "bf16_ring"])
def test_paged_diff_attention_matches_dense(groups, window, ring, width,
                                            depths, dtype):
    rng = np.random.default_rng(groups + window)
    B, D, page = len(depths), 8, 4
    pool = jnp.asarray(
        rng.normal(size=(2, 2 * groups, 1 + B * width, page, 2 * D)
                   ).astype(np.float32), dtype)
    table = 1 + np.arange(B * width, dtype=np.int32).reshape(B, width)
    q = rng.normal(size=(B, groups, 4, D)).astype(np.float32)
    n_valid = np.asarray(depths, np.int32)
    got = np.asarray(pk.paged_diff_attention(
        jnp.asarray(q), pool, table, n_valid, 1, window=window, ring=ring))
    # the oracle reads what the kernel reads: the pool's rounding of the
    # rows and of the queries
    cast = lambda x: np.asarray(jnp.asarray(x, dtype).astype(jnp.float32))
    live = n_valid > 0
    want = _dense(cast(q)[live], cast(pool), table[live], n_valid[live], 1,
                  window, ring)
    # bfloat16 probabilities in the second product, as the kernel casts them
    tol = 1e-5 if dtype == "float32" else 2e-2
    assert np.abs(got[live] - want).max() < tol
    # a dead slot costs no copy and no loop step, and reads as zeros
    assert np.all(got[~live] == 0)


@pytest.mark.parametrize("start,n_write,n_rows", [
    (0, 5, 8), (0, 31, 32), (17, 1, 1), (40, 0, 1)],
    ids=["short_prompt", "prompt_past_the_ring", "decode", "dead_slot"])
def test_ring_write_plan_keeps_the_latest_rows(start, n_write, n_rows):
    page, R = 4, 3
    ring = np.asarray([[5, 6, 7]], np.int32)
    pool = jnp.zeros((1, 2, 8, page, 4), jnp.float32)
    k = jnp.arange(1, n_rows + 1, dtype=jnp.float32
                   )[None, :, None, None] * jnp.ones((1, n_rows, 2, 2))
    plan = pk.paged_ring_write_plan(ring, np.asarray([start]),
                                    np.asarray([n_write]), n_rows, page)
    out = np.asarray(pk.paged_kv_write(pool, 0, k, k, plan))
    end = start + n_write
    kept = range(max(start, ((end - 1) // page - (R - 1)) * page), end)
    for pos in range(start, end):
        cell = out[0, 0, ring[0, (pos // page) % R], pos % page, 0]
        if pos in kept and pos // page > (end - 1) // page - min(
                R, (n_rows + 2 * page - 2) // page):
            assert cell == pos - start + 1, pos
    assert np.count_nonzero(out[0, 0, :5]) == 0  # nothing outside the ring
