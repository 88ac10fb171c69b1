"""Serving-tier tests: the page allocator, the prefix cache and the
continuous-batching engine with its levers (CPU, Pallas interpret mode).
The kernels and the model's programs are tests/test_serving.py's."""
import numpy as np

import jax.numpy as jnp
import pytest

from incubator_mxnet_tpu.models import transformer as tfm
from incubator_mxnet_tpu.serving import PageAllocator, ServingEngine
from test_serving import _small_cfg


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


# -- one decode step ahead ------------------------------------------------------

LEVERS_OFF = {"prefix_cache": 0, "prefill_chunk": 0, "spec_ngram": 0}
# seven requests through four slots: runs that start, end with a request
# and start again behind an admission
ASKED = ((5, 9), (16, 30), (14, 12), (9, 40), (3, 17), (21, 6), (8, 25))


@pytest.fixture(scope="module", params=["transformer", "falcon_h1"])
def ahead(request):
    """A model whose programs say `decode_ahead`, behind four slots: its
    programs' class, a maker of engines, and what each of four prompts
    gets when it is served alone."""
    import types

    if request.param == "transformer":
        programs, cfg, page = tfm.TransformerPrograms, _small_cfg(), 8
        params = tfm.init_params(cfg, seed=3)
    else:
        from incubator_mxnet_tpu.models import falcon_h1
        from test_falcon_h1 import CFG as cfg, PAGE as page
        programs = falcon_h1.FalconH1Programs
        params = falcon_h1.init_params(cfg, 3)

    def engine(**kw):
        return ServingEngine(params, cfg, slots=4, page_size=page,
                             max_len=64, **{**LEVERS_OFF, **kw})

    rng = np.random.default_rng(19)
    prompts = [rng.integers(1, cfg.vocab, size=n).astype(np.int32)
               for n in (6, 11, 4, 9)]
    alone, want = engine(), []
    for p in prompts:
        rid = alone.submit(p, 20)
        want.append(alone.run()[rid].tokens)
    return types.SimpleNamespace(programs=programs, engine=engine, cfg=cfg,
                                 prompts=prompts, want=want)


def _step_through(eng, arrivals=()):
    """Steps `eng` until it drains, submitting `arrivals` (after how many
    step() calls, prompt, new tokens) on the way. Returns, per step(),
    whether a decode step was left in flight and how many tokens the
    callers could read, and per request after how many dispatched decode
    steps its first token could be read."""
    arrivals = list(arrivals)
    flights, readable, admitted = [], [], {}
    while arrivals or eng.queue_depth or eng.slots_in_use:
        while arrivals and arrivals[0][0] <= len(flights):
            eng.submit(*arrivals.pop(0)[1:])
        eng.step()
        flights.append(eng.decode_in_flight)
        live, done = eng.live_tokens(), eng.results()
        for rid in (*live, *done):
            admitted.setdefault(rid, eng.cache_stats()["decode_steps"])
        readable.append(sum(map(len, live.values()))
                        + sum(len(r.tokens) for r in done.values()))
    return flights, readable, admitted


def _went_ahead(flights):
    """The steps that dispatched ahead: they began with a step in flight
    and left one."""
    return sum(a and b for a, b in zip(flights, flights[1:]))


def _books(eng):
    """The engine's host counters but the one that says how a step went
    out."""
    stats = eng.cache_stats()
    return {k: v for k, v in stats.items() if k != "decode_steps_ahead"
            }, eng.goodput()


def test_decode_runs_one_step_ahead_while_every_slot_decodes(ahead,
                                                             monkeypatch):
    """With all four slots decoding and nobody about to end, step() returns
    with the next decode step dispatched and unread; a step that ends a
    request is never run ahead of; the run's first step delivers no decode
    token. Tokens, finish reasons, admissions and the cache's books are
    those of the loop that reads every step before it dispatches the
    next."""
    rng = np.random.default_rng(17)
    asked = [(rng.integers(1, ahead.cfg.vocab, size=n).astype(np.int32), new)
             for n, new in ASKED]

    def serve():
        eng = ahead.engine()
        rids = [eng.submit(p, new) for p, new in asked]
        flights, readable, admitted = _step_through(eng)
        return (eng, [eng.results()[r] for r in rids], flights, readable,
                [admitted[r] for r in rids])

    eng, got, flights, readable, admitted = serve()
    monkeypatch.setattr(ahead.programs, "decode_ahead", False)
    eng_sync, sync, flights_sync, _, admitted_sync = serve()
    assert not any(flights_sync) and any(flights)
    assert [(r.tokens, r.finish_reason) for r in got] == [
        (r.tokens, r.finish_reason) for r in sync]
    assert admitted == admitted_sync
    assert _books(eng) == _books(eng_sync)
    # a run starts with a step that dispatches and reads nothing: one more
    # step() per run and no other, so no token is more than one step late
    starts = sum(b and not a for a, b in zip([False] + flights, flights))
    assert len(flights) == len(flights_sync) + starts and starts >= 2
    assert readable[-1] == sum(new for _, new in ASKED)
    # the step that ended the last request left nothing in flight
    assert not flights[-1]
    # the counter says what the flights show, here and at /debug/engine
    assert eng.cache_stats()["decode_steps_ahead"] == _went_ahead(flights) > 0
    assert eng_sync.cache_stats()["decode_steps_ahead"] == 0
    snap = eng.debug_snapshot()
    assert snap["decode_in_flight"] is False
    assert snap["cache"]["decode_steps"] == eng.cache_stats()["decode_steps"]
    assert snap["cache"]["decode_steps_ahead"] == _went_ahead(flights)


def test_decode_ahead_keeps_to_the_loop_with_a_free_slot(ahead):
    """A request could be admitted into the free slot between two steps, so
    nothing runs ahead."""
    eng = ahead.engine()
    rids = [eng.submit(p, 20) for p in ahead.prompts[:3]]
    flights, _, _ = _step_through(eng)
    assert not any(flights)
    assert eng.cache_stats()["decode_steps_ahead"] == 0
    assert [eng.results()[r].tokens for r in rids] == ahead.want[:3]


def test_decode_ahead_keeps_to_the_loop_for_an_eos_id(ahead):
    """An eos_id can end a request on any token: while one is live every
    step is read before the next goes out."""
    want = ahead.want
    eng = ahead.engine()
    rids = [eng.submit(p, 20, eos_id=want[i][7] if i == 2 else None)
            for i, p in enumerate(ahead.prompts)]
    flights, _, _ = _step_through(eng)
    assert not any(flights[:8])
    got = [eng.results()[r] for r in rids]
    assert got[2].finish_reason == "eos"
    assert got[2].tokens == want[2][: want[2].index(want[2][7]) + 1]
    assert [g.tokens for i, g in enumerate(got) if i != 2] == [
        w for i, w in enumerate(want) if i != 2]


def test_a_cancel_reads_the_step_in_flight_first(ahead):
    """A cancel with a step in flight: that step's tokens are read first,
    the neighbours go on as if nothing had happened."""
    want = ahead.want
    eng = ahead.engine()
    rids = [eng.submit(p, 20) for p in ahead.prompts]
    for _ in range(6):
        eng.step()
    assert eng.decode_in_flight and eng.debug_snapshot()["decode_in_flight"]
    assert eng.cancel(rids[1]) and not eng.decode_in_flight
    eng.run()
    got = [eng.results()[r] for r in rids]
    assert got[1].finish_reason == "evicted"
    assert got[1].tokens == want[1][: len(got[1].tokens)]
    assert 5 <= len(got[1].tokens) < 20
    assert [g.tokens for i, g in enumerate(got) if i != 1] == [
        w for i, w in enumerate(want) if i != 1]


def _transformer_engine(slots=4, **kw):
    cfg = _small_cfg()
    return cfg, ServingEngine(tfm.init_params(cfg, seed=3), cfg, slots=slots,
                              page_size=8, max_len=64,
                              **{**LEVERS_OFF, **kw})


def _staggered(cfg, seed, n):
    rng = np.random.default_rng(seed)
    return [(rng.integers(1, cfg.vocab, size=int(rng.integers(2, 24))
                          ).astype(np.int32), int(rng.integers(2, 30)))
            for _ in range(n)]


def test_a_cancel_of_a_request_whose_last_step_is_in_flight():
    """The step in flight makes the request's last token: read first, it
    ends the request as the loop would have a step() earlier, and the
    cancel finds nothing left to cancel."""
    cfg, eng = _transformer_engine(slots=2)
    rng = np.random.default_rng(5)
    short, long = (eng.submit(rng.integers(1, cfg.vocab, size=5), new)
                   for new in (6, 15))
    while len(eng.live_tokens().get(short, ())) < 5:
        eng.step()
    assert eng.decode_in_flight and len(eng.live_tokens()[short]) == 5
    assert not eng.cancel(short) and not eng.decode_in_flight
    done = eng.results()[short]
    assert done.finish_reason == "length" and len(done.tokens) == 6
    assert len(eng.live_tokens()[long]) == 6
    assert eng.goodput()["wasted_evicted"] == 0
    assert len(eng.run()[long].tokens) == 15


def test_staggered_requests_are_admitted_at_the_synchronous_loops_steps(
        monkeypatch):
    """Sixteen requests of staggered lengths through four slots, half of
    them arriving on the way: they end inside runs with a queue behind
    them, and each is admitted after as many decode steps, gets the tokens
    and ends for the reason that the synchronous loop gives it."""
    def serve():
        cfg, eng = _transformer_engine()
        asked = _staggered(cfg, 23, 16)
        rids = [eng.submit(*a) for a in asked[:8]]
        flights, _, admitted = _step_through(
            eng, [(3 * i, *a) for i, a in enumerate(asked[8:], start=1)])
        done = eng.results()
        assert sorted(done) == sorted(rids) + list(range(8, 16))
        return (eng, flights, admitted,
                {r: (done[r].tokens, done[r].finish_reason) for r in done})

    eng, flights, admitted, got = serve()
    monkeypatch.setattr(tfm.TransformerPrograms, "decode_ahead", False)
    eng_sync, flights_sync, admitted_sync, want = serve()
    assert got == want
    # arrivals are keyed on step() calls, which a run's first step adds to:
    # the ones that came on the way found the engine at most that far on
    assert {r: admitted[r] for r in range(8)} == {
        r: admitted_sync[r] for r in range(8)}
    assert _went_ahead(flights) >= 8 and not any(flights_sync)
    stats, stats_sync = eng.cache_stats(), eng_sync.cache_stats()
    assert stats["decode_steps_ahead"] == _went_ahead(flights)
    assert stats["decode_steps"] == stats_sync["decode_steps"]
    assert eng.allocator.num_in_use == 0


@pytest.fixture(scope="module")
def plain_run():
    """Nine staggered requests through four slots with every lever off:
    what was asked, and the tokens each got a step ahead."""
    cfg, plain = _transformer_engine()
    asked = _staggered(cfg, 29, 9)
    rids = [plain.submit(*a) for a in asked]
    flights, _, _ = _step_through(plain)
    assert _went_ahead(flights) > 0
    return asked, [plain.results()[r].tokens for r in rids]


@pytest.mark.parametrize("lever", [
    {"prefix_cache": 1}, {"prefill_chunk": 4},
    {"spec_ngram": 2, "spec_lookahead": 3}], ids=lambda kw: next(iter(kw)))
def test_a_lever_keeps_the_synchronous_loop(lever, plain_run):
    """With every slot decoding and a lever on, every step is read before
    the next goes out, through that lever's programs."""
    asked, want = plain_run
    _, eng = _transformer_engine(**lever)
    rids = [eng.submit(*a) for a in asked]
    flights, _, _ = _step_through(eng)
    assert not any(flights)
    assert eng.cache_stats()["decode_steps_ahead"] == 0
    assert [eng.results()[r].tokens for r in rids] == want


@pytest.mark.parametrize("weights", ["uncommitted", "committed",
                                     "compile_cache"])
def test_the_decode_program_is_one_executable_over_both_dispatches(
        weights, tmp_path, monkeypatch):
    """Tokens uploaded from the host's books and tokens handed on from the
    step in flight reach the decode program under ONE signature: nothing
    is traced, lowered or compiled at the first step that goes ahead,
    wherever the caller put the weights."""
    import jax

    if weights == "compile_cache":
        monkeypatch.setenv("MXTPU_COMPILE_CACHE_DIR", str(tmp_path / "cc"))
    cfg = _small_cfg()
    params = tfm.init_params(cfg, seed=3)
    if weights == "committed":
        params = jax.device_put(params, jax.devices()[0])
        assert all(leaf.committed for leaf in jax.tree_util.tree_leaves(
            params))
    eng = ServingEngine(params, cfg, slots=2, page_size=8, max_len=64,
                        **LEVERS_OFF)
    rng = np.random.default_rng(31)
    for new in (9, 14, 6):
        eng.submit(rng.integers(1, cfg.vocab, size=7), new)
    flights, _, _ = _step_through(eng)
    stats = eng.cache_stats()
    assert 0 < stats["decode_steps_ahead"] < stats["decode_steps"]
    assert stats["decode_steps_ahead"] == _went_ahead(flights)
    if weights == "compile_cache":
        assert eng._decode.is_cached and len(eng._decode._compiled) == 1
    else:
        assert eng._decode._cache_size() == 1
    # the pool a committed program leaves is committed, and so then are
    # the tokens the host uploads
    pool = jax.tree_util.tree_leaves(eng.paged)[0]
    assert pool.committed == (weights == "committed")
    assert eng._as_a_step_leaves(eng._next_tok).committed == pool.committed


def test_decode_steps_reach_telemetry_by_how_they_went_out(monkeypatch):
    from incubator_mxnet_tpu import telemetry
    from incubator_mxnet_tpu.serving import engine as engine_mod

    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    telemetry.refresh_from_env()
    try:
        telemetry.REGISTRY.reset()
        cfg, eng = _transformer_engine(slots=2)
        for prompt, new in _staggered(cfg, 37, 5):
            eng.submit(prompt, new)
        flights, _, _ = _step_through(eng)
        stats = eng.cache_stats()
        by = {dict(labels)["dispatch"]: ch.value for labels, ch in
              telemetry.REGISTRY.get(engine_mod.DECODE_STEPS).series()}
        assert by["ahead"] == stats["decode_steps_ahead"] == _went_ahead(
            flights) > 0
        assert by["ahead"] + by["sync"] == stats["decode_steps"]
    finally:
        monkeypatch.delenv("MXNET_TELEMETRY", raising=False)
        telemetry.refresh_from_env()
        telemetry.REGISTRY.reset()
