"""Runtime-sanitizer tests: lockdep (order graph, blocking ops, hold
times), the KV-page shadow-state checker, engine-drain quiescence, and
the zero-cost-when-off contract. The MXL008-MXL010 lint rules have their
fixtures in test_mxlint.py; tools/sanitize.py injection plumbing is in
test_tools.py style CLI tests here."""
import threading
import time

import numpy as np
import pytest

from incubator_mxnet_tpu import telemetry
from incubator_mxnet_tpu.analysis import sanitizers
from incubator_mxnet_tpu.models import transformer as tfm
from incubator_mxnet_tpu.serving import PageAllocator, ServingEngine


@pytest.fixture(autouse=True)
def _clean_findings():
    """Findings are global and deduped by (code, detail); isolate tests."""
    sanitizers.reset()
    yield
    sanitizers.reset()


@pytest.fixture
def sanitized(monkeypatch):
    monkeypatch.setenv("MXTPU_SANITIZERS", "locks,pages")
    sanitizers.refresh_from_env()
    yield
    monkeypatch.delenv("MXTPU_SANITIZERS", raising=False)
    sanitizers.refresh_from_env()


def _codes():
    return sorted(d.code for d in sanitizers.report())


# -- knob resolution ----------------------------------------------------------

def test_disabled_mode_returns_plain_primitives(monkeypatch):
    monkeypatch.delenv("MXTPU_SANITIZERS", raising=False)
    sanitizers.refresh_from_env()
    assert sanitizers.enabled_set() == frozenset()
    assert type(sanitizers.san_lock("x")) is type(threading.Lock())
    assert type(sanitizers.san_rlock("x")) is type(threading.RLock())
    assert isinstance(sanitizers.san_condition("x"), threading.Condition)
    # no blocking-op patches installed: stdlib sleep is untouched
    assert sanitizers._real_sleep is None
    # and the page checker does not arm
    assert sanitizers.attach_page_sanitizer(PageAllocator(4, 4)) is None


def test_enabled_mode_returns_instrumented_primitives(sanitized):
    lk = sanitizers.san_lock("t.lock")
    assert type(lk).__name__ == "_SanLock"
    assert sanitizers.enabled("locks") and sanitizers.enabled("pages")
    assert sanitizers._real_sleep is not None  # patches active


def test_unknown_sanitizer_token_rejected(monkeypatch):
    monkeypatch.setenv("MXTPU_SANITIZERS", "locks,bogus")
    with pytest.raises(ValueError, match="bogus"):
        sanitizers.refresh_from_env()
    monkeypatch.delenv("MXTPU_SANITIZERS", raising=False)
    sanitizers.refresh_from_env()


# -- lockdep ------------------------------------------------------------------

def test_abba_inversion_across_two_threads(sanitized):
    a = sanitizers.san_lock("t.A")
    b = sanitizers.san_lock("t.B")

    def order_ab():
        with a:
            with b:
                pass

    def order_ba():
        with b:
            with a:
                pass

    t1 = threading.Thread(target=order_ab, daemon=True, name="t-ab")
    t1.start()
    t1.join()
    t2 = threading.Thread(target=order_ba, daemon=True, name="t-ba")
    t2.start()
    t2.join()

    # lockdep needs no actual collision: establishing both edges is
    # enough, and the report carries both acquisition stacks
    (f,) = sanitizers.findings("MXS001")
    assert "t.A" in f.detail and "t.B" in f.detail
    assert "this acquisition" in f.message
    assert "reverse edge" in f.message


def test_consistent_order_is_clean(sanitized):
    a = sanitizers.san_lock("t.A")
    b = sanitizers.san_lock("t.B")
    for _ in range(3):
        with a:
            with b:
                pass
    assert not sanitizers.findings("MXS001")


def test_rlock_reentry_is_not_an_edge(sanitized):
    r = sanitizers.san_rlock("t.R")
    with r:
        with r:  # re-entrant: same lock class, no self-edge, no cycle
            pass
    assert not sanitizers.findings("MXS001")


def test_blocking_op_under_lock(sanitized):
    lk = sanitizers.san_lock("t.holder")
    with lk:
        time.sleep(0.001)  # patched while the locks sanitizer is on
    (f,) = sanitizers.findings("MXS002")
    assert "t.holder" in f.message
    # the same site reports once, not once per iteration
    with lk:
        time.sleep(0.001)
    assert len(sanitizers.findings("MXS002")) == 1


def test_condition_wait_excludes_its_own_lock(sanitized):
    cv = sanitizers.san_condition("t.cv")
    with cv:
        cv.wait(timeout=0.005)  # waiting on ONLY yourself is fine
    assert not sanitizers.findings("MXS002")
    outer = sanitizers.san_lock("t.outer")
    with outer:
        with cv:
            cv.wait(timeout=0.005)  # holding another lock across a wait
    (f,) = sanitizers.findings("MXS002")
    assert "t.outer" in f.message


def test_long_hold_flags(sanitized, monkeypatch):
    monkeypatch.setattr(sanitizers, "_hold_ms", 5.0)
    lk = sanitizers.san_lock("t.slow")
    lk.acquire()
    sanitizers._real_sleep(0.02)  # un-patched sleep: no MXS002 noise
    lk.release()
    (f,) = sanitizers.findings("MXS003")
    assert "t.slow" in f.message
    assert not sanitizers.findings("MXS002")


# -- page shadow state --------------------------------------------------------

def _armed_allocator(num_pages=8, page_size=4):
    alloc = PageAllocator(num_pages, page_size)
    return alloc, sanitizers.attach_page_sanitizer(alloc, force=True)


def test_double_free_reports_mxs010():
    alloc, san = _armed_allocator()
    pages = alloc.alloc(1, owner=1)
    alloc.free(pages, owner=1)
    with pytest.raises(ValueError):
        alloc.free(pages, owner=1)
    assert _codes() == ["MXS010"]


def test_share_after_free_reports_uaf():
    alloc, san = _armed_allocator()
    pages = alloc.alloc(1, owner=1)
    alloc.free(pages, owner=1)
    with pytest.raises(ValueError):
        alloc.share(pages, owner=2)
    assert _codes() == ["MXS011"]


def test_write_to_shared_page_reports_cow_violation():
    alloc, san = _armed_allocator()
    pages = alloc.alloc(1, owner=1)
    alloc.share(pages, owner=2)
    san.note_write(1, pages)  # owner 1 writes without copy-on-write
    assert _codes() == ["MXS012"]
    # after a proper cow the writer's fresh page is exclusive: clean
    fresh = alloc.cow(pages[0], owner=1)
    san.note_write(1, [fresh])
    assert _codes() == ["MXS012"]  # no new findings


def test_leaked_reference_at_drain_reports_mxs013():
    alloc, san = _armed_allocator()
    pages = alloc.alloc(1, owner=1)
    alloc.share(pages)  # anonymous reference: nobody owns it at drain
    assert san.check()
    assert _codes() == ["MXS013"]
    with pytest.raises(sanitizers.SanitizerError):
        san.assert_quiescent()


def test_shadow_divergence_reports_mxs014():
    alloc, san = _armed_allocator()
    alloc.alloc(2, owner=1)
    alloc._refs[5] = 1  # tampered allocator state behind the shadow map
    san.check()
    assert "MXS014" in _codes()


def test_balanced_lifecycle_is_quiescent():
    alloc, san = _armed_allocator()
    pages = alloc.alloc(2, owner=1)
    alloc.share(pages, owner=2)
    moved = alloc.cow(pages[0], owner=2)
    alloc.free([moved, pages[1]], owner=2)
    alloc.free(pages, owner=1)
    assert san.assert_quiescent()
    assert not sanitizers.report()
    assert alloc.num_in_use == 0


# -- engine integration -------------------------------------------------------

def test_engine_full_run_is_quiescent_under_sanitizers(sanitized,
                                                       monkeypatch):
    """ServingEngine with prefix cache, chunked prefill and speculation
    all ON: run() drains through assert_quiescent(), the decode/prefill
    write paths go through note_write, and nothing fires."""
    # the engine holds its lock through step(); the first step compiles
    # every lever's program under it, a benign long hold: 2.6-3.4 s alone
    # on the CPU (the interpret-mode paged kernel), 5.8 s seen beside five
    # busy xdist workers. A stuck lock never lets go
    monkeypatch.setattr(sanitizers, "_hold_ms", 30000.0)
    cfg = tfm.TransformerConfig(vocab=32, d_model=16, n_heads=2,
                                n_layers=1, d_ff=32, max_len=64)
    params = tfm.init_params(cfg, seed=0)
    rng = np.random.RandomState(13)
    shared = rng.randint(1, 32, size=(9,)).astype(np.int32)
    eng = ServingEngine(params, cfg, slots=2, page_size=8, num_pages=20,
                        prefix_cache=1, prefill_chunk=4,
                        spec_ngram=2, spec_lookahead=3)
    assert eng._page_san is not None
    rids = []
    for i in range(4):
        tail = rng.randint(1, 32, size=(2 + i,)).astype(np.int32)
        rids.append(eng.submit(np.concatenate([shared, tail]), 4 + i % 2))
    res = eng.run()
    assert sorted(res) == sorted(rids)
    assert not sanitizers.report(), str(sanitizers.report())
    # cached prefix pages are owned by the cache, everything else freed
    held = eng.prefix_cache.cached_pages
    assert eng.allocator.num_in_use == held


def test_engine_a_step_ahead_writes_into_its_own_pages(sanitized,
                                                       monkeypatch):
    """Levers off and both slots decoding: the engine dispatches a step
    before it has read the one in flight, and tells the sanitizer the page
    of the position that step writes, one past the host's books. Every
    such page is the slot's own and live, across page boundaries, finishes
    and admissions, and run() drains quiescent."""
    monkeypatch.setattr(sanitizers, "_hold_ms", 30000.0)
    cfg = tfm.TransformerConfig(vocab=32, d_model=16, n_heads=2,
                                n_layers=1, d_ff=32, max_len=64)
    eng = ServingEngine(tfm.init_params(cfg, seed=0), cfg, slots=2,
                        page_size=4, num_pages=40, prefix_cache=0,
                        prefill_chunk=0, spec_ngram=0)
    assert eng._page_san is not None
    written = {}
    note_write = eng._page_san.note_write

    def spy(owner, pages):
        (slot,) = [s for s, r in enumerate(eng._slot_req)
                   if r is not None and r.request_id == owner]
        (page,) = pages
        written.setdefault(owner, []).append(
            eng._slot_pages[slot].index(page))
        return note_write(owner, pages)

    monkeypatch.setattr(eng._page_san, "note_write", spy)
    asked = ((3, 14), (6, 9), (2, 11), (5, 7))
    rng = np.random.RandomState(17)
    rids = [eng.submit(rng.randint(1, 32, size=(n,)).astype(np.int32), new)
            for n, new in asked]
    res = eng.run()
    assert [len(res[r].tokens) for r in rids] == [new for _, new in asked]
    assert eng.cache_stats()["decode_steps_ahead"] > 8
    # a request's decode steps write positions n .. n + new - 2, each into
    # the page of its own that holds it, however the step went out
    assert written == {r: [(n + i) // 4 for i in range(new - 1)]
                       for r, (n, new) in zip(rids, asked)}
    assert not sanitizers.report(), str(sanitizers.report())
    assert eng.allocator.num_in_use == 0


def test_engine_without_pages_sanitizer_has_no_shadow(monkeypatch):
    monkeypatch.delenv("MXTPU_SANITIZERS", raising=False)
    sanitizers.refresh_from_env()
    cfg = tfm.TransformerConfig(vocab=32, d_model=16, n_heads=2,
                                n_layers=1, d_ff=32, max_len=32)
    params = tfm.init_params(cfg, seed=0)
    eng = ServingEngine(params, cfg, slots=2, page_size=8, num_pages=12)
    assert eng._page_san is None
    assert eng.allocator.sanitizer is None


# -- findings sink ------------------------------------------------------------

def test_findings_feed_metrics_and_recorder(sanitized, monkeypatch):
    from incubator_mxnet_tpu.telemetry import recorder as _recorder
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    telemetry.refresh_from_env()
    telemetry.REGISTRY.reset()
    try:
        alloc, san = _armed_allocator()
        pages = alloc.alloc(1, owner=1)
        alloc.free(pages, owner=1)
        with pytest.raises(ValueError):
            alloc.free(pages, owner=1)
        c = telemetry.REGISTRY.counter(sanitizers.FINDINGS_TOTAL)
        assert c.value(sanitizer="pages", code="MXS010") == 1
        kinds = [e for e in _recorder.snapshot()
                 if e["kind"] == "sanitizer_finding"]
        assert kinds and kinds[-1]["code"] == "MXS010"
    finally:
        monkeypatch.delenv("MXNET_TELEMETRY", raising=False)
        telemetry.refresh_from_env()
        telemetry.REGISTRY.reset()


def test_page_lifecycle_events(sanitized):
    """alloc/share/cow/free log page_lifecycle flight events with owner
    provenance while the pages sanitizer is armed — and stay silent on
    an unarmed allocator (no default-path ring traffic)."""
    from incubator_mxnet_tpu.telemetry import recorder as _recorder
    plain = PageAllocator(8, 4)
    plain.sanitizer = None  # belt-and-braces: unarmed despite the env
    before = len([e for e in _recorder.snapshot()
                  if e["kind"] == "page_lifecycle"])
    plain.free(plain.alloc(1))
    assert len([e for e in _recorder.snapshot()
                if e["kind"] == "page_lifecycle"]) == before

    alloc = PageAllocator(8, 4)
    assert sanitizers.attach_page_sanitizer(alloc) is not None
    pages = alloc.alloc(2, owner=7)
    alloc.share([pages[0]], owner=9)
    moved = alloc.cow(pages[0], owner=9)
    alloc.free([moved], owner=9)
    events = [e for e in _recorder.snapshot()
              if e["kind"] == "page_lifecycle"]
    ops = [e["op"] for e in events]
    # cow allocs its fresh page first, then logs the move itself
    assert ops[-5:] == ["alloc", "share", "alloc", "cow", "free"]
    assert events[-5]["owner"] == 7
    assert events[-4]["owner"] == 9
    assert events[-2]["pages"] == [pages[0], moved]
    assert events[-1]["pages"] == [moved]


# -- satellite regression: embedding worker error handoff ---------------------

def test_embedding_worker_error_handoff(sanitized):
    """The prefetch worker hands push errors to the training thread via
    a locked read-and-clear (the unlocked swap was a lost-error race)."""
    from incubator_mxnet_tpu.embedding import ShardedEmbeddingService
    svc = ShardedEmbeddingService(clients=[object()], prefetch=True)
    try:
        assert type(svc._worker_error_lock).__name__ == "_SanLock"
        boom = RuntimeError("push exploded")

        def _fail(pending):
            raise boom

        svc._rpc_push = _fail
        svc._jobs.put(("push", []))
        deadline = time.monotonic() + 5.0
        while svc._worker_error is None and time.monotonic() < deadline:
            sanitizers._real_sleep(0.001)
        with pytest.raises(RuntimeError, match="push exploded"):
            svc._check_worker()
        svc._check_worker()  # read-and-clear: reported exactly once
    finally:
        svc._jobs.put(("stop",))
        svc._worker.join(timeout=5)
