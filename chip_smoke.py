#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the framework's two main paths once, through the entry points a user
calls, at the full width of models the repo ships, on whatever TPU jax
finds — and checks what comes out by the repo's own means:

  train    model-zoo ResNet-50 v1 (NHWC, bf16, batch 128 at 224x224) under
           SGD-momentum through fused.GluonTrainStep, built by
           bench.build_train_step exactly as bench.py measures it: a few
           step(x, y) calls and one scan_steps of K=8.
  serve    the d_model=512 / 8-head / 6-layer / vocab-32000 transformer
           through serving.ServingEngine at its default knobs: a warm-up
           wave, then eight requests of mixed lengths into its eight slots
           (a few decode steps dispatched one step ahead); greedy tokens of
           a float32 engine must equal models.transformer.generate().
  kernels  every Pallas kernel of ops/pallas_kernels.py compiled by Mosaic
           (interpret=False, explicitly) against its dense oracle.
  mesh     with more than one chip: the train step again over a 'data'
           mesh, replicated and zero1, against the one-chip loss.

It is a smoke, not a benchmark: each phase reports its wall time split into
compile and run, and neither is a speed. One process, no children. Any
failed check raises, so a phase that fails ends the run with a non-zero
exit code and no result line. Without a TPU it fails at once.

Last line of stdout on success:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""
import dataclasses
import importlib.metadata
import json
import sys
import time

# jax.monitoring events that make up "compile": tracing, lowering, and the
# backend compile (XLA + Mosaic, or the persistent-cache load in its place)
_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
_BACKEND_COMPILE = _COMPILE_EVENTS[2]


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Listens to jax's compile events: how many executables were built
    (or loaded from the persistent cache) and how much wall time the
    union of all trace/lower/compile intervals covers. Trace events nest,
    so durations are merged as intervals, not summed."""

    def __init__(self):
        import jax.monitoring

        self._spans = []
        self.backend_compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, duration, **_):
        if name in _COMPILE_EVENTS:
            end = time.perf_counter()
            self._spans.append((end - duration, end))
            if name == _BACKEND_COMPILE:
                self.backend_compiles += 1

    def seconds_since(self, t0):
        """Wall seconds of compile activity at or after perf_counter t0."""
        total, cur_end = 0.0, t0
        for start, end in sorted(s for s in self._spans if s[1] > t0):
            start = max(start, cur_end)
            if end > start:
                total += end - start
                cur_end = end
        return total


@dataclasses.dataclass
class Sizes:
    """What the smoke runs. The defaults ARE the smoke; tests/test_chip_smoke
    shrinks them to walk the same control flow on the CPU backend with the
    kernels interpreted. Widths are the shipped models' own."""
    # train
    batch: int = 128
    image: int = 224
    steps: int = 4
    scan_k: int = 8
    # serve (TransformerConfig fields; ServingEngine knobs stay default)
    vocab: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 6
    d_ff: int = 2048
    max_len: int = 512
    # (prompt_len, max_new_tokens) per request of the measured wave
    # as many as the engine has slots and none under 7 new tokens, so every
    # slot decodes for a few steps and the engine dispatches them one step
    # ahead: the chip runs that dispatch here, outside the benchmark
    requests: tuple = ((5, 12), (23, 7), (40, 16), (64, 8), (90, 9),
                       (130, 7), (17, 20), (200, 11))
    # requests of the float32 engine checked token-for-token vs generate()
    identity_requests: tuple = ((9, 10), (70, 8), (33, 12))
    # the tied embedding's two ends at gpt2_xl's shape: (decode rows, V, d)
    head_shape: tuple = (16, 50257, 1600)
    # kernels
    attn_shape: tuple = (2, 8, 512, 64)        # (B, H, T, D)
    xent_shape: tuple = (4096, 32000)          # (rows, vocab)
    decode_batch: int = 8
    decode_len: int = 512
    page_size: int = 16
    table_width: int = 32
    wide_q: tuple = (4, 16)
    # paged_diff_attention at phi4_mini_flash's geometry: slots, K/V pairs,
    # table width, pages of the shared pool (past the 7680 a VMEM-mapped
    # pool could have), pages of a slot's ring, window
    diff_geometry: tuple = (16, 10, 448, 8193, 33, 512)
    # grouped-query attention and the head-wise recurrence at
    # falcon_h1_34b's geometry: slots, K/V heads, query heads a K/V head,
    # head width, table width, pages of the pool; Mamba heads, their
    # width, the state's, groups
    hybrid_geometry: tuple = (32, 4, 5, 128, 256, 8193, 32, 128, 256, 2)
    ssd_chunk: int = 128
    epilogue_shapes: tuple = ((128 * 56 * 56, 64), (128 * 7 * 7, 2048))
    interpret: bool = False


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def _on_devices(tree, devices):
    import jax

    want = set(devices)
    return all(leaf.devices() == want
               for leaf in jax.tree_util.tree_leaves(tree))


def phase_train(devices, sz):
    """`steps` single steps, one K-step scan and one last step, all on ONE
    repeated batch (so a working optimizer must lower its loss). Every
    loss is fetched, which is what closes the timing."""
    import jax.numpy as jnp
    import numpy as np
    import bench
    from incubator_mxnet_tpu import nd

    step = bench.build_train_step("bfloat16", sz.batch, device=devices[0])
    x, y = bench.synthetic_batch(0, "bfloat16", sz.batch, sz.image)
    losses = [float(step(x, y).asscalar()) for _ in range(sz.steps)]
    xs = nd.from_jax(jnp.broadcast_to(x._data[None],
                                      (sz.scan_k,) + x.shape))
    ys = nd.from_jax(jnp.broadcast_to(y._data[None],
                                      (sz.scan_k,) + y.shape))
    losses += [float(v) for v in step.scan_steps(xs, ys).asnumpy()]
    losses.append(float(step(x, y).asscalar()))
    check(all(np.isfinite(losses)), f"non-finite training loss: {losses}")
    check(losses[-1] < losses[0],
          f"loss on the repeated batch did not fall: {losses}")
    check(_on_devices((step._params, step._states), devices[:1]),
          f"parameters or optimizer state are not on {devices[0]}")
    return {"first_loss": losses[0], "last_loss": losses[-1],
            "losses": [round(v, 3) for v in losses]}


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def _counter_total(name):
    from incubator_mxnet_tpu import telemetry

    fam = telemetry.REGISTRY.get(name)
    return sum(ch.value for _, ch in fam.series()) if fam else 0.0


def _prompts(rng, vocab, requests):
    return [(rng.randint(1, vocab, size=n).astype("int32"), m)
            for n, m in requests]


def phase_serve(clock, sz):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from incubator_mxnet_tpu.models import transformer as tfm
    from incubator_mxnet_tpu.ops.pallas_kernels import DENSE_FALLBACKS_TOTAL
    from incubator_mxnet_tpu.serving import ServingEngine

    def build(dtype):
        cfg = tfm.TransformerConfig(
            vocab=sz.vocab, d_model=sz.d_model, n_heads=sz.n_heads,
            n_layers=sz.n_layers, d_ff=sz.d_ff, max_len=sz.max_len,
            dtype=dtype)
        params = tfm.init_params(cfg, seed=0)
        return cfg, params, ServingEngine(params, cfg)

    rng = np.random.RandomState(0)
    cfg, params, eng = build("bfloat16")
    wave = _prompts(rng, cfg.vocab, sz.requests)
    buckets = sorted({eng._bucket_for(p.size) for p, _ in wave})
    check(len(buckets) >= 2,
          f"the wave must span two prefill buckets, spans {buckets}")
    # warm-up: one request per prefill bucket the wave will hit, decoded
    # for two tokens — every program the wave needs compiles here
    for b in buckets:
        eng.submit(rng.randint(1, cfg.vocab,
                               min(b, cfg.max_len - 2)).astype(np.int32), 2)
    eng.run()
    compiles_before = clock.backend_compiles
    rids = [eng.submit(p, m) for p, m in wave]
    results = eng.run()
    steady_compiles = clock.backend_compiles - compiles_before
    for rid, (p, m) in zip(rids, wave):
        got = len(results[rid].tokens)
        check(got == m, f"request {rid} (prompt {p.size}) returned {got} "
                        f"tokens, asked for {m}")
    check(steady_compiles == 0,
          f"{steady_compiles} program(s) compiled after the warm-up wave")
    # the wave fills every slot at once: until its shortest request is one
    # token from its end each decode step goes out before the one in flight
    # is read, its tokens handed on from the device, and (checked above)
    # under the executable the warm-up wave compiled
    stats = eng.cache_stats()
    ahead, ahead_floor = (stats["decode_steps_ahead"],
                          min(m for _, m in wave) - 2)
    check(len(wave) >= eng.slots and ahead >= ahead_floor > 0,
          f"{ahead} of {stats['decode_steps']} decode steps went out one "
          f"step ahead, expected {ahead_floor} or more: {len(wave)} "
          f"requests into {eng.slots} slots")

    # end-to-end agreement with the dense path, at a precision where token
    # equality means something: float32 weights, full-precision matmuls,
    # the same engine code (and the same Mosaic-compiled paged kernel)
    with jax.default_matmul_precision("highest"):
        cfg32, params32, eng32 = build("float32")
        checked = _prompts(rng, cfg32.vocab, sz.identity_requests)
        rids = [eng32.submit(p, m) for p, m in checked]
        results = eng32.run()
        for rid, (p, m) in zip(rids, checked):
            want = np.asarray(tfm.generate(
                params32, jnp.asarray(p)[None], m, cfg32))[0]
            got = np.asarray(results[rid].tokens)
            check(np.array_equal(got, want),
                  f"float32 engine tokens differ from generate() for "
                  f"prompt length {p.size}: {got.tolist()} vs "
                  f"{want.tolist()}")

    fallbacks = (_counter_total(DENSE_FALLBACKS_TOTAL)
                 + _counter_total(tfm.FLASH_DENSE_FALLBACKS_TOTAL))
    check(fallbacks == 0, f"{fallbacks} dense-attention fallback(s) counted")
    return {**_embedding_ends(sz),
            "requests": len(wave), "prefill_buckets": buckets,
            "tokens": sum(m for _, m in wave),
            "steady_compiles": steady_compiles,
            "decode_steps": stats["decode_steps"],
            "decode_steps_ahead": ahead,
            "token_identical_requests": len(checked),
            "dense_fallbacks": int(fallbacks),
            "pool_pages": eng.allocator.num_pages}


# what float32 rows lose against a bfloat16 table at the default matmul
# precision, in sigma of a row of logits: the MXU rounds the rows to
# bfloat16 (measured 0.0084 for 16 rows, 1.2e-6 for one, which is a float32
# multiply-and-reduce; my chip run, PR 33). The benchmark's check allows 0.05.
HEAD_SIGMA = 0.02


def _embedding_ends(sz):
    """models.transformer's two uses of the tied embedding at the cell's
    shape, float32 rows and a bfloat16 table in the layout this backend
    gives it: _token_rows must be the plain lookup to the bit (16 tokens a
    decode step, one, a prompt's worth), _logits within HEAD_SIGMA of the
    same product at precision "highest" (16 rows, and the prefill's one)."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.models import transformer as tfm

    rows, vocab, d = sz.head_shape
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    params = {
        "embed": (0.02 * jax.random.normal(ks[0], (vocab, d))
                  ).astype(jnp.bfloat16),
        "ln_f_g": (1 + 0.1 * jax.random.normal(ks[1], (d,))
                   ).astype(jnp.bfloat16),
        "ln_f_b": (0.1 * jax.random.normal(ks[2], (d,))).astype(jnp.bfloat16),
    }
    lookup = jax.jit(tfm._token_rows)
    for n in (rows, 1, 8 * rows + 1):
        tokens = jax.random.randint(ks[3], (n,), 0, vocab, jnp.int32)
        tokens = tokens.at[0].set(vocab - 1)  # the last, short slab
        same = jnp.array_equal(lookup(params["embed"], tokens),
                               params["embed"][tokens])
        check(bool(same), f"_token_rows differs from the plain lookup "
                          f"for {n} token(s)")

    head = jax.jit(tfm._logits)

    @jax.jit
    def exact(p, x):
        return jnp.matmul(tfm._ln(x, p["ln_f_g"], p["ln_f_b"]),
                          p["embed"].T, precision="highest")

    worst = {}
    for n in (rows, 1):
        x = 3.0 * jax.random.normal(ks[4], (n, d), jnp.float32) + 0.5
        got, want = head(params, x), exact(params, x)
        sigma = float(jnp.max(jnp.max(jnp.abs(got - want), -1)
                              / jnp.std(want, -1)))
        print(f"  head _logits[{n} x {d} . {vocab} x {d}]: {sigma:.2e} sigma "
              f"of a row from precision=highest (tol {HEAD_SIGMA:.0e})",
              flush=True)
        check(sigma <= HEAD_SIGMA, f"_logits of {n} row(s) is {sigma:.3e} "
                                   f"sigma from precision=highest")
        worst[n] = sigma
    return {"head_sigma_rows": worst[rows], "head_sigma_one_row": worst[1]}


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

# max |got - want| / max(1, max |want|) a kernel may show against its oracle
# (the oracle is float32 math at full matmul precision on the same inputs)
TOLERANCE = {"bfloat16": 4e-2, "float32": 2e-4}


class _Oracle:
    """Collects kernel-vs-oracle comparisons; the phase fails at its end if
    any is off, after every one has been printed."""

    def __init__(self):
        self.rows = []

    def close(self, name, got, want, dtype):
        import jax
        import jax.numpy as jnp

        tol = TOLERANCE[jnp.dtype(dtype).name]
        worst = 0.0
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            check(g.shape == w.shape, f"{name}: shape {g.shape} vs {w.shape}")
            g = g.astype(jnp.float32)
            w = w.astype(jnp.float32)
            err = float(jnp.max(jnp.abs(g - w))
                        / jnp.maximum(1.0, jnp.max(jnp.abs(w))))
            worst = max(worst, err) if err == err else float("nan")
        ok = worst <= tol
        print(f"  kernel {name}: err {worst:.2e} (tol {tol:.0e}) "
              f"{'ok' if ok else 'OFF'}", flush=True)
        self.rows.append((name, worst, ok))

    def finish(self):
        bad = [f"{n} ({e:.2e})" for n, e, ok in self.rows if not ok]
        check(not bad, f"kernels off their oracle: {', '.join(bad)}")
        return {"compared": len(self.rows),
                "worst_err": max(e for _, e, _ in self.rows)}


def _dense_attention(q, k, v, causal):
    import jax
    import jax.numpy as jnp

    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / (q.shape[-1] ** 0.5)
    if causal:
        T = q.shape[2]
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)


def _diff_attention_ref(q, pool, table, n_valid, window):
    """Oracle of pk.paged_diff_attention: q (B, G, 4, D); pool one layer
    (2G, P, ps, 2D); table (B, W) a growing table (window 0) or a ring."""
    import jax
    import jax.numpy as jnp

    B, G, _, D = q.shape
    ps, W = pool.shape[2], table.shape[1]
    L = window or W * ps
    # the L positions before each sequence's end, newest first
    pos = n_valid[:, None] - 1 - jnp.arange(L)[None]            # (B, L)
    page = jnp.take_along_axis(table, (jnp.maximum(pos, 0) // ps) % W, 1)
    rows = pool[:, page, jnp.maximum(pos, 0) % ps]              # (2G, B, L, 2D)
    rows = rows.reshape(G, 2, B, L, 2 * D)
    keys = rows[..., :D]                                        # (G, 2, B, L, D)
    value = jnp.concatenate([rows[:, 0, ..., D:], rows[:, 1, ..., D:]], -1)
    s = jnp.einsum("bgerd,gebld->bgerl", q.reshape(B, G, 2, 2, D),
                   keys) / (D ** 0.5)
    s = jnp.where((pos >= 0)[:, None, None, None], s, -1e30)
    out = jnp.einsum("bgerl,gblv->bgerv", jax.nn.softmax(s, -1), value)
    # a dead slot (n_valid 0) reads as zeros
    return out.reshape(B, G, 4, 2 * D) * (n_valid > 0)[:, None, None, None]


def _selective_scan_ref(dt, a, Bm, Cm, A, s0):
    import jax
    import jax.numpy as jnp

    def token(s, xs):
        dt_t, a_t, b_t, c_t = xs                # (S, Di), (S, Di), (S, N) x 2
        s = (jnp.exp(dt_t[:, None] * A) * s
             + (dt_t * a_t)[:, None] * b_t[..., None])
        return s, jnp.sum(s * c_t[..., None], axis=1)

    sT, y = jax.lax.scan(token, s0, tuple(
        x.swapaxes(0, 1) for x in (dt, a, Bm, Cm)))
    return y.swapaxes(0, 1), sT


def _ssd_ref(x, dt, A, B, C, s0):
    """Mamba-2's recurrence row by row: x (S, T, H, P), dt (S, T, H),
    A (H,), B, C (S, T, G, N), s0 (S, H, P, N). Returns (y, the state)."""
    import jax
    import jax.numpy as jnp

    hg = x.shape[2] // B.shape[2]

    def token(s, xs):
        x_t, dt_t, b_t, c_t = xs
        b_t, c_t = (jnp.repeat(a, hg, axis=1) for a in (b_t, c_t))
        s = (jnp.exp(dt_t * A)[..., None, None] * s
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None])
        return s, jnp.einsum("shpn,shn->shp", s, c_t)

    sT, y = jax.lax.scan(token, s0, tuple(
        a.swapaxes(0, 1) for a in (x, dt, B, C)))
    return y.swapaxes(0, 1), sT


def _gqa_decode_ref(q, pool, table, n_valid):
    """q (B, H * g, D) over one layer's pool (H, P, ps, 2D), query head j
    reading K/V head j // g; a dead slot reads as zeros."""
    import jax
    import jax.numpy as jnp

    H, D = pool.shape[0], q.shape[-1]
    k, v = (_gather_pages(a, table) for a in (pool[..., :D], pool[..., D:]))
    s = jnp.einsum("bhgd,bthd->bhgt", q.reshape(q.shape[0], H, -1, D),
                   k) / (D ** 0.5)
    s = jnp.where(jnp.arange(k.shape[1])[None, None, None]
                  < n_valid[:, None, None, None], s, -1e30)
    out = jnp.einsum("bhgt,bthd->bhgd", jax.nn.softmax(s, -1), v)
    return out.reshape(q.shape) * (n_valid > 0)[:, None, None]


def _gather_pages(pool, table):
    """(H, P, ps, D) pool + (B, W) table -> dense (B, W*ps, H, D) cache."""
    g = pool[:, table]                       # (H, B, W, ps, D)
    H, B, W, ps, D = g.shape
    return g.transpose(1, 2, 3, 0, 4).reshape(B, W * ps, H, D)


def phase_kernels(sz):
    import contextlib
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops import pallas_kernels as pk

    ip = sz.interpret
    f32 = jnp.float32
    orc = _Oracle()
    key = iter(jax.random.split(jax.random.PRNGKey(0), 192))

    def rand(shape, dtype, scale=1.0):
        return (jax.random.normal(next(key), shape, f32) * scale).astype(dtype)

    def full_precision():
        return jax.default_matmul_precision("highest")

    def oracle(fn, *args):
        # every oracle is float32 math at full matmul precision
        with full_precision():
            return fn(*args)

    for dtype in (jnp.bfloat16, f32):
        dn = jnp.dtype(dtype).name
        # a float32 kernel is compared at full precision too, or its own
        # dots would be the error; Mosaic refuses that setting on bfloat16
        # operands ("Bad lhs type"), which need no help to be exact
        kernel_precision = (full_precision if dtype == f32
                            else contextlib.nullcontext)

        def kernel(fn, *args):
            with kernel_precision():
                return fn(*args)

        # flash_attention, forward and backward
        q, k, v, w = (rand(sz.attn_shape, dtype) for _ in range(4))
        wf = w.astype(f32)
        for causal in (True, False):
            tag = f"flash_attention[{dn},causal={causal}]"

            def flash(*a):
                return pk.flash_attention(*a, causal=causal, interpret=ip)

            def dense(*a):
                return _dense_attention(*a, causal)

            orc.close(f"{tag} fwd", kernel(flash, q, k, v),
                      oracle(dense, q, k, v), dtype)
            orc.close(
                f"{tag} bwd",
                kernel(jax.grad(lambda *a: jnp.sum(flash(*a).astype(f32)
                                                   * wf), (0, 1, 2)),
                       q, k, v),
                oracle(jax.grad(lambda *a: jnp.sum(dense(*a) * wf),
                                (0, 1, 2)), q, k, v), dtype)

        # softmax_xent, forward and backward
        rows, vocab = sz.xent_shape
        logits = rand(sz.xent_shape, dtype, 3.0)
        labels = jax.random.randint(next(key), (rows,), 0, vocab)
        wrow = rand((rows,), f32)

        def xent(lg):
            return pk.softmax_xent(lg, labels, interpret=ip)

        def xent_ref(lg):
            logp = jax.nn.log_softmax(lg.astype(f32), axis=-1)
            return -jnp.take_along_axis(logp, labels[:, None], -1)[:, 0]

        orc.close(f"softmax_xent[{dn}] fwd", kernel(xent, logits),
                  oracle(xent_ref, logits), f32)
        orc.close(
            f"softmax_xent[{dn}] bwd",
            kernel(jax.grad(lambda lg: jnp.sum(xent(lg) * wrow)), logits),
            oracle(jax.grad(lambda lg: jnp.sum(xent_ref(lg) * wrow)),
                   logits), dtype)

        # flash_decode over a dense cache, ragged depths
        B, T = sz.decode_batch, sz.decode_len
        H, D = sz.attn_shape[1], sz.attn_shape[3]
        q1 = rand((B, H, D), dtype)
        kc, vc = rand((B, T, H, D), dtype), rand((B, T, H, D), dtype)
        depth = jax.random.randint(next(key), (B,), 1, T + 1)
        depth = depth.at[0].set(T).at[1].set(1)
        orc.close(f"flash_decode[{dn},B={B}]",
                  kernel(lambda *a: pk.flash_decode(*a, interpret=ip),
                         q1, kc, vc, depth),
                  oracle(pk.dense_decode_attention, q1.astype(f32),
                         kc.astype(f32), vc.astype(f32), depth), dtype)

        # paged kernels at the engine's default geometry: one pool for
        # every layer, K|V fused per row, read and written by layer index
        # (layer 1 of 2 here; layer 0 is noise nothing may touch). The
        # decode kernels gather each slot's pages from the pool in HBM,
        # a 128-token block of a shuffled table at a time
        ps, W = sz.page_size, sz.table_width
        P = B * W + 1
        kp, vp = rand((H, P, ps, D), dtype), rand((H, P, ps, D), dtype)
        pool = jnp.stack([rand((H, P, ps, 2 * D), dtype),
                          jnp.concatenate([kp, vp], -1)])
        table = (1 + jax.random.permutation(next(key), B * W)
                 ).reshape(B, W).astype(jnp.int32)
        kd, vd = (_gather_pages(a.astype(f32), table) for a in (kp, vp))
        nv = jax.random.randint(next(key), (B,), 1, W * ps + 1)
        nv = nv.at[0].set(W * ps).at[1].set(1)
        orc.close(f"paged_decode_attention[{dn}]",
                  kernel(lambda *a: pk.paged_decode_attention(
                      *a, layer=1, interpret=ip), q1, pool, table, nv),
                  oracle(pk.dense_decode_attention, q1.astype(f32), kd, vd,
                         nv), dtype)
        for Q in sz.wide_q:
            qw = rand((B, Q, H, D), dtype)
            nb = jnp.minimum(nv, W * ps - Q)
            want = jnp.stack([
                oracle(pk.dense_decode_attention, qw[:, i].astype(f32), kd,
                       vd, nb + i + 1) for i in range(Q)], axis=1)
            orc.close(f"paged_decode_attention_wide[{dn},Q={Q}]",
                      kernel(lambda *a: pk.paged_decode_attention_wide(
                          *a, layer=1, interpret=ip), qw, pool, table, nb),
                      want, dtype)
            # the write those Q rows take first: each sequence stores its
            # first n_w rows from nb on, and no other row of the pool moves
            kw, vw = rand((B, Q, H, D), dtype), rand((B, Q, H, D), dtype)
            n_w = jax.random.randint(next(key), (B,), 0, Q + 1).at[0].set(Q)
            plan = pk.paged_write_plan(table, nb, n_w, Q, ps)
            got = pk.paged_kv_write(pool, 1, kw, vw, plan, interpret=ip)
            pos = nb[:, None] + jnp.arange(Q)[None]
            page = jnp.take_along_axis(table, pos // ps, axis=1)
            page = jnp.where(jnp.arange(Q)[None] < n_w[:, None], page, P)
            want = pool.at[1, :, page, pos % ps].set(
                jnp.concatenate([kw, vw], -1), mode="drop")
            orc.close(f"paged_kv_write[{dn},Q={Q}]", got, want, dtype)

        # grouped differential attention (models.sambay): 4 query rows
        # over each pair of K/V heads, by a growing table and by a ring of
        # 3 pages under a window of 2 pages, slots far past the ring
        q4 = rand((B, H // 2, 4, D), dtype)
        for tag, tbl, depth, win in (
                ("table", table, nv, 0),
                ("ring", table[:, :3], nv + 7 * ps, 2 * ps)):
            orc.close(f"paged_diff_attention[{dn},{tag}]",
                      kernel(lambda *a: pk.paged_diff_attention(
                          *a, layer=1, window=win, ring=bool(win),
                          interpret=ip), q4, pool, tbl, depth),
                      oracle(_diff_attention_ref, q4.astype(f32),
                             pool[1].astype(f32), tbl, depth, win), dtype)

    # the same kernel as models.sambay's decode step calls it at the
    # benchmark's geometry: a bfloat16 pool, float32 queries, slots to the
    # table's full depth beside a shallow and a dead one; the shared cache
    # by a shuffled table, and rings many times round under the window
    S, G, W, P, R, win = sz.diff_geometry
    D, ps = sz.attn_shape[3], sz.page_size
    q4 = rand((S, G, 4, D), f32)
    depth = jax.random.randint(next(key), (S,), 1, W * ps + 1)
    depth = depth.at[0].set(W * ps).at[1].set(1).at[2].set(0)
    table = (1 + jax.random.permutation(next(key), P - 1)[:S * W]
             ).reshape(S, W).astype(jnp.int32)
    rings = (1 + jnp.arange(S * R, dtype=jnp.int32)).reshape(S, R)
    for tag, tbl, pages, win in (("shared", table.at[2].set(0), P, 0),
                                 ("ring", rings, S * R + 1, win)):
        pool = rand((2, 2 * G, pages, ps, 2 * D), jnp.bfloat16)
        orc.close(f"paged_diff_attention[cell,{tag},{pages} pages]",
                  pk.paged_diff_attention(q4, pool, tbl, depth, 1, window=win,
                                          ring=bool(win), interpret=ip),
                  oracle(_diff_attention_ref,
                         q4.astype(jnp.bfloat16).astype(f32),
                         pool[1].astype(f32), tbl, depth, win), jnp.bfloat16)

    # selective_scan: a prompt's worth of steps from a given state, and
    # the single step of a decode batch (float32 by contract)
    Di, N = 2 * sz.d_model, 16
    for S, T in ((1, 2 * pk.SCAN_TIME_BLOCK), (sz.decode_batch, 1)):
        dt = jax.nn.softplus(rand((S, T, Di), f32) - 3.0)
        args = (dt, rand((S, T, Di), f32), rand((S, T, N), f32),
                rand((S, T, N), f32),
                -jnp.broadcast_to(jnp.arange(1.0, N + 1)[:, None], (N, Di)),
                rand((S, N, Di), f32))
        orc.close(f"selective_scan[S={S},T={T}]",
                  pk.selective_scan(*args, interpret=ip),
                  oracle(_selective_scan_ref, *args), f32)

    # models.falcon_h1's decode step at the benchmark's geometry: five
    # float32 query rows a K/V head of 128 over a bfloat16 pool, K|V fused
    # in 256 lanes, slots to the table's full depth beside a shallow and a
    # dead one; every live slot's (heads, 128, 256) state through one
    # layer's recurrence in place, a dead slot's untouched; and a prompt's
    # chunked scan against the row-by-row one, its true length mid-chunk
    S, Hkv, g, D, W, P, Hs, Pd, N, G = sz.hybrid_geometry
    ps = sz.page_size
    q = rand((S, Hkv * g, D), f32)
    pool = rand((2, Hkv, P, ps, 2 * D), jnp.bfloat16)
    depth = jax.random.randint(next(key), (S,), 1, W * ps + 1)
    depth = depth.at[0].set(W * ps).at[1].set(1).at[2].set(0)
    table = (1 + jax.random.permutation(next(key), P - 1)[:S * W]
             ).reshape(S, W).astype(jnp.int32).at[2].set(0)
    orc.close(f"paged_decode_attention[cell,{g} rows x {D},{P} pages]",
              pk.paged_decode_attention(q, pool, table, depth, 1,
                                        interpret=ip),
              oracle(_gqa_decode_ref, q, pool[1].astype(f32), table, depth),
              jnp.bfloat16)
    A = -jnp.exp(rand((Hs,), f32))

    def rows(T):
        return (rand((S, T, Hs, Pd), f32),
                jax.nn.softplus(rand((S, T, Hs), f32) - 3.0), A,
                rand((S, T, G, N), f32), rand((S, T, G, N), f32))

    # dead slots before the first live one and between live ones
    state, live = rand((2, S, Hs, Pd, N), f32), (depth > 0).at[0].set(False)
    step = rows(1)
    want_y, want_s = oracle(_ssd_ref, *step, state[1])
    keep = live[:, None, None, None]
    orc.close("ssd_state_update[cell]",
              pk.ssd_state_update(state, 1, live, *(
                  a if a.ndim == 1 else a[:, 0] for a in step),
                  interpret=ip),
              (jnp.where(keep[..., 0], want_y[:, 0], 0.0),
               state.at[1].set(jnp.where(keep, want_s, state[1]))), f32)
    prompt = tuple(a[:2] if a.ndim > 1 else a
                   for a in rows(2 * sz.ssd_chunk))
    real = jnp.asarray([sz.ssd_chunk + 5, 2 * sz.ssd_chunk])
    prompt = (prompt[0], jnp.where(
        jnp.arange(2 * sz.ssd_chunk)[None, :, None] < real[:, None, None],
        prompt[1], 0.0)) + prompt[2:]
    orc.close(f"ssd_chunk_scan[T={2 * sz.ssd_chunk}]",
              pk.ssd_chunk_scan(*prompt, sz.ssd_chunk),
              oracle(_ssd_ref, *prompt, jnp.zeros((2, Hs, Pd, N), f32)), f32)

    # bn_act_epilogue at ResNet-50's first and last stage, bf16 (no dots)
    for r, c in sz.epilogue_shapes:
        x, res, w = (rand((r, c), jnp.bfloat16) for _ in range(3))
        wf = w.astype(f32)
        scale, shift = rand((c,), f32), rand((c,), f32)

        def epi(*a):
            return pk.bn_act_epilogue(*a, interpret=ip)

        def pre_activation(x, scale, shift, res=None):
            y = x.astype(f32) * scale + shift
            return y if res is None else y + res.astype(f32)

        def epi_ref(*a):
            return jnp.maximum(pre_activation(*a), 0.0)

        for tag, extra in (("plain", ()), ("residual", (res,))):
            args = (x, scale, shift) + extra
            nargs = tuple(range(len(args)))
            name = f"bn_act_epilogue[{r}x{c},{tag}]"
            orc.close(f"{name} fwd", epi(*args), epi_ref(*args),
                      jnp.bfloat16)
            # an element whose pre-activation is within float32 rounding
            # of 0 has its ReLU gate decided by the order of the adds,
            # which the kernel and XLA are both free to choose: about one
            # such element in 25 million, and its whole cotangent is the
            # difference (first chip run). Gradients of elements the
            # reference itself cannot decide are left out.
            decided = jnp.abs(pre_activation(*args)) > 1e-4

            def settled(grads):
                return tuple(jnp.where(decided, g, 0) if g.shape == x.shape
                             else g for g in grads)

            orc.close(
                f"{name} bwd",
                settled(jax.grad(lambda *a: jnp.sum(epi(*a).astype(f32)
                                                    * wf), nargs)(*args)),
                settled(jax.grad(lambda *a: jnp.sum(epi_ref(*a) * wf),
                                 nargs)(*args)), jnp.bfloat16)
    return orc.finish()


# ---------------------------------------------------------------------------
# mesh
# ---------------------------------------------------------------------------

# |loss(mesh) - loss(one chip)| / loss(one chip) on the first step.
# __graft_entry__'s dp phase allows bf16 compute 5e-2 absolute on a toy whose
# loss is ln(10) = 2.3 (reduction-order noise at bf16 resolution); this is
# the same bound as a ratio, for a loss near ln(1000)
MESH_LOSS_RTOL = 2e-2


def _per_device_bytes(tree, devices):
    import jax

    out = {str(d): 0 for d in devices}
    for leaf in jax.tree_util.tree_leaves(tree):
        for sh in leaf.addressable_shards:
            out[str(sh.device)] += sh.data.nbytes
    return out


def phase_mesh(devices, sz, one_chip_first_loss):
    import jax
    import numpy as np
    from jax.sharding import Mesh
    import bench

    n = len(devices)
    mesh = Mesh(np.array(devices), ("data",))
    report = {}
    for policy in ("replicated", "zero1"):
        step = bench.build_train_step("bfloat16", sz.batch, mesh=mesh,
                                      shard_policy=policy)
        x, y = bench.synthetic_batch(0, "bfloat16", sz.batch, sz.image)
        first = float(step(x, y).asscalar())
        second = float(step(x, y).asscalar())
        check(np.isfinite([first, second]).all(),
              f"{policy}: non-finite loss {first}, {second}")
        rel = abs(first - one_chip_first_loss) / abs(one_chip_first_loss)
        check(rel <= MESH_LOSS_RTOL,
              f"{policy}: first-step loss {first} vs one chip "
              f"{one_chip_first_loss}: off by {rel:.2e} of it "
              f"(tol {MESH_LOSS_RTOL})")
        check(_on_devices(step._params, devices),
              f"{policy}: parameters are not addressable on every device")
        leaves = jax.tree_util.tree_leaves(step._states)
        check(_on_devices(leaves, devices),
              f"{policy}: optimizer state is not on every device")
        sharded = [s for s in leaves
                   if s.addressable_shards[0].data.size * n == s.size]
        if policy == "zero1":
            check(sharded, "zero1: no optimizer-state leaf is sharded 1/N")
            big = sum(s.nbytes for s in sharded) / sum(s.nbytes
                                                       for s in leaves)
            check(big > 0.9, f"zero1: only {big:.0%} of optimizer-state "
                             f"bytes are sharded 1/{n}")
        else:
            check(not sharded, "replicated: optimizer state is sharded")
        report[policy] = {
            "first_loss": first, "second_loss": second,
            "first_loss_rel_diff": rel,
            "state_leaves_sharded": f"{len(sharded)}/{len(leaves)}",
            "param_bytes_per_device": _per_device_bytes(step._params,
                                                        devices),
            "state_bytes_per_device": _per_device_bytes(leaves, devices),
            "bytes_in_use": {str(d): (d.memory_stats() or {}).get(
                "bytes_in_use") for d in devices},
        }
    return report


# ---------------------------------------------------------------------------

def run_phase(name, clock, fn, *args):
    t0 = time.perf_counter()
    detail = fn(*args)
    wall = time.perf_counter() - t0
    compile_s = clock.seconds_since(t0)
    print(f"PASS {name}: smoke, not a speed — wall {wall:.1f}s = compile "
          f"{compile_s:.1f}s + run {wall - compile_s:.1f}s  "
          f"{json.dumps(detail)}", flush=True)
    return {"wall_s": round(wall, 1), "compile_s": round(compile_s, 1),
            "run_s": round(wall - compile_s, 1), **detail}


def main():
    import bench

    devices = bench.require_tpu()  # exits, naming the platforms found
    import jax
    import jaxlib
    from incubator_mxnet_tpu import compile_cache, telemetry

    cache_dir = compile_cache.enable_jax_cache()
    telemetry.enable()  # the fallback counters only count when it is on
    clock = CompileClock()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    print(f"chip_smoke: platform={dev['platform']} "
          f"device_kind={dev['kind']!r} devices={dev['count']} "
          f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
          f"libtpu={importlib.metadata.version('libtpu')} "
          f"compile_cache={cache_dir}", flush=True)

    sz = Sizes()
    phases = {"train": run_phase("train", clock, phase_train, devices, sz)}
    phases["serve"] = run_phase("serve", clock, phase_serve, clock, sz)
    phases["kernels"] = run_phase("kernels", clock, phase_kernels, sz)
    if len(devices) > 1:
        phases["mesh"] = run_phase("mesh", clock, phase_mesh, devices, sz,
                                   phases["train"]["first_loss"])
    print(json.dumps({"phases": phases}), flush=True)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
