"""models.falcon_h1 through its programs and through ServingEngine, against
the plain reference (benchmark/references/falcon_h1.py), logits and not
tokens, at a small size: 3 blocks of grouped-query attention (10 query heads
over 2 K/V heads of 8, rotary) beside Mamba-2 (4 heads of 16, state 16, 2
groups), d 64, vocab 128, the published multipliers. And the kernels it
added, each against its plain form."""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from benchmark.harness import loader
from incubator_mxnet_tpu import config as knobs
from incubator_mxnet_tpu.models import falcon_h1
from incubator_mxnet_tpu.ops import pallas_kernels as pk
from incubator_mxnet_tpu.serving import ServingEngine

REFERENCE = loader.load_callable("references", "falcon_h1.py:logits")
PUBLISHED = loader.load_json("configs", "falcon_h1_34b")
MULTIPLIERS = {k: PUBLISHED[k] for k in (
    "embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
    "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
    "ssm_out_multiplier", "ssm_multipliers", "mlp_multipliers")}
CONFIG = {**MULTIPLIERS, "num_attention_heads": 10, "num_key_value_heads": 2,
          "head_dim": 8, "rope_theta": PUBLISHED["rope_theta"],
          "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_state": 16,
          "mamba_n_groups": 2, "mamba_d_conv": 4, "mamba_d_ssm": 64,
          "rms_norm_eps": 1e-5}
CFG = falcon_h1.FalconH1Config(
    vocab=128, d_model=64, n_layers=3, n_heads=10, n_kv_heads=2, head_dim=8,
    d_ff=128, d_ssm=64, ssm_heads=4, d_state=16, n_groups=2, chunk=8,
    prefill_block=8, max_len=64,
    **{k: tuple(v) if isinstance(v, list) else v
       for k, v in MULTIPLIERS.items()})
PAGE, WIDTH = 4, 16
# float32 on both sides; the logits' own scale is ~0.008 (lm_head_multiplier)
TOL = 2e-6


@pytest.fixture(scope="module")
def params():
    return falcon_h1.init_params(CFG, 3)


@pytest.fixture(scope="module")
def programs():
    prog = CFG.paged_programs()
    return jax.jit(prog.prefill), jax.jit(prog.decode)


def _reference_rows(params, tokens, first, count):
    toks = np.zeros((64,), np.int32)  # one length: one compile
    toks[: len(tokens)] = tokens
    return np.asarray(REFERENCE(params, jnp.asarray(toks), CONFIG)
                      )[first: first + count]


def _prompt(rng, n):
    return rng.integers(1, CFG.vocab, size=n).astype(np.int32)


def _serve(params, programs, prompts, buckets, steps):
    """Prefills each prompt into its slot, then `steps` greedy decode steps
    for all of them in one batch beside a dead slot. Returns per prompt
    (tokens, logits of the last prompt row and of every decoded row)."""
    prefill, decode = programs
    S = len(prompts) + 1
    cache = falcon_h1.init_cache(CFG, S, S * WIDTH + 1, PAGE)
    tables = np.zeros((S, WIDTH), np.int32)
    positions = np.zeros((S,), np.int32)
    nxt = np.zeros((S,), np.int32)
    seqs, rows = {}, {}
    for s, (prompt, bucket) in enumerate(zip(prompts, buckets), start=1):
        padded = np.zeros((1, bucket), np.int32)
        padded[0, : prompt.size] = prompt
        row = 1 + s * WIDTH + np.arange(WIDTH, dtype=np.int32) - WIDTH
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
        lg = np.asarray(lg)
        for s in seqs:
            seqs[s].append(int(nxt[s]))
            positions[s] += 1
            rows[s].append(lg[s])
            nxt[s] = int(np.argmax(lg[s]))
    return [(np.asarray(seqs[s], np.int32), np.stack(rows[s])) for s in seqs]


@pytest.mark.parametrize("lengths,buckets,steps", [
    # each under its bucket's length, one inside a chunk of 8 and one
    # across two: the states are the ones after the last real row
    ((5, 13, 11), (8, 16, 16), 6),
    # true length == bucket; three depths in one decode batch; 30 steps
    # cross pages and the 32-token mark
    ((8, 16, 3), (8, 16, 8), 30),
], ids=["under_bucket", "at_bucket"])
def test_programs_match_the_reference(params, programs, lengths, buckets,
                                      steps):
    rng = np.random.default_rng(sum(lengths))
    prompts = [_prompt(rng, n) for n in lengths]
    for prompt, (tokens, got) in zip(prompts, _serve(
            params, programs, prompts, buckets, steps)):
        want = _reference_rows(params, tokens, prompt.size - 1, steps + 1)
        assert np.abs(got - want).max() < TOL


def test_every_branch_is_as_large_as_the_residual_it_joins(params):
    """The published multipliers are made for trained weights; with drawn
    ones they leave every branch a per-mille of the residual and a check
    of logits would pass with a branch missing. init_params scales each
    branch's last matrix; on another prompt every layer's attention, Mamba
    and MLP output is within a factor of two of the residual."""
    fresh = jax.random.randint(jax.random.key(5), (1, 48), 1, CFG.vocab)
    ratios = np.asarray(falcon_h1.branch_ratios(params, fresh, CFG))
    assert ratios.shape == (CFG.n_layers, 3)
    assert (ratios > 0.5).all() and (ratios < 2.0).all(), ratios
    raw = falcon_h1.init_params(CFG, 3, calibrate=False)
    assert np.asarray(falcon_h1.branch_ratios(raw, fresh, CFG)).max() < 0.05


# -- through the engine ---------------------------------------------------------


def _engine(params, **kw):
    return ServingEngine(params, CFG, slots=4, page_size=PAGE, max_len=64,
                         **{"prefix_cache": 0, "prefill_chunk": 0,
                            "spec_ngram": 0, **kw})


def test_continuous_batching_equals_each_request_alone(params):
    """Seven requests through four slots, ending and admitted mid-run:
    every token is the one the request gets when served alone, and the
    reference's own choice for its row."""
    rng = np.random.default_rng(11)
    asked = [(_prompt(rng, n), new) for n, new in
             ((5, 9), (16, 30), (14, 12), (9, 40), (3, 17), (21, 6), (8, 25))]
    eng = _engine(params)
    rids = [eng.submit(p, new) for p, new in asked]
    together = eng.run()
    for rid, (prompt, new) in zip(rids, asked):
        alone = _engine(params)
        one = alone.submit(prompt, new)
        out = together[rid].tokens
        assert out == alone.run()[one].tokens and len(out) == new
        assert together[rid].finish_reason == "length"
        rows = _reference_rows(params, np.concatenate([prompt, out[:-1]]),
                               prompt.size - 1, new)
        assert (rows.max(-1) - rows[np.arange(new), out]).max() < TOL
    stats = eng.cache_stats()
    assert stats["pool"]["pages_reserved"] == 0
    assert stats["kinds"]["paged_kv"] == {"layers": 3, "kv_heads": 2,
                                          "grows": True}
    assert stats["kinds"]["recurrent"] == {
        "layers": 3, "state_bytes_per_slot": 4 * 3 * (4 * 16 * 16 + 3 * 128)}
    # every decode step attended each live slot's depth in every layer
    assert stats["attended_tokens"] == {"paged_kv": 3 * sum(
        sum(range(p.size + 1, p.size + new)) for p, new in asked)}
    # in whole blocks of 128 tokens: no slot gets past its first
    assert stats["fetched_tokens"] == {"paged_kv": 3 * 128 * sum(
        new - 1 for _, new in asked)}
    assert eng.debug_snapshot()["cache"]["kinds"].keys() == {
        "paged_kv", "recurrent", "logits"}


# the engine a step ahead of this model (tokens, the flights, an eos_id, a
# free slot, a cancel): tests/test_serving_engine.py, beside the transformer


def test_engine_programs_leave_each_tokens_row_on_the_device(params):
    """Five requests over four slots: four decode together with a step in
    flight, the fifth alone and read step by step; every token's row is
    found, whichever way its step was run."""
    served_rows = loader.load_callable(
        "jobs", "serve_parallel_hybrid.py:served_rows")
    rng = np.random.default_rng(13)
    prompts = [_prompt(rng, n) for n in (15, 9, 12, 5, 20)]
    new = 10
    served = served_rows(_engine(params), prompts, new, set(range(new)))
    for prompt, (out, rows) in zip(prompts, served.values()):
        got = np.stack([np.asarray(rows[i]) for i in range(new)])
        assert (got.argmax(-1) == out).all()
        want = _reference_rows(params, np.concatenate([prompt, out[:-1]]),
                               prompt.size - 1, new)
        assert np.abs(got - want).max() < TOL


@pytest.mark.parametrize("lever", ["prefix_cache", "prefill_chunk",
                                   "spec_ngram"])
def test_engine_refuses_a_lever_without_state_snapshots(params, lever):
    with pytest.raises(ValueError, match="state snapshots") as e:
        _engine(params, **{lever: 4})
    assert lever in str(e.value)
    with pytest.raises(NotImplementedError, match="state snapshots"):
        CFG.paged_programs().wide()


def test_no_new_knob():
    assert len(knobs.KNOBS) == 93


# -- the kernels ------------------------------------------------------------------


def _ssd_rows(x, dt, A, B, C, s0):
    """The recurrence row by row in NumPy: x (S, T, H, P), dt (S, T, H),
    A (H,), B, C (S, T, G, N), s0 (S, H, P, N)."""
    hg = x.shape[2] // B.shape[2]
    s, ys = s0.astype(np.float64), []
    for t in range(x.shape[1]):
        b, c = (np.repeat(a[:, t], hg, axis=1) for a in (B, C))
        s = (np.exp(dt[:, t] * A)[..., None, None] * s
             + (dt[:, t, :, None] * x[:, t])[..., None] * b[:, :, None])
        ys.append(np.einsum("shpn,shn->shp", s, c))
    return np.stack(ys, 1), s


def _ssd_operands(rng, S, T, H=4, P=8, N=16, G=2):
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (S, T, H)))
    return (rng.normal(size=(S, T, H, P)).astype(np.float32),
            dt.astype(np.float32),
            -rng.uniform(1, 16, (H,)).astype(np.float32),
            rng.normal(size=(S, T, G, N)).astype(np.float32),
            rng.normal(size=(S, T, G, N)).astype(np.float32))


@pytest.mark.parametrize("T,chunk,real", [
    (40, 8, (29, 40)),     # true lengths inside a chunk and at its end
    (40, 128, (40, 3)),    # one chunk for the whole bucket
    (256, 128, (130, 256)),  # the published chunk
], ids=["mid_chunk", "one_chunk", "chunk_128"])
def test_ssd_chunk_scan_is_the_row_by_row_recurrence(T, chunk, real):
    rng = np.random.default_rng(T + chunk)
    x, dt, A, B, C = _ssd_operands(rng, 2, T)
    real = np.asarray(real)
    masked = dt * (np.arange(T)[None, :, None] < real[:, None, None])
    y, last = pk.ssd_chunk_scan(*map(jnp.asarray, (x, masked, A, B, C)),
                                chunk)
    want_y, _ = _ssd_rows(x, masked, A, B, C, np.zeros((2, 4, 8, 16)))
    assert np.abs(np.asarray(y) - want_y).max() < 2e-5
    # the state is the one after the last REAL row, not the bucket's end
    for s, n in enumerate(real):
        _, want = _ssd_rows(x[s:s + 1, :n], dt[s:s + 1, :n], A, B[s:s + 1, :n],
                            C[s:s + 1, :n], np.zeros((1, 4, 8, 16)))
        assert np.abs(np.asarray(last)[s] - want[0]).max() < 2e-5


def test_a_prompts_states_are_those_after_its_last_real_row(params):
    """_mamba2_mix over a padded bucket leaves the convolution's tail and
    the scan state of the true length, whatever the padding holds."""
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.normal(size=(1, 16, 64)).astype(np.float32))
    empty = jnp.zeros((1, 3, CFG.d_xbc), jnp.float32)

    def recur(*operands):
        return pk.ssd_chunk_scan(*operands, CFG.chunk)

    def mix(h, n_real):
        out, (conv, state) = falcon_h1._mamba2_mix(lp, h, empty, recur,
                                                   n_real, CFG)
        return np.asarray(out), np.asarray(conv), np.asarray(state)

    out, conv, state = mix(h, jnp.asarray([11]))
    want_out, want_conv, want_state = mix(h[:, :11], None)
    assert np.abs(out[:, :11] - want_out).max() < 2e-6
    assert np.abs(conv - want_conv).max() == 0
    assert np.abs(state - want_state).max() < 2e-6


def test_ssd_state_update_is_in_place_and_leaves_a_dead_slot():
    rng = np.random.default_rng(4)
    S, L = 5, 3
    x, dt, A, B, C = _ssd_operands(rng, S, 1)
    state = rng.normal(size=(L, S, 4, 8, 16)).astype(np.float32)
    live = np.asarray([False, True, True, False, True])
    y, new = pk.ssd_state_update(
        jnp.asarray(state), jnp.asarray(1, jnp.int32), jnp.asarray(live),
        *(jnp.asarray(a if a.ndim == 1 else a[:, 0])
          for a in (x, dt, A, B, C)))
    want_y, want = _ssd_rows(x, dt, A, B, C, state[1])
    y, new = np.asarray(y), np.asarray(new)
    assert np.abs(y[live] - want_y[live, 0]).max() < 2e-6
    assert np.abs(new[1][live] - want[live]).max() < 2e-6
    # a dead slot's state is what it was, bit for bit, and so is every
    # other layer's; its y is zeros
    assert (new[1][~live] == state[1][~live]).all()
    assert (new[[0, 2]] == state[[0, 2]]).all()
    assert (y[~live] == 0).all()
    # all slots dead: nothing moves
    _, same = pk.ssd_state_update(
        jnp.asarray(state), 2, jnp.zeros((S,), bool),
        *(jnp.asarray(a if a.ndim == 1 else a[:, 0])
          for a in (x, dt, A, B, C)))
    assert (np.asarray(same) == state).all()


def _dense_gqa(q, pool, table, n_valid, layer):
    B, Hq, D = q.shape
    H, page = pool.shape[1], pool.shape[3]
    out = np.zeros((B, Hq, D), np.float32)
    for b in range(B):
        pos = np.arange(int(n_valid[b]))
        if not pos.size:
            continue
        for j in range(Hq):
            rows = pool[layer, j // (Hq // H), table[b, pos // page],
                        pos % page]
            s = rows[:, :D] @ q[b, j] / np.sqrt(D)
            p = np.exp(s - s.max())
            out[b, j] = (p / p.sum()) @ rows[:, D:]
    return out


# at page 16 a block is 8 pages, 128 tokens: tails inside a page, at a
# block's end, a shallow slot, a dead one, a table's full depth
@pytest.mark.parametrize("depths,dtype", [
    ((1, 130, 0, 255, 384, 400), "float32"),
    ((129, 17, 400, 0, 256, 5), "bfloat16"),
], ids=["float32", "bf16_pool"])
def test_grouped_query_decode_matches_dense(depths, dtype):
    """Five query rows a K/V head of width 128 on the shared walk."""
    rng = np.random.default_rng(len(depths))
    B, H, g, D, page, width = len(depths), 2, 5, 128, 16, 25
    pool = jnp.asarray(rng.normal(
        size=(2, H, 1 + B * width, page, 2 * D)).astype(np.float32), dtype)
    table = 1 + rng.permutation(B * width).astype(np.int32).reshape(B, width)
    q = rng.normal(size=(B, H * g, D)).astype(np.float32)
    n_valid = np.asarray(depths, np.int32)
    got = np.asarray(pk.paged_decode_attention(
        jnp.asarray(q), pool, table, n_valid, 1))
    cast = lambda x: np.asarray(jnp.asarray(x, dtype).astype(jnp.float32))
    want = _dense_gqa(cast(q), cast(pool), table, n_valid, 1)
    # bfloat16 probabilities in the second product, as the kernel casts them
    assert np.abs(got - want).max() < (1e-5 if dtype == "float32" else 2e-2)
    assert (got[n_valid == 0] == 0).all()


def test_grouped_query_wide_rows_are_causal_within_the_call():
    """Q positions x g heads a K/V head: row r is position r // g."""
    rng = np.random.default_rng(9)
    B, H, g, D, page, width, Q = 2, 2, 3, 16, 4, 12, 4
    pool = jnp.asarray(rng.normal(
        size=(1, H, 1 + B * width, page, 2 * D)).astype(np.float32))
    table = 1 + np.arange(B * width, dtype=np.int32).reshape(B, width)
    q = rng.normal(size=(B, Q, H * g, D)).astype(np.float32)
    base = np.asarray([7, 30], np.int32)
    got = np.asarray(pk.paged_decode_attention_wide(jnp.asarray(q), pool,
                                                    table, base))
    for i in range(Q):
        want = _dense_gqa(q[:, i], np.asarray(pool), table, base + i + 1, 0)
        assert np.abs(got[:, i] - want).max() < 1e-5


def test_rotary_angles_turn_pairs_of_the_heads_halves():
    x = jnp.ones((1, 3, 1, 8), jnp.float32)
    pos = jnp.asarray([[0, 1, 4096]])
    out = np.asarray(falcon_h1._rope(x, pos, 1e11))
    assert np.allclose(out[0, 0], 1.0)                       # position 0
    inv = 1e11 ** (-np.arange(4) / 4)
    for t, p in ((1, 1.0), (2, 4096.0)):
        assert np.allclose(out[0, t, 0, :4], np.cos(p * inv) - np.sin(p * inv),
                           atol=1e-5)
        assert np.allclose(out[0, t, 0, 4:], np.cos(p * inv) + np.sin(p * inv),
                           atol=1e-5)


def test_parameters_are_counted_from_the_leaves(params):
    leaves = jax.tree_util.tree_leaves(params)
    assert falcon_h1.param_count(CFG) == sum(a.size for a in leaves)
    big = dataclasses.replace(CFG, vocab=261120, d_model=5120, n_layers=72,
                              n_heads=20, n_kv_heads=4, head_dim=128,
                              d_ff=21504, d_ssm=4096, ssm_heads=32,
                              d_state=256)
    assert falcon_h1.param_count(big) == PUBLISHED["published"]["parameters"]
