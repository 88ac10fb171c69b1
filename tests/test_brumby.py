"""models.brumby through its programs and through ServingEngine, against the
plain reference (benchmark/references/brumby.py, and a NumPy copy of its
equations here), logits and not tokens, at a small size: 2 layers of power
retention (4 query heads over 2 K/V heads of 8, so phi is 36 features wide,
in 40 lanes), d 64, vocab 512. And the kernels it added, each against its
plain form."""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from benchmark.harness import loader
from incubator_mxnet_tpu.models import brumby
from incubator_mxnet_tpu.ops import pallas_kernels as pk
from incubator_mxnet_tpu.serving import ServingEngine

REFERENCE = loader.load_callable("references", "brumby.py:logits")
PUBLISHED = loader.load_json("configs", "brumby_14b")
CONFIG = {"num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
          "rms_norm_eps": 1e-6, "rope_theta": PUBLISHED["rope_theta"]}
CFG = brumby.BrumbyConfig(vocab=512, d_model=64, n_layers=2, n_heads=4,
                          n_kv_heads=2, head_dim=8, d_ff=128, chunk=8,
                          max_len=64)
MAX = 64
# float32 on both sides; the logits' own scale is ~1
TOL = 1e-4


@pytest.fixture(scope="module")
def params():
    return brumby.init_params(CFG, 3)


# -- the plain forms ----------------------------------------------------------


def _phi(u):
    """The symmetric square of u (..., D) as a list of its pairs a <= b,
    c_ab u_a u_b: the equations' own order, not the kernels' layout."""
    D = u.shape[-1]
    return np.stack([u[..., a] * u[..., b] * (1.0 if a == b else np.sqrt(2))
                     for a in range(D) for b in range(a, D)], -1)


def _retention_rows(q, k, v, lg, eps=1e-6):
    """The quadratic form in NumPy float64: q (T, H, D), k, v (T, Hkv, D),
    lg (T, Hkv) log gamma. Returns (o (T, H, D), the state (Hkv, D, F) and
    normaliser (Hkv, F) after the last row, in the pairs' order)."""
    T, H, D = q.shape
    g = H // k.shape[1]
    G = np.cumsum(lg, 0)
    o = np.zeros((T, H, D))
    for t in range(T):
        for j in range(H):
            w = ((k[: t + 1, j // g] @ q[t, j]) ** 2
                 * np.exp(G[t, j // g] - G[: t + 1, j // g]))
            o[t, j] = w @ v[: t + 1, j // g] / (w.sum() + eps)
    decay = np.exp(G[-1] - G)                                 # (T, Hkv)
    pk_ = _phi(k)                                             # (T, Hkv, F)
    state = np.einsum("th,thd,thf->hdf", decay, v, pk_)
    return o, state, np.einsum("th,thf->hf", decay, pk_)


def _numpy_logits(params, tokens, cfg):
    """benchmark/references/brumby.py's equations once more, in NumPy
    float64 and row by row."""
    P = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), params)
    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def rms(x, g):
        return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + cfg.eps) * g

    def rope(x):
        half = D // 2
        ang = (np.arange(len(x))[:, None, None]
               * cfg.rope_theta ** (-np.arange(half) / half))
        x1, x2 = x[..., :half], x[..., half:]
        return np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                               x2 * np.cos(ang) + x1 * np.sin(ang)], -1)

    x = P["embed"][tokens]
    T = len(tokens)
    for l in range(cfg.n_layers):
        lp = {k: a[l] for k, a in P["layers"].items()}
        h = rms(x, lp["input_norm"])
        q, k, v = np.split(h @ lp["w_qkv"], [H * D, (H + Hkv) * D], -1)
        q = rope(rms(q.reshape(T, H, D), lp["q_norm"]))
        k = rope(rms(k.reshape(T, Hkv, D), lp["k_norm"]))
        z = h @ lp["w_gate"] + lp["gate_bias"]
        lg = -np.logaddexp(0.0, -z)
        o, _, _ = _retention_rows(q, k, v.reshape(T, Hkv, D), lg)
        x = x + o.reshape(T, H * D) @ lp["wo"]
        gu = rms(x, lp["pre_ff_norm"]) @ lp["w_gate_up"]
        gate, up = np.split(gu, 2, -1)
        x = x + (gate / (1 + np.exp(-gate)) * up) @ lp["w_down"]
    return rms(x, P["final_norm"]) @ P["head"].T


def test_the_features_square_the_dot_product():
    rng = np.random.default_rng(0)
    q, k = rng.normal(size=(2, 5, 8))
    fq, fk = np.asarray(pk.power_features(jnp.asarray(q))), np.asarray(
        pk.power_features(jnp.asarray(k)))
    # 4 + 1 blocks of 8 lanes, the last half of the last block empty: the
    # 36 pairs of 8 lanes each once
    assert fq.shape == (5, pk.retention_width(8)) == (5, 40)
    assert (fq[:, 36:] == 0).all()
    assert np.allclose((fq * fk).sum(-1), (q * k).sum(-1) ** 2, rtol=1e-5)
    # the same multiset of features as the pairs' own order
    assert np.allclose(np.sort(fq[:, :36], -1), np.sort(_phi(q), -1),
                       rtol=1e-5)


def test_the_benchmark_reference_is_the_plain_equations(params):
    tokens = np.random.default_rng(1).integers(1, CFG.vocab, 24)
    got = np.asarray(REFERENCE(params, jnp.asarray(tokens), CONFIG))
    assert np.abs(got - _numpy_logits(params, tokens, CFG)).max() < TOL


def _operands(rng, S, T, H=4, Hkv=2, D=8):
    lg = np.log(1 / (1 + np.exp(-rng.normal(3, 1, (S, T, Hkv)))))
    return (rng.normal(size=(S, T, H, D)).astype(np.float32),
            rng.normal(size=(S, T, Hkv, D)).astype(np.float32),
            rng.normal(size=(S, T, Hkv, D)).astype(np.float32),
            lg.astype(np.float32))


def _in_pairs(features):
    """A kernel's feature lanes (..., 40) in the pairs' order (..., 36):
    products of distinct primes are distinct, so each pair's lane is the
    one its product lands on."""
    primes = np.asarray([2, 3, 5, 7, 11, 13, 17, 19], np.float32)
    lanes = np.asarray(pk.power_features(jnp.asarray(primes)))
    where = [int(np.argmin(np.abs(lanes - p))) for p in _phi(primes)]
    return features[..., where]


@pytest.mark.parametrize("T,chunk,real", [
    (40, 8, (29, 40)),     # true lengths inside a chunk and at its end
    (20, 8, (20, 7)),      # not a multiple of the chunk: one chunk
], ids=["mid_chunk", "one_chunk"])
def test_the_chunked_form_is_the_quadratic_form(T, chunk, real):
    rng = np.random.default_rng(T)
    q, k, v, lg = _operands(rng, 2, T)
    real = np.asarray(real)
    pad = np.arange(T)[None, :] >= real[:, None]
    k[pad], lg[pad] = 0.0, 0.0        # what a padding row carries
    o, state, norm = pk.power_retention_chunk(
        *map(jnp.asarray, (q, k, v, lg)), chunk)
    for s, n in enumerate(real):
        want_o, want_s, want_z = _retention_rows(q[s, :n], k[s, :n],
                                                 v[s, :n], lg[s, :n])
        assert np.abs(np.asarray(o)[s, :n] - want_o).max() < 2e-5
        # the state is the one after the last REAL row
        assert np.abs(_in_pairs(np.asarray(state)[s]) - want_s).max() < 2e-4
        assert np.abs(_in_pairs(np.asarray(norm)[s]) - want_z).max() < 2e-4


def test_the_retention_step_is_one_recurrent_step_in_place():
    rng = np.random.default_rng(4)
    S, L, Hkv, D = 5, 3, 2, 8
    F = pk.retention_width(D)
    q, k, v, lg = (a[:, 0] for a in _operands(rng, S, 1))
    state = rng.normal(size=(L, S, Hkv, D, F)).astype(np.float32)
    norm = np.abs(rng.normal(size=(L, S, Hkv, F))).astype(np.float32)
    live = np.asarray([False, True, True, False, True])
    o, new_s, new_z = map(np.asarray, pk.power_retention_step(
        jnp.asarray(state), jnp.asarray(norm), jnp.asarray(1, jnp.int32),
        jnp.asarray(live), *map(jnp.asarray, (q, k, v, lg))))
    fk = np.asarray(pk.power_features(jnp.asarray(k)), np.float64)
    fq = np.asarray(pk.power_features(jnp.asarray(q)), np.float64)
    gamma = np.exp(lg)[..., None]
    want_s = gamma[..., None] * state[1] + v[..., None] * fk[:, :, None]
    want_z = gamma * norm[1] + fk
    g = fq.reshape(S, Hkv, 2, F)
    want_o = (np.einsum("shdf,shjf->shjd", want_s, g)
              / (np.einsum("shf,shjf->shj", want_z, g)[..., None] + 1e-6)
              ).reshape(S, 4, D)
    assert np.abs(o[live] - want_o[live]).max() < 2e-5
    assert np.abs(new_s[1][live] - want_s[live]).max() < 2e-5
    assert np.abs(new_z[1][live] - want_z[live]).max() < 2e-5
    # a dead slot's state is what it was, bit for bit, and so is every
    # other layer's; its o is zeros
    assert (new_s[1][~live] == state[1][~live]).all()
    assert (new_z[1][~live] == norm[1][~live]).all()
    assert (new_s[[0, 2]] == state[[0, 2]]).all()
    assert (o[~live] == 0).all()
    # all slots dead: nothing moves
    _, same_s, same_z = pk.power_retention_step(
        jnp.asarray(state), jnp.asarray(norm), 2, jnp.zeros((S,), bool),
        *map(jnp.asarray, (q, k, v, lg)))
    assert (np.asarray(same_s) == state).all()
    assert (np.asarray(same_z) == norm).all()


# -- the programs -------------------------------------------------------------


def _reference_rows(params, tokens, first, count):
    toks = np.zeros((MAX,), np.int32)  # one length: one compile
    toks[: len(tokens)] = tokens
    return np.asarray(REFERENCE(params, jnp.asarray(toks), CONFIG)
                      )[first: first + count]


def _serve(params, prompts, buckets, steps, cfg=CFG):
    """Prefills each prompt into its slot, then `steps` greedy decode steps
    for all of them in one batch beside a dead slot (slot 0). Returns per
    prompt (tokens, logits of the last prompt row and of every decoded
    row)."""
    prog = cfg.paged_programs()
    prefill, decode = jax.jit(prog.prefill), jax.jit(prog.decode)
    S = len(prompts) + 1
    cache = brumby.init_cache(cfg, S)
    positions = np.zeros((S,), np.int32)
    nxt = np.zeros((S,), np.int32)
    table = jnp.zeros((S, 0), jnp.int32)
    seqs, rows = {}, {}
    for s, (prompt, bucket) in enumerate(zip(prompts, buckets), start=1):
        padded = np.zeros((1, bucket), np.int32)
        padded[0, : prompt.size] = prompt
        cache, lg = prefill(params, cache, jnp.asarray(padded),
                            jnp.asarray([prompt.size], jnp.int32),
                            jnp.asarray([s], jnp.int32))
        positions[s] = prompt.size
        seqs[s], rows[s] = list(prompt), [np.asarray(lg[0])]
        nxt[s] = int(np.argmax(lg[0]))
    for _ in range(steps):
        lg, cache = decode(params, cache, jnp.asarray(nxt),
                           jnp.asarray(positions), table)
        lg = np.asarray(lg)
        for s in seqs:
            seqs[s].append(int(nxt[s]))
            positions[s] += 1
            rows[s].append(lg[s])
            nxt[s] = int(np.argmax(lg[s]))
    return [(np.asarray(seqs[s], np.int32), np.stack(rows[s])) for s in seqs]


def _worst(params, prompts, buckets, steps, cfg=CFG):
    worst = 0.0
    for prompt, (tokens, got) in zip(prompts, _serve(
            params, prompts, buckets, steps, cfg)):
        want = _reference_rows(params, tokens, prompt.size - 1, steps + 1)
        worst = max(worst, float(np.abs(got - want).max()))
    return worst


@pytest.mark.parametrize("lengths,buckets,steps", [
    # each under its bucket's length, one inside a chunk of 8 and one
    # across two: the states are the ones after the last real row
    ((5, 13, 11), (8, 16, 16), 4),
    # true length == bucket; three depths in one decode batch; 30 steps
    ((8, 16, 3), (8, 16, 8), 30),
], ids=["under_bucket", "at_bucket"])
def test_programs_match_the_reference(params, lengths, buckets, steps):
    rng = np.random.default_rng(sum(lengths))
    prompts = [rng.integers(1, CFG.vocab, n).astype(np.int32)
               for n in lengths]
    assert _worst(params, prompts, buckets, steps) < TOL


def test_a_state_rounded_to_bfloat16_at_every_step_fails(params,
                                                         monkeypatch):
    """What the chip's check of logits cannot tell from bfloat16
    activations, this float32 comparison sees: the state and normaliser
    rounded to bfloat16 by every program, prefill and decode step alike."""
    def rounded(fn):
        def wrapped(*a, **k):
            *out, state, norm = fn(*a, **k)
            return (*out, jax.lax.reduce_precision(state, 8, 7),
                    jax.lax.reduce_precision(norm, 8, 7))
        return wrapped

    monkeypatch.setattr(brumby, "power_retention_step",
                        rounded(pk.power_retention_step))
    monkeypatch.setattr(brumby, "power_retention_chunk",
                        rounded(pk.power_retention_chunk))
    rng = np.random.default_rng(27)
    prompts = [rng.integers(1, CFG.vocab, n).astype(np.int32)
               for n in (8, 16, 3)]
    assert _worst(params, prompts, (8, 16, 8), 30) > 10 * TOL


def test_every_branch_is_as_large_as_the_residual_it_joins(params):
    """init_params scales each layer's wo and w_down so that on another
    prompt every layer's retention and MLP output is within a factor of
    two of the residual it joins."""
    fresh = jax.random.randint(jax.random.key(5), (1, 48), 1, CFG.vocab)
    ratios = np.asarray(brumby.branch_ratios(params, fresh, CFG))
    assert ratios.shape == (CFG.n_layers, 2)
    assert (ratios > 0.5).all() and (ratios < 2.0).all(), ratios


def test_the_gate_keeps_the_horizons_it_was_drawn_for(params):
    gamma = 1 / (1 + np.exp(-np.asarray(params["layers"]["gate_bias"])))
    horizon = 1 / (1 - gamma)
    assert (horizon > 63).all() and (horizon < 8193).all()


def test_parameters_are_counted_from_the_leaves(params):
    leaves = jax.tree_util.tree_leaves(params)
    assert brumby.param_count(CFG) == sum(a.size for a in leaves)
    big = dataclasses.replace(CFG, vocab=151936, d_model=5120, n_layers=40,
                              n_heads=40, n_kv_heads=8, head_dim=128,
                              d_ff=17408)
    # the published count from the keys, and the gate's bias (8 a layer)
    assert brumby.param_count(big) == (PUBLISHED["published"]["parameters"]
                                       + 8 * 40)
    assert brumby.param_count(dataclasses.replace(big, n_layers=8)) == (
        PUBLISHED["parameters"])


# -- through the engine -------------------------------------------------------


def _engine(params, **kw):
    return ServingEngine(params, CFG, slots=4, page_size=4, max_len=MAX,
                         **{"prefix_cache": 0, "prefill_chunk": 0,
                            "spec_ngram": 0, **kw})


def test_the_engine_builds_no_pool_and_admits_up_to_its_slots(params):
    """A model whose cache has no kind that grows gets no page pool: the
    allocator holds the null page alone, the tables are empty, and six
    requests of the longest length the engine takes fill its four slots in
    one step, whatever their lengths."""
    eng = _engine(params)
    assert eng.allocator.capacity == 0 and eng.table_width == 0
    assert set(eng.paged) == {"state", "norm", "logits_prefill",
                              "logits_decode"}
    rng = np.random.default_rng(6)
    for n in (40, 30, 50, 20, 33, 9):
        eng.submit(rng.integers(1, CFG.vocab, n), MAX - n)
    eng.step()
    assert eng.slots_in_use == 4 and eng.queue_depth == 2
    stats = eng.cache_stats()
    assert stats["pool"] == {"pages_live": 0, "pages_reserved": 0,
                             "capacity": 0}
    assert not any(k.get("grows") for k in stats["kinds"].values())
    assert stats["attended_tokens"] == stats["fetched_tokens"] == {}
    assert eng.debug_snapshot()["pages"]["occupancy"] == 0.0
    eng.run()
    assert len(eng.results()) == 6


def test_the_engine_serves_the_reference_logits_one_step_ahead(params):
    """Five requests over four slots: four decode together with a step in
    flight, the fifth alone; every token's row, prefill and 30 decode steps,
    is the reference's at float32 tolerance."""
    served_rows = loader.load_callable(
        "jobs", "serve_parallel_hybrid.py:served_rows")
    rng = np.random.default_rng(13)
    prompts = [rng.integers(1, CFG.vocab, n).astype(np.int32)
               for n in (15, 9, 12, 5, 20)]
    new = 31
    eng = _engine(params)
    served = served_rows(eng, prompts, new, set(range(new)))
    assert eng.cache_stats()["decode_steps_ahead"] > 0
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
