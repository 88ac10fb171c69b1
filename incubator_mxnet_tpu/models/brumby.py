"""Brumby: an attention-free decoder LM of gated power-retention layers
(manifestai/Brumby-14B-Base, model_type `brumby`), the serving side.

Qwen3's block (RMSNorm, a SwiGLU, an untied head) with attention replaced
by power retention of degree 2: the same query, key and value projections
(grouped, query head j reading K/V head j // (H / Hkv)), QK-norm and
rotary, and one gate a K/V head and token, log gamma = logsigmoid(x W_g +
b_g). Token t's query head j reads

    o_t = sum_{s<=t} w_ts v_s / (sum_{s<=t} w_ts + eps),
    w_ts = (q_t . k_s)^2 exp(G_t - G_s),   G the running sum of log gamma

(`benchmark/references/brumby.py` writes every equation out). With phi the
symmetric square of a head, w_ts = phi(q_t) . phi(k_s) exp(G_t - G_s), so
every layer carries, per slot and K/V head, a float32 matrix state and a
normaliser of fixed size and no K/V cache at all (`init_cache`):

    state  (L, slots, Hkv, Dh, F_pad)   S_t = gamma S_{t-1} + v (outer)
                                        phi(k), the value on the sublanes,
                                        F = Dh (Dh + 1) / 2 features on the
                                        lanes (8256 at Dh 128, in 8320)
    norm   (L, slots, Hkv, F_pad)       z_t = gamma z_{t-1} + phi(k)

and beside them `logits_prefill`, `logits_decode` (slots, V) float32: the
row each slot's first token, and its latest, was chosen from.

One body per part (`_retention_mix`, `_block`) serves the prefill and the
decode step alike; they differ in the `retain` they pass: the chunked form
from an empty state over a prompt (ops.pallas_kernels.power_retention_chunk)
and the in-place state step for a decode step (power_retention_step). The
states are carried through the layer loop and written in place by layer
index: donate the cache. The norms, rotary, SwiGLU and untied head are
models.falcon_h1's own bodies.

`BrumbyPrograms` is what serving.ServingEngine asks of a model; with no
kind of cache that grows with the context, it gets no page pool.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..ops.pallas_kernels import (power_retention_chunk, power_retention_step,
                                  retention_width)
from .falcon_h1 import _draw_leaf, _logits, _mlp, _rms, _rope
from .sambay import _mm

__all__ = ["BrumbyConfig", "BrumbyPrograms", "init_params", "init_cache",
           "decode_step", "prefill", "param_count", "branch_ratios"]


@dataclasses.dataclass
class BrumbyConfig:
    vocab: int = 512
    d_model: int = 64
    n_layers: int = 2
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 8
    d_ff: int = 128
    chunk: int = 128        # rows of a prompt per step of the chunked form
    rope_theta: float = 1e6
    eps: float = 1e-6       # RMSNorm
    max_len: int = 40960    # max_position_embeddings: rotary, no table
    dtype: str = "float32"  # weights, activations; the states are float32

    # the shared bodies (models.falcon_h1 _mlp, _logits) read these;
    # Qwen3's block has no multipliers
    mlp_multipliers = (1.0, 1.0)
    lm_head_multiplier = 1.0

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")
        if self.head_dim % 2:
            raise ValueError("rotary positions and the features pair the "
                             "head's lanes")

    @property
    def features(self):
        """Lanes a K/V head's state and normaliser take: F padded."""
        return retention_width(self.head_dim)

    def paged_programs(self):
        return BrumbyPrograms(self)


# ---------------------------------------------------------------------------
# parameters: every layer alike, stacked
# ---------------------------------------------------------------------------

def _leaves(cfg):
    """({leaf outside the layers: (shape, init)}, {leaf of a layer: ...})."""
    d, f, Dh = cfg.d_model, cfg.d_ff, cfg.head_dim
    dq, dkv = cfg.n_heads * Dh, cfg.n_kv_heads * Dh
    top = {"embed": ((cfg.vocab, d), ("normal", 1.0)),
           "head": ((cfg.vocab, d), ("normal", d ** -0.5)),
           "final_norm": ((d,), "ones")}
    layer = {
        "input_norm": ((d,), "ones"), "pre_ff_norm": ((d,), "ones"),
        "w_qkv": ((d, dq + 2 * dkv), "fan_in"), "wo": ((dq, d), "fan_in"),
        "q_norm": ((Dh,), "ones"), "k_norm": ((Dh,), "ones"),
        "w_gate": ((d, cfg.n_kv_heads), "fan_in"),
        "gate_bias": ((cfg.n_kv_heads,), "gate_bias"),
        "w_gate_up": ((d, 2 * f), "fan_in"), "w_down": ((f, d), "fan_in"),
    }
    return top, layer


def param_count(cfg):
    top, layer = _leaves(cfg)
    return (sum(int(np.prod(s)) for s, _ in top.values())
            + cfg.n_layers * sum(int(np.prod(s)) for s, _ in layer.values()))


# retention horizons 1 / (1 - gamma) the gate's bias is drawn over: with a
# plain N(0, 1) logit gamma is about 0.5 and the state forgets all in a few
# tokens, so that no check of logits could see it
HORIZONS = (64.0, 8192.0)


def _draw(key, shape, init, dtype):
    if init == "gate_bias":
        # logit log(h - 1) gives gamma = 1 - 1 / h: horizons h log-uniform
        lo, hi = (math.log(h) for h in HORIZONS)
        h = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        return jnp.log(h - 1.0).astype(dtype)
    return _draw_leaf(key, shape, init, dtype)


def init_params(cfg: BrumbyConfig, seed=0, device=None, calibrate=True):
    """The parameter tree for `seed`, made on the device, every leaf in
    cfg.dtype: {"embed", "head", "final_norm", "layers": {leaf: (L, ...)}}.

    Matrices N(0, 1/fan_in), norms' gains 1, the gate's bias so that the
    retention horizons 1 / (1 - gamma) spread log-uniform over HORIZONS.
    With `calibrate`, each layer's wo and w_down are then scaled so that
    its retention and MLP outputs are as large as the residual they join
    (`branch_ratios` = 1 on a seeded prompt of max_len / 2 rows, at most
    2048), as models.falcon_h1 does."""
    dtype = jnp.dtype(cfg.dtype)
    top, layer = _leaves(cfg)

    def make(key):
        keys = iter(jax.random.split(key, len(top) + len(layer)))
        out = {name: _draw(next(keys), shape, init, dtype)
               for name, (shape, init) in top.items()}
        out["layers"] = {
            name: lax.map(lambda k: _draw(k, shape, init, dtype),
                          jax.random.split(next(keys), cfg.n_layers))
            for name, (shape, init) in layer.items()}
        return out

    key = jax.random.key(seed, impl="rbg")
    with jax.default_device(device):
        params = jax.jit(make)(key)
        if not calibrate:
            return params
        rows = max(16, min(cfg.max_len // 2, 2048))
        tokens = jax.random.randint(jax.random.fold_in(key, 1), (1, rows), 1,
                                    cfg.vocab)
        ratios = branch_ratios(params, tokens, cfg)          # (L, 2)
        rescale = jax.jit(
            lambda w, r: (w.astype(jnp.float32) / r[:, None, None]
                          ).astype(dtype), donate_argnums=0)
        layers = dict(params["layers"])
        for i, name in enumerate(_BRANCH_ENDS):
            layers[name] = rescale(layers[name], ratios[:, i])
        return dict(params, layers=layers)


# ---------------------------------------------------------------------------
# the cache
# ---------------------------------------------------------------------------

def init_cache(cfg: BrumbyConfig, slots):
    """Every layer's retention state and normaliser of every slot (module
    docstring), zero, and the logits rows beside them."""
    L, Hkv, F = cfg.n_layers, cfg.n_kv_heads, cfg.features
    return {
        "state": jnp.zeros((L, slots, Hkv, cfg.head_dim, F), jnp.float32),
        "norm": jnp.zeros((L, slots, Hkv, F), jnp.float32),
        "logits_prefill": jnp.zeros((slots, cfg.vocab), jnp.float32),
        "logits_decode": jnp.zeros((slots, cfg.vocab), jnp.float32),
    }


# ---------------------------------------------------------------------------
# the layer bodies
# ---------------------------------------------------------------------------

def _retention_mix(lp, h, positions, retain, cfg):
    """Power retention over T tokens of S sequences. h (S, T, d) the normed
    input; `retain(q, k, v, log_gamma)` takes q (S, T, H, Dh) and k, v
    (S, T, Hkv, Dh), QK-normed and rotated, and the gate (S, T, Hkv), runs
    them through the states it owns and returns (o (S, T, H, Dh), whatever
    it hands on: the states it wrote). Returns (out, handed)."""
    S, T, _ = h.shape
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    # the barrier keeps the head split out of the matmul (PERF.md, PR 26)
    qkv = lax.optimization_barrier(_mm(h, lp["w_qkv"]))
    q, k, v = jnp.split(qkv, [H * Dh, (H + Hkv) * Dh], axis=-1)
    q = _rope(_rms(q.reshape(S, T, H, Dh), lp["q_norm"], cfg.eps),
              positions, cfg.rope_theta)
    k = _rope(_rms(k.reshape(S, T, Hkv, Dh), lp["k_norm"], cfg.eps),
              positions, cfg.rope_theta)
    log_gamma = jax.nn.log_sigmoid(
        _mm(h, lp["w_gate"]) + lp["gate_bias"].astype(jnp.float32))
    o, handed = retain(q, k, v.reshape(S, T, Hkv, Dh), log_gamma)
    return _mm(o.reshape(S, T, H * Dh), lp["wo"]), handed


def _block(lp, x, positions, retain, cfg):
    """x (S, T, d) float32 -> x + Retention(RMSNorm(x)), then
    + MLP(RMSNorm(.)). Returns (x, whatever `retain` hands on)."""
    a, handed = _retention_mix(lp, _rms(x, lp["input_norm"], cfg.eps),
                               positions, retain, cfg)
    x = x + a
    return x + _mlp(lp, x, cfg), handed


# what `init_params` scales, in the order `branch_ratios` reports
_BRANCH_ENDS = ("wo", "w_down")


def _chunked(cfg, n_real=None):
    """The prompt's retain: the chunked form from an empty state. With
    n_real (S,), rows t >= n_real are padding and carry k = 0 and log gamma
    = 0: they leave the state as it was, so the state handed on is the one
    after the last REAL row."""
    def retain(q, k, v, log_gamma):
        if n_real is not None:
            real = (jnp.arange(q.shape[1]) < n_real[:, None])[..., None]
            k = jnp.where(real[..., None], k, 0.0)
            log_gamma = jnp.where(real, log_gamma, 0.0)
        return power_retention_chunk(q, k, v, log_gamma, cfg.chunk,
                                     dtype=jnp.dtype(cfg.dtype))
    return retain


def branch_ratios(params, tokens, cfg):
    """(L, 2) float32: the root mean square of each layer's retention and
    MLP output over that of the residual it is added to, on the later half
    of the rows of one prompt `tokens` (1, T); each layer's input is the
    residual the layers before it leave with THEIR outputs divided by their
    ratios, so dividing every branch's last matrix by its ratio makes every
    ratio 1."""
    T = tokens.shape[1]
    positions = jnp.arange(T, dtype=jnp.int32)[None]
    chunked = _chunked(cfg)

    def size(a):
        return jnp.sqrt(jnp.mean(jnp.square(a[:, T // 2:])))

    @jax.jit
    def layer(lp, x):
        a, _ = _retention_mix(
            lp, _rms(x, lp["input_norm"], cfg.eps), positions,
            lambda *ops: (chunked(*ops)[0], None), cfg)
        ra = size(a) / size(x)
        x = x + a / ra
        f = _mlp(lp, x, cfg)
        rf = size(f) / size(x)
        return x + f / rf, jnp.stack([ra, rf])

    x = params["embed"][tokens].astype(jnp.float32)
    out = []
    for l in range(cfg.n_layers):
        x, r = layer(jax.tree_util.tree_map(lambda a: a[l], params["layers"]),
                     x)
        out.append(r)
    return jnp.stack(out)


# ---------------------------------------------------------------------------
# the two programs
# ---------------------------------------------------------------------------

def _stack(cfg, params, x, cache, positions, retain):
    """Layers 0 .. L-1 over x (S, T, d). `retain(q, k, v, log_gamma,
    state, norm, l)` runs layer l's retention on the states of every layer
    and returns (o, (state, norm)). Returns (x, state, norm)."""
    def block(carry, xs):
        x, state, norm = carry
        lp, l = xs
        x, (state, norm) = _block(
            lp, x, positions,
            lambda *ops: retain(*ops, state, norm, l), cfg)
        return (x, state, norm), None

    (x, state, norm), _ = lax.scan(
        block, (x, cache["state"], cache["norm"]),
        (params["layers"], jnp.arange(cfg.n_layers, dtype=jnp.int32)))
    return x, state, norm


def decode_step(params, cache, tokens, positions, cfg: BrumbyConfig):
    """One token for every decode slot, each at its own depth.

    tokens, positions (S,) int32 (tokens already taken in per slot); a
    dead slot's position is 0 (the engine's books: a live one's is at least
    its prompt's length), its row of logits garbage the caller discards and
    its states untouched. The cache is carried and written in place: donate
    it. Returns (logits (S, V), new cache); the cache keeps the logits too."""
    live = positions > 0
    dtype = jnp.dtype(cfg.dtype)

    def retain(q, k, v, log_gamma, state, norm, l):
        o, state, norm = power_retention_step(
            state, norm, l, live, q[:, 0], k[:, 0], v[:, 0],
            log_gamma[:, 0], dtype=dtype)
        return o[:, None], (state, norm)

    x = params["embed"][tokens].astype(jnp.float32)[:, None]
    x, state, norm = _stack(cfg, params, x, cache, positions[:, None], retain)
    logits = _logits(params, x[:, 0], cfg)
    return logits, dict(cache, state=state, norm=norm, logits_decode=logits)


def prefill(params, cache, prompt, true_len, slot, cfg: BrumbyConfig):
    """One prompt into slot `slot`, from an empty state.

    prompt (1, T_b) int32 padded to its bucket; true_len (1,) its real
    length; slot (1,) int32. Every layer's state and normaliser, as they
    are after row true_len - 1 (a padding row carries k = 0 and log gamma =
    0), go to the slot's rows of the cache. Returns (new cache, logits
    (1, V) of the last real token); the cache keeps them in the slot's row
    of logits_prefill."""
    _, T = prompt.shape
    chunked = _chunked(cfg, true_len)

    def retain(q, k, v, log_gamma, state, norm, l):
        o, last, z = chunked(q, k, v, log_gamma)
        return o, (lax.dynamic_update_slice(state, last[None],
                                            (l, slot[0], 0, 0, 0)),
                   lax.dynamic_update_slice(norm, z[None],
                                            (l, slot[0], 0, 0)))

    x = params["embed"][prompt].astype(jnp.float32)
    x, state, norm = _stack(cfg, params, x, cache,
                            jnp.arange(T, dtype=jnp.int32)[None], retain)
    last = jnp.maximum(true_len - 1, 0)
    logits = _logits(params, jnp.take_along_axis(
        x, last[:, None, None], axis=1)[:, 0], cfg)
    return dict(
        cache, state=state, norm=norm,
        logits_prefill=lax.dynamic_update_slice_in_dim(
            cache["logits_prefill"], logits, slot[0], axis=0)), logits


class BrumbyPrograms:
    """What serving.ServingEngine asks of a model (the seam's fourth
    implementer, after models.transformer.TransformerPrograms,
    models.sambay.SambaYPrograms and models.falcon_h1.FalconH1Programs).
    No layer keeps K/V, so no kind in `cache_kinds` grows and the engine
    builds no pool: slots alone bound admission."""

    # fixed-size state that a lever would have to snapshot and restore:
    # the engine refuses prefix cache, chunked prefill and speculation
    recurrent_state = True
    # while every slot decodes, the engine dispatches the step after the
    # one in flight before it reads that one (serving/engine.py
    # `_runs_ahead`); who reads the cache after a step() asks
    # `decode_in_flight`
    decode_ahead = True

    def __init__(self, cfg):
        self.cfg = cfg

    def init_cache(self, slots, num_pages, page_size):
        return init_cache(self.cfg, slots)

    def decode(self, params, cache, tokens, positions, table):
        return decode_step(params, cache, tokens, positions, self.cfg)

    def prefill(self, params, cache, prompt, true_len, slot):
        return prefill(params, cache, prompt, true_len, slot, self.cfg)

    def wide(self, *args):
        raise NotImplementedError(
            "no wide program for a model with recurrent state: rows past "
            "an accepted prefix would have to be rolled back out of the "
            "retention state (state snapshots)")

    def prefill_inputs(self, prompt, true_len, row, slot):
        return (prompt, np.asarray([true_len], np.int32),
                np.asarray([slot], np.int32))

    def prefill_shapes(self, bucket, table_width):
        return [(1, bucket), (1,), (1,)]

    def cache_kinds(self, page_size):
        """Host-side description of the cache, per slot, by kind."""
        cfg = self.cfg
        F = cfg.features
        return {
            "recurrent": {"layers": cfg.n_layers,
                          "state_bytes_per_slot": 4 * cfg.n_layers
                          * cfg.n_kv_heads * (cfg.head_dim + 1) * F},
            "logits": {"rows_per_slot": 2,
                       "bytes_per_slot": 2 * 4 * cfg.vocab},
        }

    def attended(self, n_valid):
        """No layer attends a cache of tokens."""
        return {}

    def fetched(self, n_valid, page_size):
        return {}
