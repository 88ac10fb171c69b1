"""Falcon-H1: a parallel-hybrid decoder LM (tiiuae/Falcon-H1, model_type
`falcon_h1`), the serving side.

Every block runs grouped-query attention with rotary positions and a
Mamba-2 mixer SIDE BY SIDE on one normed input and adds both to the
residual, then a SwiGLU; RMSNorm throughout, an untied head, and the
family's fixed multipliers (muP) on the embedding, on each branch's input
and output, on the keys, on the five parts of the mixer's projection, on the
gate and on the logits:

    x0 = E[token] * embedding_multiplier
    h  = RMSNorm(x);  x = x + Attn(h) + Mamba2(h);  x = x + MLP(RMSNorm(x))
    logits = W_head RMSNorm(x) * lm_head_multiplier

(`benchmark/references/falcon_h1.py` writes every equation out.) Two kinds
of per-slot state live in EVERY layer (`init_cache`):

    kv         (L, Hkv, pages, page, 2*Dh)   grows with the context, paged,
               K|V fused per row; the allocator's pages index it
    conv, ssm  (L, slots, d_conv - 1, d_ssm + 2 G N), (L, slots, Hs, P, N)
               float32, fixed size: the convolution's tail and the scan
               state, 4 MB a slot and layer at P = 128, N = 256

and beside them `logits_prefill`, `logits_decode` (slots, V) float32: the
row each slot's first token, and its latest, was chosen from (whoever holds
the served model to a reference reads them there).

One body per part (`_attn_mix`, `_mamba2_mix`, `_block`) is used by the
prefill and the decode step alike; they differ in the `attend` and `recur`
they pass: dense blocked attention and the chunked scan over a prompt
(ops.pallas_kernels.ssd_chunk_scan), the paged kernel and the in-place
state update for a decode step (paged_decode_attention, ssd_state_update).
Pool and scan states are carried through the layer loop and written in
place by layer index: donate the cache.

`FalconH1Programs` is what serving.ServingEngine asks of a model.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..ops.pallas_kernels import (paged_decode_attention, paged_kv_write,
                                  paged_walk_tokens, paged_write_plan,
                                  ssd_chunk_scan, ssd_state_update)
from .sambay import _draw, _mm

__all__ = ["FalconH1Config", "FalconH1Programs", "init_params", "init_cache",
           "decode_step_paged", "prefill_paged", "param_count",
           "branch_ratios"]

_NEG_INF = -1e30


@dataclasses.dataclass
class FalconH1Config:
    vocab: int = 128
    d_model: int = 64
    n_layers: int = 2
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    d_ff: int = 128
    d_ssm: int = 64         # Mamba-2's inner width, ssm_heads * its head size
    ssm_heads: int = 4
    d_state: int = 16
    n_groups: int = 2       # B and C are shared by ssm_heads / n_groups heads
    d_conv: int = 4
    chunk: int = 128        # rows of a prompt per step of the chunked scan
    rope_theta: float = 1e11
    eps: float = 1e-5
    max_len: int = 262144   # max_position_embeddings: rotary, no table
    dtype: str = "float32"  # weights, K/V pool; scan state is float32
    prefill_block: int = 512  # query rows per block of the prompt's attention
    # the family's fixed multipliers
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: tuple = (1.0, 1.0, 1.0, 1.0, 1.0)  # z, x, B, C, dt
    mlp_multipliers: tuple = (1.0, 1.0)                 # gate, down

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")
        if self.ssm_heads % self.n_groups or self.d_ssm % self.ssm_heads:
            raise ValueError("d_ssm splits into ssm_heads, and those into "
                             "n_groups")
        if self.head_dim % 2:
            raise ValueError("rotary positions pair the head's halves")

    @property
    def ssm_head_dim(self):
        return self.d_ssm // self.ssm_heads

    @property
    def d_xbc(self):  # what the convolution runs over: x, B and C
        return self.d_ssm + 2 * self.n_groups * self.d_state

    def paged_programs(self):
        return FalconH1Programs(self)


# ---------------------------------------------------------------------------
# parameters: every layer alike, stacked
# ---------------------------------------------------------------------------

def _leaves(cfg):
    """({leaf outside the layers: (shape, init)}, {leaf of a layer: ...})."""
    d, f, K = cfg.d_model, cfg.d_ff, cfg.d_conv
    dq, dkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    # the rows a token id picks and the rows the logits contract: N(0, 1)
    # under embedding_multiplier, N(0, 1/d) under the final norm
    top = {"embed": ((cfg.vocab, d), ("normal", 1.0)),
           "head": ((cfg.vocab, d), ("normal", d ** -0.5)),
           "final_norm": ((d,), "ones")}
    layer = {
        "input_norm": ((d,), "ones"), "pre_ff_norm": ((d,), "ones"),
        "w_qkv": ((d, dq + 2 * dkv), "fan_in"), "wo": ((dq, d), "fan_in"),
        # [z | x | B | C | dt]
        "w_in": ((d, cfg.d_ssm + cfg.d_xbc + cfg.ssm_heads), "fan_in"),
        "conv_w": ((K, cfg.d_xbc), ("uniform", 1 / math.sqrt(K))),
        "conv_b": ((cfg.d_xbc,), ("uniform", 1 / math.sqrt(K))),
        "dt_bias": ((cfg.ssm_heads,), "dt_bias"),
        "A_log": ((cfg.ssm_heads,), "a_log_heads"),
        "D": ((cfg.ssm_heads,), "ones"),
        "ssm_norm": ((cfg.d_ssm,), "ones"),
        "w_out": ((cfg.d_ssm, d), "fan_in"),
        "w_gate_up": ((d, 2 * f), "fan_in"), "w_down": ((f, d), "fan_in"),
    }
    return top, layer


def param_count(cfg):
    top, layer = _leaves(cfg)
    return (sum(int(np.prod(s)) for s, _ in top.values())
            + cfg.n_layers * sum(int(np.prod(s)) for s, _ in layer.values()))


# rows of a leaf drawn at a time: the generator's 32-bit words for a whole
# (vocab, d) or (d, 2 f) leaf of the 34B would be 5 GB beside the weights
_DRAW_ELEMS = 1 << 27


def _draw_leaf(key, shape, init, dtype):
    if init == "a_log_heads":  # A = -U[1, 16], one scalar a head
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
                       ).astype(dtype)
    rows = shape[0]
    parts = max(1, min(rows, int(np.prod(shape)) // _DRAW_ELEMS))
    while rows % parts:
        parts -= 1
    if parts == 1:
        return _draw(key, shape, init, ()).astype(dtype)
    # fan_in draws scale by the whole leaf's rows, not a part's
    scale = math.sqrt(rows // parts / rows) if init == "fan_in" else 1.0
    part = (rows // parts,) + shape[1:]
    out = lax.map(lambda k: (_draw(k, part, init, ()) * scale).astype(dtype),
                  jax.random.split(key, parts))
    return out.reshape(shape)


def init_params(cfg: FalconH1Config, seed=0, device=None, calibrate=True):
    """The parameter tree for `seed`, made on the device, every leaf in
    cfg.dtype: {"embed", "head", "final_norm", "layers": {leaf: (L, ...)}}.

    Matrices N(0, 1/fan_in); the recurrence's leaves as the Mamba family
    initialises them (A_log = log U[1, 16], dt_bias the inverse softplus
    of steps log-uniform in [1e-3, 1e-1], D = 1). With `calibrate`, each
    branch's last matrix (wo, w_out, w_down) of each layer is then scaled
    so that the branch's output is as large as the residual it joins
    (`branch_ratios` = 1 on a seeded prompt of max_len / 2 rows, at most
    2048): drawn as they are, the family's multipliers (made for trained
    weights) leave every branch a per-mille of the residual, and a model
    whose logits are its embedding's says nothing about its layers."""
    dtype = jnp.dtype(cfg.dtype)
    top, layer = _leaves(cfg)

    def make(key):
        keys = iter(jax.random.split(key, len(top) + len(layer)))
        out = {name: _draw_leaf(next(keys), shape, init, dtype)
               for name, (shape, init) in top.items()}
        out["layers"] = {
            name: lax.map(lambda k: _draw_leaf(k, shape, init, dtype),
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
        ratios = branch_ratios(params, tokens, cfg)          # (L, 3)
        # one leaf at a time, in place: a float32 copy of the largest is
        # 2.6 GB that the chip does not have beside the 34B's weights
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

def init_cache(cfg: FalconH1Config, slots, num_pages, page_size):
    """The two kinds of per-slot state of every layer (module docstring).
    Page 0 of the pool is the null page: dead slots read and write it."""
    L = cfg.n_layers
    return {
        "kv": jnp.zeros((L, cfg.n_kv_heads, num_pages, page_size,
                         2 * cfg.head_dim), cfg.dtype),
        "conv": jnp.zeros((L, slots, cfg.d_conv - 1, cfg.d_xbc),
                          jnp.float32),
        "ssm": jnp.zeros((L, slots, cfg.ssm_heads, cfg.ssm_head_dim,
                          cfg.d_state), jnp.float32),
        "logits_prefill": jnp.zeros((slots, cfg.vocab), jnp.float32),
        "logits_decode": jnp.zeros((slots, cfg.vocab), jnp.float32),
    }


# ---------------------------------------------------------------------------
# the layer bodies
# ---------------------------------------------------------------------------

def _rms(x, g, eps):
    return (x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
            * g.astype(x.dtype))


def _rope(x, positions, theta):
    """x (S, T, H, Dh) float32 at `positions` (S, T): the head's two
    halves rotated as pairs (x1, x2) -> (x1 cos - x2 sin, x2 cos + x1 sin),
    angle position * theta^(-i / (Dh/2)) for pair i, in float32."""
    half = x.shape[-1] // 2
    inv = jnp.exp(jnp.arange(half, dtype=jnp.float32)
                  * (-math.log(theta) / half))
    ang = positions.astype(jnp.float32)[..., None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attn_mix(lp, h, positions, attend, cfg):
    """Grouped-query attention with rotary positions. `attend(q, k, v)`
    takes q (S, T, H, Dh) and this layer's new k, v (S, T, Hkv, Dh),
    rotated and scaled, stores them and returns ((S, T, H, Dh): query
    head j over K/V head j // (H / Hkv), whatever it hands on: the pool
    it wrote). Returns (out, handed)."""
    S, T, _ = h.shape
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    # the barrier keeps the head split out of the matmul (PERF.md, PR 26)
    qkv = lax.optimization_barrier(
        _mm(h * cfg.attention_in_multiplier, lp["w_qkv"]))
    q, k, v = jnp.split(qkv, [H * Dh, (H + Hkv) * Dh], axis=-1)
    q = _rope(q.reshape(S, T, H, Dh), positions, cfg.rope_theta)
    k = _rope(k.reshape(S, T, Hkv, Dh) * cfg.key_multiplier, positions,
              cfg.rope_theta)
    a, handed = attend(q, k, v.reshape(S, T, Hkv, Dh))
    return (_mm(a.reshape(S, T, H * Dh), lp["wo"])
            * cfg.attention_out_multiplier), handed


def _mup(cfg):
    """ssm_multipliers spread over the projection's [z | x | B | C | dt]."""
    gn = cfg.n_groups * cfg.d_state
    widths = (cfg.d_ssm, cfg.d_ssm, gn, gn, cfg.ssm_heads)
    return jnp.asarray(np.repeat(np.asarray(cfg.ssm_multipliers, np.float32),
                                 widths))


def _mamba2_mix(lp, h, conv, recur, n_real, cfg):
    """Mamba-2 over T tokens of S sequences from the convolution's tail.

    h (S, T, d); conv (S, K-1, d_xbc) the last K-1 inputs of the
    convolution; n_real (S,) or None: rows t >= n_real are padding and
    leave the state as it was; `recur(x, dt, A, B, C)` runs the
    recurrence from the scan state it owns, x (S, T, Hs, P), dt (S, T, Hs)
    after the softplus (0 on padding), A (Hs,), B, C (S, T, G, N), and
    returns (y = S_t C_t (S, T, Hs, P), whatever it hands on: the states
    it wrote). Returns (out (S, T, d), (the new tail, as it is after row
    n_real - 1, handed))."""
    S, T, _ = h.shape
    f32 = jnp.float32
    Hs, P, K = cfg.ssm_heads, cfg.ssm_head_dim, cfg.d_conv
    G, N = cfg.n_groups, cfg.d_state
    p = _mm(h * cfg.ssm_in_multiplier, lp["w_in"]) * _mup(cfg)
    z, xbc, dt = jnp.split(p, [cfg.d_ssm, cfg.d_ssm + cfg.d_xbc], axis=-1)
    ext = jnp.concatenate([conv, xbc], axis=1)              # (S, T+K-1, .)
    w = lp["conv_w"].astype(f32)
    xbc = jax.nn.silu(sum(ext[:, k:k + T] * w[k] for k in range(K))
                      + lp["conv_b"].astype(f32))
    x, B, C = jnp.split(xbc, [cfg.d_ssm, cfg.d_ssm + G * N], axis=-1)
    x = x.reshape(S, T, Hs, P)
    dt = jax.nn.softplus(dt + lp["dt_bias"].astype(f32))    # (S, T, Hs)
    if n_real is None:
        n_real = jnp.full((S,), T, jnp.int32)
    else:
        real = jnp.arange(T)[None, :] < n_real[:, None]
        dt = jnp.where(real[..., None], dt, 0.0)  # exp(0 A) = 1, 0 B = 0
    y, handed = recur(x, dt, -jnp.exp(lp["A_log"].astype(f32)),
                      B.reshape(S, T, G, N), C.reshape(S, T, G, N))
    y = y + lp["D"].astype(f32)[:, None] * x
    # gate, then RMSNorm over each group's channels (norm_before_gate false)
    y = (y.reshape(S, T, cfg.d_ssm) * jax.nn.silu(z)).reshape(S, T, G, -1)
    y = y * lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True) + cfg.eps)
    y = y.reshape(S, T, cfg.d_ssm) * lp["ssm_norm"].astype(f32)
    # rows n_real .. n_real+K-2 of ext are inputs n_real-K+1 .. n_real-1
    tail = n_real[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None]
    conv = jnp.take_along_axis(ext, tail[..., None], axis=1)
    return _mm(y, lp["w_out"]) * cfg.ssm_out_multiplier, (conv, handed)


def _mlp(lp, x, cfg):
    g, u = jnp.split(_mm(_rms(x, lp["pre_ff_norm"], cfg.eps),
                         lp["w_gate_up"]), 2, axis=-1)
    return (_mm(jax.nn.silu(g * cfg.mlp_multipliers[0]) * u, lp["w_down"])
            * cfg.mlp_multipliers[1])


def _mixers(lp, x, attn, mamba, cfg):
    """The two branches on one normed input: ((a, m), (attn's, mamba's
    handed))."""
    h = _rms(x, lp["input_norm"], cfg.eps)
    a, kept = attn(h)
    m, state = mamba(h)
    return (a, m), (kept, state)


def _block(lp, x, attn, mamba, cfg):
    """x (S, T, d) float32 -> x + Attn(h) + Mamba2(h), h = RMSNorm(x), then
    + MLP(RMSNorm(.)). `attn(h)` and `mamba(h)` return (their output,
    whatever else they hand on); so does this."""
    (a, m), handed = _mixers(lp, x, attn, mamba, cfg)
    x = x + a + m
    return x + _mlp(lp, x, cfg), handed


def _prompt_attention(q, k, v, block):
    """Causal grouped-query attention of one prompt over itself, q
    (T, H, Dh), k, v (T, Hkv, Dh), in blocks of `block` query rows so that
    no (T, T) score exists per head. Returns (T, H, Dh) float32."""
    T, H, Dh = q.shape
    Hkv = k.shape[1]
    block = min(block, T)
    if T % block:
        raise ValueError(f"{T} rows do not split into blocks of {block}")
    q = q.reshape(T, Hkv, H // Hkv, Dh).astype(k.dtype)
    k_pos = jnp.arange(T, dtype=jnp.int32)

    def one(i):
        qb = lax.dynamic_slice_in_dim(q, i * block, block, axis=0)
        s = jnp.einsum("qhgd,khd->hgqk", qb, k,
                       preferred_element_type=jnp.float32) / math.sqrt(Dh)
        q_pos = i * block + jnp.arange(block, dtype=jnp.int32)
        p = jax.nn.softmax(
            jnp.where(q_pos[:, None] >= k_pos[None, :], s, _NEG_INF), -1)
        return jnp.einsum("hgqk,khd->qhgd", p.astype(v.dtype), v,
                          preferred_element_type=jnp.float32)

    out = lax.map(one, jnp.arange(T // block, dtype=jnp.int32))
    return out.reshape(T, H, Dh)


# what `init_params` scales, in the order `branch_ratios` reports
_BRANCH_ENDS = ("wo", "w_out", "w_down")


def branch_ratios(params, tokens, cfg):
    """(L, 3) float32: the root mean square of each layer's attention,
    Mamba and MLP output over that of the residual it is added to, on the
    later half of the rows of one prompt `tokens` (1, T); each layer's
    input is the residual the layers before it leave with THEIR outputs
    divided by their ratios, so dividing every branch's last matrix by its
    ratio makes every ratio 1."""
    T = tokens.shape[1]
    positions = jnp.arange(T, dtype=jnp.int32)[None]
    dtype = jnp.dtype(cfg.dtype)

    def size(a):
        return jnp.sqrt(jnp.mean(jnp.square(a[:, T // 2:])))

    def attend(q, k, v):
        return _prompt_attention(q[0], k[0].astype(dtype), v[0].astype(dtype),
                                 cfg.prefill_block)[None], None

    def recur(*operands):
        return ssd_chunk_scan(*operands, cfg.chunk)[0], None

    @jax.jit
    def layer(lp, x):
        (a, m), _ = _mixers(
            lp, x, lambda h: _attn_mix(lp, h, positions, attend, cfg),
            lambda h: _mamba2_mix(
                lp, h, jnp.zeros((1, cfg.d_conv - 1, cfg.d_xbc), jnp.float32),
                recur, None, cfg), cfg)
        ra, rm = size(a) / size(x), size(m) / size(x)
        x = x + a / ra + m / rm
        f = _mlp(lp, x, cfg)
        rf = size(f) / size(x)
        return x + f / rf, jnp.stack([ra, rm, rf])

    x = (params["embed"][tokens].astype(jnp.float32)
         * cfg.embedding_multiplier)
    out = []
    for l in range(cfg.n_layers):
        x, r = layer(jax.tree_util.tree_map(lambda a: a[l], params["layers"]),
                     x)
        out.append(r)
    return jnp.stack(out)


# ---------------------------------------------------------------------------
# the two programs
# ---------------------------------------------------------------------------

def _stack(cfg, params, x, cache, conv, positions, n_real, attend, recur):
    """Layers 0 .. L-1 over x (S, T, d). `conv` (L, S, K-1, .) the tails of
    x's sequences; `attend(q, k, v, pool, l)` stores the layer's K/V and
    reads them back, returning (out, pool); `recur(operands, ssm, l)`
    runs the recurrence on the states `ssm` of every layer, returning
    (y, ssm). Returns (x, the pool, the new tails, the states)."""
    def block(carry, xs):
        x, pool, ssm = carry
        lp, l, tail = xs
        x, (pool, (tail, ssm)) = _block(
            lp, x,
            lambda h: _attn_mix(lp, h, positions,
                                lambda q, k, v: attend(q, k, v, pool, l),
                                cfg),
            lambda h: _mamba2_mix(lp, h, tail,
                                  lambda *ops: recur(ops, ssm, l),
                                  n_real, cfg), cfg)
        return (x, pool, ssm), tail

    (x, pool, ssm), conv = lax.scan(
        block, (x, cache["kv"], cache["ssm"]),
        (params["layers"], jnp.arange(cfg.n_layers, dtype=jnp.int32), conv))
    return x, pool, conv, ssm


def _logits(params, x, cfg):
    """x (S, d) -> (S, V): the head as the left operand, contracted where
    it lies (x @ W.T re-lays-out all of it in every call)."""
    h = _rms(x, params["final_norm"], cfg.eps)
    W = params["head"]
    return lax.dot_general(W, h.astype(W.dtype), (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32
                           ).T * cfg.lm_head_multiplier


def decode_step_paged(params, cache, tokens, positions, page_table,
                      cfg: FalconH1Config):
    """One token for every decode slot, each at its own depth.

    tokens, positions (S,) int32 (tokens already cached per slot);
    page_table (S, W) int32 rows of the pool's pages, all zero for a dead
    slot (whose row of logits is garbage the caller discards, and whose
    scan states stay as they are). The cache is carried and written in place:
    donate it. Returns (logits (S, V), new cache); the cache keeps the
    logits too."""
    page_size = cache["kv"].shape[3]
    live = page_table[:, 0] != 0
    n_valid = jnp.where(live, positions + 1, 0)
    plan = paged_write_plan(page_table, positions, live.astype(jnp.int32), 1,
                            page_size)

    def attend(q, k, v, pool, l):
        pool = paged_kv_write(pool, l, k, v, plan)
        return paged_decode_attention(q[:, 0], pool, page_table, n_valid,
                                      l)[:, None], pool

    def recur(operands, ssm, l):
        y, ssm = ssd_state_update(ssm, l, live, *(
            a if a.ndim == 1 else a[:, 0] for a in operands))
        return y[:, None], ssm

    x = (params["embed"][tokens].astype(jnp.float32)[:, None]
         * cfg.embedding_multiplier)
    x, pool, conv, ssm = _stack(cfg, params, x, cache, cache["conv"],
                                positions[:, None], None, attend, recur)
    logits = _logits(params, x[:, 0], cfg)
    return logits, dict(cache, kv=pool, conv=conv, ssm=ssm,
                        logits_decode=logits)


def prefill_paged(params, cache, prompt, true_len, page_table, slot,
                  cfg: FalconH1Config):
    """One prompt into slot `slot`, from an empty state.

    prompt (1, T_b) int32 padded to its bucket; true_len (1,) its real
    length; page_table (1, W) its pages of the pool; slot (1,) int32.
    Every layer's K/V rows go to the pool, its convolution tail and its
    scan state, as they are after row true_len - 1, to the slot's rows of
    the states. Returns (new cache, logits (1, V) of the last real token);
    the cache keeps them in the slot's row of logits_prefill."""
    _, T = prompt.shape
    page_size = cache["kv"].shape[3]
    plan = paged_write_plan(page_table, jnp.zeros_like(true_len), true_len,
                            T, page_size)
    dtype = cache["kv"].dtype

    def attend(q, k, v, pool, l):
        # what the decode steps will read back: the cache's rounding
        return _prompt_attention(q[0], k[0].astype(dtype), v[0].astype(dtype),
                                 cfg.prefill_block)[None], paged_kv_write(
                                     pool, l, k, v, plan)

    def recur(operands, ssm, l):
        y, last = ssd_chunk_scan(*operands, cfg.chunk)
        return y, lax.dynamic_update_slice(ssm, last[None],
                                           (l, slot[0], 0, 0, 0))

    # an admitted request starts from an empty state, whatever the slot held
    empty = jnp.zeros_like(cache["conv"][:, :1])
    x = (params["embed"][prompt].astype(jnp.float32)
         * cfg.embedding_multiplier)
    x, pool, conv, ssm = _stack(
        cfg, params, x, cache, empty,
        jnp.arange(T, dtype=jnp.int32)[None], true_len, attend, recur)
    last = jnp.maximum(true_len - 1, 0)
    logits = _logits(params, jnp.take_along_axis(
        x, last[:, None, None], axis=1)[:, 0], cfg)
    return dict(
        cache, kv=pool, ssm=ssm,
        conv=lax.dynamic_update_slice_in_dim(cache["conv"], conv, slot[0],
                                             axis=1),
        logits_prefill=lax.dynamic_update_slice_in_dim(
            cache["logits_prefill"], logits, slot[0], axis=0)), logits


class FalconH1Programs:
    """What serving.ServingEngine asks of a model (the seam's third
    implementer, after models.transformer.TransformerPrograms and
    models.sambay.SambaYPrograms)."""

    # fixed-size state that a lever would have to snapshot and restore:
    # the engine refuses prefix cache, chunked prefill and speculation
    recurrent_state = True
    # while every slot decodes, the engine dispatches the step after the
    # one in flight before it reads that one (serving/engine.py
    # `_runs_ahead`): a 15.7 ms step of 32 slots otherwise waits 2.6 ms
    # on the host's turn, a sixth of its wall and all of its run-to-run
    # spread; who reads the cache after a step() asks `decode_in_flight`
    decode_ahead = True

    def __init__(self, cfg):
        self.cfg = cfg

    def init_cache(self, slots, num_pages, page_size):
        return init_cache(self.cfg, slots, num_pages, page_size)

    def decode(self, params, cache, tokens, positions, table):
        return decode_step_paged(params, cache, tokens, positions, table,
                                 self.cfg)

    def prefill(self, params, cache, prompt, true_len, table, slot):
        return prefill_paged(params, cache, prompt, true_len, table, slot,
                             self.cfg)

    def wide(self, *args):
        raise NotImplementedError(
            "no wide program for a model with recurrent state: rows past "
            "an accepted prefix would have to be rolled back out of the "
            "scan state (state snapshots)")

    def prefill_inputs(self, prompt, true_len, row, slot):
        return (prompt, np.asarray([true_len], np.int32), row[None],
                np.asarray([slot], np.int32))

    def prefill_shapes(self, bucket, table_width):
        return [(1, bucket), (1,), (1, table_width), (1,)]

    def cache_kinds(self, page_size):
        """Host-side description of the cache, per slot, by kind."""
        cfg = self.cfg
        state = cfg.ssm_heads * cfg.ssm_head_dim * cfg.d_state
        return {
            "paged_kv": {"layers": cfg.n_layers, "kv_heads": cfg.n_kv_heads,
                         "grows": True},
            "recurrent": {"layers": cfg.n_layers,
                          "state_bytes_per_slot": 4 * cfg.n_layers * (
                              state + (cfg.d_conv - 1) * cfg.d_xbc)},
            "logits": {"rows_per_slot": 2,
                       "bytes_per_slot": 2 * 4 * cfg.vocab},
        }

    def attended(self, n_valid):
        """Tokens one decode step attends, summed over the live slots'
        depths `n_valid` and over the layers."""
        return {"paged_kv": int(np.asarray(n_valid, np.int64).sum())
                * self.cfg.n_layers}

    def fetched(self, n_valid, page_size):
        """Tokens one decode step's kernel fetches to attend those: whole
        blocks of the walk, the last block's tail masked."""
        return {"paged_kv": paged_walk_tokens(n_valid, page_size)
                * self.cfg.n_layers}
