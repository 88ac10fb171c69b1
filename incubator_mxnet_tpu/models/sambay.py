"""SambaY: a decoder-hybrid-decoder LM (arXiv:2507.06607), the serving side.

A self-decoder of Mamba and sliding-window differential-attention layers
ends in one Mamba layer that hands on its scan output m ("the memory") and
one full-attention layer whose K/V are THE cache; a cross-decoder of gated
memory units (GMU, gated by m) and cross-attention layers (their own
queries over the full layer's K/V) follows. Every block is
x <- x + Mix(LN1(x)); x <- x + SwiGLU(LN2(x)); no positional encoding.

    layer i <  n/2, even : Mamba            i <  n/2, odd : window attention
    layer i == n/2       : Mamba, hands on m
    layer i == n/2 + 1   : full attention, writes the shared K/V
    layer i >  n/2 + 1, even : GMU          odd : cross-attention

Three kinds of per-slot state serve it (`init_cache`):

    kv_shared  (1, Hkv, pages, page, 2*Dh)   grows with the context, paged,
               written by the full layer, read by it and every cross layer
    kv_ring    (n_window, Hkv, slots*R + 1, page, 2*Dh)   a ring of R pages
               per slot and window layer: the last `window` tokens
    conv, ssm  (n_mamba, slots, d_conv - 1, Di), (n_mamba, slots, N, Di)
               float32, fixed size: the convolution's tail and the scan state

and beside them `logits_prefill`, `logits_decode` (slots, V) float32: the
row each slot's first token, and its latest, was chosen from, left on the
device by the program that chose it. Whoever holds the served model to a
reference reads them there; the engine fetches tokens only.

One body per layer kind (`_mamba_mix`, `_attn_mix`, `_gmu_mix`, `_block`)
is used by the prefill and the decode step alike; they differ in the
`attend` they pass (dense blocked attention over the prompt, the paged
kernel over the cache). The prefill runs the self-decoder over the prompt
and the cross-decoder on the last real token's row only: no cross-decoder
layer owns a cache and no other row's output is read.

`SambaYPrograms` is what serving.ServingEngine asks of a model.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..ops.pallas_kernels import (paged_diff_attention, paged_kv_write,
                                  paged_ring_write_plan, paged_walk_tokens,
                                  paged_write_plan, selective_scan)

__all__ = ["SambaYConfig", "SambaYPrograms", "init_params", "init_cache",
           "decode_step_paged", "prefill_paged", "param_count"]

_NEG_INF = -1e30


@dataclasses.dataclass
class SambaYConfig:
    vocab: int = 128
    d_model: int = 64
    n_layers: int = 8
    n_heads: int = 8
    n_kv_heads: int = 4
    d_ff: int = 256
    window: int = 8
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 4
    eps: float = 1e-5
    max_len: int = 262144   # max_position_embeddings: there is no table
    dtype: str = "float32"  # weights, K/V pools; scan state is float32
    prefill_block: int = 512  # query rows per block of the prompt's attention

    def __post_init__(self):
        if self.n_layers % 4 or self.n_layers < 8:
            raise ValueError("n_layers must be a multiple of 4, at least 8")
        if self.n_heads != 2 * self.n_kv_heads or self.n_kv_heads % 2:
            raise ValueError("differential attention pairs the heads: "
                             "n_heads = 2 * n_kv_heads, n_kv_heads even")

    @property
    def d_inner(self):
        return self.expand * self.d_model

    @property
    def head_dim(self):
        return self.d_model // self.n_heads

    @property
    def n_self_pairs(self):   # (Mamba, window) pairs before the memory layer
        return self.n_layers // 4

    @property
    def n_cross_pairs(self):  # (GMU, cross) pairs after the full layer
        return (self.n_layers - self.n_layers // 2 - 2) // 2

    def lam0(self, layers):
        """lambda_init of the attention layers at depths `layers`."""
        return 0.8 - 0.6 * np.exp(-0.3 * np.asarray(layers, np.float64))

    def paged_programs(self):
        return SambaYPrograms(self)


# ---------------------------------------------------------------------------
# parameters: one stacked group per run of like layers
# ---------------------------------------------------------------------------

def _leaves(cfg):
    """{group: (n_stacked or None, {leaf: (shape, init)})}."""
    d, f, di = cfg.d_model, cfg.d_ff, cfg.d_inner
    dkv = cfg.n_kv_heads * cfg.head_dim
    N, R, K = cfg.d_state, cfg.dt_rank, cfg.d_conv
    ln = {"ln1_g": ((d,), "ones"), "ln1_b": ((d,), "zeros"),
          "ln2_g": ((d,), "ones"), "ln2_b": ((d,), "zeros")}
    mlp = {"w_gate_up": ((d, 2 * f), "fan_in"), "w_down": ((f, d), "fan_in")}
    mamba = {"w_in": ((d, 2 * di), "fan_in"),
             "conv_w": ((K, di), ("uniform", 1 / math.sqrt(K))),
             "conv_b": ((di,), ("uniform", 1 / math.sqrt(K))),
             "w_x": ((di, R + 2 * N), "fan_in"),
             "w_dt": ((R, di), ("uniform", R ** -0.5)),
             "b_dt": ((di,), "dt_bias"), "A_log": ((N, di), "a_log"),
             "D": ((di,), "ones"), "w_out": ((di, d), "fan_in")}
    lam = {k: ((cfg.head_dim,), ("normal", 0.1))
           for k in ("lq1", "lk1", "lq2", "lk2")}
    q_side = {"wq": ((d, d), "fan_in"), "wo": ((d, d), "fan_in"),
              "subln": ((2 * cfg.head_dim,), "ones"), **lam}
    attn = {**q_side, "wk": ((d, dkv), "fan_in"), "wv": ((d, dkv), "fan_in")}
    gmu = {"w_in": ((d, di), "fan_in"), "w_out": ((di, d), "fan_in")}
    return {
        "mamba": (cfg.n_self_pairs, {**ln, **mamba, **mlp}),
        "window": (cfg.n_self_pairs, {**ln, **attn, **mlp}),
        "memory": (None, {**ln, **mamba, **mlp}),
        "full": (None, {**ln, **attn, **mlp}),
        "gmu": (cfg.n_cross_pairs, {**ln, **gmu, **mlp}),
        "cross": (cfg.n_cross_pairs, {**ln, **q_side, **mlp}),
    }


def param_count(cfg):
    n = cfg.vocab * cfg.d_model + 2 * cfg.d_model
    for stacked, leaves in _leaves(cfg).values():
        n += (stacked or 1) * sum(int(np.prod(s)) for s, _ in leaves.values())
    return n


def _draw(key, shape, init, lead):
    full = lead + shape
    f32 = jnp.float32
    if init == "ones":
        return jnp.ones(full, f32)
    if init == "zeros":
        return jnp.zeros(full, f32)
    if init == "fan_in":
        return jax.random.normal(key, full, f32) / math.sqrt(shape[0])
    if init == "a_log":   # S4D-real: A = -(1..N), for every channel
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[0] + 1, dtype=f32))[:, None], full)
    if init == "dt_bias":  # softplus(b_dt) log-uniform in [1e-3, 1e-1]
        dt = jnp.exp(jax.random.uniform(key, full, f32)
                     * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
        return dt + jnp.log(-jnp.expm1(-dt))
    kind, scale = init
    if kind == "normal":
        return jax.random.normal(key, full, f32) * scale
    return jax.random.uniform(key, full, f32, -scale, scale)


def init_params(cfg: SambaYConfig, seed=0, device=None):
    """The parameter tree for `seed`, made on the device in one jitted
    call, every leaf in cfg.dtype. The recurrence's leaves get the Mamba
    family's own initialisation (a normal draw would blow the scan up or
    kill it); the lambda vectors N(0, 0.1); matrices N(0, 1/fan_in)."""
    dtype = jnp.dtype(cfg.dtype)

    def make(key):
        out = {"embed": (jax.random.normal(jax.random.fold_in(key, 0),
                                           (cfg.vocab, cfg.d_model),
                                           jnp.float32) * 0.02).astype(dtype),
               "ln_f_g": jnp.ones((cfg.d_model,), dtype),
               "ln_f_b": jnp.zeros((cfg.d_model,), dtype)}
        n = 1
        for group, (stacked, leaves) in _leaves(cfg).items():
            lead = () if stacked is None else (stacked,)
            out[group] = {}
            for name, (shape, init) in leaves.items():
                out[group][name] = _draw(jax.random.fold_in(key, n), shape,
                                         init, lead).astype(dtype)
                n += 1
        return out

    key = jax.random.key(seed, impl="rbg")
    with jax.default_device(device):
        return jax.jit(make)(key)


# ---------------------------------------------------------------------------
# the cache
# ---------------------------------------------------------------------------

def ring_pages(cfg, page_size):
    """Pages of a slot's ring: the window, plus the page being written."""
    return -(-cfg.window // page_size) + 1


def init_cache(cfg: SambaYConfig, slots, num_pages, page_size):
    """The three kinds of per-slot state (module docstring). Page 0 of
    either pool is the null page: dead slots read and write it."""
    Hkv, Dh, di = cfg.n_kv_heads, cfg.head_dim, cfg.d_inner
    n_m = cfg.n_self_pairs + 1
    R = ring_pages(cfg, page_size)
    return {
        "kv_shared": jnp.zeros((1, Hkv, num_pages, page_size, 2 * Dh),
                               cfg.dtype),
        "kv_ring": jnp.zeros((cfg.n_self_pairs, Hkv, slots * R + 1,
                              page_size, 2 * Dh), cfg.dtype),
        "conv": jnp.zeros((n_m, slots, cfg.d_conv - 1, di), jnp.float32),
        "ssm": jnp.zeros((n_m, slots, cfg.d_state, di), jnp.float32),
        "logits_prefill": jnp.zeros((slots, cfg.vocab), jnp.float32),
        "logits_decode": jnp.zeros((slots, cfg.vocab), jnp.float32),
    }


def _ring_table(slot_ids, live, R):
    """(S, R) ring rows of slots `slot_ids`: slot s owns pages
    1 + s*R .. (s+1)*R of kv_ring; a dead slot gets the null page."""
    rows = 1 + slot_ids[:, None] * R + jnp.arange(R, dtype=jnp.int32)[None]
    return jnp.where(live[:, None], rows, 0)


# ---------------------------------------------------------------------------
# the layer bodies
# ---------------------------------------------------------------------------

def _mm(h, w):
    return jnp.dot(h.astype(w.dtype), w, preferred_element_type=jnp.float32)


def _ln(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return ((x - mu) * lax.rsqrt(var + eps) * g.astype(x.dtype)
            + b.astype(x.dtype))


def _block(lp, x, mix, cfg):
    """x (S, T, d) float32 -> x + Mix(LN1 x), then + SwiGLU(LN2 .).
    `mix(h)` returns (its output, whatever else it hands on); so does
    this."""
    out, handed = mix(_ln(x, lp["ln1_g"], lp["ln1_b"], cfg.eps))
    x = x + out
    h = _ln(x, lp["ln2_g"], lp["ln2_b"], cfg.eps)
    g, u = jnp.split(_mm(h, lp["w_gate_up"]), 2, axis=-1)
    return x + _mm(jax.nn.silu(g) * u, lp["w_down"]), handed


def _mamba_mix(lp, h, conv, ssm, n_real, cfg):
    """Mamba-1 over T tokens of S sequences from the state (conv, ssm).

    h (S, T, d); conv (S, K-1, Di) the last K-1 inputs of the convolution;
    ssm (S, N, Di); n_real (S,) or None: rows t >= n_real are padding and
    leave the state as it was. Returns (out (S, T, d), (y (S, T, Di) the
    scan output before the gate, new conv, new ssm)), the state as it is
    after row n_real - 1."""
    S, T, _ = h.shape
    f32 = jnp.float32
    N, R, K = cfg.d_state, cfg.dt_rank, cfg.d_conv
    a_pre, z = jnp.split(_mm(h, lp["w_in"]), 2, axis=-1)       # (S, T, Di)
    ext = jnp.concatenate([conv, a_pre], axis=1)               # (S, T+K-1, Di)
    w = lp["conv_w"].astype(f32)
    a = sum(ext[:, k:k + T] * w[k] for k in range(K)) + lp["conv_b"].astype(f32)
    a = jax.nn.silu(a)
    dbc = _mm(a, lp["w_x"])
    dt = jax.nn.softplus(_mm(dbc[..., :R], lp["w_dt"])
                         + lp["b_dt"].astype(f32))              # (S, T, Di)
    Bm, Cm = dbc[..., R:R + N], dbc[..., R + N:]                # (S, T, N)
    if n_real is None:
        n_real = jnp.full((S,), T, jnp.int32)
    else:
        real = jnp.arange(T)[None, :] < n_real[:, None]
        dt = jnp.where(real[..., None], dt, 0.0)  # exp(0 A) = 1, 0 B = 0
    A = -jnp.exp(lp["A_log"].astype(f32))                       # (N, Di)

    y, ssm = selective_scan(dt, a, Bm, Cm, A, ssm)
    y = y + lp["D"].astype(f32) * a
    out = _mm(y * jax.nn.silu(z), lp["w_out"])
    # rows n_real .. n_real+K-2 of ext are inputs n_real-K+1 .. n_real-1
    tail = n_real[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None]
    conv = jnp.take_along_axis(ext, tail[..., None], axis=1)
    return out, (y, conv, ssm)


def _group_queries(q, cfg):
    """(S, T, H*Dh) -> (S, T, G, 4, Dh): for K/V group g rows 0-1 are q1 of
    the pairs j = 2g, 2g+1 (heads 4g, 4g+2) and rows 2-3 their q2 (heads
    4g+1, 4g+3)."""
    S, T, _ = q.shape
    G = cfg.n_kv_heads // 2
    q = q.reshape(S, T, G, 2, 2, cfg.head_dim)  # (g, pair in group, 1|2)
    return q.transpose(0, 1, 2, 4, 3, 5).reshape(S, T, G, 4, cfg.head_dim)


def _attn_mix(lp, h, lam0, attend, cfg, own_kv=True):
    """Differential attention. `attend(q, k, v)` takes q (S, T, G, 4, Dh)
    as _group_queries lays it out and this layer's k, v (S, T, Hkv, Dh)
    (None for a cross layer, which reads the shared cache) and returns
    ((S, T, G, 4, 2*Dh): each row's softmax applied to [v[2g] | v[2g+1]],
    whatever it hands on: the pool it wrote). Returns (out, handed)."""
    S, T, _ = h.shape
    Dh, Hkv = cfg.head_dim, cfg.n_kv_heads
    f32 = jnp.float32
    # the barrier keeps the head split out of the matmul (PERF.md, PR 26)
    q = _group_queries(lax.optimization_barrier(_mm(h, lp["wq"])), cfg)
    k = v = None
    if own_kv:
        k, v = (lax.optimization_barrier(_mm(h, lp[w]))
                .reshape(S, T, Hkv, Dh) for w in ("wk", "wv"))
    a, handed = attend(q, k, v)                           # (S, T, G, 4, 2Dh)
    lam = (jnp.exp(jnp.sum(lp["lq1"].astype(f32) * lp["lk1"].astype(f32)))
           - jnp.exp(jnp.sum(lp["lq2"].astype(f32) * lp["lk2"].astype(f32)))
           + lam0)
    o = a[..., :2, :] - lam * a[..., 2:, :]               # (S, T, G, 2, 2Dh)
    o = o * lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + cfg.eps)
    o = o * lp["subln"].astype(f32) * (1.0 - lam0)
    return _mm(o.reshape(S, T, cfg.d_model), lp["wo"]), handed


def _gmu_mix(lp, h, m):
    return _mm(jax.nn.silu(_mm(h, lp["w_in"])) * m, lp["w_out"]), None


def _dense_diff_attention(q, k, v, q_pos, k_pos, window):
    """q (Tq, G, 4, Dh), k, v (Tk, Hkv, Dh), positions (Tq,), (Tk,): key
    at k_pos is seen by the query at q_pos when 0 <= q_pos - k_pos
    (< window, if any) and k_pos >= 0. Returns (Tq, G, 4, 2*Dh)."""
    Tk, Hkv, Dh = k.shape
    G = Hkv // 2
    k = k.reshape(Tk, G, 2, Dh)
    s = jnp.einsum("qgerd,kged->gerqk",
                   q.reshape(q.shape[0], G, 2, 2, Dh).astype(k.dtype), k,
                   preferred_element_type=jnp.float32) / math.sqrt(Dh)
    back = q_pos[:, None] - k_pos[None, :]
    seen = (back >= 0) & (k_pos >= 0)[None, :]
    if window:
        seen &= back < window
    p = jax.nn.softmax(jnp.where(seen, s, _NEG_INF), axis=-1)
    o = jnp.einsum("gerqk,kgv->qgerv", p.astype(v.dtype),
                   v.reshape(Tk, G, 2 * Dh),
                   preferred_element_type=jnp.float32)
    return o.reshape(q.shape[0], G, 4, 2 * Dh)


def _prompt_attention(q, k, v, window, block):
    """Causal (windowed) differential attention of one prompt over itself,
    in blocks of `block` query rows so that no (T, T) score exists: a
    windowed block reads the `window` keys before it and its own."""
    T = q.shape[0]
    block = min(block, T)
    if T % block:
        raise ValueError(f"{T} rows do not split into blocks of {block}")
    pos = jnp.arange(T, dtype=jnp.int32)
    if block == T:
        return _dense_diff_attention(q, k, v, pos, pos, window)
    if window:
        pad = ((window, 0), (0, 0), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)

    def one(i):
        q_pos = i * block + jnp.arange(block, dtype=jnp.int32)
        qb = lax.dynamic_slice_in_dim(q, i * block, block, axis=0)
        if not window:
            return _dense_diff_attention(qb, k, v, q_pos, pos, 0)
        span = window + block  # padded rows i*block .. : positions - window
        kb, vb = (lax.dynamic_slice_in_dim(x, i * block, span, axis=0)
                  for x in (k, v))
        k_pos = i * block - window + jnp.arange(span, dtype=jnp.int32)
        return _dense_diff_attention(qb, kb, vb, q_pos, k_pos, window)

    out = lax.map(one, jnp.arange(T // block, dtype=jnp.int32))
    return out.reshape((T,) + out.shape[2:])


# ---------------------------------------------------------------------------
# the two programs
# ---------------------------------------------------------------------------

def _stack(cfg, params, x, cache, state, plans, n_real, attend_window,
           attend_full, cross_rows):
    """Layers 0 .. n-1 over x (S, T, d). `state` = (conv, ssm), the rows
    of x's sequences, (n_mamba, S, ...); `plans` = (shared, ring) write
    plans; `attend_window(q, k, v, ring, l)` and `attend_full(q, k, v,
    shared)` read what was just written; `cross_rows(x, m, shared, k, v)`
    picks the rows the cross-decoder runs on and gives its attend(q).
    Returns (x of those rows, the two pools, the new state)."""
    shared_plan, ring_plan = plans
    n_self = cfg.n_self_pairs
    half = cfg.n_layers // 2
    f32 = jnp.float32
    conv_in, ssm_in = state

    def self_pair(carry, xs):
        x, ring = carry
        mp, wp, lam0, l, conv, ssm = xs
        x, (_, conv, ssm) = _block(
            mp, x, lambda h: _mamba_mix(mp, h, conv, ssm, n_real, cfg), cfg)

        def attend(q, k, v):
            wrote = paged_kv_write(ring, l, k, v, ring_plan)
            return attend_window(q, k, v, wrote, l), wrote

        x, ring = _block(
            wp, x, lambda h: _attn_mix(wp, h, lam0, attend, cfg), cfg)
        return (x, ring), (conv, ssm)

    (x, ring), (conv_s, ssm_s) = lax.scan(
        self_pair, (x, cache["kv_ring"]),
        (params["mamba"], params["window"],
         jnp.asarray(cfg.lam0(np.arange(1, half, 2)), f32),
         jnp.arange(n_self, dtype=jnp.int32), conv_in[:n_self],
         ssm_in[:n_self]))

    mp = params["memory"]
    x, (m, conv_m, ssm_m) = _block(
        mp, x, lambda h: _mamba_mix(mp, h, conv_in[n_self], ssm_in[n_self],
                                    n_real, cfg), cfg)

    def attend(q, k, v):
        wrote = paged_kv_write(cache["kv_shared"], 0, k, v, shared_plan)
        return attend_full(q, k, v, wrote), (wrote, k, v)

    fp = params["full"]
    x, (shared, k, v) = _block(
        fp, x, lambda h: _attn_mix(fp, h, float(cfg.lam0(half + 1)), attend,
                                   cfg), cfg)
    x, m, attend_cross = cross_rows(x, m, shared, k, v)

    def cross_pair(x, xs):
        gp, cp, lam0 = xs
        x, _ = _block(gp, x, lambda h: _gmu_mix(gp, h, m), cfg)
        x, _ = _block(cp, x, lambda h: _attn_mix(
            cp, h, lam0, lambda q, *_: (attend_cross(q), None), cfg,
            own_kv=False), cfg)
        return x, None

    x, _ = lax.scan(
        cross_pair, x,
        (params["gmu"], params["cross"],
         jnp.asarray(cfg.lam0(np.arange(half + 3, cfg.n_layers, 2)), f32)))

    return x, {"kv_shared": shared, "kv_ring": ring}, (
        jnp.concatenate([conv_s, conv_m[None]], 0),
        jnp.concatenate([ssm_s, ssm_m[None]], 0))


def _logits(params, x, cfg):
    """x (S, d) -> (S, V): E . LN_f(x), the embedding as the left operand
    so that it is contracted where it lies (x @ E.T re-lays-out all of E
    in every call)."""
    h = _ln(x, params["ln_f_g"], params["ln_f_b"], cfg.eps)
    E = params["embed"]
    return lax.dot_general(E, h.astype(E.dtype), (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32).T


def decode_step_paged(params, cache, tokens, positions, page_table,
                      cfg: SambaYConfig):
    """One token for every decode slot, each at its own depth.

    tokens, positions (S,) int32 (tokens already cached per slot);
    page_table (S, W) int32 rows of the shared pool's pages, all zero for
    a dead slot (whose row of logits is garbage the caller discards).
    The cache is carried and written in place: donate it. Returns
    (logits (S, V), new cache); the cache keeps the logits too."""
    S = tokens.shape[0]
    page_size = cache["kv_shared"].shape[3]
    live = page_table[:, 0] != 0
    n_valid = jnp.where(live, positions + 1, 0)
    n_write = live.astype(jnp.int32)
    ring_table = _ring_table(jnp.arange(S, dtype=jnp.int32), live,
                             ring_pages(cfg, page_size))
    plans = (paged_write_plan(page_table, positions, n_write, 1, page_size),
             paged_ring_write_plan(ring_table, positions, n_write, 1,
                                   page_size))

    def attend_window(q, k, v, ring, l):
        return paged_diff_attention(q[:, 0], ring, ring_table, n_valid, l,
                                    window=cfg.window, ring=True)[:, None]

    def attend_shared(q, k, v, shared):
        return paged_diff_attention(q[:, 0], shared, page_table,
                                    n_valid)[:, None]

    def cross_rows(x, m, shared, k, v):
        return x, m, lambda q: attend_shared(q, None, None, shared)

    x = params["embed"][tokens].astype(jnp.float32)[:, None]
    x, pools, (conv, ssm) = _stack(
        cfg, params, x, cache, (cache["conv"], cache["ssm"]), plans, None,
        attend_window, attend_shared, cross_rows)
    logits = _logits(params, x[:, 0], cfg)
    return logits, dict(pools, conv=conv, ssm=ssm, logits_decode=logits,
                        logits_prefill=cache["logits_prefill"])


def prefill_paged(params, cache, prompt, true_len, page_table, slot,
                  cfg: SambaYConfig):
    """One prompt into slot `slot`, from an empty state.

    prompt (1, T_b) int32 padded to its bucket; true_len (1,) its real
    length; page_table (1, W) its pages of the shared pool; slot (1,)
    int32. The self-decoder runs over the prompt: the full layer's K/V go
    to the shared pool, each window layer's last rows to the slot's ring,
    each Mamba layer's state, as it is after row true_len - 1, to the
    slot's state rows. The cross-decoder runs on that row alone. Returns
    (new cache, logits (1, V) of the last real token); the cache keeps
    them in the slot's row of logits_prefill."""
    _, T = prompt.shape
    page_size = cache["kv_shared"].shape[3]
    last = jnp.maximum(true_len - 1, 0)
    ring_table = _ring_table(slot, jnp.ones((1,), bool),
                             ring_pages(cfg, page_size))
    zero = jnp.zeros_like(true_len)
    plans = (paged_write_plan(page_table, zero, true_len, T, page_size),
             paged_ring_write_plan(ring_table, zero, true_len, T, page_size))
    dtype = cache["kv_shared"].dtype

    def attend_prompt(window):
        def attend(q, k, v, *_):
            # what the decode steps will read back: the cache's rounding
            return _prompt_attention(q[0], k[0].astype(dtype),
                                     v[0].astype(dtype), window,
                                     cfg.prefill_block)[None]
        return attend

    def cross_rows(x, m, shared, k, v):
        k, v = k[0].astype(dtype), v[0].astype(dtype)

        def attend_cross(q):
            return _dense_diff_attention(
                q[0], k, v, last, jnp.arange(T, dtype=jnp.int32), 0)[None]

        pick = last[:, None, None]
        return (jnp.take_along_axis(x, pick, axis=1),
                jnp.take_along_axis(m, pick, axis=1), attend_cross)

    # an admitted request starts from an empty state, whatever the slot held
    empty = tuple(jnp.zeros_like(cache[k][:, :1]) for k in ("conv", "ssm"))
    x = params["embed"][prompt].astype(jnp.float32)
    x, pools, state = _stack(
        cfg, params, x, cache, empty, plans, true_len,
        attend_prompt(cfg.window), attend_prompt(0), cross_rows)
    conv, ssm = (lax.dynamic_update_slice_in_dim(cache[k], new, slot[0],
                                                 axis=1)
                 for k, new in zip(("conv", "ssm"), state))
    logits = _logits(params, x[:, 0], cfg)
    kept = lax.dynamic_update_slice_in_dim(cache["logits_prefill"], logits,
                                           slot[0], axis=0)
    return dict(pools, conv=conv, ssm=ssm, logits_prefill=kept,
                logits_decode=cache["logits_decode"]), logits


class SambaYPrograms:
    """What serving.ServingEngine asks of a model (the seam's second
    implementer; models.transformer.TransformerPrograms is the first)."""

    # fixed-size state that a lever would have to snapshot and restore:
    # the engine refuses prefix cache, chunked prefill and speculation
    recurrent_state = True
    # the engine reads each decode step before it dispatches the next:
    # benchmark/jobs/serve_hybrid.py's check reads the row of the token
    # just delivered from the cache after every step() (ROADMAP S7)
    decode_ahead = False

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
        R = ring_pages(cfg, page_size)
        n_m = cfg.n_self_pairs + 1
        return {
            "shared_kv": {"layers_written": 1,
                          "layers_reading": 1 + cfg.n_cross_pairs,
                          "grows": True},
            "window_kv": {"layers": cfg.n_self_pairs, "window": cfg.window,
                          "ring_pages_per_slot": R},
            "recurrent": {"layers": n_m, "state_bytes_per_slot": 4 * n_m * (
                cfg.d_state + cfg.d_conv - 1) * cfg.d_inner},
            "logits": {"rows_per_slot": 2,
                       "bytes_per_slot": 2 * 4 * cfg.vocab},
        }

    def attended(self, n_valid):
        """Tokens one decode step attends, by cache kind, summed over the
        live slots' depths `n_valid` and over the layers that read."""
        cfg = self.cfg
        n_valid = np.asarray(n_valid, np.int64)
        return {"shared_kv": int(n_valid.sum()) * (1 + cfg.n_cross_pairs),
                "window_kv": int(np.minimum(n_valid, cfg.window).sum())
                * cfg.n_self_pairs}

    def fetched(self, n_valid, page_size):
        """Tokens one decode step's kernels fetch to attend those, by
        cache kind: paged_diff_attention gathers a slot's pages a block
        at a time, from the first page the window reaches to the page
        being written, and masks what the first and last block hold
        besides."""
        cfg = self.cfg
        return {"shared_kv": paged_walk_tokens(n_valid, page_size)
                * (1 + cfg.n_cross_pairs),
                "window_kv": paged_walk_tokens(n_valid, page_size, cfg.window)
                * cfg.n_self_pairs}
