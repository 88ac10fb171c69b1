"""Plain reference of Phi-4-mini-flash-reasoning (SambaY, arXiv:2507.06607;
microsoft/Phi-4-mini-flash-reasoning, `modeling_phi4flash.py`).

Layer i of n (n = 32), from 0; LN is LayerNorm with gain and bias; there is
no positional encoding of any kind:

    x0 = E[tokens];  x <- x + Mix_i(LN1_i(x));
    x <- x + (silu(g) * u) W_down, [g | u] = LN2_i(x) W_gate_up;  logits = LN_f(x) E^T
    Mix_i = Mamba             i even, i <= n/2   (layer n/2 also hands on its scan output m)
            DiffAttn, window  i odd,  i <  n/2   (a query sees the `sliding_window` latest positions, its own included)
            DiffAttn, full    i = n/2 + 1        (its K, V are read again by every CrossAttn layer)
            GMU               i even, i > n/2    GMU(h) = (silu(h W_in) * m) W_out, m of the same token
            CrossAttn         i odd,  i > n/2+1  DiffAttn with its own W_q, W_o, lambda, subln; K, V of layer n/2+1; causal
    Mamba(h): [a | z] = h W_in;  a = silu(conv(a)), conv causal, per channel, width d_conv, with bias,
              conv_w[k] weighing the input d_conv-1-k tokens back;
              [dt' | B | C] = a W_x;  dt = softplus(dt' W_dt + b_dt);  A = -exp(A_log);
              s_t = exp(dt_t A) * s_{t-1} + (dt_t a_t) B_t^T  (float32);  y_t = s_t C_t + D a_t;  m = y;
              out = (y * silu(z)) W_out
    DiffAttn(h): q = h W_q -> (T, H, Dh); k, v = h W_k, h W_v -> (T, Hkv, Dh), Hkv = H/2;
              q1, q2 = q[:, 0::2], q[:, 1::2]; k1, k2 = k[:, 0::2], k[:, 1::2];
              V = [v[:, 0::2] | v[:, 1::2]]  (Hkv/2 heads of 2 Dh);  for j < Hkv, g = j // 2:
              A1_j = softmax(q1_j k1_g^T / sqrt(Dh) + mask) V_g;  A2_j = softmax(q2_j k2_g^T / sqrt(Dh) + mask) V_g
              lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0,  lam0 = 0.8 - 0.6 exp(-0.3 i)
              O_j = (1 - lam0) * RMSNorm_subln(A1_j - lam A2_j);  out = concat_j(O_j) W_o

Straightforward `jax.numpy` in float32 at full matmul precision: the whole
sequence at once, a sequential `lax.scan` over tokens for the recurrence,
dense masked softmax in blocks of query rows (so that no (H, T, T) score
is held beside the served model), no cache, no kernels, no batching. Each
layer's weights are upcast as it is reached. Logits are worked out for the
rows asked for only (T x 200064 float32 would be 3.3 GB at 4096).

Parameters: the tree of `models.sambay.init_params` — embed (V, d),
ln_f_g/b, and one group per run of like layers, stacked where there are
several: mamba and window (layers 0 .. n/2-1, even and odd), memory
(n/2), full (n/2+1), gmu and cross (the rest, even and odd). A_log is
(N, Di): state index first. Departure shared with the system under test:
no bias on W_q/k/v/o.
"""
import math

import jax
import jax.numpy as jnp
from jax import lax

_QUERY_BLOCK = 256


def _layer_norm(x, gain, bias, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gain + bias


def _mlp(lp, x, eps):
    h = _layer_norm(x, lp["ln2_g"], lp["ln2_b"], eps)
    g, u = jnp.split(h @ lp["w_gate_up"], 2, axis=-1)
    return x + (jax.nn.silu(g) * u) @ lp["w_down"]


def _mamba(lp, h, sizes):
    """h (T, d) -> (out (T, d), y (T, Di))."""
    T = h.shape[0]
    N, R, K = sizes["d_state"], sizes["dt_rank"], sizes["d_conv"]
    a, z = jnp.split(h @ lp["w_in"], 2, axis=-1)
    padded = jnp.pad(a, ((K - 1, 0), (0, 0)))
    a = jax.nn.silu(sum(padded[k:k + T] * lp["conv_w"][k] for k in range(K))
                    + lp["conv_b"])
    dbc = a @ lp["w_x"]
    dt = jax.nn.softplus(dbc[:, :R] @ lp["w_dt"] + lp["b_dt"])   # (T, Di)
    B, C = dbc[:, R:R + N], dbc[:, R + N:]                        # (T, N)
    A = -jnp.exp(lp["A_log"])                                     # (N, Di)

    def token(s, xs):
        dt_t, a_t, b_t, c_t = xs
        s = jnp.exp(dt_t[None, :] * A) * s + (dt_t * a_t)[None, :] * b_t[:, None]
        return s, jnp.sum(s * c_t[:, None], axis=0)

    _, y = lax.scan(token, jnp.zeros_like(A), (dt, a, B, C))
    y = y + lp["D"] * a
    return (y * jax.nn.silu(z)) @ lp["w_out"], y


def _softmax_rows(q, k, v, window):
    """q (T, J, Dh), k (T, J, Dh), v (T, J, Dv) per pair j: causal (and
    windowed) softmax(q k^T / sqrt(Dh)) v, in blocks of query rows."""
    T, J, Dh = q.shape
    block = min(_QUERY_BLOCK, T)
    pad = (-T) % block
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    k_pos = jnp.arange(T)

    def rows(i):
        q_pos = i * block + jnp.arange(block)
        qb = lax.dynamic_slice_in_dim(q, i * block, block, axis=0)
        s = jnp.einsum("qjd,kjd->jqk", qb, k) / math.sqrt(Dh)
        back = q_pos[:, None] - k_pos[None, :]
        seen = back >= 0
        if window:
            seen &= back < window
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum("jqk,kjv->qjv", jax.nn.softmax(s, -1), v)

    out = lax.map(rows, jnp.arange((T + pad) // block))
    return out.reshape(T + pad, J, -1)[:T]


def _diff_attn(lp, h, kv, depth, window, n_head, eps):
    """h (T, d); kv: the (k, v) to read, or None to make them from h.
    Returns (out (T, d), (k, v))."""
    T = h.shape[0]
    q = (h @ lp["wq"]).reshape(T, n_head, -1)
    if kv is None:
        kv = tuple((h @ lp[w]).reshape(T, n_head // 2, -1)
                   for w in ("wk", "wv"))
    k, v = kv
    q1, q2 = q[:, 0::2], q[:, 1::2]                       # (T, Hkv, Dh)
    k1, k2 = k[:, 0::2], k[:, 1::2]                       # (T, Hkv/2, Dh)
    V = jnp.concatenate([v[:, 0::2], v[:, 1::2]], -1)     # (T, Hkv/2, 2 Dh)
    per_pair = lambda x: jnp.repeat(x, 2, axis=1)         # g = j // 2
    A1 = _softmax_rows(q1, per_pair(k1), per_pair(V), window)
    A2 = _softmax_rows(q2, per_pair(k2), per_pair(V), window)
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * depth)
    lam = (jnp.exp(jnp.sum(lp["lq1"] * lp["lk1"]))
           - jnp.exp(jnp.sum(lp["lq2"] * lp["lk2"])) + lam0)
    o = A1 - lam * A2
    o = o / jnp.sqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + eps)
    o = (1.0 - lam0) * o * lp["subln"]
    return o.reshape(T, -1) @ lp["wo"], kv


def logits(params, tokens, config, rows=None):
    """tokens (T,) int32 -> float32 logits of rows [start, start + count)
    (`rows` = (start, count), static; all T rows when None): row t predicts
    token t + 1. Causal, so padding appended after the tokens of interest
    does not change their rows."""
    n = int(config["num_hidden_layers"])
    n_head = int(config["num_attention_heads"])
    window = int(config["sliding_window"])
    eps = float(config["layer_norm_eps"])
    sizes = config["mamba"]
    half = n // 2
    f32 = jnp.float32

    def upcast(lp):
        """The leaves of one layer, upcast as it is reached."""
        return {k: v.astype(f32) for k, v in lp.items()}

    def mamba_layer(x, lp):
        lp = upcast(lp)
        out, y = _mamba(lp, _layer_norm(x, lp["ln1_g"], lp["ln1_b"], eps),
                        sizes)
        return _mlp(lp, x + out, eps), y

    def attn_layer(x, lp, kv, depth, window):
        lp = upcast(lp)
        out, kv = _diff_attn(lp, _layer_norm(x, lp["ln1_g"], lp["ln1_b"], eps),
                             kv, depth, window, n_head, eps)
        return _mlp(lp, x + out, eps), kv

    def gmu_layer(x, lp, m):
        lp = upcast(lp)
        h = _layer_norm(x, lp["ln1_g"], lp["ln1_b"], eps)
        return _mlp(lp, x + (jax.nn.silu(h @ lp["w_in"]) * m) @ lp["w_out"],
                    eps)

    # like layers come in stacked runs, so each run is one scan over its
    # layers in depth order: (Mamba 2p, window 2p+1), then (GMU, cross)
    def self_pair(x, xs):
        mp, wp, p = xs
        x, _ = mamba_layer(x, mp)
        x, _ = attn_layer(x, wp, None, 2 * p + 1, window)
        return x, None

    def cross_pair(carry, xs):
        x, m, k, v = carry
        gp, cp, p = xs
        x = gmu_layer(x, gp, m)
        x, _ = attn_layer(x, cp, (k, v), half + 3 + 2 * p, 0)
        return (x, m, k, v), None

    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(f32)
        x, _ = lax.scan(self_pair, x, (params["mamba"], params["window"],
                                       jnp.arange(half // 2, dtype=f32)))
        x, m = mamba_layer(x, params["memory"])                # layer n/2
        x, (k, v) = attn_layer(x, params["full"], None, half + 1, 0)
        (x, _, _, _), _ = lax.scan(
            cross_pair, (x, m, k, v),
            (params["gmu"], params["cross"],
             jnp.arange((n - half - 2) // 2, dtype=f32)))
        if rows is not None:
            x = lax.dynamic_slice_in_dim(x, rows[0], rows[1], axis=0)
        x = _layer_norm(x, params["ln_f_g"].astype(f32),
                        params["ln_f_b"].astype(f32), eps)
        # the head in slices of the vocabulary: 200064 x 2560 upcast whole
        # would be 2 GB beside the served model
        embed = params["embed"]
        parts = next(n for n in (16, 8, 4, 2, 1) if embed.shape[0] % n == 0)
        out = lax.map(lambda e: x @ e.astype(f32).T,
                      embed.reshape(parts, -1, embed.shape[1]))
        return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], -1)
