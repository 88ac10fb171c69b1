"""Plain reference of Falcon-H1 (tiiuae/Falcon-H1-34B-Instruct, model_type
`falcon_h1`, `modeling_falcon_h1.py`): Mamba-2 and grouped-query attention
side by side in every block.

d = hidden_size; attention H query heads over Hkv K/V heads of Dh, rotary
over the whole head; Mamba-2 with Hs heads of P channels (Hs P = d_ssm),
state N, G groups (head h reads group h // (Hs / G)), causal convolution of
width K with bias over [x | B | C]; RMSNorm with gain; every multiplier is
the config's:

    x0      = E[token] * embedding_multiplier
    block:    h = RMSNorm(x; input_norm)
              a = Attn(h * attention_in_multiplier) * attention_out_multiplier
              m = Mamba2(h) * ssm_out_multiplier
              x = x + a + m
              x = x + MLP(RMSNorm(x; pre_ff_norm))
    MLP(y)  = ((silu(g * mlp_multipliers[0]) * u) W_down) * mlp_multipliers[1],  [g | u] = y W_gate_up
    Attn(u) : [q | k | v] = u W_qkv;  k = k * key_multiplier;  q, k = rope(q, k, position)
              rope: pairs (x[i], x[i + Dh/2]) turned by position * rope_theta^(-2 i / Dh)
              o_j = softmax_causal(q_j k_g^T / sqrt(Dh)) v_g,  g = j // (H / Hkv);  out = concat_j(o_j) W_o
    Mamba2(h): p = (h * ssm_in_multiplier) W_in * mup,  mup = ssm_multipliers[0..4] spread over [z | x | B | C | dt]
              split p -> z (d_ssm), xBC (d_ssm + 2 G N), dt (Hs);  xBC = silu(conv(xBC) + conv_b),
              conv_w[k] weighing the input K-1-k tokens back;  split -> x (Hs, P), B (G, N), C (G, N)
              dt = softplus(dt + dt_bias);  A = -exp(A_log)  (one scalar a head)
              S_t = exp(dt_t A) S_{t-1} + (dt_t x_t) (outer) B_t     S: (Hs, P, N), float32
              y_t = S_t C_t + D x_t
              y = RMSNorm over each of the G groups' d_ssm / G channels of (y * silu(z)), times ssm_norm
              out = y W_out
    logits  = (RMSNorm(x; final_norm) W_head^T) * lm_head_multiplier

Straightforward `jax.numpy` in float32 at full matmul precision: the whole
sequence at once, the recurrence row by row (`lax.scan` over time, no
chunks), dense causal softmax in blocks of query rows (so that no (H, T, T)
score is held beside the served model), no cache, no kernels, no batching.
One layer's weights are upcast as it is reached, its MLP in slices of the
intermediate width (a whole layer of the 34B in float32 is 1.7 GB), and the
head in slices of the vocabulary over the rows asked for only.

Parameters: the tree of `models.falcon_h1.init_params` — embed, head (V, d),
final_norm, and `layers`, every leaf stacked over the layers.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_QUERY_BLOCK = 256
_MLP_SLICES = 4


def _rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * gain


def _rope(x, theta):
    """x (T, heads, Dh) at positions 0 .. T-1."""
    T, _, Dh = x.shape
    half = Dh // 2
    freq = float(theta) ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * freq
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _attention(lp, u, config):
    T = u.shape[0]
    H, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    Dh = config["head_dim"]
    q, k, v = jnp.split(u @ lp["w_qkv"], [H * Dh, (H + Hkv) * Dh], axis=-1)
    q = _rope(q.reshape(T, H, Dh), config["rope_theta"])
    k = _rope(k.reshape(T, Hkv, Dh) * config["key_multiplier"],
              config["rope_theta"])
    # query head j reads K/V head j // (H / Hkv)
    k, v = (jnp.repeat(a, H // Hkv, axis=1) for a in (k, v.reshape(T, Hkv, Dh)))
    block = min(_QUERY_BLOCK, T)
    pad = (-T) % block
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    k_pos = jnp.arange(T)

    def rows(i):
        q_pos = i * block + jnp.arange(block)
        qb = lax.dynamic_slice_in_dim(q, i * block, block, axis=0)
        s = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(Dh)
        s = jnp.where((q_pos[:, None] >= k_pos[None, :])[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    o = lax.map(rows, jnp.arange((T + pad) // block))
    return o.reshape(T + pad, H * Dh)[:T] @ lp["wo"]


def _mamba2(lp, h, config):
    T = h.shape[0]
    Hs, P = config["mamba_n_heads"], config["mamba_d_head"]
    N, G, K = (config["mamba_d_state"], config["mamba_n_groups"],
               config["mamba_d_conv"])
    d_ssm = config["mamba_d_ssm"]
    mup = jnp.asarray(np.repeat(
        np.asarray(config["ssm_multipliers"], np.float32),
        (d_ssm, d_ssm, G * N, G * N, Hs)))
    p = (h * config["ssm_in_multiplier"]) @ lp["w_in"] * mup
    z, xbc, dt = jnp.split(p, [d_ssm, 2 * d_ssm + 2 * G * N], axis=-1)
    padded = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[k:k + T] * lp["conv_w"][k] for k in range(K))
                      + lp["conv_b"])
    x, B, C = jnp.split(xbc, [d_ssm, d_ssm + G * N], axis=-1)
    x = x.reshape(T, Hs, P)
    # every head its group's B and C
    B, C = (jnp.repeat(a.reshape(T, G, N), Hs // G, axis=1) for a in (B, C))
    dt = jax.nn.softplus(dt + lp["dt_bias"])                     # (T, Hs)
    A = -jnp.exp(lp["A_log"])                                    # (Hs,)

    def token(S, row):
        dt_t, x_t, b_t, c_t = row
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return S, jnp.einsum("hpn,hn->hp", S, c_t)

    _, y = lax.scan(token, jnp.zeros((Hs, P, N), jnp.float32), (dt, x, B, C))
    y = (y + lp["D"][:, None] * x).reshape(T, d_ssm) * jax.nn.silu(z)
    y = y.reshape(T, G, d_ssm // G)
    y = y / jnp.sqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                     + config["rms_norm_eps"])
    return (y.reshape(T, d_ssm) * lp["ssm_norm"]) @ lp["w_out"]


def _mlp(lp, y, config):
    """SwiGLU in slices of the intermediate width: lp holds w_gate_up and
    w_down as stored; a slice of each is upcast at a time."""
    m_gate, m_down = config["mlp_multipliers"]
    d, f2 = lp["w_gate_up"].shape
    f = f2 // 2
    n = next(n for n in (_MLP_SLICES, 2, 1) if f % n == 0)
    gate_up = lp["w_gate_up"].reshape(d, 2, n, f // n)
    down = lp["w_down"].reshape(n, f // n, d)

    def part(i):
        w = gate_up[:, :, i].astype(jnp.float32)
        g, u = y @ w[:, 0], y @ w[:, 1]
        return (jax.nn.silu(g * m_gate) * u) @ down[i].astype(jnp.float32)

    return jnp.sum(lax.map(part, jnp.arange(n)), axis=0) * m_down


def logits(params, tokens, config, rows=None):
    """tokens (T,) int32 -> float32 logits of rows [start, start + count)
    (`rows` = (start, count), count static; all T rows when None): row t
    predicts token t + 1. Causal, so padding appended after the tokens of
    interest does not change their rows."""
    eps = float(config["rms_norm_eps"])
    f32 = jnp.float32
    big = ("w_gate_up", "w_down")

    def block(x, lp):
        mlp = {k: lp[k] for k in big}
        lp = {k: v.astype(f32) for k, v in lp.items() if k not in big}
        h = _rms_norm(x, lp["input_norm"], eps)
        a = (_attention(lp, h * config["attention_in_multiplier"], config)
             * config["attention_out_multiplier"])
        m = _mamba2(lp, h, config) * config["ssm_out_multiplier"]
        x = x + a + m
        return x + _mlp(mlp, _rms_norm(x, lp["pre_ff_norm"], eps),
                        config), None

    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(f32) * config["embedding_multiplier"]
        x, _ = lax.scan(block, x, params["layers"])
        if rows is not None:
            x = lax.dynamic_slice_in_dim(x, rows[0], rows[1], axis=0)
        x = _rms_norm(x, params["final_norm"].astype(f32), eps)
        # the head in slices of the vocabulary: 261120 x 5120 upcast whole
        # would be 5.3 GB beside the served model
        head = params["head"]
        parts = next(n for n in (16, 8, 4, 2, 1) if head.shape[0] % n == 0)
        out = lax.map(lambda w: x @ w.astype(f32).T,
                      head.reshape(parts, -1, head.shape[1]))
        return (jnp.moveaxis(out, 0, 1).reshape(x.shape[0], -1)
                * config["lm_head_multiplier"])
