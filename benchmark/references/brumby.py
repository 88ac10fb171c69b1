"""Plain reference of Brumby (manifestai/Brumby-14B-Base, model_type
`brumby`): Qwen3's block with attention replaced by power retention of
degree 2, in its quadratic form.

d = hidden_size; H query heads over Hkv K/V heads of Dh (query head j reads
K/V head g = j // (H / Hkv)); RMSNorm with gain, eps rms_norm_eps:

    x0      = E[token]
    block:    h = RMSNorm(x; input_norm)
              [q | k | v] = h W_qkv;  q_j = rope(RMSNorm(q_j; q_norm)),  k_g = rope(RMSNorm(k_g; k_norm))
              rope: pairs (x[i], x[i + Dh/2]) turned by position * rope_theta^(-2 i / Dh)
              log gamma_tg = logsigmoid((h W_gate + gate_bias)_g);  G_tg = sum_{r<=t} log gamma_rg
              w_tsj = (q_tj . k_sg)^2 exp(G_tg - G_sg)           s <= t, else 0
              o_tj = sum_s w_tsj v_sg / (sum_s w_tsj + 1e-6)
              x = x + concat_j(o_j) W_o
              x = x + MLP(RMSNorm(x; pre_ff_norm))
    MLP(y)  = (silu(g) * u) W_down,  [g | u] = y W_gate_up
    logits  = RMSNorm(x; final_norm) W_head^T

Straightforward `jax.numpy` in float32 at full matmul precision: the whole
sequence at once, the retention as the (T, T) weights above in blocks of
query rows (so that no (H, T, T) array is held beside the served model), no
state, no chunks, no cache, no kernels, no batching. One layer's weights are
upcast as it is reached, its MLP in slices of the intermediate width, and
the head in slices of the vocabulary over the rows asked for only.

Parameters: the tree of `models.brumby.init_params` — embed, head (V, d),
final_norm, and `layers`, every leaf stacked over the layers.
"""
import jax
import jax.numpy as jnp
from jax import lax

_QUERY_BLOCK = 128
_MLP_SLICES = 4
# the normaliser's epsilon (assumed: configs/brumby_14b.json)
_EPS_R = 1e-6


def _rms_norm(x, gain, eps):
    return (x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
            * gain)


def _rope(x, theta):
    """x (T, heads, Dh) at positions 0 .. T-1."""
    T, _, Dh = x.shape
    half = Dh // 2
    freq = float(theta) ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * freq
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _retention(lp, h, config):
    T = h.shape[0]
    H, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    Dh = config["head_dim"]
    eps = float(config["rms_norm_eps"])
    q, k, v = jnp.split(h @ lp["w_qkv"], [H * Dh, (H + Hkv) * Dh], axis=-1)
    q = _rope(_rms_norm(q.reshape(T, H, Dh), lp["q_norm"], eps),
              config["rope_theta"])
    k = _rope(_rms_norm(k.reshape(T, Hkv, Dh), lp["k_norm"], eps),
              config["rope_theta"])
    G = jnp.cumsum(jax.nn.log_sigmoid(h @ lp["w_gate"] + lp["gate_bias"]),
                   axis=0)                                      # (T, Hkv)
    # query head j reads K/V head j // (H / Hkv)
    k, v, G = (jnp.repeat(a, H // Hkv, axis=1)
               for a in (k, v.reshape(T, Hkv, Dh), G))
    block = min(_QUERY_BLOCK, T)
    pad = (-T) % block
    q, Gq = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
             for a in (q, G))
    k_pos = jnp.arange(T)

    def rows(i):
        q_pos = i * block + jnp.arange(block)
        qb = lax.dynamic_slice_in_dim(q, i * block, block, axis=0)
        gb = lax.dynamic_slice_in_dim(Gq, i * block, block, axis=0)
        seen = (q_pos[:, None] >= k_pos[None, :])[None]         # (1, q, k)
        decay = jnp.exp(jnp.where(seen, gb.T[:, :, None] - G.T[:, None, :],
                                  -jnp.inf))
        w = jnp.square(jnp.einsum("qhd,khd->hqk", qb, k)) * decay
        return (jnp.einsum("hqk,khd->qhd", w, v)
                / (jnp.sum(w, -1).T[..., None] + _EPS_R))

    o = lax.map(rows, jnp.arange((T + pad) // block))
    return o.reshape(T + pad, H * Dh)[:T] @ lp["wo"]


def _mlp(lp, y):
    """SwiGLU in slices of the intermediate width: lp holds w_gate_up and
    w_down as stored; a slice of each is upcast at a time."""
    d, f2 = lp["w_gate_up"].shape
    f = f2 // 2
    n = next(n for n in (_MLP_SLICES, 2, 1) if f % n == 0)
    gate_up = lp["w_gate_up"].reshape(d, 2, n, f // n)
    down = lp["w_down"].reshape(n, f // n, d)

    def part(i):
        w = gate_up[:, :, i].astype(jnp.float32)
        g, u = y @ w[:, 0], y @ w[:, 1]
        return (jax.nn.silu(g) * u) @ down[i].astype(jnp.float32)

    return jnp.sum(lax.map(part, jnp.arange(n)), axis=0)


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
        x = x + _retention(lp, _rms_norm(x, lp["input_norm"], eps), config)
        return x + _mlp(mlp, _rms_norm(x, lp["pre_ff_norm"], eps)), None

    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(f32)
        x, _ = lax.scan(block, x, params["layers"])
        if rows is not None:
            x = lax.dynamic_slice_in_dim(x, rows[0], rows[1], axis=0)
        x = _rms_norm(x, params["final_norm"].astype(f32), eps)
        # the head in slices of the vocabulary: 151936 x 5120 upcast whole
        # would be 3.1 GB beside the served model
        head = params["head"]
        parts = next(n for n in (16, 8, 4, 2, 1) if head.shape[0] % n == 0)
        out = lax.map(lambda w: x @ w.astype(f32).T,
                      head.reshape(parts, -1, head.shape[1]))
        return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], -1)
