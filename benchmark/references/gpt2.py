"""Plain reference of GPT-2 (Radford et al. 2019; openai-community/gpt2-xl):
learned positions, pre-LayerNorm blocks, multi-head causal attention,
tanh-approximated GELU (`gelu_new`), a final LayerNorm and an output head
tied to the token embedding.

Straightforward `jax.numpy` in float32 at full matmul precision: the whole
sequence at once, no cache, no batching, no kernels. Each layer's weights
are upcast as the scan reaches them, so no second copy of the model is
held. Departure from the published model, shared with the system under
test: the linear layers have no bias (the repo's block has none; 0.04 % of
GPT-2 XL's parameters).

Parameters are the stacked tree of `models.transformer.init_params`:
embed (V, d), pos (P, d), ln_f_g/b (d,), and per layer, with a leading
(L,) axis, ln1_g/b, wq, wk, wv, wo, ln2_g/b, w1 (d, f), w2 (f, d).
"""
import math

import jax
import jax.numpy as jnp
from jax import lax

_PER_LAYER = ("ln1_g", "ln1_b", "wq", "wk", "wv", "wo", "ln2_g", "ln2_b",
              "w1", "w2")


def _layer_norm(x, gain, bias, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gain + bias


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def logits(params, tokens, config):
    """tokens (T,) int32 -> float32 logits (T, V): row t predicts token
    t + 1. Causal, so padding appended after the tokens of interest does
    not change their rows."""
    n_head = int(config["n_head"])
    eps = float(config["layer_norm_epsilon"])
    f32 = jnp.float32
    T = tokens.shape[0]
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(f32) + params["pos"][:T].astype(f32)
        causal = jnp.tril(jnp.ones((T, T), bool))

        def block(x, lp):
            lp = {k: v.astype(f32) for k, v in lp.items()}
            h = _layer_norm(x, lp["ln1_g"], lp["ln1_b"], eps)
            q, k, v = (jnp.reshape(h @ lp[w], (T, n_head, -1))
                       for w in ("wq", "wk", "wv"))
            scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(q.shape[-1])
            scores = jnp.where(causal[None], scores, -jnp.inf)
            attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
            x = x + jnp.reshape(attn, (T, -1)) @ lp["wo"]
            h = _layer_norm(x, lp["ln2_g"], lp["ln2_b"], eps)
            return x + _gelu_new(h @ lp["w1"]) @ lp["w2"], None

        x, _ = lax.scan(block, x, {k: params[k] for k in _PER_LAYER})
        x = _layer_norm(x, params["ln_f_g"].astype(f32),
                        params["ln_f_b"].astype(f32), eps)
        return x @ params["embed"].astype(f32).T
