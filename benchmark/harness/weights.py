"""Seeded weights made on the device in one jitted call.

`models.transformer.init_params` draws every number in host NumPy (1.56 G
of them for GPT-2 XL, about 40 s) and ships them over. This makes the same
tree — the same keys, shapes and scales — on the device from a key, in
the types the configuration serves.

One thing differs from init_params on purpose, and the configuration file
says so: for dtype "bfloat16" init_params returns float32 matrices (a
bfloat16 NumPy array times a NumPy float64 scale promotes), which no
deployment serves. Here every leaf has the dtype `param_dtypes` gives it.
"""
import math

import jax
import jax.numpy as jnp

# leaf -> (shape over (L, d, f, V, P), scale); None scale = LayerNorm gain
# (ones) and 0.0 = LayerNorm bias (zeros). Scales are init_params' own.
_LEAVES = {
    "embed": (("V", "d"), 0.02),
    "pos": (("P", "d"), 0.02),
    "ln_f_g": (("d",), None),
    "ln_f_b": (("d",), 0.0),
    "wq": (("L", "d", "d"), "fan_in"),
    "wk": (("L", "d", "d"), "fan_in"),
    "wv": (("L", "d", "d"), "fan_in"),
    "wo": (("L", "d", "d"), "fan_in"),
    "ln1_g": (("L", "d"), None),
    "ln1_b": (("L", "d"), 0.0),
    "ln2_g": (("L", "d"), None),
    "ln2_b": (("L", "d"), 0.0),
    "w1": (("L", "d", "f"), "fan_in"),
    "w2": (("L", "f", "d"), "fan_in"),
}


def transformer_params(cfg, seed, param_dtypes, device=None):
    """The dense-FFN parameter tree of `cfg` (a TransformerConfig) for
    `seed`. `param_dtypes` maps a leaf name, or "default", to a dtype."""
    if cfg.n_experts:
        raise ValueError("weights.transformer_params makes dense-FFN trees")
    dims = {"L": cfg.n_layers, "d": cfg.d_model, "f": cfg.d_ff,
            "V": cfg.vocab, "P": cfg.max_len}

    def make(key):
        out = {}
        for i, (name, (axes, scale)) in enumerate(_LEAVES.items()):
            shape = tuple(dims[a] for a in axes)
            dtype = jnp.dtype(param_dtypes.get(name,
                                               param_dtypes["default"]))
            if scale is None:
                out[name] = jnp.ones(shape, dtype)
            elif scale == 0.0:
                out[name] = jnp.zeros(shape, dtype)
            else:
                if scale == "fan_in":
                    scale = 1.0 / math.sqrt(shape[-2])
                draw = jax.random.normal(jax.random.fold_in(key, i), shape,
                                         jnp.float32)
                out[name] = (draw * scale).astype(dtype)
        return out

    # rbg: the chip's own generator, several times faster than threefry
    # for 1.5 G draws; the key is ours, so the program's RNG is untouched
    key = jax.random.key(seed, impl="rbg")
    with jax.default_device(device):
        return jax.jit(make)(key)
