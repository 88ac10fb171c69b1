"""Plain reference of the bottleneck ResNet v1 of the Gluon model zoo
(He et al. 2015, arXiv:1512.03385; MXNet's resnet50_v1: stride on the first
1x1 of a downsampling unit, bias on the 1x1 convolutions).

Straightforward `lax.conv` in float32 at full matmul precision, NHWC, with
BatchNorm on the statistics of the batch (training mode) and the mean
softmax cross-entropy — no framework code, no fusion, no kernels. The
parameters come as the ordered (name, array) list the model's
`collect_params()` gives; the walk below consumes them in construction
order and checks each name's ending, so a change of the model's layout
fails loudly instead of comparing the wrong tensors.
"""
import jax
import jax.numpy as jnp
from jax import lax

_DN = ("NHWC", "OIHW", "NHWC")  # Gluon keeps conv weights (O, I, kh, kw)


class _Params:
    def __init__(self, named):
        self._it = iter(named)

    def take(self, ending):
        name, value = next(self._it)
        if not name.endswith(ending):
            raise ValueError(f"reference walk expected a *{ending}, "
                             f"the model has {name}")
        return jnp.asarray(value, jnp.float32)

    def maybe_bias(self, has_bias):
        return self.take("_bias") if has_bias else None

    def done(self):
        left = [n for n, _ in self._it]
        if left:
            raise ValueError(f"reference walk left parameters over: {left}")


def _conv(x, w, b, stride, pad):
    y = lax.conv_general_dilated(x, w, (stride, stride),
                                 [(pad, pad), (pad, pad)],
                                 dimension_numbers=_DN)
    return y if b is None else y + b


def _bn(x, p, eps):
    gamma, beta = p.take("_gamma"), p.take("_beta")
    p.take("_running_mean"), p.take("_running_var")  # unused in training
    mean = jnp.mean(x, (0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), (0, 1, 2))
    return (x - mean) * lax.rsqrt(var + eps) * gamma + beta


def loss(named_params, x, y, config):
    """Mean cross-entropy of the model's forward pass in training mode.

    named_params: [(name, array)] in collect_params() order; x: (N, H, W, 3);
    y: (N,) class ids (any numeric dtype); config: the configuration file
    (units, channels, bn_epsilon)."""
    eps = float(config["bn_epsilon"])
    channels = config["channels"]
    with jax.default_matmul_precision("highest"):
        p = _Params(named_params)
        h = x.astype(jnp.float32)
        h = _conv(h, p.take("_weight"), None, 2, 3)
        h = jax.nn.relu(_bn(h, p, eps))
        h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1),
                              ((0, 0), (1, 1), (1, 1), (0, 0)))
        in_c = channels[0]
        for i, n_units in enumerate(config["units"]):
            out_c = channels[i + 1]
            for j in range(n_units):
                stride = 2 if (j == 0 and i > 0) else 1
                # (kernel, stride, pad, bias) of the three convolutions
                plan = ((1, stride, 0, True), (3, 1, 1, False),
                        (1, 1, 0, True))
                convs = [(p.take("_weight"), p.maybe_bias(bias))
                         for _, _, _, bias in plan]
                t = h
                for k, ((w, b), (_, s, pad, _)) in enumerate(
                        zip(convs, plan)):
                    t = _bn(_conv(t, w, b, s, pad), p, eps)
                    if k < 2:
                        t = jax.nn.relu(t)
                if j == 0 and out_c != in_c:
                    skip = _bn(_conv(h, p.take("_weight"), None, stride, 0),
                               p, eps)
                else:
                    skip = h
                h = jax.nn.relu(skip + t)
                in_c = out_c
        h = jnp.mean(h, (1, 2))
        logits = h @ p.take("_weight").T + p.take("_bias")
        p.done()
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(
            logp, y.astype(jnp.int32)[:, None], axis=-1)[:, 0]
        return -jnp.mean(picked)
