#!/usr/bin/env python
"""CPU-vs-real-TPU consistency sweep (the SURVEY §4 oracle on hardware).

The suite's `check_consistency` runs on a virtual CPU mesh; this tool
runs the same cross-context oracle against the real chip — the analog of the reference's `test_operator_gpu.py`
re-running the CPU operator suite under a GPU context and cross-checking
(ref: tests/python/gpu/test_operator_gpu.py:2202).

Covers the compute families the headline models exercise: convolution
(+grouped/strided), BN, pooling, FC/matmul, activations, softmax/xent,
reductions, broadcast arithmetic, RNN cells via symbols, plus a
5-step LeNet TRAINING trajectory cpu-vs-tpu.

Usage: python tools/tpu_consistency.py   (exits 1 if the chip is absent)
Appends one JSON line per case to tools/tpu_consistency.log.
"""
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

LOG = os.path.join(REPO, "tools", "tpu_consistency.log")


def log(rec):
    line = json.dumps(dict(rec, ts=time.strftime("%H:%M:%S")))
    print(line, flush=True)
    with open(LOG, "a") as f:  # JSON-lines parseable (ts inside the record)
        f.write(line + "\n")


def main():
    import numpy as np

    self_check = "--self-check" in sys.argv  # cpu-vs-cpu harness smoke
    if self_check:
        import jax as _jax

        _jax.config.update("jax_platforms", "cpu")
    import jax

    accel = [d for d in jax.devices() if d.platform != "cpu"]
    if not accel and not self_check:
        print("no accelerator", file=sys.stderr)
        return 1

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import compile_cache, nd, sym as S, test_utils

    compile_cache.enable_jax_cache()
    mx.random.seed(0)
    np.random.seed(0)
    cpu = mx.cpu()
    tpu = mx.cpu() if self_check else mx.tpu()

    data = S.var("data")
    w = S.var("w")
    cases = [
        ("conv3x3", S.Convolution(data=data, weight=w, num_filter=8,
                                  kernel=(3, 3), no_bias=True),
         {"data": (2, 4, 14, 14), "w": (8, 4, 3, 3)}),
        ("conv_grouped_strided", S.Convolution(
            data=data, weight=w, num_filter=8, kernel=(3, 3), stride=(2, 2),
            pad=(1, 1), num_group=2, no_bias=True),
         {"data": (2, 4, 14, 14), "w": (8, 2, 3, 3)}),
        ("fully_connected", S.FullyConnected(data=data, weight=w,
                                             num_hidden=16, no_bias=True),
         {"data": (4, 32), "w": (16, 32)}),
        ("batch_norm", S.BatchNorm(data=S.Convolution(
            data=data, weight=w, num_filter=4, kernel=(3, 3), no_bias=True),
            fix_gamma=False),
         {"data": (2, 3, 10, 10), "w": (4, 3, 3, 3)}),
        ("maxpool", S.Pooling(data=data, kernel=(2, 2), stride=(2, 2),
                              pool_type="max"),
         {"data": (2, 3, 12, 12)}),
        ("avgpool_pad", S.Pooling(data=data, kernel=(3, 3), stride=(2, 2),
                                  pad=(1, 1), pool_type="avg"),
         {"data": (2, 3, 12, 12)}),
        # a weighted softmax head: a plain sum-of-softmax head has an
        # identically-zero input gradient (sum_i dy_i/dx_j = 0), which
        # would make the backward check vacuous
        ("softmax_weighted", S.sum(S.softmax(data=data, axis=-1)
                                   * S.square(w)),
         {"data": (8, 100), "w": (8, 100)}),
        ("reductions", S.sum(S.broadcast_mul(data, w), axis=(1,)),
         {"data": (6, 7), "w": (1, 7)}),
        ("tanh_sigmoid", S.tanh(data) + S.Activation(data,
                                                     act_type="sigmoid"),
         {"data": (5, 9)}),
        ("dot", S.dot(data, w), {"data": (8, 16), "w": (16, 12)}),
    ]

    failures = 0
    for name, symbol, shapes in cases:
        t0 = time.perf_counter()
        try:
            test_utils.check_consistency(
                symbol,
                [dict(ctx=cpu, **shapes), dict(ctx=tpu, **shapes)],
                rtol=2e-3, atol=2e-4, use_uniform=True)
            log({"case": name, "ok": True,
                 "wall_s": round(time.perf_counter() - t0, 1)})
        except Exception as e:
            failures += 1
            log({"case": name, "ok": False, "err": str(e)[:300]})

    # 5-step LeNet training trajectory, cpu vs tpu
    t0 = time.perf_counter()
    try:
        losses = {}
        for label, ctx in (("cpu", cpu), ("tpu", tpu)):
            mx.random.seed(7)
            rng = np.random.RandomState(7)
            from incubator_mxnet_tpu import fused, gluon
            from incubator_mxnet_tpu.gluon import nn

            net = nn.HybridSequential()
            net.add(nn.Conv2D(8, 3, activation="relu"), nn.MaxPool2D(2),
                    nn.Flatten(), nn.Dense(10))
            net.initialize(mx.init.Xavier())
            L = gluon.loss.SoftmaxCrossEntropyLoss()
            # NOTE: GluonTrainStep takes the batch MEAN of the loss, so
            # rescale_grad must stay 1 (1/batch here would freeze the
            # trajectory 16x and blunt the divergence oracle)
            opt = mx.optimizer.SGD(learning_rate=0.1)
            step = fused.GluonTrainStep(net, lambda n, x, y: L(n(x), y), opt,
                                        device=ctx.jax_device())
            x = nd.array(rng.rand(16, 1, 12, 12).astype(np.float32))
            y = nd.array(rng.randint(0, 10, 16).astype(np.float32))
            traj = []
            for _ in range(5):
                traj.append(float(step(x, y).asnumpy().sum()))
            losses[label] = traj
        diff = max(abs(a - b) / (abs(a) + 1e-6)
                   for a, b in zip(losses["cpu"], losses["tpu"]))
        ok = diff < 5e-3
        failures += 0 if ok else 1
        log({"case": "lenet_5step_trajectory", "ok": ok,
             "max_rel_diff": round(diff, 6),
             "wall_s": round(time.perf_counter() - t0, 1)})
    except Exception as e:
        failures += 1
        log({"case": "lenet_5step_trajectory", "ok": False,
             "err": str(e)[:300]})

    log({"summary": True, "cases": len(cases) + 1, "failures": failures})
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
