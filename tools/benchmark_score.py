#!/usr/bin/env python
"""Inference throughput across the model zoo (the benchmark_score analog).

Mirrors the reference's inference benchmark protocol
(ref: example/image-classification/benchmark_score.py — synthetic data,
forward-only, images/sec per model per batch size; headline numbers in
docs/faq/perf.md:167-193: ResNet-50 fp32 1233.15 img/s @ bs128, fp16
2355.04 img/s @ bs128, AlexNet 10990 img/s @ bs256 on one V100).

TPU-native measurement:
  - params are regenerated on the device from (shape, dtype, mean, std)
    specs; weight values do not affect timing
  - predict-mode forward under jit (BN uses running stats, no aux writes)
  - two modes per model: per-batch dispatch, and a lax.scan over K
    device-resident batches inside ONE program (free of host dispatch
    latency — the bulked-exec analog)

Prints one JSON line per (model, dtype) plus a final summary line keyed
against the reference's headline inference numbers.

Usage:
  python tools/benchmark_score.py                     # headline set
  python tools/benchmark_score.py --models resnet18_v1 --batch 8 \
      --iters 2 --scan 2 --platform cpu               # smoke (tests)
"""
import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# reference inference baselines (docs/faq/perf.md:167-193, 1x V100)
REF_V100 = {
    ("resnet50_v1", "float32"): 1233.15,
    ("resnet50_v1", "bfloat16"): 2355.04,  # reference fp16 row
    ("alexnet", "float32"): 10990.0,
    ("inceptionv3", "float32"): 904.33,  # fp32 table @ bs128
    # no AlexNet column in the reference's fp16 table (perf.md:181-193)
    ("vgg16", "float32"): 703.30,
    ("vgg16", "bfloat16"): 1169.81,   # reference fp16 row @ bs128
    ("inceptionv3", "bfloat16"): 1818.26,  # reference fp16 row @ bs128
}


def make_gen_batch(target, data_shape, jdtype=None):
    """On-device synthetic batch generator."""
    import jax
    import jax.numpy as jnp

    sharding = jax.sharding.SingleDeviceSharding(target)

    def gen_batch(seed, lead=()):
        def g(s):
            k = jax.random.PRNGKey(s)
            x = jax.random.uniform(k, lead + data_shape, jnp.float32)
            return x if jdtype is None else x.astype(jdtype)
        return jax.jit(g, out_shardings=sharding)(seed)

    return gen_batch


def time_modes(fwd, gen_batch, batch, iters, scan_k, params=()):
    """Shared measurement protocol: compile, per-batch dispatch timing,
    then a lax.scan over K device-resident batches in one program.

    `fwd(params, x)` must be traceable (jnp in -> jnp out); params ride
    as RUNTIME jit arguments, never closure constants — weights baked
    into the HLO would let XLA fold weight-only subgraphs out of the
    timed steady-state and duplicate ~100MB models in device memory."""
    import jax
    import jax.numpy as jnp

    jfwd = jax.jit(fwd)

    def scan_fwd(ps, xs):
        def body(carry, x):
            # per-batch argmax: forces the full forward while keeping the
            # program output (and the device->host copy) tiny
            return carry, jnp.argmax(fwd(ps, x), axis=-1)
        _, outs = jax.lax.scan(body, 0, xs)
        return outs

    jscan = jax.jit(scan_fwd)

    # executions on one device are stream-ordered, so fetching a tiny
    # slice of the LAST output closes the whole timed chain
    def sync(o):
        return jax.device_get(jax.numpy.ravel(o)[0])

    x = gen_batch(0)
    t0 = time.perf_counter()
    sync(jfwd(params, x))
    compile_s = time.perf_counter() - t0
    sync(jfwd(params, x))  # steady-state warm
    t0 = time.perf_counter()
    out = None
    for _ in range(max(1, iters)):
        out = jfwd(params, x)
    sync(out)
    ips = batch * max(1, iters) / (time.perf_counter() - t0)

    scan_ips = 0.0
    if scan_k > 1:
        xs = gen_batch(1, lead=(scan_k,))
        sync(jscan(params, xs))  # compile + warm
        reps = max(1, iters // scan_k)
        t0 = time.perf_counter()
        outs = None
        for _ in range(reps):
            outs = jscan(params, xs)
        sync(outs)
        scan_ips = batch * scan_k * reps / (time.perf_counter() - t0)
    return round(ips, 2), round(scan_ips, 2), round(compile_s, 1)


def bench_model(name, batch, image, dtype, iters, scan_k, target):
    import numpy as np
    import jax
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import autograd
    from incubator_mxnet_tpu.ndarray.ndarray import NDArray
    from incubator_mxnet_tpu.gluon.block import _ParamSubst
    from incubator_mxnet_tpu.gluon.model_zoo import vision

    mx.random.seed(0)
    try:
        cpu0 = jax.devices("cpu")[0]
    except RuntimeError:
        cpu0 = target
    # build + init on host CPU (avoids hundreds of tiny per-param device
    # programs); ResNet supports TPU-native NHWC
    kwargs = {"classes": 1000}
    if name.startswith("resnet") and dtype != "int8":
        # int8 stays NCHW: the quantized-conv path (and the residual-unit
        # quantizer) is NCHW; fp32/bf16 resnets use the TPU-native NHWC
        kwargs["layout"] = "NHWC"
        data_shape = (batch, image, image, 3)
    else:
        data_shape = (batch, 3, image, image)
    if name.replace("_", "") == "inceptionv3":
        image = max(image, 299)
        data_shape = (batch, 3, image, image)
    with jax.default_device(cpu0):
        net = vision.get_model(name, **kwargs)
        net.initialize(mx.init.Xavier())
        if dtype == "bfloat16":
            net.cast("bfloat16")
        # shape-resolve deferred params with one tiny host forward
        prev = autograd.set_training(False)
        try:
            net(mx.nd.zeros((1,) + data_shape[1:],
                            dtype="bfloat16" if dtype == "bfloat16"
                            else "float32"))
        finally:
            autograd.set_training(prev)

    if dtype == "int8":
        # calibrated int8 program (v5e int8 MXU rate: 2x bf16); only
        # chain-structured nets quantize fully — residual nets fall back
        # to fp32 islands and are not int8 benchmarks, so reject them
        return bench_int8(name, net, batch, data_shape, iters, scan_k,
                          target, cpu0)

    params = list(net.collect_params().items())
    names = [n for n, _ in params]
    specs = []
    for _, p in params:
        d = p.data()._data
        h = np.asarray(d, dtype=np.float32)
        specs.append((tuple(d.shape), d.dtype, float(h.mean()),
                      float(h.std())))

    sharding = jax.sharding.SingleDeviceSharding(target)

    def gen_params(seed):
        key = jax.random.PRNGKey(seed)
        outs = []
        for i, (shape, dt, mean, std) in enumerate(specs):
            k = jax.random.fold_in(key, i)
            v = mean + jax.random.normal(k, shape, jnp.float32) * std
            outs.append(v.astype(dt))
        return tuple(outs)

    dev_params = jax.jit(gen_params, out_shardings=sharding)(0)

    jdtype = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    gen_batch = make_gen_batch(target, data_shape, jdtype)

    def fwd(ps, x):
        mapping = {n: NDArray._from_data(d) for n, d in zip(names, ps)}
        prev_t = autograd.set_training(False)
        prev_r = autograd.set_recording(False)
        try:
            with _ParamSubst(mapping):
                out = net(NDArray._from_data(x))
        finally:
            autograd.set_training(prev_t)
            autograd.set_recording(prev_r)
        return out._data

    ips, scan_ips, compile_s = time_modes(fwd, gen_batch, batch, iters,
                                          scan_k, params=dev_params)
    return {"model": name, "dtype": dtype, "batch": batch,
            "ips": ips, "scan_ips": scan_ips,
            "platform": target.platform, "compile_s": compile_s}


def bench_int8(name, net, batch, data_shape, iters, scan_k, target, cpu0):
    """Calibrated int8 inference throughput (the quantize_net path:
    int8 convs/matmuls with int32 accumulation on the MXU integer path;
    ref role: src/operator/quantization/ + contrib quantize_model)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.contrib import quantization as q

    rng = np.random.RandomState(0)
    with jax.default_device(cpu0):
        probe = nd.array(rng.rand(*(2,) + data_shape[1:])
                         .astype(np.float32))
        chain = q.as_chain(net, probe=probe)  # zoo nets: output(features(x))
        calib = [[nd.array(rng.rand(*(4,) + data_shape[1:])
                           .astype(np.float32))] for _ in range(2)]
        qnet = q.quantize_net(chain, calib, num_calib_batches=2)
    if qnet.num_fp32_islands:
        raise RuntimeError(
            f"{name}: {qnet.num_fp32_islands} fp32 island(s) after "
            f"quantization — not a pure int8 chain, skipping as an int8 "
            f"benchmark")

    gen_batch = make_gen_batch(target, data_shape)
    # the int8 weights live inside QuantizedNet's program by design (its
    # own jit embeds them); params therefore stays empty here
    ips, scan_ips, compile_s = time_modes(lambda _ps, x: qnet.apply(x),
                                          gen_batch, batch, iters, scan_k)
    return {"model": name, "dtype": "int8", "batch": batch,
            "ips": ips, "scan_ips": scan_ips,
            "platform": target.platform, "compile_s": compile_s}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", nargs="+",
                    default=["resnet50_v1", "alexnet", "mobilenet1_0"])
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--iters", type=int, default=40)
    ap.add_argument("--scan", type=int, default=8)
    ap.add_argument("--dtypes", nargs="+",
                    default=["bfloat16", "float32"])
    ap.add_argument("--platform", default=None,
                    help="force a jax platform (--platform cpu runs the "
                         "sweep's control flow off-chip; every row names "
                         "the platform it ran on)")
    args = ap.parse_args()

    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    from incubator_mxnet_tpu import compile_cache

    compile_cache.enable_jax_cache()
    target = jax.devices()[0]

    results = []
    for name in args.models:
        for dtype in args.dtypes:
            try:
                r = bench_model(name, args.batch, args.image, dtype,
                                args.iters, args.scan, target)
            except Exception as e:  # keep going: one model must not kill the sweep
                r = {"model": name, "dtype": dtype, "batch": args.batch,
                     "error": str(e)[:300]}
            print(json.dumps(r), flush=True)
            results.append(r)

    summary = {"metric": "inference_images_per_sec", "results": []}
    for r in results:
        if "error" in r:
            continue
        best = max(r["ips"], r.get("scan_ips", 0.0))
        entry = {"model": r["model"], "dtype": r["dtype"], "best_ips": best,
                 "platform": r["platform"]}
        ref = REF_V100.get((r["model"], r["dtype"]))
        if ref:
            entry["vs_v100_ref"] = round(best / ref, 3)
        summary["results"].append(entry)
    print(json.dumps(summary), flush=True)


if __name__ == "__main__":
    main()
