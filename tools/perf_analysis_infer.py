#!/usr/bin/env python
"""Hardware-independent analysis of the INFERENCE benchmark programs
(companion to tools/perf_analysis.py, which covers the training step;
docs/PERF_ANALYSIS_INFER.md is generated from this).

Compiles the exact programs tools/benchmark_score.py measures —
ResNet-50 v1 NHWC bf16 inference and calibrated int8 AlexNet, each as a
K-batch lax.scan — through the full XLA pipeline on the CPU backend,
then extracts backend-independent facts (XLA cost-model flop totals,
conv dtypes/layouts from the pre-backend StableHLO) and derives v5e
roofline predictions to stand next to the reference's V100 inference
table (ref: docs/faq/perf.md:167-193 — ResNet-50 fp32 1233.15 / fp16
2355.04 img/s @ bs128, AlexNet fp32 10990 img/s @ bs256).

Usage:
  python tools/perf_analysis_infer.py [--report docs/PERF_ANALYSIS_INFER.md]
"""
import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# v5e single-chip peaks (public spec)
V5E_BF16_FLOPS = 197e12
V5E_INT8_OPS = 394e12
V5E_HBM_BW = 819e9

# analytic forward costs (multiply-add x2), standard counts
RESNET50_FWD_FLOPS = 4.09e9   # per image at 224^2
ALEXNET_FWD_FLOPS = 1.43e9    # ~0.72 GMACs per image at 224^2

REF_V100_RESNET_FP16 = 2355.04
REF_V100_ALEXNET_FP32 = 10990.0


def _force_cpu():
    import jax

    jax.config.update("jax_platforms", "cpu")
    from incubator_mxnet_tpu import compile_cache

    compile_cache.enable_jax_cache()


def _conv_facts(stablehlo):
    import collections
    import re

    dtypes = collections.Counter()
    nhwc = 0
    lines = [ln for ln in stablehlo.splitlines()
             if "stablehlo.convolution" in ln]
    for ln in lines:
        m = re.search(r"-> tensor<[\dx]+x(\w+)>", ln)
        if m:
            dtypes[m.group(1)] += 1
        if re.search(r"dim_numbers = \[b, 0, 1, f\]", ln):
            nhwc += 1
    return {"convolutions": len(lines), "conv_out_dtypes": dict(dtypes),
            "nhwc_convs": nhwc}


def analyze_resnet_bf16(batch, image, scan_k):
    """The zoo bf16 NHWC inference scan program benchmark_score times."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import autograd, nd
    from incubator_mxnet_tpu.ndarray.ndarray import NDArray
    from incubator_mxnet_tpu.gluon.block import _ParamSubst
    from incubator_mxnet_tpu.gluon.model_zoo import vision

    mx.random.seed(0)
    net = vision.resnet50_v1(classes=1000, layout="NHWC")
    net.initialize(mx.init.Xavier())
    net.cast("bfloat16")
    prev = autograd.set_training(False)
    try:
        net(nd.zeros((1, image, image, 3), dtype="bfloat16"))
    finally:
        autograd.set_training(prev)
    items = list(net.collect_params().items())
    names = [n for n, _ in items]
    params = tuple(p.data()._data for _, p in items)

    def fwd(ps, x):
        mapping = {n: NDArray._from_data(d) for n, d in zip(names, ps)}
        prev_t = autograd.set_training(False)
        try:
            with _ParamSubst(mapping):
                return net(NDArray._from_data(x))._data
        finally:
            autograd.set_training(prev_t)

    def scan_fwd(ps, xs):
        def body(c, x):
            return c, jnp.argmax(fwd(ps, x), axis=-1)
        _, outs = jax.lax.scan(body, 0, xs)
        return outs

    p_sds = tuple(jax.ShapeDtypeStruct(p.shape, p.dtype) for p in params)
    xs_sds = jax.ShapeDtypeStruct((scan_k, batch, image, image, 3),
                                  jnp.bfloat16)
    t0 = time.time()
    lowered = jax.jit(scan_fwd).lower(p_sds, xs_sds)
    stablehlo = lowered.as_text()
    compiled = lowered.compile()
    compile_s = time.time() - t0

    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    # XLA counts a while body once: totals are per K-batch... verify by
    # comparing against the single-batch program
    per_batch_flops = flops  # scan body counted once => per batch of `batch`
    flops_per_img = per_batch_flops / batch
    analytic = RESNET50_FWD_FLOPS * (image / 224.0) ** 2

    # v5e roofline, one inference batch: compute vs HBM. Traffic estimate:
    # one pass over bf16 activations (~12M acts/img x 2B, written+consumed
    # inside fusions => ~1.5 passes) + one pass over the 25.6M bf16 params.
    t_comp_xla = per_batch_flops / V5E_BF16_FLOPS
    t_comp_analytic = batch * analytic / V5E_BF16_FLOPS
    est_bytes = 1.5 * 12e6 * 2 * (image / 224.0) ** 2 * batch + 25.6e6 * 2
    t_mem = est_bytes / V5E_HBM_BW
    pred_lo = batch / max(t_comp_xla, t_mem)
    pred_hi = batch / max(t_comp_analytic, t_mem)
    return {
        "program": "resnet50_v1 bf16 NHWC inference",
        "batch": batch, "scan_k": scan_k, "compile_s": round(compile_s, 1),
        "xla_flops_per_image_gflop": round(flops_per_img / 1e9, 2),
        "analytic_flops_per_image_gflop": round(analytic / 1e9, 2),
        "est_tpu_bytes_per_batch": round(est_bytes),
        "bound": "memory" if t_mem > t_comp_xla else "compute",
        "v5e_roofline_img_per_s": round(min(pred_lo, pred_hi)),
        "roofline_vs_v100_fp16_ref": round(
            min(pred_lo, pred_hi) / REF_V100_RESNET_FP16, 2),
        **_conv_facts(stablehlo),
    }


def analyze_alexnet_int8(batch, image, scan_k):
    """The calibrated int8 AlexNet program (as_chain + quantize_net)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import autograd, nd
    from incubator_mxnet_tpu.contrib import quantization as q
    from incubator_mxnet_tpu.gluon.model_zoo import vision

    mx.random.seed(0)
    net = vision.alexnet(classes=1000)
    net.initialize(mx.init.Xavier())
    prev = autograd.set_training(False)
    try:
        net(nd.zeros((1, 3, image, image)))
        probe = nd.array(np.random.RandomState(0)
                         .rand(2, 3, image, image).astype(np.float32))
        chain = q.as_chain(net, probe=probe)
    finally:
        autograd.set_training(prev)
    rng = np.random.RandomState(0)
    calib = [[nd.array(rng.rand(4, 3, image, image).astype(np.float32))]
             for _ in range(2)]
    qnet = q.quantize_net(chain, calib, num_calib_batches=2)
    assert qnet.num_fp32_islands == 0

    def scan_fwd(xs):
        def body(c, x):
            return c, jnp.argmax(qnet.apply(x), axis=-1)
        _, outs = jax.lax.scan(body, 0, xs)
        return outs

    xs_sds = jax.ShapeDtypeStruct((scan_k, batch, 3, image, image),
                                  jnp.float32)
    t0 = time.time()
    lowered = jax.jit(scan_fwd).lower(xs_sds)
    stablehlo = lowered.as_text()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))

    analytic_macs = ALEXNET_FWD_FLOPS / 2 * (image / 224.0) ** 2
    # int8 MACs ride the MXU integer path at 2x the bf16 MAC rate
    t_comp = batch * analytic_macs * 2 / V5E_INT8_OPS
    # traffic: int8 activations (~0.66M acts/img x 1B, ~1.5 passes) + one
    # pass over the ~61M int8 params (AlexNet is FC-heavy: params dominate)
    est_bytes = 1.5 * 0.66e6 * (image / 224.0) ** 2 * batch + 61e6
    t_mem = est_bytes / V5E_HBM_BW
    pred = batch / max(t_comp, t_mem)
    return {
        "program": "alexnet int8 inference (calibrated, chain-flattened)",
        "batch": batch, "scan_k": scan_k, "compile_s": round(compile_s, 1),
        "xla_flops_per_batch": flops,
        "analytic_int8_ops_per_image_gop": round(analytic_macs * 2 / 1e9, 2),
        "est_tpu_bytes_per_batch": round(est_bytes),
        "bound": "memory" if t_mem > t_comp else "compute",
        "v5e_roofline_img_per_s": round(pred),
        "roofline_vs_v100_fp32_ref": round(pred / REF_V100_ALEXNET_FP32, 2),
        **_conv_facts(stablehlo),
    }


def analyze_resnet50_int8(batch, image, scan_k):
    """The calibrated int8 ResNet-50 program (residual units quantize as
    units, round 5 — NCHW; every conv FLOP int8, skip-joins in the f32
    epilogue)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import autograd, nd
    from incubator_mxnet_tpu.contrib import quantization as q
    from incubator_mxnet_tpu.gluon.model_zoo import vision

    mx.random.seed(0)
    net = vision.get_model("resnet50_v1", classes=1000)
    net.initialize(mx.init.Xavier())
    prev = autograd.set_training(False)
    try:
        net(nd.zeros((1, 3, image, image)))
        probe = nd.array(np.random.RandomState(0)
                         .rand(2, 3, image, image).astype(np.float32))
        chain = q.as_chain(net, probe=probe)
    finally:
        autograd.set_training(prev)
    rng = np.random.RandomState(0)
    calib = [[nd.array(rng.rand(2, 3, image, image).astype(np.float32))]
             for _ in range(2)]
    qnet = q.quantize_net(chain, calib, num_calib_batches=2)
    assert qnet.num_fp32_islands == 0

    def scan_fwd(xs):
        def body(c, x):
            return c, jnp.argmax(qnet.apply(x), axis=-1)
        _, outs = jax.lax.scan(body, 0, xs)
        return outs

    xs_sds = jax.ShapeDtypeStruct((scan_k, batch, 3, image, image),
                                  jnp.float32)
    t0 = time.time()
    lowered = jax.jit(scan_fwd).lower(xs_sds)
    stablehlo = lowered.as_text()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))

    analytic_macs = RESNET50_FWD_FLOPS / 2 * (image / 224.0) ** 2
    t_comp = batch * analytic_macs * 2 / V5E_INT8_OPS
    # traffic: int8 activations (~11M acts/img, ~2 passes through the
    # requant epilogues) + one pass over ~25.5M int8 params
    est_bytes = 2.0 * 11e6 * (image / 224.0) ** 2 * batch + 25.5e6
    t_mem = est_bytes / V5E_HBM_BW
    pred = batch / max(t_comp, t_mem)
    return {
        "program": "resnet50_v1 int8 inference (residual units quantized)",
        "batch": batch, "scan_k": scan_k, "compile_s": round(compile_s, 1),
        "xla_flops_per_batch": flops,
        "analytic_int8_ops_per_image_gop": round(analytic_macs * 2 / 1e9, 2),
        "est_tpu_bytes_per_batch": round(est_bytes),
        "bound": "memory" if t_mem > t_comp else "compute",
        "v5e_roofline_img_per_s": round(pred),
        "roofline_vs_v100_fp16_ref": round(pred / REF_V100_RESNET_FP16, 2),
        **_conv_facts(stablehlo),
    }


def write_report(rows, path):
    lines = [
        "# Inference program analysis (offline, XLA-compiled)",
        "",
        "*Generated by `tools/perf_analysis_infer.py` from the COMPILED",
        "programs `tools/benchmark_score.py` measures (K-batch scan,",
        "on-device data). Companion to docs/PERF_ANALYSIS.md (training).",
        "Facts below are backend-independent (XLA cost model + pre-backend",
        "StableHLO dtype/layout structure). The v5e numbers are ROOFLINE",
        "UPPER BOUNDS — compute/HBM limits of the compiled program, not",
        "predictions of achieved throughput; dispatch, DMA, and padding",
        "overheads land real numbers below them. The first live-chip sweep",
        "measures where under the bound the program lands, keyed against",
        "the reference V100 table (docs/faq/perf.md:167-193). The int8",
        "chain runs NCHW (quantized zoo chains are layout-fixed); XLA",
        "inserts the TPU-internal transposes.*",
        "",
    ]
    for d in rows:
        lines.append(f"## {d['program']}")
        lines.append("")
        lines.append("| quantity | value |")
        lines.append("|---|---|")
        for k, v in d.items():
            if k == "program":
                continue
            lines.append(f"| {k} | {v} |")
        lines.append("")
    with open(path, "w") as f:
        f.write("\n".join(lines))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch-resnet", type=int, default=128)
    ap.add_argument("--batch-alexnet", type=int, default=256)
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--scan", type=int, default=8)
    ap.add_argument("--report", default=None)
    args = ap.parse_args()

    _force_cpu()
    rows = [
        analyze_resnet_bf16(args.batch_resnet, args.image, args.scan),
        analyze_alexnet_int8(args.batch_alexnet, args.image, args.scan),
        analyze_resnet50_int8(args.batch_resnet, args.image, args.scan),
    ]
    for d in rows:
        print(json.dumps(d), flush=True)
    if args.report:
        write_report(rows, args.report)


if __name__ == "__main__":
    main()
