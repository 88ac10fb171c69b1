"""bench.py and chip_smoke.py measure on a TPU or fail: no probe, no cached
number, no CPU fallback. What is pinned here is that refusal, the peaks
table, the accelerator contexts, and where the compile cache lives."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(argv, cwd=REPO, **env_overrides):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_overrides)
    return subprocess.run([sys.executable] + argv, capture_output=True,
                          text=True, timeout=240, env=env, cwd=cwd)


@pytest.mark.parametrize("script", ["bench.py", "chip_smoke.py"])
def test_no_tpu_is_an_error_not_a_number(script):
    """Without a TPU the measurement entry points exit non-zero naming the
    platforms jax found, and print no result."""
    p = _run([os.path.join(REPO, script)])
    assert p.returncode != 0
    assert "TPU" in p.stderr and "['cpu']" in p.stderr, p.stderr[-500:]
    assert p.stdout.strip() == "", p.stdout[-500:]


def test_unknown_device_kind_is_an_error_in_the_peaks_table():
    import bench

    row = bench.device_peaks("TPU v5 lite")
    assert row["bf16_flops"] == 197e12 and row["source"]
    with pytest.raises(ValueError, match="no published peaks"):
        bench.device_peaks("TPU v9 imaginary")
    with pytest.raises(ValueError, match="no published peaks"):
        bench.device_peaks("cpu")


def test_headline_and_mfu_are_per_chip(monkeypatch, capsys):
    """A mesh run over four chips reports img/s and MFU per chip, against
    the peak of the device_kind the children ran on."""
    import bench

    def child(dtype, ips):
        return {"ips": ips, "scan_ips": 0.0, "scan_k": 0, "layout": "NHWC",
                "dtype": dtype, "platform": "tpu",
                "device_kind": "TPU v5 lite", "device_count": 4, "chips": 4,
                "compile_s": 1.0, "loss": 7.4, "bytes_per_step": 1,
                "remat_policy": "", "fused_epilogue": False}

    fake = {"bfloat16": child("bfloat16", 8000.0),
            "float32": child("float32", 4000.0)}
    monkeypatch.setattr(bench, "_run_child", lambda dtype: fake[dtype])
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    for var in ("BENCH_CHILD", "BENCH_DISPATCH", "BENCH_OBSERVATORY",
                "BENCH_SHARDING", "BENCH_RECOMMENDER", "BENCH_COLD_CHILD",
                "BENCH_COLD_START"):
        monkeypatch.delenv(var, raising=False)
    bench.main()
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["value"] == 2000.0 and out["chips"] == 4
    assert out["fp32_ips"] == 1000.0
    assert out["bf16_mfu"] == round(
        2000.0 * bench.FLOPS_PER_IMAGE_TRAIN / 197e12, 3)
    fake["bfloat16"]["device_kind"] = "TPU v9 imaginary"
    with pytest.raises(ValueError, match="no published peaks"):
        bench.main()


def test_accelerator_context_raises_on_a_cpu_only_host():
    """mx.tpu()/mx.gpu() never resolve to a CPU device."""
    import incubator_mxnet_tpu as mx

    assert mx.num_tpus() == 0 and mx.num_gpus() == 0
    for ctx in (mx.tpu(), mx.gpu()):
        with pytest.raises(RuntimeError, match="needs an accelerator"):
            ctx.jax_device()
    assert mx.cpu().jax_device().platform == "cpu"


_PRINT_CACHE_DIR = (
    "import jax; from incubator_mxnet_tpu import compile_cache as c; "
    "d = c.enable_jax_cache(); "
    "print(d); print(jax.config.jax_compilation_cache_dir)")


def test_cache_dir_is_the_environments_when_set(tmp_path):
    want = str(tmp_path / "from-env")
    p = _run(["-c", _PRINT_CACHE_DIR], JAX_COMPILATION_CACHE_DIR=want)
    assert p.returncode == 0, p.stderr[-1000:]
    assert p.stdout.split() == [want, want]


def test_cache_dir_is_one_in_checkout_path_across_processes(tmp_path):
    """Unset, the cache sits at <checkout>/.jax_cache, wherever the
    process starts and whatever its pid or clock say."""
    outs = []
    for cwd in (REPO, str(tmp_path)):
        p = _run(["-c", _PRINT_CACHE_DIR], cwd=cwd)
        assert p.returncode == 0, p.stderr[-1000:]
        outs.append(p.stdout.split())
    want = os.path.join(REPO, ".jax_cache")
    assert outs == [[want, want], [want, want]]


def test_no_entry_point_places_the_cache_itself():
    """Only compile_cache.enable_jax_cache chooses the directory."""
    offenders = []
    for root in (REPO, os.path.join(REPO, "tools"),
                 os.path.join(REPO, "incubator_mxnet_tpu")):
        for name in os.listdir(root):
            if not name.endswith(".py"):
                continue
            with open(os.path.join(root, name)) as f:
                text = f.read()
            if ("JAX_COMPILATION_CACHE_DIR" in text
                    or "jax_compilation_cache_dir" in text) \
                    and name != "compile_cache.py":
                offenders.append(name)
    assert not offenders, offenders


def test_importing_a_tool_leaves_the_environment_alone():
    """A tool imported into a test process must not leak settings (a
    compile-cache directory, a platform) into later subprocesses."""
    env_before = dict(os.environ)
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import importlib
        import benchmark_score as bs
        importlib.reload(bs)
    finally:
        sys.path.pop(0)
    assert dict(os.environ) == env_before


@pytest.mark.skipif(not os.environ.get("MXTPU_NIGHTLY"),
                    reason="two small inference compiles; nightly tier")
def test_benchmark_score_inference_sweep_executes(tmp_path):
    """The inference benchmark (benchmark_score analog, ref:
    example/image-classification/benchmark_score.py) must execute its
    full sweep — on-device param regen, per-batch AND scan modes, both
    dtypes — so the tool is proven before a live chip window."""
    import subprocess

    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jc")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "benchmark_score.py"),
         "--models", "resnet18_v1", "--batch", "4", "--image", "32",
         "--iters", "2", "--scan", "2", "--platform", "cpu"],
        capture_output=True, text=True, timeout=900, env=env)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.strip().splitlines()]
    rows = [r for r in lines if "model" in r]
    assert {r["dtype"] for r in rows} == {"bfloat16", "float32"}
    for r in rows:
        assert "error" not in r, r
        assert r["ips"] > 0 and r["scan_ips"] > 0
    summary = lines[-1]
    assert summary["metric"] == "inference_images_per_sec"
    assert len(summary["results"]) == 2
    # the int8 path (as_chain + quantize_net + int8 MXU program) must
    # also execute end-to-end
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "benchmark_score.py"),
         "--models", "alexnet", "--batch", "4", "--image", "64",
         "--iters", "2", "--scan", "2", "--dtypes", "int8",
         "--platform", "cpu"],
        capture_output=True, text=True, timeout=900, env=env)
    assert p.returncode == 0, p.stderr[-2000:]
    rows = [json.loads(ln) for ln in p.stdout.strip().splitlines()]
    int8 = [r for r in rows if r.get("dtype") == "int8"][0]
    assert "error" not in int8, int8
    assert int8["ips"] > 0 and int8["scan_ips"] > 0


@pytest.mark.skipif(not os.environ.get("MXTPU_NIGHTLY"),
                    reason="two program compiles + calibration; nightly tier")
def test_perf_analysis_infer_executes(tmp_path):
    """The offline inference-program analysis (perf_analysis_infer) must
    run end-to-end and report the structural facts the TPU mapping
    relies on: all resnet convs bf16 (NHWC), all int8 convs accumulating
    in i32."""
    import subprocess

    report = tmp_path / "infer.md"
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jc")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "perf_analysis_infer.py"),
         "--batch-resnet", "4", "--batch-alexnet", "4", "--image", "64",
         "--scan", "2", "--report", str(report)],
        capture_output=True, text=True, timeout=1200, env=env)
    assert p.returncode == 0, p.stderr[-2000:]
    rows = [json.loads(ln) for ln in p.stdout.strip().splitlines()]
    assert len(rows) == 3
    resnet, alexnet, resnet_i8 = rows
    assert set(resnet["conv_out_dtypes"]) == {"bf16"}
    assert resnet["nhwc_convs"] == resnet["convolutions"]
    assert set(alexnet["conv_out_dtypes"]) == {"i32"}
    assert alexnet["v5e_roofline_img_per_s"] > 0
    # int8 resnet: every conv (incl. residual-unit bodies + projection
    # shortcuts) accumulates in i32 — no fp32 conv islands in the HLO
    assert set(resnet_i8["conv_out_dtypes"]) == {"i32"}
    assert resnet_i8["convolutions"] == resnet["convolutions"]
    assert resnet_i8["v5e_roofline_img_per_s"] > 0
    assert "ROOFLINE" in report.read_text()
