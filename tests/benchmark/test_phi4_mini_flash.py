"""The cell `phi4mf_serve_reason_deep` on the CPU: it loads from files alone,
its rehearsal walks the job end to end against the reference, the
configuration's keys give the published parameter count, the new kernel
costs count what they say and the op classes find the new kernels by name.
No speed is measured here."""
import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import loader, roofline, tracered

CELL = "phi4mf_serve_reason_deep"
NEW_METRICS = {"attn_shared_kv_time_share", "attn_window_time_share",
               "paged_diff_attention_roofline",
               "paged_diff_attention_ring_roofline", "ssm_time_share",
               "selective_scan_roofline", "prefill_device_ms_per_ktok.sat"}


def test_the_cell_loads_from_files_alone():
    cell = loader.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "phi4_mini_flash", "backlog_reason_deep", 1)
    assert callable(loader.load_job(cell.traffic))
    assert callable(loader.load_reference(cell.config))
    assert {m.name for m in cell.end_to_end} == {"serve_out_tok_per_s",
                                                 "setup_s"}
    reported = {m.name for m in cell.per_layer}
    assert NEW_METRICS <= reported
    # the accepted kernel's roofline is not this cell's: another entry
    # point does the reading
    assert "paged_decode_attention_roofline" not in reported
    mix, serving = cell.traffic, cell.config["serving"]
    assert (mix["prompt_len"]["max"] + mix["output_len"]["max"]
            == serving["max_len"] == 7168)
    assert cell.config["reduced"] == [] and serving["slots"] == 16


def test_the_configuration_counts_3_85_billion_parameters_from_its_keys():
    from incubator_mxnet_tpu.models import sambay
    from benchmark.jobs import serve_hybrid

    config = loader.load_cell(CELL).config
    cfg = serve_hybrid.model_config(config)
    d, f, V = 2560, 10240, 200064
    mlp = 3 * d * f + 4 * d            # gate, up, down; two LayerNorms
    mamba = (d * 4 * d + 5 * 2 * d     # in; conv (4 + bias), channels 2 d
             + 2 * d * (160 + 32) + 160 * 2 * d + 2 * d   # x, dt, dt bias
             + 16 * 2 * d + 2 * d + 2 * d * d)            # A, D, out
    attn = 2 * d * d + 2 * d * d // 2 + 4 * 64 + 128      # q, o; k, v
    gmu = 2 * d * 2 * d
    cross = 2 * d * d + 4 * 64 + 128
    want = (9 * (mamba + mlp) + 9 * (attn + mlp) + 7 * (gmu + mlp)
            + 7 * (cross + mlp) + V * d + 2 * d)
    assert sambay.param_count(cfg) == want == config["parameters"]
    assert round(want / 1e9, 2) == 3.85                    # "3.8B"
    assert config["flops_per_item"] == pytest.approx(2 * want, rel=0.01)


def test_the_file_keeps_every_number_of_the_catalogs_entry():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "Phi-4-mini-flash-reasoning")
    config = loader.load_json("configs", "phi4_mini_flash")
    assert config["source"] == entry["source_url"]
    assert {k: config.get(k) for k in entry["config"]} == entry["config"]


# a fault patched into the served model (never into the reference) before
# benchmark/run.py runs as the driver runs it
FAULTS = {
    None: "",
    "state_at_the_padded_end": (
        "from incubator_mxnet_tpu.models import sambay\n"
        "mix = sambay._mamba_mix\n"
        "sambay._mamba_mix = lambda lp, h, conv, ssm, n_real, cfg: "
        "mix(lp, h, conv, ssm, None, cfg)\n"),
    "ring_one_page_short": (
        "from incubator_mxnet_tpu.models import sambay\n"
        "pages = sambay.ring_pages\n"
        "sambay.ring_pages = lambda cfg, page_size: "
        "pages(cfg, page_size) - 1\n"),
}


def _rehearse(fault, seed):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=loader.ROOT)
    env.pop("XLA_FLAGS", None)
    argv = ["benchmark/run.py", "--workload", CELL, "--seed", str(seed),
            "--seconds", "2", "--trace", "0", "--rehearse"]
    code = (FAULTS[fault] + f"import runpy, sys\nsys.argv = {argv!r}\n"
            "runpy.run_path('benchmark/run.py', run_name='__main__')\n")
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=loader.ROOT,
                       capture_output=True, text=True, timeout=300)
    lines = p.stdout.strip().splitlines()
    assert len(lines) >= 2, p.stderr[-2000:]
    return (p.returncode, json.loads(lines[-1]),
            json.loads(lines[-2])["detail"])


def test_the_rehearsal_walks_the_cell_and_the_reference_agrees():
    rc, result, detail = _rehearse(None, 2147483900)
    assert rc == 0
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"serve_out_tok_per_s", "setup_s"}
    assert detail["dense_fallbacks"] == 0 and len(detail["check_buckets"]) == 2
    assert detail["attended_tokens"]["shared_kv"] > 0
    # one warm-up request per slot, each token the largest of the row the
    # program left on the device, each compared row within the limit
    mix = loader.load_cell(CELL, rehearse=True).traffic
    assert len(detail["check_prompt_lens"]) == detail["slots"]
    assert detail["tokens_are_rows_argmax"] is True
    assert 0 < detail["worst_logit_err_std"] <= mix["logit_tol_std"]
    assert len(detail["logit_err_std_by_row"]) == 2 * mix["check_rows"]


@pytest.mark.parametrize("fault", [f for f in FAULTS if f])
def test_the_harness_own_comparison_refuses_a_fault(fault):
    rc, result, detail = _rehearse(fault, 2147483901)
    mix = loader.load_cell(CELL, rehearse=True).traffic
    # run.py's verdict is the line's `correct`; its exit code stays 0
    assert rc == 0 and result["correct"] is False
    assert detail["worst_logit_err_std"] > 3 * mix["logit_tol_std"]


def test_kernel_costs_count_each_reading_layers_rows_once():
    shared = loader.load_callable("kernel_costs",
                                  "paged_diff_attention.py:shared_cost")
    ring = loader.load_callable("kernel_costs",
                                "paged_diff_attention.py:ring_cost")
    cache = {"n_kv_heads": 20, "head_dim": 64, "itemsize": 2}
    # one step, one slot 3000 tokens deep: the shared cache read by 8
    # layers, 8 window layers clipped to 512
    facts = {"cache": cache, "traced": {"attended": {
        "shared_kv": 8 * 3000, "window_kv": 8 * 512}}}
    flops, nbytes = shared(facts)
    assert nbytes == 8 * 3000 * 20 * 64 * 2 * 2      # K and V, 2 bytes each
    assert flops == 8 * 3000 * 10 * 4 * 3 * 64 * 2   # 4 rows: q.k 64, p.v 128
    assert ring(facts)[1] == 8 * 512 * 20 * 64 * 2 * 2
    assert shared({"cache": cache}) is None            # an untraced run
    least, side = roofline.seconds(flops, nbytes, loader.peaks("TPU v5 lite"))
    assert side == "memory" and least == pytest.approx(nbytes / 819e9)


def test_selective_scan_cost_moves_a_decode_rows_state_and_a_prompts_rows():
    cost = loader.load_callable("kernel_costs", "selective_scan.py:cost")
    cache = {"recurrent_layers": 9, "d_inner": 5120, "d_state": 16}
    # 16 decode rows (one step of 16 slots) and a 3000-token prompt
    flops, nbytes = cost({"cache": cache, "traced": {
        "decode_tokens": 16, "prefill_tokens": 3000}})
    rows = 3016
    assert nbytes == 4 * 9 * (rows * (3 * 5120 + 32) + 16 * 2 * 16 * 5120)
    assert flops == 7 * 9 * rows * 16 * 5120
    assert cost({"cache": cache, "traced": {"kv_tokens": 5}}) is None


@pytest.mark.parametrize("op,cls", [
    ("%selective_scan.2 = (f32[16,1,5120]{2,1,0}, f32[16,16,5120]{2,1,0}) "
     "custom-call(f32[16,1,5120]{2,1,0} %dt), "
     "custom_call_target=\"tpu_custom_call\"", "selective_scan"),
    ("%paged_diff_attention_ring.7 = f32[16,10,4,256]{3,2,1,0} custom-call("
     "s32[16,33]{1,0} %a), custom_call_target=\"tpu_custom_call\"",
     "paged_diff_attention_ring"),
    ("%paged_diff_attention.12 = f32[16,10,4,256]{3,2,1,0} custom-call("
     "s32[16,448]{1,0} %a), custom_call_target=\"tpu_custom_call\"",
     "paged_diff_attention"),
    ("%paged_kv_write.3 = bf16[8,20,529,16,128]{4,3,2,1,0} custom-call("
     "s32[1]{0} %l), custom_call_target=\"tpu_custom_call\"",
     "paged_kv_write"),
    ("%fusion.41 = f32[16,20480]{1,0} fusion(%p), kind=kOutput",
     "matmul_fusion"),
    ("%fusion.9 = f32[16,16,5120]{2,1,0} fusion(%p), kind=kLoop",
     "elementwise_or_reduce_fusion"),
])
def test_op_classes_find_the_new_kernels_by_name(op, cls):
    classify = tracered.classifier(loader.load_opclasses("serve_hybrid"))
    assert classify(op) == cls
