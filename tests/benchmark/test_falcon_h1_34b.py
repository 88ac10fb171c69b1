"""The cell `falconh1_serve_doc_chat` on the CPU: it loads from files alone,
its rehearsal walks the job end to end against the reference, the
configuration keeps every published number, the new kernel costs count what
they say, the op classes find the kernels by name, and the cell's own
comparison refuses the faults the mix file lists. No speed is measured here."""
import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import loader, roofline, tracered

CELL = "falconh1_serve_doc_chat"
NEW_METRICS = {"ssd_state_time_share", "ssd_state_update_roofline",
               "attn_paged_kv_time_share"}
APPENDED = {"engine_decode_occupancy.sat", "decode_step_device_ms.sat",
            "itl_p95_s.sat", "stall_share.sat", "pool_live_page_share.sat",
            "engine_host_ms_per_step.sat",
            "engine_exposed_idle_ms_per_step.sat",
            "prefill_device_ms_per_ktok.sat",
            "paged_decode_attention_roofline"}


def test_the_cell_loads_from_files_alone():
    cell = loader.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "falcon_h1_34b", "backlog_doc_chat", 1)
    assert callable(loader.load_job(cell.traffic))
    assert callable(loader.load_reference(cell.config))
    assert {m.name for m in cell.end_to_end} == {"serve_out_tok_per_s",
                                                 "setup_s"}
    assert {m.name for m in cell.per_layer} == NEW_METRICS | APPENDED
    for m in cell.per_layer:       # every cost file resolves too
        if "cost" in m.args:
            assert callable(loader.load_callable("kernel_costs",
                                                 m.args["cost"]))
    bj = loader.benchmark_json()
    assert len(bj["workloads"]) == 7
    assert sum(w["chips"] == 4 for w in bj["workloads"]) == 1


def test_the_traffic_is_the_issues_table():
    cell = loader.load_cell(CELL)
    mix, serving = cell.traffic, cell.config["serving"]
    assert mix["arrivals"] == {"process": "backlog", "min_queued_per_slot": 2}
    assert mix["prompt_len"] == mix["output_len"] == {
        "dist": "uniform", "min": 1024, "max": 2048}
    assert (mix["prompt_len"]["max"] + mix["output_len"]["max"]
            == serving["max_len"] == 4096)
    assert mix["requests"] == 6 * serving["slots"] and mix["trace_seed"] == 22
    assert mix["stagger_first"] == {"min": 0.01, "max": 1.0}
    assert (mix["trace_seconds"], mix["check_new_tokens"],
            mix["check_rows"]) == (8, 160, 6)
    assert serving["slots"] in (32, 24, 16) and serving["page_size"] == 16
    assert (serving["dtype"], serving["state_dtype"]) == ("bfloat16",
                                                          "float32")


def test_the_file_keeps_every_number_of_the_catalogs_entry_but_the_depth():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "Falcon-H1-34B-Instruct")
    config = loader.load_json("configs", "falcon_h1_34b")
    assert config["source"] == entry["source_url"]
    differs = {k for k, v in entry["config"].items() if config.get(k) != v}
    # the contract's list is BENCHMARK.json's (the file's own stays []:
    # its `reduced_why`)
    declared = next(c for c in loader.benchmark_json()["configs"]
                    if c["name"] == "falcon_h1_34b")
    assert differs == set(declared["reduced"]) == {"num_hidden_layers"}
    assert declared["source"] == config["source"]
    assert config["num_hidden_layers"] == 6
    assert config["published"]["num_hidden_layers"] == (
        entry["config"]["num_hidden_layers"]) == 72


# a fault patched into the served model (never into the reference, nor into
# the weights: it goes in once they are made) before benchmark/run.py runs
# as the driver runs it; `fh` is models.falcon_h1
_AFTER_INIT = (
    "import dataclasses, jax\n"
    "from incubator_mxnet_tpu.models import falcon_h1 as fh\n"
    "def fault():\n{body}\n"
    "init = fh.init_params\n"
    "def init_then_fault(*a, **k):\n"
    "    params = init(*a, **k)\n"
    "    fault()\n"
    "    return params\n"
    "fh.init_params = init_then_fault\n")
FAULTS = {
    None: "",
    "attention_left_out": _AFTER_INIT.format(body=(
        "    mix = fh._attn_mix\n"
        "    def without(*a, **k):\n"
        "        out, handed = mix(*a, **k)\n"
        "        return out * 0, handed\n"
        "    fh._attn_mix = without\n")),
    "mamba_left_out": _AFTER_INIT.format(body=(
        "    mix = fh._mamba2_mix\n"
        "    def without(*a, **k):\n"
        "        out, handed = mix(*a, **k)\n"
        "        return out * 0, handed\n"
        "    fh._mamba2_mix = without\n")),
    "state_at_the_padded_end": _AFTER_INIT.format(body=(
        "    mix = fh._mamba2_mix\n"
        "    fh._mamba2_mix = lambda lp, h, conv, recur, n_real, cfg: "
        "mix(lp, h, conv, recur, None, cfg)\n")),
    "key_multiplier_left_out": _AFTER_INIT.format(body=(
        "    mix = fh._attn_mix\n"
        "    fh._attn_mix = lambda lp, h, pos, attend, cfg: mix(lp, h, pos, "
        "attend, dataclasses.replace(cfg, key_multiplier=1.0))\n")),
    "state_in_bfloat16_between_programs": _AFTER_INIT.format(body=(
        "    def rounded(fn):\n"
        "        def wrapped(*a, **k):\n"
        "            y, state = fn(*a, **k)\n"
        "            return y, jax.lax.reduce_precision(state, 8, 7)\n"
        "        return wrapped\n"
        "    fh.ssd_state_update = rounded(fh.ssd_state_update)\n"
        "    fh.ssd_chunk_scan = rounded(fh.ssd_chunk_scan)\n")),
    "activations_in_float8": _AFTER_INIT.format(body=(
        "    mm = fh._mm\n"
        "    fh._mm = lambda h, w: mm(jax.lax.reduce_precision(h, 4, 3), w)\n")),
}
# what the rehearsal's comparison sees (bfloat16 against float32 at three
# layers of d 64; the rounded state it does not: its worst row is a
# prefill's, which no stored state reaches); the chip's readings of all six
# are in the mix file
SEEN_ON_THE_CPU = [f for f in FAULTS
                   if f and f != "state_in_bfloat16_between_programs"]


def run_cell(fault, seed, extra=()):
    """benchmark/run.py as the driver runs it, `fault` patched in first.
    Returns (exit code, the result line, the detail line)."""
    env = dict(os.environ, PYTHONPATH=loader.ROOT)
    argv = ["benchmark/run.py", "--workload", CELL, "--seed", str(seed),
            *extra]
    code = (FAULTS[fault] + f"import runpy, sys\nsys.argv = {argv!r}\n"
            "runpy.run_path('benchmark/run.py', run_name='__main__')\n")
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=loader.ROOT,
                       capture_output=True, text=True, timeout=1500)
    lines = p.stdout.strip().splitlines()
    assert len(lines) >= 2, p.stderr[-2000:]
    return (p.returncode, json.loads(lines[-1]),
            json.loads(lines[-2])["detail"])


def _rehearse(fault, seed):
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.pop("XLA_FLAGS", None)
    return run_cell(fault, seed, ("--seconds", "2", "--trace", "0",
                                  "--rehearse"))


def test_the_rehearsal_walks_the_cell_and_the_reference_agrees(monkeypatch):
    monkeypatch.setattr(os, "environ", dict(os.environ))
    rc, result, detail = _rehearse(None, 2147483900)
    assert rc == 0
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"serve_out_tok_per_s", "setup_s"}
    assert detail["dense_fallbacks"] == 0 and len(detail["check_buckets"]) == 2
    assert detail["attended_tokens"]["paged_kv"] > 0
    assert 0 < detail["fetched_fill_share"] <= 1
    # one warm-up request per slot, the first two a row short of a bucket,
    # each token the largest of the row the program left on the device
    mix = loader.load_cell(CELL, rehearse=True).traffic
    lens = detail["check_prompt_lens"]
    assert len(lens) == detail["slots"] and lens[:2] == [31, 15]
    assert detail["tokens_are_rows_argmax"] is True
    assert 0 < detail["worst_logit_err_std"] <= mix["logit_tol_std"]
    assert len(detail["logit_err_std_by_row"]) == 2 * mix["check_rows"]
    assert detail["cache_kinds"]["recurrent"]["layers"] == 3


@pytest.mark.parametrize("fault", SEEN_ON_THE_CPU)
def test_the_harness_own_comparison_refuses_a_fault(fault, monkeypatch):
    monkeypatch.setattr(os, "environ", dict(os.environ))
    rc, result, detail = _rehearse(fault, 2147483901)
    mix = loader.load_cell(CELL, rehearse=True).traffic
    # run.py's verdict is the line's `correct`; its exit code stays 0
    assert rc == 0 and result["correct"] is False
    assert detail["worst_logit_err_std"] > 2.5 * mix["logit_tol_std"]


def test_the_state_update_cost_moves_every_live_slots_state_once():
    cost = loader.load_callable("kernel_costs", "ssd.py:state_update_cost")
    cache = {"recurrent_layers": 6, "ssm_heads": 32, "ssm_head_dim": 128,
             "d_state": 256, "n_groups": 2}
    # one decode step of 32 live slots
    flops, nbytes = cost({"cache": cache, "traced": {"decode_tokens": 32,
                                                     "prefill_tokens": 1500}})
    state = 32 * 128 * 256
    assert 4 * 2 * state == 8388608                  # 8.39 MB a slot, layer
    assert nbytes == 4 * 6 * 32 * (2 * state + 2 * 4096 + 32 + 2 * 2 * 256)
    assert flops == 5 * 6 * 32 * state
    assert cost({"cache": cache, "traced": {"kv_tokens": 5}}) is None
    assert cost({"cache": {"recurrent_layers": 9}, "traced": {
        "decode_tokens": 16}}) is None                 # another job's facts
    least, side = roofline.seconds(flops, nbytes, loader.peaks("TPU v5 lite"))
    assert side == "memory" and least == pytest.approx(nbytes / 819e9)


def test_the_accepted_attention_cost_reads_this_models_kv_heads():
    cost = loader.load_callable("kernel_costs",
                                "paged_decode_attention.py:cost")
    # one step, one slot 2300 deep: 4 K/V heads of 128, K and V, 6 layers
    flops, nbytes = cost({"kv": {"n_heads": 4, "head_dim": 128, "n_layers": 6,
                                 "itemsize": 2},
                          "traced": {"kv_tokens": 2300}})
    assert nbytes == 2300 * 4 * 128 * 2 * 2 * 6 == 2300 * 12288


@pytest.mark.parametrize("op,cls", [
    ("%ssd_state_update.2 = (f32[32,128,32]{2,1,0}, f32[6,32,32,128,256]"
     "{4,3,2,1,0}) custom-call(s32[1]{0} %l), "
     "custom_call_target=\"tpu_custom_call\"", "ssd_state_update"),
    ("%paged_decode_attention.7 = f32[32,4,5,256]{3,2,1,0} custom-call("
     "s32[32,256]{1,0} %a), custom_call_target=\"tpu_custom_call\"",
     "paged_decode_kernel"),
    ("%paged_kv_write.3 = bf16[6,4,8193,16,256]{4,3,2,1,0} custom-call("
     "s32[1]{0} %l), custom_call_target=\"tpu_custom_call\"",
     "paged_kv_write"),
    ("%fusion.41 = f32[32,43008]{1,0} fusion(%p), kind=kOutput",
     "matmul_fusion"),
    ("%fusion.9 = f32[32,5120]{1,0} fusion(%p), kind=kLoop",
     "elementwise_or_reduce_fusion"),
])
def test_op_classes_find_the_kernels_by_name(op, cls):
    classify = tracered.classifier(
        loader.load_opclasses("serve_parallel_hybrid"))
    assert classify(op) == cls
