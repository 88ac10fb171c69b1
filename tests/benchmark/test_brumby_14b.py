"""The cell `brumby14b_serve_longdoc_gen` on the CPU: it loads from files
alone, its rehearsal walks the job end to end against the reference, the
configuration keeps every published number, the new kernel cost counts what
it says, the op classes find the kernels by name, and the cell's own
comparison refuses the faults the mix file lists. No speed is measured here."""
import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import loader, roofline, tracered

CELL = "brumby14b_serve_longdoc_gen"
NEW_METRICS = {"power_retention_step_roofline", "retention_state_time_share",
               "decode_step_device_ms.retention"}


def test_the_cell_loads_from_files_alone():
    cell = loader.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "brumby_14b", "backlog_longdoc_longgen", 1)
    assert callable(loader.load_job(cell.traffic))
    assert callable(loader.load_reference(cell.config))
    assert {"serve_out_tok_per_s", "setup_s"} <= {
        m.name for m in cell.end_to_end}
    # later PRs may append the cell to shared entries: the new ones at least
    assert NEW_METRICS <= {m.name for m in cell.per_layer}
    for m in cell.per_layer:
        if m.name in NEW_METRICS:
            assert m.entry["moves"] == "serve_out_tok_per_s"
            assert CELL in m.entry["workloads"]
        if "cost" in m.args:
            assert callable(loader.load_callable("kernel_costs",
                                                 m.args["cost"]))
    cells = loader.benchmark_json()["workloads"]
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)


def test_the_traffic_is_the_cells_own():
    cell = loader.load_cell(CELL)
    mix, serving = cell.traffic, cell.config["serving"]
    assert mix["job"] == "serve_retention"
    assert mix["arrivals"] == {"process": "backlog", "min_queued_per_slot": 2}
    assert mix["prompt_len"] == {"dist": "uniform", "min": 4096, "max": 8192}
    assert mix["output_len"] == {"dist": "uniform", "min": 1024, "max": 2048}
    assert (mix["prompt_len"]["max"] + mix["output_len"]["max"]
            == serving["max_len"] == 10240)
    assert mix["requests"] == 6 * serving["slots"]
    assert mix["stagger_first"] == {"min": 0.01, "max": 1.0}
    assert mix["trace_seconds"] == 8
    assert serving["slots"] in (16, 12, 8)
    assert (serving["dtype"], serving["state_dtype"]) == ("bfloat16",
                                                          "float32")


# the source's config.json as published, every key that sizes the model
PUBLISHED_CONFIG = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 17408,
    "max_position_embeddings": 32768, "max_window_layers": 40,
    "model_type": "brumby", "num_attention_heads": 40,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


def test_the_file_keeps_every_published_number_but_the_depth():
    config = loader.load_json("configs", "brumby_14b")
    assert config["source"] == ("https://huggingface.co/manifestai/"
                                "Brumby-14B-Base/blob/main/config.json")
    differs = {k for k, v in PUBLISHED_CONFIG.items() if config.get(k) != v}
    declared = next(c for c in loader.benchmark_json()["configs"]
                    if c["name"] == "brumby_14b")
    assert config["reduced"] == declared["reduced"]
    assert differs == set(declared["reduced"]) == {"num_hidden_layers"}
    assert declared["source"] == config["source"]
    assert config["num_hidden_layers"] == 8
    assert config["published"]["num_hidden_layers"] == 40


# a fault patched into the served model (never into the reference, nor into
# the weights: it goes in once they are made) before benchmark/run.py runs
# as a check runs it; `br` is models.brumby, `pk` ops.pallas_kernels
_AFTER_INIT = (
    "import jax\n"
    "from incubator_mxnet_tpu.models import brumby as br, falcon_h1 as fh\n"
    "from incubator_mxnet_tpu.ops import pallas_kernels as pk\n"
    "def fault():\n{body}\n"
    "init = br.init_params\n"
    "def init_then_fault(*a, **k):\n"
    "    params = init(*a, **k)\n"
    "    fault()\n"
    "    return params\n"
    "br.init_params = init_then_fault\n")
FAULTS = {
    None: "",
    "retention_left_out": _AFTER_INIT.format(body=(
        "    mix = br._retention_mix\n"
        "    def without(*a, **k):\n"
        "        out, handed = mix(*a, **k)\n"
        "        return out * 0, handed\n"
        "    br._retention_mix = without\n")),
    "gate_left_out": _AFTER_INIT.format(body=(
        "    mix = br._retention_mix\n"
        "    br._retention_mix = lambda lp, h, pos, retain, cfg: mix(\n"
        "        lp, h, pos, lambda q, k, v, g: retain(q, k, v, g * 0), "
        "cfg)\n")),
    "normaliser_left_out": _AFTER_INIT.format(body=(
        "    pk._normalised = lambda num, den: num\n")),
    "activations_in_float8": _AFTER_INIT.format(body=(
        "    for m in (br, fh):\n"
        "        m._mm = (lambda mm: lambda h, w: mm(\n"
        "            jax.lax.reduce_precision(h, 4, 3), w))(m._mm)\n")),
    "state_at_the_padded_end": _AFTER_INIT.format(body=(
        "    chunked = br._chunked\n"
        "    br._chunked = lambda cfg, n_real=None: chunked(cfg)\n")),
    "state_in_bfloat16_at_every_step": _AFTER_INIT.format(body=(
        "    def rounded(fn):\n"
        "        def wrapped(*a, **k):\n"
        "            *out, state, norm = fn(*a, **k)\n"
        "            return (*out, jax.lax.reduce_precision(state, 8, 7),\n"
        "                    jax.lax.reduce_precision(norm, 8, 7))\n"
        "        return wrapped\n"
        "    br.power_retention_step = rounded(br.power_retention_step)\n"
        "    br.power_retention_chunk = rounded(br.power_retention_chunk)\n")),
}
# what the rehearsal's comparison sees (bfloat16 against float32 at two
# layers of d 64); the rounded state it does not, and tests/test_brumby.py
# holds it at float32; the chip's readings of all six are in the mix file
SEEN_ON_THE_CPU = [f for f in FAULTS
                   if f and f != "state_in_bfloat16_at_every_step"]


def run_cell(fault, seed, extra=()):
    """benchmark/run.py as a check runs it, `fault` patched in first.
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


# run_cell for several faults in ONE process, one after another: each is
# patched in, benchmark/run.py's main() runs, and every attribute of the
# patched modules is put back before the next. The imports are paid once.
_IN_ONE_PROCESS = """
import contextlib, io, json, sys
from incubator_mxnet_tpu.models import brumby, falcon_h1
from incubator_mxnet_tpu.ops import pallas_kernels
from benchmark import run
modules = (brumby, falcon_h1, pallas_kernels)
for fault, code, argv in json.loads(sys.argv[1]):
    saved = [dict(vars(m)) for m in modules]
    exec(code, {})
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(argv)
    for m, before in zip(modules, saved):
        for k in set(vars(m)) - set(before):
            delattr(m, k)
        vars(m).update(before)
    print(json.dumps({"fault": fault, "rc": rc,
                      "lines": out.getvalue().strip().splitlines()}),
          flush=True)
"""


def run_cells(faults, seed, extra=()):
    """run_cell for each of `faults` in one process, each on `seed`.
    Returns {fault: (exit code, result, detail)}."""
    env = dict(os.environ, PYTHONPATH=loader.ROOT)
    runs = [(f, FAULTS[f], ["--workload", CELL, "--seed", str(seed),
                            *extra]) for f in faults]
    p = subprocess.run([sys.executable, "-c", _IN_ONE_PROCESS,
                        json.dumps(runs)], env=env, cwd=loader.ROOT,
                       capture_output=True, text=True, timeout=1500)
    got = {}
    for line in p.stdout.strip().splitlines():
        one = json.loads(line)
        lines = one["lines"]
        got[one["fault"]] = (one["rc"], json.loads(lines[-1]),
                             json.loads(lines[-2])["detail"])
    assert p.returncode == 0 and set(got) == set(faults), p.stderr[-2000:]
    return got


def _rehearsal_env():
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.pop("XLA_FLAGS", None)


def _rehearse(fault, seed, seconds=2):
    _rehearsal_env()
    return run_cell(fault, seed, ("--seconds", str(seconds), "--trace", "0",
                                  "--rehearse"))


def test_the_rehearsal_walks_the_cell_and_the_reference_agrees(monkeypatch):
    monkeypatch.setattr(os, "environ", dict(os.environ))
    rc, result, detail = _rehearse(None, 2147483900)
    assert rc == 0
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert {"serve_out_tok_per_s", "setup_s"} <= set(result["metrics"])
    assert detail["dense_fallbacks"] == 0 and len(detail["check_buckets"]) == 2
    # no page pool: the null page alone
    assert detail["pool_pages"] == 1
    mix = loader.load_cell(CELL, rehearse=True).traffic
    lens = detail["check_prompt_lens"]
    assert len(lens) == detail["slots"] and lens[:2] == [31, 15]
    assert detail["tokens_are_rows_argmax"] is True
    assert 0 < detail["worst_logit_err_std"] <= mix["logit_tol_std"]
    assert len(detail["logit_err_std_by_row"]) == 2 * mix["check_rows"]
    assert set(detail["cache_kinds"]) == {"recurrent", "logits"}


@pytest.fixture(scope="module")
def fault_rehearsals():
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(os, "environ", dict(os.environ))
        _rehearsal_env()
        # the check comes before the window: one second of it is enough
        return run_cells(SEEN_ON_THE_CPU, 2147483901,
                         ("--seconds", "1", "--trace", "0", "--rehearse"))


@pytest.mark.parametrize("fault", SEEN_ON_THE_CPU)
def test_the_harness_own_comparison_refuses_a_fault(fault, fault_rehearsals):
    rc, result, detail = fault_rehearsals[fault]
    mix = loader.load_cell(CELL, rehearse=True).traffic
    # run.py's verdict is the line's `correct`; its exit code stays 0
    assert rc == 0 and result["correct"] is False
    assert detail["worst_logit_err_std"] > 2.5 * mix["logit_tol_std"]


def test_the_state_step_cost_moves_every_live_slots_state_once():
    cost = loader.load_callable("kernel_costs",
                                "power_retention.py:state_step_cost")
    ret = {"layers": 8, "kv_heads": 8, "query_heads": 40, "head_dim": 128}
    # one decode step of 16 live slots
    flops, nbytes = cost({"retention": ret,
                          "traced": {"decode_tokens": 16,
                                     "prefill_tokens": 5000}})
    F = 128 * 129 // 2
    assert F == 8256
    # 34.1 MB a slot and layer: 8 K/V heads' state and normaliser, each
    # read and written once
    assert 4 * 8 * (128 * F + F) == 34_080_768
    assert nbytes == 4 * 8 * 16 * (8 * (2 * 128 * F + 2 * F + 2 * 128 + 1)
                                   + 2 * 40 * 128)
    assert flops == 8 * 16 * 8 * (3 * 128 * F + 2 * F + 2 * 5 * 129 * F
                                  + 2 * 6 * F)
    assert cost({"retention": ret, "traced": {"kv_tokens": 5}}) is None
    assert cost({"cache": {"recurrent_layers": 6}, "traced": {
        "decode_tokens": 16}}) is None                 # another job's facts
    least, side = roofline.seconds(flops, nbytes, loader.peaks("TPU v5 lite"))
    assert side == "memory" and least == pytest.approx(nbytes / 819e9)


@pytest.mark.parametrize("op,cls", [
    ("%power_retention_step.3 = (f32[16,8,8,128]{3,2,1,0}, "
     "f32[8,16,8,128,8320]{4,3,2,1,0}, f32[8,16,8,8320]{3,2,1,0}) "
     "custom-call(s32[1]{0} %l), custom_call_target=\"tpu_custom_call\"",
     "power_retention_step"),
    ("%power_retention_chunk.1 = (f32[1,8,5,8192,128]{4,3,2,1,0}, "
     "f32[1,8,128,8320]{3,2,1,0}) custom-call(f32[1,8,5,8192,128] %q), "
     "custom_call_target=\"tpu_custom_call\"", "power_retention_chunk"),
    ("%fusion.41 = f32[16,34816]{1,0} fusion(%p), kind=kOutput",
     "matmul_fusion"),
    # a prompt's gate and up, which XLA names for the bitcast it fuses
    ("%convolution_bitcast_fusion.5 = f32[1,8192,34816]{2,1,0} fusion("
     "bf16[8,5120,34816]{2,1,0} %p), kind=kOutput", "matmul_fusion"),
    ("%copy.45 = f32[1,8192,40,128]{3,1,2,0} copy(f32[1,8192,40,128]"
     "{3,2,1,0} %q)", "copy_or_format"),
    ("%fusion.9 = f32[16,5120]{1,0} fusion(%p), kind=kLoop",
     "elementwise_or_reduce_fusion"),
])
def test_op_classes_find_the_kernels_by_name(op, cls):
    classify = tracered.classifier(loader.load_opclasses("serve_retention"))
    assert classify(op) == cls
