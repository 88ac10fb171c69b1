"""The benchmark's harness on the CPU: the loader finds everything by name,
new cells are files and entries only, the traffic generator repeats for a
seed, the statistics follow their rule, the seeded weights have the model's
tree, and the result line has the contract's keys. No speed is measured
here."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from benchmark.harness import arrivals, loader, roofline, stats, weights

BENCH = loader.benchmark_json()
CELLS = [w["name"] for w in BENCH["workloads"]]


# ---------------------------------------------------------------------------
# loader
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", CELLS)
def test_loader_resolves_every_part_of_a_cell(name):
    cell = loader.load_cell(name)
    assert callable(loader.load_job(cell.traffic))
    assert callable(loader.load_reference(cell.config))
    assert loader.load_opclasses(cell.traffic["job"])["classes"]
    e2e = {m.name for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:  # reported only where the metric it moves is
        assert m.entry["moves"] in e2e, (m.name, m.entry["moves"])
    # the configuration file lists every key it changed from its source,
    # as its entry in BENCHMARK.json does
    entry = next(c for c in BENCH["configs"] if c["name"] == cell.config_name)
    assert cell.config["reduced"] == entry["reduced"]
    rehearsal = loader.load_cell(name, rehearse=True)
    assert "rehearse" not in rehearsal.config
    assert rehearsal.config != cell.config


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_every_metric_has_a_file_and_a_reader(kind):
    used = {m.name for c in CELLS
            for m in getattr(loader.load_cell(c), kind)}
    assert used == {e["name"] for e in BENCH[kind]}


@pytest.mark.parametrize("call", [
    lambda: loader.load_cell("no_such_cell"),
    lambda: loader.load_json("traffic", "no_such_mix"),
    lambda: loader.load_job({"job": "no_such_job"}),
    lambda: loader.load_callable("readers", "host.py:no_such_reader"),
    lambda: loader.load_callable("readers", "no_such_file.py:read"),
    lambda: loader.peaks("TPU v99"),
])
def test_an_unknown_name_is_an_error_that_lists_the_known(call):
    with pytest.raises(loader.UnknownName):
        call()


@pytest.mark.parametrize("name", ["itl_p95_s", "decode_step_device_ms",
                                  "engine_decode_occupancy",
                                  "pool_live_page_share"])
def test_a_metrics_variants_read_one_file(name):
    """`<metric>.sat` and `<metric>.open` are two entries of BENCHMARK.json
    (each moves one end-to-end metric) and one file, <metric>.json."""
    spec = loader.load_json("layer_metrics", name)
    for variant in ("sat", "open"):
        assert f"{name}.{variant}" in {e["name"] for e in BENCH["per_layer"]}
        assert loader._metric_spec("layer_metrics",
                                   f"{name}.{variant}", loader.ROOT) == spec
    for unknown in (f"no_such_{name}.sat", f"no_such_{name}"):
        with pytest.raises(loader.UnknownName):
            loader._metric_spec("layer_metrics", unknown, loader.ROOT)


def test_stalls_stay_in_the_judged_rate_and_show_in_stall_share():
    """serve_out_tok_per_s is tokens over wall time; stall_share.sat says
    how much of that time steps far over the median took beyond it."""
    cell = loader.load_cell("gpt2xl_serve_decode_sat")
    rate = next(m for m in cell.end_to_end if m.name == "serve_out_tok_per_s")
    stall = next(m for m in cell.per_layer if m.name == "stall_share.sat")
    steps = [0.2] * 96 + [0.32] * 3 + [2.2]   # prefills are not stalls

    class Run:
        facts = {"out_tokens_spanned": 1600, "delivery_span_s": sum(steps),
                 "delivery_steps_s": steps}

    assert rate.reader(Run, **rate.args) == pytest.approx(1600 / sum(steps))
    assert stall.reader(Run, **stall.args) == pytest.approx(
        100 * 2.0 / sum(steps))
    Run.facts = {"out_tokens_spanned": 1600, "delivery_span_s": 20.0,
                 "delivery_steps_s": [0.2] * 100}
    assert stall.reader(Run, **stall.args) == 0.0
    Run.facts = {}
    assert stall.reader(Run, **stall.args) is None


def test_peaks_are_the_published_v5e_figures():
    p = loader.peaks("TPU v5 lite")
    assert (p["bf16_flops"], p["hbm_bytes_per_s"]) == (197e12, 819e9)
    assert p["source"]


# ---------------------------------------------------------------------------
# a later PR's cell: files and entries, no Python edited
# ---------------------------------------------------------------------------

CHAT_OPEN = {
    "who": "interactive chat (benchmark/README.md's worked example)",
    "job": "serve_engine",
    "arrivals": {"process": "gamma", "cv": 1.0, "rate_per_s": 2.0},
    "lead_in_s": 2.0, "drain_cap_s": 10.0,
    "slo": {"ttft_s": 1.0, "gap_s": 0.2},
    "prompt_len": {"dist": "lognormal", "median": 128, "sigma": 0.7,
                   "min": 32, "max": 512},
    "output_len": {"dist": "lognormal", "median": 192, "sigma": 0.6,
                   "min": 64, "max": 512},
    "check_new_tokens": 12, "logit_tol_std": 0.05, "trace_seconds": 4,
}

FAKE_JOB = '''
import time
import jax
import jax.numpy as jnp
from benchmark.harness.tracing import TailTrace, span


def run(ctx):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    float(f(x))
    tail = TailTrace(ctx, ctx.cell.traffic["trace_seconds"])
    before = ctx.compiles.count
    t0 = time.perf_counter()
    n = 0
    while time.perf_counter() - t0 < ctx.seconds:
        tail.tick(time.perf_counter() - t0)
        with span("bench.train_dispatch"):
            y = f(x)
        with span("bench.fetch"):
            float(y)
        n += 1
    tail.stop()
    return {"correct": True, "attempted": n, "failed": 0,
            "window_start": t0, "window_s": time.perf_counter() - t0,
            "compiles_in_window": ctx.compiles.count - before,
            "items": n, "chips": 1}
'''


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    """A copy of the benchmark to which a later PR's cells were added."""
    root = str(tmp_path_factory.mktemp("grown"))
    shutil.copy(os.path.join(loader.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(loader.ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = os.path.join(root, "benchmark")

    def write(rel, obj):
        with open(os.path.join(bench, rel), "w") as f:
            f.write(obj if isinstance(obj, str) else json.dumps(obj))

    bj = loader.benchmark_json(root)
    # the README's worked example: a new mix and one `workloads` entry
    write("traffic/chat_open.json", CHAT_OPEN)
    bj["workloads"].append({
        "name": "gpt2xl_serve_chat_open", "config": "gpt2_xl",
        "traffic": "chat_open", "chips": 1, "why": "worked example"})
    # a new per-layer metric reading a new fact through an existing reader
    write("layer_metrics/queue_share.json",
          {"reader": "host.py:ratio",
           "args": {"num": "queued", "den": ["attempted"], "percent": True}})
    bj["per_layer"].append({
        "name": "queue_share", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "serving.engine",
        "moves": "ttft_p90_s", "workloads": ["gpt2xl_serve_chat_open"]})
    for e in bj["end_to_end"] + bj["per_layer"]:
        if "gpt2xl_serve_prefill_open" in e.get("workloads", ()):
            e["workloads"].append("gpt2xl_serve_chat_open")
    # a new kind of job with its own mix, op classes and cell
    write("jobs/fake.py", FAKE_JOB)
    write("traffic/fake_mix.json", {"job": "fake", "trace_seconds": 0.3})
    shutil.copy(os.path.join(bench, "opclasses", "train_step.json"),
                os.path.join(bench, "opclasses", "fake.json"))
    bj["workloads"].append({
        "name": "fake_cell", "config": "resnet50_v1", "traffic": "fake_mix",
        "chips": 1, "why": "a job that compiles in a second"})
    for e in bj["end_to_end"] + bj["per_layer"]:
        if e["name"] in ("items_per_s_per_chip", "conv_time_share"):
            e["workloads"].append("fake_cell")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bj, f)
    return root


def test_a_new_mix_cell_and_metric_are_picked_up_from_files(grown):
    cell = loader.load_cell("gpt2xl_serve_chat_open", root=grown)
    assert cell.traffic["prompt_len"]["median"] == 128
    assert loader.load_job(cell.traffic, root=grown).__name__ == "run"
    names = {m.name for m in cell.per_layer}
    assert {"queue_share", "gen_lateness_p99_s"} <= names
    assert {m.name for m in cell.end_to_end} == {
        "ttft_p90_s", "itl_p90_s", "setup_s"}
    metric = next(m for m in cell.per_layer if m.name == "queue_share")

    class Run:
        facts = {"queued": 3, "attempted": 12}

    assert metric.reader(Run, **metric.args) == pytest.approx(25.0)
    # and the mix is one the general generator reads as it is
    reqs = arrivals.requests(cell.traffic, 50257, 1, seconds=30.0)
    assert sum(r.due_s >= 0 for r in reqs) == 60
    with pytest.raises(loader.UnknownName):
        loader.load_cell("gpt2xl_serve_chat_open")  # not in the real one


def _run(root, *args, pythonpath=loader.ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=pythonpath)
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *args],
        env=env, cwd=root, capture_output=True, text=True, timeout=120)


CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def test_result_line_has_exactly_the_contracts_keys(grown):
    """A new job file runs through run.py untouched, traced, on the CPU as
    a rehearsal: the last line is the contract's object and names `cpu`."""
    p = _run(grown, "--workload", "fake_cell", "--seed", "3", "--seconds",
             "1", "--trace", "1", "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert set(result) == CONTRACT_KEYS | {"breakdown", "checks"}
    # each number the verdict compared, with its limit: the line's last key
    # and the last lines on standard error
    assert list(result)[-1] == "checks"
    checks = result["checks"]
    assert {"failed", "compiles_in_window"} <= set(checks)
    assert all(set(c) == {"value", "limit"} for c in checks.values())
    assert p.stderr.strip().splitlines()[-len(checks):] == [
        f"check {name} {c['value']} limit {c['limit']}"
        for name, c in checks.items()]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    dev = result["device"]
    assert set(dev) == {"platform", "kind", "count", "memory_peak_bytes",
                        "busy_s", "window_s"}
    assert dev["platform"] == "cpu"  # a rehearsal says what it ran on
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert all(len(v) <= 10 for v in result["breakdown"].values())
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"}
    assert json.loads(lines[-2])["detail"]["rehearsal"] is True


def test_no_accelerator_or_no_program_is_an_error_not_a_result(grown):
    # the CPU is no accelerator unless the run is a rehearsal
    p = _run(grown, "--workload", "fake_cell", "--seed", "3", "--seconds",
             "1", "--trace", "0")
    assert p.returncode != 0 and not p.stdout.strip()
    assert "accelerator" in p.stderr
    # BENCHMARK.json and the benchmark's files alone, without the program
    p = _run(grown, "--workload", "fake_cell", "--seed", "3", "--seconds",
             "1", "--trace", "0", "--rehearse", pythonpath="")
    assert p.returncode != 0 and not p.stdout.strip()


def test_a_rehearsal_times_setup_without_the_chip(grown):
    """`setup_s` is the window's opening less the first `jax.devices()`
    (the runtime's start and the chip's hand-over); the detail line gives
    every stage in the order it ran, with `chip_s` beside them."""
    p = _run(grown, "--workload", "fake_cell", "--seed", "2900000001",
             "--seconds", "1", "--trace", "0", "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    result, detail = json.loads(lines[-1]), json.loads(lines[-2])["detail"]
    marks = detail["setup_reached_s"]
    assert list(marks)[:4] == ["imports", "chip", "compile_cache", "peaks"]
    assert list(marks.values()) == sorted(marks.values())
    assert detail["window_opened_s"] >= marks["peaks"]
    # the marks are rounded to 0.1 ms
    assert 0 < detail["chip_s"] <= marks["chip"] - marks["imports"] + 2e-4
    assert result["metrics"]["setup_s"]["value"] == pytest.approx(
        detail["window_opened_s"] - detail["chip_s"])
    assert detail["backend_started_by_imports"] is False


# ---------------------------------------------------------------------------
# a cell added as files and entries alone passes tests/benchmark unedited
# ---------------------------------------------------------------------------

ROOM_CELL = "gpt2xl_serve_unseen_trace"


@pytest.fixture(scope="module")
def room(tmp_path_factory):
    """A copy of the benchmark and of its tests with one more configuration
    (a copy cut in depth: the cut in its file and in its entry), one more
    mix (a copy under another name, replaying another trace), one more cell
    in every entry that lists its model's saturated cell, and a per-layer
    entry of its own. Not a file that was there is edited."""
    root = str(tmp_path_factory.mktemp("room"))
    for rel in ("BENCHMARK.json", "conftest.py", "pytest.ini"):
        shutil.copy(os.path.join(loader.ROOT, rel), root)
    os.makedirs(os.path.join(root, "tests"))
    shutil.copy(os.path.join(loader.ROOT, "tests", "conftest.py"),
                os.path.join(root, "tests"))
    for rel in ("benchmark", os.path.join("tests", "benchmark")):
        shutil.copytree(os.path.join(loader.ROOT, rel),
                        os.path.join(root, rel),
                        ignore=shutil.ignore_patterns("__pycache__"))

    def write(rel, obj):
        with open(os.path.join(root, "benchmark", rel), "w") as f:
            json.dump(obj, f)

    config = loader.load_json("configs", "gpt2_xl")
    config.update(name="gpt2_xl_half", n_layer=24, reduced=["n_layer"])
    write("configs/gpt2_xl_half.json", config)
    mix = loader.load_json("traffic", "backlog_short_in_long_out")
    mix["trace_seed"] += 1
    write("traffic/backlog_unseen_trace.json", mix)
    write("layer_metrics/requests_ended_share.json",
          {"what": "requests that ended over those attempted",
           "reader": "host.py:ratio",
           "args": {"num": "finished", "den": ["attempted"],
                    "percent": True}})
    bj = loader.benchmark_json()
    bj["configs"].append({
        "name": "gpt2_xl_half", "source": config["source"],
        "file": "benchmark/configs/gpt2_xl_half.json",
        "reduced": ["n_layer"], "why": "half the depth"})
    bj["workloads"].append({
        "name": ROOM_CELL, "config": "gpt2_xl_half",
        "traffic": "backlog_unseen_trace", "chips": 1,
        "why": "the saturated mix replaying a trace nobody tuned against"})
    for e in bj["end_to_end"] + bj["per_layer"]:
        if "gpt2xl_serve_decode_sat" in e.get("workloads", ()):
            e["workloads"].append(ROOM_CELL)
    bj["per_layer"].append({
        "name": "requests_ended_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "serving.engine",
        "moves": "serve_out_tok_per_s", "workloads": [ROOM_CELL]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bj, f, indent=2)
    return root


def test_a_cell_of_files_and_entries_alone_passes_these_tests(room):
    """tests/benchmark, as they are, on the copy: every check of the
    loader, the entries and the configurations that runs no cell holds
    with the new cell in it (the walks through cells are left out: they
    are each cell's own)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([room, loader.ROOT]))
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/benchmark", "-v",
         "-p", "no:cacheprovider", "-p", "no:randomly", "-k",
         "not rehearsal and not refuses_a_fault and not result_line and "
         "not no_accelerator and not entries_alone"],
        env=env, cwd=room, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-3000:]
    for case in (f"test_loader_resolves_every_part_of_a_cell[{ROOM_CELL}]",
                 "test_every_metric_has_a_file_and_a_reader[per_layer]",
                 "test_the_entries_are_the_last_five_and_every_older_one",
                 "test_new_entry_loads_in_its_cells[engine_stall_share.sat]",
                 "test_new_entry_loads_in_its_cell[engine_host_ms_per_step",
                 "test_falcon_h1_34b.py::test_the_cell_loads_from_files_alone"):
        assert any(case in line and "PASSED" in line
                   for line in p.stdout.splitlines()), case


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------

OPEN = loader.load_json("traffic", "poisson_long_in_short_out")
BACKLOG = loader.load_json("traffic", "backlog_short_in_long_out")


def _same(a, b, tokens=True):
    return (len(a) == len(b) and all(
        x.due_s == y.due_s and x.max_new_tokens == y.max_new_tokens
        and x.prompt.size == y.prompt.size
        and (not tokens or np.array_equal(x.prompt, y.prompt))
        for x, y in zip(a, b)))


@pytest.mark.parametrize("mix,kw", [(OPEN, {"seconds": 20.0}),
                                    (BACKLOG, {"slots": 16})])
def test_traffic_repeats_for_a_seed_and_differs_for_another(mix, kw):
    a = arrivals.requests(mix, 50257, 7, **kw)
    assert _same(a, arrivals.requests(mix, 50257, 7, **kw))
    b = arrivals.requests(mix, 50257, 8, **kw)
    assert not _same(a, b)
    # a mix with a trace_seed replays one trace: another seed, other token
    # ids, the same lengths, and due times within the mix's jitter
    jitter = mix.get("due_jitter_s", 0.0)
    assert "trace_seed" in mix
    assert [(x.prompt.size, x.max_new_tokens) for x in a] == [
        (x.prompt.size, x.max_new_tokens) for x in b]
    if jitter:
        moved = np.abs(np.array([x.due_s for x in a])
                       - np.array([x.due_s for x in b]))
        assert 0 < moved.max() <= 2 * jitter + 1e-9
    still = {k: v for k, v in mix.items() if k != "due_jitter_s"}
    assert _same(arrivals.requests(still, 50257, 7, **kw),
                 arrivals.requests(still, 50257, 8, **kw), tokens=False)
    # without it the run's seed draws the due times and lengths too
    free = {k: v for k, v in mix.items() if k != "trace_seed"}
    c = arrivals.requests(free, 50257, 7, **kw)
    assert _same(c, arrivals.requests(free, 50257, 7, **kw))
    assert not _same(c, arrivals.requests(free, 50257, 8, **kw), tokens=False)


def test_open_loop_offers_a_fixed_amount_of_work():
    rate = OPEN["arrivals"]["rate_per_s"]
    for seed in (1, 2, 3):
        reqs = arrivals.requests(OPEN, 50257, seed, seconds=20.0)
        window = [r for r in reqs if r.due_s >= 0]
        lead = [r for r in reqs if r.due_s < 0]
        assert len(window) == round(rate * 20.0)
        assert len(lead) == round(rate * OPEN["lead_in_s"])
        assert all(-OPEN["lead_in_s"] <= r.due_s < 0 for r in lead)
        assert all(r.due_s < 20.0 for r in window)
        assert [r.due_s for r in reqs] == sorted(r.due_s for r in reqs)
        lens = np.array([r.prompt.size for r in window])
        assert lens.min() >= 512 and lens.max() <= 960
        # stratified: the mean sits on the distribution's, whatever the seed
        assert abs(lens.mean() - 736) < 8
        assert all(4 <= r.max_new_tokens <= 32 for r in reqs)
        assert all(r.prompt.min() >= 1 and r.prompt.max() < 50257
                   for r in reqs)


def test_backlog_staggers_the_first_batch_and_clips_lengths():
    reqs = arrivals.requests(BACKLOG, 50257, 5, slots=16)
    assert len(reqs) == BACKLOG["requests"]
    assert all(r.due_s is None for r in reqs)
    assert all(32 <= r.prompt.size <= 256 for r in reqs)
    assert all(128 <= r.max_new_tokens <= 384 for r in reqs[16:])
    first = sorted(r.max_new_tokens for r in reqs[:16])
    assert first[0] < 128 * 0.5 and len(set(first)) >= 12
    median = np.median([r.prompt.size for r in reqs])
    assert 90 <= median <= 102


def test_gamma_arrivals_are_burstier_with_a_larger_cv():
    def cv_of_gaps(cv):
        rng = np.random.default_rng(0)
        t = arrivals.schedule({"process": "gamma", "rate_per_s": 50.0,
                               "cv": cv}, 100.0, rng)
        assert len(t) == 5000 and 0 <= t.min() and t.max() < 100.0
        gaps = np.diff(t)
        return gaps.std() / gaps.mean()

    assert cv_of_gaps(1.0) == pytest.approx(1.0, abs=0.08)
    assert cv_of_gaps(3.0) == pytest.approx(3.0, abs=0.4)
    with pytest.raises(ValueError):
        arrivals.schedule({"process": "backlog"}, 10.0,
                          np.random.default_rng(0))


def test_mixture_lengths_and_shared_prefixes():
    mix = {"arrivals": {"process": "backlog"}, "requests": 100,
           "prompt_len": {"dist": "mixture", "parts": [
               {"weight": 0.8, "dist": "uniform", "min": 32, "max": 128},
               {"weight": 0.2, "dist": "uniform", "min": 768, "max": 960}]},
           "output_len": {"dist": "uniform", "min": 4, "max": 8},
           "prefix": {"groups": 2, "len": 24}}
    reqs = arrivals.requests(mix, 1000, 1)
    sizes = np.array([r.prompt.size for r in reqs])
    assert (sizes <= 128).sum() == 80 and (sizes >= 768).sum() == 20
    heads = {tuple(r.prompt[:24]) for r in reqs}
    assert len(heads) == 2  # every request starts with one of two prefixes
    with pytest.raises(ValueError):
        arrivals.draw_lengths({"dist": "zipf", "min": 1, "max": 2}, 3,
                              np.random.default_rng(0))


# ---------------------------------------------------------------------------
# statistics
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,q", [(5, 50.0), (39, 50.0), (40, 75.0),
                                 (99, 75.0), (100, 90.0), (199, 90.0),
                                 (200, 95.0), (1000, 99.0), (10000, 99.9)])
def test_highest_percentile_with_ten_samples_beyond_it(n, q):
    assert stats.supported_percentile(n) == q


def test_percentiles_are_measured_values_and_come_with_their_count():
    xs = list(range(1, 201))  # 1..200
    assert stats.percentile(xs, 50) == 100
    assert stats.percentile(xs, 95) == 190
    assert stats.percentile(xs, 100) == 200
    assert stats.summary(xs) == {"n": 200, "median": 100, "q": 95.0,
                                 "tail": 190}
    assert stats.summary([]) == {"n": 0}
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_spread_is_interquartile_range_over_median():
    assert stats.spread([10, 10, 10, 10]) == 0
    # quartiles of 1..5 (interpolated, exclusive of the ends) are 1.5 and
    # 4.5, the median 3
    assert stats.spread([1, 2, 3, 4, 5]) == pytest.approx(1.0)
    with pytest.raises(ValueError):
        stats.spread([0, 0, 0])


# ---------------------------------------------------------------------------
# weights, kernel costs
# ---------------------------------------------------------------------------

def test_seeded_weights_have_the_tree_shapes_and_scales_of_init_params():
    from incubator_mxnet_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(vocab=96, d_model=32, n_heads=4, n_layers=3,
                                d_ff=64, max_len=48, dtype="float32")
    want = tfm.init_params(cfg, seed=0)
    got = weights.transformer_params(cfg, 0, {"default": "float32"})
    assert set(got) == set(want)
    for k in want:
        assert got[k].shape == want[k].shape, k
        assert got[k].dtype == want[k].dtype, k
        w, g = np.asarray(want[k], np.float32), np.asarray(got[k], np.float32)
        if k.startswith("ln"):
            assert np.array_equal(w, g), k  # ones and zeros
        else:
            assert g.std() == pytest.approx(w.std(), rel=0.15), k
            assert abs(g.mean()) < 0.1 * g.std() + 1e-3, k
    again = weights.transformer_params(cfg, 0, {"default": "float32"})
    other = weights.transformer_params(cfg, 1, {"default": "float32"})
    assert np.array_equal(np.asarray(got["wq"]), np.asarray(again["wq"]))
    assert not np.array_equal(np.asarray(got["wq"]), np.asarray(other["wq"]))


def test_serving_dtypes_are_the_configurations_not_init_params():
    """init_params returns float32 matrices for dtype bfloat16 (a NumPy
    promotion); the benchmark serves what the configuration file states."""
    from incubator_mxnet_tpu.models import transformer as tfm

    serving = loader.load_cell("gpt2xl_serve_decode_sat",
                               rehearse=True).config["serving"]
    cfg = tfm.TransformerConfig(vocab=96, d_model=32, n_heads=4, n_layers=2,
                                d_ff=64, max_len=48, dtype=serving["dtype"])
    got = weights.transformer_params(cfg, 0, serving["param_dtypes"])
    assert {k: v.dtype.name for k, v in got.items() if k != "pos"} == {
        k: "bfloat16" for k in got if k != "pos"}
    assert got["pos"].dtype.name == "float32"


def test_paged_decode_attention_cost_counts_each_cached_row_once():
    cost = loader.load_callable("kernel_costs",
                                "paged_decode_attention.py:cost")
    # 1000 attended tokens, 25 heads of 64, 48 layers, bf16
    flops, nbytes = cost({"traced": {"kv_tokens": 1000},
                          "kv": {"n_heads": 25, "head_dim": 64,
                                 "n_layers": 48, "itemsize": 2}})
    assert nbytes == 1000 * 25 * 64 * 48 * 2 * 2   # K and V, 2 bytes each
    assert flops == 1000 * 25 * 64 * 48 * 4        # q.k and p.v, 2 each
    assert cost({"kv": {}}) is None                # an untraced run
    least, side = roofline.seconds(flops, nbytes, loader.peaks("TPU v5 lite"))
    assert side == "memory" and least == pytest.approx(nbytes / 819e9)
