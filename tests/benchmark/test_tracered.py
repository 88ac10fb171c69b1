"""benchmark/harness/tracered.py on a hand-made trace: every expected number
is worked out here from benchmark/harness/testdata/synthetic_trace.json.

Device 0, window [0, 1,000,000] ns (the `bench.window` span):

  while.1        100k..500k   holds fusion.1 120k..220k and the kernel
                              (custom-call.7) 230k..380k; its self time is
                              400k - 250k = 150k
  all-reduce.3   500k..600k   overlapped from 550k by
  fusion.2       550k..650k
  fusion.3       900k..950k

  busy = [100k, 650k] + [900k, 950k] = 600k; idle gaps 0..100k (100k),
  650k..900k (250k), 950k..1000k (50k).

Device 1 is busy for the whole window.
"""
import json
import os

import pytest

from benchmark.harness import loader, tracered

TESTDATA = os.path.join(loader.ROOT, "benchmark", "harness", "testdata")
WIN = [0, 1_000_000]


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(TESTDATA, "synthetic_trace.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def classify():
    return tracered.classifier(loader.load_opclasses("serve_engine"))


@pytest.fixture(scope="module")
def op(trace):
    """Short name -> the op's name in the trace (its whole HLO text)."""
    return trace["names"]


def test_window_is_the_benchmarks_own_span(trace):
    assert tracered.window(trace) == WIN
    no_span = dict(trace, host=[])
    assert tracered.window(no_span) == [0, 1_000_000]  # device 1's extent


def test_busy_union_counts_overlapping_and_nested_ops_once(trace):
    busy = tracered.busy_intervals(trace["devices"][0], WIN)
    assert busy == [[100_000, 650_000], [900_000, 950_000]]
    assert tracered.total(busy) == 600_000
    assert tracered.idle_gaps(trace["devices"][0], WIN) == [
        [0, 100_000], [650_000, 900_000], [950_000, 1_000_000]]


def test_self_time_takes_children_out_of_their_parent(trace, op):
    got = dict(tracered.self_times(trace["devices"][0]["ops"]))
    assert got == {op["while.1"]: 150_000, op["fusion.1"]: 100_000,
                   op["custom-call.7"]: 150_000,
                   op["all-reduce.3"]: 50_000,  # what fusion.2 does not cover
                   op["fusion.2"]: 100_000, op["fusion.3"]: 50_000}


def test_time_per_op_class(trace, classify):
    got = tracered.by_class(tracered.op_seconds(trace["devices"][0], WIN),
                            classify)
    assert got == pytest.approx({
        "loop_control": 150e-6, "paged_decode_kernel": 150e-6,
        "collective": 50e-6, "elementwise_or_reduce_fusion": 250e-6})
    assert classify("%never-seen.1 = f32[] never-seen()") == "other"


def test_an_operand_named_like_a_collective_does_not_make_one(classify, op):
    """fusion.2 reads %all-reduce.3: the pattern anchors at the op's own
    name, so the fusion stays a fusion — in both jobs' classes."""
    assert classify(op["fusion.2"]) == "elementwise_or_reduce_fusion"
    assert classify(op["all-reduce.3"]) == "collective"
    train = tracered.classifier(loader.load_opclasses("train_step"))
    assert train(op["fusion.2"]) == "elementwise_fusion"
    assert train(op["fusion.3"]) == "reduce_fusion"
    assert train(op["all-reduce.3"]) == "collective"
    assert train("%fusion.84 = (bf16[256]{0}) fusion(bf16[128,56,56,256] "
                 "%get-tuple-element.2), kind=kOutput, calls=%fc.104"
                 ) == "conv_fusion"


def test_time_per_module(trace):
    dev = trace["devices"][0]
    assert tracered.module_stats(dev, WIN, "_decode_fn") == (
        1, pytest.approx(550e-6))
    assert tracered.module_stats(dev, WIN, "_prefill_fn") == (
        1, pytest.approx(50e-6))
    assert tracered.module_stats(dev, WIN, "no_such_module") == (0, 0.0)


def test_exposed_collective_time_is_what_nothing_overlaps(trace, classify):
    # all-reduce.3 runs 500k..600k; fusion.2 hides 550k..600k of it
    assert tracered.exposed_seconds(trace["devices"][0], WIN, classify,
                                    "collective") == pytest.approx(50e-6)
    assert tracered.exposed_seconds(trace["devices"][1], WIN, classify,
                                    "collective") == 0.0


def test_idle_gaps_go_to_the_host_span_open_during_them(trace):
    gaps = tracered.idle_gaps(trace["devices"][0], WIN)
    got = tracered.attribute_gaps(gaps, trace["host"])
    # 0..100k: no span. 650k..900k: read_tokens to 700k (50k), submit to
    # 720k (20k), engine_step from 720k (180k). 950k..1000k: engine_step.
    assert got == pytest.approx({
        "(no span)": 100e-6, "bench.read_tokens": 50e-6,
        "bench.submit": 20e-6, "bench.engine_step": 230e-6})
    # a gap under the threshold is left out
    assert tracered.attribute_gaps([[0, 49_999]], trace["host"]) == {}


def test_summary_averages_busy_and_reports_the_worst_device(trace):
    trace = {k: v for k, v in trace.items() if k in ("devices", "host")}
    s = tracered.summarize(trace, loader.load_opclasses("serve_engine"))
    assert s["window_s"] == pytest.approx(1e-3)
    assert s["busy_s"] == pytest.approx((600e-6 + 1000e-6) / 2)
    ops = dict(s["breakdown"]["device_ops"])
    assert ops["class elementwise_or_reduce_fusion"] == pytest.approx(250e-6)
    kernel = next(v for k, v in ops.items() if k.startswith("op %closed_call"))
    assert kernel == pytest.approx(150e-6)
    assert all(len(k) <= 3 + tracered.SHORT_NAME for k in ops)
    assert len(s["breakdown"]["device_ops"]) <= 10
    gaps = dict(s["breakdown"]["idle_gaps"])
    assert gaps["bench.engine_step"] == pytest.approx(230e-6)


def test_interval_helpers():
    assert tracered.merge([[5, 7], [1, 3], [2, 4], [7, 7]]) == [[1, 4], [5, 7]]
    assert tracered.subtract([[0, 10], [20, 30]], [[2, 3], [8, 22], [29, 40]]
                             ) == [[0, 2], [3, 8], [22, 29]]
    assert tracered.clip([[0, 10], [20, 30]], 5, 25) == [[5, 10], [20, 25]]


def test_read_xplane_on_a_recorded_file():
    """A 6 KB trace of two jitted steps recorded on the CPU backend with the
    benchmark's spans around them: no device plane, so for a rehearsal the
    XLA ops the host's executor threads ran stand in as one device."""
    path = os.path.join(TESTDATA, "cpu_two_steps.xplane.pb")
    with pytest.raises(ValueError, match="no device was traced"):
        tracered.read_xplane(path)  # only a rehearsal may read it so
    t = tracered.read_xplane(path, rehearse=True)
    names = [e[0] for e in t["host"]]
    assert names.count("bench.window") == 1
    assert names.count("bench.train_dispatch") == 2
    assert names.count("bench.fetch") == 2
    assert len(t["devices"]) == 1 and t["devices"][0]["ops"]
    win = tracered.window(t)
    busy = tracered.total(tracered.busy_intervals(t["devices"][0], win))
    assert 0 < busy <= win[1] - win[0]
