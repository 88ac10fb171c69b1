#!/usr/bin/env bash
# CI tiers (ref: ci/docker/runtime_functions.sh — unittest / nightly /
# distributed stages). Usage:
#   ci/run_tests.sh [unit|nightly|dist|examples|telemetry|aggregation|static-analysis|sanitizers|perf-structure|perf-gate|cold-start|serving|sharding|recommender|chaos|all]
set -euo pipefail
cd "$(dirname "$0")/.."

tier="${1:-unit}"

run_unit() {
    echo "=== unit tier (virtual 8-device CPU mesh) ==="
    # nightly-class files run (with the big cases enabled) in the
    # nightly tier — keep each test out of exactly one tier
    python -m pytest tests/ -q -x --ignore=tests/test_dist.py \
        --ignore-glob='tests/test_examples_*.py' \
        --ignore=tests/test_large_array.py \
        --ignore=tests/test_checkpoint_compat.py
}

run_dist() {
    echo "=== distributed tier (multi-process launcher) ==="
    python -m pytest tests/test_dist.py -q
}

run_examples() {
    echo "=== examples tier (toy-scale end-to-end) ==="
    python -m pytest tests/test_examples_*.py -q
}

run_suite() {
    echo "=== full suite, ONE process, no -x (the honest green bar) ==="
    # wall-clock budget (seconds): growth must stay visible — if the suite
    # blows past this, split/trim tests instead of silently absorbing it.
    # Round-5 second session measured 50:00 (1345 tests) after the
    # graph-ABI/executor additions; budget raised 3300 -> 3600 to keep
    # headroom on slower machines while still flagging runaway growth.
    local budget="${MXTPU_SUITE_BUDGET:-3600}"
    local t0 t1
    t0=$(date +%s)
    python -m pytest tests/ -q --durations=25
    t1=$(date +%s)
    echo "suite wall clock: $((t1 - t0))s (budget ${budget}s)"
    if [ $((t1 - t0)) -gt "$budget" ]; then
        echo "FAIL: suite exceeded its ${budget}s wall-clock budget" >&2
        exit 1
    fi
}

run_telemetry() {
    echo "=== telemetry smoke (off/on loop, exporter parse, overhead) ==="
    # tiny train loop twice: telemetry off then on; asserts JSON/Prometheus
    # dumps parse and the disabled path adds <5% wall time (no-op stubs)
    python tools/telemetry_smoke.py
}

run_aggregation() {
    echo "=== aggregation smoke (dispatch counts + aggregated==eager weights) ==="
    # ~200-param model stepped both ways on CPU; asserts (via the
    # mxtpu_trainer_dispatches_total counter) strictly fewer dispatches on
    # the aggregated path and bit-identical final weights
    JAX_PLATFORMS=cpu python bench.py --dispatch-overhead --assert
}

run_static_analysis() {
    echo "=== static-analysis tier (mxlint + graph validation) ==="
    # framework lint: MUST be clean modulo the committed (empty) baseline.
    # Runs without jax — keep it first so a bad sandbox fails fast.
    python tools/mxlint.py --baseline ci/mxlint_baseline.json
    # graph validation over two traced model_zoo networks: any
    # error-severity MXA finding fails the tier (INFO findings like the
    # 1000-class FC head's lane padding are expected and pass).
    JAX_PLATFORMS=cpu python tools/graph_check.py \
        --model resnet18_v1 --shape data=1,3,224,224
    JAX_PLATFORMS=cpu python tools/graph_check.py \
        --model squeezenet1.0 --shape data=1,3,224,224
}

run_sanitizers() {
    echo "=== sanitizer tier (lockdep + page shadow state over real workloads) ==="
    # clean scenarios: the serving engine (prefix cache + chunked prefill
    # + speculation on), the fleet gateway (threaded router + HTTP front
    # end + drain handshake), and the elastic chaos run execute under
    # MXTPU_SANITIZERS=locks,pages with ZERO findings, plus the
    # MXL008-MXL010 concurrency lint over the package
    JAX_PLATFORMS=cpu python tools/sanitize.py --scenario all
    # seeded negatives: each planted bug MUST be caught (exit 0 only when
    # the sanitizer reports it) — a regression that blinds a sanitizer
    # fails here instead of silently passing the clean scenarios forever
    for inj in abba leaked-page lint; do
        if ! JAX_PLATFORMS=cpu python tools/sanitize.py --inject "$inj"; then
            echo "FAIL: sanitizers missed the seeded '$inj' bug" >&2
            exit 1
        fi
    done
    echo "sanitizer tier: clean scenarios green, all 3 seeded bugs caught"
}

run_chaos() {
    echo "=== chaos tier (fault injection: PS drops + torn checkpoint) ==="
    # deterministic 2-worker sync-SGD over the real PS wire with seeded
    # connection kills and one injected torn checkpoint; asserts the run
    # completes, auto-resumes from the latest VALID epoch, and recovers
    # weights bit-identical to the fault-free reference
    JAX_PLATFORMS=cpu python tools/chaos_train.py
    echo "=== chaos tier: distributed tracing + flight recorder ==="
    # traced chaos run (seeded drop + slow rank + forced retry
    # exhaustion), then merge the trace files and gate on: >=1
    # post-mortem dump, a straggler report naming the faulted rank
    # (asserted inside chaos_train), and a parseable merged timeline
    local obs_dir
    obs_dir="$(mktemp -d -t mxtpu-chaos-obs-XXXXXX)"
    JAX_PLATFORMS=cpu python tools/chaos_train.py --observability \
        --workdir "$obs_dir"
    JAX_PLATFORMS=cpu python tools/trace_merge.py "$obs_dir/traces" \
        -o "$obs_dir/timeline.json" --stragglers --check
    python - "$obs_dir" <<'PY'
import json, os, sys
d = sys.argv[1]
dumps = [f for f in os.listdir(os.path.join(d, "traces"))
         if f.startswith("flightrec-") and f.endswith(".json")]
assert dumps, "chaos observability run produced no flight-recorder dump"
json.load(open(os.path.join(d, "timeline.json")))
print(f"chaos observability artifacts ok: {len(dumps)} dump(s) "
      "+ parseable merged timeline")
PY
    echo "=== chaos tier: elastic membership (kill + rejoin mid-epoch) ==="
    # rank 1 killed mid-epoch, evicted by heartbeat staleness, replaced
    # by a fresh join that bootstraps state over the wire; asserts the
    # stale-epoch rejection, bit-identical final weights, >=1 readmission
    # in the metrics snapshot, and join/readmit in trace + flight recorder
    # (all inside chaos_train); then re-merge the traces as CI would
    local el_dir
    el_dir="$(mktemp -d -t mxtpu-chaos-elastic-XXXXXX)"
    JAX_PLATFORMS=cpu python tools/chaos_train.py --elastic \
        --workdir "$el_dir"
    JAX_PLATFORMS=cpu python tools/trace_merge.py "$el_dir/traces" \
        -o "$el_dir/timeline.json" --check
    python - "$el_dir" <<'PY'
import json, os, sys
d = sys.argv[1]
snap = json.load(open(os.path.join(d, "metrics.json")))
series = snap["metrics"]["mxtpu_ps_readmissions_total"]["series"]
total = sum(s["value"] for s in series)
assert total >= 1, f"metrics snapshot records {total} readmissions"
json.load(open(os.path.join(d, "timeline.json")))
print(f"chaos elastic artifacts ok: {int(total)} readmission(s) in the "
      "metrics snapshot + parseable merged timeline")
PY
    echo "=== chaos tier: preemption + exact resume (SIGTERM mid-epoch) ==="
    # a training subprocess takes SIGTERM mid-epoch, drains the in-flight
    # step, writes a resume bundle (params + optimizer state + data
    # cursor + RNG), and exits 83; a second subprocess auto-resumes and
    # must land on the uninterrupted run's batch order AND final weights
    # bit-identically; then a grad.nonfinite injection under the rollback
    # guardrail policy must replay back onto the fault-free trajectory
    # (all asserted inside chaos_train)
    local pre_dir
    pre_dir="$(mktemp -d -t mxtpu-chaos-preempt-XXXXXX)"
    JAX_PLATFORMS=cpu python tools/chaos_train.py --preempt \
        --workdir "$pre_dir"
    python - "$pre_dir" <<'PY'
import os, sys
d = sys.argv[1]
for f in ("batches-reference.txt", "batches-interrupt.txt",
          "batches-resume.txt", "final-weights.npz"):
    assert os.path.exists(os.path.join(d, f)), f"missing artifact {f}"
bundle = [f for f in os.listdir(os.path.join(d, "bundle"))
          if f.endswith("-preempt.bundle")]
assert bundle, "no resume bundle left in the workdir"
print("chaos preempt artifacts ok: batch logs + final weights + bundle")
PY
}

run_perf_structure() {
    echo "=== perf-structure tier (HLO structural gates on the headline program) ==="
    # the scaled-down resnet50 bf16+scan step, compiled twice. Gate 1:
    # default knobs — conv dtypes all-bf16, zero loose entry elementwise,
    # zero standalone bf16 elementwise producers, zero epilogue rewrites
    # (the knob-off program must not change shape as the levers evolve).
    JAX_PLATFORMS=cpu python tools/perf_analysis.py \
        --batch 4 --image 32 --scan 2 \
        --assert-structure --max-unfused-bf16 0
    # Gate 2: all three traffic levers on — the epilogue rewrite must
    # actually fire (>0 rewrites) and the program must stay structurally
    # clean under the selective remat policy + stochastic rounding.
    JAX_PLATFORMS=cpu python tools/perf_analysis.py \
        --batch 4 --image 32 --scan 2 \
        --remat-policy convs --fused-epilogue --stochastic-rounding \
        --assert-structure
}

run_perf_gate() {
    echo "=== perf-gate tier (bench metrics vs committed baseline) ==="
    # both JSON-emitting bench modes against ci/perf_baseline.json:
    # deterministic counters (dispatch counts, retraces, anomalies) carry
    # zero-tolerance bands; wall-clock ratios are report-only. --assert on
    # the observatory run also enforces phase-sum coverage, HBM peak span
    # attribution, and zero second-epoch retraces inside the bench itself.
    local gate_dir
    gate_dir="$(mktemp -d -t mxtpu-perf-gate-XXXXXX)"
    JAX_PLATFORMS=cpu python bench.py --dispatch-overhead \
        > "$gate_dir/bench.json"
    JAX_PLATFORMS=cpu python bench.py --observatory --assert \
        >> "$gate_dir/bench.json"
    # --subset: the cold_start.* baseline keys belong to the cold-start
    # tier's own bench run, not this results file
    python tools/perf_gate.py "$gate_dir/bench.json" \
        --baseline ci/perf_baseline.json \
        --subset trainer_dispatch_overhead --subset perf_observatory
    # negative self-test: a seeded dispatch-count regression MUST fail
    if python tools/perf_gate.py "$gate_dir/bench.json" \
        --baseline ci/perf_baseline.json \
        --subset trainer_dispatch_overhead --subset perf_observatory \
        --inject trainer_dispatch_overhead.aggregated_dispatches=4.0 \
        > "$gate_dir/inject.log" 2>&1; then
        echo "FAIL: perf_gate passed a seeded 4x dispatch regression" >&2
        cat "$gate_dir/inject.log" >&2
        exit 1
    fi
    echo "perf-gate: baseline comparison passed; seeded regression rejected"
}

run_cold_start() {
    echo "=== cold-start tier (persistent compile cache across processes) ==="
    # bench.py --cold-start runs the same training child three times
    # against one MXTPU_COMPILE_CACHE_DIR: cold (populates), warm (a
    # fresh process that MUST perform zero compiles — compilereg shows
    # only cached entries and the mxtpu_compile_seconds histogram stays
    # empty), and corrupt (every entry's bytes flipped — the load must
    # evict, fall back to a fresh compile, and still produce weights
    # bit-identical to the other legs). --assert enforces all of that
    # inside the bench; the gate then bands the counters + warm/cold
    # time-to-first-step ratio against the committed baseline.
    local cs_dir
    cs_dir="$(mktemp -d -t mxtpu-cold-start-XXXXXX)"
    JAX_PLATFORMS=cpu python bench.py --cold-start --assert \
        > "$cs_dir/cold.json"
    python tools/perf_gate.py "$cs_dir/cold.json" \
        --baseline ci/perf_baseline.json --subset cold_start
    # negative self-test: a seeded warm-slower-than-cold ratio MUST fail
    # (the zero-valued compile counters can't be perturbed by a
    # multiplicative inject, so the ratio is the tripwire)
    if python tools/perf_gate.py "$cs_dir/cold.json" \
        --baseline ci/perf_baseline.json --subset cold_start \
        --inject cold_start.value=3.0 \
        > "$cs_dir/inject.log" 2>&1; then
        echo "FAIL: perf_gate passed a seeded 3x cold-start ratio" >&2
        cat "$cs_dir/inject.log" >&2
        exit 1
    fi
    # AOT warmup tool end-to-end: precompile two batch buckets of a real
    # model_zoo net into a fresh cache, then re-run — the second pass
    # must be all hits (nothing left to compile)
    local wu_dir
    wu_dir="$(mktemp -d -t mxtpu-warmup-XXXXXX)"
    JAX_PLATFORMS=cpu MXTPU_COMPILE_CACHE_DIR="$wu_dir" \
        python tools/warmup.py --model squeezenet1.0 \
        --shape data=2,3,64,64 --batch-buckets 1,2 \
        --classes 10 > "$cs_dir/warmup.json"
    JAX_PLATFORMS=cpu MXTPU_COMPILE_CACHE_DIR="$wu_dir" \
        python tools/warmup.py --model squeezenet1.0 \
        --shape data=2,3,64,64 --batch-buckets 1,2 \
        --classes 10 > "$cs_dir/warmup2.json"
    python - "$cs_dir" <<'PY'
import json, sys
d = sys.argv[1]
runs = []
for f in ("warmup.json", "warmup2.json"):
    lines = [json.loads(l) for l in open(f"{d}/{f}") if l.startswith("{")]
    runs.append([o for o in lines if o["metric"] == "warmup_summary"][0])
first, second = runs
assert first["misses"] == first["combos"] > 0, first
assert first["cache_entries"] == first["combos"], first
assert second["hits"] == second["combos"] and second["misses"] == 0, second
print(f"warmup tool ok: {first['combos']} combos precompiled, "
      f"second pass {second['hits']}/{second['combos']} hits in "
      f"{second['seconds']}s (first: {first['seconds']}s)")
PY
    # same contract for the serving decode/prefill programs: --decode
    # precompiles the decode step + every prefill bucket into a fresh
    # cache; the re-run must be all hits
    local wd_dir
    wd_dir="$(mktemp -d -t mxtpu-warmup-decode-XXXXXX)"
    JAX_PLATFORMS=cpu MXTPU_COMPILE_CACHE_DIR="$wd_dir" \
        python tools/warmup.py --decode \
        --d-model 32 --n-layers 2 --n-heads 2 --d-ff 64 \
        --vocab 64 --seq 64 --slots 3 --page-size 8 \
        > "$cs_dir/warmup_decode.json"
    JAX_PLATFORMS=cpu MXTPU_COMPILE_CACHE_DIR="$wd_dir" \
        python tools/warmup.py --decode \
        --d-model 32 --n-layers 2 --n-heads 2 --d-ff 64 \
        --vocab 64 --seq 64 --slots 3 --page-size 8 \
        > "$cs_dir/warmup_decode2.json"
    python - "$cs_dir" <<'PY'
import json, sys
d = sys.argv[1]
runs = []
for f in ("warmup_decode.json", "warmup_decode2.json"):
    lines = [json.loads(l) for l in open(f"{d}/{f}") if l.startswith("{")]
    runs.append(([o for o in lines if o["metric"] == "warmup_summary"][0],
                 [o for o in lines if o["metric"] == "warmup"]))
(first, sites1), (second, sites2) = runs
assert first["misses"] == first["combos"] > 1, first
assert first["cache_entries"] == first["combos"], first
assert second["hits"] == second["combos"] and second["misses"] == 0, second
assert {s["site"] for s in sites1} == {s["site"] for s in sites2}
assert any(s["site"] == "serving_decode_step" for s in sites1), sites1
print(f"warmup --decode ok: {first['combos']} serving sites precompiled "
      f"(decode step + prefill buckets), second pass all-hit")
PY
    echo "cold-start tier: zero warm compiles, corrupt fallback bit-identical, warmup tool all-hit on re-run (model + serving)"
}

run_sharding() {
    echo "=== sharding tier (ZeRO policies: bit-identity + the memory gate) ==="
    # bench.py --sharding trains the same bf16 multi-precision model on a
    # forced 8-device CPU mesh under replicated/zero1/zero2; --assert
    # enforces bitwise-equal final weights across all three policies, the
    # >=6x per-device optimizer-state ledger reduction, and the knob-off
    # contract (meshless + exported MXTPU_SHARD_POLICY lowers to the
    # byte-identical program). The gate then bands the emitted counters.
    local sh_dir
    sh_dir="$(mktemp -d -t mxtpu-sharding-XXXXXX)"
    JAX_PLATFORMS=cpu python bench.py --sharding --assert \
        > "$sh_dir/sharding.json"
    python tools/perf_gate.py "$sh_dir/sharding.json" \
        --baseline ci/perf_baseline.json --subset sharding
    # negative self-test: a seeded weight divergence MUST fail
    if python tools/perf_gate.py "$sh_dir/sharding.json" \
        --baseline ci/perf_baseline.json --subset sharding \
        --inject sharding.weights_match=0 \
        > "$sh_dir/inject.log" 2>&1; then
        echo "FAIL: perf_gate passed a seeded shard-policy weight divergence" >&2
        cat "$sh_dir/inject.log" >&2
        exit 1
    fi
    echo "=== sharding tier: chaos leg (membership change mid-job) ==="
    # a zero1/N=8 job checkpoints after 2 epochs through the
    # manifest-verified sharded writer; a HALVED fleet (4 devices,
    # replicated) restores the manifests, re-saves, and the restored
    # 8-device job re-shards back onto the zero1 layout and runs the
    # final epoch — final weights must be BIT-IDENTICAL to the
    # uninterrupted run
    local ch_dir
    ch_dir="$(mktemp -d -t mxtpu-sharding-chaos-XXXXXX)"
    JAX_PLATFORMS=cpu python - "$ch_dir" <<'PY'
import json
import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.pop("MXTPU_SHARD_POLICY", None)

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import fused, gluon, nd
from incubator_mxnet_tpu.contrib import sharded_checkpoint as sc

workdir = sys.argv[1]
STEPS, SPLIT = 12, 8  # 3 epochs of 4 steps; preempted after epoch 2
L = gluon.loss.SoftmaxCrossEntropyLoss()
rng = np.random.RandomState(1)
xs = rng.rand(STEPS, 16, 64).astype(np.float32)
ys = rng.randint(0, 8, size=(STEPS, 16)).astype(np.float32)


def make_step():
    mx.random.seed(0)
    net = gluon.nn.HybridSequential(prefix="chs_")
    with net.name_scope():
        net.add(gluon.nn.Dense(64, activation="relu", in_units=64))
        net.add(gluon.nn.Dense(64, activation="relu", in_units=64))
        net.add(gluon.nn.Dense(8, in_units=64))
    net.initialize(mx.init.Xavier())
    net.cast("bfloat16")
    opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9,
                           multi_precision=True, rescale_grad=1.0 / 16)
    mesh = Mesh(np.array(jax.devices()[:8]), axis_names=("data",))
    return fused.GluonTrainStep(net, lambda n, a, b: L(n(a), b), opt,
                                mesh=mesh, shard_policy="zero1")


def run(step, lo, hi):
    for i in range(lo, hi):
        mx.random.seed(100 + i)  # pin the per-step key stream
        step(nd.array(xs[i]), nd.array(ys[i])).asscalar()


# the uninterrupted reference trajectory
ref = make_step()
run(ref, 0, STEPS)
ref.sync_params()
ref_w = [np.asarray(d) for d in ref._params]

# the preempted job: 2 epochs, then checkpoint params + sharded states
job = make_step()
run(job, 0, SPLIT)
s_leaves, s_def = jax.tree_util.tree_flatten(job._states)
tree = {f"p{i}": a for i, a in enumerate(job._params)}
tree.update({f"s{i}": a for i, a in enumerate(s_leaves)})
ck1 = os.path.join(workdir, "zero1-n8")
sc.save(ck1, tree)
assert sc.verify(ck1), "checkpoint 1 failed manifest verification"
with open(os.path.join(workdir, "meta.json"), "w") as f:
    json.dump({"n": job._n}, f)
del job

# membership change: half the fleet picks the manifests up — restore
# onto a 4-device replicated mesh, then hand the state back via a
# second manifest-verified save
mesh4 = Mesh(np.array(jax.devices()[:4]), axis_names=("data",))
on4 = sc.restore(ck1, shardings={k: NamedSharding(mesh4, P())
                                 for k in tree})
assert all(v.sharding.mesh == mesh4 for v in on4.values())
ck2 = os.path.join(workdir, "rep-n4")
sc.save(ck2, on4)
assert sc.verify(ck2), "checkpoint 2 failed manifest verification"

# fleet restored: re-shard back onto the 8-device zero1 layout and
# finish the final epoch
res = make_step()
res._build(nd.array(xs[0]), nd.array(ys[0]))
r_leaves, r_def = jax.tree_util.tree_flatten(res._states)
want = {f"p{i}": a.sharding for i, a in enumerate(res._params)}
want.update({f"s{i}": a.sharding for i, a in enumerate(r_leaves)})
back = sc.restore(ck2, shardings=want)
assert any(s.spec != P() for s in want.values()), \
    "re-shard target has no sharded leaf"
res._params = type(res._params)(
    back[f"p{i}"] for i in range(len(res._params)))
res._states = jax.tree_util.tree_unflatten(
    r_def, [back[f"s{i}"] for i in range(len(r_leaves))])
with open(os.path.join(workdir, "meta.json")) as f:
    res._n = int(json.load(f)["n"])
res.opt.num_update = res._n
run(res, SPLIT, STEPS)
res.sync_params()
res_w = [np.asarray(d) for d in res._params]

for name, a, b in zip(res.names, res_w, ref_w):
    assert np.array_equal(a, b), (
        f"chaos leg diverged from the uninterrupted run at {name}")
print(f"sharding chaos leg ok: zero1/N=8 -> replicated/N=4 -> "
      f"zero1/N=8 membership change; {len(ref_w)} tensors bit-identical "
      f"after the final epoch")
PY
    echo "sharding tier: policies bit-identical, >=6x opt-state bytes cut, knob-off program identical, membership-change re-shard bit-exact"
}

run_recommender() {
    echo "=== recommender tier (sparse embedding: RPC budget + retrace + bit-identity gates) ==="
    # unit coverage for the tier first: the sharded service, the remote
    # SparseEmbedding block, DLRM, row-sparse kvstore plumbing, bucketing
    JAX_PLATFORMS=cpu python -m pytest tests/test_embedding.py -q
    # bench.py --recommender trains DLRM twice over a 2-server in-process
    # shard fleet on one seeded zipfian trace: the naive per-key wire
    # (blocking RPC per table per server, no bucketing, no overlap) vs the
    # optimized path (dedup + nnz buckets + one multi-table RPC per server
    # + background prefetch). --assert enforces <= num_servers pull RPCs
    # per step, zero steady-state retraces, bit-identical final weights
    # across the two paths, and O(batch) worker-side embedding bytes; the
    # gate then bands the emitted counters (throughput is report-only).
    local rc_dir
    rc_dir="$(mktemp -d -t mxtpu-recommender-XXXXXX)"
    JAX_PLATFORMS=cpu python bench.py --recommender --assert \
        > "$rc_dir/recommender.json"
    python tools/perf_gate.py "$rc_dir/recommender.json" \
        --baseline ci/perf_baseline.json --subset recommender
    # negative self-test: a seeded cross-path weight divergence MUST fail
    if python tools/perf_gate.py "$rc_dir/recommender.json" \
        --baseline ci/perf_baseline.json --subset recommender \
        --inject recommender.weights_match=0 \
        > "$rc_dir/inject.log" 2>&1; then
        echo "FAIL: perf_gate passed a seeded sparse-path weight divergence" >&2
        cat "$rc_dir/inject.log" >&2
        exit 1
    fi
    echo "=== recommender tier: chaos leg (shard server lost mid-epoch) ==="
    # DLRM trains over 2 shard servers; after epoch 1 the fleet snapshots
    # through the manifest-verified bootstrap pull, shard 0's server is
    # KILLED, a replacement bootstraps from the snapshot (PR-6
    # state-transfer contract), and epoch 2 finishes on the healed fleet —
    # final tables AND dense params must be bit-identical to an
    # uninterrupted reference run
    local rch_dir
    rch_dir="$(mktemp -d -t mxtpu-recommender-chaos-XXXXXX)"
    JAX_PLATFORMS=cpu python - "$rch_dir" <<'PY'
import hashlib
import os
import sys

os.environ["MXTPU_SPARSE_NNZ_BUCKETING"] = "1"
os.environ["MXTPU_SPARSE_PREFETCH"] = "1"

import numpy as np

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, gluon, nd
from incubator_mxnet_tpu.embedding import launch_local_fleet
from incubator_mxnet_tpu.models import DLRM
from incubator_mxnet_tpu.ps import ParameterServer, PSClient

workdir = sys.argv[1]
FIELDS, VOCABS = 3, [120, 137, 154]
STEPS, SPLIT, BATCH = 8, 4, 16  # 2 epochs of 4 steps; shard dies after ep. 1
rng = np.random.RandomState(11)
dense_x = rng.randn(STEPS, BATCH, 4).astype(np.float32)
ids = np.stack([rng.zipf(1.3, size=(STEPS, BATCH)) % v
                for v in VOCABS], -1).astype(np.int64)
labels = rng.randint(0, 2, size=(STEPS, BATCH, 1)).astype(np.float32)
loss_fn = gluon.loss.SigmoidBinaryCrossEntropyLoss()


def make(svc):
    mx.random.seed(42)
    net = DLRM(VOCABS, num_dense=4, embed_dim=8, bottom_units=(16,),
               top_units=(16,), service=svc, seed=5)
    net.initialize(mx.init.Xavier())
    svc.set_optimizer(mx.optimizer.SGD(learning_rate=0.05))
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05})
    tr.attach_sparse_service(svc)
    return net, tr


def run(net, tr, svc, lo, hi):
    net.prefetch(ids[lo])
    for i in range(lo, hi):
        with autograd.record():
            loss = loss_fn(net(nd.array(dense_x[i]), ids[i]),
                           nd.array(labels[i])).mean()
        loss.backward()
        tr.step(1)
        if i + 1 < hi:
            net.prefetch(ids[i + 1])
        loss.asnumpy()
    svc.flush()


def digest(net, svc):
    h = hashlib.sha256()
    for i in range(FIELDS):
        h.update(np.ascontiguousarray(svc.full_table(f"dlrm_f{i}")))
    for name in sorted(net.collect_params()):
        h.update(np.ascontiguousarray(
            net.collect_params()[name].data().asnumpy()))
    return h.hexdigest()


# uninterrupted reference trajectory
servers, svc = launch_local_fleet(2)
net, tr = make(svc)
run(net, tr, svc, 0, STEPS)
ref = digest(net, svc)
svc.close()
[s.shutdown() for s in servers]

# the chaos run: epoch 1, snapshot, LOSE shard 0, heal, epoch 2
servers, svc = launch_local_fleet(2)
net, tr = make(svc)
run(net, tr, svc, 0, SPLIT)
svc.snapshot(workdir)
servers[0].shutdown()  # the fleet loses a shard server mid-job
repl = ParameterServer(num_workers=1, host="127.0.0.1", port=0)
servers.append(repl)
svc.restore_shard(0, workdir, PSClient("127.0.0.1", repl.port))
run(net, tr, svc, SPLIT, STEPS)
got = digest(net, svc)
svc.close()
[s.shutdown() for s in servers[1:]]

assert got == ref, (
    "healed fleet diverged from the uninterrupted run: "
    f"{got[:12]} != {ref[:12]}")
print("recommender chaos leg ok: shard server killed after epoch 1, "
      "replacement bootstrapped from the manifest-verified snapshot, "
      "final tables + dense params bit-identical")
PY
    echo "recommender tier: RPC budget held, zero steady retraces, paths bit-identical, shard loss healed bit-exact"
}

run_serving() {
    echo "=== serving tier (paged decode engine + steady-state retrace gate) ==="
    # engine smoke: kernel equivalence, allocator, token-identity vs
    # generate(), and the steady-state zero-retrace assertions
    JAX_PLATFORMS=cpu python -m pytest tests/test_serving.py \
        tests/test_serving_engine.py \
        tests/test_serving_observability.py -q
    # seeded mixed-length trace through the continuous-batching engine;
    # the gate zero-tolerates steady-state compiles/retraces and dense
    # decode fallbacks (wall-clock throughput/latency are report-only)
    local sv_dir
    sv_dir="$(mktemp -d -t mxtpu-serving-XXXXXX)"
    JAX_PLATFORMS=cpu MXNET_TELEMETRY=1 \
        MXTPU_COMPILE_CACHE_DIR="$sv_dir/cache" \
        python tools/bench_transformer.py --serving \
        --d-model 32 --n-layers 2 --n-heads 2 --d-ff 64 \
        --vocab 64 --seq 64 --serving-requests 12 --slots 3 \
        --page-size 8 > "$sv_dir/serving.json"
    python tools/perf_gate.py "$sv_dir/serving.json" \
        --baseline ci/perf_baseline.json --subset serving.
    # negative self-test: a seeded lost-request regression MUST fail
    if python tools/perf_gate.py "$sv_dir/serving.json" \
        --baseline ci/perf_baseline.json --subset serving. \
        --inject serving.requests_completed=0.5 \
        > "$sv_dir/inject.log" 2>&1; then
        echo "FAIL: perf_gate passed a seeded lost-request regression" >&2
        cat "$sv_dir/inject.log" >&2
        exit 1
    fi
    # -- serving lever legs ----------------------------------------------
    # prefix-cache leg: seeded shared-system-prompt trace (half the
    # requests share one 32-token prefix). Gates the hit rate, the
    # >=50% prefill-token elimination, greedy token identity vs
    # generate(), and zero steady-state retraces — all deterministic.
    JAX_PLATFORMS=cpu MXNET_TELEMETRY=1 \
        MXTPU_COMPILE_CACHE_DIR="$sv_dir/cache_prefix" \
        python tools/bench_transformer.py --serving \
        --d-model 32 --n-layers 2 --n-heads 2 --d-ff 64 \
        --vocab 64 --seq 64 --serving-requests 12 --slots 3 \
        --page-size 8 --serving-tag prefix --prefix-cache 1 \
        --shared-prefix-frac 0.5 --prefix-len 32 --verify-tokens \
        > "$sv_dir/serving_prefix.json"
    python tools/perf_gate.py "$sv_dir/serving_prefix.json" \
        --baseline ci/perf_baseline.json --subset serving_prefix.
    # negative self-test: a seeded prefix-hit-rate collapse MUST fail
    if python tools/perf_gate.py "$sv_dir/serving_prefix.json" \
        --baseline ci/perf_baseline.json --subset serving_prefix. \
        --inject serving_prefix.prefix_hit_rate=0.2 \
        > "$sv_dir/inject_prefix.log" 2>&1; then
        echo "FAIL: perf_gate passed a seeded prefix-hit-rate collapse" >&2
        cat "$sv_dir/inject_prefix.log" >&2
        exit 1
    fi
    # chunked-prefill leg: same mixed trace with MXTPU_PREFILL_CHUNK=8.
    # Wall-clock TTFT is report-only on shared runners; the gated
    # improvement is the term that drives short-request p99 TTFT under
    # load — the head-of-line blocking bound (max prefill tokens any
    # single step computed) must be strictly below the unchunked run's.
    JAX_PLATFORMS=cpu MXNET_TELEMETRY=1 \
        MXTPU_COMPILE_CACHE_DIR="$sv_dir/cache_chunked" \
        python tools/bench_transformer.py --serving \
        --d-model 32 --n-layers 2 --n-heads 2 --d-ff 64 \
        --vocab 64 --seq 64 --serving-requests 12 --slots 3 \
        --page-size 8 --serving-tag chunked --prefill-chunk 8 \
        --verify-tokens > "$sv_dir/serving_chunked.json"
    python tools/perf_gate.py "$sv_dir/serving_chunked.json" \
        --baseline ci/perf_baseline.json --subset serving_chunked.
    SV_DIR="$sv_dir" python - <<'EOF'
import json, os
sv = os.environ["SV_DIR"]
off = json.load(open(os.path.join(sv, "serving.json")))
on = json.load(open(os.path.join(sv, "serving_chunked.json")))
assert on["max_step_prefill_tokens"] < off["max_step_prefill_tokens"], (
    "chunked prefill did not reduce head-of-line blocking: "
    f"{on['max_step_prefill_tokens']} !< {off['max_step_prefill_tokens']}")
print("chunked prefill: per-step prefill bound "
      f"{off['max_step_prefill_tokens']} -> {on['max_step_prefill_tokens']} "
      f"tokens; short-request p99 TTFT {on['ttft_p99_short_s']}s "
      f"(report-only) vs {off['ttft_p99_short_s']}s unchunked")
EOF
    # speculation leg: n-gram prompt-lookup with lookahead 4 — gates
    # the acceptance rate, token identity, and zero steady retraces
    JAX_PLATFORMS=cpu MXNET_TELEMETRY=1 \
        MXTPU_COMPILE_CACHE_DIR="$sv_dir/cache_spec" \
        python tools/bench_transformer.py --serving \
        --d-model 32 --n-layers 2 --n-heads 2 --d-ff 64 \
        --vocab 64 --seq 64 --serving-requests 12 --slots 3 \
        --page-size 8 --serving-tag spec --spec-ngram 2 \
        --spec-lookahead 4 --verify-tokens \
        > "$sv_dir/serving_spec.json"
    python tools/perf_gate.py "$sv_dir/serving_spec.json" \
        --baseline ci/perf_baseline.json --subset serving_spec.
    # -- serving observatory leg -----------------------------------------
    # traced rerun of the same seeded trace: every request must yield a
    # well-formed lifecycle lane, and the --requests report's TTFT
    # figures must agree with the telemetry histogram dump
    JAX_PLATFORMS=cpu MXNET_TELEMETRY=1 \
        MXTPU_COMPILE_CACHE_DIR="$sv_dir/cache" \
        MXTPU_TRACE_DIR="$sv_dir/traces" \
        MXTPU_FLIGHT_RECORDER_DIR="$sv_dir/traces" \
        python tools/bench_transformer.py --serving \
        --d-model 32 --n-layers 2 --n-heads 2 --d-ff 64 \
        --vocab 64 --seq 64 --serving-requests 12 --slots 3 \
        --page-size 8 --metrics-out "$sv_dir/metrics.json" \
        > "$sv_dir/serving_traced.json"
    python tools/trace_merge.py "$sv_dir/traces" \
        -o "$sv_dir/timeline.json" --requests \
        --requests-json "$sv_dir/requests.json" --check
    SV_DIR="$sv_dir" python - <<'EOF'
import glob, json, os
sv = os.environ["SV_DIR"]
# 12 timed requests plus one bucket-warmup request per prefill bucket
report = json.load(open(os.path.join(sv, "requests.json")))
assert report["count"] >= 12, f"expected >=12 request lanes, got {report['count']}"
hist = json.load(open(os.path.join(sv, "metrics.json")))
[series] = hist["metrics"]["mxtpu_serving_ttft_seconds"]["series"]
ttfts = [row["ttft_s"] for row in report["requests"]]
assert len(ttfts) == series["count"], (
    f"--requests report has {len(ttfts)} TTFTs, histogram observed "
    f"{series['count']}")
assert abs(sum(ttfts) - series["sum"]) <= 1e-6 * max(1.0, series["sum"]), (
    f"--requests TTFT sum {sum(ttfts)} != histogram sum {series['sum']}")
lat = [row["latency_s"] for row in report["requests"]]
[lseries] = hist["metrics"]["mxtpu_serving_request_seconds"]["series"]
assert abs(sum(lat) - lseries["sum"]) <= 1e-6 * max(1.0, lseries["sum"])
dumps = glob.glob(os.path.join(sv, "traces", "flightrec-*"))
assert not dumps, f"clean traced run wrote post-mortem dumps: {dumps}"
print(f"serving observability: {report['count']} request lanes check "
      "out; trace TTFT/latency agree with histograms; no spurious SLO "
      "dumps")
EOF
    # negative self-test: a seeded 1000x latency inflation against a
    # 250ms TTFT objective MUST walk ok->warning->breach and write
    # exactly ONE post-mortem dump carrying request timelines
    mkdir -p "$sv_dir/breach"
    JAX_PLATFORMS=cpu MXNET_TELEMETRY=1 \
        MXTPU_COMPILE_CACHE_DIR="$sv_dir/cache" \
        MXTPU_FLIGHT_RECORDER_DIR="$sv_dir/breach" \
        MXTPU_SLO_TTFT_P99=0.25 MXTPU_SLO_WINDOW_SHORT=4 \
        MXTPU_SLO_WINDOW_LONG=8 MXTPU_SLO_MIN_SAMPLES=4 \
        python tools/bench_transformer.py --serving \
        --d-model 32 --n-layers 2 --n-heads 2 --d-ff 64 \
        --vocab 64 --seq 64 --serving-requests 12 --slots 3 \
        --page-size 8 --inject-latency 1000 \
        > "$sv_dir/breach/serving.json"
    SV_DIR="$sv_dir" python - <<'EOF'
import glob, json, os
sv = os.environ["SV_DIR"]
out = json.load(open(os.path.join(sv, "breach", "serving.json")))
assert out["slo"]["ttft"] == "breach", (
    f"seeded latency inflation did not breach the TTFT SLO: {out['slo']}")
assert out["slo_breaches"]["ttft"] == 1, out["slo_breaches"]
dumps = glob.glob(os.path.join(sv, "breach", "flightrec-*slo-breach-ttft*"))
assert len(dumps) == 1, (
    f"expected exactly one slo-breach dump, got {dumps}")
payload = json.load(open(dumps[0]))
assert payload["request_timelines"], "breach dump carries no timelines"
assert {"ttft_s", "latency_s", "finish"} <= set(
    payload["request_timelines"][0])
print("serving observability: seeded breach detected, one post-mortem "
      "dump with request timelines")
EOF
    # fleet chaos: kill a replica mid-stream under load, roll the whole
    # fleet, and hit the real HTTP gateway — gates on zero lost
    # requests, token-identical failover vs the undisturbed reference,
    # SLO monitors never reaching breach, and 429 backpressure
    JAX_PLATFORMS=cpu python tools/chaos_serving.py --scenario all
    # negative self-test: a silently dropped in-flight request MUST
    # fail the zero-lost gate (exit 0 only when the gate catches it)
    JAX_PLATFORMS=cpu python tools/chaos_serving.py --inject lost-request
    # -- fleet observatory leg -------------------------------------------
    # traced failover chaos: the mid-stream kill must yield ONE trace
    # per request with spans on both replicas, pass the distributed
    # causal-chain checks, and write the failover post-mortem dump
    mkdir -p "$sv_dir/fleet-traces"
    JAX_PLATFORMS=cpu MXTPU_TRACE_DIR="$sv_dir/fleet-traces" \
        python tools/chaos_serving.py --scenario failover
    python tools/trace_merge.py "$sv_dir/fleet-traces" --fleet --check \
        --fleet-json "$sv_dir/fleet.json"
    SV_DIR="$sv_dir" python - <<'EOF'
import glob, json, os
sv = os.environ["SV_DIR"]
report = json.load(open(os.path.join(sv, "fleet.json")))
assert report["failovers"] >= 1, report
multi = [row for row in report["entries"] if len(row["replicas"]) >= 2]
assert multi, f"no entry ran on more than one replica: {report['entries']}"
dumps = glob.glob(os.path.join(sv, "fleet-traces",
                               "flightrec-*fleet-failover*"))
assert len(dumps) >= 1, "failover wrote no flight-recorder post-mortem"
payload = json.load(open(dumps[0]))
assert payload["fleet"]["journal_entries"], "dump carries no journal rows"
assert payload["fleet"]["replica_timelines"], "dump carries no timelines"
print(f"fleet observatory: {report['count']} traced entries, "
      f"{report['failovers']} failover span(s), causal chain checked, "
      f"{len(dumps)} post-mortem dump(s)")
EOF
    # negative self-test: an orphaned replica span (broken causal chain)
    # MUST fail `trace_merge --fleet --check`
    JAX_PLATFORMS=cpu python tools/chaos_serving.py --inject broken-chain
    echo "serving tier: trace completed, zero steady-state retraces/fallbacks, seeded regression rejected, lever legs gated (prefix/chunked/spec token-identical), observatory legs green, fleet chaos green (zero lost, token-identical failover, rolling restart zero drops, seeded lost-request caught), fleet observatory green (one trace across failover, causal chain checked, post-mortem dump present, broken-chain negative caught)"
}

run_nightly() {
    echo "=== nightly tier (large tensors, checkpoint compat, 7-worker dist) ==="
    MXTPU_NIGHTLY=1 python -m pytest tests/test_large_array.py \
        tests/test_checkpoint_compat.py -q
    MXTPU_NIGHTLY=1 python -m pytest tests/test_dist.py -q -k seven
    # the measured train configuration (bench.build_train_step: bf16
    # ResNet-50, per-step and scan) walks chip_smoke.py's train and mesh
    # phases at a tiny size on the CPU, so a broken measurement path
    # cannot wait for a chip call to surface; plus the full-size int8
    # proofs (inception @299, trained resnet accuracy)
    MXTPU_NIGHTLY=1 python -m pytest \
        tests/test_chip_smoke.py::test_train_and_mesh_phases \
        "tests/test_quantization_int8.py::test_quantize_net_inceptionv3_full_int8_nightly" \
        "tests/test_quantization_int8.py::test_quantized_trained_resnet_accuracy_within_2pct" \
        -q
}

case "$tier" in
    unit)      run_unit ;;
    dist)      run_dist ;;
    examples)  run_examples ;;
    suite)     run_suite ;;
    telemetry) run_telemetry ;;
    aggregation) run_aggregation ;;
    static-analysis) run_static_analysis ;;
    sanitizers) run_sanitizers ;;
    chaos)     run_chaos ;;
    perf-structure) run_perf_structure ;;
    perf-gate) run_perf_gate ;;
    cold-start) run_cold_start ;;
    serving)   run_serving ;;
    sharding)  run_sharding ;;
    recommender) run_recommender ;;
    nightly)   run_nightly ;;
    all)       run_static_analysis; run_sanitizers; run_unit; run_telemetry; run_aggregation; run_perf_structure; run_perf_gate; run_cold_start; run_serving; run_sharding; run_recommender; run_chaos; run_dist; run_examples; run_nightly ;;
    *) echo "unknown tier: $tier (unit|nightly|dist|examples|suite|telemetry|aggregation|static-analysis|sanitizers|perf-structure|perf-gate|cold-start|serving|sharding|recommender|chaos|all)"; exit 2 ;;
esac
echo "tier '$tier' green"
