"""Job `train_step`: a Gluon vision model under `fused.GluonTrainStep`, one
host call per step, on one chip or data-parallel over a mesh of all the
cell's chips.

The model, loss and optimizer are built as `bench.build_train_step` builds
them (copied here: bench.py is on the roadmap's deletion list). Inputs are
a ring of distinct synthetic batches made on the device from the seed and
staged before the window, so no step waits for the host's data and the
loss is not that of one repeated batch. The window is closed by a value
fetch of the last loss; the host keeps `in_flight` steps queued ahead of
the one it last fetched, as a training loop that logs its loss does.

Mix parameters: batch_per_chip, ring_batches, mesh_axis (null = one chip),
shard_policy, in_flight, warmup_steps, trace_seconds, loss_rtol.
"""
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import fused, gluon, nd
from incubator_mxnet_tpu.gluon.model_zoo import vision

from benchmark.harness import loader
from benchmark.harness.tracing import TailTrace, span


def build_train_step(config, batch_size, seed, device, mesh, shard_policy):
    """Model-zoo ResNet v1 at the configuration's sizes under SGD with
    momentum, fused into one GluonTrainStep (bench.build_train_step)."""
    mx.random.seed(seed)
    # build and initialize on the host: avoids hundreds of tiny per-param
    # device programs; GluonTrainStep moves the weights once at build
    with jax.default_device(jax.devices("cpu")[0]):
        net = vision.ResNet(1, tuple(config["units"]),
                            tuple(config["channels"]), True,
                            classes=config["classes"],
                            layout=config["layout"])
        net.initialize(mx.init.Xavier())
        if config["dtype"] == "bfloat16":
            net.cast("bfloat16")
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    o = config["optimizer"]
    opt = mx.optimizer.SGD(learning_rate=o["learning_rate"],
                           momentum=o["momentum"], wd=o["wd"],
                           rescale_grad=1.0 / batch_size)
    return fused.GluonTrainStep(net, lambda n, x, y: loss(n(x), y), opt,
                                device=None if mesh is not None else device,
                                mesh=mesh, shard_policy=shard_policy)


def synthetic_ring(config, n, batch_size, seed, sharding):
    """`n` distinct (x, y) batches made on the device in one jitted call:
    pixels uniform in [0, 1) in the model's dtype, labels as float32 class
    ids (what bench.synthetic_batch draws on the host)."""
    size, classes = config["image_size"], config["classes"]
    dtype = jnp.dtype(config["dtype"])

    def make(key):
        kx, ky = jax.random.split(key)
        xs = jax.random.uniform(kx, (n, batch_size, size, size, 3),
                                jnp.float32).astype(dtype)
        ys = jax.random.randint(ky, (n, batch_size), 0,
                                classes).astype(jnp.float32)
        return [xs[i] for i in range(n)], [ys[i] for i in range(n)]

    out_sh = ([sharding] * n, [sharding] * n)
    xs, ys = jax.jit(make, out_shardings=out_sh)(jax.random.key(seed))
    # from_jax keeps the dtype; nd.array() would force-cast bf16 to f32
    return [nd.from_jax(x) for x in xs], [nd.from_jax(y) for y in ys]


def reference_loss(ctx, step, x, y):
    """The plain reference's loss on batch (x, y) with the weights the
    step holds now, in float32 on the first chip."""
    ref = loader.load_reference(ctx.cell.config, ctx.root)
    dev = ctx.devices[0]
    named = [(n, p.data()._data) for n, p in
             step.net.collect_params().items()]
    names = [n for n, _ in named]
    arrays = [jax.device_put(a, dev).astype(jnp.float32) for _, a in named]
    fn = jax.jit(lambda arrs, xb, yb: ref(list(zip(names, arrs)), xb, yb,
                                          ctx.cell.config))
    return float(fn(arrays, jax.device_put(x._data, dev),
                    jax.device_put(y._data, dev)))


def run(ctx):
    config, mix = ctx.cell.config, ctx.cell.traffic
    chips = len(ctx.devices)
    batch = int(mix["batch_per_chip"]) * chips
    mesh = None
    sharding = jax.sharding.SingleDeviceSharding(ctx.devices[0])
    if mix.get("mesh_axis"):
        mesh = Mesh(np.array(ctx.devices), (mix["mesh_axis"],))
        sharding = NamedSharding(mesh, P(mix["mesh_axis"]))
    elif chips != 1:
        raise ValueError("a mix with no mesh_axis runs on one chip")

    step = build_train_step(config, batch, ctx.seed, ctx.devices[0], mesh,
                            mix.get("shard_policy"))
    ctx.mark("model_built_on_host")
    xs, ys = synthetic_ring(config, int(mix["ring_batches"]), batch,
                            ctx.seed, sharding)

    # correctness, outside the window: the first step's loss against the
    # plain float32 reference on the same weights and batch. On a mesh the
    # reference is still one device's forward over the whole batch.
    step.warmup(xs[0], ys[0])  # builds without running a step
    ctx.mark("step_built")
    ref_loss = reference_loss(ctx, step, xs[0], ys[0])
    ctx.mark("reference_loss")
    first_loss = float(step(xs[0], ys[0]).asscalar())
    ctx.mark("first_step")
    rel = abs(first_loss - ref_loss) / abs(ref_loss)
    losses = [first_loss]
    # warm-up: zero1 settles the weights into the state layout at step 2,
    # which compiles once more; every ring slot is touched once
    for i in range(1, max(int(mix["warmup_steps"]), len(xs))):
        losses.append(float(step(xs[i % len(xs)],
                                 ys[i % len(xs)]).asscalar()))

    # the program hands out PRNG keys from blocks of 256 refilled by a small
    # jitted program, which compiles once per default device: the host-side
    # init above compiled it for the CPU, and the first refill after it
    # would compile it for the chip some hundred steps into the window.
    # Drawing one block's worth of keys here puts that compile in set-up.
    for _ in range(256):
        mx.random.next_key()
    ctx.mark("warmed_up")

    in_flight = int(mix["in_flight"])
    tail = TailTrace(ctx, mix["trace_seconds"])
    pending = []
    steps = 0
    compiles_before = ctx.compiles.count
    t0 = time.perf_counter()
    while True:
        elapsed = time.perf_counter() - t0
        if elapsed >= ctx.seconds:
            break
        tail.tick(elapsed)
        with span("bench.train_dispatch"):
            pending.append(step(xs[steps % len(xs)], ys[steps % len(xs)]))
        steps += 1
        if len(pending) > in_flight:
            with span("bench.fetch"):
                losses.append(float(pending.pop(0).asscalar()))
    with span("bench.fetch"):
        losses.extend(float(p.asscalar()) for p in pending)
    window_s = time.perf_counter() - t0
    tail.stop()
    compiles_in_window = ctx.compiles.count - compiles_before

    finite = all(math.isfinite(v) for v in losses)
    correct = finite and rel <= float(mix["loss_rtol"])
    return {
        "correct": correct, "attempted": steps,
        "checks": {"first_loss_rel_diff": [rel, float(mix["loss_rtol"])]},
        "failed": sum(not math.isfinite(v) for v in losses),
        "window_start": t0, "window_s": window_s,
        "compiles_in_window": compiles_in_window,
        "steps": steps, "items": steps * batch, "chips": chips,
        "detail": {"first_loss": first_loss, "reference_loss": ref_loss,
                   "first_loss_rel_diff": rel, "last_loss": losses[-1],
                   "steps": steps, "window_s": window_s,
                   "global_batch": batch},
    }
