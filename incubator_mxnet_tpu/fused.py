"""Fused training steps.

TPU-native analog of the reference's bulked execution: where the graph
executor pre-creates engine ops and bulks whole fwd/bwd segments
(ref: graph_executor.cc InitCachedOps:1073, InitOpSegs:1187,
MXNET_EXEC_BULK_EXEC_*), here the ENTIRE training step — forward, backward,
and optimizer update — is one jit-compiled XLA program with parameter
buffers donated, so updates are in-place in HBM and the only per-step host
work is the dispatch call.

Under a mesh, inputs sharded on the batch axis + replicated params make the
same program data-parallel: GSPMD inserts the gradient all-reduce over ICI
(the kvstore='device'/'nccl' path of the reference).

Optimizer coverage: EVERY built-in optimizer (SGD, NAG, SGLD, Signum, FTML,
DCASGD, LBSGD, Adam, AdaGrad, RMSProp, AdaDelta, Ftrl, Adamax, Nadam,
AdamW, Test) ships an exact fused_update whose 3-step trajectory is tested
against its eager update() (tests/test_optimizer.py). Custom optimizers
without one fall back to tracing their eager update() inside the step
(with a RuntimeWarning): correct for pure-jnp-math updates, but Python-side
state (per-index update counts, host RNG draws) freezes at trace time —
implement fused_update(name, weight, grad, state, lr, t=None) for
time-dependent or stochastic custom updates.
"""
from __future__ import annotations

import time as _time

import jax
import jax.numpy as jnp

from . import autograd
from . import config
from . import random as _global_random
from . import telemetry as _telemetry
from .telemetry import compilereg as _compilereg
from . import compile_cache as _compile_cache
from .telemetry import stepstats as _stepstats
from .gluon.block import _ParamSubst
from .ndarray.ndarray import NDArray
from .optimizer import _cast_state_like as _cast_like

__all__ = ["GluonTrainStep", "resolve_remat_policy"]

# Friendly tiers for MXTPU_REMAT_POLICY, ordered by how much they save
# (everything_saveable = no recompute) vs recompute (nothing_saveable =
# the legacy remat=True behavior). Any exact jax.checkpoint_policies
# attribute name is also accepted.
_REMAT_POLICY_ALIASES = {
    "dots": "dots_saveable",
    "dots_no_batch": "dots_with_no_batch_dims_saveable",
    "offload": "offload_dot_with_no_batch_dims",
    "nothing": "nothing_saveable",
    "everything": "everything_saveable",
}


def _convs_and_dots_saveable(prim, *_, **__):
    """The 'convs' tier: keep MXU results (convolutions AND matmuls) for
    the backward, recompute only cheap elementwise/BN chains. jax's
    builtin dots_* policies save dot_general only — on a conv net they
    recompute every convolution (the expensive op) while saving nothing,
    which is why the batch-256 bf16 remat config regressed instead of
    merely trading flops for memory."""
    return prim.name in ("conv_general_dilated", "dot_general")


def resolve_remat_policy(name):
    """Map a MXTPU_REMAT_POLICY value to a jax.checkpoint policy callable.

    Accepts the friendly tier names ('convs', 'dots', 'dots_no_batch',
    'offload', 'nothing', 'everything') or any exact attribute of
    jax.checkpoint_policies. Returns None for the empty string (legacy
    all-or-nothing checkpointing). Raises ValueError for unknown names,
    listing what is available."""
    if not name:
        return None
    if name == "convs":
        return _convs_and_dots_saveable
    cp = jax.checkpoint_policies
    attr = _REMAT_POLICY_ALIASES.get(name, name)
    pol = getattr(cp, attr, None)
    if pol is None:
        known = ["convs"] + sorted(_REMAT_POLICY_ALIASES) + sorted(
            a for a in dir(cp) if not a.startswith("_"))
        raise ValueError(
            f"unknown remat policy {name!r} (MXTPU_REMAT_POLICY); expected "
            f"one of {known}")
    if attr == "offload_dot_with_no_batch_dims":
        # this policy is a factory taking (src, dst) memory kinds
        pol = pol("device", "pinned_host")
    return pol


class GluonTrainStep:
    """Compile net+loss+optimizer into one donated-buffer step.

    step(x, y) -> loss (device scalar, async). Parameters and optimizer
    states live as jax arrays owned by this object and are written back into
    the net's Parameters after every step (same objects, rebound data).
    """

    def __init__(self, net, loss_fn, optimizer, mesh=None, batch_axis=0, device=None,
                 compute_dtype=None,
                 shard_optimizer_states=False, remat=False,
                 remat_policy=None, shard_policy=None):
        self.net = net
        self.loss_fn = loss_fn
        self.opt = optimizer
        self.mesh = mesh
        self.device = device  # single target device (e.g. the TPU chip)
        # mixed precision the TPU way (the reference's multi-precision SGD,
        # ref: optimizer_op.cc mp_sgd_update): master params and optimizer
        # states stay float32; inside the step, floating params and inputs
        # are cast to compute_dtype (e.g. bfloat16) so convs/matmuls ride
        # the MXU at full rate, while gradients and updates are f32.
        # Contrast with net.cast("bfloat16"), which trains pure-bf16.
        self.compute_dtype = jnp.dtype(compute_dtype) if compute_dtype else None
        # rematerialization (jax.checkpoint over the whole forward): the
        # backward recomputes activations instead of keeping them in HBM —
        # the TPU-native form of the reference's MXNET_BACKWARD_DO_MIRROR /
        # memonger (ref: docs/faq/env_var.md, example memonger usage).
        # Trades ~1/3 more FLOPs for activation memory, buying larger
        # batches on memory-bound models. Numerics are identical (same
        # ops, same order, recomputed).
        self.remat = bool(remat)
        # selective remat: a named jax.checkpoint_policies policy (see
        # resolve_remat_policy) decides WHICH intermediates survive to
        # the backward instead of recomputing everything. On the
        # HBM-saturated bf16 path, blanket recompute ADDS traffic (the
        # measured batch-256 regression, docs/PERF_ANALYSIS.md §0);
        # 'convs' keeps the expensive conv/matmul results and recomputes
        # only cheap elementwise, trading the least bandwidth for the
        # memory saved. A non-empty policy implies remat.
        if remat_policy is None:
            remat_policy = config.get("MXTPU_REMAT_POLICY")
        self.remat_policy = remat_policy or ""
        resolve_remat_policy(self.remat_policy)  # validate eagerly
        if self.remat_policy:
            self.remat = True
        # ZeRO sharding policy over the mesh's 'data' axis (ROADMAP item
        # 5): 'replicated' keeps the legacy placement; 'zero1' shards
        # optimizer state + f32 masters 1/N (largest divisible axis per
        # tensor, recorded per param — see parallel.zero); 'zero2' also
        # reduce-scatters gradients so the update reads only the local
        # shard. shard_optimizer_states=True (the pre-policy spelling)
        # remains an alias for zero1.
        from .parallel import zero as _zero

        explicit = shard_policy is not None
        if shard_policy is None:
            shard_policy = config.get("MXTPU_SHARD_POLICY")
        if not shard_policy and shard_optimizer_states:
            shard_policy = "zero1"
        shard_policy = _zero.resolve_policy(shard_policy)
        if shard_policy != "replicated" and mesh is None:
            if explicit or shard_optimizer_states:
                raise ValueError(
                    f"shard_policy={shard_policy!r} requires a mesh")
            # env knob set globally but this step has no mesh: nothing
            # to shard over — keep the (identical) replicated program
            shard_policy = "replicated"
        self.shard_policy = shard_policy
        self.shard_optimizer_states = shard_policy != "replicated"
        self.state_specs = None  # per-tensor placement record (mesh builds)
        self._built = False
        self._n = 0
        from .optimizer import Optimizer as _OptBase

        if (type(self.opt).fused_update is _OptBase.fused_update
                and type(self.opt) is not _OptBase):
            # every built-in optimizer ships an exact fused_update; a custom
            # one falls back to tracing its eager update(), which freezes
            # any Python-side state (update counts, host RNG) at trace time
            import warnings

            warnings.warn(
                f"{type(self.opt).__name__} has no dedicated fused_update; "
                f"tracing its eager update() instead. Time-dependent or "
                f"stochastic optimizers should implement "
                f"fused_update(name, weight, grad, state, lr, t=None).",
                RuntimeWarning)

    def _build(self, x, y):
        # resolve deferred parameter shapes abstractly: eval_shape traces the
        # forward without touching the device (no per-op dispatch/compile)
        def warm(xd, yd):
            # predict mode: BN must not write (traced) aux values into the
            # real parameter arrays during this abstract pass
            prev = autograd.set_training(False)
            try:
                return self.loss_fn(
                    self.net, NDArray._from_data(xd), NDArray._from_data(yd)
                )._data
            finally:
                autograd.set_training(prev)

        from .gluon.parameter import abstract_init_mode

        with abstract_init_mode():
            jax.eval_shape(
                warm,
                jax.ShapeDtypeStruct(x.shape, x._data.dtype),
                jax.ShapeDtypeStruct(y.shape, y._data.dtype),
            )
        net = self.net
        # materialize any still-deferred params concretely (outside trace)
        for _n, _p in net.collect_params().items():
            if _p._data is None and _p._deferred_init is not None and _p._shape_known():
                _p._finish_deferred_init()
        params = list(net.collect_params().items())
        self.names = [n for n, _ in params]
        self.param_objs = [p for _, p in params]
        self.grad_mask = [p.grad_req != "null" for p in self.param_objs]
        # create_fused_state lets an optimizer carry extra traced state that
        # its eager path keeps in Python (e.g. Nadam's m_schedule)
        make_state = getattr(self.opt, "create_fused_state",
                             self.opt.create_state)
        self._states = [
            self._state_data(make_state(i, p.data())) if m else None
            for i, (p, m) in enumerate(zip(self.param_objs, self.grad_mask))
        ]
        self._params = [p.data()._data for p in self.param_objs]
        if self.device is not None and self.mesh is None:
            # bulk host->device transfer of params/states (host init)
            self._params = [jax.device_put(d, self.device)
                            for d in self._params]
            self._states = jax.tree_util.tree_map(
                lambda d: jax.device_put(d, self.device), self._states
            )
        mesh = self.mesh
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            rep = NamedSharding(mesh, P())
            self._params = [jax.device_put(d, rep) for d in self._params]
            if self.shard_policy != "replicated":
                # ZeRO-1 the GSPMD way: optimizer states (including f32
                # masters, which live inside the multi-precision state
                # tuples) sharded over the dp axis along each tensor's
                # largest divisible axis; the scalar/ragged remainder
                # stays replicated. From these placements XLA derives
                # reduce-scatter(grads) -> sharded update ->
                # all-gather(params) instead of a full gradient
                # all-reduce + replicated update — same math, 1/N state
                # HBM. zero2 makes the grad reduce-scatter explicit in
                # _make_step. The per-tensor decision lands in
                # self.state_specs (see shard_placements()).
                from .parallel import zero as _zero

                self._states, self.state_specs = _zero.place_tree(
                    self._states, mesh)
            else:
                self._states = jax.tree_util.tree_map(
                    lambda d: jax.device_put(d, rep), self._states
                )
            self._data_sharding = NamedSharding(mesh, P("data"))
        else:
            self._data_sharding = None
        pending = getattr(self, "_pending_states", None)
        if pending is not None:
            # load_states() was called before the first step: overwrite the
            # freshly created states with the checkpointed values, keeping
            # this build's placements (incl. sharded optimizer states)
            self._states = jax.tree_util.tree_map(
                lambda cur, new: jax.device_put(jnp.asarray(new),
                                                cur.sharding)
                if hasattr(cur, "sharding") else new,
                self._states, pending)
            self._pending_states = None
        # HBM ledger: the fused path owns its state buffers (the eager
        # Trainer tracks its own), so account them here — with sharded
        # placements the ledger reports per-device (addressable-shard)
        # bytes, which is where ZeRO's (N-1)/N saving shows up
        _telemetry.ledger.track(list(self._states), "optimizer_state")
        self._step_fn = self._make_step()
        if mesh is not None:
            # pin output placements to the input ones: without this XLA may
            # propagate replicated outputs for sharded optimizer states,
            # re-sharding every step and defeating the 1/N state HBM
            param_sh = [d.sharding for d in self._params]
            state_sh = jax.tree_util.tree_map(lambda d: d.sharding,
                                              self._states)
            self._out_sh = (None, param_sh, state_sh)
        else:
            self._out_sh = None
        # each fused program goes through the persistent compile cache
        # (no-op wrapper when MXTPU_COMPILE_CACHE_DIR is unset): a
        # restarted process deserializes the executable instead of
        # paying the 81-111s XLA compile again (ROADMAP item 4)
        self._step = _compile_cache.wrap(
            "GluonTrainStep.step",
            jax.jit(self._step_fn, donate_argnums=(0, 1),
                    out_shardings=self._out_sh),
            donated=(0, 1))

        def scan_fn(params, states, xs, ys, keys, lrs, ts):
            def body(carry, inp):
                p, s = carry
                x, y, key, lr, t = inp
                loss, p2, s2 = self._step_fn(p, s, x, y, key, lr, t)
                return (p2, s2), loss

            (params, states), losses = jax.lax.scan(
                body, (params, states), (xs, ys, keys, lrs, ts))
            return losses, params, states

        # one jit wrapper; its cache keys on shapes, so varying K reuses
        # previously compiled executables
        self._scan = _compile_cache.wrap(
            "GluonTrainStep.scan",
            jax.jit(scan_fn, donate_argnums=(0, 1),
                    out_shardings=(None,) + self._out_sh[1:]
                    if self._out_sh is not None else None),
            donated=(0, 1))
        self._accum = _compile_cache.wrap(
            "GluonTrainStep.accum",
            jax.jit(self._accum_fn, donate_argnums=(0, 1),
                    out_shardings=self._out_sh),
            donated=(0, 1))
        self._built = True

    def shard_placements(self):
        """Per-parameter record of the optimizer-state placements the
        shard policy chose: {param_name: [PartitionSpec, ...]} with one
        spec per state leaf (empty list for grad_req='null' params).
        P('data')-style specs mark sharded leaves; P() marks the
        divisibility fallback to replication. None before the first
        build or for meshless/replicated steps."""
        if self.state_specs is None:
            return None
        out = {}
        for name, spec in zip(self.names, self.state_specs):
            out[name] = jax.tree_util.tree_leaves(spec)
        return out

    def _retrack_states(self, old_states):
        """Each step donates the state buffers and returns fresh arrays;
        move the HBM ledger's optimizer_state accounting from the dead
        buffers to the live ones (donation frees device memory NOW,
        before the Python objects die)."""
        _telemetry.ledger.untrack(list(old_states))
        _telemetry.ledger.track(list(self._states), "optimizer_state")

    @staticmethod
    def _state_data(state):
        if state is None:
            return None
        if isinstance(state, (tuple, list)):
            return tuple(s._data if isinstance(s, NDArray) else s for s in state)
        return state._data if isinstance(state, NDArray) else state

    def _make_step(self):
        names = self.names
        grad_names = [n for n, m in zip(names, self.grad_mask) if m]

        cdt = self.compute_dtype
        mesh = self.mesh
        grad_specs = None
        pin_rep = None
        if mesh is not None and self.shard_policy != "replicated":
            # The bit-identity fence (see parallel.zero.pin_replicated):
            # params entering the forward and gradients leaving the
            # backward are pinned replicated so the sharded state inputs
            # cannot repartition the fwd/bwd math. Sharding then lives
            # only in the elementwise update; the new weights *settle
            # into the state layout* after the first step (GSPMD
            # propagates it through the update), which is exact, saves
            # param bytes too, and costs one extra compile at step 2.
            from .parallel import zero as _zero

            def pin_rep(tree):
                return _zero.pin_replicated(tree, mesh)

            if self.shard_policy == "zero2":
                # zero2: additionally constrain each pinned gradient to
                # the same largest-divisible-axis layout its optimizer
                # state uses, so the update consumes only the local
                # shard and the full gradient dies right after the
                # slice (a layout constraint — values unchanged)
                n_dev = mesh.shape["data"]
                grad_specs = [
                    _zero.largest_axis_spec(tuple(d.shape), n_dev)
                    for d, m in zip(self._params, self.grad_mask) if m]
                _shard_grads = _zero.shard_grads

        def forward(grad_params, other_params, x, y, key):
            if cdt is not None:
                # bf16 compute against f32 master weights: cast floating
                # params and data; BN aux stats stay f32 (other_params)
                grad_params = [d.astype(cdt)
                               if jnp.issubdtype(d.dtype, jnp.floating) else d
                               for d in grad_params]
                if jnp.issubdtype(x.dtype, jnp.floating):
                    x = x.astype(cdt)
            mapping = {}
            for n, d in zip(grad_names, grad_params):
                mapping[n] = NDArray._from_data(d)
            for n, d in other_params.items():
                mapping[n] = NDArray._from_data(d)
            prev_t = autograd.set_training(True)
            prev_r = autograd.set_recording(False)
            try:
                with _ParamSubst(mapping), _global_random.key_override(key):
                    loss = self.loss_fn(self.net, NDArray._from_data(x), NDArray._from_data(y))
            finally:
                autograd.set_training(prev_t)
                autograd.set_recording(prev_r)
            # loss reduction in at least f32 (a bf16 batch-mean loses
            # precision in exactly the scalar people monitor); promoted,
            # not pinned, so float64 nets keep an f64 loss
            ldt = jnp.promote_types(loss._data.dtype, jnp.float32)
            loss_data = jnp.mean(loss._data.astype(ldt))
            # aux state updates (BN running stats) show up as rebound arrays
            aux_new = {
                n: mapping[n]._data
                for n in other_params
                if mapping[n]._data is not other_params[n]
            }
            return loss_data, aux_new

        forward_scan = forward
        if self.remat and self.remat_policy:
            # policy-selective remat: the named policy decides which
            # intermediates are saved (e.g. 'convs' keeps conv and
            # matmul results, recomputing only cheap elementwise in the
            # backward) — strictly less recompute AND less traffic than
            # the blanket checkpoint below on bandwidth-bound programs.
            policy = resolve_remat_policy(self.remat_policy)
            forward_scan = jax.checkpoint(forward, policy=policy,
                                          prevent_cse=False)
            forward = jax.checkpoint(forward, policy=policy)
        elif self.remat:
            # recompute the forward during backward instead of saving
            # activations (identical numerics, ~1/3 more FLOPs, far less
            # HBM) — applied to the WHOLE net forward; XLA still fuses
            # inside each recomputation. The accum scan body gets the
            # barrier-free variant (prevent_cse=False is documented safe
            # under scan and avoids optimization-barrier ops); `step`
            # keeps the default because the same function is jitted
            # standalone (scan_steps reuses step inside its scan, where
            # the barrier is merely conservative).
            forward_scan = jax.checkpoint(forward, prevent_cse=False)
            forward = jax.checkpoint(forward)

        def step(params, states, x, y, key, lr, t):
            grad_params = [d for d, m in zip(params, self.grad_mask) if m]
            other_params = {
                n: d for n, d, m in zip(names, params, self.grad_mask) if not m
            }
            if pin_rep is not None:
                grad_params = pin_rep(grad_params)
                other_params = pin_rep(other_params)
            (loss, aux_new), grads = jax.value_and_grad(forward, has_aux=True)(
                grad_params, other_params, x, y, key
            )
            if pin_rep is not None:
                grads = pin_rep(grads)
            if grad_specs is not None:
                grads = _shard_grads(grads, mesh, grad_specs)
            new_params, new_states = [], []
            gi = 0
            for i, (n, d, m) in enumerate(zip(names, params, self.grad_mask)):
                if m:
                    w, st = self.opt.fused_update(n, d, grads[gi], states[i],
                                                  lr, t=t)
                    gi += 1
                    # pin param/state dtypes: the f32 lr/hyperparam scalars
                    # promote bf16 update math to f32 (the right accumulation
                    # discipline), but the OUTPUT must keep the input dtype
                    # or the scan_steps carry (params/states thread through
                    # a lax.scan) fails to typecheck for bf16-cast nets
                    new_params.append(w.astype(d.dtype))
                    new_states.append(_cast_like(st, states[i]))
                else:
                    new_params.append(aux_new.get(n, d))
                    new_states.append(None)
            return loss, new_params, new_states

        def accum(params, states, xs, ys, keys, lr, t):
            """K micro-batches -> ONE optimizer update, one device program.

            Gradients SUM over micro-batches (set rescale_grad to
            1/(micro_batch * K) for a mean over the effective batch —
            the reference's grad_req='add' accumulation contract); BN aux
            stats update every micro-batch, threaded through the scan
            carry."""
            grad_params = [d for d, m in zip(params, self.grad_mask) if m]
            other_params = {
                n: d for n, d, m in zip(names, params, self.grad_mask) if not m
            }
            if pin_rep is not None:
                grad_params = pin_rep(grad_params)
                other_params = pin_rep(other_params)

            def body(carry, inp):
                others, gsum, lsum = carry
                x, y, key = inp
                (loss, aux_new), grads = jax.value_and_grad(
                    forward_scan, has_aux=True)(grad_params, others, x, y,
                                                key)
                if pin_rep is not None:
                    grads = pin_rep(grads)
                if grad_specs is not None:
                    # shard inside the scan: the micro-batch accumulator
                    # itself lives 1/N per device (sum of slices ==
                    # slice of sum, so accumulation order is untouched)
                    grads = _shard_grads(grads, mesh, grad_specs)
                others = {**others, **aux_new}
                gsum = [a + g for a, g in zip(gsum, grads)]
                return (others, gsum, lsum + loss.astype(lsum.dtype)), None

            zero_g = [jnp.zeros_like(d) for d in grad_params]
            # loss accumulator in the same promoted dtype forward() emits
            # (>= f32; f64 for float64 nets), so the f64 path keeps an f64
            # loss through accumulation too
            float_dts = [d.dtype for d in grad_params
                         if jnp.issubdtype(d.dtype, jnp.floating)]
            acc_dt = jnp.promote_types(
                jnp.result_type(*float_dts) if float_dts else jnp.float32,
                jnp.float32)
            (others_f, gsum, lsum), _ = jax.lax.scan(
                body, (other_params, zero_g, jnp.zeros((), acc_dt)),
                (xs, ys, keys))
            new_params, new_states = [], []
            gi = 0
            for i, (n, d, m) in enumerate(zip(names, params, self.grad_mask)):
                if m:
                    w, st = self.opt.fused_update(n, d, gsum[gi], states[i],
                                                  lr, t=t)
                    gi += 1
                    new_params.append(w.astype(d.dtype))
                    new_states.append(_cast_like(st, states[i]))
                else:
                    new_params.append(others_f.get(n, d))
                    new_states.append(None)
            return lsum / xs.shape[0], new_params, new_states

        self._accum_fn = accum
        return step

    def __call__(self, x, y):
        if not self._built:
            self._build(
                x if isinstance(x, NDArray) else NDArray(jnp.asarray(x)),
                y if isinstance(y, NDArray) else NDArray(jnp.asarray(y)),
            )
        with _telemetry.span("trainstep.call", n=self._n + 1):
            return self._call(x, y)

    def _call(self, x, y):
        xd = x._data if isinstance(x, NDArray) else jnp.asarray(x)
        yd = y._data if isinstance(y, NDArray) else jnp.asarray(y)
        with _stepstats.phase("h2d"):
            if self._data_sharding is not None:
                xd = jax.device_put(xd, self._data_sharding)
                yd = jax.device_put(yd, self._data_sharding)
            elif self.device is not None:
                xd = jax.device_put(xd, self.device)
                yd = jax.device_put(yd, self.device)
        with _stepstats.phase("scalars"):
            # the key, and two tiny uploads a step: lr and the step count
            key = _global_random.next_key()
            self._n += 1
            self.opt.num_update = self._n
            lr = (self.opt.lr_scheduler(self._n) if self.opt.lr_scheduler
                  else self.opt.lr)
            lr = jnp.asarray(lr, jnp.float32)
            t = jnp.asarray(float(self._n), jnp.float32)
        sig = None
        telem = _telemetry.enabled()
        if telem and not getattr(self._step, "is_cached", False):
            # the persistent-cache wrapper does its own registration
            # (cached hits must NOT count as compile events); this
            # dispatch-timing fallback covers the plain-jit path only
            sig = ((tuple(xd.shape), str(xd.dtype)),
                   (tuple(yd.shape), str(yd.dtype)))
            first = not _compilereg.seen("GluonTrainStep.step", sig)
            t0 = _time.perf_counter()
        old_states = self._states if telem else None
        with _stepstats.phase("dispatch"):
            loss, self._params, self._states = self._step(
                self._params, self._states, xd, yd, key, lr, t)
        if telem:
            self._retrack_states(old_states)
        if sig is not None:
            # a first-seen batch signature means this dispatch traced and
            # compiled; any later new signature is a retrace (the event
            # the persistent compile cache exists to eliminate)
            _compilereg.register(
                "GluonTrainStep.step", sig,
                compile_s=(_time.perf_counter() - t0) if first else None)
        if telem:
            _stepstats.step_end()
        return NDArray._from_data(loss)

    def scan_steps(self, xs, ys):
        """Run K training steps as ONE device program: `lax.scan` over the
        leading axis of pre-staged batches, params/states threaded through
        the carry with buffers donated.

        This is the deepest form of the reference's bulked execution
        (MXNET_EXEC_BULK_EXEC_*): zero host work between steps, so device
        throughput is independent of dispatch latency (which dominates on
        remote-attached chips and matters on busy hosts). Feed distinct
        batches stacked on axis 0: xs (K, B, ...), ys (K, B, ...).
        Returns the K per-step losses as one NDArray.
        """
        xd = xs._data if isinstance(xs, NDArray) else jnp.asarray(xs)
        yd = ys._data if isinstance(ys, NDArray) else jnp.asarray(ys)
        if not self._built:
            self._build(NDArray._from_data(xd[0]), NDArray._from_data(yd[0]))
        k = int(xd.shape[0])
        with _telemetry.span("trainstep.call", n=self._n + 1, steps=k):
            return self._scan_call(xd, yd, k)

    def _scan_call(self, xd, yd, k):
        if self._data_sharding is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            stacked = NamedSharding(self.mesh, P(None, "data"))
            xd = jax.device_put(xd, stacked)
            yd = jax.device_put(yd, stacked)
        elif self.device is not None:
            xd = jax.device_put(xd, self.device)
            yd = jax.device_put(yd, self.device)
        keys = jnp.stack([_global_random.next_key() for _ in range(k)])
        lrs, ts = [], []
        for _ in range(k):
            self._n += 1
            lrs.append(self.opt.lr_scheduler(self._n)
                       if self.opt.lr_scheduler else self.opt.lr)
            ts.append(float(self._n))
        self.opt.num_update = self._n
        telem = _telemetry.enabled()
        old_states = self._states if telem else None
        losses, self._params, self._states = self._scan(
            self._params, self._states, xd, yd, keys,
            jnp.asarray(lrs, jnp.float32), jnp.asarray(ts, jnp.float32))
        if telem:
            self._retrack_states(old_states)
        return NDArray._from_data(losses)

    def accum_steps(self, xs, ys):
        """K micro-batches -> ONE optimizer update (gradient accumulation)
        as one device program: grads sum over the K forward/backwards
        (lax.scan), then the optimizer applies once. The big-effective-
        batch analog of the reference's grad_req='add' workflow — set
        rescale_grad = 1/(micro_batch*K) for a mean over the effective
        batch. xs: (K, B, ...), ys: (K, ...). Returns the mean loss."""
        xd = xs._data if isinstance(xs, NDArray) else jnp.asarray(xs)
        yd = ys._data if isinstance(ys, NDArray) else jnp.asarray(ys)
        if not self._built:
            self._build(NDArray._from_data(xd[0]), NDArray._from_data(yd[0]))
        k = int(xd.shape[0])
        if self._data_sharding is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            stacked = NamedSharding(self.mesh, P(None, "data"))
            xd = jax.device_put(xd, stacked)
            yd = jax.device_put(yd, stacked)
        elif self.device is not None:
            xd = jax.device_put(xd, self.device)
            yd = jax.device_put(yd, self.device)
        keys = jnp.stack([_global_random.next_key() for _ in range(k)])
        self._n += 1  # ONE update
        self.opt.num_update = self._n
        lr = (self.opt.lr_scheduler(self._n) if self.opt.lr_scheduler
              else self.opt.lr)
        telem = _telemetry.enabled()
        old_states = self._states if telem else None
        loss, self._params, self._states = self._accum(
            self._params, self._states, xd, yd, keys,
            jnp.asarray(lr, jnp.float32),
            jnp.asarray(float(self._n), jnp.float32))
        if telem:
            self._retrack_states(old_states)
        return NDArray._from_data(loss)

    def save_states(self, fname):
        """Serialize optimizer states + the update count for resume (the
        fused path's Trainer.save_states). Parameters travel separately
        via sync_params() + net.save_parameters; this file carries the
        optimizer side only."""
        import pickle

        if not self._built:
            raise RuntimeError("save_states before the first step: "
                               "optimizer states do not exist yet")
        states_np = jax.tree_util.tree_map(jax.device_get, self._states)
        with open(fname, "wb") as f:
            pickle.dump({"n": self._n, "states": states_np}, f)

    def load_states(self, fname):
        """Restore optimizer states saved by save_states. May be called
        before or after the first step; placements (including sharded
        optimizer states) follow the step's current configuration."""
        import pickle

        with open(fname, "rb") as f:
            d = pickle.load(f)
        self._n = int(d["n"])
        self.opt.num_update = self._n
        if self._built:
            self._states = jax.tree_util.tree_map(
                lambda cur, new: jax.device_put(jnp.asarray(new),
                                                cur.sharding)
                if hasattr(cur, "sharding") else new,
                self._states, d["states"])
        else:
            self._pending_states = d["states"]

    def memory_stats(self, x, y, name="train_step"):
        """Compile-time device memory breakdown of the fused step (the
        storage-profiler answer: per-program HBM from XLA's own analysis,
        recorded into profiler.dumps_memory())."""
        from . import profiler
        from . import random as _rng_mod

        if not self._built:
            self._build(
                x if isinstance(x, NDArray) else NDArray(jnp.asarray(x)),
                y if isinstance(y, NDArray) else NDArray(jnp.asarray(y)),
            )
        xd = x._data if isinstance(x, NDArray) else jnp.asarray(x)
        yd = y._data if isinstance(y, NDArray) else jnp.asarray(y)
        return profiler.memory_analysis(
            self._step, self._params, self._states, xd, yd,
            _rng_mod.next_key(), jnp.asarray(self.opt.lr, jnp.float32),
            jnp.asarray(1.0, jnp.float32), name=name)

    def cost_stats(self, x, y):
        """XLA cost-model totals (flops, bytes accessed) of the compiled
        single-step program — the bytes/step number bench.py records next
        to img/s. Lowers against abstract shapes (no donated buffer is
        touched); with the persistent compilation cache the re-lower is a
        cache hit. Returns {} when the backend exposes no cost model."""
        if not self._built:
            self._build(
                x if isinstance(x, NDArray) else NDArray(jnp.asarray(x)),
                y if isinstance(y, NDArray) else NDArray(jnp.asarray(y)),
            )
        xd = x._data if isinstance(x, NDArray) else jnp.asarray(x)
        yd = y._data if isinstance(y, NDArray) else jnp.asarray(y)
        try:
            abstract = _compile_cache.abstractify(
                (self._params, self._states, xd, yd,
                 jnp.zeros((2,), jnp.uint32),
                 jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)))
            if getattr(self._step, "is_cached", False):
                # cache-resolved: a warm process reads the executable
                # from disk (and registers a cached hit, not a compile)
                ca = self._step.aot_compile(*abstract).cost_analysis()
            else:
                ca = self._step.lower(*abstract).compile().cost_analysis()
            res = {"flops": float(ca.get("flops", 0.0)),
                   "bytes_accessed": float(ca.get("bytes accessed", 0.0))}
            if res and _telemetry.enabled():
                if getattr(self._step, "is_cached", False):
                    sigd = _compile_cache.abstract_signature(abstract)
                else:
                    sigd = ((tuple(xd.shape), str(xd.dtype)),
                            (tuple(yd.shape), str(yd.dtype)))
                    _compilereg.register("GluonTrainStep.step", sigd)
                _compilereg.annotate("GluonTrainStep.step", signature=sigd,
                                     cost=res)
            return res
        except Exception:  # no cost model on this backend/runtime
            return {}

    def warmup(self, x, y):
        """AOT-precompile the fused train step for (x, y)-shaped batches
        into the persistent compile cache without executing a step (no
        param/state buffer is touched or donated) — `tools/warmup.py`'s
        entry point. Abstract args keep the live buffers' committed
        shardings, so the entry written here is the exact one the first
        real step will look up. Returns the cache resolution status:
        "hit" (already on disk), "miss" (compiled and persisted), "memo"
        (already resolved in this process), or "disabled" (no
        MXTPU_COMPILE_CACHE_DIR configured)."""
        if not self._built:
            self._build(
                x if isinstance(x, NDArray) else NDArray(jnp.asarray(x)),
                y if isinstance(y, NDArray) else NDArray(jnp.asarray(y)),
            )
        xd = x._data if isinstance(x, NDArray) else jnp.asarray(x)
        yd = y._data if isinstance(y, NDArray) else jnp.asarray(y)
        if self._data_sharding is not None:
            xd = jax.device_put(xd, self._data_sharding)
            yd = jax.device_put(yd, self._data_sharding)
        elif self.device is not None:
            xd = jax.device_put(xd, self.device)
            yd = jax.device_put(yd, self.device)
        if not getattr(self._step, "is_cached", False):
            return "disabled"
        abstract = _compile_cache.abstractify(
            (self._params, self._states, xd, yd,
             jnp.zeros((2,), jnp.uint32),
             jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)))
        return self._step.warm(*abstract)

    def sync_params(self):
        """Write current param values back into the net's Parameters."""
        for p, d in zip(self.param_objs, self._params):
            p._data._data = d
