"""Pallas TPU kernels for hot ops.

Where the reference reaches for hand-written CUDA (ref: SURVEY §2 N6/N8),
the TPU build authors Pallas kernels. Flash attention here is TRAINABLE:
the forward is the blocked online-softmax kernel (never materializing the
(T, T) score matrix in HBM), and the backward is the standard
FlashAttention-2 recomputation pair — a dQ kernel gridded over query blocks
and a dK/dV kernel gridded over key blocks — wired up with jax.custom_vjp.
Falls back to `interpret=True` off-TPU so the same kernels run in CPU tests.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "softmax_xent", "flash_decode",
           "dense_decode_attention", "paged_decode_attention",
           "paged_decode_attention_wide", "paged_kv_write",
           "paged_write_plan", "paged_diff_attention",
           "paged_ring_write_plan", "selective_scan", "ssd_state_update",
           "ssd_chunk_scan", "power_retention_step",
           "power_retention_chunk", "bn_act_epilogue",
           "DECODE_BLOCK", "DENSE_FALLBACKS_TOTAL"]

_NEG_INF = -1e30

# Per-row statistics (lse, delta) ride with a trailing lane dimension:
# Mosaic requires the last two dims of every block to be (8, 128)-tileable
# or equal to the array dims, so a rank-1 (block_q,) stats block — whose
# sublane dim is a squeezed batch axis — does not lower. The official TPU
# flash kernels (jax.experimental.pallas.ops.tpu.flash_attention
# MIN_BLOCK_SIZE) replicate the scalar across a full 128-wide lane dim;
# 8 lanes satisfies the same rule via the equal-to-array-dim clause at
# 1/16th the HBM footprint.
_STAT_LANES = 8


def _causal_mask(s, q_start, k_start):
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(q_pos >= k_pos, s, _NEG_INF)


def _blocks_until(q_end, block):
    """Number of `block`-sized chunks covering positions [0, q_end)."""
    return (q_end + block - 1) // block


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k, seq_len,
                causal, scale):
    # one grid step handles one (batch*head, q_block); loops over k blocks
    q = q_ref[...]  # (block_q, d)
    block_q, d = q.shape
    q_idx = pl.program_id(1)

    def body(start, carry):
        o, m, l = carry
        k = k_ref[pl.ds(start * block_k, block_k), :]
        v = v_ref[pl.ds(start * block_k, block_k), :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, q_idx * block_q, start * block_k)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1)
        o_new = o * alpha[:, None] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        return o_new, m_new, l_new

    o0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    num_k = seq_len // block_k
    if causal:  # skip fully-masked key blocks above the diagonal
        num_k = _blocks_until((q_idx + 1) * block_q, block_k)
    o, m, l = jax.lax.fori_loop(0, num_k, body, (o0, m0, l0))
    l = jnp.maximum(l, 1e-30)
    o_ref[...] = (o / l[:, None]).astype(o_ref.dtype)
    lse_ref[...] = jnp.broadcast_to((m + jnp.log(l))[:, None],
                                    (block_q, _STAT_LANES))


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
               block_k, seq_len, causal, scale):
    """dQ for one query block: dq = sum_k (P*(dP - D)) * scale @ K."""
    q = q_ref[...]
    do = do_ref[...]
    lse = lse_ref[...][:, :1]    # (block_q, 1) from the lane-replicated tile
    delta = delta_ref[...][:, :1]  # rowsum(dO * O)
    block_q, d = q.shape
    q_idx = pl.program_id(1)

    def body(start, dq):
        k = k_ref[pl.ds(start * block_k, block_k), :]
        v = v_ref[pl.ds(start * block_k, block_k), :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, q_idx * block_q, start * block_k)
        p = jnp.exp(s - lse)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        return dq + jnp.dot(ds.astype(k.dtype), k,
                            preferred_element_type=jnp.float32)

    dq0 = jnp.zeros((block_q, d), jnp.float32)
    num_k = seq_len // block_k
    if causal:
        num_k = _blocks_until((q_idx + 1) * block_q, block_k)
    dq = jax.lax.fori_loop(0, num_k, body, dq0)
    dq_ref[...] = dq.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, *, block_q, seq_len, causal, scale):
    """dK, dV for one key block: loops over query blocks recomputing P."""
    k = k_ref[...]
    v = v_ref[...]
    block_k, d = k.shape
    k_idx = pl.program_id(1)

    def body(start, carry):
        dk, dv = carry
        q = q_ref[pl.ds(start * block_q, block_q), :]
        do = do_ref[pl.ds(start * block_q, block_q), :]
        lse = lse_ref[pl.ds(start * block_q, block_q), :1]    # (bq, 1)
        delta = delta_ref[pl.ds(start * block_q, block_q), :1]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, start * block_q, k_idx * block_k)
        p = jnp.exp(s - lse)                                # (bq, bk)
        dv_new = dv + jnp.dot(p.T.astype(do.dtype), do,
                              preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale                       # (bq, bk)
        dk_new = dk + jnp.dot(ds.T.astype(q.dtype), q,
                              preferred_element_type=jnp.float32)
        return dk_new, dv_new

    zeros = jnp.zeros((block_k, d), jnp.float32)
    num_q = seq_len // block_q
    # skip query blocks strictly above the diagonal (they see no key here)
    start_q = (k_idx * block_k) // block_q if causal else 0
    dk, dv = jax.lax.fori_loop(start_q, num_q, body, (zeros, zeros))
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret):
    B, H, T, D = q.shape
    scale = 1.0 / np.sqrt(D)
    qr = q.reshape(B * H, T, D)
    kr = k.reshape(B * H, T, D)
    vr = v.reshape(B * H, T, D)
    kernel = functools.partial(
        _fwd_kernel, block_k=block_k, seq_len=T, causal=causal, scale=scale)
    o, lse = pl.pallas_call(
        kernel,
        name="flash_attention_fwd",
        grid=(B * H, T // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, T, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, T, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_q, _STAT_LANES),
                         lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, T, _STAT_LANES), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return o.reshape(B, H, T, D), lse[..., 0].reshape(B, H, T)


def _flash_bwd(q, k, v, o, lse, do, causal, block_q, block_k, interpret):
    B, H, T, D = q.shape
    scale = 1.0 / np.sqrt(D)
    qr = q.reshape(B * H, T, D)
    kr = k.reshape(B * H, T, D)
    vr = v.reshape(B * H, T, D)
    dor = do.reshape(B * H, T, D)
    # lane-replicate the per-row stats (see _STAT_LANES)
    lser = jnp.broadcast_to(lse.reshape(B * H, T)[..., None],
                            (B * H, T, _STAT_LANES))
    # D_i = rowsum(dO * O): cheap dense elementwise, no kernel needed
    delta = jnp.sum(dor.astype(jnp.float32)
                    * o.reshape(B * H, T, D).astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (B * H, T, _STAT_LANES))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, block_k=block_k, seq_len=T,
                          causal=causal, scale=scale),
        name="flash_attention_bwd_dq",
        grid=(B * H, T // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, T, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, T, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_q, _STAT_LANES),
                         lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_q, _STAT_LANES),
                         lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, T, D), q.dtype),
        interpret=interpret,
    )(qr, kr, vr, dor, lser, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, block_q=block_q, seq_len=T,
                          causal=causal, scale=scale),
        name="flash_attention_bwd_dkv",
        grid=(B * H, T // block_k),
        in_specs=[
            pl.BlockSpec((None, T, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((None, T, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((None, T, _STAT_LANES), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((None, T, _STAT_LANES), lambda b, j: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, D), k.dtype),
            jax.ShapeDtypeStruct((B * H, T, D), v.dtype),
        ],
        interpret=interpret,
    )(qr, kr, vr, dor, lser, delta)

    return (dq.reshape(B, H, T, D), dk.reshape(B, H, T, D),
            dv.reshape(B, H, T, D))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, block_q, block_k, interpret):
    o, _ = _flash_fwd(q, k, v, causal, block_q, block_k, interpret)
    return o


def _flash_vjp_fwd(q, k, v, causal, block_q, block_k, interpret):
    o, lse = _flash_fwd(q, k, v, causal, block_q, block_k, interpret)
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(causal, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    return _flash_bwd(q, k, v, o, lse, do, causal, block_q, block_k,
                      interpret)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, causal=False, block_q=128, block_k=128,
                    interpret=None):
    """Fused attention: q,k,v (B, H, T, D) -> (B, H, T, D).

    Blocked flash-attention Pallas kernels, forward AND backward
    (FlashAttention-2 recomputation scheme): O(T) HBM, scores live in VMEM,
    trainable under jax.grad.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    B, H, T, D = q.shape
    block_q = min(block_q, T)
    block_k = min(block_k, T)
    assert T % block_q == 0 and T % block_k == 0, "seq len must divide blocks"
    return _flash(q, k, v, causal, block_q, block_k, interpret)


# ---------------------------------------------------------------------------
# Fused softmax cross-entropy (the transformer loss hot path)
# ---------------------------------------------------------------------------
#
# For large vocabularies the naive loss materializes softmax(logits) in HBM
# (B*V floats) twice — once forward, once backward. These kernels keep each
# (block_b, V) tile in VMEM: the forward computes max/logsumexp/label-logit
# in one pass and emits only per-row scalars; the backward regenerates
# softmax from the saved logsumexp and fuses the one-hot subtraction
# (ref role: softmax_output-inl.h fused SoftmaxOutput grad kernel).


def _xent_fwd_kernel(logits_ref, labels_ref, loss_ref, lse_ref):
    # per-row tensors ride as (block_b, 1): Mosaic rejects rank-1 blocks
    # unless they span the array or tile by 128 (the trailing unit lane
    # dim passes via the equal-to-array-dim clause)
    logits = logits_ref[...].astype(jnp.float32)      # (block_b, V)
    labels = labels_ref[...][:, 0]                    # (block_b,)
    m = jnp.max(logits, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[:, None]), axis=-1))
    onehot = (jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
              == labels[:, None])
    picked = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
    loss_ref[...] = (lse - picked)[:, None]
    lse_ref[...] = lse[:, None]


def _xent_bwd_kernel(logits_ref, labels_ref, lse_ref, dloss_ref, dlogits_ref):
    logits = logits_ref[...].astype(jnp.float32)
    labels = labels_ref[...][:, 0]
    lse = lse_ref[...][:, 0]
    dloss = dloss_ref[...][:, 0]
    p = jnp.exp(logits - lse[:, None])                # softmax, recomputed
    onehot = (jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
              == labels[:, None])
    dlogits_ref[...] = ((p - onehot.astype(jnp.float32))
                        * dloss[:, None]).astype(dlogits_ref.dtype)


def _sds(shape, dtype, vma):
    """ShapeDtypeStruct carrying varying-mesh-axes metadata when the kernel
    runs inside a shard_map body (jax requires it with check_vma)."""
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=frozenset(vma))
    return jax.ShapeDtypeStruct(shape, dtype)


def _xent_fwd(logits, labels, block_b, interpret, vma):
    b, v = logits.shape
    grid = (pl.cdiv(b, block_b),)
    loss, lse = pl.pallas_call(
        _xent_fwd_kernel,
        name="softmax_xent_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, v), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            _sds((b, 1), jnp.float32, vma),
            _sds((b, 1), jnp.float32, vma),
        ],
        interpret=interpret,
    )(logits, labels[:, None])
    return loss[:, 0], lse[:, 0]


def _xent_bwd_call(logits, labels, lse, dloss, block_b, interpret, vma):
    b, v = logits.shape
    grid = (pl.cdiv(b, block_b),)
    return pl.pallas_call(
        _xent_bwd_kernel,
        name="softmax_xent_bwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, v), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, v), lambda i: (i, 0)),
        out_shape=_sds((b, v), logits.dtype, vma),
        interpret=interpret,
    )(logits, labels[:, None], lse[:, None], dloss[:, None])


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _xent(logits, labels, block_b, interpret, vma):
    loss, _ = _xent_fwd(logits, labels, block_b, interpret, vma)
    return loss


def _xent_vjp_fwd(logits, labels, block_b, interpret, vma):
    loss, lse = _xent_fwd(logits, labels, block_b, interpret, vma)
    return loss, (logits, labels, lse)


def _xent_vjp_bwd(block_b, interpret, vma, res, dloss):
    logits, labels, lse = res
    dlogits = _xent_bwd_call(logits, labels, lse, dloss, block_b, interpret,
                             vma)
    return dlogits, None


_xent.defvjp(_xent_vjp_fwd, _xent_vjp_bwd)


def softmax_xent(logits, labels, block_b=8, interpret=None, vma=None):
    """Fused per-row softmax cross-entropy: logits (..., V) x int labels
    (...,) -> loss (...,). Differentiable (custom VJP regenerates softmax
    from the saved logsumexp — no (B, V) softmax tensor ever hits HBM).
    Inside a shard_map body pass `vma` = the mesh axes the data varies
    over (jax requires the metadata on pallas outputs there)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    shape = logits.shape[:-1]
    v = logits.shape[-1]
    flat = logits.reshape(-1, v)
    lab = labels.reshape(-1).astype(jnp.int32)
    block_b = min(block_b, flat.shape[0])
    if vma is None:
        # inside a shard_map body the outputs must carry the same
        # varying-mesh-axes metadata as the traced inputs
        vma = tuple(getattr(jax.typeof(flat), "vma", ()) or ())
    if interpret and vma and jax.default_backend() == "cpu":
        # interpret-mode Pallas inside shard_map trips jax's vma accounting
        # in the emulation machinery itself (a CPU-test-only configuration,
        # unreachable on a TPU backend); use the numerically-identical
        # dense form there. Compiled kernels take the pallas_call path
        # with vma-tagged outputs.
        logp = jax.nn.log_softmax(flat.astype(jnp.float32), axis=-1)
        loss = -jnp.take_along_axis(logp, lab[:, None], axis=-1)[:, 0]
        return loss.reshape(shape)
    loss = _xent(flat, lab, block_b, interpret,
                 tuple(vma) if vma else None)
    return loss.reshape(shape)


# ---------------------------------------------------------------------------
# Flash decode: single-query attention over a KV cache (the serving-side
# memory-bound op — one (1, D) query streams the cache once, online softmax,
# no (T,) probability vector in HBM). Valid lengths arrive as data so every
# decode step is the same compiled kernel; `n_valid` may be a scalar (whole
# batch at one depth — the lockstep decode_step path) or a (B,) vector
# (per-sequence depths — the continuous-batching serving path).
# ---------------------------------------------------------------------------

# flash_decode tiles the cache time axis in blocks of this size; caches are
# padded up to a multiple at init (models.transformer.init_kv_cache) so the
# Pallas path always engages instead of silently falling back to dense.
DECODE_BLOCK = 128

DENSE_FALLBACKS_TOTAL = "mxtpu_decode_dense_fallbacks_total"
_FALLBACKS_HELP = ("flash_decode calls that fell back to the dense "
                   "(non-Pallas) cache attention because the cache length "
                   "does not tile into decode blocks, by reason.")


def _count_dense_fallback(reason):
    # trace-time event (shapes are static), so the counter costs nothing
    # on the per-step hot path, and it counts whether or not telemetry is
    # on: a program is traced once, often before anyone enables it
    from ..telemetry import REGISTRY

    REGISTRY.counter(DENSE_FALLBACKS_TOTAL, _FALLBACKS_HELP).inc(
        1, reason=reason)


def _per_seq_n_valid(n_valid, batch):
    """Canonicalize `n_valid` (python/traced scalar or (B,) vector) to a
    (B,) int32 vector."""
    nv = jnp.asarray(n_valid, jnp.int32)
    return jnp.broadcast_to(nv, (batch,))


def _online_softmax_update(q, k, v, live, carry, scale):
    """One key block of the decode kernels' online softmax. q (Q, d),
    k/v (block, d), live (Q, block) bool. Row statistics ride as (Q, 1)
    columns: Mosaic has no layout for the rank-1 (Q,) vectors a plain
    axis reduction would carry through the fori_loop."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    return _online_softmax_accumulate(s, v, live, carry)


def _online_softmax_accumulate(s, v, live, carry):
    """The update above from the scaled scores s (Q, block) on; with a
    leading axis of heads on everything, (Hg, Q, block) against
    (Hg, block, d), it is Hg such updates in one."""
    o, m, l = carry
    s = jnp.where(live, s, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    o_new = o * alpha + jnp.matmul(p.astype(v.dtype), v,
                                   preferred_element_type=jnp.float32)
    return o_new, m_new, l_new


def _online_softmax_init(*shape):
    """The carry before the first block, for an accumulator of `shape`
    ((Q, d), or (Hg, Q, d))."""
    stat = shape[:-1] + (1,)
    return (jnp.zeros(shape, jnp.float32),
            jnp.full(stat, _NEG_INF, jnp.float32),
            jnp.zeros(stat, jnp.float32))


def _online_softmax_finish(carry, dtype):
    o, _, l = carry
    return (o / jnp.maximum(l, 1e-30)).astype(dtype)


def _decode_kernel(nv_ref, q_ref, k_ref, v_ref, o_ref, *, block_k, scale):
    """One (b, h) grid step; nv_ref (B,) is a scalar-prefetch (SMEM) ref."""
    q = q_ref[...]  # (1, d)
    nv = nv_ref[pl.program_id(0)]

    def body(j, carry):
        start = pl.multiple_of(j * block_k, block_k)
        k = k_ref[pl.ds(start, block_k), :]
        v = v_ref[pl.ds(start, block_k), :]
        idx = start + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        return _online_softmax_update(q, k, v, idx < nv, carry, scale)

    num_k = (nv + block_k - 1) // block_k  # dynamic: stream only live blocks
    carry = jax.lax.fori_loop(0, num_k, body,
                              _online_softmax_init(1, q.shape[1]))
    o_ref[...] = _online_softmax_finish(carry, o_ref.dtype)


def dense_decode_attention(q, k_cache, v_cache, n_valid):
    """Reference single-query cache attention (also the non-tiling
    fallback and decode_step's dense path): q (B, H, D), caches
    (B, T, H, D), attend to the first n_valid positions. `n_valid` is a
    scalar (one depth for the whole batch) or a (B,) vector (ragged
    per-sequence depths)."""
    B, T = k_cache.shape[0], k_cache.shape[1]
    D = q.shape[-1]
    nv = _per_seq_n_valid(n_valid, B)
    s = jnp.einsum("bhd,bthd->bht", q, k_cache) / np.sqrt(D)
    s = jnp.where(jnp.arange(T)[None, None] < nv[:, None, None], s,
                  _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bht,bthd->bhd", p, v_cache)


def _epilogue_fwd_kernel(x_ref, scale_ref, shift_ref, o_ref):
    """y = relu(x*scale + shift) for one (block_r, C) tile, f32 math."""
    x = x_ref[...].astype(jnp.float32)
    y = x * scale_ref[...] + shift_ref[...]
    o_ref[...] = jnp.maximum(y, 0.0).astype(o_ref.dtype)


def _epilogue_res_fwd_kernel(x_ref, scale_ref, shift_ref, r_ref, o_ref):
    """y = relu(x*scale + shift + residual) in one tile pass."""
    x = x_ref[...].astype(jnp.float32)
    y = x * scale_ref[...] + shift_ref[...] + r_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.maximum(y, 0.0).astype(o_ref.dtype)


def _epilogue_bwd_kernel(x_ref, scale_ref, y_ref, dy_ref,
                         dx_ref, dscale_ref, dshift_ref, *, block_r, rows,
                         dres_ref=None):
    """Backward tile: mask from y>0 (no pre-activation tensor saved),
    dx = dy*mask*scale, channel sums dscale/dshift ACCUMULATE across the
    sequential TPU grid into one revisited (1, C) block (zeroed at i==0).
    The final row block may be ragged (cdiv grid): rows beyond `rows` are
    masked out of both dx and the channel sums."""
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    row = i * block_r + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    live = (row < rows) & (y_ref[...].astype(jnp.float32) > 0.0)
    g = jnp.where(live, dy, 0.0)
    # x must be masked too: the padded tail of a ragged block reads as
    # NaN in interpret mode, and 0 * NaN poisons the channel sums
    x = jnp.where(live, x, 0.0)
    dx_ref[...] = (g * scale_ref[...]).astype(dx_ref.dtype)
    if dres_ref is not None:
        dres_ref[...] = g.astype(dres_ref.dtype)

    @pl.when(i == 0)
    def _zero():
        dscale_ref[...] = jnp.zeros_like(dscale_ref)
        dshift_ref[...] = jnp.zeros_like(dshift_ref)

    dscale_ref[...] += jnp.sum(g * x, axis=0, keepdims=True)
    dshift_ref[...] += jnp.sum(g, axis=0, keepdims=True)


def _epilogue_fwd_call(x, scale, shift, residual, block_r, interpret):
    r, c = x.shape
    grid = (pl.cdiv(r, block_r),)
    row_spec = pl.BlockSpec((block_r, c), lambda i: (i, 0))
    chan_spec = pl.BlockSpec((1, c), lambda i: (0, 0))
    if residual is None:
        return pl.pallas_call(
            _epilogue_fwd_kernel,
            name="bn_act_epilogue_fwd",
            grid=grid,
            in_specs=[row_spec, chan_spec, chan_spec],
            out_specs=row_spec,
            out_shape=jax.ShapeDtypeStruct((r, c), x.dtype),
            interpret=interpret,
        )(x, scale, shift)
    return pl.pallas_call(
        _epilogue_res_fwd_kernel,
        name="bn_act_epilogue_res_fwd",
        grid=grid,
        in_specs=[row_spec, chan_spec, chan_spec, row_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((r, c), x.dtype),
        interpret=interpret,
    )(x, scale, shift, residual)


def _epilogue_bwd_call(x, scale, y, dy, with_res, block_r, interpret):
    r, c = x.shape
    grid = (pl.cdiv(r, block_r),)
    row_spec = pl.BlockSpec((block_r, c), lambda i: (i, 0))
    chan_spec = pl.BlockSpec((1, c), lambda i: (0, 0))
    kernel = functools.partial(_epilogue_bwd_kernel, block_r=block_r, rows=r)
    if with_res:
        # dres rides as a 4th output; wrap so it lands after dshift in the
        # positional out_refs yet reaches the kernel as a keyword
        def kernel(x_ref, scale_ref, y_ref, dy_ref, dx_ref, dscale_ref,
                   dshift_ref, dres_ref):
            _epilogue_bwd_kernel(x_ref, scale_ref, y_ref, dy_ref, dx_ref,
                                 dscale_ref, dshift_ref, block_r=block_r,
                                 rows=r, dres_ref=dres_ref)
    out_specs = [row_spec, chan_spec, chan_spec]
    out_shape = [
        jax.ShapeDtypeStruct((r, c), x.dtype),
        jax.ShapeDtypeStruct((1, c), jnp.float32),
        jax.ShapeDtypeStruct((1, c), jnp.float32),
    ]
    if with_res:
        out_specs.append(row_spec)
        out_shape.append(jax.ShapeDtypeStruct((r, c), dy.dtype))
    return pl.pallas_call(
        kernel,
        name="bn_act_epilogue_bwd",
        grid=grid,
        in_specs=[row_spec, chan_spec, row_spec, row_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(x, scale, y, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _epi_plain(x, scale, shift, block_r, interpret):
    return _epilogue_fwd_call(x, scale, shift, None, block_r, interpret)


def _epi_plain_fwd(x, scale, shift, block_r, interpret):
    y = _epilogue_fwd_call(x, scale, shift, None, block_r, interpret)
    return y, (x, scale, y)


def _epi_plain_bwd(block_r, interpret, res, dy):
    x, scale, y = res
    dx, dscale, dshift = _epilogue_bwd_call(x, scale, y, dy, False, block_r,
                                            interpret)
    return dx, dscale, dshift  # scale/shift primals are (1, C)


_epi_plain.defvjp(_epi_plain_fwd, _epi_plain_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _epi_res(x, scale, shift, residual, block_r, interpret):
    return _epilogue_fwd_call(x, scale, shift, residual, block_r, interpret)


def _epi_res_fwd(x, scale, shift, residual, block_r, interpret):
    y = _epilogue_fwd_call(x, scale, shift, residual, block_r, interpret)
    # the residual itself is NOT saved: its gradient is dy*mask, and the
    # mask regenerates from y
    return y, (x, scale, y)


def _epi_res_bwd(block_r, interpret, res, dy):
    x, scale, y = res
    dx, dscale, dshift, dres = _epilogue_bwd_call(x, scale, y, dy, True,
                                                  block_r, interpret)
    return dx, dscale, dshift, dres


_epi_res.defvjp(_epi_res_fwd, _epi_res_bwd)


def bn_act_epilogue(x, scale, shift, residual=None, block_rows=256,
                    interpret=None):
    """Fused conv/matmul epilogue: relu(x*scale + shift [+ residual]) on a
    channels-last accumulator in ONE HBM pass, with a custom-VJP backward.

    x: (..., C) — typically an NHWC conv output; scale/shift: (C,) — the
    BN affine folded to per-channel scale = gamma*rsqrt(var+eps) and
    shift = beta - mean*scale; residual: same shape as x or None. Math in
    f32, output in x.dtype. The backward recomputes the ReLU mask from
    the saved OUTPUT (y > 0), so no pre-activation tensor is kept:
    dx = dy*mask*scale, dresidual = dy*mask, dscale = Σ dy*mask*x,
    dshift = Σ dy*mask (channel sums accumulated across the sequential
    grid). This is the HBM-traffic lever MXTPU_FUSED_EPILOGUE arms: the
    BN-normalize + ReLU + residual-add chain reads and writes the
    activation tensor once instead of once per op."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    c = x.shape[-1]
    flat = x.reshape(-1, c)
    r = flat.shape[0]
    block_r = min(block_rows, r)
    scale2 = jnp.asarray(scale, jnp.float32).reshape(1, c)
    shift2 = jnp.asarray(shift, jnp.float32).reshape(1, c)
    if residual is None:
        y = _epi_plain(flat, scale2, shift2, block_r, interpret)
    else:
        y = _epi_res(flat, scale2, shift2, residual.reshape(-1, c), block_r,
                     interpret)
    return y.reshape(x.shape)


def flash_decode(q, k_cache, v_cache, n_valid, block_k=DECODE_BLOCK,
                 interpret=None):
    """Single-step attention: q (B, H, D) against caches (B, T, H, D),
    attending to the first `n_valid` positions (traced scalar, or a (B,)
    vector of per-sequence depths). Returns (B, H, D)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    B, T, H, D = k_cache.shape
    blk = min(block_k, T)
    if T % blk != 0:  # cache length must tile; fall back to dense
        _count_dense_fallback("untiled_cache")
        return dense_decode_attention(q, k_cache, v_cache, n_valid)
    qr = q.reshape(B, H, 1, D)
    kr = k_cache.transpose(0, 2, 1, 3)  # (B, H, T, D)
    vr = v_cache.transpose(0, 2, 1, 3)
    nv = _per_seq_n_valid(n_valid, B)
    kernel = functools.partial(_decode_kernel, block_k=blk,
                               scale=1.0 / np.sqrt(D))
    # n_valid rides as a scalar-prefetch (SMEM) argument: a rank-1 (1,)
    # block over a (B,) array is not a legal TPU block shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H),
        in_specs=[
            pl.BlockSpec((None, None, 1, D), lambda b, h, *refs: (b, h, 0, 0)),
            pl.BlockSpec((None, None, T, D), lambda b, h, *refs: (b, h, 0, 0)),
            pl.BlockSpec((None, None, T, D), lambda b, h, *refs: (b, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, 1, D),
                               lambda b, h, *refs: (b, h, 0, 0)),
    )
    o = pl.pallas_call(
        kernel,
        name="flash_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, 1, D), q.dtype),
        interpret=interpret,
    )(nv, qr, kr, vr)
    return o.reshape(B, H, D)


# ---------------------------------------------------------------------------
# Paged decode: single-query attention where K/V live in a global page pool
# shared by every sequence (the vLLM/PagedAttention data structure). Each
# sequence owns a page-table row; the kernel walks it with dynamic page
# indices and runs the same online-softmax accumulation as _decode_kernel.
# Per-sequence valid lengths make it the continuous-batching serving kernel:
# slots at different depths decode in ONE launch of one compiled program.
#
# The pool is ONE buffer for every layer, (L, H, num_pages, page_size, 2*D),
# and it never moves: the layer loop carries it, paged_kv_write stores a
# step's new rows into layer l in place (input_output_aliases), and the
# attention kernels read layer l where it lies, by a scalar-prefetched layer
# index (in the DMA's source slice, or in a block's index map). Nothing is
# sliced, copied or re-laid-out per layer.
#
# A row holds a token's K in lanes [0, D) and its V in lanes [D, 2*D). With
# D = 64 that is one full 128-lane row, so the row-major tiled layout Mosaic
# wants for the block is also the layout XLA gives the array at the jit
# boundary, without padding. (K and V as separate (..., page_size, 64)
# arrays pad every row to 128 lanes for Mosaic, twice the bytes, and XLA
# keeps them at the boundary with the page axis minor-most instead: every
# step would re-lay-out the whole pool going in and coming out.) The kernels
# read K as the row's first D lanes, a lane-prefix load that moves nothing,
# and never cut V out: p . row carries the attention output in its V lanes,
# and the caller drops the K lanes of the result.
#
# Head-major: a page of one head is (page_size, 2*D) with its last two
# dimensions whole, the only shape the TPU lowering accepts for a per-head
# slice (a head axis squeezed in the second-minor position is refused), and
# a page of a RUN of heads, pool[l, h0:h0+Hg, page], is Hg such runs of
# whole rows: one strided DMA.
#
# Every attention kernel over the pool leaves it in HBM and walks a slot's
# table in BLOCKS (_paged_block_walk): one grid step per slot, all its heads
# at once; a block of 128 tokens' pages gathered into VMEM by one copy per
# page, double-buffered; one loop step per block for every head. Only the
# slot's live pages move, and the pool may be of any size.
# paged_decode_attention(_wide) scores Q rows per head (_paged_decode_kernel),
# paged_diff_attention (models.sambay) 4 rows per pair of heads, from the
# first page its window reaches, through a table or a ring
# (_paged_diff_kernel): two small bodies on the one walk.
# ---------------------------------------------------------------------------


def _layer_index(layer):
    return jnp.asarray(layer, jnp.int32).reshape(1)


def _paged_write_kernel(l_ref, pg_ref, lo_ref, hi_ref, new_ref, old_ref,
                        out_ref):
    """One (s, j) grid step: the j-th page sequence s writes this call.
    Rows [lo, hi) of the page take the new values, the rest keep what
    the pool holds. All refs are (H, page_size, 2*D)."""
    s, j = pl.program_id(0), pl.program_id(1)
    row = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    take = (row >= lo_ref[s, j]) & (row < hi_ref[s, j])
    out_ref[...] = jnp.where(take, new_ref[...], old_ref[...])


def paged_write_plan(page_table, start, n_write, n_rows, page_size):
    """Where a call's new K/V rows go, worked out once for every layer.

    page_table: (S, P_max) int32; start, n_write: (S,) int32 — row t of
    sequence s sits at position start[s] + t and is stored only for
    t < n_write[s] (padding, dead slots and speculative overrun store
    nothing; positions past the table's capacity are dropped too);
    n_rows: T, the rows each sequence brings; page_size: static.

    Returns (pages, lo, hi, src): for the j-th page a sequence can touch,
    pages (S, J) its id — the null page 0 where there is nothing to
    store — and lo, hi (S, J) the rows [lo, hi) of it that take new
    values; src (S, J * page_size) maps the page grid back to the call's
    rows (aligned row i of sequence s is position
    (start[s] // page_size) * page_size + i)."""
    W = page_table.shape[1]
    # pages a run of T rows can touch when it starts anywhere in a page
    J = (n_rows + 2 * page_size - 2) // page_size
    start = jnp.asarray(start, jnp.int32)
    end = jnp.minimum(start + jnp.asarray(n_write, jnp.int32),
                      W * page_size)
    col = ((start // page_size)[:, None]
           + jnp.arange(J, dtype=jnp.int32)[None])  # (S, J) table columns
    lo = jnp.clip(start[:, None] - col * page_size, 0, page_size)
    hi = jnp.clip(end[:, None] - col * page_size, 0, page_size)
    pages = jnp.where(
        hi > lo,
        jnp.take_along_axis(jnp.asarray(page_table, jnp.int32),
                            jnp.minimum(col, W - 1), axis=1), 0)
    src = (jnp.arange(J * page_size, dtype=jnp.int32)[None]
           - (start % page_size)[:, None])
    return pages, lo, hi, jnp.clip(src, 0, n_rows - 1)


def paged_kv_write(pool, layer, k, v, plan, interpret=None):
    """Store new K/V rows into one layer of the paged pool, in place.

    pool: (L, H, num_pages, page_size, 2*D); layer: int32 scalar (traced
    in the layer loop); k, v: (S, T, H, D); plan: paged_write_plan's
    tuple for these S sequences of T rows.

    One grid step per (sequence, page touched): the page's block comes in
    and goes out through the SAME buffer (input_output_aliases), so under
    a donated jit and a loop that carries the pool, XLA allocates nothing
    and copies nothing. Steps with no row to store point at the null
    page 0 and leave it as it is. A sequence's pages are its own (the
    allocator's copy-on-write guarantees it), so no two steps that store
    rows share a page.

    Returns the pool."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    pages, lo, hi, src = plan
    H, page_size, D2 = pool.shape[1], pool.shape[3], pool.shape[4]
    # rows head-major, K|V fused, shifted onto the page grid
    rows = jnp.concatenate([k, v], axis=-1).astype(pool.dtype)
    rows = jnp.take_along_axis(rows.transpose(0, 2, 1, 3),  # (S, H, T, 2*D)
                               src[:, None, :, None], axis=2)
    page_spec = pl.BlockSpec(
        (None, H, None, page_size, D2),
        lambda s, j, l, pg, *refs: (l[0], 0, pg[s, j], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=pages.shape,
        in_specs=[
            pl.BlockSpec((None, H, page_size, D2),
                         lambda s, j, *refs: (s, 0, j, 0)),
            page_spec,
        ],
        out_specs=page_spec,
    )
    return pl.pallas_call(
        _paged_write_kernel,
        name="paged_kv_write",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        # operand 5 (after the four scalar-prefetch arrays and the rows)
        # is the pool, and it is the output
        input_output_aliases={5: 0},
        interpret=interpret,
    )(_layer_index(layer), pages, lo, hi, rows, pool)


# Tokens one loop step of the paged decode walk covers: the lane width, so a
# step's scores are full 128-lane rows. A block is this many tokens' pages,
# consecutive page-table entries of one slot (8 pages of 16); a page at
# least this long is a block by itself.
PAGED_BLOCK_TOKENS = 128

# VMEM one grid step of the walk may take, half of what Mosaic scopes a
# kernel by default. Every head of a slot rides in one grid step while they
# fit (25 heads of 64 in bf16 with one query row: 1.7 MB); beyond it the
# heads go in equal groups, each a grid step of its own.
_PAGED_BLOCK_VMEM_BYTES = 8 * 1024 * 1024


def paged_block_tokens(page_size):
    """Tokens the paged decode kernel fetches per block, whole pages: what
    a slot's depth is rounded up to when the rows it reads are counted."""
    return max(PAGED_BLOCK_TOKENS // page_size, 1) * page_size


def paged_walk_tokens(n_valid, page_size, window=0):
    """Tokens the block walk fetches for slots `n_valid` deep (host
    integers, (S,)): whole blocks from the first page the window reaches
    (page 0 without one) to the last live page, summed over the slots.
    The host's count of what the kernels below gather, to set beside what
    they attend."""
    n = np.asarray(n_valid, np.int64)
    per_block = paged_block_tokens(page_size) // page_size
    first = np.maximum(n - window, 0) // page_size if window else 0
    pages = -(-n // page_size) - first
    return int((-(-pages // per_block)).sum()) * per_block * page_size


def _paged_head_group(n_heads, n_q, block_tokens, row_lanes, dtype):
    """Heads per grid step: the largest divisor of n_heads whose two block
    buffers and float32 working rows (scores, probabilities and the
    accumulator, in and out, of n_q query rows) fit
    _PAGED_BLOCK_VMEM_BYTES."""
    per_head = (2 * block_tokens * row_lanes * jnp.dtype(dtype).itemsize
                + 4 * n_q * 3 * (block_tokens + row_lanes))
    fit = max(_PAGED_BLOCK_VMEM_BYTES // per_head, 1)
    return max(g for g in range(1, min(n_heads, fit) + 1)
               if n_heads % g == 0)


def _paged_block_walk(pt_ref, pool_ref, buf, sem, b, layer, h0, first,
                      n_pages, *, page_size, ring=False):
    """The walk both paged attention kernels make: slot b's `n_pages`
    table entries from absolute page `first` on (None: from page 0, with
    no arithmetic for it), in layer `layer`, heads h0 .. h0 + Hg, block
    by block.

    pt_ref (B, W) is the scalar-prefetched page table, a ring under `ring`
    (absolute page a in column a % W, else in column a); pool_ref the whole
    pool where it lies in HBM, (L, H, num_pages, page_size, 2*d); buf
    (2, Hg, block, 2*d) and sem (2,) the two block buffers and their DMA
    semaphores.

    A block is `pages` consecutive entries, gathered by one strided copy
    per page that covers all Hg heads (pool[l, h0:h0+Hg, page] is
    (Hg, page_size, 2*d) with whole rows: Hg runs of one page each), block
    j + 1 in flight while block j is computed. Entries past the walk's
    last page are not trusted: a block's tail is the null page 0, finite,
    and the caller masks it.

    Returns (start, run): start() sets block 0 going (whatever the caller
    does next hides behind it); run(step, carry) loops over the blocks,
    carry = step(j, rows, carry) with rows (Hg, block, 2*d) the j-th. No
    page means no copy and no loop step."""
    width = pt_ref.shape[1]
    hg = buf.shape[1]
    pages = buf.shape[2] // page_size
    n_blocks = (n_pages + pages - 1) // pages

    def copies(j, slot):
        out = []
        for c in range(pages):
            i = j * pages + c
            live = i < n_pages
            a = i if first is None else first + i
            col = a % width if ring else jnp.minimum(a, width - 1)
            page = jnp.where(live, pt_ref[b, col], 0)
            out.append(pltpu.make_async_copy(
                pool_ref.at[layer, pl.ds(h0, hg), page],
                buf.at[slot, :, pl.ds(c * page_size, page_size)],
                sem.at[slot]))
        return out

    def start():
        @pl.when(n_blocks > 0)
        def _():
            for c in copies(0, 0):
                c.start()

    def run(step, carry):
        def body(j, carry):
            slot = j % 2

            @pl.when(j + 1 < n_blocks)
            def _():
                for c in copies(j + 1, 1 - slot):
                    c.start()

            for c in copies(j, slot):
                c.wait()
            return step(j, buf[slot], carry)

        return jax.lax.fori_loop(0, n_blocks, body, carry)

    return start, run


def _paged_decode_kernel(pt_ref, nv_ref, l_ref, q_ref, pool_ref, o_ref,
                         buf, sem, *, page_size, scale, group=1):
    """One (b, g) grid step: slot b, head group g, Q query rows at
    consecutive positions whose LAST row attends nv tokens (row i attends
    idx < nv - (Q - 1 - i): the paged prefix plus causal masking within
    the call; nv == 0 is a dead slot, which fetches and computes nothing).
    With `group` > 1 a K/V head serves `group` query heads (grouped-query
    attention): its rows are position-major, row r the query of position
    r // group, and the Q above is their number of positions.

    pt_ref (B, W), nv_ref (B,) and l_ref (1,) are scalar-prefetch refs
    (SMEM: control flow and the DMA's page indices). q_ref is (Hg, Q, d);
    o_ref (Hg, Q, 2*d), whose V lanes are the output; pool_ref, buf and
    sem are _paged_block_walk's.

    One loop step scores all Hg heads against the block's 128 K rows in
    one batched product, updates the online softmax over full-lane rows,
    and takes p . rows in another: Hg independent chains per step, so the
    MXUs and the DMA overlap across heads."""
    b, g = pl.program_id(0), pl.program_id(1)
    hg, n_q, d = q_ref.shape
    span = buf.shape[2]
    # positions past the table's capacity hold nothing (speculative
    # overrun; those rows' outputs are discarded): they are never live,
    # and the table is never indexed out of bounds
    cap = pt_ref.shape[1] * page_size
    layer, h0 = l_ref[0], g * hg
    n_pages = (jnp.minimum(nv_ref[b], cap) + page_size - 1) // page_size
    start, run = _paged_block_walk(pt_ref, pool_ref, buf, sem, b, layer, h0,
                                   None, n_pages, page_size=page_size)
    start()
    q = q_ref[...]
    row = jax.lax.broadcasted_iota(jnp.int32, (1, n_q, span), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, n_q, span), 2)
    if group > 1:
        row = row // group
    limit = jnp.minimum(nv_ref[b] - (n_q // group - 1) + row, cap)

    def step(j, rows, carry):
        s = jax.lax.dot_general(
            q, rows[:, :, :d], (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale  # (Hg, Q, span)
        return _online_softmax_accumulate(s, rows, j * span + col < limit,
                                          carry)

    carry = run(step, _online_softmax_init(*o_ref.shape))
    o_ref[...] = _online_softmax_finish(carry, o_ref.dtype)


def _paged_diff_kernel(pt_ref, nv_ref, l_ref, q_ref, pool_ref, o_ref, buf,
                       sem, *, page_size, scale, window, ring):
    """One (b, g) grid step: slot b, Gg pairs of K/V heads and the 4 query
    rows of each, over the slot's n cached tokens (the query's own
    included), the last `window` of them when there is a window; n == 0
    is a dead slot, which fetches and computes nothing.

    Heads 2g and 2g+1 of the pool hold k1_g|v[2g] and k2_g|v[2g+1]; query
    rows 0-1 (q1 of query pairs 2g, 2g+1) are scored against k1_g, rows
    2-3 (their q2) against k2_g, and all four softmaxes weight the same
    value V_g = [v[2g] | v[2g+1]]: the two heads' rows of a token side by
    side, (block, 4*d), whose lanes [d, 2d) and [3d, 4d) carry the output
    (the K lanes ride along as in the kernel above).

    q_ref is (Gg, 4, d), o_ref (Gg, 4, 4*d); the rest as in
    _paged_decode_kernel, buf holding the 2*Gg heads. The walk starts at
    the first page the window reaches."""
    b, g = pl.program_id(0), pl.program_id(1)
    gg, _, d = q_ref.shape
    span = buf.shape[2]
    n = nv_ref[b]
    lo_tok = jnp.maximum(n - window, 0) if window else 0
    first = lo_tok // page_size
    n_pages = (n + page_size - 1) // page_size - first
    start, run = _paged_block_walk(pt_ref, pool_ref, buf, sem, b, l_ref[0],
                                   g * 2 * gg, first, n_pages,
                                   page_size=page_size, ring=ring)
    start()
    q = q_ref[...]
    row = jax.lax.broadcasted_iota(jnp.int32, (1, 4, span), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, 4, span), 2)

    def step(j, rows, carry):
        rows = rows.reshape(gg, 2, span, 2 * d)
        r1, r2 = rows[:, 0], rows[:, 1]
        s1, s2 = (jax.lax.dot_general(
            q, r[:, :, :d], (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) for r in (r1, r2))
        idx = first * page_size + j * span + col  # (1, 4, span)
        return _online_softmax_accumulate(
            jnp.where(row < 2, s1, s2) * scale,
            jnp.concatenate([r1, r2], axis=2), (idx >= lo_tok) & (idx < n),
            carry)

    carry = run(step, _online_softmax_init(*o_ref.shape))
    o_ref[...] = _online_softmax_finish(carry, o_ref.dtype)


def _paged_walk_scalars(page_table, n_valid, layer, batch):
    """The walk's scalar-prefetch operands (SMEM: control flow and the
    copies' page indices): the table, the count per slot, the layer."""
    return (jnp.asarray(page_table, jnp.int32),
            _per_seq_n_valid(n_valid, batch), _layer_index(layer))


def _paged_walk_call(kernel, name, scalars, q, pool, out_dtype, interpret):
    """Launch one of the walk's kernels. q (B, N, Q, D): Q query rows for
    each of N heads, or groups of heads, of pool
    (L, H, num_pages, page_size, 2*D), H // N K/V heads a group; scalars:
    _paged_walk_scalars'. One grid step per slot and run of groups
    (_paged_head_group). Returns (B, N, Q, (H // N) * 2*D): a group's
    heads' lanes side by side."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    B, N, Q, D = q.shape
    H, page_size, D2 = pool.shape[1], pool.shape[3], pool.shape[4]
    lanes = H // N * D2
    span = paged_block_tokens(page_size)
    ng = _paged_head_group(N, Q, span, lanes, pool.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, N // ng),
        in_specs=[
            pl.BlockSpec((None, ng, Q, D),
                         lambda b, g, *refs: (b, g, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),  # the pool stays in HBM
        ],
        out_specs=pl.BlockSpec((None, ng, Q, lanes),
                               lambda b, g, *refs: (b, g, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, H // N * ng, span, D2), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    return pl.pallas_call(
        functools.partial(kernel, page_size=page_size,
                          scale=1.0 / np.sqrt(D)),
        name=name,  # what a trace reduction finds the kernel by
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, N, Q, lanes), out_dtype),
        interpret=interpret,
    )(*scalars, q, pool)


def _paged_decode_call(q, pool, page_table, n_last, layer, interpret):
    """q (B, Q, Hq, D) over pool (L, H, num_pages, page_size, 2*D) in layer
    `layer`, query head j reading K/V head j // (Hq // H); n_last (B,):
    tokens the last query row of each slot attends. Returns (B, Q, Hq, D)."""
    B, Q, Hq, D = q.shape
    H = pool.shape[1]
    group = Hq // H
    if Hq != H * group:
        raise ValueError(f"{Hq} query heads do not group over {H} K/V heads")
    scalars = _paged_walk_scalars(page_table, n_last, layer, B)
    kernel, rows = _paged_decode_kernel, q.transpose(0, 2, 1, 3)
    if group > 1:
        # a K/V head's rows: its queries at position 0, then position 1, ...
        kernel = functools.partial(kernel, group=group)
        rows = (q.reshape(B, Q, H, group, D).transpose(0, 2, 1, 3, 4)
                .reshape(B, H, Q * group, D))
    o = _paged_walk_call(
        kernel,
        # the engine's decode step is the Q = 1 case
        "paged_decode_attention" if Q == 1 else "paged_decode_attention_wide",
        scalars, rows, pool, q.dtype, interpret)[..., D:]  # the V lanes
    if group > 1:
        return (o.reshape(B, H, Q, group, D).transpose(0, 2, 1, 3, 4)
                .reshape(B, Q, Hq, D))
    return o.transpose(0, 2, 1, 3)


def paged_decode_attention_wide(q, pool, page_table, n_base, layer=0,
                                interpret=None):
    """Wider-query attention over a paged KV cache: Q consecutive query
    tokens per sequence in ONE launch.

    q: (B, Q, H, D) — query i of sequence b sits at position
    n_base[b] + i; pool: (L, H, num_pages, page_size, 2*D), the whole
    pool (paged_kv_write has already stored the Q new tokens' rows in
    layer `layer`); layer: int32 scalar, the layer to read — the kernel's
    copies take it, nothing is sliced out of the pool; page_table:
    (B, P_max) int32 — page ids owned by each sequence, in order
    (entries past the live length are ignored; a row that starts at the
    null page 0 owns nothing: a dead slot, which costs no copy and no
    loop step and reads as zeros); n_base: (B,) int32 —
    tokens cached per sequence BEFORE this call's first query. Query i
    attends positions < n_base + i + 1 (paged prefix + intra-call
    causal), so a single launch serves chunked prefill (Q = chunk),
    cached-prefix tail prefill (n_base = cached tokens) and speculative
    verification (Q = lookahead + 1) — the vLLM/Sarathi "one kernel,
    many query widths" trick on the repo's own block walk.

    Each grid step gathers only its own sequence's live pages out of the
    pool in HBM, a block of paged_block_tokens(page_size) tokens at a
    time; the pool's size is no concern of the kernel's.

    Returns (B, Q, H, D)."""
    nb = _per_seq_n_valid(n_base, q.shape[0])
    live = jnp.asarray(page_table, jnp.int32)[:, 0] != 0
    return _paged_decode_call(q, pool, page_table,
                              jnp.where(live, nb + q.shape[1], 0), layer,
                              interpret)


def paged_decode_attention(q, pool, page_table, n_valid, layer=0,
                           interpret=None):
    """Single-query attention over a paged KV cache — the kernel above
    with Q = 1, under the name a trace reduction finds the engine's
    decode step by.

    q: (B, Hq, D) — one query per decode slot and query head; pool:
    (L, H, num_pages, page_size, 2*D), read in layer `layer`, Hq a
    multiple of H (grouped-query attention: query head j reads K/V head
    j // (Hq // H), a K/V head's Hq // H query rows scored in one
    product, its rows fetched once);
    page_table: (B, P_max) int32; n_valid: (B,) int32 (or scalar) —
    tokens live per slot INCLUDING the one just written; 0 marks a dead
    slot, which costs no copy and no loop step (its output is zeros that
    the caller discards). Returns (B, Hq, D)."""
    return _paged_decode_call(q[:, None], pool, page_table, n_valid, layer,
                              interpret)[:, 0]


# ---------------------------------------------------------------------------
# Grouped differential attention over the paged pool (models.sambay): the
# same block walk, the 4 query heads of each pair of K/V heads scored as
# _paged_diff_kernel says. With `window` the walk starts at the first page
# the window reaches, and with `ring` the table is a ring of pages indexed
# modulo its width: absolute page a lives in column a % width.
# ---------------------------------------------------------------------------


def paged_diff_attention(q, pool, page_table, n_valid, layer=0, *,
                         window=0, ring=False, interpret=None):
    """Single-token grouped differential attention over a paged cache.

    q: (B, G, 4, D) — for K/V group g the query heads that read it, rows
    0-1 scored against head 2g's keys and rows 2-3 against head 2g+1's;
    pool: (L, 2*G, num_pages, page_size, 2*D), K|V fused per row, read in
    layer `layer` where it lies in HBM (a pool of any size); page_table:
    (B, W) int32; n_valid: (B,) tokens cached per slot INCLUDING the
    query's own (0: a dead slot, which costs no copy and no loop step and
    reads as zeros).
    `window` > 0 keeps the last `window` tokens only; `ring` says
    page_table is a ring (absolute page a in column a % W) instead of a
    table that grows with the context.

    Returns (B, G, 4, 2*D): each row's softmax over its keys applied to
    [v[2g] | v[2g+1]], float32."""
    B, G, R, D = q.shape
    H, D2 = pool.shape[1], pool.shape[4]
    if R != 4 or H != 2 * G or D2 != 2 * D:
        raise ValueError(f"q {q.shape} does not group over pool {pool.shape}")
    o = _paged_walk_call(
        functools.partial(_paged_diff_kernel, window=int(window),
                          ring=bool(ring)),
        # a trace reduction tells the two uses apart by name
        "paged_diff_attention_ring" if ring else "paged_diff_attention",
        _paged_walk_scalars(page_table, n_valid, layer, B),
        q.astype(pool.dtype), pool, jnp.float32, interpret)
    return jnp.concatenate([o[..., D:D2], o[..., D2 + D:]], axis=-1)


def paged_ring_write_plan(ring_table, start, n_write, n_rows, page_size):
    """paged_write_plan for a ring of pages: row t of sequence s sits at
    position start[s] + t, stored for t < n_write[s], and absolute page a
    lives in ring_table[s, a % R]. Only the rows the ring can still hold
    when the call ends are stored: the last min(R, pages a run of n_rows
    can touch) pages up to the one holding the last stored row. Returns
    paged_kv_write's plan."""
    R = ring_table.shape[1]
    J = min(R, (n_rows + 2 * page_size - 2) // page_size)
    start = jnp.asarray(start, jnp.int32)
    end = start + jnp.asarray(n_write, jnp.int32)
    last = (end - 1) // page_size  # -1 when nothing was ever stored
    a = last[:, None] - (J - 1) + jnp.arange(J, dtype=jnp.int32)[None]
    lo = jnp.clip(start[:, None] - a * page_size, 0, page_size)
    hi = jnp.clip(end[:, None] - a * page_size, 0, page_size)
    pages = jnp.where(
        (a >= 0) & (hi > lo),
        jnp.take_along_axis(jnp.asarray(ring_table, jnp.int32),
                            jnp.maximum(a, 0) % R, axis=1), 0)
    src = (jnp.repeat(a, page_size, axis=1) * page_size
           + jnp.tile(jnp.arange(page_size, dtype=jnp.int32), J)[None]
           - start[:, None])
    return pages, lo, hi, jnp.clip(src, 0, n_rows - 1)


# ---------------------------------------------------------------------------
# Selective scan (Mamba-1's recurrence): s_t = exp(dt_t A) * s_{t-1}
# + (dt_t a_t) B_t^T over a state s (N, channels) per sequence, float32,
# y_t = s_t^T C_t. The time axis is the grid's last, sequential axis; the
# state of one block of channels lives in VMEM scratch across it, so a
# prompt costs its inputs once and no (T, N, channels) array exists. B_t
# and C_t arrive as (N, 1) columns: the state has the channels on the
# lanes, and a column broadcasts over them without a transpose.
# ---------------------------------------------------------------------------

SCAN_CHANNEL_BLOCK = 512
SCAN_TIME_BLOCK = 128


def _selective_scan_kernel(dt_ref, a_ref, b_ref, c_ref, A_ref, s0_ref, y_ref,
                           sT_ref, s_scr, *, rows):
    """One (sequence, channel block, time block) grid step. dt, a, y:
    (bt, bd); b, c: (bt, N, 1); A, s0, sT, s_scr: (N, bd). Tokens go
    `rows` at a time: one aligned load and store per group."""
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        s_scr[...] = s0_ref[...]

    A = A_ref[...]

    def group(g, s):
        r0 = pl.multiple_of(g * rows, rows)
        dt = dt_ref[pl.ds(r0, rows), :]
        a = a_ref[pl.ds(r0, rows), :]
        ys = []
        for i in range(rows):
            dt_t = dt[i:i + 1, :]                                # (1, bd)
            s = (jnp.exp(dt_t * A) * s
                 + (dt_t * a[i:i + 1, :]) * b_ref[r0 + i])       # (N, bd)
            ys.append(jnp.sum(s * c_ref[r0 + i], axis=0, keepdims=True))
        y_ref[pl.ds(r0, rows), :] = (ys[0] if rows == 1
                                     else jnp.concatenate(ys, axis=0))
        return s

    s = jax.lax.fori_loop(0, dt_ref.shape[0] // rows, group, s_scr[...])
    s_scr[...] = s

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        sT_ref[...] = s


def selective_scan(dt, a, B, C, A, s0, interpret=None):
    """dt, a: (S, T, Di) float32 (a row with dt = 0 leaves the state as it
    is); B, C: (S, T, N); A: (N, Di), negative; s0: (S, N, Di) the state
    before row 0. Returns (y (S, T, Di), the state after row T - 1)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    S, T, Di = dt.shape
    N = A.shape[0]
    bd = min(SCAN_CHANNEL_BLOCK, Di)
    bt = SCAN_TIME_BLOCK if T % SCAN_TIME_BLOCK == 0 else T
    if Di % bd:
        raise ValueError(f"{Di} channels do not split into blocks of {bd}")
    f32 = jnp.float32
    rows_spec = pl.BlockSpec((None, bt, bd), lambda s, d, j: (s, j, d))
    col_spec = pl.BlockSpec((None, bt, N, 1), lambda s, d, j: (s, j, 0, 0))
    state_spec = pl.BlockSpec((None, N, bd), lambda s, d, j: (s, 0, d))
    y, sT = pl.pallas_call(
        functools.partial(_selective_scan_kernel,
                          rows=8 if bt % 8 == 0 else 1),
        name="selective_scan",
        grid=(S, Di // bd, T // bt),
        in_specs=[rows_spec, rows_spec, col_spec, col_spec,
                  pl.BlockSpec((N, bd), lambda s, d, j: (0, d)), state_spec],
        out_specs=[rows_spec, state_spec],
        out_shape=[jax.ShapeDtypeStruct((S, T, Di), f32),
                   jax.ShapeDtypeStruct((S, N, Di), f32)],
        scratch_shapes=[pltpu.VMEM((N, bd), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(dt.astype(f32), a.astype(f32), B.astype(f32)[..., None],
      C.astype(f32)[..., None], A.astype(f32), s0.astype(f32))
    return y, sT


# ---------------------------------------------------------------------------
# State-space duality (Mamba-2's recurrence): one scalar decay per head,
# B_t and C_t shared by a group of heads, a state S (H, P, N) per sequence,
# float32:  S_t = exp(dt_t A) S_{t-1} + (dt_t x_t) (outer) B_t,  y_t = S_t C_t.
# With P = 128 and N = 256 the state is 4 MB a slot and layer, as many bytes
# as 2048 tokens of K/V: a decode step is the state in and out
# (ssd_state_update, in place in the cache's one array for every layer), and
# a prompt goes through it in chunks as matrix products (ssd_chunk_scan).
# ---------------------------------------------------------------------------

SSD_HEAD_BLOCK = 8


def _dead_slot_source(live):
    """(live as int32, src): the slot whose block each dead slot's steps
    point at, the LAST block of the live slot before it (the step before:
    the same block twice in a row is neither fetched nor stored again),
    else the FIRST block of the first live slot (the step after); a live
    slot is its own."""
    slot = jnp.arange(live.shape[0], dtype=jnp.int32)
    live = live.astype(jnp.int32)
    before = jax.lax.cummax(jnp.where(live != 0, slot, -1))
    return live, jnp.where(before >= 0, before,
                           jnp.argmax(live).astype(jnp.int32))


def _ssd_state_kernel(l_ref, src_ref, live_ref, cols_ref, b_ref, c_ref,
                      s_ref, y_ref, out_ref):
    """One (slot, head block) grid step. s_ref, out_ref (hb, P, N): the
    block's heads of the slot's state, in and out through one buffer;
    cols_ref (2, P, H): exp(dt A) and dt x with the heads on the lanes, so
    that a head's column broadcasts over the state's lanes; b_ref, c_ref
    (1, N): the block's group's rows; y_ref (P, H), revisited by the
    slot's head blocks.

    A dead slot moves nothing: all its steps point at ONE block of a live
    slot (src_ref; ssd_state_update says which), the block of the step
    before or after them, so the pipeline neither fetches nor stores for
    them, and they leave the output buffer as the live step left it, or
    will find it. Only before the first live slot is there no such
    buffer yet: there the block goes out as it came in."""
    s, j = pl.program_id(0), pl.program_id(1)
    hb = s_ref.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, y_ref.shape, 1)
    live = live_ref[s] != 0

    @pl.when(j == 0)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(jnp.logical_not(live) & (src_ref[s] >= s))
    def _():
        out_ref[...] = s_ref[...]

    @pl.when(live)
    def _():
        decay, drive = cols_ref[0], cols_ref[1]
        b, c = b_ref[...], c_ref[...]
        y = y_ref[...]
        for i in range(hb):
            mine = lane == j * hb + i

            def column(a):
                return jnp.sum(jnp.where(mine, a, 0.0), axis=1,
                               keepdims=True)                     # (P, 1)

            new = column(decay) * s_ref[i] + column(drive) * b    # (P, N)
            out_ref[i] = new
            y = jnp.where(mine, jnp.sum(new * c, axis=1, keepdims=True), y)
        y_ref[...] = y


def ssd_state_update(state, layer, live, x, dt, A, B, C, interpret=None):
    """One token of every live sequence through one layer's recurrence.

    state: (L, S, H, P, N) float32, the cache's states of every layer,
    read and written in layer `layer` (int32 scalar, traced in the layer
    loop) in place (input_output_aliases: donate it and carry it);
    live: (S,) bool — a dead slot's state moves neither in nor out, and
    its y is zeros; x: (S, H, P); dt: (S, H), after the softplus;
    A: (H,), negative; B, C: (S, G, N), head h reading group h // (H // G).

    Returns (y (S, H, P) float32 = S_new C, the state)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    _, S, H, P, N = state.shape
    G = B.shape[1]
    hb = min(SSD_HEAD_BLOCK, H // G)
    if H % G or (H // G) % hb:
        raise ValueError(f"{H} heads in {G} groups do not split into "
                         f"blocks of {hb}")
    f32 = jnp.float32
    dt = dt.astype(f32)
    decay = jnp.exp(dt * A.astype(f32))                             # (S, H)
    cols = jnp.stack([jnp.broadcast_to(decay[:, None, :], (S, P, H)),
                      (dt[..., None] * x.astype(f32)).transpose(0, 2, 1)],
                     axis=1)                                        # (S,2,P,H)
    live, src = _dead_slot_source(live)
    n_blocks = H // hb

    def state_index(s, j, l, src, live):
        j = jnp.where(live[s] != 0, j,
                      jnp.where(src[s] < s, n_blocks - 1, 0))
        return l[0], src[s], j, 0, 0

    state_spec = pl.BlockSpec((None, None, hb, P, N), state_index)
    group_spec = pl.BlockSpec(
        (None, None, 1, N),
        lambda s, j, *refs: (s, j * hb // (H // G), 0, 0))
    yT, state = pl.pallas_call(
        _ssd_state_kernel,
        name="ssd_state_update",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S, n_blocks),
            in_specs=[pl.BlockSpec((None, 2, P, H),
                                   lambda s, j, *refs: (s, 0, 0, 0)),
                      group_spec, group_spec, state_spec],
            out_specs=[pl.BlockSpec((None, P, H),
                                    lambda s, j, *refs: (s, 0, 0)),
                       state_spec]),
        out_shape=[jax.ShapeDtypeStruct((S, P, H), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        # operand 6 (after the three scalar-prefetch arrays, the columns
        # and the groups' rows) is the state, and it is output 1
        input_output_aliases={6: 1},
        interpret=interpret,
    )(_layer_index(layer), src, live, cols,
      B.astype(f32)[:, :, None], C.astype(f32)[:, :, None], state)
    return yT.transpose(0, 2, 1), state


def ssd_chunk_scan(x, dt, A, B, C, chunk):
    """A prompt through the recurrence from an empty state, in chunks of
    `chunk` rows as matrix products (the SSD form of arXiv:2405.21060).

    x: (S, T, H, P); dt: (S, T, H), after the softplus, 0 on a row that
    is padding (exp(0 A) = 1, 0 B = 0: it leaves the state as it is, so
    the state returned is the one after the last REAL row); A: (H,),
    negative; B, C: (S, T, G, N). T is a multiple of `chunk`, or one
    chunk. With cs the running sum of dt A within a chunk, row i reads
    the chunk's rows j <= i through ((C B^T) * L) (dt x),
    L[i, j] = exp(cs_i - cs_j), and the state S the chunk found through
    exp(cs_i) C_i S; the chunk leaves exp(cs_Q) S
    + sum_j exp(cs_Q - cs_j) (dt_j x_j) (outer) B_j. Float32 throughout,
    the products at full precision (on the chip 0.87 ms a 2048-row prompt
    of 32 heads against 0.56 at the default: PERF.md, PR 32).

    Returns (y (S, T, H, P) = S_t C_t, the state (S, H, P, N) after row
    T - 1)."""
    S, T, H, P = x.shape
    G, N = B.shape[2:]
    Q = chunk if T % chunk == 0 else T
    nc, hg = T // Q, H // G
    f32 = jnp.float32
    einsum = functools.partial(jnp.einsum, precision="highest",
                               preferred_element_type=f32)
    dt = dt.astype(f32)
    xd = (x.astype(f32) * dt[..., None]).reshape(S, nc, Q, G, hg, P)
    Bc, Cc = (a.astype(f32).reshape(S, nc, Q, G, N) for a in (B, C))
    cs = jnp.cumsum((dt * A.astype(f32)).reshape(S, nc, Q, G, hg), axis=2)
    cs = cs.transpose(0, 1, 3, 4, 2)                       # (S, nc, G, hg, Q)

    # within a chunk
    back = cs[..., :, None] - cs[..., None, :]             # cs_i - cs_j
    seen = jnp.tril(jnp.ones((Q, Q), bool))
    L = jnp.exp(jnp.where(seen, back, -jnp.inf))           # (S,nc,G,hg,Q,Q)
    CB = einsum("scign,scjgn->scgij", Cc, Bc)
    y = einsum("scghij,scjghp->scighp", CB[:, :, :, None] * L, xd)

    # what each chunk adds to the state by its end, and the state it finds
    to_end = jnp.exp(cs[..., -1:] - cs).transpose(0, 1, 4, 2, 3)
    added = einsum("scjgn,scjghp->scghpn", Bc, xd * to_end[..., None])
    through = jnp.exp(cs[..., -1])                         # (S, nc, G, hg)

    def carry_on(found, xs):
        keep, add = xs
        return keep[..., None, None] * found + add, found

    last, found = jax.lax.scan(
        carry_on, jnp.zeros((S, G, hg, P, N), f32),
        (through.swapaxes(0, 1), added.swapaxes(0, 1)))
    found = found.swapaxes(0, 1)                           # (S,nc,G,hg,P,N)
    y = y + (einsum("scign,scghpn->scighp", Cc, found)
             * jnp.exp(cs).transpose(0, 1, 4, 2, 3)[..., None])
    return y.reshape(S, T, H, P), last.reshape(S, H, P, N)


# ---------------------------------------------------------------------------
# Power retention of degree 2 (models.brumby): gated, normalised power
# attention, one scalar gate a K/V head and token, G query heads a K/V head.
# With phi(u) the symmetric square of a head (phi(q).phi(k) = (q.k)^2, F =
# D (D + 1) / 2 features), every K/V head carries a matrix state and a
# normaliser, float32:
#     S_t = gamma_t S_{t-1} + v_t (outer) phi(k_t)     (D, F): the value on
#     z_t = gamma_t z_{t-1} + phi(k_t)                 the sublanes, the
#     o_t = S_t phi(q_t) / (z_t . phi(q_t) + eps)      features on the lanes
#
# The features come in D / 2 + 1 blocks of D lanes: block d is c_d u *
# roll(u, d) (lane a pairs u_a with u_{a - d mod D}; c_0 = 1, else sqrt 2),
# which meets every unordered pair of the head once at cyclic distance d,
# and the last block (d = D / 2) keeps its first D / 2 lanes, where each pair
# at distance D / 2 would otherwise come twice. So phi is D rolls and
# products, no gather: F = 8256 at D = 128 in 65 blocks of 128 lanes, the
# last half zero (F_pad = 8320). A decode step is every live slot's state in
# and out (power_retention_step, in place in the cache's one array for every
# layer); a prompt goes through it in chunks as matrix products
# (power_retention_chunk).
# ---------------------------------------------------------------------------

RETENTION_EPS = 1e-6
_SQRT2 = float(np.sqrt(2.0))


def retention_width(head_dim):
    """Lanes of phi(u) for a head of `head_dim`: D / 2 + 1 blocks of D."""
    if head_dim % 2:
        raise ValueError("the features pair a head's lanes: an even width")
    return (head_dim // 2 + 1) * head_dim


def _feature_block(u, d, roll):
    """Block d of phi(u) over u's last axis (lanes): c_d u * roll(u, d),
    the last block's second half zero."""
    D = u.shape[-1]
    if d == 0:
        return u * u
    out = _SQRT2 * u * roll(u, d)
    if d == D // 2:
        lane = jax.lax.broadcasted_iota(jnp.int32, out.shape, out.ndim - 1)
        out = jnp.where(lane < D // 2, out, 0.0)
    return out


def power_features(u):
    """phi(u) (..., retention_width(D)) of u (..., D), in the layout the
    kernels keep: phi(q) . phi(k) = (q . k)^2."""
    D = u.shape[-1]
    return jnp.concatenate(
        [_feature_block(u, d, lambda x, s: jnp.roll(x, s, -1))
         for d in range(D // 2 + 1)], axis=-1)


def _lane_roll(x, shift):
    return pltpu.roll(x, shift, x.ndim - 1)


def _dot(a, b, dtype, b_transposed=False):
    """a (M, K) . b (K, N), or . b^T for b (N, K), in `dtype` operands,
    float32 sums."""
    return jax.lax.dot_general(
        a.astype(dtype), b.astype(dtype),
        (((1,), (1 if b_transposed else 0,)), ((), ())),
        precision=(jax.lax.Precision.HIGHEST if dtype == jnp.float32
                   else None),
        preferred_element_type=jnp.float32)


def _normalised(num, den):
    """o = S phi(q) / (z . phi(q) + eps): a row's read-out over its
    normaliser's."""
    return num / (den + RETENTION_EPS)


# a head's state is 4.26 MB at D = 128: in and out, double-buffered
RETENTION_VMEM_BYTES = 48 * 1024 * 1024


def _retention_step_kernel(l_ref, src_ref, live_ref, q_ref, kg_ref, v_ref,
                           s_ref, z_ref, o_ref, s_out, z_out, *, dtype):
    """One (slot, K/V head) grid step. q_ref, o_ref (Gp, D): the head's
    query rows (G real, the rest zero); kg_ref (2, D): k, and log gamma on
    every lane; v_ref (D, 1): v as a column, to broadcast over the state's
    lanes; s_ref, s_out (D, F_pad): the head's state in and out through one
    buffer; z_ref, z_out (Hkv, F_pad): the normalisers of every K/V head of
    the slot, revisited by its heads (row g is this head's).

    A dead slot moves nothing, as in ssd_state_update: all its steps point
    at ONE block of a live slot (src_ref), the block of the step before or
    after them, so the pipeline neither fetches nor stores for them; only
    before the first live slot do its blocks go out as they came in."""
    s, g = pl.program_id(0), pl.program_id(1)
    D = v_ref.shape[0]
    live = live_ref[s] != 0

    @pl.when(jnp.logical_not(live) & (src_ref[s] >= s))
    def _():
        s_out[...] = s_ref[...]
        z_out[...] = z_ref[...]

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live & (g == 0))
    def _():
        z_out[...] = z_ref[...]

    @pl.when(live)
    def _():
        k, gamma = kg_ref[0:1, :], jnp.exp(kg_ref[1:2, :])        # (1, D)
        q = q_ref[...]
        v = jnp.broadcast_to(v_ref[...], (D, D))
        mine = jax.lax.broadcasted_iota(jnp.int32, (z_ref.shape[0], D), 0) == g
        num = jnp.zeros(o_ref.shape, jnp.float32)
        den = jnp.zeros(o_ref.shape, jnp.float32)
        for d in range(D // 2 + 1):
            cols = slice(d * D, (d + 1) * D)
            kd = _feature_block(k, d, _lane_roll)                 # (1, D)
            qd = _feature_block(q, d, _lane_roll)                 # (Gp, D)
            sd = gamma * s_ref[:, cols] + v * kd                  # (D, D)
            s_out[:, cols] = sd
            zs = z_out[:, cols]                                   # (Hkv, D)
            zs = jnp.where(mine, gamma * zs + kd, zs)
            z_out[:, cols] = zs
            num = num + _dot(qd, sd, dtype, b_transposed=True)
            den = den + qd * jnp.sum(jnp.where(mine, zs, 0.0), axis=0,
                                     keepdims=True)
        o_ref[...] = _normalised(num, jnp.sum(den, axis=1, keepdims=True))


def power_retention_step(state, norm, layer, live, q, k, v, log_gamma,
                         dtype=jnp.float32, interpret=None):
    """One token of every live sequence through one layer's retention.

    state: (L, S, Hkv, D, F_pad) float32 and norm: (L, S, Hkv, F_pad)
    float32, the cache's states and normalisers of every layer, read and
    written in layer `layer` (int32 scalar, traced in the layer loop) in
    place (input_output_aliases: donate them and carry them); live: (S,)
    bool — a dead slot's state moves neither in nor out, and its o is
    zeros; q: (S, H, D), query head j reading K/V head j // (H / Hkv);
    k, v: (S, Hkv, D); log_gamma: (S, Hkv), at most 0. `dtype` is the
    operands' of the read-out's product (the state stays float32).

    Returns (o (S, H, D) float32, state, norm)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    _, S, Hkv, D, F = state.shape
    H = q.shape[1]
    G = H // Hkv
    if H % Hkv or F != retention_width(D):
        raise ValueError(f"{H} query heads over {Hkv} K/V heads of {D}, "
                         f"{F} feature lanes")
    Gp = -(-G // 8) * 8
    f32 = jnp.float32
    qp = jnp.pad(q.astype(f32).reshape(S, Hkv, G, D),
                 ((0, 0), (0, 0), (0, Gp - G), (0, 0)))
    kg = jnp.stack([k.astype(f32), jnp.broadcast_to(
        log_gamma.astype(f32)[..., None], k.shape)], axis=2)  # (S,Hkv,2,D)
    live, src = _dead_slot_source(live)

    def state_index(s, g, l, src, live):
        g = jnp.where(live[s] != 0, g, jnp.where(src[s] < s, Hkv - 1, 0))
        return l[0], src[s], g, 0, 0

    state_spec = pl.BlockSpec((None, None, None, D, F), state_index)
    norm_spec = pl.BlockSpec((None, None, Hkv, F),
                             lambda s, g, l, src, live: (l[0], src[s], 0, 0))

    def rows(n):
        return pl.BlockSpec((None, None, n, D),
                            lambda s, g, *refs: (s, g, 0, 0))

    o, state, norm = pl.pallas_call(
        functools.partial(_retention_step_kernel, dtype=jnp.dtype(dtype)),
        name="power_retention_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S, Hkv),
            in_specs=[rows(Gp), rows(2),
                      pl.BlockSpec((None, None, D, 1),
                                   lambda s, g, *refs: (s, g, 0, 0)),
                      state_spec, norm_spec],
            out_specs=[rows(Gp), state_spec, norm_spec]),
        out_shape=[jax.ShapeDtypeStruct((S, Hkv, Gp, D), f32),
                   jax.ShapeDtypeStruct(state.shape, f32),
                   jax.ShapeDtypeStruct(norm.shape, f32)],
        # operands 6 and 7 (after the three scalar-prefetch arrays, q, k|g
        # and v) are the state and the normaliser: outputs 1 and 2
        input_output_aliases={6: 1, 7: 2},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=RETENTION_VMEM_BYTES),
        interpret=interpret,
    )(_layer_index(layer), src, live, qp, kg,
      v.astype(f32)[..., None], state, norm)
    return o[:, :, :G].reshape(S, H, D), state, norm


def _retention_chunk_kernel(q_ref, k_ref, vt_ref, cs_ref, csr_ref, o_ref,
                            s_out, z_out, s_scr, z_scr, *, dtype):
    """One (sequence, K/V head, chunk) grid step. q_ref, o_ref (G, Q, D):
    the head's query heads over the chunk's rows; k_ref (Q, D); vt_ref
    (D, Q): v with the value on the sublanes; cs_ref (Q, 1) and csr_ref
    (1, Q): the running sum of log gamma within the chunk as a column and
    as a row; s_scr (D, F_pad), z_scr (1, F_pad): the state the chunk
    finds, float32, handed to s_out, z_out after the last chunk."""
    c = pl.program_id(2)
    G, Q, D = q_ref.shape

    @pl.when(c == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)
        z_scr[...] = jnp.zeros_like(z_scr)

    k, vt = k_ref[...], vt_ref[...]
    cs, csr = cs_ref[...], csr_ref[...]
    # log gamma <= 0: the running sum falls, and its last is its least
    end = jnp.min(csr, axis=1, keepdims=True)                      # (1, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    decay = jnp.exp(jnp.where(row >= col, cs - csr, -jnp.inf))     # (Q, Q)
    into = jnp.exp(cs)                     # (Q, 1): from the chunk's start

    # what the state the chunk found gives each row
    qa = q_ref[...].reshape(G * Q, D)
    num = jnp.zeros((G * Q, D), jnp.float32)
    dz = jnp.zeros((G * Q, D), jnp.float32)
    for d in range(D // 2 + 1):
        cols = slice(d * D, (d + 1) * D)
        qd = _feature_block(qa, d, _lane_roll)
        num = num + _dot(qd, s_scr[:, cols], dtype, b_transposed=True)
        dz = dz + qd * z_scr[:, cols]
    found = jnp.sum(dz, axis=1, keepdims=True)                     # (GQ, 1)

    # and the chunk's own rows, j <= i: (q_i . k_j)^2 exp(cs_i - cs_j)
    for j in range(G):
        qj = qa[j * Q:(j + 1) * Q]
        sc = _dot(qj, k, dtype, b_transposed=True)
        w = sc * sc * decay
        top = (into * num[j * Q:(j + 1) * Q]
               + _dot(w, vt, dtype, b_transposed=True))
        bottom = (into * found[j * Q:(j + 1) * Q]
                  + jnp.sum(w, axis=1, keepdims=True))
        o_ref[j] = _normalised(top, bottom)

    # the state the next chunk finds: the value rows and, under them, the
    # normaliser's, weighed by exp(cs_end - cs_j)
    to_end = jnp.exp(end - csr)                                    # (1, Q)
    weights = jnp.concatenate(
        [vt * to_end,
         jnp.where(jax.lax.broadcasted_iota(jnp.int32, (8, Q), 0) == 0,
                   to_end, 0.0)], axis=0)                          # (D+8, Q)
    through = jnp.exp(end)
    for d in range(D // 2 + 1):
        cols = slice(d * D, (d + 1) * D)
        add = _dot(weights, _feature_block(k, d, _lane_roll), dtype)
        s_scr[:, cols] = through * s_scr[:, cols] + add[:D]
        z_scr[:, cols] = through * z_scr[:, cols] + add[D:D + 1]

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        s_out[...] = s_scr[...]
        z_out[...] = z_scr[...]


def power_retention_chunk(q, k, v, log_gamma, chunk, dtype=jnp.float32,
                          interpret=None):
    """A prompt through the retention from an empty state, in chunks of
    `chunk` rows as matrix products. Within a chunk row i reads rows
    j <= i through (q_i . k_j)^2 exp(cs_i - cs_j), cs the running sum of
    log gamma; across chunks through the state by phi. The state is
    scanned over the chunks in VMEM: no chunk's added state is ever
    written out.

    q: (S, T, H, D); k, v: (S, T, Hkv, D); log_gamma: (S, T, Hkv), at most
    0. A padding row carries k = 0 and log gamma = 0: it leaves the state
    as it is, so the state returned is the one after the last REAL row. T
    is a multiple of `chunk`, or one chunk. `dtype` is the operands' of the
    products (sums float32).

    Returns (o (S, T, H, D) float32, the state (S, Hkv, D, F_pad) and the
    normaliser (S, Hkv, F_pad) after row T - 1)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    S, T, H, D = q.shape
    Hkv = k.shape[2]
    G, F = H // Hkv, retention_width(D)
    Q = chunk if T % chunk == 0 else T
    nc = T // Q
    f32 = jnp.float32
    qh = q.astype(f32).reshape(S, T, Hkv, G, D).transpose(0, 2, 3, 1, 4)
    kh = k.astype(f32).transpose(0, 2, 1, 3)                  # (S, Hkv, T, D)
    vt = v.astype(f32).transpose(0, 2, 3, 1)                  # (S, Hkv, D, T)
    cs = jnp.cumsum(log_gamma.astype(f32).transpose(0, 2, 1).reshape(
        S, Hkv, nc, Q), axis=-1).reshape(S, Hkv, T)
    heads_spec = pl.BlockSpec((None, None, G, Q, D),
                              lambda s, g, c: (s, g, 0, c, 0))
    o, state, norm = pl.pallas_call(
        functools.partial(_retention_chunk_kernel, dtype=jnp.dtype(dtype)),
        name="power_retention_chunk",
        grid=(S, Hkv, nc),
        in_specs=[heads_spec,
                  pl.BlockSpec((None, None, Q, D),
                               lambda s, g, c: (s, g, c, 0)),
                  pl.BlockSpec((None, None, D, Q),
                               lambda s, g, c: (s, g, 0, c)),
                  pl.BlockSpec((None, None, Q, 1),
                               lambda s, g, c: (s, g, c, 0)),
                  pl.BlockSpec((None, None, 1, Q),
                               lambda s, g, c: (s, g, 0, c))],
        out_specs=[heads_spec,
                   pl.BlockSpec((None, None, D, F),
                                lambda s, g, c: (s, g, 0, 0)),
                   pl.BlockSpec((None, None, 1, F),
                                lambda s, g, c: (s, g, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((S, Hkv, G, T, D), f32),
                   jax.ShapeDtypeStruct((S, Hkv, D, F), f32),
                   jax.ShapeDtypeStruct((S, Hkv, 1, F), f32)],
        scratch_shapes=[pltpu.VMEM((D, F), f32), pltpu.VMEM((1, F), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=RETENTION_VMEM_BYTES),
        interpret=interpret,
    )(qh, kh, vt, cs[..., None], cs[:, :, None, :])
    return (o.transpose(0, 3, 1, 2, 4).reshape(S, T, H, D), state,
            norm[:, :, 0])
