"""Transformer LM flagship: every parallelism axis on one model.

Capability beyond the reference (SURVEY §2.2/§5.7: MXNet's long-sequence
story was bucketing + the fused RNN op; TP/PP/SP/EP were absent). This is the
TPU-native composition point for the `parallel` package:

- data parallel       : batch sharded on the `dp` mesh axis (GSPMD or shard_map)
- tensor parallel     : attention heads + FFN hidden sharded on `tp` (GSPMD
                        sharding rules, parallel.tensor)
- expert parallel     : MoE expert axis sharded on `ep` (parallel.moe)
- sequence parallel   : ring attention over `sp` (parallel.ring_attention)
- pipeline parallel   : layer stack sharded on `pp` (parallel.pipeline)

Two jitted training steps are provided:
- `make_gspmd_train_step`   — mesh ('dp','ep','tp'): annotation-driven
  sharding; XLA inserts the grad all-reduce and MoE all-to-all.
- `make_pipeline_train_step`— mesh ('dp','sp','pp'): explicit shard_map SPMD
  pipeline with ring attention inside each stage.

Both return scalar loss and apply an SGD update in the same jit (donated
params — the fused-step pattern of incubator_mxnet_tpu.fused).
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.pipeline import spmd_pipeline
from ..parallel.moe import moe_ffn
from ..parallel.ring_attention import ring_attention
from ..parallel.tensor import make_shardings

__all__ = [
    "TransformerConfig",
    "init_params",
    "apply",
    "make_gspmd_train_step",
    "make_pipeline_train_step",
    "init_kv_cache",
    "decode_step",
    "prefill",
    "generate",
    "beam_search",
    "init_paged_kv_cache",
    "decode_step_paged",
    "prefill_paged",
    "decode_step_paged_wide",
    "TransformerPrograms",
]


@dataclasses.dataclass
class TransformerConfig:
    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 128
    max_len: int = 128
    n_experts: int = 0  # 0 = dense FFN
    dtype: str = "float32"
    use_flash: bool = False  # Pallas flash-attention kernels for attention
    use_fused_xent: bool = False  # Pallas fused softmax-xent loss kernel

    def paged_programs(self):
        """What serving.ServingEngine asks of a model: TransformerPrograms."""
        return TransformerPrograms(self)


def init_params(cfg: TransformerConfig, seed: int = 0):
    """Stacked-layer parameter dict: every per-layer tensor has a leading
    (n_layers,) axis so the stack can be scanned (single-chip) or sharded on
    `pp` (pipeline)."""
    rng = np.random.RandomState(seed)
    d, f, L, V = cfg.d_model, cfg.d_ff, cfg.n_layers, cfg.vocab
    dt = cfg.dtype

    def W(*shape, scale=None):
        scale = scale if scale is not None else 1.0 / np.sqrt(shape[-2])
        return jnp.asarray(rng.randn(*shape).astype(dt) * scale)

    p = {
        "embed": W(V, d, scale=0.02),
        "pos": W(cfg.max_len, d, scale=0.02),
        "ln_f_g": jnp.ones((d,), dt),
        "ln_f_b": jnp.zeros((d,), dt),
        "wq": W(L, d, d),
        "wk": W(L, d, d),
        "wv": W(L, d, d),
        "wo": W(L, d, d),
        "ln1_g": jnp.ones((L, d), dt),
        "ln1_b": jnp.zeros((L, d), dt),
        "ln2_g": jnp.ones((L, d), dt),
        "ln2_b": jnp.zeros((L, d), dt),
    }
    if cfg.n_experts:
        p["router"] = W(L, d, cfg.n_experts, scale=0.02)
        p["w1"] = W(L, cfg.n_experts, d, f)
        p["w2"] = W(L, cfg.n_experts, f, d, scale=1.0 / np.sqrt(f))
    else:
        p["w1"] = W(L, d, f)
        p["w2"] = W(L, f, d, scale=1.0 / np.sqrt(f))
    return p


_NON_STACKED = ("embed", "pos", "ln_f_g", "ln_f_b")


def _stack_keys(params):
    """Keys of per-layer (stacked, leading n_layers axis) params — the single
    predicate used by both the scanned forward and the pipeline sharding."""
    return [k for k in params if k not in _NON_STACKED]


def _stacked(params):
    """The per-layer params: what a layer loop scans over."""
    return {k: params[k] for k in _stack_keys(params)}


# The scopes "norm", "attention" and "ffn" below are for whoever reads the
# device side: they land in every op's `op_name` metadata (a dumped HLO,
# TensorBoard's op profile), so a block's time can be sorted by them after a
# refactor renames the Python around them. The v5e's trace events as
# `jax.profiler.ProfileData` gives them carry the HLO text and no `op_name`
# (PERF.md, PR 25): the benchmark's reduction does not see the scopes yet.

def _ln(x, g, b, eps=1e-5):
    with jax.named_scope("norm"):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.var(x, -1, keepdims=True)
        return (x - mu) * lax.rsqrt(var + eps) * g + b


def _split_heads(x, n_heads):
    B, T, d = x.shape
    return x.reshape(B, T, n_heads, d // n_heads)


FLASH_DENSE_FALLBACKS_TOTAL = "mxtpu_flash_dense_fallbacks_total"
_FLASH_FALLBACKS_HELP = (
    "Training flash-attention calls that fell back to the dense S×S "
    "attention (non-causal sequences that do not tile into blocks — "
    "causal remainders are padded into the Pallas path instead), by site "
    "and reason.")


def _count_flash_dense_fallback(site, reason):
    # trace-time event (shapes are static), so the counter costs nothing
    # on the per-step hot path; lazy import keeps this module jax-only
    # when telemetry is off (same idiom as pallas_kernels flash_decode)
    from .. import telemetry

    telemetry.inc(FLASH_DENSE_FALLBACKS_TOTAL, help=_FLASH_FALLBACKS_HELP,
                  site=site, reason=reason)


def _flash_attention_fn(q, k, v, causal=True, block=128):
    """Adapter onto the Pallas flash kernels (ops/pallas_kernels.py):
    model layout (B, T, H, Dh) <-> kernel layout (B, H, T, Dh).

    A sequence length that does not tile into blocks no longer silently
    pays the dense S×S path when causal: q/k/v zero-pad along T to the
    next block multiple, the kernel runs, and the output slices back to
    T. Exact because a causal query at t < T never attends a padded key
    at t' >= T (cost: < one block of extra rows). Non-causal remainders
    would let every query see the padded keys, so they still fall back to
    dense — now COUNTED via mxtpu_flash_dense_fallbacks_total instead of
    vanishing from the perf picture."""
    from ..ops.pallas_kernels import flash_attention

    T = q.shape[1]
    blk = min(block, T)
    pad = (-T) % blk
    if pad and not causal:
        _count_flash_dense_fallback("models.transformer",
                                    "non_causal_remainder")
        return _dense_attention(q, k, v, causal)
    if pad:
        widths = ((0, 0), (0, pad), (0, 0), (0, 0))
        q = jnp.pad(q, widths)
        k = jnp.pad(k, widths)
        v = jnp.pad(v, widths)
    out = flash_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                          v.transpose(0, 2, 1, 3), causal=causal,
                          block_q=blk, block_k=blk)
    out = out.transpose(0, 2, 1, 3)
    return out[:, :T] if pad else out


def _dense_attention(q, k, v, causal=True):
    # q,k,v: (B, T, H, Dh)
    scale = 1.0 / np.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        T = q.shape[1]
        mask = jnp.tril(jnp.ones((T, T), bool))
        logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# tokens a slab: the lanes of one TPU tile
_SLAB = 128
# the most tokens _token_rows looks up slab by slab. On the v5e the slabs
# cost 3.4 us a token (16 tokens, 54 us) and the gather's re-layout of
# GPT-2 XL's table 496 us whatever the count (my chip run, PR 33)
_SLAB_LOOKUP_MAX_TOKENS = 128


def _token_rows(table, tokens):
    """table[tokens], read where the table lies: table (V, d), tokens
    (...) int -> (..., d), the same rows as the plain lookup for every
    index (negative ones wrap, others past the table clamp).

    At the jit boundary the TPU runtime gives an array the layout that
    pads least, by its shape alone (PERF.md section 6, PR 26). A table
    whose rows are not whole lanes (d = 1600: 12.5 of them) lies
    VOCABULARY-minor there, as (d, V). The logits product reads that as
    it is, but XLA's gather wants rows, and copies all of the table to
    get them: 161 MB read and written in every decode step to fetch 16
    rows of GPT-2 XL's. So a lookup of few tokens takes, per token, the
    slab of 128 tokens its row lies in (whole tiles of that layout) and
    its row out of the slab: no program copies the table. A lookup of
    many tokens (a prompt, a training batch) would read more in slabs
    than the copy moves and stays the plain gather, to the letter; so
    does a table whose rows are whole lanes, which lies row-major, and
    one of a slab or less."""
    V, d = table.shape
    if (d % _SLAB == 0 or V <= _SLAB
            or tokens.size > _SLAB_LOOKUP_MAX_TOKENS):
        return table[tokens]
    return _slab_rows(table, tokens)


@jax.custom_jvp
def _slab_rows(table, tokens):
    """_token_rows' lookup by slabs: per token a (128, d) slice of the
    table at a multiple of 128 (the last one ends with the table), then
    the token's row of it."""
    V, d = table.shape

    def row(t):
        t = jnp.clip(jnp.where(t < 0, t + V, t), 0, V - 1)
        t0 = jnp.minimum(t // _SLAB * _SLAB, V - _SLAB)
        slab = lax.dynamic_slice(table, (t0, 0), (_SLAB, d))
        return lax.dynamic_slice(slab, (t - t0, 0), (1, d))[0]

    return jax.vmap(row)(tokens.reshape(-1)).reshape(*tokens.shape, d)


@_slab_rows.defjvp
def _slab_rows_jvp(primals, tangents):
    # linear in the table: differentiate the plain lookup, whose transpose
    # is one scatter-add, not a table-sized update per token
    table, tokens = primals
    return _slab_rows(table, tokens), tangents[0][tokens]


def _embed(params, tokens, pos):
    """Token rows plus positional rows: tokens (S, T) -> x (S, T, d).
    `pos` is where the tokens sit: an (S, T) int array, one position per
    token (one past the table reads row 0: such a row's output is the
    caller's to discard), or the position of every row's first token, a
    Python int or a traced scalar."""
    x, table = _token_rows(params["embed"], tokens), params["pos"]
    T = tokens.shape[1]
    if jnp.ndim(pos):
        return x + table[jnp.where(pos < table.shape[0], pos, 0)]
    if isinstance(pos, int):
        return x + table[pos:pos + T][None]
    return x + lax.dynamic_slice_in_dim(table, pos, T, axis=0)[None]


def _block(lp, x, cache, cfg, attend):
    """The transformer block, written once for every program of this file
    (the pattern of models.sambay._block). lp = per-layer param dict (no
    leading L axis); x: (S, T, d). What differs between the programs is
    the caller's: `attend(q, k, v, cache)`, q/k/v (S, T, H, Dh) each,
    stores what its cache keeps, reads what its attention needs and
    returns (a (S, T, H, Dh), the cache after it). Returns (x, cache,
    router auxiliary loss)."""
    S, T, d = x.shape
    h = _ln(x, lp["ln1_g"], lp["ln1_b"])
    # the barrier keeps the head split out of the matmul: fused in, XLA
    # turns the product into a convolution over heads that wants the
    # weight transposed, and re-lays-out all three in every layer
    q, k, v = (
        _split_heads(lax.optimization_barrier(h @ lp[w]), cfg.n_heads)
        for w in ("wq", "wk", "wv"))
    with jax.named_scope("attention"):
        a, cache = attend(q, k, v, cache)
    x = x + a.reshape(S, T, d) @ lp["wo"]
    h = _ln(x, lp["ln2_g"], lp["ln2_b"])
    with jax.named_scope("ffn"):
        if cfg.n_experts:
            out, aux = moe_ffn(h.reshape(S * T, d), lp["router"], lp["w1"],
                               lp["w2"])
            out = out.reshape(S, T, d)
        else:
            out = jax.nn.gelu(h @ lp["w1"]) @ lp["w2"]
            aux = jnp.zeros((), x.dtype)
    return x + out, cache, aux


def _cacheless(attn_fn):
    """_block's `attend` for a program that keeps no cache."""
    return lambda q, k, v, _: (attn_fn(q, k, v), None)


def _logits(params, x):
    """Final LayerNorm and the product with the tied embedding:
    (..., d) -> (..., V), float32 rows against the table as it is stored.

    The operand order is XLA's to choose, not this line's: `h @ E.T`,
    `dot_general(h, E, (((1,), (1,)), ((), ())))` and
    `dot_general(E, h, ...).T` compile to one and the same convolution
    over the table where it lies (vocabulary-minor for GPT-2 XL's shape,
    see _token_rows), with or without the serving engine's argmax fused
    in as its root, and give the same bits; 16 rows against
    (50257, 1600) bf16 take 217 us on a v5e, 741 GB/s (my chip run,
    PR 33). So one statement serves every program, from one decode row to
    a training batch, and no row count is tested. At the default
    precision the MXU rounds float32 rows to the table's bfloat16 (0.008
    sigma of a row against precision="highest"); a single row is a
    float32 multiply-and-reduce and is not rounded."""
    return _ln(x, params["ln_f_g"], params["ln_f_b"]) @ params["embed"].T


def apply(params, tokens, cfg: TransformerConfig, attn_fn=None):
    """Forward pass: tokens (B, T) int32 -> logits (B, T, V). Scans the layer
    stack (compiler-friendly: one compiled block body); it keeps no cache."""
    if attn_fn is None:
        attn_fn = _flash_attention_fn if cfg.use_flash else _dense_attention

    def body(carry, lp):
        x, aux = carry
        x, _, a = _block(lp, x, None, cfg, _cacheless(attn_fn))
        return (x, aux + a), None

    x = _embed(params, tokens, 0)
    (x, aux), _ = lax.scan(body, (x, jnp.zeros((), x.dtype)),
                           _stacked(params))
    return _logits(params, x), aux / max(cfg.n_layers, 1)


def _xent(logits, targets, fused=False):
    if fused:
        return _xent_fused_local(logits, targets)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def _xent_fused_local(logits, targets):
    """Per-device fused loss: Pallas kernel computing max/logsumexp/pick in
    one VMEM pass — no (B, V) softmax tensor in HBM
    (ops/pallas_kernels.softmax_xent)."""
    from ..ops.pallas_kernels import softmax_xent

    return softmax_xent(logits, targets)


# ---------------------------------------------------------------------------
# Incremental decoding: KV cache + one-token steps + jitted generate
# (the reference has no serving path; on TPU the decode loop is a single
# lax.scan program — static shapes, cache updates via dynamic_update_slice)
# ---------------------------------------------------------------------------


def init_kv_cache(cfg: TransformerConfig, batch: int, max_len: int | None = None):
    """Per-layer key/value cache: (L, B, T_max, H, Dh) + a scalar write
    position. Static T_max keeps every decode step the same XLA program.

    T_max is rounded up to a DECODE_BLOCK multiple (when larger than one
    block) so `flash_decode` always tiles — the silent dense fallback on
    untiled caches cost the Pallas path exactly when caches got long
    enough to need it. Extra slots are masked by `n_valid`, so numerics
    are unchanged."""
    from ..ops.pallas_kernels import DECODE_BLOCK

    T = int(max_len or cfg.max_len)
    if T > DECODE_BLOCK and T % DECODE_BLOCK:
        T += DECODE_BLOCK - T % DECODE_BLOCK
    H = cfg.n_heads
    Dh = cfg.d_model // H
    shape = (cfg.n_layers, batch, T, H, Dh)
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def _dense_layers(params, cache, x, pos, cfg, read):
    """The layer loop of the two dense-cache programs. x: (B, T, d), the
    tokens at positions pos .. pos + T - 1; each layer stores their K/V
    rows there in its (B, T_max, H, Dh) cache layers and
    `read(q, k, v, k_cache, v_cache)` -> (B, T, H, Dh) attends. Returns
    (x, new_k, new_v)."""

    def attend(q, k, v, kv):
        # cache dtype follows cfg.dtype; activations may be wider (f32
        # master weights) — cast at the cache-write boundary
        kv = tuple(
            lax.dynamic_update_slice_in_dim(c, rows.astype(c.dtype), pos,
                                            axis=1)
            for c, rows in zip(kv, (k, v)))
        return read(q, k, v, *kv), kv

    def body(x, layer_in):
        lp, kv = layer_in
        x, kv, _ = _block(lp, x, kv, cfg, attend)
        return x, kv

    x, (new_k, new_v) = lax.scan(
        body, x, (_stacked(params), (cache["k"], cache["v"])))
    return x, new_k, new_v


def decode_step(params, cache, tokens, cfg: TransformerConfig):
    """One token through the stack with cached attention state.

    tokens: (B,) int32 — the token at position cache["pos"]. The caller
    must keep pos < the cache's T_max (generate() checks this at trace
    time; past capacity, dynamic_update_slice would silently clamp).
    Returns (logits (B, V), new_cache). Attention reads the full static
    cache and masks positions beyond pos (no dynamic shapes)."""
    from ..ops.pallas_kernels import dense_decode_attention, flash_decode

    pos = cache["pos"]
    kernel = flash_decode if cfg.use_flash else dense_decode_attention

    def read(q, k, v, k_cache, v_cache):
        return kernel(q[:, 0], k_cache, v_cache, pos + 1)[:, None]

    x, new_k, new_v = _dense_layers(
        params, cache, _embed(params, tokens[:, None], pos), pos, cfg, read)
    new_cache = {"k": new_k, "v": new_v, "pos": pos + 1}
    return _logits(params, x[:, 0]), new_cache


def prefill(params, cache, prompt, cfg: TransformerConfig):
    """Fill the cache with the whole prompt in ONE batched pass (the
    O(T_p)-sequential decode_step loop would serialize T_p attention
    launches). Returns (cache, last-token logits (B, V))."""

    def read(q, k, v, k_cache, v_cache):
        return _dense_attention(q, k, v, causal=True)

    x, new_k, new_v = _dense_layers(
        params, cache, _embed(params, prompt, 0), 0, cfg, read)
    new_cache = {"k": new_k, "v": new_v,
                 "pos": jnp.asarray(prompt.shape[1], jnp.int32)}
    return new_cache, _logits(params, x[:, -1])


# ---------------------------------------------------------------------------
# Paged decoding: K/V in a global page pool shared by every decode slot
# (serving path — the dense cache above burns B x T_max HBM and forces the
# whole batch to one depth; pages + per-slot positions are what continuous
# batching needs: serving/engine.py drives these three functions)
# ---------------------------------------------------------------------------


def init_paged_kv_cache(cfg: TransformerConfig, num_pages: int,
                        page_size: int):
    """The paged K/V pool, one buffer for every layer:
    {"kv": (L, H, num_pages, page_size, 2 * Dh)}, head-major, a token's
    K in lanes [0, Dh) of its row and its V in lanes [Dh, 2 * Dh) (why:
    the layout comment above ops.pallas_kernels' paged kernels).

    The pool is carried, never copied: the engine donates it to every
    program, the layer loop below carries it, each layer's new rows are
    written in place (ops.pallas_kernels.paged_kv_write) and the paged
    kernels read a layer where it lies, by layer index. No position
    scalar — slot positions live with the caller (the engine), one per
    decode slot. Page 0 is the null page by convention
    (serving.pages.PageAllocator never hands it out): dead slots read and
    write it, and rows with nowhere to go (padding, overrun) are dropped."""
    H = cfg.n_heads
    Dh = cfg.d_model // H
    shape = (cfg.n_layers, H, num_pages, page_size, 2 * Dh)
    return {"kv": jnp.zeros(shape, cfg.dtype)}


def _paged_layers(params, paged, x, start, n_write, page_table, cfg,
                  attend):
    """The one layer loop of the three paged programs.

    x: (S, T, d) — row t of sequence s is the token at position
    start[s] + t; its K/V rows are stored for t < n_write[s] and
    dropped otherwise. The pool is the loop's CARRY next to x: each
    layer writes its rows into pool[l] in place and `attend(q, k, v,
    pool, l)` -> (S, T, H, Dh) reads what it needs. With the pool donated
    at the jit boundary, input, carry and output are one allocation.
    Returns (x, new_paged)."""
    from ..ops.pallas_kernels import paged_kv_write, paged_write_plan

    plan = paged_write_plan(page_table, start, n_write, x.shape[1],
                            paged["kv"].shape[3])

    def body(carry, layer_in):
        x, pool = carry
        lp, l = layer_in

        def write_then_attend(q, k, v, pool):
            pool = paged_kv_write(pool, l, k, v, plan)
            return attend(q, k, v, pool, l), pool

        x, pool, _ = _block(lp, x, pool, cfg, write_then_attend)
        return (x, pool), None

    layers = jnp.arange(cfg.n_layers, dtype=jnp.int32)
    (x, pool), _ = lax.scan(body, (x, paged["kv"]),
                            (_stacked(params), layers))
    return x, {"kv": pool}


def decode_step_paged(params, paged, tokens, positions, page_table,
                      cfg: TransformerConfig):
    """One token for every decode slot, each at its OWN depth: the
    Q = 1 case of decode_step_paged_wide.

    paged: init_paged_kv_cache dict, carried through the layer loop and
    written in place (donate it); tokens (S,) int32; positions (S,)
    int32 — tokens already cached per slot (the new token is written at
    that offset, then attention covers positions+1); page_table
    (S, P_max) int32 rows of owned page ids. Dead slots (all-zero table
    row, position 0) write to the null page and produce garbage logits
    the caller discards. Returns (logits (S, V), new_paged). Shapes are
    static in (S, P_max, pool) — every call is one XLA program."""
    logits, paged = decode_step_paged_wide(
        params, paged, tokens[:, None], positions,
        jnp.ones_like(positions), page_table, cfg)
    return logits[:, 0], paged


def prefill_paged(params, paged, prompts, true_lens, page_table,
                  cfg: TransformerConfig):
    """Prefill a BUCKET of prompts straight into their pages in one pass.

    prompts: (S, T_b) int32 padded to the bucket length; true_lens (S,)
    — real prompt length per row (padding rows use 0); page_table
    (S, P_max). Causal attention makes every position < true_len exact
    regardless of the padding tail; padded positions are not stored and
    their activations are never read. The pool is carried and written in
    place, whole pages at a time (_paged_layers). Returns (new_paged,
    logits (S, V) at each row's LAST REAL token — the first sampled
    continuation token, matching prefill()'s x[:, -1] for full rows."""

    def attend(q, k, v, pool, l):
        return _dense_attention(q, k, v, causal=True)

    x, paged = _paged_layers(params, paged, _embed(params, prompts, 0),
                             jnp.zeros_like(true_lens), true_lens,
                             page_table, cfg, attend)
    last = jnp.maximum(true_lens - 1, 0)  # (S,)
    x_last = jnp.take_along_axis(
        x, last[:, None, None].astype(jnp.int32), axis=1)[:, 0]  # (S, d)
    return paged, _logits(params, x_last)


def decode_step_paged_wide(params, paged, tokens, start, n_real, page_table,
                           cfg: TransformerConfig):
    """Q consecutive tokens per decode slot in ONE pass — the wider-query
    decode program behind three serving levers: chunked prefill
    (Q = chunk size, carrying the running position in `start`),
    cached-prefix tail prefill (`start` = tokens mapped from the prefix
    cache), and n-gram speculative verification (Q = lookahead + 1,
    accepted prefixes advance positions in bulk).

    tokens: (S, Q) int32 — token j of row s sits at position
    start[s] + j; start: (S,) int32 — tokens already cached per slot;
    n_real: (S,) int32 — rows store K/V only for j < n_real (tokens
    beyond are dropped: chunk-tail padding, dead slots).
    Attention for query j covers positions < start[s] + j + 1 — the
    paged prefix written by earlier calls plus intra-call causal — via
    ops.pallas_kernels.paged_decode_attention_wide, which reads the
    carried pool by layer index. Positions past the page table's
    capacity or the positional table are dropped too (speculative rows
    may run past a sequence's last owned page; their outputs are
    discarded by the caller).

    Returns (logits (S, Q, V), new_paged). Shapes are static in
    (S, Q, P_max, pool) — every call is one XLA program."""
    S, Q = tokens.shape
    page_size = paged["kv"].shape[3]
    pos = start[:, None] + jnp.arange(Q, dtype=jnp.int32)[None, :]  # (S, Q)
    cap = min(page_table.shape[1] * page_size, params["pos"].shape[0])
    x = _embed(params, tokens, pos)  # (S, Q, d)
    n_write = jnp.clip(cap - start, 0, n_real)
    from ..ops.pallas_kernels import paged_decode_attention_wide

    def attend(q, k, v, pool, l):
        return paged_decode_attention_wide(q, pool, page_table, start, l)

    x, paged = _paged_layers(params, paged, x, start, n_write, page_table,
                             cfg, attend)
    return _logits(params, x), paged


class TransformerPrograms:
    """What serving.ServingEngine asks of a model, for this one: its cache
    (one paged pool for every layer), its three programs over it, the
    host arrays a prefill takes, and what the host can say of the cache
    without asking the device. The engine owns slots, queue, page tables
    and admission, and no shape. models.sambay.SambaYPrograms is the
    second implementer, models.falcon_h1.FalconH1Programs the third."""

    # no fixed-size state: every lever's rollback is a page-table write
    recurrent_state = False
    # while every slot decodes, no lever is on, no live request has an
    # `eos_id` and the step in flight ends no request, the engine
    # dispatches the step after the one in flight before it reads that one
    # (serving/engine.py `_runs_ahead`, `_launch`): the host's turn, 2.4 ms
    # around an 8.7 ms step of gpt2_xl, then runs beside the device. A
    # step() may so return with a decode step dispatched and unread: who
    # reads `eng.paged` after a step() asks `eng.decode_in_flight` first
    # (the cache is then one row a slot past `live_tokens()`); tokens,
    # finish reasons and admission steps are the synchronous loop's
    decode_ahead = True

    def __init__(self, cfg):
        self.cfg = cfg

    def init_cache(self, slots, num_pages, page_size):
        return init_paged_kv_cache(self.cfg, num_pages, page_size)

    def decode(self, params, cache, tokens, positions, table):
        return decode_step_paged(params, cache, tokens, positions, table,
                                 self.cfg)

    def prefill(self, params, cache, prompt, true_len, table):
        return prefill_paged(params, cache, prompt, true_len, table,
                             self.cfg)

    def wide(self, params, cache, tokens, start, n_real, table):
        return decode_step_paged_wide(params, cache, tokens, start, n_real,
                                      table, self.cfg)

    def copy_page(self, cache, src, dst):
        # pool is (L, H, num_pages, page_size, 2 * Dh): pages are axis 2
        kv = cache["kv"]
        return {"kv": kv.at[:, :, dst].set(kv[:, :, src])}

    def prefill_inputs(self, prompt, true_len, row, slot):
        """Host arrays of one prefill call after params and cache: the
        padded prompt (1, T_b), its real length, its page-table row."""
        return prompt, np.asarray([true_len], np.int32), row[None]

    def prefill_shapes(self, bucket, table_width):
        return [(1, bucket), (1,), (1, table_width)]

    def cache_kinds(self, page_size):
        return {"paged_kv": {"layers": self.cfg.n_layers, "grows": True}}

    def attended(self, n_valid):
        """Tokens one decode step attends, by cache kind, summed over the
        live slots' depths `n_valid` and over the layers that read."""
        return {"paged_kv": int(np.sum(n_valid)) * self.cfg.n_layers}

    def fetched(self, n_valid, page_size):
        """Tokens one decode step's kernels fetch to attend those, by
        cache kind: paged_decode_attention gathers a slot's pages a
        block at a time and masks the last block's tail."""
        from ..ops.pallas_kernels import paged_walk_tokens

        return {"paged_kv": paged_walk_tokens(n_valid, page_size)
                * self.cfg.n_layers}


def _filter_logits(logits, top_k=0, top_p=0.0):
    """Standard sampling filters, static-shape (jit-safe): top_k keeps the
    k largest logits, top_p (nucleus) keeps the smallest prefix of the
    sorted distribution whose mass exceeds p; everything else goes to
    -inf. The caller must pass TEMPERATURE-SCALED logits so the nucleus
    is taken on the actual sampling distribution."""
    need_sorted = (top_p and top_p > 0.0) or (top_k and top_k > 0)
    if not need_sorted:
        return logits
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]  # descending
    if top_k and top_k > 0:
        k = min(int(top_k), logits.shape[-1])  # clamp to vocab
        kth = sorted_logits[..., k - 1][..., None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p and top_p > 0.0:
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep tokens whose PRECEDING mass is < p (always keeps the top-1)
        keep_sorted = jnp.concatenate(
            [jnp.zeros_like(cum[..., :1]), cum[..., :-1]], -1) < top_p
        cutoff = jnp.min(jnp.where(keep_sorted, sorted_logits, jnp.inf),
                         axis=-1, keepdims=True)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return logits


def generate(params, prompt, n_steps, cfg: TransformerConfig, key=None,
             temperature=0.0, max_len=None, top_k=0, top_p=0.0):
    """Autoregressive generation as ONE jittable program: prefill the cache
    by scanning the prompt, then sample/argmax n_steps continuation tokens.

    prompt: (B, T_p) int32. Returns (B, n_steps) int32. temperature 0 =
    greedy; otherwise categorical sampling with `key`, optionally
    restricted by top_k / nucleus top_p."""
    B, T_p = prompt.shape
    cache = init_kv_cache(cfg, B, max_len)
    T_max = cache["k"].shape[2]
    if T_p + n_steps > T_max:
        # all lengths are static: fail at trace time instead of letting
        # dynamic_update_slice clamp writes onto the last cache slot
        raise ValueError(
            f"prompt ({T_p}) + n_steps ({n_steps}) exceeds the cache "
            f"capacity ({T_max}); raise max_len")
    if T_p + n_steps > params["pos"].shape[0]:
        raise ValueError(
            f"prompt ({T_p}) + n_steps ({n_steps}) exceeds max_len "
            f"({params['pos'].shape[0]}) positional embeddings")
    if key is None:
        key = jax.random.PRNGKey(0)

    cache, last_logits = prefill(params, cache, prompt, cfg)

    def sample(logits, k):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # temperature first, then filters: the nucleus must be taken on
        # the distribution actually sampled from
        logits = _filter_logits(logits / temperature, top_k=top_k,
                                top_p=top_p)
        return jax.random.categorical(k, logits, axis=-1).astype(jnp.int32)

    def gen_body(carry, k):
        cache, logits = carry
        tok = sample(logits, k)
        new_logits, cache = decode_step(params, cache, tok, cfg)
        return (cache, new_logits), tok

    keys = jax.random.split(key, n_steps)
    _, toks = lax.scan(gen_body, (cache, last_logits), keys)
    return toks.T  # (B, n_steps)


def beam_search(params, prompt, n_steps, cfg: TransformerConfig,
                beam_size=4, max_len=None):
    """Beam-search decoding as one jittable program.

    prompt (B, T_p) int32 -> (sequences (B, beam, n_steps) int32,
    scores (B, beam) summed log-probs), beams sorted best-first. The scan
    carries only the cache and per-beam scores; sequences are rebuilt at
    the end by backtracking the per-step parent pointers (no growing
    buffers inside the loop)."""
    B, T_p = prompt.shape
    K, V = int(beam_size), cfg.vocab
    cache = init_kv_cache(cfg, B, max_len)
    T_max = cache["k"].shape[2]
    # the first token comes from prefill logits, so only n_steps-1 decode
    # writes/pos-embedding reads happen (positions T_p .. T_p+n_steps-2)
    if T_p + n_steps - 1 > T_max:
        raise ValueError(
            f"prompt ({T_p}) + n_steps ({n_steps}) exceeds the cache "
            f"capacity ({T_max}); raise max_len")
    if T_p + n_steps - 1 > params["pos"].shape[0]:
        raise ValueError(
            f"prompt ({T_p}) + n_steps ({n_steps}) exceeds max_len "
            f"({params['pos'].shape[0]}) positional embeddings")

    cache, logits = prefill(params, cache, prompt, cfg)
    logp = jax.nn.log_softmax(logits, axis=-1)  # (B, V)
    scores, first = lax.top_k(logp, K)  # (B, K)
    first = first.astype(jnp.int32)

    # replicate the cache per beam: (L, B, T, H, D) -> (L, B*K, T, H, D)
    def rep(x):
        return jnp.repeat(x, K, axis=1)

    cache = {"k": rep(cache["k"]), "v": rep(cache["v"]), "pos": cache["pos"]}

    def step(carry, _):
        cache, scores, tokens = carry  # tokens (B, K) from previous step
        logits, cache = decode_step(params, cache, tokens.reshape(B * K),
                                    cfg)
        logp = jax.nn.log_softmax(logits, axis=-1).reshape(B, K, V)
        total = scores[..., None] + logp  # (B, K, V)
        scores, flat = lax.top_k(total.reshape(B, K * V), K)  # (B, K)
        parents = (flat // V).astype(jnp.int32)  # which beam each came from
        tokens = (flat % V).astype(jnp.int32)
        # reorder every beam-replicated cache row to follow its parent
        gather = (jnp.arange(B)[:, None] * K + parents).reshape(B * K)
        cache = {"k": cache["k"][:, gather], "v": cache["v"][:, gather],
                 "pos": cache["pos"]}
        return (cache, scores, tokens), (tokens, parents)

    (cache, scores, last), (toks, parents) = lax.scan(
        step, (cache, scores, first), None, length=n_steps - 1)
    # toks/parents: (n_steps-1, B, K); prepend the first-step tokens
    # and backtrack parents from the end to recover each beam's sequence
    def back(carry, step_data):
        beam_idx = carry  # (B, K) which beam each final beam was at t+1
        tok_t, par_t = step_data
        tok = jnp.take_along_axis(tok_t, beam_idx, axis=1)
        beam_idx = jnp.take_along_axis(par_t, beam_idx, axis=1)
        return beam_idx, tok

    init_idx = jnp.tile(jnp.arange(K, dtype=jnp.int32)[None], (B, 1))
    beam_idx, rev = lax.scan(back, init_idx, (toks, parents), reverse=True)
    first_tok = jnp.take_along_axis(first, beam_idx, axis=1)  # (B, K)
    seqs = jnp.concatenate([first_tok[None], rev], axis=0)  # (n_steps, B, K)
    return seqs.transpose(1, 2, 0), scores


# ---------------------------------------------------------------------------
# GSPMD step: dp x ep x tp
# ---------------------------------------------------------------------------

TP_RULES = [
    # attention: split heads (= output features of wq/wk/wv, input of wo)
    (r"^wq$|^wk$|^wv$", P(None, None, "tp")),
    (r"^wo$", P(None, "tp", None)),
    # dense FFN: Megatron column-then-row
    (r"^w1$", P(None, None, "tp") ),
    (r"^w2$", P(None, "tp", None)),
    (r"^embed$", P(None, None)),
]

MOE_TP_RULES = [
    (r"^wq$|^wk$|^wv$", P(None, None, "tp")),
    (r"^wo$", P(None, "tp", None)),
    # MoE FFN: experts on ep, hidden on tp
    (r"^w1$", P(None, "ep", None, "tp")),
    (r"^w2$", P(None, "ep", "tp", None)),
    (r"^router$", P()),
]


def make_gspmd_train_step(mesh: Mesh, cfg: TransformerConfig, lr=0.1, aux_weight=0.01):
    """Fused train step over a ('dp','ep','tp') mesh: batch on dp, MoE experts
    on ep, heads/FFN-hidden on tp. Returns (step, sharded_params).

    step(params, tokens, targets) -> (loss, new_params); jitted with donated
    params, shardings annotation-driven (GSPMD inserts collectives)."""
    params = init_params(cfg)
    rules = MOE_TP_RULES if cfg.n_experts else TP_RULES
    shardings = make_shardings(params, rules, mesh)
    params = {k: jax.device_put(v, shardings[k]) for k, v in params.items()}
    data_sharding = NamedSharding(mesh, P("dp", None))

    def loss_fn(p, tokens, targets):
        logits, aux = apply(p, tokens, cfg)
        if cfg.use_fused_xent:
            # pallas_call has no GSPMD partitioning rule — without this
            # shard_map XLA would replicate the (B, T, V) logits on every
            # chip to run the kernel; mapping over dp keeps it local
            losses = jax.shard_map(
                _xent_fused_local, mesh=mesh,
                in_specs=(P("dp", None, None), P("dp", None)),
                out_specs=P("dp", None))(logits, targets)
        else:
            losses = _xent(logits, targets)
        return jnp.mean(losses) + aux_weight * aux

    def step(p, tokens, targets):
        loss, grads = jax.value_and_grad(loss_fn)(p, tokens, targets)
        new_p = jax.tree.map(lambda w, g: w - lr * g, p, grads)
        return loss, new_p

    jstep = jax.jit(
        step,
        in_shardings=(shardings, data_sharding, data_sharding),
        out_shardings=(NamedSharding(mesh, P()), shardings),
        donate_argnums=(0,),
    )

    def run_step(p, tokens, targets):
        # stage host batches onto the mesh explicitly: on a mesh spanning
        # processes, jit cannot auto-commit raw host arrays (every process
        # holds the same batch; device_put builds the global array from
        # each process's addressable shards)
        tokens = jax.device_put(jnp.asarray(tokens), data_sharding)
        targets = jax.device_put(jnp.asarray(targets), data_sharding)
        return jstep(p, tokens, targets)

    return run_step, params


# ---------------------------------------------------------------------------
# shard_map step: dp x sp x pp (ring attention + SPMD pipeline)
# ---------------------------------------------------------------------------

def make_pipeline_train_step(mesh: Mesh, cfg: TransformerConfig, lr=0.1, n_micro=2):
    """Fused train step over a ('dp','sp','pp') mesh: batch sharded on dp and
    microbatched through an SPMD pipeline whose stages are the layer stack
    sharded on pp; inside each stage, attention is ring attention with the
    sequence sharded on sp. Returns (step, params).

    Per-call global shapes: tokens/targets (batch, seq). Requires
    batch % (dp * n_micro) == 0, seq % sp == 0, n_layers % pp == 0."""
    assert cfg.n_experts == 0, "pipeline step uses the dense FFN"
    params = init_params(cfg)
    pp = mesh.shape["pp"]
    assert cfg.n_layers % pp == 0

    stack_keys = _stack_keys(params)
    pspecs = {k: (P("pp") if k in stack_keys else P()) for k in params}
    params = {
        k: jax.device_put(v, NamedSharding(mesh, pspecs[k])) for k, v in params.items()
    }

    def stage_fn(stage_params, x):
        """Apply this stage's layer slice to one microbatch activation.
        x: (mb_local, T_local, d); stage_params leaves: (L/pp, ...)."""
        attn = functools.partial(ring_attention, axis_name="sp", causal=True)

        def body(h, lp):
            y, _, _ = _block(lp, h, None, cfg, _cacheless(attn))
            return y, None

        y, _ = lax.scan(body, x, stage_params)
        return y

    def local_step(p, tokens, targets):
        """Runs per-device under shard_map over ('dp','sp','pp').
        tokens/targets: (b_local, T_local) int32."""
        def loss_fn(p):
            b, t = tokens.shape
            sp_idx = lax.axis_index("sp")
            pos0 = sp_idx * t  # global position offset of this sequence shard
            x = _embed(p, tokens, pos0)
            stage_params = _stacked(p)
            mb = b // n_micro
            micro = x.reshape(n_micro, mb, t, cfg.d_model)
            out = spmd_pipeline(stage_fn, stage_params, micro, axis_name="pp")
            logits = _logits(p, out.reshape(b, t, cfg.d_model))
            losses = _xent(logits, targets, cfg.use_fused_xent)
            # replicated-scalar loss: only the device's own shard contributes,
            # psum over every mesh axis; pp ranks all hold identical outputs so
            # gate the contribution to pp rank 0.
            is_pp0 = (lax.axis_index("pp") == 0).astype(losses.dtype)
            total = lax.psum(jnp.sum(losses) * is_pp0, ("dp", "sp", "pp"))
            count = losses.size * mesh.shape["dp"] * mesh.shape["sp"]  # static
            return total / count

        loss, grads = jax.value_and_grad(loss_fn)(p)
        # grads of replicated params are device-varying partials (each device
        # saw its batch/sequence shard): all-reduce to the replicated mean.
        # pp-sharded stack grads are already correct per-stage; average over
        # the axes they are replicated on (dp, sp).
        def reduce_grad(k, g):
            axes = ("dp", "sp") if k in stack_keys else ("dp", "sp", "pp")
            return lax.pmean(g, axes)

        grads = {k: reduce_grad(k, g) for k, g in grads.items()}
        new_p = {k: p[k] - lr * grads[k] for k in p}
        return loss, new_p

    in_specs = (pspecs, P("dp", "sp"), P("dp", "sp"))
    out_specs = (P(), pspecs)
    smapped = jax.shard_map(local_step, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    jstep = jax.jit(smapped, donate_argnums=(0,))
    dp, sp = mesh.shape["dp"], mesh.shape["sp"]

    def checked_step(p, tokens, targets):
        b, t = tokens.shape
        if b % (dp * n_micro):
            raise ValueError(f"batch {b} not divisible by dp*n_micro = {dp * n_micro}")
        if t % sp:
            raise ValueError(f"seq len {t} not divisible by sp = {sp}")
        return jstep(p, tokens, targets)

    return checked_step, params
