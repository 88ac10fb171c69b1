"""Every public Pallas kernel must be acceptable to the TPU toolchain at the
shapes chip_smoke.py runs and at the serving engine's default geometry —
checked here on the CPU, in seconds, without a chip:

- lowering for platform "tpu" with interpret=False (the Pallas TPU block-shape
  rules: this alone would have caught the three decode kernels the first
  chip run refused);
- where libtpu can describe a v5e topology, the full ahead-of-time compile,
  which runs Mosaic itself (vector layouts, matmul operand types, VMEM).

The interpret-mode oracle tests (test_pallas.py, test_serving.py) check the
numbers; the chip checks them again in chip_smoke.py's kernel phase.
"""
import functools

import numpy as np

import jax
import jax.numpy as jnp
import pytest

from incubator_mxnet_tpu import config
from incubator_mxnet_tpu.ops import pallas_kernels as pk

import chip_smoke

SZ = chip_smoke.Sizes()
B_ATT, H, T, D = SZ.attn_shape
# ServingEngine's defaults for the smoke's transformer (max_len 512)
SLOTS = int(config.get("MXTPU_DECODE_SLOTS"))
PAGE = int(config.get("MXTPU_PAGE_SIZE"))
TABLE_W = -(-SZ.max_len // PAGE)
POOL = SLOTS * TABLE_W + 1
I32 = jnp.int32


def _grad(f, n):
    return jax.grad(lambda *a: jnp.sum(f(*a).astype(jnp.float32)),
                    argnums=tuple(range(n)))


def _write(n_rows, pool, k, v, table, start, n_write):
    plan = pk.paged_write_plan(table, start, n_write, n_rows, PAGE)
    return pk.paged_kv_write(pool, 1, k, v, plan, interpret=False)


def _cases():
    """(id, fn, [(shape, dtype), ...]) — every kernel, forward and backward,
    interpret=False passed explicitly."""
    out = []
    for dt in (jnp.bfloat16, jnp.float32):
        dn = jnp.dtype(dt).name
        att = [((B_ATT, H, T, D), dt)] * 3
        for causal in (True, False):
            flash = functools.partial(pk.flash_attention, causal=causal,
                                      interpret=False)
            out.append((f"flash_attention-{dn}-causal{causal}-fwd", flash,
                        att))
            out.append((f"flash_attention-{dn}-causal{causal}-bwd",
                        _grad(flash, 3), att))
        xent = functools.partial(pk.softmax_xent, interpret=False)
        xargs = [(SZ.xent_shape, dt), (SZ.xent_shape[:1], I32)]
        out.append((f"softmax_xent-{dn}-fwd", xent, xargs))
        out.append((f"softmax_xent-{dn}-bwd",
                    jax.grad(lambda lg, lb: jnp.sum(xent(lg, lb))), xargs))
        out.append((
            f"flash_decode-{dn}",
            functools.partial(pk.flash_decode, interpret=False),
            [((SLOTS, H, D), dt)]
            + [((SLOTS, SZ.decode_len, H, D), dt)] * 2 + [((SLOTS,), I32)]))
        # one pool for every layer, K|V fused per row, read and written
        # in layer 1 of 2
        pool = [((2, H, POOL, PAGE, 2 * D), dt)]
        tail = [((SLOTS, TABLE_W), I32), ((SLOTS,), I32)]
        out.append((
            f"paged_decode_attention-{dn}",
            functools.partial(pk.paged_decode_attention, layer=1,
                              interpret=False),
            [((SLOTS, H, D), dt)] + pool + tail))
        for q in SZ.wide_q:
            out.append((
                f"paged_decode_attention_wide-{dn}-Q{q}",
                functools.partial(pk.paged_decode_attention_wide, layer=1,
                                  interpret=False),
                [((SLOTS, q, H, D), dt)] + pool + tail))
        for q in (1,) + SZ.wide_q:
            out.append((
                f"paged_kv_write-{dn}-Q{q}",
                functools.partial(_write, q),
                pool + [((SLOTS, q, H, D), dt)] * 2 + tail + tail[1:]))
    # the block walk at the benchmark's geometry (gpt2_xl: 16 slots, 25
    # heads of 64, 1025 pages of 16, a table 64 wide, bf16 pool, float32
    # queries), the layer index traced as the layer loop passes it; and
    # over a pool no VMEM could map, 8193 pages a head
    for name, q_rows, n_pages in (("xl-Q1", 1, 1025), ("xl-Q8", 8, 1025),
                                  ("xl-8193pages-Q1", 1, 8193)):
        out.append((
            f"paged_decode_attention_wide-{name}",
            lambda q, pool, table, n_base, layer: (
                pk.paged_decode_attention_wide(q, pool, table, n_base,
                                               layer, interpret=False)),
            [((16, q_rows, 25, 64), jnp.float32),
             ((2, 25, n_pages, 16, 128), jnp.bfloat16),
             ((16, 64), I32), ((16,), I32), ((), I32)]))
    # grouped differential attention (models.sambay) at the benchmark's
    # geometry: 16 slots, 10 K/V pairs of 64, the shared pool of 7169 pages
    # and the window layers' rings of 33; and each over a pool no VMEM
    # could map, 16385 pages a head
    for name, pool, width, kw in (
            ("shared", (1, 20, 7169, 16, 128), 448, {}),
            ("ring", (8, 20, 529, 16, 128), 33,
             {"window": 512, "ring": True}),
            ("shared-16385pages", (1, 20, 16385, 16, 128), 1024, {}),
            ("ring-16385pages", (8, 20, 16385, 16, 128), 33,
             {"window": 512, "ring": True})):
        out.append((
            f"paged_diff_attention-{name}",
            functools.partial(pk.paged_diff_attention, layer=0,
                              interpret=False, **kw),
            [((16, 10, 4, 64), jnp.float32), (pool, jnp.bfloat16),
             ((16, width), I32), ((16,), I32)]))
    for S_, T_ in ((1, 4096), (16, 1)):  # a prompt bucket; a decode batch
        out.append((
            f"selective_scan-S{S_}-T{T_}",
            functools.partial(pk.selective_scan, interpret=False),
            [((S_, T_, 5120), jnp.float32)] * 2
            + [((S_, T_, 16), jnp.float32)] * 2
            + [((16, 5120), jnp.float32), ((S_, 16, 5120), jnp.float32)]))
    # grouped-query attention at head width 128 and the head-wise
    # recurrence's decode step at the benchmark's geometry (falcon_h1_34b:
    # 32 slots, 20 query heads over 4 K/V heads of 128, K|V fused in 256
    # lanes, 8193 pages of 16, a table 256 wide; a state of (32, 128, 256)
    # a slot and layer), the layer index traced
    gqa_pool = [((6, 4, 8193, 16, 256), jnp.bfloat16)]
    gqa_tail = [((32, 256), I32), ((32,), I32)]
    out.append((
        "paged_decode_attention-gqa5x128",
        lambda q, pool, table, n_valid, layer: pk.paged_decode_attention(
            q, pool, table, n_valid, layer, interpret=False),
        [((32, 20, 128), jnp.float32)] + gqa_pool + gqa_tail + [((), I32)]))
    for name, s_, q in (("decode", 32, 1), ("prompt", 1, 2048)):
        out.append((
            f"paged_kv_write-gqa5x128-{name}",
            functools.partial(_write, q),
            gqa_pool + [((s_, q, 4, 128), jnp.float32)] * 2
            + [((s_, 256), I32), ((s_,), I32), ((s_,), I32)]))
    out.append((
        "ssd_state_update-S32",
        functools.partial(pk.ssd_state_update, interpret=False),
        [((6, 32, 32, 128, 256), jnp.float32), ((), I32), ((32,), jnp.bool_),
         ((32, 32, 128), jnp.float32), ((32, 32), jnp.float32),
         ((32,), jnp.float32)] + [((32, 2, 256), jnp.float32)] * 2))
    # power retention (models.brumby) at the benchmark's geometry
    # (brumby_14b: 16 slots, 40 query heads over 8 K/V heads of 128, a
    # (128, 8320) float32 state a K/V head, 8 layers, the layer index
    # traced; bfloat16 products), and a prompt's chunked form
    F = pk.retention_width(128)
    out.append((
        "power_retention_step-brumby",
        lambda st, z, l, live, q, k, v, g: pk.power_retention_step(
            st, z, l, live, q, k, v, g, dtype=jnp.bfloat16,
            interpret=False),
        [((8, 16, 8, 128, F), jnp.float32), ((8, 16, 8, F), jnp.float32),
         ((), I32), ((16,), jnp.bool_), ((16, 40, 128), jnp.float32)]
        + [((16, 8, 128), jnp.float32)] * 2 + [((16, 8), jnp.float32)]))
    out.append((
        "power_retention_chunk-brumby-T1024",
        functools.partial(pk.power_retention_chunk, chunk=128,
                          dtype=jnp.bfloat16, interpret=False),
        [((1, 1024, 40, 128), jnp.float32)]
        + [((1, 1024, 8, 128), jnp.float32)] * 2
        + [((1, 1024, 8), jnp.float32)]))
    epi = functools.partial(pk.bn_act_epilogue, interpret=False)
    for r, c in SZ.epilogue_shapes:
        plain = [((r, c), jnp.bfloat16), ((c,), jnp.float32),
                 ((c,), jnp.float32)]
        res = plain + [((r, c), jnp.bfloat16)]
        out.append((f"bn_act_epilogue-{r}x{c}-fwd", epi, plain))
        out.append((f"bn_act_epilogue-{r}x{c}-bwd", _grad(epi, 3), plain))
        out.append((f"bn_act_epilogue-{r}x{c}-residual-fwd", epi, res))
        out.append((f"bn_act_epilogue-{r}x{c}-residual-bwd", _grad(epi, 4),
                    res))
    return out


CASES = _cases()
IDS = [c[0] for c in CASES]


def test_every_public_kernel_has_a_case():
    kernels = {n for n in pk.__all__
               if callable(getattr(pk, n))
               # plain jax.numpy, no kernel to lower
               and n not in ("dense_decode_attention", "paged_write_plan",
                             "paged_ring_write_plan", "ssd_chunk_scan")}
    covered = {i.split("-")[0] for i in IDS}
    assert kernels == covered, kernels ^ covered


@pytest.mark.parametrize("case", CASES, ids=IDS)
def test_lowers_for_tpu(case):
    _, fn, args = case
    avals = [jax.ShapeDtypeStruct(s, d) for s, d in args]
    text = jax.jit(fn).trace(*avals).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text


@pytest.fixture(scope="module")
def v5e_device():
    """A compile-only v5e device from libtpu — no chip needed, none used."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    except Exception as e:  # no libtpu, or one that cannot describe a v5e
        pytest.skip(f"libtpu cannot describe a v5e topology here: {e}")
    return topo.devices[0]


@pytest.mark.parametrize("case", CASES, ids=IDS)
def test_mosaic_compiles_for_v5e(case, v5e_device):
    _, fn, args = case
    sharding = jax.sharding.SingleDeviceSharding(v5e_device)
    avals = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in args]
    jax.jit(fn).lower(*avals).compile()


# -- the serving programs keep the KV pool where it lies -----------------------

# GPT-2 XL's widths and the benchmark's engine geometry (16 slots, pages of
# 16, max_len 1024: 1025 pages), at a depth that compiles in seconds
XL = dict(vocab=50257, d_model=1600, n_heads=25, d_ff=6400, n_layers=4,
          max_len=1024, dtype="bfloat16")
XL_SLOTS, XL_PAGE = 16, 16
XL_TABLE_W = XL["max_len"] // XL_PAGE
XL_PAGES = XL_SLOTS * XL_TABLE_W + 1
# what one layer's K (or V) holds: nothing this large may be copied, sliced,
# transposed or scattered once per layer
LAYER_K_ELEMS = XL["n_heads"] * XL_PAGES * XL_PAGE * (XL["d_model"]
                                                      // XL["n_heads"])
_BIG_OP = r"= \w+\[([\d,]+)\]\S* (copy|transpose|dynamic-slice|scatter)\("


def _engine_programs(cfg):
    """ServingEngine's jitted functions over a stand-in self, each with
    its argument shapes after params and pool."""
    import types
    from incubator_mxnet_tpu.serving import ServingEngine

    me = types.SimpleNamespace(cfg=cfg)
    S, W = XL_SLOTS, XL_TABLE_W
    return {
        "decode": (functools.partial(ServingEngine._decode_fn, me),
                   [(S,), (S,), (S, W)]),
        "prefill_b1024": (functools.partial(ServingEngine._prefill_fn, me),
                          [(1, 1024), (1,), (1, W)]),
    }


@pytest.mark.parametrize("program", ["decode", "prefill_b1024"])
def test_serving_program_carries_the_pool_in_place(program, v5e_device,
                                                   monkeypatch):
    """The compiled decode step and a prefill bucket hold the pool once:
    donated input, loop carry and output are one allocation, and no op
    moves a layer's worth of it. Fails if the pool goes back to scan
    xs -> ys, to a scatter over a reshaped slice, or to a layout the
    kernels cannot read in place."""
    import re
    from incubator_mxnet_tpu.models import transformer as tfm

    # the kernels pick interpret mode from the backend, which is the CPU here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = tfm.TransformerConfig(**XL)
    sharding = jax.sharding.SingleDeviceSharding(v5e_device)

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    # the benchmark's leaves: bf16 matrices, float32 positions
    params = {k: on_chip(v.shape, jnp.float32 if k == "pos" else jnp.bfloat16)
              for k, v in jax.eval_shape(
                  lambda: tfm.init_params(cfg, 0)).items()}
    paged = jax.tree_util.tree_map(
        lambda v: on_chip(v.shape, v.dtype),
        jax.eval_shape(lambda: tfm.init_paged_kv_cache(cfg, XL_PAGES,
                                                       XL_PAGE)))
    fn, tail = _engine_programs(cfg)[program]
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, paged, *[on_chip(s, I32) for s in tail]).compile()

    mem = compiled.memory_analysis()
    itemsize = jnp.dtype(cfg.dtype).itemsize
    pool_bytes = sum(v.size for v in paged.values()) * itemsize
    layer_bytes = pool_bytes // cfg.n_layers  # one layer's K + V
    # the tied embedding lies vocabulary-minor at the jit boundary, and a
    # gather of its rows re-lays-out all of it (161 MB): a prompt's 1024
    # tokens still take that gather; the decode step's 16 take slabs
    # (models.transformer._token_rows) and no program op may touch the
    # table but the logits product
    gathers_table = program != "decode"
    embed_bytes = gathers_table * cfg.vocab * cfg.d_model * itemsize
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes - embed_bytes < layer_bytes, mem
    big = [m.group(0) for m in re.finditer(_BIG_OP, compiled.as_text())
           if np.prod([int(d) for d in m.group(1).split(",")])
           >= LAYER_K_ELEMS
           and not (gathers_table
                    and m.group(1) == f"{cfg.vocab},{cfg.d_model}")]
    assert not big, big
