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
        pool = [((H, POOL, PAGE, D), dt)] * 2
        tail = [((SLOTS, TABLE_W), I32), ((SLOTS,), I32)]
        out.append((
            f"paged_decode_attention-{dn}",
            functools.partial(pk.paged_decode_attention, interpret=False),
            [((SLOTS, H, D), dt)] + pool + tail))
        for q in SZ.wide_q:
            out.append((
                f"paged_decode_attention_wide-{dn}-Q{q}",
                functools.partial(pk.paged_decode_attention_wide,
                                  interpret=False),
                [((SLOTS, q, H, D), dt)] + pool + tail))
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
               if callable(getattr(pk, n)) and n != "dense_decode_attention"}
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
