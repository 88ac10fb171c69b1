"""The two ends of models.transformer that touch the tied embedding: the
token lookup (_token_rows: slab by slab for few tokens, so that no serving
program re-lays-out the table; the plain gather otherwise, to the letter)
and the logits product (_logits: one statement for every program). CPU:
results and program texts; what the v5e compiles is
test_pallas_tpu_lowering.py's, what it takes is PERF.md's."""
import numpy as np

import jax
import jax.numpy as jnp
import pytest

from incubator_mxnet_tpu.models import transformer as tfm
from incubator_mxnet_tpu.serving import ServingEngine


def _plain_rows(table, tokens):
    return table[tokens]


def _plain_logits(params, x):
    return tfm._ln(x, params["ln_f_g"], params["ln_f_b"]) @ params["embed"].T


def _slab_cfg(**kw):
    # rows that are not whole lanes, a vocabulary past one slab: the
    # shape class of GPT-2 XL's table (50257, 1600)
    base = dict(vocab=300, d_model=72, n_heads=4, n_layers=2, d_ff=96,
                max_len=64)
    base.update(kw)
    return tfm.TransformerConfig(**base)


def _takes_slabs(table, tokens):
    text = str(jax.make_jaxpr(tfm._token_rows)(table, tokens))
    return "name=_slab_rows" in text


# -- the lookup ----------------------------------------------------------------

# every index a caller could hand over: both ends of the table and of a
# slab, the last (short) slab, past the table, negative, below -V
_INDICES = [0, 1, 127, 128, 255, 256, 871, 872, 873, 999, 1000, 5000, -1,
            -128, -999, -1000, -1001, -5000]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("shape,slabs", [
    ((18,), True), ((3, 6), True), ((1, 128), True),
    ((1, 129), False), ((4, 64), False)], ids=str)
def test_token_rows_is_the_plain_lookup(shape, slabs, dtype):
    """Bit for bit table[tokens], whichever way it is fetched: slabs up
    to _SLAB_LOOKUP_MAX_TOKENS tokens, the gather past them."""
    table = jax.random.normal(jax.random.PRNGKey(0), (1000, 72)).astype(dtype)
    n = int(np.prod(shape))
    tokens = jnp.asarray(np.resize(np.asarray(_INDICES, np.int32), n)
                         .reshape(shape))
    assert _takes_slabs(table, tokens) == slabs
    got = jax.jit(tfm._token_rows)(table, tokens)
    want = table[tokens]
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.float32)))


@pytest.mark.parametrize("table_shape", [(1000, 128), (1000, 256), (128, 72),
                                         (64, 32)], ids=str)
def test_token_rows_leaves_row_major_and_small_tables_alone(table_shape):
    """Rows of whole lanes lie row-major on the TPU, and a table of one
    slab has nothing to skip: the plain gather, the parent's text."""
    table = jnp.zeros(table_shape, jnp.bfloat16)
    tokens = jnp.zeros((16,), jnp.int32)
    assert not _takes_slabs(table, tokens)
    assert (str(jax.make_jaxpr(tfm._token_rows)(table, tokens))
            == str(jax.make_jaxpr(_plain_rows)(table, tokens)))


def test_token_rows_differentiates_as_the_plain_lookup():
    table = jax.random.normal(jax.random.PRNGKey(1), (1000, 72))
    tokens = jnp.asarray([[3, 3, 999, -1, 500, 128]], jnp.int32)
    assert _takes_slabs(table, tokens)

    def loss(rows):
        return lambda t: jnp.sum(jnp.sin(rows(t, tokens)) ** 2)

    got = jax.grad(loss(tfm._token_rows))(table)
    want = jax.grad(loss(_plain_rows))(table)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and its backward pass is the plain lookup's one scatter-add
    text = str(jax.make_jaxpr(jax.grad(loss(tfm._token_rows)))(table))
    assert text.count("scatter-add") == 1, text
    assert "dynamic_update_slice" not in text, text


# -- the product ---------------------------------------------------------------

@pytest.mark.parametrize("table_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32_table", "bf16_table"])
@pytest.mark.parametrize("rows", [(1, 72), (16, 72), (4, 5, 72), (2, 160, 72),
                                  (400, 72)], ids=str)
def test_logits_against_float64(rows, table_dtype):
    """_logits is LayerNorm then the product with the table as stored,
    float32 out, from one decode row to a training batch (more rows than
    the table has, fewer, one), against NumPy in float64."""
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    params = {
        "embed": (0.05 * jax.random.normal(ks[0], (300, 72))
                  ).astype(table_dtype),
        "ln_f_g": (1 + 0.1 * jax.random.normal(ks[1], (72,))
                   ).astype(table_dtype),
        "ln_f_b": (0.1 * jax.random.normal(ks[2], (72,))).astype(table_dtype),
    }
    x = 3.0 * jax.random.normal(ks[3], rows) + 0.5
    got = jax.jit(tfm._logits)(params, x)
    assert got.shape == rows[:-1] + (300,) and got.dtype == jnp.float32

    p64 = {k: np.asarray(v.astype(jnp.float32)).astype(np.float64)
           for k, v in params.items()}
    x64 = np.asarray(x).astype(np.float64)
    mu = x64.mean(-1, keepdims=True)
    var = ((x64 - mu) ** 2).mean(-1, keepdims=True)
    h = (x64 - mu) / np.sqrt(var + 1e-5) * p64["ln_f_g"] + p64["ln_f_b"]
    want = h @ p64["embed"].T
    err = np.abs(np.asarray(got, np.float64) - want).max(-1) / want.std(-1)
    assert err.max() < 1e-4, err.max()  # in sigma of a row


# -- the programs --------------------------------------------------------------

def _paged_decode_text(cfg, slots=4, table_w=4, page=16):
    params = jax.eval_shape(lambda: tfm.init_params(cfg, 0))
    paged = jax.eval_shape(
        lambda: tfm.init_paged_kv_cache(cfg, slots * table_w + 1, page))
    i32 = jnp.int32
    return jax.jit(
        lambda p, kv, t, pos, tab: tfm.decode_step_paged(p, kv, t, pos, tab,
                                                         cfg)
    ).lower(params, paged, jax.ShapeDtypeStruct((slots,), i32),
            jax.ShapeDtypeStruct((slots,), i32),
            jax.ShapeDtypeStruct((slots, table_w), i32)).as_text()


def test_paged_decode_gathers_no_row_of_the_table():
    """The decode program's lowered text holds no gather of ROWS of the
    (V, d) table (XLA's TPU gather re-lays-out all of it first): what it
    takes from the table are whole slabs."""
    cfg = _slab_cfg()
    text = _paged_decode_text(cfg)
    table = f"(tensor<{cfg.vocab}x{cfg.d_model}xf32>"
    gathers = [ln for ln in text.splitlines()
               if "stablehlo.gather" in ln and table in ln]
    slabs = f"slice_sizes = array<i64: {tfm._SLAB}, {cfg.d_model}>"
    assert gathers and all(slabs in ln for ln in gathers), gathers


def _lowered(fn, *args):
    text = jax.jit(fn).lower(*args).as_text()
    return "\n".join(ln for ln in text.splitlines()
                     if not ln.startswith("#loc"))


@pytest.mark.parametrize("batch,seq", [(2, 128), (1, 129)], ids=str)
@pytest.mark.parametrize("grad", [False, True], ids=["forward", "grad"])
def test_apply_at_training_shapes_lowers_as_written_plain(batch, seq, grad,
                                                         monkeypatch):
    """No cell times the training path, so it may not change unmeasured:
    past _SLAB_LOOKUP_MAX_TOKENS tokens `apply`, forward and
    differentiated, lowers to the text it has with `table[tokens]` and
    `_ln(x) @ embed.T` written out."""
    cfg = _slab_cfg(max_len=256)
    params = jax.eval_shape(lambda: tfm.init_params(cfg, 0))
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)

    def forward(p, t):
        return tfm.apply(p, t, cfg)

    def backward(p, t):
        return jax.grad(
            lambda q: jnp.mean(tfm._xent(tfm.apply(q, t, cfg)[0], t)))(p)

    fn = backward if grad else forward
    ours = _lowered(fn, params, tokens)
    monkeypatch.setattr(tfm, "_token_rows", _plain_rows)
    monkeypatch.setattr(tfm, "_logits", _plain_logits)
    assert ours == _lowered(fn, params, tokens)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_engine_tokens_are_what_the_plain_lookup_gives(dtype, monkeypatch):
    """Greedy tokens of ServingEngine over a table that takes the slab
    path (prefill buckets past the threshold take the gather, decode
    steps the slabs) are those of the parent's statements. (Against
    sequential generate(): test_serving_engine.py's token identity
    tests, which run the lookup both ways too.)"""
    cfg = _slab_cfg(dtype=dtype)
    params = tfm.init_params(cfg, seed=5)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, cfg.vocab, size=(n,)).astype(np.int32)
               for n in (4, 19, 33, 7)]

    def served():
        eng = ServingEngine(params, cfg, slots=3, page_size=8, num_pages=40)
        rids = [eng.submit(p, m) for p, m in zip(prompts, (6, 4, 5, 8))]
        out = eng.run()
        return [list(out[r].tokens) for r in rids]

    ours = served()
    monkeypatch.setattr(tfm, "_token_rows", _plain_rows)
    monkeypatch.setattr(tfm, "_logits", _plain_logits)
    assert ours == served()
