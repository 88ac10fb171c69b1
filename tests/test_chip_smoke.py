"""chip_smoke.py's phases, walked on the CPU backend at sizes that take
seconds, with the kernels interpreted: the smoke's own control flow and
checks must not wait for a chip call to show a typo. (That the entry point
itself refuses to run without a TPU is pinned in test_bench.py; what the
chip says is in CHANGES.md.)"""
import jax
import pytest

import chip_smoke as cs

TINY = cs.Sizes(
    batch=8, image=32, steps=2, scan_k=2,
    vocab=128, d_model=32, n_heads=2, n_layers=1, d_ff=64, max_len=64,
    requests=((5, 4), (20, 6), (12, 5), (3, 7), (9, 4), (15, 5), (7, 6),
              (11, 4)), identity_requests=((7, 3), (19, 2)),
    head_shape=(4, 300, 72),
    attn_shape=(1, 2, 32, 16), xent_shape=(16, 128), decode_batch=2,
    decode_len=128, page_size=8, table_width=4, wide_q=(2,),
    diff_geometry=(4, 2, 40, 170, 4, 24),
    hybrid_geometry=(4, 2, 5, 16, 40, 170, 4, 8, 16, 2), ssd_chunk=8,
    epilogue_shapes=((40, 16),), interpret=True)


@pytest.fixture
def clock():
    from incubator_mxnet_tpu import telemetry

    was = telemetry.enabled()
    telemetry.enable()  # the fallback counters only count when it is on
    yield cs.CompileClock()
    if not was:
        telemetry.disable()


def test_serve_phase(clock):
    out = cs.run_phase("serve", clock, cs.phase_serve, clock, TINY)
    assert out["steady_compiles"] == 0 and out["dense_fallbacks"] == 0
    # eight requests into eight slots, the shortest of 4 tokens: two decode
    # steps go out ahead, under the executable the warm-up compiled
    assert out["decode_steps_ahead"] == 2 < out["decode_steps"]
    assert out["token_identical_requests"] == 2
    assert out["head_sigma_rows"] < 1e-4 > out["head_sigma_one_row"]
    assert out["compile_s"] > 0 and out["run_s"] >= 0


def test_kernels_phase_compares_every_kernel(clock):
    out = cs.run_phase("kernels", clock, cs.phase_kernels, TINY)
    # per dtype: 4 flash_attention, 2 xent, flash_decode, paged, one wide
    # and the write its rows take, the grouped differential kernel by a
    # table and by a ring; then that kernel twice at the benchmark's
    # geometry, 2 selective scans, the grouped-query decode kernel, the
    # state update and the chunked scan at theirs, and 4 epilogues
    assert out["compared"] == 2 * 12 + 2 + 2 + 3 + 4


def test_a_kernel_off_its_oracle_fails_the_phase(clock, monkeypatch):
    from incubator_mxnet_tpu.ops import pallas_kernels as pk

    real = pk.flash_decode
    monkeypatch.setattr(pk, "flash_decode",
                        lambda *a, **k: real(*a, **k) * 1.5)
    with pytest.raises(cs.SmokeFailure, match="flash_decode"):
        cs.phase_kernels(TINY)


def test_a_lookup_off_the_plain_one_fails_the_smoke(monkeypatch):
    from incubator_mxnet_tpu.models import transformer as tfm

    monkeypatch.setattr(tfm, "_token_rows",
                        lambda table, tokens: table[tokens - 1])
    with pytest.raises(cs.SmokeFailure, match="_token_rows"):
        cs._embedding_ends(TINY)


def test_a_head_off_full_precision_fails_the_smoke(monkeypatch):
    from incubator_mxnet_tpu.models import transformer as tfm

    real = tfm._logits
    monkeypatch.setattr(tfm, "_logits", lambda p, x: real(p, x) * 1.01)
    with pytest.raises(cs.SmokeFailure, match="_logits"):
        cs._embedding_ends(TINY)


@pytest.mark.slow
def test_train_and_mesh_phases(clock, monkeypatch):
    """ResNet-50 compiles for a minute on the CPU: the slow tier."""
    devices = jax.devices()[:4]
    out = cs.run_phase("train", clock, cs.phase_train, devices, TINY)
    assert out["last_loss"] < out["first_loss"]
    # at batch 2 per device bf16 BatchNorm noise swamps the smoke's bound
    # (measured 2.6e-2 of the loss here); the chip run holds the real one
    monkeypatch.setattr(cs, "MESH_LOSS_RTOL", 0.1)
    mesh = cs.run_phase("mesh", clock, cs.phase_mesh, devices, TINY,
                        out["first_loss"])
    assert mesh["zero1"]["first_loss"] == mesh["replicated"]["first_loss"]
