"""Gluon tests (ref: tests/python/unittest/test_gluon.py)."""
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, nd, autograd
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.test_utils import assert_almost_equal


def test_parameter():
    p = gluon.Parameter("weight", shape=(4, 8))
    p.initialize(init=mx.init.One())
    assert p.data().shape == (4, 8)
    assert (p.data().asnumpy() == 1).all()
    assert p.grad().shape == (4, 8)
    p.set_data(nd.zeros((4, 8)))
    assert (p.data().asnumpy() == 0).all()


def test_parameter_deferred():
    p = gluon.Parameter("w", shape=(4, 0), allow_deferred_init=True)
    p.initialize()
    with pytest.raises(gluon.DeferredInitializationError):
        p.data()
    p.shape = (4, 7)
    assert p.data().shape == (4, 7)


def test_dense_deferred_shape():
    net = nn.Dense(5)
    net.initialize()
    out = net(nd.ones((3, 11)))
    assert out.shape == (3, 5)
    assert net.weight.shape == (5, 11)


def test_sequential_and_children():
    net = nn.HybridSequential()
    net.add(nn.Dense(8), nn.Activation("relu"), nn.Dense(2))
    net.initialize()
    assert len(net) == 3
    y = net(nd.ones((4, 3)))
    assert y.shape == (4, 2)
    params = net.collect_params()
    assert len(list(params.keys())) == 4


def test_block_save_load(tmp_path):
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=4), nn.Dense(2, in_units=8))
    net.initialize(mx.init.Xavier())
    x = nd.ones((2, 4))
    y1 = net(x).asnumpy()
    f = str(tmp_path / "net.params")
    net.save_parameters(f)
    net2 = nn.HybridSequential()
    net2.add(nn.Dense(8, in_units=4), nn.Dense(2, in_units=8))
    net2.load_parameters(f)
    y2 = net2(x).asnumpy()
    assert_almost_equal(y1, y2)


def test_hybridize_consistency():
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize(mx.init.Xavier())
    x = nd.array(np.random.randn(5, 8).astype("float32"))
    y_eager = net(x).asnumpy()
    net.hybridize()
    y_hybrid = net(x).asnumpy()
    assert_almost_equal(y_eager, y_hybrid, rtol=1e-5, atol=1e-6)


def test_hybrid_batchnorm_aux_update():
    net = nn.HybridSequential()
    net.add(nn.Conv2D(4, 3, padding=1), nn.BatchNorm())
    net.initialize()
    net.hybridize()
    bn = net[1]
    x = nd.array(np.random.randn(2, 3, 8, 8).astype("float32"))
    net(x)  # first forward resolves deferred shapes (predict: no stat update)
    before = bn.running_mean.data().asnumpy().copy()
    with autograd.record():
        net(x)
    after = bn.running_mean.data().asnumpy()
    assert not np.allclose(before, after)


def test_gluon_trainer_convergence():
    np.random.seed(0)
    X = np.random.randn(400, 8).astype("float32")
    W = np.random.randn(8, 1).astype("float32")
    Y = X @ W + 0.01 * np.random.randn(400, 1).astype("float32")
    net = nn.Dense(1)
    net.initialize(mx.init.Normal(0.1))
    trainer = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    L = gluon.loss.L2Loss()
    for _ in range(50):
        with autograd.record():
            loss = L(net(nd.array(X)), nd.array(Y))
        loss.backward()
        trainer.step(400)
    final = float(loss.mean().asscalar())
    assert final < 0.01, final


def test_losses_values():
    L = gluon.loss.L2Loss()
    a, b = nd.ones((2, 3)), nd.zeros((2, 3))
    assert_almost_equal(L(a, b).asnumpy(), np.full(2, 0.5), rtol=1e-6)
    L1 = gluon.loss.L1Loss()
    assert_almost_equal(L1(a, b).asnumpy(), np.ones(2), rtol=1e-6)
    sce = gluon.loss.SoftmaxCrossEntropyLoss()
    pred = nd.array([[10.0, 0.0], [0.0, 10.0]])
    label = nd.array([0.0, 1.0])
    assert float(sce(pred, label).mean().asscalar()) < 0.01
    hinge = gluon.loss.HingeLoss()
    assert float(hinge(nd.array([[2.0]]), nd.array([[1.0]])).asscalar()) == 0.0


def test_loss_grad_flows():
    net = nn.Dense(2, in_units=3)
    net.initialize()
    L = gluon.loss.SoftmaxCrossEntropyLoss()
    with autograd.record():
        loss = L(net(nd.ones((4, 3))), nd.zeros((4,)))
    loss.backward()
    g = net.weight.grad().asnumpy()
    assert np.abs(g).sum() > 0


def test_lstm_layer_forward_backward():
    lstm = gluon.rnn.LSTM(16, num_layers=2)
    lstm.initialize(mx.init.Xavier())
    x = nd.array(np.random.randn(5, 3, 8).astype("float32"))
    with autograd.record():
        out = lstm(x)
        loss = out.sum()
    loss.backward()
    assert out.shape == (5, 3, 16)
    p = lstm.collect_params()
    some_w = [v for k, v in p.items() if k.endswith("l0_i2h_weight")][0]
    assert np.abs(some_w.grad().asnumpy()).sum() > 0


def test_gru_bidirectional_states():
    gru = gluon.rnn.GRU(8, num_layers=1, bidirectional=True)
    gru.initialize()
    x = nd.array(np.random.randn(4, 2, 5).astype("float32"))
    states = gru.begin_state(batch_size=2)
    out, new_states = gru(x, states)
    assert out.shape == (4, 2, 16)
    assert new_states[0].shape == (2, 2, 8)


def test_grouped_deconv_bn_inference_dense_noflatten():
    """Grouped transposed conv vs torch; BatchNorm inference uses the
    running stats exactly; Dense(flatten=False) applies to the last axis."""
    import torch

    rng = np.random.RandomState(0)
    netd = nn.Conv2DTranspose(4, 3, strides=2, padding=1, groups=2,
                              in_channels=4)
    netd.initialize()
    xd = rng.rand(1, 4, 6, 6).astype("float32")
    t = torch.nn.ConvTranspose2d(4, 4, 3, stride=2, padding=1, groups=2,
                                 bias=False)
    with torch.no_grad():
        t.weight.copy_(torch.from_numpy(netd.weight.data().asnumpy().copy()))
        ref = t(torch.from_numpy(xd)).numpy()
    assert_almost_equal(netd(nd.array(xd)).asnumpy(), ref,
                        rtol=1e-4, atol=1e-5)

    bn = nn.BatchNorm(in_channels=3)
    bn.initialize()
    xb = rng.rand(8, 3, 4, 4).astype("float32") * 2 + 1
    with mx.autograd.record():
        bn(nd.array(xb))  # one training pass moves the running stats
    out = bn(nd.array(xb)).asnumpy()
    rm = bn.running_mean.data().asnumpy()
    rv = bn.running_var.data().asnumpy()
    g = bn.gamma.data().asnumpy()
    b = bn.beta.data().asnumpy()
    ref = ((xb - rm[None, :, None, None])
           / np.sqrt(rv[None, :, None, None] + 1e-5)
           * g[None, :, None, None] + b[None, :, None, None])
    assert_almost_equal(out, ref, rtol=1e-3, atol=1e-4)

    dn = nn.Dense(5, flatten=False, in_units=4)
    dn.initialize()
    xf = rng.rand(2, 3, 4).astype("float32")
    out = dn(nd.array(xf)).asnumpy()
    assert out.shape == (2, 3, 5)
    assert_almost_equal(out, xf @ dn.weight.data().asnumpy().T
                        + dn.bias.data().asnumpy(), rtol=1e-5)


def test_conv_pool_variants_match_torch():
    """External oracles for the conv/pool lowerings the 2D tests don't
    cover: Conv1D (strided+padded), Conv3D, padded AvgPool2D, and LP
    pooling at p=1/2/3."""
    import torch

    rng = np.random.RandomState(0)

    net1 = nn.Conv1D(6, 3, strides=2, padding=1, in_channels=4)
    net1.initialize()
    x1 = rng.rand(2, 4, 16).astype("float32")
    t1 = torch.nn.Conv1d(4, 6, 3, stride=2, padding=1)
    with torch.no_grad():
        t1.weight.copy_(torch.from_numpy(net1.weight.data().asnumpy().copy()))
        t1.bias.copy_(torch.from_numpy(net1.bias.data().asnumpy().copy()))
        ref1 = t1(torch.from_numpy(x1)).numpy()
    assert_almost_equal(net1(nd.array(x1)).asnumpy(), ref1,
                        rtol=1e-4, atol=1e-5)

    net3 = nn.Conv3D(4, 3, padding=1, in_channels=2)
    net3.initialize()
    x3 = rng.rand(1, 2, 6, 6, 6).astype("float32")
    t3 = torch.nn.Conv3d(2, 4, 3, padding=1)
    with torch.no_grad():
        t3.weight.copy_(torch.from_numpy(net3.weight.data().asnumpy().copy()))
        t3.bias.copy_(torch.from_numpy(net3.bias.data().asnumpy().copy()))
        ref3 = t3(torch.from_numpy(x3)).numpy()
    assert_almost_equal(net3(nd.array(x3)).asnumpy(), ref3,
                        rtol=1e-4, atol=1e-5)

    xp = rng.rand(1, 2, 7, 7).astype("float32")
    out = nn.AvgPool2D(3, strides=2, padding=1)(nd.array(xp)).asnumpy()
    refp = torch.nn.functional.avg_pool2d(torch.from_numpy(xp), 3,
                                          stride=2, padding=1).numpy()
    assert_almost_equal(out, refp, rtol=1e-5)

    xl = rng.rand(1, 2, 8).astype("float32")
    for pv in (1, 2, 3):
        out = nd.Pooling(nd.array(xl), kernel=(2,), stride=(2,),
                         pool_type="lp", p_value=pv).asnumpy()
        refl = torch.nn.functional.lp_pool1d(torch.from_numpy(xl),
                                             pv, 2).numpy()
        assert_almost_equal(out, refl, rtol=1e-4)


def test_lstm_layer_matches_torch():
    """External oracle for the fused lax.scan RNN: a 2-layer gluon LSTM
    with weights copied into torch.nn.LSTM produces the same outputs to
    float32 resolution (gate order i,f,g,o on both sides)."""
    import torch

    mx.random.seed(0)
    T, B, I, H, L = 5, 3, 4, 6, 2
    net = gluon.rnn.LSTM(H, num_layers=L, layout="TNC", input_size=I)
    net.initialize(mx.init.Xavier())
    x_np = np.random.RandomState(0).rand(T, B, I).astype("float32")
    out = net(nd.array(x_np)).asnumpy()

    tl = torch.nn.LSTM(I, H, num_layers=L)
    params = dict(net.collect_params().items())
    with torch.no_grad():
        for layer in range(L):
            def find(sfx, _l=layer):
                return [p for n, p in params.items()
                        if n.endswith(sfx)][_l].data().asnumpy().copy()
            getattr(tl, f"weight_ih_l{layer}").copy_(
                torch.from_numpy(find("i2h_weight")))
            getattr(tl, f"weight_hh_l{layer}").copy_(
                torch.from_numpy(find("h2h_weight")))
            getattr(tl, f"bias_ih_l{layer}").copy_(
                torch.from_numpy(find("i2h_bias")))
            getattr(tl, f"bias_hh_l{layer}").copy_(
                torch.from_numpy(find("h2h_bias")))
        ref, _ = tl(torch.from_numpy(x_np))
    assert_almost_equal(out, ref.numpy(), rtol=1e-5, atol=1e-6)

    # GRU too: same r,z,n order and the cuDNN-style reset-before-matmul
    # candidate gate on both sides
    gnet = gluon.rnn.GRU(H, num_layers=1, layout="TNC", input_size=I)
    gnet.initialize(mx.init.Xavier())
    gout = gnet(nd.array(x_np)).asnumpy()
    tg = torch.nn.GRU(I, H)
    gparams = dict(gnet.collect_params().items())

    def gfind(sfx):
        return [p for n, p in gparams.items()
                if n.endswith(sfx)][0].data().asnumpy().copy()
    with torch.no_grad():
        tg.weight_ih_l0.copy_(torch.from_numpy(gfind("i2h_weight")))
        tg.weight_hh_l0.copy_(torch.from_numpy(gfind("h2h_weight")))
        tg.bias_ih_l0.copy_(torch.from_numpy(gfind("i2h_bias")))
        tg.bias_hh_l0.copy_(torch.from_numpy(gfind("h2h_bias")))
        gref, _ = tg(torch.from_numpy(x_np))
    assert_almost_equal(gout, gref.numpy(), rtol=1e-5, atol=1e-6)


def test_unroll_valid_length():
    """valid_length zeroes outputs past each sequence's length and returns
    LAST-VALID states; the bidirectional form reverses only the valid
    prefix. Oracle: a truncated run of the same cells (ref:
    test_gluon_rnn.py test_rnn_unroll_variant_length)."""
    mx.random.seed(0)
    cell = gluon.rnn.LSTMCell(8)
    cell.initialize()
    x_np = np.random.RandomState(0).rand(2, 5, 4).astype("float32")
    vl = nd.array(np.array([3.0, 5.0]))
    outs, states = cell.unroll(5, nd.array(x_np), layout="NTC",
                               merge_outputs=True, valid_length=vl)
    o = outs.asnumpy()
    assert np.all(o[0, 3:] == 0) and np.any(o[0, 2] != 0)
    cell2 = gluon.rnn.LSTMCell(8, params=cell.params)
    _, st3 = cell2.unroll(3, nd.array(x_np[:, :3]), layout="NTC",
                          merge_outputs=True)
    for s_full, s_trunc in zip(states, st3):
        assert_almost_equal(s_full.asnumpy()[0], s_trunc.asnumpy()[0],
                            rtol=1e-5, atol=1e-6)

    # valid_length 0 (an all-padding row): outputs zeroed, state = the
    # UNTOUCHED begin state, not zeros
    begin = [nd.array(np.full((2, 8), 9.0, "float32")),
             nd.array(np.full((2, 8), 7.0, "float32"))]
    vl0 = nd.array(np.array([0.0, 5.0]))
    outs0, st0 = cell.unroll(5, nd.array(x_np), begin_state=begin,
                             layout="NTC", merge_outputs=True,
                             valid_length=vl0)
    assert np.all(outs0.asnumpy()[0] == 0)
    assert_almost_equal(st0[0].asnumpy()[0], np.full(8, 9.0))
    assert_almost_equal(st0[1].asnumpy()[0], np.full(8, 7.0))

    bi = gluon.rnn.BidirectionalCell(gluon.rnn.LSTMCell(6),
                                     gluon.rnn.LSTMCell(6))
    bi.initialize()
    outs, states = bi.unroll(5, nd.array(x_np), layout="NTC",
                             merge_outputs=True, valid_length=vl)
    o = outs.asnumpy()
    assert np.all(o[0, 3:] == 0)
    bi2 = gluon.rnn.BidirectionalCell(bi._children["l_cell"],
                                      bi._children["r_cell"])
    outs3, st3 = bi2.unroll(3, nd.array(x_np[:, :3]), layout="NTC",
                            merge_outputs=True)
    assert_almost_equal(o[0, :3], outs3.asnumpy()[0], rtol=1e-5, atol=1e-6)
    for s_full, s_trunc in zip(states, st3):
        assert_almost_equal(s_full.asnumpy()[0], s_trunc.asnumpy()[0],
                            rtol=1e-5, atol=1e-6)


def test_sigmoid_bce_pos_weight():
    """pos_weight weights the positive term (both logits and from_sigmoid
    paths), matching torch's binary_cross_entropy_with_logits."""
    import torch

    pred = np.array([[0.5, -0.5, 2.0]], np.float32)
    lbl = np.array([[1.0, 0.0, 1.0]], np.float32)
    pw = np.array([[2.0, 2.0, 0.5]], np.float32)
    ref = torch.nn.functional.binary_cross_entropy_with_logits(
        torch.tensor(pred), torch.tensor(lbl),
        pos_weight=torch.tensor(pw)).item()
    L = gluon.loss.SigmoidBinaryCrossEntropyLoss()
    out = float(L(nd.array(pred), nd.array(lbl), None,
                  nd.array(pw)).asscalar())
    assert abs(out - ref) < 1e-5
    L2 = gluon.loss.SigmoidBinaryCrossEntropyLoss(from_sigmoid=True)
    p = 1 / (1 + np.exp(-pred))
    out2 = float(L2(nd.array(p), nd.array(lbl), None,
                    nd.array(pw)).asscalar())
    assert abs(out2 - ref) < 1e-4


def test_lstm_cell_unroll():
    cell = gluon.rnn.LSTMCell(8)
    cell.initialize()
    x = nd.array(np.random.randn(2, 5, 4).astype("float32"))  # NTC
    outputs, states = cell.unroll(5, x, layout="NTC", merge_outputs=True)
    assert outputs.shape == (2, 5, 8)
    assert len(states) == 2


def test_sequential_rnn_cells():
    stack = gluon.rnn.SequentialRNNCell()
    stack.add(gluon.rnn.LSTMCell(8))
    stack.add(gluon.rnn.LSTMCell(8))
    stack.initialize()
    x = nd.ones((3, 4))
    states = stack.begin_state(batch_size=3)
    out, new_states = stack(x, states)
    assert out.shape == (3, 8)
    assert len(new_states) == 4


def test_dataset_dataloader():
    X = np.random.randn(20, 3).astype("float32")
    Y = np.arange(20).astype("float32")
    ds = gluon.data.ArrayDataset(X, Y)
    assert len(ds) == 20
    loader = gluon.data.DataLoader(ds, batch_size=5)
    batches = list(loader)
    assert len(batches) == 4
    xb, yb = batches[0]
    assert xb.shape == (5, 3)
    loader2 = gluon.data.DataLoader(ds, batch_size=6, last_batch="discard", shuffle=True)
    assert len(list(loader2)) == 3
    loader3 = gluon.data.DataLoader(ds, batch_size=5, num_workers=2)
    assert len(list(loader3)) == 4


def test_dataset_transform():
    ds = gluon.data.SimpleDataset(list(range(10)))
    t = ds.transform(lambda x: x * 2)
    assert t[3] == 6
    tf = gluon.data.ArrayDataset(np.ones((4, 2), "float32"), np.zeros(4, "float32")).transform_first(
        lambda x: x + 1
    )
    x, y = tf[0]
    assert (x == 2).all() and y == 0


def test_vision_transforms():
    from incubator_mxnet_tpu.gluon.data.vision import transforms

    img = nd.array((np.random.rand(8, 8, 3) * 255).astype("uint8"))
    t = transforms.ToTensor()
    out = t(img)
    assert out.shape == (3, 8, 8)
    assert out.asnumpy().max() <= 1.0
    norm = transforms.Normalize(mean=(0.5, 0.5, 0.5), std=(0.5, 0.5, 0.5))
    out2 = norm(out)
    assert out2.asnumpy().min() >= -1.01
    comp = transforms.Compose([transforms.ToTensor(), norm])
    assert comp(img).shape == (3, 8, 8)


def test_synthetic_dataset():
    from incubator_mxnet_tpu.gluon.data.vision import SyntheticImageDataset

    ds = SyntheticImageDataset(num_samples=10, shape=(3, 8, 8), num_classes=4)
    x, y = ds[0]
    assert x.shape == (3, 8, 8) and 0 <= y < 4
    # deterministic
    x2, _ = ds[0]
    assert_almost_equal(x.asnumpy(), x2.asnumpy())


def test_split_and_load():
    data = nd.array(np.arange(24).reshape(8, 3))
    parts = gluon.utils.split_data(data, 4)
    assert len(parts) == 4 and parts[0].shape == (2, 3)
    norm = gluon.utils.clip_global_norm([nd.ones((2,)) * 3, nd.ones((2,)) * 4], 1.0)
    assert abs(norm - np.sqrt(9 * 2 + 16 * 2)) < 1e-4


def test_nhwc_layout_matches_nchw():
    """Channels-last conv/pool/BN path (TPU-native layout) computes the same
    function as the default NCHW path."""
    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.gluon import nn

    rng = np.random.RandomState(0)
    x = rng.rand(2, 3, 16, 16).astype("float32")

    def build(layout):
        mx.random.seed(7)
        net = nn.HybridSequential()
        net.add(nn.Conv2D(8, 3, padding=1, activation="relu", layout=layout))
        net.add(nn.MaxPool2D(2, layout=layout))
        net.add(nn.Conv2D(4, 3, padding=1, layout=layout))
        net.add(nn.BatchNorm(axis=-1 if layout == "NHWC" else 1))
        net.add(nn.GlobalAvgPool2D(layout=layout))
        net.add(nn.Flatten())
        net.initialize(mx.init.Xavier())
        return net

    out_c = build("NCHW")(nd.array(x)).asnumpy()
    out_l = build("NHWC")(nd.array(x.transpose(0, 2, 3, 1))).asnumpy()
    np.testing.assert_allclose(out_c, out_l, rtol=1e-5, atol=1e-6)


def test_nhwc_resnet_trains():
    """A training step through the NHWC ResNet (grads + BN aux updates flow
    through the channels-last path)."""
    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import fused, gluon, nd
    from incubator_mxnet_tpu.gluon.model_zoo import vision

    mx.random.seed(0)
    net = vision.get_resnet(1, 18, classes=10, thumbnail=True, layout="NHWC")
    net.initialize(mx.init.Xavier())
    L = gluon.loss.SoftmaxCrossEntropyLoss()
    opt = mx.optimizer.SGD(learning_rate=0.1, rescale_grad=1.0 / 4)
    step = fused.GluonTrainStep(net, lambda n, x, y: L(n(x), y), opt)
    rng = np.random.RandomState(0)
    x = nd.array(rng.rand(4, 32, 32, 3).astype("float32"))
    y = nd.array(rng.randint(0, 10, 4).astype("float32"))
    l0 = float(step(x, y).asscalar())
    for _ in range(3):
        loss = step(x, y)
    assert float(loss.asscalar()) < l0


def test_train_step_compute_dtype_mixed_precision():
    """compute_dtype='bfloat16': params/optimizer states stay float32
    (master weights), the forward runs in bf16, and a few steps track the
    pure-f32 trajectory to bf16 tolerance (the reference's multi-precision
    SGD semantics, ref: optimizer_op.cc mp_sgd_update)."""
    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import fused, gluon, nd
    from incubator_mxnet_tpu.gluon import nn

    def build(compute_dtype):
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="relu"))
        net.add(nn.Dense(4))
        net.initialize(mx.init.Xavier())
        L = gluon.loss.SoftmaxCrossEntropyLoss()
        opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9)
        return net, fused.GluonTrainStep(net, lambda n, x, y: L(n(x), y), opt,
                                         compute_dtype=compute_dtype)

    rng = np.random.RandomState(1)
    x = nd.array(rng.rand(8, 10).astype("float32"))
    y = nd.array(rng.randint(0, 4, 8).astype("float32"))
    (net_mp, mp), (net_full, full) = build("bfloat16"), build(None)
    # per-param init keys derive from the global auto-naming counters, so
    # two builds differ — pin identical starting weights explicitly
    # (a forward first: Dense defers weight shapes until it sees data)
    net_mp(x), net_full(x)
    for p_src, p_dst in zip(net_mp.collect_params().values(),
                            net_full.collect_params().values()):
        # a real copy: the fused step donates its param buffers, and two
        # nets must not share one donated array
        p_dst.set_data(nd.array(p_src.data().asnumpy()))
    losses_mp, losses_f32 = [], []
    for _ in range(5):
        losses_mp.append(float(mp(x, y).asscalar()))
        losses_f32.append(float(full(x, y).asscalar()))
    # master weights stayed f32
    assert all(str(d.dtype) == "float32" for d in mp._params)
    st = next(s for s, m in zip(mp._states, mp.grad_mask) if m)
    import jax
    assert all(str(leaf.dtype) == "float32"
               for leaf in jax.tree_util.tree_leaves(st))
    # loss is reported in f32 and tracks the full-precision trajectory
    np.testing.assert_allclose(losses_mp, losses_f32, rtol=0.05)
    assert losses_mp[-1] < losses_mp[0]


def test_fused_step_state_checkpoint_resume():
    """save_states/load_states on the fused step: train 2 steps, save,
    rebuild fresh, restore params+states, continue — the resumed
    trajectory equals the uninterrupted one exactly (momentum intact)."""
    import os
    import tempfile
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import fused, gluon, nd
    from incubator_mxnet_tpu.gluon import nn

    def build():
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="relu", in_units=6), nn.Dense(3))
        net.initialize(mx.init.Xavier())
        L = gluon.loss.SoftmaxCrossEntropyLoss()
        opt = mx.optimizer.Adam(learning_rate=0.05)
        return net, fused.GluonTrainStep(net, lambda n, x, y: L(n(x), y),
                                         opt)

    rng = np.random.RandomState(5)
    X = nd.array(rng.rand(8, 6).astype("float32"))
    Y = nd.array(rng.randint(0, 3, 8).astype("float32"))

    net_b, b = build()
    # run 2 steps, checkpoint, resume into a fresh net/step (c), and
    # compare c's continuation against b's own
    [float(b(X, Y).asscalar()) for _ in range(2)]
    with tempfile.TemporaryDirectory() as td:
        fst = os.path.join(td, "opt.states")
        fpar = os.path.join(td, "net.params")
        b.save_states(fst)
        b.sync_params()
        net_b.save_parameters(fpar)

        net_c, c = build()
        net_c(X)  # materialize shapes, then restore
        net_c.load_parameters(fpar)
        c.load_states(fst)  # before the first step: pending path
        l_c = [float(c(X, Y).asscalar()) for _ in range(2)]
    l_cont = [float(b(X, Y).asscalar()) for _ in range(2)]
    np.testing.assert_allclose(l_c, l_cont, rtol=1e-5, atol=1e-6)
    assert c._n == 4 and b._n == 4


def test_accum_steps_matches_big_batch():
    """K accumulated micro-batches == ONE step on the concatenated batch
    (exact for a BN-free f32 net when rescale_grads match: summed
    micro-batch mean-grads at rescale r == big-batch mean-grad at
    rescale K*r). BN aux stats update every micro-batch."""
    import jax
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import fused, gluon, nd
    from incubator_mxnet_tpu.gluon import nn

    def build(rescale):
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="relu", in_units=6), nn.Dense(3))
        net.initialize(mx.init.Xavier())
        L = gluon.loss.SoftmaxCrossEntropyLoss()
        opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9,
                               rescale_grad=rescale)
        return net, fused.GluonTrainStep(net, lambda n, x, y: L(n(x), y),
                                         opt)

    rng = np.random.RandomState(3)
    X = rng.rand(8, 6).astype("float32")
    Y = rng.randint(0, 3, 8).astype("float32")
    net_a, acc = build(0.5)
    net_b, big = build(1.0)
    net_a(nd.array(X)), net_b(nd.array(X))  # materialize deferred shapes
    for p_src, p_dst in zip(net_a.collect_params().values(),
                            net_b.collect_params().values()):
        p_dst.set_data(nd.array(p_src.data().asnumpy()))

    for _ in range(3):
        la = float(acc.accum_steps(
            nd.array(X.reshape(2, 4, 6)),
            nd.array(Y.reshape(2, 4))).asscalar())
        lb = float(big(nd.array(X), nd.array(Y)).asscalar())
        np.testing.assert_allclose(la, lb, rtol=1e-5)
    for da, db in zip(acc._params, big._params):
        np.testing.assert_allclose(np.asarray(da), np.asarray(db),
                                   rtol=1e-5, atol=1e-6)


def test_scan_steps_matches_sequential():
    """K steps in one lax.scan program == K per-dispatch steps
    (params, optimizer states, losses all equal)."""
    from incubator_mxnet_tpu import fused

    def build():
        mx.random.seed(42)
        net = nn.HybridSequential()
        net.add(nn.Dense(8, activation="relu"))
        net.add(nn.Dense(3))
        net.initialize(mx.init.Xavier())
        L = gluon.loss.SoftmaxCrossEntropyLoss()
        opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9)
        return net, fused.GluonTrainStep(net, lambda n, x, y: L(n(x), y), opt)

    rng = np.random.RandomState(0)
    xs = rng.rand(4, 6, 5).astype(np.float32)
    ys = rng.randint(0, 3, size=(4, 6)).astype(np.float32)

    net_a, step_a = build()
    seq_losses = [float(step_a(nd.array(xs[i]), nd.array(ys[i])).asscalar())
                  for i in range(4)]
    step_a.sync_params()
    pa = {k: v.data().asnumpy() for k, v in net_a.collect_params().items()}

    net_b, step_b = build()
    losses = step_b.scan_steps(nd.array(xs), nd.array(ys))
    step_b.sync_params()
    pb = {k: v.data().asnumpy() for k, v in net_b.collect_params().items()}

    np.testing.assert_allclose(losses.asnumpy(), seq_losses, rtol=1e-5)
    # block prefixes differ between the two nets; compare positionally
    for va, vb in zip(pa.values(), pb.values()):
        np.testing.assert_allclose(va, vb, rtol=1e-5, atol=1e-6)
    # continuing with per-step calls after a scan keeps working
    more = step_b(nd.array(xs[0]), nd.array(ys[0]))
    assert np.isfinite(float(more.asscalar()))


def test_scan_steps_bf16_cast_net():
    """scan_steps on a bf16-CAST net (the bench.py bf16 configuration)
    must compile and keep dtypes stable: the f32 lr scalar promotes the
    update math to f32, and without the cast-back the lax.scan carry
    typecheck fails (params/states enter bf16, exit f32). Regression for
    the armed-bench bug found by tools/perf_analysis.py in round 5."""
    from incubator_mxnet_tpu import fused

    mx.random.seed(3)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"))
    net.add(nn.Dense(3))
    net.initialize(mx.init.Xavier())
    net.cast("bfloat16")
    L = gluon.loss.SoftmaxCrossEntropyLoss()
    opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9)
    step = fused.GluonTrainStep(net, lambda n, x, y: L(n(x), y), opt)
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    xs = nd.from_jax(jnp.asarray(rng.rand(3, 6, 5), jnp.bfloat16))
    ys = nd.array(rng.randint(0, 3, size=(3, 6)).astype(np.float32))
    losses = step.scan_steps(xs, ys)
    assert np.all(np.isfinite(losses.asnumpy().astype(np.float32)))
    step.sync_params()
    for _, p in net.collect_params().items():
        assert p.data().dtype == jnp.bfloat16, p
    # loss should drop over a few more scans on the same batches
    first = float(losses.asnumpy().astype(np.float32)[0])
    for _ in range(3):
        losses = step.scan_steps(xs, ys)
    last = float(losses.asnumpy().astype(np.float32)[-1])
    assert last < first


def test_scan_steps_adam_bias_correction():
    """Adam's per-step bias correction t must advance INSIDE the scan —
    each of the K steps sees its own update count."""
    from incubator_mxnet_tpu import fused

    def build():
        mx.random.seed(11)
        net = nn.Dense(2, in_units=3)
        net.initialize(mx.init.Xavier())
        L = gluon.loss.L2Loss()
        opt = mx.optimizer.Adam(learning_rate=0.01)
        return net, fused.GluonTrainStep(net, lambda n, x, y: L(n(x), y), opt)

    rng = np.random.RandomState(5)
    xs = rng.rand(3, 4, 3).astype(np.float32)
    ys = rng.rand(3, 4, 2).astype(np.float32)

    net_a, step_a = build()
    seq = [float(step_a(nd.array(xs[i]), nd.array(ys[i])).asscalar())
           for i in range(3)]
    step_a.sync_params()

    net_b, step_b = build()
    losses = step_b.scan_steps(nd.array(xs), nd.array(ys))
    step_b.sync_params()

    np.testing.assert_allclose(losses.asnumpy(), seq, rtol=1e-5)
    for va, vb in zip(net_a.collect_params().values(),
                      net_b.collect_params().values()):
        np.testing.assert_allclose(va.data().asnumpy(), vb.data().asnumpy(),
                                   rtol=1e-5, atol=1e-7)


def test_groupnorm_reflectionpad_poisson_nll():
    """Round-2 API tail: GroupNorm, ReflectionPad2D, PoissonNLLLoss
    (ref: gluon/nn/basic_layers.py + gluon/loss.py v1.6 surface)."""
    mx.random.seed(0)
    gn = nn.GroupNorm(num_groups=2)
    gn.initialize()
    x = nd.array(np.random.RandomState(0).randn(2, 4, 3, 3).astype("float32"))
    out = gn(x).asnumpy()
    xr = x.asnumpy().reshape(2, 2, 2, 3, 3)
    mean = xr.mean(axis=(2, 3, 4), keepdims=True)
    var = xr.var(axis=(2, 3, 4), keepdims=True)
    ref = ((xr - mean) / np.sqrt(var + 1e-5)).reshape(2, 4, 3, 3)
    assert_almost_equal(out, ref, rtol=1e-4, atol=1e-5)
    # gradient flows through gamma
    with autograd.record():
        loss = (gn(x) ** 2).sum()
    loss.backward()
    assert np.abs(gn.gamma.grad().asnumpy()).sum() > 0

    rp = nn.ReflectionPad2D(1)
    y = rp(nd.array(np.arange(16, dtype="float32").reshape(1, 1, 4, 4)))
    assert_almost_equal(y.asnumpy()[0, 0],
                        np.pad(np.arange(16.0).reshape(4, 4), 1,
                               mode="reflect"))

    L = gluon.loss.PoissonNLLLoss()
    pred = nd.array(np.array([[0.5, -0.2]], "float32"))
    lab = nd.array(np.array([[1.0, 2.0]], "float32"))
    ref_l = np.mean(np.exp([0.5, -0.2])
                    - np.array([1.0, 2.0]) * np.array([0.5, -0.2]))
    assert_almost_equal(float(L(pred, lab).asscalar()), ref_l, rtol=1e-5)
    assert nn.HybridBlock is gluon.HybridBlock


def test_poisson_nll_scalar_reduction_and_frozen_groupnorm():
    # reference-unique reduction: scalar mean over ALL axes
    L = gluon.loss.PoissonNLLLoss()
    pred = nd.array(np.zeros((4, 2), "float32"))
    lab = nd.array(np.ones((4, 2), "float32"))
    out = L(pred, lab)
    assert out.shape == ()
    assert_almost_equal(float(out.asscalar()), 1.0, rtol=1e-6)  # e^0 - 1*0
    # weight positional arg matches reference order: weight first
    L2 = gluon.loss.PoissonNLLLoss(2.0)
    assert_almost_equal(float(L2(pred, lab).asscalar()), 2.0, rtol=1e-6)

    gn = nn.GroupNorm(num_groups=1, scale=False, center=False)
    gn.initialize()
    x = nd.array(np.random.RandomState(1).randn(2, 4, 3).astype("float32"))
    with autograd.record():
        loss = (gn(x) ** 2).sum()
    loss.backward()
    assert gn.gamma.grad_req == "null" and gn.beta.grad_req == "null"


def test_remat_step_matches_plain():
    """GluonTrainStep(remat=True) — jax.checkpoint over the forward (the
    reference's MXNET_BACKWARD_DO_MIRROR / memonger role, the TPU way) —
    must produce the SAME losses and parameters as the plain step:
    rematerialization changes memory/FLOPs, never numerics."""
    from incubator_mxnet_tpu import fused

    rng = np.random.RandomState(0)
    x = nd.array(rng.rand(6, 3, 8, 8).astype(np.float32))
    y = nd.array(rng.randint(0, 5, size=6).astype(np.float32))

    def build(remat):
        mx.random.seed(11)
        net = nn.HybridSequential()
        net.add(nn.Conv2D(4, 3, padding=1, activation="relu"))
        net.add(nn.Flatten())
        net.add(nn.Dense(5))
        net.initialize(mx.init.Xavier())
        net(x)  # materialize deferred params NOW, under the fresh seed
        L = gluon.loss.SoftmaxCrossEntropyLoss()
        opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9)
        return net, fused.GluonTrainStep(net, lambda n, x, y: L(n(x), y),
                                         opt, remat=remat)
    net_a, step_a = build(False)
    net_b, step_b = build(True)
    for _ in range(3):
        la = float(step_a(x, y).asscalar())
        lb = float(step_b(x, y).asscalar())
        np.testing.assert_allclose(la, lb, rtol=1e-6)
    step_a.sync_params()
    step_b.sync_params()
    for (_, pa), (_, pb) in zip(net_a.collect_params().items(),
                                net_b.collect_params().items()):
        np.testing.assert_allclose(pa.data().asnumpy(),
                                   pb.data().asnumpy(), rtol=1e-6,
                                   atol=1e-7)
    # remat composes with scan bulking AND matches the plain scan
    xs = nd.array(rng.rand(2, 6, 3, 8, 8).astype(np.float32))
    ys = nd.array(rng.randint(0, 5, size=(2, 6)).astype(np.float32))
    l_scan_b = step_b.scan_steps(xs, ys).asnumpy()
    l_scan_a = step_a.scan_steps(xs, ys).asnumpy()
    np.testing.assert_allclose(l_scan_b, l_scan_a, rtol=1e-6, atol=1e-7)
    # and with accum_steps (which uses the barrier-free checkpoint)
    a_acc = float(step_a.accum_steps(xs, ys).asscalar())
    b_acc = float(step_b.accum_steps(xs, ys).asscalar())
    np.testing.assert_allclose(a_acc, b_acc, rtol=1e-6)


@pytest.mark.parametrize("name,shape", [
    ("resnet18_v1", (2, 3, 32, 32)),
    ("resnet18_v2", (2, 3, 32, 32)),
    ("vgg11_bn", (2, 3, 32, 32)),
    ("squeezenet1_1", (2, 3, 64, 64)),
    ("mobilenet0_25", (2, 3, 32, 32)),
    ("mobilenet_v2_0_25", (2, 3, 32, 32)),
])
def test_zoo_bf16_forward_tracks_f32(name, shape):
    """Every zoo family forwards in pure bf16 (the TPU headline dtype)
    with outputs finite and tracking the f32 forward — guards the
    net.cast('bfloat16') path across architectures (BN stats promote to
    f32 internally, ops/nn.py)."""
    import jax.numpy as jnp

    from incubator_mxnet_tpu.gluon.model_zoo import vision

    mx.random.seed(0)
    net = getattr(vision, name)(classes=10)
    net.initialize(mx.init.Xavier())
    x32 = np.random.RandomState(0).rand(*shape).astype("float32")
    ref = net(nd.array(x32)).asnumpy()
    net.cast("bfloat16")
    out = net(nd.from_jax(jnp.asarray(x32, jnp.bfloat16)))
    assert out.dtype == jnp.bfloat16
    o = out.asnumpy().astype("float32")
    assert np.all(np.isfinite(o))
    # bf16 has ~3 decimal digits: elementwise agreement at bf16
    # resolution; overall correlation only when the logits carry signal
    # (the mobilenets emit near-zero logits at init, where cosine is
    # bf16 noise over bf16 noise)
    np.testing.assert_allclose(o, ref, rtol=0.1, atol=0.08)
    nrm = np.linalg.norm(ref)
    if nrm > 1e-2:
        cos = float((o * ref).sum() / (np.linalg.norm(o) * nrm + 1e-12))
        assert cos > 0.995, (cos, nrm)
