"""Converter tails: caffe_translator (training-script emission) and the
CoreML converter (ref: tools/caffe_translator/ and tools/coreml/).

The translator's output is EXECUTED: a bundled LeNet train_val.prototxt +
solver must yield a script that trains (loss drops) on the synthetic data
stub. The CoreML converter's layer specs are validated structurally;
.mlmodel serialization is gated on coremltools exactly like the
reference's converter, and must fail with a clear message without it.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

LENET_PROTOTXT = """
name: "LeNet"
layer {
  name: "data"  type: "Data"  top: "data"  top: "label"
  include { phase: TRAIN }
  data_param { source: "train_lmdb" batch_size: 16 }
}
layer {
  name: "data"  type: "Data"  top: "data"  top: "label"
  include { phase: TEST }
  data_param { source: "test_lmdb" batch_size: 100 }
}
layer {
  name: "conv1" type: "Convolution" bottom: "data" top: "conv1"
  convolution_param { num_output: 8 kernel_size: 5 stride: 1 }
}
layer { name: "relu1" type: "ReLU" bottom: "conv1" top: "conv1" }
layer {
  name: "pool1" type: "Pooling" bottom: "conv1" top: "pool1"
  pooling_param { pool: MAX kernel_size: 2 stride: 2 }
}
layer {
  name: "ip1" type: "InnerProduct" bottom: "pool1" top: "ip1"
  inner_product_param { num_output: 64 }
}
layer { name: "relu2" type: "ReLU" bottom: "ip1" top: "ip1" }
layer { name: "drop1" type: "Dropout" bottom: "ip1" top: "ip1"
  dropout_param { dropout_ratio: 0.25 } }
layer {
  name: "ip2" type: "InnerProduct" bottom: "ip1" top: "ip2"
  inner_product_param { num_output: 10 }
}
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip2" bottom: "label" }
layer { name: "acc" type: "Accuracy" bottom: "ip2" bottom: "label"
  include { phase: TEST } }
"""

SOLVER = """
base_lr: 0.05
momentum: 0.9
weight_decay: 0.0005
lr_policy: "step"
stepsize: 50
gamma: 0.5
max_iter: 60
type: "SGD"
"""


@pytest.fixture(scope="module")
def translated(tmp_path_factory):
    d = tmp_path_factory.mktemp("caffe_translate")
    (d / "train_val.prototxt").write_text(LENET_PROTOTXT)
    (d / "solver.prototxt").write_text(SOLVER)
    out = d / "train_translated.py"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "caffe_translator.py"),
         "--training-prototxt", str(d / "train_val.prototxt"),
         "--solver", str(d / "solver.prototxt"),
         "--output-file", str(out)],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-1500:]
    return out


def test_translator_emits_expected_structure(translated):
    src = translated.read_text()
    assert "nn.Conv2D(8, 5" in src
    assert "nn.MaxPool2D(pool_size=2, strides=2" in src
    assert "nn.Dense(64)" in src
    assert "nn.Dropout(0.25)" in src
    assert "nn.Dense(10)" in src
    assert "momentum=0.9" in src and "wd=0.0005" in src
    assert "FactorScheduler(step=50, factor=0.5)" in src
    # TEST-phase layers must not leak into the training net
    assert src.count("nn.Conv2D") == 1


def test_translated_script_trains(translated):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, str(translated), "--max-iter", "60"],
                       capture_output=True, text=True, timeout=420, env=env)
    assert r.returncode == 0, (r.stdout[-800:], r.stderr[-1500:])
    assert "trained:" in r.stdout
    # loss must actually drop on the stub data
    line = [l for l in r.stdout.splitlines() if "trained:" in l][0]
    first, last = line.split("trained:")[1].split("->")
    assert float(last) < float(first), line


def _lenet():
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon import nn

    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Conv2D(8, 5, activation="relu"))
        net.add(nn.MaxPool2D(2, 2))
        net.add(nn.BatchNorm())
        net.add(nn.Flatten())
        net.add(nn.Dense(32, activation="relu"))
        net.add(nn.Dropout(0.25))
        net.add(nn.Dense(10))
    net.initialize(mx.init.Xavier())
    from incubator_mxnet_tpu import nd

    net(nd.array(np.zeros((1, 1, 20, 20), np.float32)))  # shape inference
    return net


def test_coreml_convert_structure():
    from coreml import convert

    net = _lenet()
    spec = convert(net, (1, 20, 20))
    assert spec.validate()
    kinds = [l["type"] for l in spec.layers]
    assert kinds == ["convolution", "activation", "pooling", "batchnorm",
                     "flatten", "innerProduct", "activation",
                     "innerProduct"]  # dropout dropped for inference
    conv = spec.layers[0]
    assert conv["weights"].shape == (5, 5, 1, 8)  # CoreML (kh,kw,in,out)
    ip = [l for l in spec.layers if l["type"] == "innerProduct"][0]
    assert ip["outputChannels"] == 32
    # blob chaining data -> ... -> output
    assert spec.layers[0]["input"] == "data"
    assert spec.layers[-1]["output"] == "output"


def test_coreml_save_gated_on_coremltools(tmp_path):
    from coreml import convert

    net = _lenet()
    spec = convert(net, (1, 20, 20))
    try:
        spec.save(str(tmp_path / "m.mlmodel"))
        # coremltools installed in this environment: file must exist
        assert os.path.exists(tmp_path / "m.mlmodel")
    except ImportError as e:
        # without coremltools: a clear actionable error, not a bare
        # ModuleNotFoundError from deep inside
        assert "coremltools is required" in str(e)


def test_coreml_unsupported_block_is_loud():
    from coreml import convert

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.gluon import nn

    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Embedding(10, 4))
    net.initialize(mx.init.Xavier())
    net(nd.array(np.zeros((1, 3), np.float32)))
    with pytest.raises(ValueError, match="no CoreML translator"):
        convert(net, (3,))
