"""Tool tests: native im2rec packer (ref: tools/im2rec.cc + test pattern of
tools/im2rec.py usage in example/image-classification)."""
import os
import subprocess
import sys

import numpy as np
import pytest

cv2 = pytest.importorskip("cv2")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_native_im2rec_packs_readable_shard(tmp_path):
    from incubator_mxnet_tpu import io, recordio

    for i in range(8):
        cv2.imwrite(str(tmp_path / f"img{i}.jpg"),
                    np.random.randint(0, 255, (50, 70, 3), np.uint8))
    lst = tmp_path / "data.lst"
    with open(lst, "w") as f:
        for i in range(8):
            f.write(f"{i}\t{i % 2}\timg{i}.jpg\n")

    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "im2rec.py"),
         str(tmp_path / "data"), str(tmp_path), "--native", "--resize", "32"],
        capture_output=True, text=True, timeout=180)
    assert out.returncode == 0, out.stderr
    rec_path = str(tmp_path / "data.rec")
    assert os.path.exists(rec_path)

    r = recordio.MXRecordIO(rec_path, "r")
    labels, n = [], 0
    while True:
        s = r.read()
        if s is None:
            break
        hdr, _ = recordio.unpack(s)
        img = recordio.unpack_img(s)[1]
        assert min(img.shape[:2]) == 32  # short-edge resize
        labels.append(float(hdr.label))
        n += 1
    assert n == 8 and labels == [i % 2 for i in range(8)]

    it = io.ImageRecordIter(path_imgrec=rec_path, data_shape=(3, 28, 28),
                            batch_size=4, preprocess_threads=2)
    b = next(iter(it))
    assert b.data[0].shape == (4, 3, 28, 28)
    it.close()


def test_native_im2rec_writes_idx(tmp_path):
    from incubator_mxnet_tpu import recordio

    for i in range(4):
        cv2.imwrite(str(tmp_path / f"p{i}.jpg"),
                    np.random.randint(0, 255, (40, 40, 3), np.uint8))
    with open(tmp_path / "d.lst", "w") as f:
        for i in range(4):
            f.write(f"{i}\t{float(i)}\tp{i}.jpg\n")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "im2rec.py"),
         str(tmp_path / "d"), str(tmp_path), "--native"],
        capture_output=True, text=True, timeout=180, cwd=str(tmp_path))
    assert out.returncode == 0, out.stderr
    assert os.path.exists(tmp_path / "d.idx")
    rec = recordio.MXIndexedRecordIO(str(tmp_path / "d.idx"),
                                     str(tmp_path / "d.rec"), "r")
    hdr, _ = recordio.unpack(rec.read_idx(2))
    assert float(hdr.label) == 2.0


def test_parse_log_metrics_and_speed():
    """(ref: tools/parse_log.py — epoch metric extraction)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import parse_log

    lines = [
        "Epoch[0] Batch [20] Speed: 1500.0 samples/sec accuracy=0.5",
        "Epoch[0] Batch [40] Speed: 1700.0 samples/sec accuracy=0.6",
        "Epoch[0] Train-accuracy=0.62",
        "Epoch[0] Time cost=10.5",
        "Epoch[0] Validation-accuracy=0.60",
        "Epoch[1] Train-accuracy=0.81",
    ]
    rows = parse_log.parse(lines)
    assert rows[0]["speed"] == 1600.0
    assert rows[0]["train-accuracy"] == 0.62
    assert rows[0]["validation-accuracy"] == 0.60
    assert rows[0]["time-cost"] == 10.5
    assert rows[1]["train-accuracy"] == 0.81
    md = parse_log.render(rows, "markdown")
    assert md.splitlines()[0].startswith("| epoch |")
    csv = parse_log.render(rows, "csv")
    assert csv.splitlines()[0].startswith("epoch,")


def test_diagnose_runs_clean():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "diagnose.py")],
        capture_output=True, text=True, timeout=240, env=env)
    assert out.returncode == 0, out.stderr[-1500:]
    assert "Python Info" in out.stdout
    assert "incubator_mxnet_tpu Info" in out.stdout
    assert "features" in out.stdout


def test_caffe_converter_cli_saves_checkpoint(tmp_path):
    """tools/caffe_converter.py CLI: prototxt+caffemodel -> checkpoint."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import caffe_converter as cc

    prototxt = tmp_path / "deploy.prototxt"
    prototxt.write_text("""
input: "data"
input_dim: 1
input_dim: 2
input_dim: 4
input_dim: 4
layer {
  name: "fc" type: "InnerProduct" bottom: "data" top: "fc"
  inner_product_param { num_output: 3 }
}
""")
    w = np.random.RandomState(0).randn(3, 32).astype(np.float32)
    blob = cc.BlobProto(data=[float(v) for v in w.ravel()],
                        shape=cc.BlobShape(dim=[3, 32]))
    net = cc.CaffeNet(layer=[cc.CaffeLayer(name="fc", type="InnerProduct",
                                           blobs=[blob])])
    cm = tmp_path / "net.caffemodel"
    cm.write_bytes(net.to_bytes())
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "caffe_converter.py"),
         str(prototxt), str(cm), str(tmp_path / "conv")],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-1500:]
    assert (tmp_path / "conv-symbol.json").exists()
    assert (tmp_path / "conv-0000.params").exists()


def test_bench_transformer_cli_emits_json(tmp_path):
    """tools/bench_transformer.py prints one parseable JSON line."""
    import json

    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_transformer.py"),
         "--d-model", "32", "--n-layers", "1", "--d-ff", "64",
         "--vocab", "128", "--batch", "2", "--seq", "16",
         "--iters", "2", "--warmup", "1", "--decode-steps", "8"],
        capture_output=True, text=True, timeout=420, env=env)
    assert out.returncode == 0, out.stderr[-1500:]
    d = json.loads(out.stdout.strip().splitlines()[-1])
    assert d["metric"] == "transformer_train_tokens_per_sec"
    assert d["value"] > 0
    assert d["decode_tokens_per_sec"] > 0
    assert d["prefill_tokens_per_sec"] > 0


def test_local_launcher_refuses_to_share_tpu_chips(monkeypatch, capsys):
    """A TPU chip belongs to one process: on a host with chips the local
    launcher must not start N ranks that would each open all of them —
    unless the job is pinned to the CPU backend."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "launch_tool", os.path.join(REPO, "tools", "launch.py"))
    launch = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(launch)
    monkeypatch.setattr(launch, "_local_tpu_chips", lambda: 4)
    monkeypatch.setattr(sys, "argv", ["launch.py", "-n", "2", "--",
                                      sys.executable, "-c", "pass"])
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(SystemExit) as e:
        launch.main()
    assert "4 TPU chip(s)" in str(e.value.code)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    with pytest.raises(SystemExit) as e:
        launch.main()
    assert e.value.code == 0
