"""Example smoke tests: tree, CRF and tagging models over sequences.

One file per family of examples, none over ~300 s alone: see
tests/common.py:run_example."""
from common import run_example as _run


def test_tree_lstm():
    log = _run("tree_lstm.py", "--epochs", "4", "--train-trees", "120",
               timeout=520)
    assert "tree_lstm OK" in log


def test_lstm_crf():
    log = _run("lstm_crf.py", "--epochs", "8", "--samples", "192",
               timeout=520)
    assert "lstm_crf OK" in log


def test_ner_bilstm():
    log = _run("ner_bilstm.py", "--steps", "200")
    assert "ner_bilstm OK" in log


def test_bi_lstm_sort():
    log = _run("bi_lstm_sort.py", "--steps", "350", timeout=500)
    assert "bi_lstm_sort OK" in log
