"""Example smoke tests: language models, bucketed RNNs, seq2seq, speech, text.

One file per family of examples, none over ~300 s alone: see
tests/common.py:run_example."""
from common import run_example as _run


def test_char_rnn():
    log = _run("char_rnn.py", "--steps", "60", "--hidden", "64",
               "--seq-len", "32", "--batch-size", "16", timeout=520)
    assert "char_rnn OK" in log


def test_word_language_model():
    log = _run("word_language_model.py", "--epochs", "2",
               "--batch-size", "64", timeout=600)
    assert "word_language_model OK" in log


def test_rnn_bucketing_stacked_cell():
    log = _run("rnn_bucketing.py", "--num-epochs", "1", "--batch-size", "16",
               "--num-hidden", "16", "--num-embed", "8", "--sentences", "300",
               "--cell", "stacked", timeout=520)
    assert "rnn_bucketing OK" in log


def test_rnn_bucketing_fused_cell():
    log = _run("rnn_bucketing.py", "--num-epochs", "1", "--batch-size", "16",
               "--num-hidden", "16", "--num-embed", "8", "--sentences", "300",
               "--cell", "fused", timeout=520)
    assert "rnn_bucketing OK" in log


def test_seq2seq_attention():
    log = _run("seq2seq_attention.py", "--steps", "400", timeout=520)
    assert "seq2seq_attention OK" in log


def test_nce_lm():
    log = _run("nce_lm.py", "--vocab", "200", "--steps", "400", timeout=500)
    assert "nce_lm OK" in log


def test_transformer_generate():
    log = _run("transformer_generate.py", "--steps", "120", timeout=520)
    assert "transformer_generate OK" in log


def test_cnn_text_classification():
    log = _run("cnn_text_classification.py", "--steps", "300")
    assert "cnn_text_classification OK" in log


def test_speech_ctc():
    log = _run("speech_ctc.py", "--steps", "200")
    assert "speech_ctc OK" in log
