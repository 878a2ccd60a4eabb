"""perf/work.py gives the counts the issue states, from the configuration files alone."""

import json
import os

import pytest

from perf import work

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_mistral_counts():
    cfg = config("mistral-7b-v0.3-l16")
    assert work.decoder_layer_params(cfg) == 218_103_808  # 16 x 218.1 M
    assert cfg["hidden_size"] * cfg["vocab_size"] == 134_217_728  # 2 x 134.2 M: embedding and head
    assert round(work.decoder_params(cfg) / 1e9, 2) == 3.76
    assert round(work.decoder_params(cfg) * 2 / 1e9, 1) == 7.5  # bf16 bytes
    assert work.kv_bytes_per_token(cfg) == 64 * 1024  # 2 x 8 x 128 x 2 B x 16 layers
    full = dict(cfg, num_hidden_layers=32)
    assert round(work.decoder_params(full) / 1e9, 2) == 7.25  # the published model


def test_bert_counts():
    cfg = config("bert-base-uncased")
    assert round(work.encoder_params(cfg, published_biases=True) / 1e6, 1) == 109.5  # bert-base-uncased
    assert work.encoder_layer_params(cfg) == 7_077_888
    # 6 FLOPs a matrix parameter and position, plus attention over 128 keys
    assert work.encoder_train_flops_per_token(cfg, 128) == 6 * 12 * 7_077_888 + 12 * 12 * 768 * 128


def test_prefill_and_decode_arithmetic():
    cfg = config("mistral-7b-v0.3-l16")
    whole = work.prefill_flops(cfg, 1000)
    assert work.prefill_flops(cfg, 1000, 1000) == 0.0
    assert work.prefill_flops(cfg, 1000, 600) < whole
    by_token = sum(work.decoder_token_flops(cfg, p + 1, head=False) for p in range(1000)) + 2.0 * 4096 * 32768
    assert whole == pytest.approx(by_token, rel=1e-12)
    peak = work.peaks("TPU v5 lite")
    least, bound = work.decode_least_seconds(cfg, peak, steps=1, token_contexts=[400] * 32)
    assert bound == "memory"
    weights = work.decoder_weight_bytes_per_step(cfg)
    assert weights == (16 * 218_103_808 + 134_217_728) * 2
    assert least == pytest.approx((weights + 32 * 401 * 65536) / 819e9)
    with pytest.raises(KeyError):
        work.peaks("some other chip")
