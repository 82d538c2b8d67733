"""The dense architecture's per-step work counts against hand counts at
both configurations, and the chip's peaks."""
import json

import pytest

import harness
import modelspec
import work
from conftest import BENCH

dense = modelspec.arch("dense")


def spec(name):
    return harness.load_config(BENCH, name)[2]


def test_layer_params_by_hand():
    # minicpm-2b: 4 x 2304^2 attention + 3 x 2304 x 5760 MLP = 61.0M
    assert dense.layer_params(spec("minicpm-2b")) == 4 * 2304 * 2304 + 3 * 2304 * 5760 == 61_046_784
    # mistral: q,o 4096^2, k,v 4096 x 1024, MLP 3 x 4096 x 14336 = 218.1M
    m = spec("mistral-7b-v0.3-16l")
    assert dense.layer_params(m) == 2 * 4096**2 + 2 * 4096 * 1024 + 3 * 4096 * 14336 == 218_103_808


@pytest.mark.parametrize("name, weights, kv", [
    # tied head: the embedding read once as the head, 5,449,761,792 B in all
    ("minicpm-2b", 2 * (40 * (61_046_784 + 2 * 2304) + 122753 * 2304 + 2304), 2 * 40 * 36 * 64 * 2),
    # untied: 16 layers and the head; the embedding's rows are read per token
    ("mistral-7b-v0.3-16l", 2 * (16 * (218_103_808 + 2 * 4096) + 32768 * 4096 + 4096), 2 * 16 * 8 * 128 * 2),
])
def test_bytes_by_hand(name, weights, kv):
    s = spec(name)
    assert dense.weight_bytes(s) == weights
    assert dense.kv_bytes_per_token(s) == kv


def test_decode_counts_live_lanes_only():
    s = spec("minicpm-2b")
    f1, b1 = dense.decode_work(s, [99])
    f2, b2 = dense.decode_work(s, [99, 9])
    per_token = 2 * (40 * 61_046_784 + 2304 * 122753)
    assert f1 == per_token + 4 * 40 * 36 * 64 * 100
    assert f2 - f1 == per_token + 4 * 40 * 36 * 64 * 10
    kv = dense.kv_bytes_per_token(s)
    assert b1 == dense.weight_bytes(s) + 99 * kv + kv + 8 + 2304 * 2 + 122753 * 4
    assert b2 - b1 == 9 * kv + kv + 8 + 2304 * 2 + 122753 * 4


def test_chunk_counts_valid_tokens_and_context():
    s = spec("mistral-7b-v0.3-16l")
    f, b = dense.chunk_work(s, 256, 100)
    attn = 4 * 16 * 32 * 128 * sum(p + 1 for p in range(256, 356))
    assert f == 2 * 100 * 16 * 218_103_808 + 2 * 4096 * 32768 + attn
    kv = dense.kv_bytes_per_token(s)
    assert b == dense.weight_bytes(s) + 356 * kv + 100 * 4 + 100 * 4096 * 2 + 32768 * 4


def test_least_time_names_its_bound():
    p = work.peaks("TPU v5 lite")
    assert p == {"flops": 197e12, "bytes": 819e9}
    s = spec("minicpm-2b")
    t, bound = work.least_time(*dense.decode_work(s, [100] * 4), p)
    assert bound == "memory" and t > dense.weight_bytes(s) / 819e9
    # a full 256-token chunk sits just past the ridge on the Mistral cut
    # (1.79 TFLOP against 7.25 GB), and just short of it on minicpm-2b,
    # whose tied head is read whole for one row of logits
    assert work.least_time(*dense.chunk_work(spec("mistral-7b-v0.3-16l"), 0, 256), p)[1] == "compute"
    assert work.least_time(*dense.chunk_work(s, 0, 256), p)[1] == "memory"


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        work.peaks("cpu")
    with open(BENCH / "peaks.json") as f:
        assert "TPU v5 lite" in json.load(f)["devices"]
