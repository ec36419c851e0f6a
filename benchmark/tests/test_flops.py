"""flops.py against counts made by hand from the published sizes."""

import json
import os

import flops
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _sizes(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_mistral_by_hand():
    sizes = _sizes("mistral-7b")
    assert sizes["num_hidden_layers"] == 2 and sizes["sequence"] == 2048
    # multiply-adds a token crosses in one layer
    projections = 4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096   # q, k+v, o
    feed_forward = 3 * 4096 * 14336
    # causal: a query sees (2048 + 1) / 2 keys on average; QK^T and AV,
    # 32 heads x 128
    attention = 2 * 32 * 128 * 1024.5
    head = 4096 * 32000
    forward = 2 * (2 * (projections + feed_forward + attention) + head)
    assert forward == pytest.approx(1_168_130_048.0)   # 2 x 584,065,024
    assert flops.mistral(sizes, 2048) == pytest.approx(3 * forward)


def test_gpt2_by_hand():
    sizes = _sizes("gpt2-xl")
    layers = sizes["n_layer"]
    assert sizes["sequence"] == 1024
    matmuls = 1600 * 4800 + 1600 * 1600 + 2 * 1600 * 6400   # qkv, proj, mlp
    attention = 2 * 25 * 64 * 512.5
    head = 1600 * 50257     # tied to the embedding, still a matmul
    forward = 2 * (layers * (matmuls + attention) + head)
    assert flops.gpt2(sizes, 1024) == pytest.approx(3 * forward)


def test_sliding_window_caps_the_keys():
    sizes = dict(_sizes("mistral-7b"), sliding_window=4)
    full = dict(sizes, sliding_window=None)
    # seq 8, window 4: rows see 1,2,3,4,4,4,4,4 keys = 26/8 on average
    per_layer = flops._attention_flops(32, 128, 8, 4)
    assert per_layer == pytest.approx(4 * 32 * 128 * 26 / 8)
    assert flops.mistral(sizes, 8) < flops.mistral(full, 8)
