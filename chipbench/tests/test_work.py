"""Work counts against hand-worked shapes of both configurations.

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests
"""

import json
import pathlib

import pytest

from chipbench import work

CONF = pathlib.Path(__file__).resolve().parents[1] / "configs"


def cfg(name):
    return json.loads((CONF / f"{name}.json").read_text())


def test_qwen_paged_attention_one_sequence():
    # 28 layers x 4 x 16 heads x 128 x 1000 positions
    f, b = work.paged_attention(cfg("qwen3-1.7b"), [1000])
    assert f == 229_376_000
    # 28 x (1000 x 8 kv heads x 128 x K,V x 2 B + q,out 2 x 16 x 128 x 2 B)
    assert b == 28 * (4_096_000 + 8_192) == 114_917_376


def test_qwen32b_stage_paged_attention_sums_sequences():
    f, b = work.paged_attention(cfg("qwen3-32b-8l"), [10, 20])
    assert f == 8 * 4 * 64 * 128 * 30
    assert b == 8 * (30 * 8 * 128 * 4 + 2 * 2 * 64 * 128 * 2)


def test_page_move_bytes():
    # read + write, K and V, every layer, one (8, 16, 128) bf16 page
    assert work.page_move(cfg("qwen3-1.7b"), 1, 16) == (0.0, 3_670_016.0)
    assert work.page_move(cfg("qwen3-32b-8l"), 3, 16) == (
        0.0, 3 * 1_048_576.0)


def test_layer_weights_and_parameter_count():
    assert work.layer_weights(cfg("qwen3-1.7b")) == 50_331_648
    q32 = cfg("qwen3-32b-8l")
    # q and o 5120 x 64 x 128, k and v 5120 x 8 x 128, MLP 3 x 5120 x 25600
    assert work.layer_weights(q32) == 487_587_840
    # 8 layers + untied embedding and head + norm scales (two per layer,
    # q and k per head, the final one): the 5,456,616,448 parameters the
    # configuration file states
    norms = 8 * (2 * 5120 + 2 * 128) + 5120
    assert (8 * 487_587_840 + 2 * 151936 * 5120 + norms
            == 5_456_616_448)


def test_model_flops_decode_and_prefill():
    c = cfg("qwen3-1.7b")
    # one decode token at attention length 1000: matrices + head + attention
    assert work.model_flops(c, [], [1000]) == (
        2 * 28 * 50_331_648 + 2 * 2048 * 151936 + 229_376_000)
    # a 3-token prefill: 3 tokens through the layers, causal pairs 1+2+3,
    # one head row
    assert work.model_flops(c, [3], []) == (
        3 * 2 * 28 * 50_331_648 + 28 * 4 * 16 * 128 * 6 + 2 * 2048 * 151936)


def test_roofline_share_names_its_bound():
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    share, bound = work.roofline_share(1e12, 1e9, 1.0, peak)
    assert bound == "compute" and share == pytest.approx(100 / 197)
    share, bound = work.roofline_share(0.0, 819e9, 2.0, peak)
    assert bound == "memory" and share == pytest.approx(50.0)
