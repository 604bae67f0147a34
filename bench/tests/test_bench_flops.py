"""The operation and byte counts against counts made by hand."""
import json

import pytest

from bench import common, flops

CNN = json.loads((common.BENCH / "configs" / "cnn-mnist.json").read_text())
QWEN = json.loads((common.BENCH / "configs" / "qwen3-1.7b.json").read_text())


def test_cnn_layers_by_hand():
    f = flops.cnn_layer_flops(CNN)
    # conv0: 28x28 outputs x 16 channels x 3x3x1 taps; conv1 at 14x14:
    # 32 channels x 3x3x16 taps; fc1 7*7*32 -> 128; fc2 128 -> 10
    assert f["conv0"] == 2 * 28 * 28 * 16 * 9
    assert f["conv1"] == 2 * 14 * 14 * 32 * 9 * 16
    assert f["fc1"] == 2 * 1568 * 128
    assert f["fc2"] == 2 * 128 * 10
    fwd = 225_792 + 1_806_336 + 401_408 + 2_560
    assert flops.cnn_forward_flops(CNN) == fwd
    assert flops.cnn_train_flops(CNN) == 3 * fwd - 225_792


def test_cnn_param_count_is_the_papers():
    # 3*3*16+16, 3*3*16*32+32, 1568*128+128, 128*10+10
    assert flops.cnn_param_count(CNN) == 160 + 4_640 + 200_832 + 1_290
    assert abs(flops.cnn_param_count(CNN) - 2.07e5) < 1e3


def test_pearson_by_hand():
    f, b = flops.pearson(100, 1000)
    assert f == 2 * 100 * 100 * 1000 + 100 * 1000
    assert b == 4 * 100 * 1000 + 4 * (100 * 100 + 100)


def test_paged_decode_by_hand():
    # two rows attending over 10 and 30 positions, 16 query heads of 128
    # over 8 key/value heads, bf16
    f, b = flops.paged_decode([10, 30], 16, 8, 128)
    assert f == 4 * 16 * 128 * 40
    assert b == 2 * (2 * 40 * 8 * 128 + 2 * 2 * 16 * 128)


def test_flash_prefill_by_hand():
    f, b = flops.flash_prefill(4, 2, 1, 8)
    # causal: 1 + 2 + 3 + 4 = 10 query-key pairs per head
    assert f == 4 * 2 * 8 * 10
    assert b == 2 * 4 * 8 * (2 * 2 + 2 * 1)


def test_qwen3_step_by_hand():
    layers, head = flops.dense_matmul_params(QWEN)
    per_layer = (2048 * 2048 + 2 * 2048 * 1024 + 2048 * 2048
                 + 3 * 2048 * 6144)
    assert layers == 28 * per_layer
    assert head == 2048 * 151936
    # about 1.4e9 matmul weights in the layers, 0.31e9 in the head
    assert 1.40e9 < layers < 1.42e9
    one = flops.serve_flops(QWEN, [], [100])
    assert one == pytest.approx(2 * (layers + head) + 28 * 4 * 16 * 128 * 100)
    pre = flops.serve_flops(QWEN, [3], [])
    assert pre == pytest.approx(2 * layers * 3 + 2 * head
                                + 28 * 4 * 16 * 128 * 6)
