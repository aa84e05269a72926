"""Operation and byte counts against the program's parameter count and the
hand counts written in ``bench/flops.py``."""
import pytest

from bench import flops, lm

CONFIGS = ["qwen1.5-0.5b", "qwen2.5-3b"]


@pytest.mark.parametrize("name", CONFIGS)
def test_matrix_params_match_the_hand_count(name):
    m = lm.dims(lm.load_config(name))
    assert flops.matrix_params(m) == flops.HAND_MATRIX_PARAMS[name]


@pytest.mark.parametrize("name", CONFIGS)
def test_param_count_matches_the_program(name):
    from repro.models.registry import build_model

    cfg = lm.load_config(name)
    arch = lm.arch(name, cfg)
    padded_rows = arch.padded_vocab - arch.vocab_size
    assert lm.param_count(cfg) + padded_rows * arch.d_model \
        == build_model(arch).param_count()
    if name in flops.HAND_PARAMS:
        assert lm.param_count(cfg) == flops.HAND_PARAMS[name]


def test_decode_prefill_and_train_agree_on_matrix_work():
    m = lm.dims(lm.load_config("qwen1.5-0.5b"))
    mm = 2.0 * flops.matrix_params(m)
    # one token: matrix work plus attention over itself
    assert flops.decode_flops(m, 0) == mm + flops.attention_flops(m, 1)
    # a prompt of one token is one decode step of position 0
    assert flops.prefill_flops(m, 1) == pytest.approx(
        flops.decode_flops(m, 0))
    # prefill pays the head once, not per token
    p = 512
    per_layer = 2.0 * m["layers"] * flops.layer_matrix_params(m)
    assert flops.prefill_flops(m, p) == pytest.approx(
        per_layer * p + 2.0 * flops.head_params(m)
        + flops.attention_flops(m, p * (p + 1) / 2))
    # training: three forward passes of the matrices, causal attention
    s = 2048
    assert flops.train_flops_per_token(m, s) == pytest.approx(
        3 * mm + 3 * flops.attention_flops(m, (s + 1) / 2))
    # about 6 N per token at short sequences
    assert flops.train_flops_per_token(m, 1) == pytest.approx(
        6 * flops.matrix_params(m), rel=0.01)


def test_decode_bytes_count_weights_and_positions_in_use():
    m = lm.dims(lm.load_config("qwen1.5-0.5b"))
    assert flops.kv_bytes_per_position(m) == 24 * 2 * 16 * 64 * 2
    w = lm.param_count(lm.load_config("qwen1.5-0.5b"))
    assert flops.decode_bytes(m, w, []) == 2 * w
    assert flops.decode_bytes(m, w, [9, 19]) == 2 * w + 30 * 98304
