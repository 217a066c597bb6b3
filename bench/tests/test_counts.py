"""FLOP and byte counts against hand-worked values for both configurations."""

import pytest

from lib import common, counts
from reference import gpt2

MEDIUM = common.read_json(common.BENCH / "configs" / "gpt2-medium.json")
XL = common.read_json(common.BENCH / "configs" / "gpt2-xl.json")


def test_parameter_counts():
    # vocab x d + positions x d + layers x (4 d^2 + 4 d + 8 d^2 + 5 d + 4 d) + 2 d
    assert counts.parameter_count(MEDIUM["published"]) == 354_823_168 == MEDIUM["parameters"]
    assert counts.parameter_count(XL["published"]) == 1_557_611_200 == XL["parameters"]
    assert gpt2.parameter_count(MEDIUM["published"]) == 354_823_168
    assert gpt2.parameter_count(XL["published"]) == 1_557_611_200


def test_kv_and_weight_bytes():
    # K and V, 48 layers, 1600 wide, 2 bytes
    assert counts.kv_bytes_per_token(XL["published"]) == 2 * 48 * 1600 * 2 == 307_200
    assert counts.weight_bytes(XL["published"]) == 3_115_222_400


def test_train_flops_per_token_medium():
    p = MEDIUM["published"]
    matmul = 24 * (4 * 1024 ** 2 + 2 * 1024 * 4096)            # 301,989,888
    assert counts.block_matmul_params(p) == matmul
    head = 50257 * 1024
    attention = 4 * 1024 * 24 * (1024 + 1) / 2                 # keys averaged over a causal row
    want = 3 * (2 * matmul + 2 * head + attention)
    assert counts.train_flops_per_token(p, 1024) == pytest.approx(want, rel=1e-12)
    assert want == pytest.approx(2.272e9, rel=2e-3)


def test_forward_flops_token_and_span_agree():
    p = XL["published"]
    one = counts.forward_flops_token(p, 200, True)
    assert one == 2 * counts.block_matmul_params(p) + 4 * 1600 * 200 * 48 + 2 * 50257 * 1600
    span = counts.forward_flops_span(p, 0, 10, 1)
    by_token = sum(counts.forward_flops_token(p, i + 1, i == 9) for i in range(10))
    assert span == by_token


def test_decode_min_bytes():
    p = XL["published"]
    got = counts.decode_min_bytes(p, [100, 300], 4)
    assert got == 400 * 307_200 + 2 * 3_115_222_400 / 4
