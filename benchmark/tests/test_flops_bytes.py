"""`flops_bytes` against values worked by hand for both configurations."""
import json
import os

import pytest

from benchmark import flops_bytes as fb

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


# matmul weights: L x (4 d^2 + 2 d f) + d x V(padded)
SMALL_MATMUL = 12 * (4 * 768 * 768 + 2 * 768 * 3072) + 768 * 50304
XL_MATMUL = 48 * (4 * 1600 * 1600 + 2 * 1600 * 6400) + 1600 * 50304


def test_matmul_params_by_hand():
    assert SMALL_MATMUL == 84_934_656 + 38_633_472 == 123_568_128
    assert XL_MATMUL == 1_474_560_000 + 80_486_400 == 1_555_046_400
    assert fb.matmul_params(config("gpt2-small")) == SMALL_MATMUL
    assert fb.matmul_params(config("gpt2-xl")) == XL_MATMUL


def test_n_params_are_the_published_sizes():
    # 124 M and 1.56 B with the head tied, the vocabulary padded to 50304
    assert fb.n_params(config("gpt2-small")) == 124_475_904
    assert fb.n_params(config("gpt2-xl")) == 1_557_686_400


@pytest.mark.parametrize("name,matmul,layers,d", [
    ("gpt2-small", SMALL_MATMUL, 12, 768), ("gpt2-xl", XL_MATMUL, 48, 1600)])
def test_train_flops_per_token(name, matmul, layers, d):
    want = 6 * matmul + 12 * layers * d * 1024
    assert fb.train_flops_per_token(config(name), 1024) == want


def test_train_flops_match_the_programs_own_arithmetic():
    from determined_tpu.models.gpt import GPTConfig

    assert fb.train_flops_per_token(config("gpt2-small"), 1024) == \
        GPTConfig().train_flops_per_token()
    xl = GPTConfig(n_layers=48, n_heads=25, d_model=1600, d_ff=6400)
    assert fb.train_flops_per_token(config("gpt2-xl"), 1024) == \
        xl.train_flops_per_token()
    assert fb.n_params(config("gpt2-xl")) == xl.n_params()


def test_train_flops_round_numbers():
    # ~0.85 and ~9.9 GFLOP a token: what ISSUE 22's predictions lean on
    assert fb.train_flops_per_token(config("gpt2-small"), 1024) \
        == pytest.approx(0.8547e9, rel=1e-3)
    assert fb.train_flops_per_token(config("gpt2-xl"), 1024) \
        == pytest.approx(10.274e9, rel=1e-3)


def test_flash_forward_and_backward_by_hand():
    # 16 rows x 12 heads, 1024 x 1024 x 64, causal
    flops, nbytes = fb.flash_forward(16, 12, 1024, 1024, 64)
    assert flops == 4 * 16 * 12 * 1024 * 1024 * 64 / 2 == 25_769_803_776
    # q, k, v, o in bf16 and one fp32 lse a query
    assert nbytes == 2 * 16 * 12 * 64 * 4 * 1024 + 4 * 16 * 12 * 1024
    bflops, bbytes = fb.flash_backward(16, 12, 1024, 1024, 64)
    assert bflops == 2 * flops
    assert bbytes == 2 * 16 * 12 * 64 * 8 * 1024 + 4 * 16 * 12 * 1024
    not_causal, _ = fb.flash_forward(16, 12, 1024, 1024, 64, causal=False)
    assert not_causal == 2 * flops


def test_paged_decode_by_hand():
    # 32 slots of 512 cached tokens, 4 pages of 128 each, XL's 25 x 64
    flops, nbytes = fb.paged_decode(32 * 512, 32 * 4, 128, 25, 64)
    assert flops == 4 * 16384 * 25 * 64
    assert nbytes == 2 * 128 * 128 * 25 * 64 * 2   # K and V, bf16
    assert nbytes == 32 * 512 * 2 * 1600 * 2       # = tokens x d x 2 x bf16
    # XL: 307 KB of K/V per cached token over its 48 layers
    assert 48 * fb.paged_decode(1, 1 / 128, 128, 25, 64)[1] == 307_200


def test_roofline_says_which_peak_binds():
    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = fb.roofline_seconds(*fb.flash_forward(16, 12, 1024, 1024, 64),
                                   peaks)
    assert bound == "compute" and t == pytest.approx(25_769_803_776 / 197e12)
    t, bound = fb.roofline_seconds(
        *fb.paged_decode(16384, 128, 128, 25, 64), peaks)
    assert bound == "memory" and t == pytest.approx(104_857_600 / 819e9)
