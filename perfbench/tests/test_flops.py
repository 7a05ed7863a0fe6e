"""FLOPs per token, attention operations and bytes against hand-worked
values for GPT-2 base and medium, and the table of peaks."""

import pytest

from perfbench import flops

BASE = dict(n_embd=768, n_layer=12, n_head=12, vocab_size=50304)
MEDIUM = dict(n_embd=1024, n_layer=24, n_head=16, vocab_size=50304)


def test_matmul_params_by_hand():
    # 12 layers x 12 * 768^2 = 84,934,656; head 50304 * 768 = 38,633,472
    assert flops.matmul_params(BASE) == 84_934_656 + 38_633_472
    # 24 x 12 * 1024^2 = 301,989,888; head 50304 * 1024 = 51,511,296
    assert flops.matmul_params(MEDIUM) == 301_989_888 + 51_511_296


@pytest.mark.parametrize("sizes,expect", [
    # 6 * 123,568,128 + 3 * 12 layers * (2*2*512*768 = 1,572,864)
    (BASE, 6 * 123_568_128 + 3 * 12 * 1_572_864),
    # 6 * 353,501,184 + 3 * 24 * (2*2*512*1024 = 2,097,152)
    (MEDIUM, 6 * 353_501_184 + 3 * 24 * 2_097_152),
])
def test_train_flops_per_token_by_hand(sizes, expect):
    assert flops.train_flops_per_token(sizes, 1024) == expect


def test_attention_flops_and_bytes_by_hand():
    # one row, base: forward 12 layers * 2 products * 2 flops * 1024 * 512
    # * 768 = 19,327,352,832; with backward at 2.5x: times 3.5
    fwd = 12 * 2 * 2 * 1024 * 512 * 768
    assert flops.attention_flops(BASE, 1, 1024, backward=False) == fwd
    assert flops.attention_flops(BASE, 1, 1024) == fwd * 3.5
    # bytes, bf16: one [1024, 768] tensor is 1,572,864 B; forward moves 4
    # of them and 1024*12*4 B of statistics, backward 8 and the same
    tensor, stats = 1024 * 768 * 2, 1024 * 12 * 4
    assert flops.attention_bytes(BASE, 1, 1024) == 12 * (
        4 * tensor + stats + 8 * tensor + stats)
    assert flops.attention_bytes(BASE, 2, 1024, backward=False) == 12 * 2 * (
        4 * tensor + stats)


def test_roofline_says_which_bound():
    peak = flops.peaks("TPU v5 lite")
    assert peak["bf16_flops"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    t, bound = flops.roofline_seconds(197e12, 1.0, peak)
    assert (t, bound) == (1.0, "flops")
    t, bound = flops.roofline_seconds(1.0, 819e9 * 2, peak)
    assert (t, bound) == (2.0, "bytes")


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        flops.peaks("cpu")
