"""Operation and byte counts from shapes, and the peak table."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import peaks  # noqa: E402
import work  # noqa: E402

M = {"d_model": 64, "num_heads": 4, "num_kv_heads": 2, "head_dim": 16,
     "d_ff": 128, "vocab_size": 1000, "num_layers": 3}


def test_decode_flops_are_twice_the_weights_plus_attention():
    per_layer = 64 * (4 + 2 * 2) * 16 + 4 * 16 * 64 + 3 * 64 * 128
    weights = 3 * per_layer + 64 * 1000
    assert work.decode_token_flops(M, 10) == 2 * weights + 4 * 3 * 4 * 16 * 10
    assert work.prefill_token_flops(M, 9) == 2 * 3 * per_layer \
        + 4 * 3 * 4 * 16 * 10


@pytest.mark.parametrize("bits,per_elem", [(None, 4.0), (8, 1.0), (4, 0.5)])
def test_kv_bytes_follow_the_pool_precision(bits, per_elem):
    got = work.kv_bytes(M, 40, bits, 16)
    exps = 0 if bits is None else 3 * 2 * 2 * 3     # 3 pages of 16
    assert got == pytest.approx(3 * 2 * 2 * 40 * 16 * per_elem + exps)


def test_mq_prefill_is_causal_and_reads_the_prefix_once():
    ops, nbytes = work.mq_prefill(M, 32, 32, None, 16)
    assert ops == sum(work.attention_flops(M, p + 1) for p in range(32, 64))
    assert nbytes == work.kv_bytes(M, 64, None, 16) \
        + 2 * 3 * 32 * 4 * 16 * 4.0


def test_roofline_share_is_one_at_the_bound():
    pk = peaks.peaks("TPU v5 lite")
    assert work.roofline_share(0, pk["hbm_bytes_per_s"], 1.0, pk) == 1.0
    assert work.roofline_share(pk["bf16_flops"], 0, 2.0, pk) == 0.5


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
