import pytest

import roofline

MISTRAL_L20 = dict(hidden_size=4096, intermediate_size=14336,
                   num_attention_heads=32, num_key_value_heads=8, head_dim=128,
                   num_hidden_layers=20, vocab_size=32768)


def test_unknown_device_kind_is_an_error_not_a_default():
    assert roofline.chip("TPU v5 lite").bf16_flops == 197e12
    assert roofline.chip("TPU v5 lite").int8_ops == 393e12
    with pytest.raises(ValueError):
        roofline.chip("cpu")


def test_decode_step_is_memory_bound_on_the_weights():
    flops, nbytes = roofline.decode_step_work(MISTRAL_L20, [600] * 16)
    assert abs(roofline.matmul_params(MISTRAL_L20) - 4.496e9) < 1e6
    assert nbytes > 2 * 4.496e9 and nbytes < 2 * 4.496e9 + 1e9
    share, bound = roofline.roofline_share(flops, nbytes, 0.0346,
                                           roofline.chip("TPU v5 lite"))
    assert bound == "memory" and 30 < share < 40


def test_prefill_is_compute_bound_and_counts_the_causal_half():
    flops, nbytes = roofline.prefill_work(MISTRAL_L20, 2048)
    _, bound = roofline.roofline_share(flops, nbytes, 0.2,
                                       roofline.chip("TPU v5 lite"))
    assert bound == "compute"
    attn, _ = roofline.flash_prefill_work(MISTRAL_L20, 2048)
    assert attn == 20 * 4 * (2048 * 2049 // 2) * 32 * 128
