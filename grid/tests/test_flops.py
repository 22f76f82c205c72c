import pytest

from grid import flops


def test_train_flops_match_the_programs_own_count():
    import bench

    got = flops.transformer_train_flops_per_example(256, 30000, 6, 512, 2048)
    assert got == bench._transformer_train_flops_per_example(256, 30000)
    assert got == pytest.approx(98.5e9, rel=0.01)
    model = dict(vocab_size=30000, n_layer=6, d_model=512, d_inner=2048)
    assert flops.train_flops_per_token(model, 256) == got / 256


def test_paged_attention_bytes():
    # 32 slots at 500 tokens of context, GPT-2 small, bf16
    assert flops.paged_attention_kv_bytes(32 * 500, 12, 12, 64, 2) \
        == 32 * 500 * 12 * 2 * 768 * 2


def test_an_unknown_device_is_an_error():
    assert flops.device_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert flops.device_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.device_peaks("cpu")
