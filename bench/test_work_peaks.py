"""Operation and byte counts against hand counts, the peak table, and
the refusal of a roofline share over 100%."""
import pytest

from bench import peaks, work

V5E = peaks.peak("TPU v5 lite")


def test_flash_attention_counts_by_hand():
    # 1 row of 4 tokens: 10 causal pairs; 2 heads of 8, 1 kv head
    flops, nbytes = work.kernel_work("flash_attention", rows=1, seq=4,
                                     heads=2, kv_heads=1, head_dim=8)
    assert flops == 4 * 2 * 8 * 10
    assert nbytes == 4 * (2 * 2 + 2 * 1) * 8 * 4


def test_paged_attention_counts_by_hand():
    # prompt 3, 3 new tokens: the two tokens fed back sit at positions 3
    # and 4 and attend to 4 and 5 cached positions
    flops, nbytes = work.kernel_work("paged_attention", rows=2, prompt_len=3,
                                     max_new=3, heads=4, kv_heads=2,
                                     head_dim=8)
    pairs = 4 + 5
    assert flops == 2 * 4 * 4 * 8 * pairs
    assert nbytes == 2 * (2 * 2 * 8 * 4 * pairs + 2 * 4 * 8 * 4 * 2)


def test_fused_logprob_counts_by_hand():
    flops, nbytes = work.kernel_work("fused_logprob", rows=2, seq=5, vocab=10)
    assert nbytes == 3 * (2 * 4) * 10 * 4
    assert flops == 6 * 8 * 10


def test_rl_row_flops_by_hand():
    spec = {"d_model": 4, "n_heads": 2, "n_kv_heads": 1, "head_dim": 2,
            "d_ff": 8, "act": "gelu", "n_layers": 1, "vocab": 10}
    layers, head = work.matmul_params(spec)
    assert layers == 4 * (2 + 2) * 2 + 2 * 2 * 4 + 2 * 4 * 8
    assert head == 40
    # prompt 2, 2 new tokens, T = 4: one decode at position 2 (3 keys)
    want = (2 * layers * 2 + 2 * head + 4 * 2 * 2 * (1 + 2)) \
        + (2 * (layers + head) * 1 + 4 * 2 * 2 * 3) \
        + 3 * (2 * (layers + head) * 4 + 4 * 2 * 2 * (1 + 2 + 3 + 4))
    assert work.rl_row_flops(spec, prompt_len=2, max_new=2) == want


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")


def test_roofline_share_over_100_is_an_error():
    flops, nbytes = work.kernel_work("fused_logprob", rows=8, seq=513,
                                     vocab=49152)
    least = nbytes / V5E["hbm_bytes_per_s"]
    share = work.roofline_pct("fused_logprob", 2 * least, V5E, rows=8,
                              seq=513, vocab=49152)
    assert share == pytest.approx(50.0)
    with pytest.raises(ValueError):
        work.roofline_pct("fused_logprob", 0.5 * least, V5E, rows=8,
                          seq=513, vocab=49152)
