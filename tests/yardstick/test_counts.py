"""The operation and byte counts against closed forms worked by hand at
small shapes, and the property that makes a share trustworthy: for work the
functions were given, no share of a peak can pass 100%."""

from __future__ import annotations

import pytest

from yardstick import counts, harness, weights

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_block_matmuls_by_hand():
    # d=4, ff=16: qkv 4x12, proj 4x4, up 4x16, down 16x4 = 192 weights
    assert counts.block_matmul_flops_per_token(4, 16) == 2 * 192


def test_causal_keys_and_attention_by_hand():
    assert counts.causal_keys(1) == 1
    assert counts.causal_keys(4) == 1 + 2 + 3 + 4
    # one query over 3 keys, d=8: scores 3*8 mult-adds, weighted sum 3*8
    assert counts.attention_flops(8, 3) == 2 * (3 * 8 + 3 * 8)


def test_forward_and_train_step_by_hand():
    # d=4 ff=16 L=2 vocab=10, 3 positions, head on all 3
    trunk = 2 * (3 * 384 + counts.attention_flops(4, 6))
    head = 3 * 2 * 4 * 10
    assert counts.lm_forward_flops(d=4, ff=16, layers=2, vocab=10,
                                   positions=3, head_rows=3) == trunk + head
    # a step of 5 rows of 4: the last position carries no loss
    assert counts.lm_train_step_flops(
        d=4, ff=16, layers=2, vocab=10, batch=5, seq=4) == 3 * 5 * (
            trunk + head)


def test_span_flops_add_up_to_the_forward():
    size = dict(d=64, ff=256, layers=3)
    whole = counts.lm_span_flops(start=0, stop=300, **size)
    assert whole == (counts.lm_span_flops(start=0, stop=128, **size)
                     + counts.lm_span_flops(start=128, stop=256, **size)
                     + counts.lm_span_flops(start=256, stop=300, **size))
    assert whole == sum(counts.lm_token_flops(position=i, **size)
                        for i in range(300))
    assert whole == counts.lm_forward_flops(
        vocab=1, positions=300, head_rows=0, **size)


def test_gpt2_medium_step_is_the_known_size():
    """2.27 GFLOP a token at 8 x 1024 (ISSUE 25 reckons 2.3)."""
    cell = harness.load_cell("gpt2-medium.train.seq1024")
    c = cell.config
    flops = counts.lm_train_step_flops(
        d=c["n_embd"], ff=c["n_inner"], layers=c["n_layer"],
        vocab=weights.sizes_of(c)["vocab"], batch=8, seq=1024)
    assert flops / (8 * 1024) == pytest.approx(2.27e9, rel=0.01)


def test_flash_kernels_by_hand():
    shape = dict(batch=2, heads=3, seq=4, head_dim=8)
    keys = 10  # 1+2+3+4
    f, b = counts.flash_forward(**shape)
    assert f == 2 * 2 * 2 * (3 * 8) * keys
    assert b == 4 * (2 * 3 * 4 * 8) * 2 + 2 * 3 * 4 * 4
    f_dq, _ = counts.flash_backward_dq(**shape)
    f_dkv, _ = counts.flash_backward_dkv(**shape)
    assert f_dq == 3 * 2 * 2 * (3 * 8) * keys
    assert f_dkv == 4 * 2 * 2 * (3 * 8) * keys


def test_paged_decode_by_hand():
    # 2 rows holding 5 + 9 = 14 live keys, 3 heads of 8, bf16
    f, b = counts.paged_decode(live_keys=14, rows=2, heads=3, head_dim=8)
    assert f == 2 * 2 * 24 * 14
    assert b == 2 * 14 * 24 * 2 + 2 * 2 * 24 * 2


@pytest.mark.parametrize("flops,nbytes", [
    (1e12, 1e6), (1e6, 1e12), (197e12, 819e9), (0.0, 5e9)])
def test_no_share_passes_100_percent_for_the_work_given(flops, nbytes):
    """Whatever time a kernel really takes is at least the least time: a
    chip at its peaks does ``flops`` in flops/peak and moves ``nbytes`` in
    bytes/peak, and must do both. So least/time <= 1 for any time the chip
    can reach, and the achieved rates under it stay under the peaks."""
    least = counts.least_seconds(flops, nbytes, PEAKS)
    assert flops / least <= PEAKS["bf16_flops_per_s"] * (1 + 1e-12)
    assert nbytes / least <= PEAKS["hbm_bytes_per_s"] * (1 + 1e-12)
    for slower in (1.0, 1.5, 40.0):
        assert least / (least * slower) <= 1.0


def test_counts_never_charge_padding_or_dead_keys():
    """Rounding live keys up to blocks of 128, or charging empty slots,
    would only raise the numerator: the functions take exact lengths."""
    exact = counts.paged_decode(live_keys=130, rows=1, heads=25,
                                head_dim=64)
    rounded = counts.paged_decode(live_keys=256, rows=1, heads=25,
                                  head_dim=64)
    assert exact[0] < rounded[0] and exact[1] < rounded[1]
    causal, _ = counts.flash_forward(batch=1, heads=1, seq=1024,
                                     head_dim=64)
    full = 2 * 2 * 64 * 1024 * 1024
    assert causal < 0.51 * full


def test_peaks_table():
    row = harness.peaks_for("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError):
        harness.peaks_for("TPU v9 imaginary")
    with pytest.raises(ValueError):
        harness.peaks_for("cpu")
