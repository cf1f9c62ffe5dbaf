"""Operation and byte counts of the dense decoder (``perf/arch/dense.py``)
against counts worked by hand, and the chip's peaks."""
import json

import pytest

from perf import model, work

dense = model.arch({"arch": "dense"})

# a toy spec small enough to count by hand: d 8, ff 16, 2 layers, 4 query
# heads over 2 kv heads of size 2, vocabulary 10
TOY = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 2,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 2,
       "vocab_size": 10}


def test_layer_params_by_hand():
    # q 8*4*2=64, k and v 8*2*2=32 each, o 4*2*8=64, mlp 3*8*16=384
    assert dense.layer_matmul_params(TOY) == 64 + 32 + 32 + 64 + 384


def test_decode_flops_by_hand():
    # 2 * (2 layers * 576) + attention 4*2*4*2*(pos+1) + head 2*8*10
    assert dense.decode_flops(TOY, 9) == 2 * 2 * 576 + 64 * 10 + 160


def test_prompt_flops_by_hand():
    # 3 tokens: matmuls on each, causal keys 1+2+3, head once
    assert dense.prompt_flops(TOY, 3) == 2 * 2 * 576 * 3 + 64 * 6 + 160


def test_paged_attention_work_by_hand():
    flops, nbytes = dense.paged_attention_work(TOY, 5)
    assert flops == 4 * 2 * 4 * 2 * 6
    row = 2 * 2 * 2 * 2                  # k and v, 2 kv heads, hd 2, bf16
    # per layer: 5 cached rows + new row in + new row out + q in + o out
    assert nbytes == 2 * (row * 5 + 2 * row + 2 * 4 * 2 * 2)


def test_published_heads_not_padded():
    spec = model.load("dsc33b_6l")
    flops, nbytes = work.paged_attention_work(spec, 0)
    assert flops == 4 * 6 * 56 * 128          # 56 heads, not the 64 slots
    assert nbytes == 6 * (2 * 8 * 128 * 2 * 2 + 2 * 56 * 128 * 2)
    assert dense.layer_matmul_params(spec) == (
        7168 * 56 * 128 * 2 + 2 * 7168 * 8 * 128 + 3 * 7168 * 19200)


def test_roofline_share_takes_the_binding_bound():
    peak = work.peaks("TPU v5 lite")
    # 819 GB in one second is the bandwidth bound exactly: 100%
    assert work.roofline_share(1.0, 819e9, 1.0, peak) == pytest.approx(100)
    assert work.roofline_share(197e12, 1.0, 2.0, peak) == pytest.approx(50)


def test_unknown_device_kind_raises():
    with pytest.raises(work.UnknownDevice):
        work.peaks("TPU v9 imaginary")


def test_peaks_name_their_source():
    table = json.loads(work.PEAKS.read_text())
    assert table["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert table["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in table["TPU v5 lite"]["source"]
