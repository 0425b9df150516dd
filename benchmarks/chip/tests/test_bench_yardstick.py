"""The count functions against values worked by hand, and the peaks
table."""

import json
from pathlib import Path

import pytest

from fqabench import yardstick

BENCH = Path(__file__).resolve().parents[1]


def dims(name):
    conf = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    return yardstick.Dims.from_config(conf)


def test_internlm2_counts():
    m = dims("internlm2-1.8b")
    # attention 2048*(16+2*8)*128 + 16*128*2048, MLP 3*2048*8192
    assert m.layer_matmul_params == 8_388_608 + 4_194_304 + 50_331_648
    assert m.nonembedding_params == 24 * 62_914_560 == 1_509_949_440
    assert m.table_params == 92_544 * 2048 == 189_530_112
    assert m.norm_params == 49 * 2048
    # bf16: layers + norms + LM head
    assert yardstick.weight_bytes(m) == 3_399_159_808
    # K and V, 8 heads of 128, 24 layers, bf16: 96 KiB a position
    assert yardstick.kv_bytes_per_token(m) == 98_304
    # one token: 2N + one query-key pair + the head
    assert yardstick.prefill_flops(m, 1) == 3_019_898_880 + 196_608 \
        + 379_060_224
    assert yardstick.decode_flops(m, 100) == 3_418_619_904


def test_mistral_nemo_cut_counts():
    m = dims("mistral-nemo-12b")
    assert m.layers == 10
    # attention 5120*(32+16)*128 + 32*128*5120, MLP 3*5120*14336
    assert m.layer_matmul_params == 31_457_280 + 20_971_520 + 220_200_960
    assert m.table_params == 671_088_640
    assert yardstick.weight_bytes(m) == 2 * (2_726_297_600 + 21 * 5120
                                             + 671_088_640)
    assert yardstick.kv_bytes_per_token(m) == 40_960
    # 1024 tokens: 2N per token, 1024*1025/2 causal pairs, the head once
    assert yardstick.prefill_flops(m, 1024) == 5_670_782_894_080


def test_step_counts_add_up():
    m = dims("internlm2-1.8b")
    assert yardstick.step_flops(m, [3], [10, 20]) == (
        yardstick.prefill_flops(m, 3) + yardstick.decode_flops(m, 10)
        + yardstick.decode_flops(m, 20))
    assert yardstick.decode_step_bytes(m, [10, 20]) == (
        yardstick.weight_bytes(m) + 2 * 2048 * 2 + 30 * 98_304)


def test_peaks_table():
    p = yardstick.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError, match="no published peaks"):
        yardstick.peaks("TPU v9 imaginary")
