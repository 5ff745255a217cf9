"""Operation and byte counts at granite widths against hand arithmetic,
and the peak table."""
import json
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import counts, peaks  # noqa: E402

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def model(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["model"]


def test_granite_parameters_and_kv_bytes():
    m = model("granite_3_2b")
    # per layer: q 2048*2048, k and v 2048*512 each, o 2048*2048, gated
    # MLP 3*2048*8192 = 4194304 + 2097152 + 4194304 + 50331648
    assert counts.layer_matmul_params(m) == 60_817_408
    # 40 layers (+ two 2048-wide norms each), tied 49155 x 2048 embedding,
    # final norm
    assert counts.param_count(m) == (40 * (60_817_408 + 4096)
                                     + 49155 * 2048 + 2048) == 2_533_531_648
    # 40 layers x (K and V) x 8 heads x 64 x 2 bytes
    assert counts.kv_bytes_per_token(m) == 81_920


def test_minicpm_kv_bytes_per_token():
    assert counts.kv_bytes_per_token(model("minicpm_2b")) == 368_640


def test_granite_decode_and_prefill_flops():
    m = model("granite_3_2b")
    mm = 2 * 40 * 60_817_408              # weight matmuls per token
    attn = 4 * 40 * 32 * 64               # QK and PV per cached key
    head = 2 * 2048 * 49155
    # two decode tokens attending 100 and 300 keys
    assert counts.decode_flops(m, [100, 300]) == \
        2 * mm + attn * 400 + 2 * head
    # a 3-token prompt: positions 0, 1, 2 attend 1, 2, 3 keys; one head row
    assert counts.prefill_flops(m, [0, 1, 2], 1) == 3 * mm + attn * 6 + head


def test_granite_paged_attention_bytes():
    m = model("granite_3_2b")
    # 300 keys: 300 x 81920 bytes, plus one query and one output row of
    # 32 x 64 bf16 values per layer
    assert counts.paged_attn_bytes(m, [300]) == 300 * 81_920 + 40 * 2 * 2048 * 2
    assert counts.paged_attn_flops(m, [300]) == 300 * 4 * 40 * 32 * 64


def test_peaks_known_and_unknown_devices():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
