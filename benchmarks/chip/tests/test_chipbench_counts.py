"""The FLOP and byte counters against hand counts at the cells' shapes,
and the peak table."""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import counts, peaks  # noqa: E402
from references import dense_gqa  # noqa: E402


def dims(name):
    return dense_gqa.dims(json.loads((BENCH / "configs" / f"{name}.json").read_text()))


# qwen3-8b cut to 2 layers and 18,992 ids, per token and layer, forward:
#   q, k, v  2 * 4096 * (32 + 2 * 8) * 128 =  50,331,648
#   out      2 * 4096 * 4096               =  33,554,432
#   ffn      3 * 2 * 4096 * 12288          = 301,989,888
# two layers 771,751,936, plus the head 2 * 4096 * 18992 = 155,582,464:
# 927,334,400 per token.  Causal pairs at 4096: 4096 * 4097 / 2 = 8,390,656.
@pytest.mark.parametrize("cell, name, batch, seq, per_token, pair_mm, step", [
    ("qwen3-8b.train-4k", "qwen3-8b", 2, 4096, 927_334_400, 137_472_507_904, 24_439_840_309_248),
    # four chips' data-parallel share of 2 x 4,096 each
    ("8 x 4k", "qwen3-8b", 8, 4096, 927_334_400, 549_890_031_616, 97_759_361_236_992),
    # one 32k sequence: causal pairs 32768 * 32769 / 2 = 536,887,296
    ("1 x 32k", "qwen3-8b", 1, 32768, 927_334_400, 4_398_180_728_832, 143_938_849_603_584),
])
def test_train_step_flops(cell, name, batch, seq, per_token, pair_mm, step):
    m = dims(name)
    assert m.layers * counts.layer_token_flops(m) + counts.head_token_flops(m) == per_token
    assert counts.pair_matmul_flops(batch, m.heads, m.head_dim, counts.causal_pairs(seq)) == pair_mm
    # forward: matmuls per token, plus QK^T and PV per layer; step = 3 x forward
    assert counts.train_step_flops(m, batch, seq) == 3 * (per_token * batch * seq + 2 * m.layers * pair_mm)
    assert counts.train_step_flops(m, batch, seq) == step


def test_flash_kernel_counts_at_4k():
    m = dims("qwen3-8b")
    mm = 137_472_507_904
    q = 2 * 4096 * 32 * 128 * 2          # bf16 q (or o, dO, dq)
    kv = 2 * 4096 * 8 * 128 * 2          # bf16 k or v at the KV heads' count
    row = 2 * 32 * 4096 * 4              # f32 lse or delta
    assert counts.flash_call("fwd_lse", m, 2, 4096) == (2 * mm, 2 * q + 2 * kv + row)
    assert counts.flash_call("bwd_dq", m, 2, 4096) == (3 * mm, 3 * q + 2 * kv + 2 * row)
    assert counts.flash_call("bwd_dkv", m, 2, 4096) == (4 * mm, 2 * q + 4 * kv + 2 * row)
    t, bound = counts.roofline_s(*counts.flash_call("fwd_lse", m, 2, 4096), peaks.peak("TPU v5 lite"))
    assert bound == "compute" and t == pytest.approx(2 * mm / 197e12)


def test_decode_step_counts():
    m = dims("qwen3-8b")
    # weights: per layer q,k,v,o 4096*(48*128) + 4096*4096, ffn 3*4096*12288,
    # two norms 2*4096, q/k norms 2*128; then final norm, head 4096*18992 and
    # 16 embedding rows; all bf16
    layer = 4096 * 48 * 128 + 4096 * 4096 + 3 * 4096 * 12288 + 2 * 4096 + 2 * 128
    w = 2 * (2 * layer + 4096 + 4096 * 18992 + 16 * 4096)
    assert counts.weight_bytes(m, 2, 16) == w == 927_507_456
    kv = 2 * 16 * 28672 * 8 * 128 * 2 * 2 + 2 * 16 * 8 * 128 * 2 * 2
    c = counts.decode_step(m, 16, 28672)
    assert c["bytes"] == w + kv + 16 * 18992 * 2 == 4_686_342_656
    assert c["flops"] == 16 * 927_334_400 + 2 * 2 * (2 * 16 * 32 * 128 * 28673) == 29_870_260_224
    t, bound = counts.roofline_s(c["flops"], c["bytes"], peaks.peak("TPU v5 lite"))
    assert bound == "memory" and t == pytest.approx(4_686_342_656 / 819e9)


def test_peak_table_refuses_an_unknown_device():
    assert peaks.peak("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peak("cpu")
