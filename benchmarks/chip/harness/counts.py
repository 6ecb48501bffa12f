"""Operations and bytes that the algorithm needs, computed from shapes.

These count the work of the mathematics, not of an implementation:
causal attention counts only its unmasked (query, key) pairs, whatever
tiles a kernel visits; grouped-query K/V count at their own number of
heads, however a program repeats them; rematerialized work is not
counted.  ``m`` is a ``references.dense_gqa.Dims``.
"""

from __future__ import annotations

from typing import Dict, Tuple

BF16 = 2
F32 = 4


def causal_pairs(s: int) -> int:
    """(query, key) pairs with key <= query in a causal s x s square."""
    return s * (s + 1) // 2


def pair_matmul_flops(batch: int, heads: int, head_dim: int, pairs: int) -> int:
    """One attention matmul (QK^T or PV shaped) over ``pairs`` pairs."""
    return 2 * batch * heads * head_dim * pairs


def layer_token_flops(m) -> int:
    """Forward matmul FLOPs per token of one layer's projections and
    feed-forward network (attention scores excluded)."""
    qkv = 2 * m.d * (m.heads + 2 * m.kv_heads) * m.head_dim
    out = 2 * m.heads * m.head_dim * m.d
    ffn = 3 * 2 * m.d * m.d_ff
    return qkv + out + ffn


def head_token_flops(m) -> int:
    return 2 * m.d * m.vocab


def train_step_flops(m, batch: int, seq: int) -> int:
    """Model FLOPs of one training step: forward and backward (twice the
    forward) of every matmul, causal attention on its unmasked half."""
    tokens = batch * seq
    dense = tokens * (m.layers * layer_token_flops(m) + head_token_flops(m))
    attn = m.layers * 2 * pair_matmul_flops(batch, m.heads, m.head_dim, causal_pairs(seq))
    return 3 * (dense + attn)


def flash_call(kernel: str, m, batch: int, seq: int, itemsize: int = BF16) -> Tuple[int, int]:
    """(FLOPs, HBM bytes) one call of a flash kernel needs, self-attention
    over ``seq`` causal positions.

    * ``fwd``: S = QK^T, O = PV (2 matmuls); reads q, k, v, writes o;
    * ``fwd_lse``: the same, and writes the f32 log-sum-exp rows too;
    * ``bwd_dq``: S again, dP = dO V^T, dQ = dS K (3); reads q, k, v, dO,
      lse, delta, writes dq.
    * ``bwd_dkv``: S again, dP, dV = P^T dO, dK = dS^T Q (4); reads q, k,
      v, dO, lse, delta, writes dk, dv.
    """
    mm = pair_matmul_flops(batch, m.heads, m.head_dim, causal_pairs(seq))
    q = batch * seq * m.heads * m.head_dim * itemsize
    kv = batch * seq * m.kv_heads * m.head_dim * itemsize
    row = batch * m.heads * seq * F32
    if kernel == "fwd":
        return 2 * mm, 2 * q + 2 * kv
    if kernel == "fwd_lse":
        return 2 * mm, 2 * q + 2 * kv + row
    if kernel == "bwd_dq":
        return 3 * mm, 3 * q + 2 * kv + 2 * row
    if kernel == "bwd_dkv":
        return 4 * mm, 2 * q + 4 * kv + 2 * row
    raise KeyError(kernel)


def weight_bytes(m, itemsize: int, batch: int) -> int:
    """Weights one decode step must read: every layer, the final norm, the
    output head, and the ``batch`` embedding rows it looks up (a tied head
    reads the whole table once)."""
    layer = (m.d * (m.heads + 2 * m.kv_heads) * m.head_dim + m.heads * m.head_dim * m.d
             + 3 * m.d * m.d_ff + 2 * m.d + (2 * m.head_dim if m.qk_norm else 0))
    head = m.d * m.vocab
    embed = 0 if m.tied else batch * m.d
    return itemsize * (m.layers * layer + m.d + head + embed)


def decode_step(m, batch: int, ctx: int, weight_itemsize: int = BF16,
                kv_itemsize: int = BF16) -> Dict[str, int]:
    """FLOPs and bytes of one decode step of ``batch`` sequences whose
    cache holds ``ctx`` positions before the step: the new token attends
    to ctx + 1 keys; K/V of the ctx positions are read, the new ones
    written."""
    flops = (batch * (m.layers * layer_token_flops(m) + head_token_flops(m))
             + m.layers * 2 * pair_matmul_flops(batch, m.heads, m.head_dim, ctx + 1))
    kv_row = m.kv_heads * m.head_dim * 2 * kv_itemsize
    nbytes = (weight_bytes(m, weight_itemsize, batch)
              + m.layers * batch * ctx * kv_row + m.layers * batch * kv_row
              + batch * m.vocab * weight_itemsize)
    return {"flops": flops, "bytes": nbytes}


def roofline_s(flops: float, nbytes: float, peak: Dict) -> Tuple[float, str]:
    """Least time on the chip and which bound sets it."""
    tc, tm = flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
