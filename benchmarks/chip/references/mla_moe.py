"""Plain reference of the DeepSeek-V3 block as Moonlight-16B-A3B publishes
it (multi-head latent attention, a leading dense layer, sigmoid-routed
experts with shared experts), for one chip's share of an expert-parallel
deployment, in float32 at "highest" precision.

It imports nothing of the program under test; it takes the numerics, the
RoPE, the blocking and AdamW of ``dense_gqa``.  Equations (h = RMSNorm(x),
eps from the configuration):

* latent attention: q = h W_q as H x [q_nope | q_rope];
  [c | k_rope] = h W_kv_a, c of ``kv_lora_rank``, k_rope one head shared
  by all; c <- RMSNorm(c); [k_nope | v] = c W_kv_b as H x [nope | v];
  RoPE on q_rope and k_rope; k = [k_nope | k_rope]; causal softmax of
  q.k / sqrt(nope + rope) against v; out = o W_o;
* the first ``first_k_dense_replace`` layers: a SwiGLU of
  ``intermediate_size``;
* the other layers: s = sigmoid(h W_r) over all the router's experts;
  top-k of s; g_i = routed_scaling_factor * s_i / sum of the top-k s;
  out = sum over the top-k that this chip holds of g_i SwiGLU_i(h) (of
  ``moe_intermediate_size``), plus a shared SwiGLU of
  ``n_shared_experts * moe_intermediate_size``.  The held experts are
  computed densely: every token through every held expert, times its gate
  or 0; there is no dispatch.

The departures it follows of the program are the configuration's
``departures``: the score correction bias held at zero, no auxiliary
loss, AdamW in place of Muon, token embeddings times sqrt(hidden_size),
and RoPE in the rotate-half layout (the published code permutes the
rotary dims to an interleaved layout first, a relabelling of random
weights).

It also holds the operation and byte counts of the cells that run this
configuration (``train_step_flops``, ``flash_call``, ``expert_cost``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from harness.counts import BF16, F32 as F32_BYTES, causal_pairs
from harness.weights import Leaf, values_in
from references.dense_gqa import (  # noqa: F401  (F32, FP8, AdamW: the reference's interface)
    BLOCK_BYTES, F32, FP8, NEG, AdamW, Numerics, _seq_blocks, leaf_norms, mm, rmsnorm, rope,
)


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int            # all layers: dense ones first, then MoE
    dense_layers: int
    d: int
    heads: int
    nope: int              # qk_nope_head_dim
    rope: int              # qk_rope_head_dim
    v_dim: int
    kv_rank: int
    d_ff: int              # the dense layers' SwiGLU
    expert_ff: int
    experts: int           # the router's width
    held: int              # experts on this chip
    first_held: int
    top_k: int
    shared: int            # shared experts (one SwiGLU of shared * expert_ff)
    routed_scale: float
    vocab: int
    eps: float
    theta: float
    tied: bool

    @property
    def qk_dim(self) -> int:
        return self.nope + self.rope

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense_layers


def dims(conf: Dict[str, Any]) -> Dims:
    if conf.get("q_lora_rank") is not None:
        raise ValueError("a low-rank query projection (q_lora_rank) is not in this reference")
    if conf["scoring_func"] != "sigmoid" or conf.get("n_group", 1) != 1 or not conf["norm_topk_prob"]:
        raise ValueError("this reference routes by sigmoid scores in one group, "
                         "gates normalised over the top-k")
    return Dims(
        layers=conf["num_hidden_layers"], dense_layers=conf["first_k_dense_replace"],
        d=conf["hidden_size"], heads=conf["num_attention_heads"],
        nope=conf["qk_nope_head_dim"], rope=conf["qk_rope_head_dim"],
        v_dim=conf["v_head_dim"], kv_rank=conf["kv_lora_rank"],
        d_ff=conf["intermediate_size"], expert_ff=conf["moe_intermediate_size"],
        experts=conf["router_experts"], held=conf["n_routed_experts"],
        first_held=conf["first_held_expert"], top_k=conf["num_experts_per_tok"],
        shared=conf["n_shared_experts"], routed_scale=float(conf["routed_scaling_factor"]),
        vocab=conf["vocab_size"],
        eps=conf["rms_norm_eps"], theta=float(conf["rope_theta"]),
        tied=bool(conf["tie_word_embeddings"]),
    )


def program_kwargs(conf: Dict[str, Any]) -> Dict[str, Any]:
    """The program's ``ModelConfig`` fields for this configuration, as
    plain values (the harness builds the program's object from them)."""
    m = dims(conf)
    return dict(
        family=conf["program_family"], num_layers=m.layers, d_model=m.d, heads=m.heads,
        kv_heads=m.heads, d_ff=m.d_ff, vocab=m.vocab, rope_theta=m.theta,
        tie_embeddings=m.tied, rms_norm_eps=m.eps, first_dense_layers=m.dense_layers,
        mla={"kv_lora_rank": m.kv_rank, "qk_nope_head_dim": m.nope,
             "qk_rope_head_dim": m.rope, "v_head_dim": m.v_dim},
        moe={"num_experts": m.experts, "top_k": m.top_k, "d_ff": m.expert_ff,
             "num_shared_experts": m.shared, "scoring": "sigmoid", "routed_scale": m.routed_scale,
             "held_experts": m.held, "first_held": m.first_held, "aux_loss_coeff": 0.0},
    )


def _layer_layout(m: Dims, n: int, moe: bool):
    D, H = m.d, m.heads
    tree = {
        "ln1": {"scale": Leaf((n, D), None)},
        "attn": {
            "wq": {"w": Leaf((n, D, H * m.qk_dim), D)},
            "wkv_a": {"w": Leaf((n, D, m.kv_rank + m.rope), D)},
            "kv_norm": {"scale": Leaf((n, m.kv_rank), None)},
            "wkv_b": {"w": Leaf((n, m.kv_rank, H * (m.nope + m.v_dim)), m.kv_rank)},
            "wo": {"w": Leaf((n, H * m.v_dim, D), H * m.v_dim)},
        },
        "ln2": {"scale": Leaf((n, D), None)},
    }
    if moe:
        F, Fs = m.expert_ff, m.expert_ff * m.shared
        tree["moe"] = {
            "router": {"w": Leaf((n, D, m.experts), D)},
            "wi": Leaf((n, m.held, D, F), D), "wg": Leaf((n, m.held, D, F), D),
            "wo": Leaf((n, m.held, F, D), F),
            "shared": {"wi": {"w": Leaf((n, D, Fs), D)}, "wg": {"w": Leaf((n, D, Fs), D)},
                       "wo": {"w": Leaf((n, Fs, D), Fs)}},
        }
    else:
        F = m.d_ff
        tree["ffn"] = {"wi": {"w": Leaf((n, D, F), D)}, "wg": {"w": Leaf((n, D, F), D)},
                       "wo": {"w": Leaf((n, F, D), F)}}
    return tree


def layout(conf: Dict[str, Any]):
    """Parameter tree (names and shapes as the program lays them out, each
    stack of layers on a leading axis) with each leaf's initialisation."""
    m = dims(conf)
    tree = {
        "embed": {"table": Leaf((m.vocab, m.d), m.d)},
        "layers": _layer_layout(m, m.moe_layers, True),
        "final_norm": {"scale": Leaf((m.d,), None)},
    }
    if m.dense_layers:
        tree["dense_layers"] = _layer_layout(m, m.dense_layers, False)
    if not m.tied:
        tree["lm_head"] = {"w": Leaf((m.d, m.vocab), m.d)}
    return tree


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------


def attention(num, q, k, v):
    """Causal attention in blocks of query rows: q, k (B, S, H, Dqk), v
    (B, S, H, Dv) -> (B, S, H * Dv)."""
    B, S, H, Dqk = q.shape
    scale = 1.0 / math.sqrt(Dqk)
    blk = S
    while blk > 8 and (B * H * blk * S * 4 > BLOCK_BYTES or S % blk):
        blk //= 2
    qb = q.reshape(B, S // blk, blk, H, Dqk).swapaxes(0, 1)
    pb = jnp.arange(S).reshape(S // blk, blk)

    @jax.checkpoint
    def one(args):
        qi, pi = args
        s = mm(num, "bqhd,bkhd->bhqk", qi * scale, k)
        s = jnp.where((jnp.arange(S)[None, :] <= pi[:, None])[None, None], s, NEG)
        return mm(num, "bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(one, (qb, pb))                       # (nb, B, blk, H, Dv)
    return out.swapaxes(0, 1).reshape(B, S, H * v.shape[-1])


def mla(num, m: Dims, ap, h, positions):
    B, S, _ = h.shape
    H, dn = m.heads, m.nope
    q = mm(num, "bsd,de->bse", h, ap["wq"]["w"]).reshape(B, S, H, m.qk_dim)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], positions[None], m.theta)], axis=-1)
    kva = mm(num, "bsd,de->bse", h, ap["wkv_a"]["w"])
    c = rmsnorm(kva[..., :m.kv_rank], ap["kv_norm"]["scale"], m.eps)
    k_rope = rope(kva[..., None, m.kv_rank:], positions[None], m.theta)   # (B, S, 1, rope)
    kv = mm(num, "bsr,re->bse", c, ap["wkv_b"]["w"]).reshape(B, S, H, dn + m.v_dim)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_rope, (B, S, H, m.rope))], axis=-1)
    o = attention(num, q, k, kv[..., dn:])
    return mm(num, "bse,ed->bsd", o, ap["wo"]["w"])


def swiglu(num, p, h):
    g = mm(num, "bsd,df->bsf", h, p["wg"]["w"])
    u = mm(num, "bsd,df->bsf", h, p["wi"]["w"])
    return mm(num, "bsf,fd->bsd", jax.nn.silu(g) * u, p["wo"]["w"])


def route(num, m: Dims, w_router, h):
    """Gates (B, S, held) of the held experts: g_i for a held expert in
    the top-k, else 0."""
    s = jax.nn.sigmoid(mm(num, "bsd,de->bse", h, w_router))
    vals, idx = jax.lax.top_k(s, m.top_k)
    vals = vals / jnp.sum(vals, axis=-1, keepdims=True) * m.routed_scale
    held = m.first_held + jnp.arange(m.held)
    return jnp.sum(jnp.where(idx[..., None] == held, vals[..., None], 0.0), axis=-2)


def moe(num, m: Dims, p, x):
    def f(h):
        gates = route(num, m, p["router"]["w"], h)
        g = mm(num, "bsd,edf->bsef", h, p["wg"])
        u = mm(num, "bsd,edf->bsef", h, p["wi"])
        y = mm(num, "bsef,efd->bsed", jax.nn.silu(g) * u, p["wo"])
        return jnp.sum(y * gates[..., None], axis=2) + swiglu(num, p["shared"], h)

    return _seq_blocks(f, (x,), max(1, 2048 // x.shape[0]))


def layer(num, m: Dims, lp, x, positions):
    x = x + mla(num, m, lp["attn"], rmsnorm(x, lp["ln1"]["scale"], m.eps), positions)
    h = rmsnorm(x, lp["ln2"]["scale"], m.eps)
    if "moe" in lp:
        return x + moe(num, m, lp["moe"], h)
    return x + _seq_blocks(lambda hb: swiglu(num, lp["ffn"], hb), (h,), max(1, 2048 // h.shape[0]))


def hidden(num, m: Dims, params, tokens, positions):
    x = params["embed"]["table"][tokens] * math.sqrt(m.d)

    def body(x, lp):
        return jax.checkpoint(lambda lp, x: layer(num, m, lp, x, positions))(lp, x), None

    for stack in ("dense_layers", "layers"):
        if stack in params:
            x, _ = jax.lax.scan(body, x, params[stack])
    return rmsnorm(x, params["final_norm"]["scale"], m.eps)


def loss(num, m: Dims, params, tokens, targets):
    """Mean next-token cross entropy over every position, in f32."""
    B, S = tokens.shape
    x = hidden(num, m, params, tokens, jnp.arange(S))
    w = params["embed"]["table"].T if m.tied else params["lm_head"]["w"]

    def nll(xb, tb):
        logits = mm(num, "bsd,dv->bsv", xb, w)
        gold = jnp.take_along_axis(logits, tb[..., None], axis=-1)[..., 0]
        return jax.nn.logsumexp(logits, axis=-1) - gold

    return jnp.mean(_seq_blocks(nll, (x, targets), max(1, 2048 // B)))


# ---------------------------------------------------------------------------
# training: the first steps of AdamW
# ---------------------------------------------------------------------------


def make_train_step(conf, opt: AdamW, num: Numerics, fault: Optional[str] = None,
                    replicas: int = 1):
    """(params, mu, nu, tokens, targets, step) -> (params, mu, nu, loss,
    raw gradient leaf norms), the inputs donated; ``fault`` as in
    ``dense_gqa.make_train_step`` ("half", "no_exchange")."""
    m = dims(conf)
    f = lambda p, tokens, targets: loss(num, m, p, tokens, targets)

    def value_and_grad(params, tokens, targets):
        if fault is None:
            return jax.value_and_grad(f)(params, tokens, targets)
        if fault == "half":
            h = tokens.shape[0] // 2
            return jax.value_and_grad(f)(params, tokens[:h], targets[:h])
        if fault == "no_exchange":
            r = tokens.shape[0] // replicas
            g = jax.grad(f)(params, tokens[:r], targets[:r])
            return f(params, tokens, targets), jax.tree_util.tree_map(lambda x: x / replicas, g)
        raise ValueError(fault)

    def grad(params, tokens, targets):
        lval, grads = value_and_grad(params, tokens, targets)
        return lval, grads, leaf_norms(grads)

    def update(params, mu, nu, grads, gn, t):
        gnorm = jnp.sqrt(sum(n * n for n in gn))
        clip = jnp.minimum(1.0, opt.grad_clip / jnp.maximum(gnorm, 1e-12))
        tf = t.astype(jnp.float32)
        prog = jnp.clip((tf - opt.warmup_steps) / max(1, opt.total_steps - opt.warmup_steps), 0.0, 1.0)
        cos = opt.min_lr_frac + (1 - opt.min_lr_frac) * 0.5 * (1 + jnp.cos(math.pi * prog))
        lr = opt.lr * jnp.where(tf < opt.warmup_steps, tf / max(1, opt.warmup_steps), cos)
        b1c, b2c = 1 - opt.b1 ** tf, 1 - opt.b2 ** tf

        def upd(p, g, a, b):
            g = g * clip
            a = opt.b1 * a + (1 - opt.b1) * g
            b = opt.b2 * b + (1 - opt.b2) * g * g
            delta = (a / b1c) / (jnp.sqrt(b / b2c) + opt.eps)
            if p.ndim >= 2:
                delta = delta + opt.weight_decay * p
            return p - lr * delta, a, b

        out = jax.tree_util.tree_map(upd, params, grads, mu, nu)
        istuple = lambda x: isinstance(x, tuple)
        pick = lambda i: jax.tree_util.tree_map(lambda o: o[i], out, is_leaf=istuple)
        return pick(0), pick(1), pick(2)

    # two programs, so that the gradient's temporaries and Adam's moments
    # are not live in one program at once
    grad_jit = jax.jit(grad)
    update_jit = jax.jit(update, donate_argnums=(0, 1, 2, 3))

    def step(params, mu, nu, tokens, targets, t):
        lval, grads, gn = grad_jit(params, tokens, targets)
        params, mu, nu = update_jit(params, mu, nu, grads, gn, t)
        return params, mu, nu, lval, gn

    return step


def change_norms(conf, seed_key, params, weight_dtype):
    """Per-leaf norms of params - (the seed's initial weights), the initial
    weights made again inside the call."""
    p0 = values_in(layout(conf), seed_key, weight_dtype)
    return leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), params, p0))


# ---------------------------------------------------------------------------
# operations and bytes (the work of the mathematics, from shapes)
# ---------------------------------------------------------------------------


def attn_proj_token_flops(m: Dims) -> int:
    """Forward FLOPs per token of one layer's latent-attention
    projections: W_q, W_kv_a, W_kv_b, W_o."""
    H = m.heads
    return 2 * (m.d * H * m.qk_dim + m.d * (m.kv_rank + m.rope)
                + m.kv_rank * H * (m.nope + m.v_dim) + H * m.v_dim * m.d)


def expert_pair_flops(m: Dims) -> int:
    """Forward FLOPs of one (token, expert) pair through a routed expert."""
    return 3 * 2 * m.d * m.expert_ff


def token_flops(m: Dims) -> int:
    """Forward matmul FLOPs per token, all layers and the head, except the
    routed experts (counted per pair) and the attention scores."""
    moe = 3 * 2 * m.d * m.expert_ff * m.shared + 2 * m.d * m.experts
    return (m.layers * attn_proj_token_flops(m) + m.dense_layers * 3 * 2 * m.d * m.d_ff
            + m.moe_layers * moe + 2 * m.d * m.vocab)


def attention_flops(m: Dims, batch: int, seq: int) -> int:
    """Forward FLOPs of one layer's QK^T (qk width) and PV (v width) over
    the causal pairs."""
    return 2 * batch * m.heads * (m.qk_dim + m.v_dim) * causal_pairs(seq)


def train_step_flops(m: Dims, batch: int, seq: int, held_pairs: int) -> int:
    """Model FLOPs of one training step: forward and backward (twice the
    forward) of every matmul, causal attention on its unmasked half, the
    routed experts at the (token, expert) pairs routed to held experts
    (``held_pairs``, summed over layers, as the program counts them); no
    recomputation."""
    fwd = (batch * seq * token_flops(m) + held_pairs * expert_pair_flops(m)
           + m.layers * attention_flops(m, batch, seq))
    return 3 * fwd


def flash_call(kernel: str, m: Dims, batch: int, seq: int, itemsize: int = BF16) -> Tuple[int, int]:
    """(FLOPs, HBM bytes) of one flash kernel call over ``seq`` causal
    positions, queries and keys of the qk width and values of the v width
    (every head its own K and V):

    * ``fwd``/``fwd_lse``: S = QK^T, O = PV; reads q, k, v, writes o (and
      the f32 log-sum-exp rows);
    * ``bwd_dq``: S, dP = dO V^T, dQ = dS K; reads q, k, v, dO, lse,
      delta, writes dq;
    * ``bwd_dkv``: S, dP, dV = P^T dO, dK = dS^T Q; reads q, k, v, dO,
      lse, delta, writes dk, dv."""
    pairs = causal_pairs(seq)
    qk = 2 * batch * m.heads * m.qk_dim * pairs
    pv = 2 * batch * m.heads * m.v_dim * pairs
    a = batch * seq * m.heads * m.qk_dim * itemsize      # q, k, dq or dk
    b = batch * seq * m.heads * m.v_dim * itemsize       # v, o, dO or dv
    row = batch * m.heads * seq * F32_BYTES
    if kernel == "fwd":
        return qk + pv, 2 * a + 2 * b
    if kernel == "fwd_lse":
        return qk + pv, 2 * a + 2 * b + row
    if kernel == "bwd_dq":
        return 2 * qk + pv, 3 * a + 2 * b + 2 * row
    if kernel == "bwd_dkv":
        return 2 * qk + 2 * pv, 3 * a + 3 * b + 2 * row
    raise KeyError(kernel)


def expert_cost(m: Dims, held_pairs: int, remat: bool, itemsize: int = BF16) -> Tuple[int, int]:
    """(FLOPs, HBM bytes) of the held experts' three grouped products over
    ``held_pairs`` pairs (summed over layers) in one training step: the
    forward, again when layers are recomputed, and the backward, whose two
    products per forward product (input and weight gradient) each cost
    the forward's.  A forward pass reads the pairs' rows and the held
    experts' weights and writes each product's output:
    3 pairs x D + 3 held x D x F + 3 pairs x F elements."""
    F = m.expert_ff
    fwd_flops = held_pairs * expert_pair_flops(m)
    fwd_bytes = itemsize * (3 * held_pairs * m.d + 3 * m.moe_layers * m.held * m.d * F
                            + 3 * held_pairs * F)
    passes = (2 if remat else 1) + 2
    return passes * fwd_flops, passes * fwd_bytes
