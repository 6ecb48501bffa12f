"""Plain reference of a dense decoder with grouped-query attention
(the Qwen2 / Qwen3 language models), in float32 at "highest" precision.

It imports nothing of the program under test.  It follows the program's
equations where they depart from the publications; each configuration
file lists those departures, and they are:

* token embeddings are multiplied by sqrt(hidden_size) (the program's
  Gemma-style convention; Qwen2 and Qwen3 do not scale);
* the attention projections have no bias (Qwen2 has one on q, k, v);
* AdamW decays every leaf of two or more dimensions, which takes in the
  RMSNorm scales stacked over layers and leaves out the final norm.

Everything is computed in blocks so that a 32k-token sequence fits on one
chip: attention over blocks of query rows, the feed-forward network and
the output head over blocks of rows, each block rematerialized in the
backward pass.

``Numerics`` names how matrix products are computed.  ``F32`` is the
reference.  ``FP8`` is the control: every matrix product takes its
operands rounded to float8 e4m3 with one scale per tensor (amax / 448),
the precision step below the bfloat16 that the configurations state.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from harness.weights import Leaf, values_in

HIGHEST = jax.lax.Precision.HIGHEST
NEG = -1e30
BLOCK_BYTES = 256 * 2**20     # one attention block's score array, f32


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    eps: float
    theta: float
    tied: bool
    qk_norm: bool


def dims(conf: Dict[str, Any]) -> Dims:
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    return Dims(
        layers=conf["num_hidden_layers"], d=d, heads=h,
        kv_heads=conf["num_key_value_heads"],
        head_dim=conf.get("head_dim") or d // h,
        d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
        eps=conf["rms_norm_eps"], theta=float(conf["rope_theta"]),
        tied=bool(conf["tie_word_embeddings"]), qk_norm=bool(conf["qk_norm"]),
    )


def program_kwargs(conf: Dict[str, Any]) -> Dict[str, Any]:
    """The program's ``ModelConfig`` fields for this configuration, as
    plain values (the harness builds the program's object from them)."""
    m = dims(conf)
    kw = dict(
        family=conf["program_family"], num_layers=m.layers, d_model=m.d,
        heads=m.heads, kv_heads=m.kv_heads, head_dim=m.head_dim, d_ff=m.d_ff,
        vocab=m.vocab, qk_norm=m.qk_norm, rope_theta=m.theta, tie_embeddings=m.tied,
    )
    rs = conf.get("rope_scaling") or {}
    if rs.get("type") == "mrope":
        kw["mrope_sections"] = tuple(rs["mrope_section"])
    return kw


def layout(conf: Dict[str, Any]):
    """Parameter tree (names and shapes as the program lays them out, the
    layers stacked on a leading axis) with each leaf's initialisation."""
    m = dims(conf)
    L, D, H, Hk, Dh, F = m.layers, m.d, m.heads, m.kv_heads, m.head_dim, m.d_ff
    attn = {
        "wq": {"w": Leaf((L, D, H * Dh), D)},
        "wk": {"w": Leaf((L, D, Hk * Dh), D)},
        "wv": {"w": Leaf((L, D, Hk * Dh), D)},
        "wo": {"w": Leaf((L, H * Dh, D), H * Dh)},
    }
    if m.qk_norm:
        attn["q_norm"] = {"scale": Leaf((L, Dh), None)}
        attn["k_norm"] = {"scale": Leaf((L, Dh), None)}
    tree = {
        "embed": {"table": Leaf((m.vocab, D), D)},
        "layers": {
            "ln1": {"scale": Leaf((L, D), None)},
            "attn": attn,
            "ln2": {"scale": Leaf((L, D), None)},
            "ffn": {"wi": {"w": Leaf((L, D, F), D)}, "wg": {"w": Leaf((L, D, F), D)},
                    "wo": {"w": Leaf((L, F, D), F)}},
        },
        "final_norm": {"scale": Leaf((D,), None)},
    }
    if not m.tied:
        tree["lm_head"] = {"w": Leaf((D, m.vocab), D)}
    return tree


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------


class Numerics(NamedTuple):
    fp8: bool


F32 = Numerics(fp8=False)
FP8 = Numerics(fp8=True)
_E4M3_MAX = 448.0


def _fp8(x):
    """Round to e4m3 with a per-tensor scale; the rounding passes no
    gradient of its own (straight through).  Returns (values, scale) with
    ``values`` exact in bfloat16."""
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
    scale = jnp.where(amax > 0, amax / _E4M3_MAX, 1.0)
    y = x / scale
    q = y + jax.lax.stop_gradient(y.astype(jnp.float8_e4m3fn).astype(jnp.float32) - y)
    return q, scale


def mm(num: Numerics, spec: str, a, b):
    """einsum in f32 at "highest" precision, or with fp8-rounded operands."""
    if not num.fp8:
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    qa, sa = _fp8(a)
    qb, sb = _fp8(b)
    return jnp.einsum(spec, qa, qb, precision=HIGHEST) * (sa * sb)


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, positions, theta):
    """x (..., S, heads, Dh), positions (..., S): rotate halves."""
    dh = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = positions[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _q_block(n_q: int, batch: int, heads: int, n_k: int) -> int:
    blk = n_q
    while blk > 8 and (batch * heads * blk * n_k * 4 > BLOCK_BYTES or n_q % blk):
        blk //= 2
    return blk if n_q % blk == 0 else 1


def attention(num, q, k, v, q_pos, k_pos):
    """Causal GQA attention in blocks of query rows.  q (B, Sq, H, Dh),
    k/v (B, Sk, Hk, Dh), q_pos (Sq,), k_pos (Sk,) absolute positions."""
    B, Sq, H, Dh = q.shape
    Hk = k.shape[2]
    G = H // Hk
    scale = 1.0 / math.sqrt(Dh)
    blk = _q_block(Sq, B, H, k.shape[1])
    qb = q.reshape(B, Sq // blk, blk, Hk, G, Dh).swapaxes(0, 1)
    pb = q_pos.reshape(Sq // blk, blk)

    @jax.checkpoint
    def one(args):
        qi, pi = args
        s = mm(num, "bqhgd,bkhd->bhgqk", qi * scale, k)
        s = jnp.where((k_pos[None, :] <= pi[:, None])[None, None, None], s, NEG)
        p = jax.nn.softmax(s, axis=-1)
        return mm(num, "bhgqk,bkhd->bqhgd", p, v)

    out = jax.lax.map(one, (qb, pb))                       # (nb, B, blk, Hk, G, Dh)
    return out.swapaxes(0, 1).reshape(B, Sq, H * Dh)


def _seq_blocks(fn, xs, rows: int):
    """Apply ``fn`` to blocks of ``rows`` positions of every array in
    ``xs`` (each (B, S, ...)), rematerialized; the batch stays whole
    inside a block, so a batch sharded over chips stays sharded."""
    S = xs[0].shape[1]
    r = rows
    while S % r:
        r //= 2
    split = lambda x: x.reshape(x.shape[0], S // r, r, *x.shape[2:]).swapaxes(0, 1)
    out = jax.lax.map(jax.checkpoint(lambda a: fn(*a)), tuple(split(x) for x in xs))
    return out.swapaxes(0, 1).reshape(out.shape[1], S, *out.shape[3:])


def ffn(num, lp, x):
    def f(h):
        g = mm(num, "bsd,df->bsf", h, lp["ffn"]["wg"]["w"])
        u = mm(num, "bsd,df->bsf", h, lp["ffn"]["wi"]["w"])
        return mm(num, "bsf,fd->bsd", jax.nn.silu(g) * u, lp["ffn"]["wo"]["w"])

    return _seq_blocks(f, (x,), max(1, 4096 // x.shape[0]))


def layer(num, m: Dims, lp, x, positions, prefix=None):
    """One decoder layer: (x out, (k, v) of the S new positions, k
    rotated, as a cache holds them).  x (B, S, D); positions (S,).
    ``prefix`` = (k, v) of earlier positions (B, P, Hk, Dh), already
    rotated, that the queries also attend to (decode against a filled
    cache)."""
    B, S, D = x.shape
    H, Hk, Dh = m.heads, m.kv_heads, m.head_dim
    h = rmsnorm(x, lp["ln1"]["scale"], m.eps)
    q = mm(num, "bsd,de->bse", h, lp["attn"]["wq"]["w"]).reshape(B, S, H, Dh)
    k = mm(num, "bsd,de->bse", h, lp["attn"]["wk"]["w"]).reshape(B, S, Hk, Dh)
    v = mm(num, "bsd,de->bse", h, lp["attn"]["wv"]["w"]).reshape(B, S, Hk, Dh)
    if m.qk_norm:
        q = rmsnorm(q, lp["attn"]["q_norm"]["scale"], m.eps)
        k = rmsnorm(k, lp["attn"]["k_norm"]["scale"], m.eps)
    q = rope(q, positions[None], m.theta)
    k = rope(k, positions[None], m.theta)
    new = (k, v)
    k_pos = positions
    if prefix is not None:
        pk, pv = prefix
        k = jnp.concatenate([pk, k], axis=1)
        v = jnp.concatenate([pv, v], axis=1)
        k_pos = jnp.concatenate([jnp.arange(pk.shape[1]), positions])
    o = attention(num, q, k, v, positions, k_pos)
    x = x + mm(num, "bse,ed->bsd", o, lp["attn"]["wo"]["w"])
    h = rmsnorm(x, lp["ln2"]["scale"], m.eps)
    return x + ffn(num, lp, h), new


def hidden(num, m: Dims, params, tokens, positions):
    """Final hidden states (B, S, D) for tokens (B, S) at positions (S,)."""
    x = params["embed"]["table"][tokens] * math.sqrt(m.d)

    def body(x, lp):
        return jax.checkpoint(lambda lp, x: layer(num, m, lp, x, positions)[0])(lp, x), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return rmsnorm(x, params["final_norm"]["scale"], m.eps)


def head(m: Dims, params):
    return params["embed"]["table"].T if m.tied else params["lm_head"]["w"]


def loss(num, m: Dims, params, tokens, targets):
    """Mean next-token cross entropy over every position, in f32."""
    B, S = tokens.shape
    x = hidden(num, m, params, tokens, jnp.arange(S))
    w = head(m, params)

    def nll(xb, tb):
        logits = mm(num, "bsd,dv->bsv", xb, w)
        gold = jnp.take_along_axis(logits, tb[..., None], axis=-1)[..., 0]
        return jax.nn.logsumexp(logits, axis=-1) - gold

    return jnp.mean(_seq_blocks(nll, (x, targets), max(1, 2048 // B)))


# ---------------------------------------------------------------------------
# training: the first steps of AdamW
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AdamW:
    lr: float
    b1: float
    b2: float
    eps: float
    weight_decay: float
    grad_clip: float
    warmup_steps: int
    total_steps: int
    min_lr_frac: float


def leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree_util.tree_leaves(tree)]


def make_train_step(conf, opt: AdamW, num: Numerics, fault: Optional[str] = None,
                    replicas: int = 1):
    """(params, mu, nu, tokens, targets, step) -> (params, mu, nu, loss,
    raw gradient leaf norms), the inputs donated.

    ``fault`` puts a broken program's arithmetic in the program's place:
    ``"half"`` takes loss and gradient over the first half of the rows
    only; ``"no_exchange"`` keeps the loss over all rows (the program
    averages it across replicas) but takes the gradient of the first
    replica's rows alone, divided by ``replicas``, as a data-parallel step
    does when its all-reduce is left out."""
    m = dims(conf)

    def value_and_grad(params, tokens, targets):
        if fault is None:
            return jax.value_and_grad(lambda p: loss(num, m, p, tokens, targets))(params)
        if fault == "half":
            h = tokens.shape[0] // 2
            return jax.value_and_grad(lambda p: loss(num, m, p, tokens[:h], targets[:h]))(params)
        if fault == "no_exchange":
            r = tokens.shape[0] // replicas
            g = jax.grad(lambda p: loss(num, m, p, tokens[:r], targets[:r]))(params)
            return (loss(num, m, params, tokens, targets),
                    jax.tree_util.tree_map(lambda x: x / replicas, g))
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
# decode: logits at every position of a sequence after a filled prefix
# ---------------------------------------------------------------------------


def decode_logits(num, conf, params, prefix_k, prefix_v, tokens, start: int):
    """(logits (B, n, V), k, v) of feeding ``tokens`` (B, n) at positions
    start .. start + n - 1, after a cache whose first ``start`` positions
    hold ``prefix_k``/``prefix_v`` (L, B, start, Hk, Dh), already rotated;
    k and v (L, B, n, Hk, Dh) are what a cache holds at the n positions."""
    m = dims(conf)
    B, n = tokens.shape
    positions = start + jnp.arange(n)
    x = params["embed"]["table"][tokens] * math.sqrt(m.d)

    def body(x, xs):
        lp, pk, pv = xs
        return layer(num, m, lp, x, positions, prefix=(pk, pv))

    x, (k, v) = jax.lax.scan(body, x, (params["layers"], prefix_k, prefix_v))
    x = rmsnorm(x, params["final_norm"]["scale"], m.eps)
    return mm(num, "bsd,dv->bsv", x, head(m, params)), k, v
