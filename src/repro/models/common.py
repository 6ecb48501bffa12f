"""Model building blocks: pure-function modules over param pytrees.

No flax/haiku — params are nested dicts of jax.Arrays; every module is an
``init_*(key, ...) -> params`` plus an apply function.  A parallel
``*_specs`` function returns the same pytree shape filled with *logical
axis name tuples* consumed by parallel.sharding.

Conventions:
  * weights stored (in_dim, out_dim); y = x @ w
  * attention heads: q heads H, kv heads Hk (GQA), head_dim Dh
  * dtype policy via ``DTypes(param, compute)``

``attention`` is the models' one dense attention layer and ``sdpa`` their
one attention core: every masked softmax over attention logits under
``models/`` is ``sdpa``'s, and ``sdpa`` is the one place where the flash
kernel is chosen.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..parallel.sharding import _manual_axes_in_context, current_mesh, shard_hint

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class DTypes:
    param: Any = jnp.float32
    compute: Any = jnp.float32

    def p(self, x):
        return x.astype(self.param)

    def c(self, x):
        return x.astype(self.compute)


def trunc_normal(key, shape, scale, dtype):
    x = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
    return (x * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Linear / norm / embedding
# ---------------------------------------------------------------------------


def init_linear(key, d_in: int, d_out: int, dt: DTypes) -> Params:
    scale = 1.0 / math.sqrt(d_in)
    return {"w": trunc_normal(key, (d_in, d_out), scale, dt.param)}


def linear_specs(axes: Tuple[Optional[str], Optional[str]]) -> Params:
    return {"w": axes}


def linear(p: Params, x: jax.Array, dt: DTypes) -> jax.Array:
    return x @ dt.c(p["w"])


def init_rmsnorm(d: int, dt: DTypes) -> Params:
    return {"scale": jnp.ones((d,), dt.param)}


def rmsnorm_specs() -> Params:
    return {"scale": (None,)}


def rmsnorm(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * p["scale"].astype(jnp.float32)).astype(dtype)


def init_layernorm(d: int, dt: DTypes) -> Params:
    return {"scale": jnp.ones((d,), dt.param), "bias": jnp.zeros((d,), dt.param)}


def layernorm_specs() -> Params:
    return {"scale": (None,), "bias": (None,)}


def layernorm(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(dtype)


def init_embedding(key, vocab: int, d: int, dt: DTypes) -> Params:
    return {"table": trunc_normal(key, (vocab, d), d ** -0.5, dt.param)}


def embedding_specs() -> Params:
    return {"table": ("vocab", "embed")}


def embed(p: Params, ids: jax.Array, dt: DTypes) -> jax.Array:
    out = jnp.take(dt.c(p["table"]), ids, axis=0)
    return shard_hint(out, ("batch", "seq", "embed"))


def unembed(p: Params, x: jax.Array, dt: DTypes) -> jax.Array:
    logits = x @ dt.c(p["table"]).T
    return shard_hint(logits, ("batch", "seq", "vocab"))


# ---------------------------------------------------------------------------
# Rotary embeddings (standard + M-RoPE)
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float = 10000.0) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """x: (B, S, H, Dh); positions: (B, S) int32."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)                       # (Dh/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, Dh/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(
    x: jax.Array, positions3: jax.Array, sections: Tuple[int, int, int],
    theta: float = 1000000.0,
) -> jax.Array:
    """Qwen2-VL M-RoPE: positions3 (3, B, S) = (temporal, height, width);
    the Dh/2 frequency slots are split into 3 sections, each rotated by its
    own position stream [arXiv:2409.12191]."""
    dh = x.shape[-1]
    half = dh // 2
    assert sum(sections) == half, (sections, half)
    freqs = rope_freqs(dh, theta)                       # (half,)
    sec_ids = jnp.concatenate(
        [jnp.full((s,), i, jnp.int32) for i, s in enumerate(sections)]
    )                                                   # (half,)
    # for each frequency slot pick the matching position stream
    pos_slot = jnp.moveaxis(positions3, 0, -1)[..., sec_ids]  # (B, S, half)
    ang = pos_slot.astype(jnp.float32) * freqs                # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA, optional sliding window / qk-norm / cross)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    causal: bool = True
    window: Optional[int] = None        # sliding-window span (local layers)
    qk_norm: bool = False
    rope_theta: float = 10000.0
    mrope_sections: Optional[Tuple[int, int, int]] = None
    use_bias: bool = False
    softmax_scale: Optional[float] = None


def init_attention(key, cfg: AttnConfig, dt: DTypes) -> Params:
    ks = jax.random.split(key, 6)
    D, H, Hk, Dh = cfg.d_model, cfg.heads, cfg.kv_heads, cfg.head_dim
    p: Params = {
        "wq": init_linear(ks[0], D, H * Dh, dt),
        "wk": init_linear(ks[1], D, Hk * Dh, dt),
        "wv": init_linear(ks[2], D, Hk * Dh, dt),
        "wo": init_linear(ks[3], H * Dh, D, dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(Dh, dt)
        p["k_norm"] = init_rmsnorm(Dh, dt)
    return p


def attention_specs(cfg: AttnConfig) -> Params:
    p: Params = {
        "wq": linear_specs(("fsdp", "heads")),
        "wk": linear_specs(("fsdp", "heads")),
        "wv": linear_specs(("fsdp", "heads")),
        "wo": linear_specs(("heads", "fsdp")),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_specs()
        p["k_norm"] = rmsnorm_specs()
    return p


def sdpa(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool, window: Optional[int], scale: float,
    q_offset: jax.Array | int = 0,
    is_global: Optional[jax.Array] = None,
    impl: str = "ref",
) -> jax.Array:
    """Scaled dot-product attention with GQA: the models' one attention
    core, and the one place the flash kernel is chosen.

    q: (B, Sq, H, Dqk); k: (B, Skv, Hk, Dqk); v: (B, Skv, Hk, Dv) ->
    (B, Sq, H, Dv).  Query i sits at position ``q_offset + i`` of the K/V
    timeline (decode over a cache filled up to ``q_offset``; later cache
    positions are masked).  The mask is causal & (window | ``is_global``):
    a traced per-layer ``is_global`` switches the sliding window off
    (gemma-style local:global layers inside one scan).

    ``impl="ref"`` is the jnp body, f32 logits and softmax; the kernels'
    oracle is kernels/flash_attention/ref.py.  ``impl="flash"`` runs the
    Pallas kernels (``_flash_attention``) for training and prefill
    (``q_offset`` 0); they have no path for a window.
    """
    if impl == "flash":
        if window is not None:
            # the kernels are called without a window: a layer's window may
            # be switched by a traced flag, which their static mask cannot follow
            raise ValueError(
                f"attn_impl={impl!r} has no path for sliding-window layers "
                f"(window={window}); use attn_impl='ref'"
            )
        return _flash_attention(q, k, v, causal, scale)
    if impl != "ref":
        raise ValueError(f"unknown attention impl {impl!r}; use 'ref' or 'flash'")
    B, Sq, H, Dh = q.shape
    Skv, Hk = k.shape[1], k.shape[2]
    group = H // Hk
    qf = q.astype(jnp.float32) * scale
    qg = qf.reshape(B, Sq, Hk, group, Dh)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(jnp.float32))
    qpos = jnp.arange(Sq)[:, None] + q_offset
    kpos = jnp.arange(Skv)[None, :]
    mask = kpos <= qpos if causal else jnp.ones((Sq, Skv), bool)
    if window is not None:
        in_window = kpos > qpos - window
        mask = mask & (in_window if is_global is None else in_window | is_global)
    logits = jnp.where(mask[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, v.shape[-1]).astype(q.dtype)


def _flash_attention(q, k, v, causal, scale):
    """Flash attention in model layout, placed per shard so that scores
    never materialize in HBM.

    * No mesh in context: the kernel is called directly.
    * Under a mesh: a shard_map island with batch over the DP axes and
      heads over the TP axis.  Inside a manual region (the ``manual_hier``
      trainer) the arrays are already per-shard on the manual axes, so the
      island spans only the remaining auto axes, and the kernel is called
      directly when none remain.

    GQA KV heads are broadcast to the query heads before an island so the
    head dim shards cleanly (the kernels reduce dk/dv back over the group)."""
    from ..kernels.flash_attention.ops import flash_attention

    def kernel(q, k, v):
        return flash_attention(q, k, v, causal=causal, scale=scale)

    mesh = current_mesh()
    manual = _manual_axes_in_context() or set()
    auto = [a for a in mesh.axis_names if a not in manual] if mesh is not None else []
    if not auto:
        return kernel(q, k, v)
    B, S, H, Dh = q.shape
    group = H // k.shape[2]
    if group > 1:
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    dp = tuple(a for a in ("pod", "data") if a in auto)
    tp = "model" if "model" in auto else None
    bspec = dp if (dp and B % math.prod(mesh.shape[a] for a in dp) == 0) else None
    hspec = tp if (tp and H % mesh.shape[tp] == 0) else None
    spec = P(bspec, None, hspec, None)
    return jax.shard_map(
        kernel, mesh=jax.sharding.get_abstract_mesh() if manual else mesh,
        in_specs=(spec, spec, spec), out_specs=spec,
        axis_names=set(auto), check_vma=False,
    )(q, k, v)


def attention(
    p: Params,
    cfg: AttnConfig,
    x: jax.Array,
    positions: jax.Array,
    dt: DTypes,
    kv_cache: Optional[Tuple[jax.Array, jax.Array]] = None,
    cache_index: Optional[jax.Array] = None,
    positions3: Optional[jax.Array] = None,
    xattn_kv: Optional[jax.Array] = None,
    is_global: Optional[jax.Array] = None,
    impl: str = "ref",
) -> Tuple[jax.Array, Optional[Tuple[jax.Array, jax.Array]]]:
    """The models' one dense attention layer: projections, qk-norm, RoPE
    (M-RoPE where ``positions3`` is given), ``sdpa``, output projection.
    Returns (output, updated_kv_cache).

    * training/prefill: kv_cache=None -> attends within x at ``impl``.
    * decode: kv_cache=(k, v) (B, S_max, Hk, Dh), cache_index = filled len;
      the jnp body attends over the whole cache, whatever ``impl`` says.
    * cross-attention: xattn_kv = encoder states (keys/values from there).

    ``is_global`` (a traced per-layer flag) switches ``cfg.window`` off.
    """
    B, S, D = x.shape
    H, Hk, Dh = cfg.heads, cfg.kv_heads, cfg.head_dim
    src = xattn_kv if xattn_kv is not None else x
    q = linear(p["wq"], x, dt).reshape(B, S, H, Dh)
    k = linear(p["wk"], src, dt).reshape(B, src.shape[1], Hk, Dh)
    v = linear(p["wv"], src, dt).reshape(B, src.shape[1], Hk, Dh)
    q = shard_hint(q, ("batch", "seq", "heads", "head_dim"))
    k = shard_hint(k, ("batch", "seq", "kv_heads", "head_dim"))
    v = shard_hint(v, ("batch", "seq", "kv_heads", "head_dim"))
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q)
        k = rmsnorm(p["k_norm"], k)
    if xattn_kv is None:
        if cfg.mrope_sections is not None and positions3 is not None:
            q = apply_mrope(q, positions3, cfg.mrope_sections, cfg.rope_theta)
            k = apply_mrope(k, positions3, cfg.mrope_sections, cfg.rope_theta)
        else:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
    scale = cfg.softmax_scale or (1.0 / math.sqrt(Dh))
    if kv_cache is None:
        out = sdpa(q, k, v, causal=cfg.causal and xattn_kv is None, window=cfg.window,
                   scale=scale, is_global=is_global, impl=impl)
        out = shard_hint(out.reshape(B, S, H * Dh), ("batch", "seq", "heads"))
        return linear(p["wo"], out, dt), None
    with jax.named_scope("kv_cache"):
        # the step's cache traffic: write the new K/V, then read the whole
        # cache in the two products over it
        ck, cv = kv_cache
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), cache_index, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), cache_index, axis=1)
        # causal in the cache's timeline: positions past the fill are masked
        out = sdpa(q, ck, cv, causal=True, window=cfg.window, scale=scale,
                   q_offset=cache_index, is_global=is_global)
    return linear(p["wo"], out.reshape(B, S, H * Dh), dt), (ck, cv)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def init_swiglu(key, d: int, d_ff: int, dt: DTypes) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "wi": init_linear(ks[0], d, d_ff, dt),
        "wg": init_linear(ks[1], d, d_ff, dt),
        "wo": init_linear(ks[2], d_ff, d, dt),
    }


def swiglu_specs() -> Params:
    return {
        "wi": linear_specs(("fsdp", "mlp")),
        "wg": linear_specs(("fsdp", "mlp")),
        "wo": linear_specs(("mlp", "fsdp")),
    }


def swiglu(p: Params, x: jax.Array, dt: DTypes) -> jax.Array:
    h = jax.nn.silu(linear(p["wg"], x, dt)) * linear(p["wi"], x, dt)
    h = shard_hint(h, ("batch", "seq", "mlp"))
    return linear(p["wo"], h, dt)


def init_gelu_mlp(key, d: int, d_ff: int, dt: DTypes) -> Params:
    ks = jax.random.split(key, 2)
    return {
        "wi": init_linear(ks[0], d, d_ff, dt),
        "wo": init_linear(ks[1], d_ff, d, dt),
    }


def gelu_mlp_specs() -> Params:
    return {
        "wi": linear_specs(("fsdp", "mlp")),
        "wo": linear_specs(("mlp", "fsdp")),
    }


def gelu_mlp(p: Params, x: jax.Array, dt: DTypes) -> jax.Array:
    h = jax.nn.gelu(linear(p["wi"], x, dt))
    h = shard_hint(h, ("batch", "seq", "mlp"))
    return linear(p["wo"], h, dt)


# ---------------------------------------------------------------------------
# Stacked-layer utilities (scan over layers)
# ---------------------------------------------------------------------------


def stack_params(key, n: int, init_fn) -> Params:
    """init_fn(key_i) -> layer params; returns pytree with leading n dim."""
    keys = jax.random.split(key, n)
    layers = [init_fn(k) for k in keys]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)


def stacked_specs(layer_specs: Params) -> Params:
    """Prefix every leaf's logical axes with the 'stack' (layer) axis."""
    return jax.tree_util.tree_map(
        lambda axes: ("stack",) + tuple(axes),
        layer_specs,
        is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(n, (str, type(None))) for n in x),
    )
