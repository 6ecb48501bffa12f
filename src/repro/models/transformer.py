"""Decoder-only transformer LM (dense GQA / MoE / local:global / M-RoPE /
latent attention).

Covers: qwen3-8b, llama3.2-3b, granite-20b, gemma3-4b (5:1 local:global
sliding window), qwen2-vl-2b (M-RoPE; embeddings provided by the stub
frontend), qwen3-moe-235b-a22b (MoE) and moonlight-16b-a3b (the
DeepSeek-V3 block: multi-head latent attention, a leading dense layer,
sigmoid-routed experts with shared experts).

Layers are stacked and executed with ``jax.lax.scan`` so the 94-layer
configs trace/compile in O(1) layers: one stack, or, with
``first_dense_layers``, a stack of leading dense-FFN layers
(``dense_layers``) and then one of MoE layers (``layers``).  Per-layer
heterogeneity inside a stack (gemma's every-Nth-global pattern) rides
along as a scanned boolean that switches the attention mask dynamically.

The dense attention layer is ``common.attention`` (projections, qk-norm,
RoPE or M-RoPE, ``common.sdpa``); ``cfg.attn_impl`` picks ``sdpa``'s jnp
body or the flash kernel, and decoding attends over the cache through the
same layer.

Latent attention (``cfg.mla``, training and prefill only): q = h W_q as
H x [nope | rope]; [c | k_rope] = h W_kv_a; c <- RMSNorm(c);
[k_nope | v] = c W_kv_b as H x [nope | v]; RoPE (rotate-half layout) on
q_rope and on the one k_rope head that all heads share; ``common.sdpa``
of q.k / sqrt(nope + rope) against v.  The latent cache of decoding is not
implemented: ``init_cache`` and ``decode_step`` refuse such configs.

API (used by train/serve/launch):
    init(key, cfg)                      -> params
    param_specs(cfg)                    -> logical-axis spec tree
    forward(params, cfg, batch)         -> (logits, aux_loss)
    init_cache(cfg, batch, cache_len)   -> cache
    decode_step(params, cfg, cache, batch) -> (logits, cache)
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..parallel.sharding import shard_hint
from . import common as C
from .common import DTypes, Params
from .moe import MoEConfig, init_moe, moe_ffn, moe_specs


def _dt(cfg: ModelConfig) -> DTypes:
    return DTypes(param=cfg.param_dtype, compute=cfg.compute_dtype)


def _attn_cfg(cfg: ModelConfig) -> C.AttnConfig:
    return C.AttnConfig(
        d_model=cfg.d_model,
        heads=cfg.heads,
        kv_heads=cfg.kv_heads,
        head_dim=cfg.resolved_head_dim,
        causal=True,
        window=cfg.sliding_window,
        qk_norm=cfg.qk_norm,
        rope_theta=cfg.rope_theta,
        mrope_sections=cfg.mrope_sections,
    )


def _moe_cfg(cfg: ModelConfig) -> Optional[MoEConfig]:
    if cfg.moe is None:
        return None
    return MoEConfig(
        d_model=cfg.d_model,
        d_ff=cfg.moe.d_ff,
        num_experts=cfg.moe.num_experts,
        top_k=cfg.moe.top_k,
        capacity_factor=cfg.moe.capacity_factor,
        aux_loss_coeff=cfg.moe.aux_loss_coeff,
        num_shared_experts=cfg.moe.num_shared_experts,
        ep_axis=cfg.moe_ep_axis,
        tp_axis="model" if cfg.moe_tp else "__none__",
        token_scatter=cfg.moe_token_scatter,
        scoring=cfg.moe.scoring,
        routed_scale=cfg.moe.routed_scale,
        held_experts=cfg.moe.held_experts,
        first_held=cfg.moe.first_held,
    )


# ---------------------------------------------------------------------------
# init / specs
# ---------------------------------------------------------------------------


def _stacks(cfg: ModelConfig) -> Tuple[Tuple[str, int, bool], ...]:
    """(params key, layers, MoE FFN) of each layer stack, in order."""
    n_dense = cfg.first_dense_layers if cfg.moe is not None else 0
    head = (("dense_layers", n_dense, False),) if n_dense else ()
    return head + (("layers", cfg.num_layers - n_dense, cfg.moe is not None),)


def _init_mla(key, cfg: ModelConfig, dt: DTypes) -> Params:
    a, D, H = cfg.mla, cfg.d_model, cfg.heads
    ks = jax.random.split(key, 4)
    return {
        "wq": C.init_linear(ks[0], D, H * (a.qk_nope_head_dim + a.qk_rope_head_dim), dt),
        "wkv_a": C.init_linear(ks[1], D, a.kv_lora_rank + a.qk_rope_head_dim, dt),
        "kv_norm": C.init_rmsnorm(a.kv_lora_rank, dt),
        "wkv_b": C.init_linear(ks[2], a.kv_lora_rank, H * (a.qk_nope_head_dim + a.v_head_dim), dt),
        "wo": C.init_linear(ks[3], H * a.v_head_dim, D, dt),
    }


def _mla_specs() -> Params:
    return {
        "wq": C.linear_specs(("fsdp", "heads")),
        "wkv_a": C.linear_specs(("fsdp", None)),
        "kv_norm": C.rmsnorm_specs(),
        "wkv_b": C.linear_specs((None, "heads")),
        "wo": C.linear_specs(("heads", "fsdp")),
    }


def _init_layer(key, cfg: ModelConfig, moe: bool) -> Params:
    dt = _dt(cfg)
    ks = jax.random.split(key, 4)
    p: Params = {
        "ln1": C.init_rmsnorm(cfg.d_model, dt),
        "attn": (_init_mla(ks[0], cfg, dt) if cfg.mla is not None
                 else C.init_attention(ks[0], _attn_cfg(cfg), dt)),
        "ln2": C.init_rmsnorm(cfg.d_model, dt),
    }
    if moe:
        p["moe"] = init_moe(ks[1], _moe_cfg(cfg), dt)
    else:
        p["ffn"] = C.init_swiglu(ks[2], cfg.d_model, cfg.d_ff, dt)
    return p


def _layer_specs(cfg: ModelConfig, moe: bool) -> Params:
    p: Params = {
        "ln1": C.rmsnorm_specs(),
        "attn": _mla_specs() if cfg.mla is not None else C.attention_specs(_attn_cfg(cfg)),
        "ln2": C.rmsnorm_specs(),
    }
    if moe:
        p["moe"] = moe_specs(_moe_cfg(cfg))
    else:
        p["ffn"] = C.swiglu_specs()
    return p


def init(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 3)
    p: Params = {
        "embed": C.init_embedding(ks[0], cfg.vocab, cfg.d_model, _dt(cfg)),
        "final_norm": C.init_rmsnorm(cfg.d_model, _dt(cfg)),
    }
    for name, n, moe in _stacks(cfg):
        k = ks[1] if name == "layers" else jax.random.fold_in(ks[1], 1)
        p[name] = C.stack_params(k, n, lambda k, moe=moe: _init_layer(k, cfg, moe))
    if not cfg.tie_embeddings:
        p["lm_head"] = C.init_linear(ks[2], cfg.d_model, cfg.vocab, _dt(cfg))
    return p


def param_specs(cfg: ModelConfig) -> Params:
    p: Params = {
        "embed": C.embedding_specs(),
        "final_norm": C.rmsnorm_specs(),
    }
    for name, _, moe in _stacks(cfg):
        p[name] = C.stacked_specs(_layer_specs(cfg, moe))
    if not cfg.tie_embeddings:
        p["lm_head"] = C.linear_specs(("embed", "vocab"))
    return p


def _is_global_flags(cfg: ModelConfig) -> jax.Array:
    """Per-layer flag: True = full (global) attention."""
    L = cfg.num_layers
    if cfg.sliding_window is None or cfg.global_every is None:
        return jnp.ones((L,), bool)
    idx = jnp.arange(L)
    return (idx % cfg.global_every) == (cfg.global_every - 1)


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------


def _layer_fwd(
    lp: Params,
    cfg: ModelConfig,
    x: jax.Array,
    positions: jax.Array,
    positions3: Optional[jax.Array],
    is_global: jax.Array,
    dt: DTypes,
) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """(x, aux loss, the MoE layer's counters (empty for others))."""
    eps = cfg.rms_norm_eps
    with jax.named_scope("attention"):
        h = C.rmsnorm(lp["ln1"], x, eps)
        if cfg.mla is not None:
            attn_out = _mla_attention(lp["attn"], cfg, h, positions, dt)
        else:
            attn_out, _ = C.attention(
                lp["attn"], _attn_cfg(cfg), h, positions, dt, positions3=positions3,
                is_global=is_global, impl=cfg.attn_impl,
            )
        x = x + attn_out
    stats: Dict[str, jax.Array] = {}
    with jax.named_scope(_ffn_scope(lp)):
        h = C.rmsnorm(lp["ln2"], x, eps)
        if "moe" in lp:
            ffn_out, aux, stats = moe_ffn(lp["moe"], _moe_cfg(cfg), h, dt)
        else:
            ffn_out, aux = C.swiglu(lp["ffn"], h, dt), jnp.zeros((), jnp.float32)
        x = x + ffn_out
    x = shard_hint(x, ("batch", "seq", "embed"))
    return x, aux, stats


def _ffn_scope(lp: Params) -> str:
    return "moe" if "moe" in lp else "mlp"


def _mla_attention(p, cfg: ModelConfig, x, positions, dt):
    """Multi-head latent attention (see the module docstring); x (B, S, D)."""
    a, H = cfg.mla, cfg.heads
    B, S, _ = x.shape
    dn, dr, dv, r = a.qk_nope_head_dim, a.qk_rope_head_dim, a.v_head_dim, a.kv_lora_rank
    q = C.linear(p["wq"], x, dt).reshape(B, S, H, dn + dr)
    q = jnp.concatenate(
        [q[..., :dn], C.apply_rope(q[..., dn:], positions, cfg.rope_theta)], axis=-1)
    with jax.named_scope("mla_kv"):
        kva = C.linear(p["wkv_a"], x, dt)
        c = C.rmsnorm(p["kv_norm"], kva[..., :r], cfg.rms_norm_eps)
        k_rope = C.apply_rope(kva[..., None, r:], positions, cfg.rope_theta)
        kv = C.linear(p["wkv_b"], c, dt).reshape(B, S, H, dn + dv)
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_rope, (B, S, H, dr))], axis=-1)
        v = kv[..., dn:]
    q = shard_hint(q, ("batch", "seq", "heads", "head_dim"))
    k = shard_hint(k, ("batch", "seq", "heads", "head_dim"))
    v = shard_hint(v, ("batch", "seq", "heads", "head_dim"))
    out = C.sdpa(q, k, v, causal=True, window=None, scale=1.0 / math.sqrt(dn + dr),
                 impl=cfg.attn_impl)
    out = shard_hint(out.reshape(B, S, H * dv), ("batch", "seq", "heads"))
    return C.linear(p["wo"], out, dt)


def _embed(params: Params, cfg: ModelConfig, batch, dt: DTypes) -> jax.Array:
    with jax.named_scope("embed"):
        if "embeds" in batch:
            return batch["embeds"].astype(cfg.compute_dtype)
        x = C.embed(params["embed"], batch["tokens"], dt)
        return x * jnp.asarray(math.sqrt(cfg.d_model), cfg.compute_dtype)


def _head(params: Params, cfg: ModelConfig, x: jax.Array, dt: DTypes) -> jax.Array:
    """Final norm and logits."""
    with jax.named_scope("head"):
        x = C.rmsnorm(params["final_norm"], x, cfg.rms_norm_eps)
        if cfg.tie_embeddings:
            return C.unembed(params["embed"], x, dt)
        return C.linear(params["lm_head"], x, dt)


def _zero_stats(cfg: ModelConfig, moe: bool) -> Dict[str, jax.Array]:
    """The counters a stack's layers return, at zero (``moe_ffn_held``'s)."""
    if not moe or cfg.moe.held_experts is None:
        return {}
    return {"moe_tokens_held": jnp.zeros((), jnp.int32),
            "moe_max_load": jnp.zeros((), jnp.float32)}


def _merge_stats(a: Dict[str, jax.Array], b: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """Counters over layers: pairs held add up, the load is the worst."""
    out = dict(a)
    for k, v in b.items():
        if k not in out:
            out[k] = v
        else:
            out[k] = jnp.maximum(out[k], v) if k == "moe_max_load" else out[k] + v
    return out


def forward_with_stats(
    params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array]
) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """``forward`` and the MoE layers' counters over all layers
    (``moe_tokens_held`` summed, ``moe_max_load`` the largest); empty
    where no layer has them."""
    dt = _dt(cfg)
    x = _embed(params, cfg, batch, dt)
    B, S, _ = x.shape
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    positions3 = batch.get("positions3")
    flags = _is_global_flags(cfg)
    fwd = _layer_fwd
    if cfg.remat:
        fwd = jax.checkpoint(
            _layer_fwd, policy=jax.checkpoint_policies.nothing_saveable,
            static_argnums=(1, 6),
        )

    def body(carry, xs):
        x, aux, stats = carry
        lp, is_global = xs
        x, aux_l, st = fwd(lp, cfg, x, positions, positions3, is_global, dt)
        return (x, aux + aux_l, _merge_stats(stats, st)), None

    aux, stats, first = jnp.zeros((), jnp.float32), {}, 0
    with jax.named_scope("layers"):
        for name, n, moe in _stacks(cfg):
            (x, aux, st), _ = jax.lax.scan(
                body, (x, aux, _zero_stats(cfg, moe)), (params[name], flags[first:first + n])
            )
            stats = _merge_stats(stats, st)
            first += n
    logits = _head(params, cfg, x, dt)
    if not cfg.tie_embeddings:
        logits = shard_hint(logits, ("batch", "seq", "vocab"))
    return logits, aux, stats


def forward(
    params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array]
) -> Tuple[jax.Array, jax.Array]:
    """batch: tokens (B,S) int32 [or embeds (B,S,D) for vlm stub],
    positions (B,S) optional, positions3 (3,B,S) for M-RoPE."""
    logits, aux, _ = forward_with_stats(params, cfg, batch)
    return logits, aux


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def _refuse_decode(cfg: ModelConfig) -> None:
    if cfg.mla is not None or len(_stacks(cfg)) > 1:
        raise NotImplementedError(
            f"{cfg.name}: decoding through a cache is not implemented for latent "
            "attention (its latent cache) or for a leading dense-layer stack; "
            "these configurations train and prefill only"
        )


def init_cache(cfg: ModelConfig, batch: int, cache_len: int) -> Dict[str, Any]:
    _refuse_decode(cfg)
    L, Hk, Dh = cfg.num_layers, cfg.kv_heads, cfg.resolved_head_dim
    dtype = cfg.compute_dtype
    return {
        "k": jnp.zeros((L, batch, cache_len, Hk, Dh), dtype),
        "v": jnp.zeros((L, batch, cache_len, Hk, Dh), dtype),
        "index": jnp.zeros((), jnp.int32),
    }


def cache_specs(cfg: ModelConfig) -> Dict[str, Any]:
    return {
        "k": ("stack", "batch", "kv_seq", "kv_heads", "head_dim"),
        "v": ("stack", "batch", "kv_seq", "kv_heads", "head_dim"),
        "index": (),
    }


def decode_step(
    params: Params, cfg: ModelConfig, cache: Dict[str, Any],
    batch: Dict[str, jax.Array],
) -> Tuple[jax.Array, Dict[str, Any]]:
    """One token step: batch has tokens (B,1) [or embeds (B,1,D)] and
    optionally positions3 (3,B,1)."""
    _refuse_decode(cfg)
    dt = _dt(cfg)
    x = _embed(params, cfg, batch, dt)
    B, S, _ = x.shape
    index = cache["index"]
    positions = jnp.broadcast_to(index + jnp.arange(S)[None], (B, S))
    positions3 = batch.get("positions3")
    flags = _is_global_flags(cfg)
    acfg = _attn_cfg(cfg)

    def body(carry, xs):
        x = carry
        lp, ck, cv, is_global = xs
        with jax.named_scope("attention"):
            h = C.rmsnorm(lp["ln1"], x, cfg.rms_norm_eps)
            out, (nk, nv) = C.attention(
                lp["attn"], acfg, h, positions, dt, kv_cache=(ck, cv), cache_index=index,
                positions3=positions3, is_global=is_global,
            )
            x = x + out
        with jax.named_scope(_ffn_scope(lp)):
            h = C.rmsnorm(lp["ln2"], x, cfg.rms_norm_eps)
            if "moe" in lp:
                ffn_out = moe_ffn(lp["moe"], _moe_cfg(cfg), h, dt)[0]
            else:
                ffn_out = C.swiglu(lp["ffn"], h, dt)
            x = x + ffn_out
        return x, (nk, nv)

    with jax.named_scope("layers"):
        x, (nks, nvs) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"], flags)
        )
    new_cache = {"k": nks, "v": nvs, "index": index + S}
    return _head(params, cfg, x, dt), new_cache

