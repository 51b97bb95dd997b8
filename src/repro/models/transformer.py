"""Unified decoder backbone covering all assigned architecture families.

Layer stacking:
  * uniform configs (dense GQA incl. local/global alternation, MLA, MoE,
    RWKV6, audio/VLM backbones) are **scan-stacked**: layer params carry a
    leading L axis and a single lax.scan walks the stack — O(1) HLO size in
    depth, which keeps the 40-pair dry-run grid compilable.  Per-layer
    heterogeneity that is a *value* (the sliding window of gemma2's L/G
    alternation) rides in a (L,) array.
  * hybrid configs (RecurrentGemma's R/R/A pattern) mix param *shapes* and
    code paths per layer, so they use a python loop over per-layer params
    (26 small layers — acceptable HLO).
  * ``first_dense_layers`` (DeepSeek: layer 0 keeps a dense FFN) are peeled
    off the scan and looped.

Caches mirror the stacking: scan-stacked caches carry a leading L axis.

Modality frontends (VLM vision tower, audio codec) are stubs by assignment:
``forward`` accepts precomputed frontend embeddings which are prepended to
the token embeddings.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro import tracing
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models import rglru as RG
from repro.models import rwkv6 as RW
from repro.models.config import ModelConfig

Params = dict[str, Any]


# --------------------------------------------------------------------------
# per-layer block
# --------------------------------------------------------------------------


def _init_block(key, cfg: ModelConfig, kind: str, dense_ffn: bool, dtype) -> Params:
    k1, k2 = jax.random.split(key)
    p: Params = {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
    }
    if kind in ("G", "L"):
        if cfg.kv_lora_rank:
            p["attn"] = MLA.init_mla(k1, cfg, dtype)
        else:
            p["attn"] = L.init_attn(k1, cfg, dtype)
    elif kind == "W":
        p["mix"] = RW.init_rwkv(k1, cfg, dtype)
    elif kind == "R":
        p["mix"] = RG.init_rglru(k1, cfg, dtype)
    else:
        raise ValueError(kind)
    if cfg.n_experts and not dense_ffn:
        p["moe"] = MOE.init_moe(k2, cfg, dtype)
    else:
        p["mlp"] = L.init_mlp(k2, cfg, dtype=dtype)
    return p


def _apply_block(
    p: Params,
    x: jax.Array,
    cfg: ModelConfig,
    kind: str,
    positions: jax.Array,
    window: jax.Array | int,
    cache: Params | None,
):
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind in ("G", "L"):
        if cfg.kv_lora_rank:
            with jax.named_scope(tracing.MLA):
                mix_out, cache = MLA.apply_mla(p["attn"], h, cfg, positions, cache)
        else:
            mix_out, cache = L.apply_attn(p["attn"], h, cfg, positions, window, cache)
    elif kind == "W":
        mix_out, cache = RW.apply_rwkv(p["mix"], h, cfg, cache)
    elif kind == "R":
        mix_out, cache = RG.apply_rglru(p["mix"], h, cfg, cache)
    x = x + mix_out
    h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if "moe" in p:
        ffn_out, aux = MOE.apply_moe(p["moe"], h, cfg)
    else:
        with jax.named_scope(tracing.FFN):
            ffn_out = L.apply_mlp(p["mlp"], h, cfg)
    return x + ffn_out, cache, aux


# --------------------------------------------------------------------------
# windows: per-layer attention window values
# --------------------------------------------------------------------------


def layer_windows(cfg: ModelConfig, serve: bool = False) -> list[int]:
    """Effective per-layer window (0 = full attention)."""
    ws = []
    for kind in cfg.layer_kinds():
        if kind == "L":
            w = cfg.sliding_window or 4096
        elif kind == "G":
            w = 0
        else:
            w = 0
        if serve and cfg.serve_window_override and kind in ("G", "L"):
            w = min(w, cfg.serve_window_override) if w else cfg.serve_window_override
        ws.append(w)
    return ws


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.float32) -> Params:
    kinds = cfg.layer_kinds()
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    params: Params = {
        "embed": L.init_embed(k_embed, cfg, dtype),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    n_pre = cfg.first_dense_layers
    if cfg.uniform:
        n_scan = cfg.n_layers - n_pre
        keys = jax.random.split(k_layers, cfg.n_layers)
        if n_pre:
            params["pre_layers"] = [
                _init_block(keys[i], cfg, kinds[i], dense_ffn=True, dtype=dtype)
                for i in range(n_pre)
            ]
        stack_kind = kinds[n_pre]  # scan body uses one code path
        blocks = [
            _init_block(keys[n_pre + i], cfg, stack_kind, dense_ffn=False, dtype=dtype)
            for i in range(n_scan)
        ]
        params["layers"] = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *blocks)
    else:
        keys = jax.random.split(k_layers, cfg.n_layers)
        params["loop_layers"] = [
            _init_block(keys[i], cfg, kinds[i], dense_ffn=False, dtype=dtype)
            for i in range(cfg.n_layers)
        ]
    if cfg.exit_interval:
        n_exits = cfg.n_layers // cfg.exit_interval
        params["exit_heads"] = (
            jax.random.normal(k_head, (n_exits, cfg.d_model)) * 0.02
        ).astype(dtype)
    return params


def abstract_params(cfg: ModelConfig, dtype=jnp.float32) -> Params:
    """Param pytree of ShapeDtypeStructs — no allocation (for the dry-run)."""
    return jax.eval_shape(
        lambda k: init_params(cfg, k, dtype), jax.ShapeDtypeStruct((2,), jnp.uint32)
    )


# --------------------------------------------------------------------------
# caches
# --------------------------------------------------------------------------


def _init_layer_cache(cfg: ModelConfig, kind: str, batch: int, seq: int, window: int, dtype):
    if kind in ("G", "L"):
        if cfg.kv_lora_rank:
            return MLA.init_mla_cache(cfg, batch, seq, dtype)
        return L.init_attn_cache(cfg, batch, seq, window, dtype)
    if kind == "W":
        return RW.init_rwkv_cache(cfg, batch, dtype)
    if kind == "R":
        return RG.init_rglru_cache(cfg, batch, dtype)
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, batch: int, seq: int, dtype=jnp.bfloat16, serve: bool = True):
    """Decode cache for the whole stack (scan-stacked where the stack is)."""
    kinds = cfg.layer_kinds()
    windows = layer_windows(cfg, serve=serve)
    n_pre = cfg.first_dense_layers
    if cfg.uniform:
        pre = [
            _init_layer_cache(cfg, kinds[i], batch, seq, windows[i], dtype)
            for i in range(n_pre)
        ]
        # scan-stacked caches must share a shape: use the max window length
        # among scanned layers (full-attn layers dominate).
        scan_windows = windows[n_pre:]
        lens = [min(seq, w) if w else seq for w in scan_windows]
        max_len = max(lens)
        per = [
            _init_layer_cache(cfg, kinds[n_pre], batch, max_len, 0, dtype)
            for _ in range(cfg.n_layers - n_pre)
        ]
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per)
        return {"pre": pre, "stack": stacked}
    return {
        "loop": [
            _init_layer_cache(cfg, kinds[i], batch, seq, windows[i], dtype)
            for i in range(cfg.n_layers)
        ]
    }


def abstract_cache(cfg: ModelConfig, batch: int, seq: int, dtype=jnp.bfloat16):
    return jax.eval_shape(lambda: init_cache(cfg, batch, seq, dtype))


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,  # (B, S_text) int32
    positions: jax.Array,  # (S_total,) absolute positions
    cache=None,
    frontend_embeds: jax.Array | None = None,  # (B, S_front, d)
    serve: bool = False,
    collect_hidden: bool = False,
    remat: bool = False,
    residual_sharding=None,  # NamedSharding/PartitionSpec for the (B,S,d) stream
    unroll: bool = False,  # unroll layer scans (roofline cost-variant only)
):
    """Returns (logits, new_cache, aux_loss[, hidden_stack])."""

    def constrain(h):
        if residual_sharding is not None:
            return jax.lax.with_sharding_constraint(h, residual_sharding)
        return h

    block_fn = jax.checkpoint(_apply_block, static_argnums=(2, 3)) if remat else _apply_block

    x = L.embed_tokens(params["embed"], tokens, cfg)
    if frontend_embeds is not None:
        x = jnp.concatenate([frontend_embeds.astype(x.dtype), x], axis=1)
    x = constrain(x)
    kinds = cfg.layer_kinds()
    windows = layer_windows(cfg, serve=serve)
    aux_total = jnp.zeros((), jnp.float32)
    hidden = []

    if cfg.uniform:
        n_pre = cfg.first_dense_layers
        new_pre = []
        for i in range(n_pre):
            c = cache["pre"][i] if cache is not None else None
            x, c, aux = block_fn(
                params["pre_layers"][i], x, cfg, kinds[i], positions, windows[i], c
            )
            x = constrain(x)
            aux_total += aux
            new_pre.append(c)
            hidden.append(x)
        stack_kind = kinds[n_pre]
        win_arr = jnp.asarray(windows[n_pre:], dtype=jnp.int32)

        def body(carry, inp):
            x, aux_total = carry
            layer_params, win, layer_cache = inp
            x, new_c, aux = block_fn(
                layer_params, x, cfg, stack_kind, positions, win, layer_cache
            )
            x = constrain(x)
            out = (x, new_c) if collect_hidden or layer_cache is not None else (None, None)
            return (x, aux_total + aux), out

        stack_cache = cache["stack"] if cache is not None else None
        if stack_cache is not None:
            (x, aux_total), (_, new_stack) = jax.lax.scan(
                body, (x, aux_total), (params["layers"], win_arr, stack_cache),
                unroll=unroll,
            )
            new_cache = {"pre": new_pre, "stack": new_stack}
        elif collect_hidden:
            (x, aux_total), (hs, _) = jax.lax.scan(
                body,
                (x, aux_total),
                (params["layers"], win_arr, None),
                unroll=unroll,
            )
            # (L, B, S, d): the peeled dense layers first
            hidden = jnp.concatenate([jnp.stack(hidden), hs]) if hidden else hs
            new_cache = None
        else:
            (x, aux_total), _ = jax.lax.scan(
                body, (x, aux_total), (params["layers"], win_arr, None),
                unroll=unroll,
            )
            new_cache = None
    else:
        new_loop = []
        for i in range(cfg.n_layers):
            c = cache["loop"][i] if cache is not None else None
            x, c, aux = block_fn(
                params["loop_layers"][i], x, cfg, kinds[i], positions, windows[i], c
            )
            x = constrain(x)
            aux_total += aux
            new_loop.append(c)
            if collect_hidden:
                hidden.append(x)
        new_cache = {"loop": new_loop} if cache is not None else None
        if collect_hidden:
            hidden = jnp.stack(hidden)

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = L.unembed(params["embed"], x, cfg)
    if collect_hidden:
        return logits, new_cache, aux_total, hidden
    return logits, new_cache, aux_total
