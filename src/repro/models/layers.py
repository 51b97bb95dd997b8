"""Shared neural building blocks (pure functions over param dicts).

Everything is functional: ``init_*`` builds a param pytree, ``apply_*``
consumes it.  Attention is flash-style (query-chunked with online masking,
never materializing the full (S, S) logit matrix) so that 32k prefill and
500k decode lower with sane memory footprints.  Decode uses ring-buffer KV
caches when a sliding window is active (cache length = min(seq, window)).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig

Params = dict[str, Any]

Q_CHUNK = 256  # flash-attention query block


# --------------------------------------------------------------------------
# basics
# --------------------------------------------------------------------------


def init_dense(key, d_in: int, d_out: int, dtype=jnp.float32) -> jax.Array:
    scale = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out)) * scale).astype(dtype)


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def softcap(x: jax.Array, cap: float) -> jax.Array:
    return cap * jnp.tanh(x / cap) if cap else x


def rope(
    x: jax.Array,
    positions: jax.Array,
    theta: float,
    inv_freq: jax.Array | None = None,
    mscale: float = 1.0,
) -> jax.Array:
    """Rotary embedding (rotate-half).  x: (..., S, n_heads, head_dim);
    positions: (..., S).  ``inv_freq`` (head_dim // 2,) replaces the plain
    ``theta ** (-2i / head_dim)`` frequencies and ``mscale`` scales cos and
    sin (YaRN, ``models/mla.py``)."""
    hd = x.shape[-1]
    half = hd // 2
    if inv_freq is None:
        inv_freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :] * mscale  # (..., S, 1, half)
    sin = jnp.sin(angles)[..., None, :] * mscale
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# MLP / SwiGLU
# --------------------------------------------------------------------------


def init_mlp(key, cfg: ModelConfig, d_ff: int | None = None, dtype=jnp.float32) -> Params:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    if cfg.mlp_kind == "swiglu":
        return {
            "wi": init_dense(k1, d, f, dtype),
            "wg": init_dense(k2, d, f, dtype),
            "wo": init_dense(k3, f, d, dtype),
        }
    return {"wi": init_dense(k1, d, f, dtype), "wo": init_dense(k3, f, d, dtype)}


def apply_mlp(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if "wg" in p:
        h = jax.nn.silu(x @ p["wi"]) * (x @ p["wg"])
    else:
        h = jax.nn.gelu(x @ p["wi"])
    return h @ p["wo"]


# --------------------------------------------------------------------------
# flash-style attention core
# --------------------------------------------------------------------------


def _attend(
    q: jax.Array,  # (B, Sq, H, hd)
    k: jax.Array,  # (B, Sk, KV, hd)
    v: jax.Array,  # (B, Sk, KV, hd)
    q_pos: jax.Array,  # (Sq,) absolute positions of queries
    k_pos: jax.Array,  # (Sk,) absolute positions of keys (ring caches permute)
    window: int,  # 0 = full causal
    attn_softcap: float,
    q_chunk: int = Q_CHUNK,
) -> jax.Array:
    """Causal (optionally sliding-window) attention, chunked over queries.

    Never materializes more than (B, H, q_chunk, Sk) logits.  ``k_pos`` allows
    ring-buffer caches: masking is computed from absolute positions, so the
    physical cache order is irrelevant.
    """
    b, sq, h, hd = q.shape
    kvh = k.shape[2]
    rep = h // kvh
    scale = 1.0 / math.sqrt(hd)

    n_chunks = max(1, (sq + q_chunk - 1) // q_chunk)
    pad = n_chunks * q_chunk - sq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pad), constant_values=-1)
    # grouped-query layout: never materialize repeated KV heads
    qc = q.reshape(b, n_chunks, q_chunk, kvh, rep, hd)
    qp = q_pos.reshape(n_chunks, q_chunk)

    def chunk(carry, inp):
        qi, qpi = inp  # (B, qc, KV, rep, hd), (qc,)
        logits = jnp.einsum(
            "bqgrd,bkgd->bgrqk", qi.astype(jnp.float32), k.astype(jnp.float32)
        )
        logits = logits * scale
        logits = softcap(logits, attn_softcap)
        causal = qpi[:, None] >= k_pos[None, :]  # (qc, Sk)
        valid = (k_pos >= 0)[None, :] & (qpi >= 0)[:, None]
        mask = causal & valid
        # window may be a traced per-layer value (scan-stacked local/global
        # alternation); window <= 0 means full attention.
        win = jnp.asarray(window, jnp.int32)
        in_win = (win <= 0) | (qpi[:, None] - k_pos[None, :] < win)
        mask &= in_win
        logits = jnp.where(mask[None, None, None], logits, -1e30)
        w = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bgrqk,bkgd->bqgrd", w, v.astype(jnp.float32))
        return carry, out.astype(qi.dtype)

    _, outs = jax.lax.scan(chunk, (), (qc.swapaxes(0, 1), qp))
    out = outs.swapaxes(0, 1).reshape(b, n_chunks * q_chunk, h, hd)
    return out[:, :sq]


# --------------------------------------------------------------------------
# GQA attention layer (optionally windowed / softcapped / qk-normed)
# --------------------------------------------------------------------------


def init_attn(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd()
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "wq": init_dense(k1, d, h * hd, dtype),
        "wk": init_dense(k2, d, kv * hd, dtype),
        "wv": init_dense(k3, d, kv * hd, dtype),
        "wo": init_dense(k4, h * hd, d, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def init_attn_cache(cfg: ModelConfig, batch: int, seq: int, window: int, dtype) -> Params:
    """Ring-buffer KV cache for one layer.  length = min(seq, window)."""
    length = min(seq, window) if window else seq
    kv, hd = cfg.n_kv_heads, cfg.hd()
    return {
        "k": jnp.zeros((batch, length, kv, hd), dtype),
        "v": jnp.zeros((batch, length, kv, hd), dtype),
        "pos": jnp.full((batch, length), -1, dtype=jnp.int32),
    }


def apply_attn(
    p: Params,
    x: jax.Array,  # (B, S, d)
    cfg: ModelConfig,
    positions: jax.Array,  # (S,)
    window: int,
    cache: Params | None = None,
) -> tuple[jax.Array, Params | None]:
    b, s, d = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd()
    q = (x @ p["wq"]).reshape(b, s, h, hd)
    k = (x @ p["wk"]).reshape(b, s, kv, hd)
    v = (x @ p["wv"]).reshape(b, s, kv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = rope(q, positions[None, :], cfg.rope_theta)
    k = rope(k, positions[None, :], cfg.rope_theta)

    if cache is None:
        out = _attend(q, k, v, positions, positions, window, cfg.attn_softcap)
    else:
        length = cache["k"].shape[1]
        slot = positions % length  # (S,) ring slots
        cache = {
            "k": cache["k"].at[:, slot].set(k.astype(cache["k"].dtype)),
            "v": cache["v"].at[:, slot].set(v.astype(cache["v"].dtype)),
            "pos": cache["pos"].at[:, slot].set(positions[None, :].astype(jnp.int32)),
        }
        out = _attend(
            q, cache["k"], cache["v"], positions, cache["pos"][0], window, cfg.attn_softcap
        )
    out = out.reshape(b, s, h * hd) @ p["wo"]
    return out, cache


# --------------------------------------------------------------------------
# embeddings / head
# --------------------------------------------------------------------------


def init_embed(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    k1, k2 = jax.random.split(key)
    p = {"tok": (jax.random.normal(k1, (cfg.vocab_size, cfg.d_model)) * 0.02).astype(dtype)}
    if not cfg.tie_embeddings:
        p["unembed"] = init_dense(k2, cfg.d_model, cfg.vocab_size, dtype)
    return p


def embed_tokens(p: Params, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    x = jnp.take(p["tok"], tokens, axis=0)
    return x * math.sqrt(cfg.d_model) if cfg.scale_embeddings else x


def unembed(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.tie_embeddings:
        logits = x @ p["tok"].T
    else:
        logits = x @ p["unembed"]
    return softcap(logits, cfg.logit_softcap)
