"""Multi-head Latent Attention (DeepSeek-V2) layer.

MLA compresses the KV path into a low-rank latent c_kv (kv_lora_rank) plus a
small decoupled RoPE key; the cache stores ONLY (c_kv, k_rope) per position —
(kv_lora_rank + rope_head_dim) floats instead of 2 * H * hd.  Queries are
(optionally) low-rank too.  The per-head no-PE keys/values are up-projected
from the latent at attention time.

Cache layout: (B, S, kv_lora_rank + rope_head_dim).  For the decode path the
up-projection is applied to the gathered latent — the structural source of
MLA's long-context memory win, visible directly in the roofline memory term.

YaRN (DeepSeek-V2, ``cfg.rope_factor`` = s > 0): with rope dim d, base b
and pre-extension length L0, the rope frequencies blend the plain
``extra_i = b^(-2i/d)`` with the interpolated ``inter_i = extra_i / s``
over the band [lo, hi] of ``corr(r) = d ln(L0 / (2 pi r)) / (2 ln b)``,
``lo = floor(corr(beta_fast))``, ``hi = ceil(corr(beta_slow))``; cos and
sin are scaled by ``m(s, mscale) / m(s, mscale_all_dim)`` and the softmax
by ``m(s, mscale_all_dim)^2``, where ``m(s, mu) = 0.1 mu ln s + 1``.
Departure from the published code: it interleaves the rope dimensions
before rotate-half; here rotate-half acts on contiguous halves, a fixed
permutation of the rope weight columns.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import init_dense, rms_norm, rope, softcap

Params = dict[str, Any]

Q_CHUNK = 256


def yarn_mscale(factor: float, mscale: float) -> float:
    """m(s, mu) = 0.1 mu ln s + 1 for s > 1, else 1."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_band(cfg: ModelConfig) -> tuple[int, int]:
    """(lo, hi): the rope dimensions over which YaRN blends frequencies."""
    d, base = cfg.rope_head_dim, cfg.rope_theta

    def corr(rotations):
        return d * math.log(cfg.rope_original_max_pos / (2 * math.pi * rotations)) / (
            2 * math.log(base)
        )

    lo = max(math.floor(corr(cfg.rope_beta_fast)), 0)
    hi = min(math.ceil(corr(cfg.rope_beta_slow)), d - 1)
    return lo, hi


def rope_inv_freq(cfg: ModelConfig) -> jax.Array | None:
    """YaRN's blended inverse frequencies, or None for plain rope."""
    if not cfg.rope_factor:
        return None
    d, base = cfg.rope_head_dim, cfg.rope_theta
    i = jnp.arange(d // 2, dtype=jnp.float32)
    extra = base ** (-2.0 * i / d)
    inter = extra / cfg.rope_factor
    lo, hi = yarn_band(cfg)
    mask = 1.0 - jnp.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return inter * (1.0 - mask) + extra * mask


def rope_mscale(cfg: ModelConfig) -> float:
    """YaRN's cos/sin factor m(s, mscale) / m(s, mscale_all_dim)."""
    if not cfg.rope_factor:
        return 1.0
    return yarn_mscale(cfg.rope_factor, cfg.rope_mscale) / yarn_mscale(
        cfg.rope_factor, cfg.rope_mscale_all_dim
    )


def softmax_scale(cfg: ModelConfig) -> float:
    """(nope + rope head dim)^-1/2, times m(s, mscale_all_dim)^2 under YaRN."""
    scale = 1.0 / math.sqrt(cfg.nope_head_dim + cfg.rope_head_dim)
    if cfg.rope_factor:
        scale *= yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) ** 2
    return scale


def init_mla(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    d, h = cfg.d_model, cfg.n_heads
    r_kv, r_q = cfg.kv_lora_rank, cfg.q_lora_rank
    dn, dr, dv = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    keys = jax.random.split(key, 8)
    p: Params = {}
    if r_q:
        p["wq_a"] = init_dense(keys[0], d, r_q, dtype)
        p["q_norm"] = jnp.ones((r_q,), dtype)
        p["wq_b"] = init_dense(keys[1], r_q, h * (dn + dr), dtype)
    else:
        p["wq"] = init_dense(keys[1], d, h * (dn + dr), dtype)
    p["wkv_a"] = init_dense(keys[2], d, r_kv + dr, dtype)  # latent + rope key
    p["kv_norm"] = jnp.ones((r_kv,), dtype)
    p["wk_b"] = init_dense(keys[3], r_kv, h * dn, dtype)
    p["wv_b"] = init_dense(keys[4], r_kv, h * dv, dtype)
    p["wo"] = init_dense(keys[5], h * dv, d, dtype)
    return p


def init_mla_cache(cfg: ModelConfig, batch: int, seq: int, dtype) -> Params:
    """Latent KV cache: just (c_kv, k_rope) per position."""
    width = cfg.kv_lora_rank + cfg.rope_head_dim
    return {
        "lat": jnp.zeros((batch, seq, width), dtype),
        "pos": jnp.full((batch, seq), -1, dtype=jnp.int32),
    }


def _mla_attend(q_n, q_r, k_n, k_r, v, q_pos, k_pos, attn_cap, scale, q_chunk=Q_CHUNK):
    """Chunked attention over concatenated (nope, rope) head dims."""
    b, sq, h, dn = q_n.shape
    dr = q_r.shape[-1]
    n_chunks = max(1, (sq + q_chunk - 1) // q_chunk)
    pad = n_chunks * q_chunk - sq
    if pad:
        q_n = jnp.pad(q_n, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_r = jnp.pad(q_r, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pad), constant_values=-1)
    qn = q_n.reshape(b, n_chunks, q_chunk, h, dn).swapaxes(0, 1)
    qr = q_r.reshape(b, n_chunks, q_chunk, h, dr).swapaxes(0, 1)
    qp = q_pos.reshape(n_chunks, q_chunk)

    def chunk(carry, inp):
        qni, qri, qpi = inp
        logits = jnp.einsum("bqhd,bkhd->bhqk", qni.astype(jnp.float32), k_n.astype(jnp.float32))
        logits += jnp.einsum("bqhd,bkd->bhqk", qri.astype(jnp.float32), k_r.astype(jnp.float32))
        logits *= scale
        logits = softcap(logits, attn_cap)
        mask = (qpi[:, None] >= k_pos[None, :]) & (k_pos >= 0)[None, :] & (qpi >= 0)[:, None]
        logits = jnp.where(mask[None, None], logits, -1e30)
        w = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32))
        return carry, out.astype(qni.dtype)

    _, outs = jax.lax.scan(chunk, (), (qn, qr, qp))
    out = outs.swapaxes(0, 1).reshape(b, n_chunks * q_chunk, h, v.shape[-1])
    return out[:, :sq]


def apply_mla(
    p: Params,
    x: jax.Array,
    cfg: ModelConfig,
    positions: jax.Array,
    cache: Params | None = None,
) -> tuple[jax.Array, Params | None]:
    b, s, d = x.shape
    h = cfg.n_heads
    r_kv = cfg.kv_lora_rank
    dn, dr, dv = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim

    if "wq_a" in p:
        q = rms_norm(x @ p["wq_a"], p["q_norm"], cfg.norm_eps) @ p["wq_b"]
    else:
        q = x @ p["wq"]
    q = q.reshape(b, s, h, dn + dr)
    q_n, q_r = q[..., :dn], q[..., dn:]
    inv_freq, mscale = rope_inv_freq(cfg), rope_mscale(cfg)
    q_r = rope(q_r, positions[None, :], cfg.rope_theta, inv_freq, mscale)

    lat_new = x @ p["wkv_a"]  # (B, S, r_kv + dr)
    c_kv_new = lat_new[..., :r_kv]
    k_r_new = rope(
        lat_new[..., r_kv:][:, :, None, :], positions[None, :], cfg.rope_theta,
        inv_freq, mscale,
    )[:, :, 0]
    lat_new = jnp.concatenate([c_kv_new, k_r_new], axis=-1)

    if cache is None:
        lat, k_pos = lat_new, positions
    else:
        slot = positions % cache["lat"].shape[1]
        cache = {
            "lat": cache["lat"].at[:, slot].set(lat_new.astype(cache["lat"].dtype)),
            "pos": cache["pos"].at[:, slot].set(positions[None, :].astype(jnp.int32)),
        }
        lat, k_pos = cache["lat"], cache["pos"][0]

    c_kv = rms_norm(lat[..., :r_kv], p["kv_norm"], cfg.norm_eps)
    k_r = lat[..., r_kv:]

    if cfg.mla_absorb and cache is not None and s <= Q_CHUNK:
        # Weight absorption (beyond-paper perf variant, DeepSeek-V2 §2.1.3
        # trick): fold wk_b into the query and wv_b into the output so the
        # S-length latent cache is contracted DIRECTLY — never materializing
        # the (B, S, H, dn) no-PE keys / (B, S, H, dv) values.  Per decoded
        # token this cuts the cache-side compute from O(S*r*H*(dn+dv)) to
        # O(S*r*H) and the HBM traffic to one read of the latent itself.
        scale = softmax_scale(cfg)
        wk = p["wk_b"].reshape(r_kv, h, dn)
        q_abs = jnp.einsum("bshd,rhd->bshr", q_n.astype(jnp.float32),
                           wk.astype(jnp.float32))
        logits = jnp.einsum("bshr,bkr->bhsk", q_abs, c_kv.astype(jnp.float32))
        logits += jnp.einsum("bshd,bkd->bhsk", q_r.astype(jnp.float32),
                             k_r.astype(jnp.float32))
        logits *= scale
        logits = softcap(logits, cfg.attn_softcap)
        mask = (positions[:, None] >= k_pos[None, :]) & (k_pos >= 0)[None, :]
        logits = jnp.where(mask[None, None], logits, -1e30)
        w = jax.nn.softmax(logits, axis=-1)
        ctx = jnp.einsum("bhsk,bkr->bshr", w, c_kv.astype(jnp.float32))
        wv = p["wv_b"].reshape(r_kv, h, dv)
        out = jnp.einsum("bshr,rhd->bshd", ctx, wv.astype(jnp.float32))
        out = out.astype(x.dtype)
    else:
        k_n = (c_kv @ p["wk_b"]).reshape(b, -1, h, dn)
        v = (c_kv @ p["wv_b"]).reshape(b, -1, h, dv)
        out = _mla_attend(
            q_n, q_r, k_n, k_r, v, positions, k_pos, cfg.attn_softcap, softmax_scale(cfg)
        )
    out = out.reshape(b, s, h * dv) @ p["wo"]
    return out, cache
