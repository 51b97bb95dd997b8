"""Plain float32 reference of a DeepSeek-V2 stack read as a depth cascade.

Written from the published description (arXiv:2405.04434 and the
DeepSeek-V2-Lite ``config.json``), in straightforward ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching tricks, and nothing of ``repro.models``.  It reads the program's
parameter tree (``embed.tok``, ``pre_layers``, the scan-stacked ``layers``,
``exit_heads``) as plain arrays, and the configuration's settings by name.

One row at a time, for layer l:

    h   = x + MLA(rms(x))
    x'  = h + FFN_l(rms(h))

MLA (no q LoRA): q = h Wq split into (nope, rope) per head; the latent
``c = rms(h Wkv_a[:r])`` and one shared rope key ``k_r = rope(h Wkv_a[r:])``;
``k_n = c Wk_b``, ``v = c Wv_b``; causal softmax of ``(q_n.k_n + q_r.k_r) *
scale`` with ``scale = (d_nope + d_rope)^-1/2 * m(s, mscale_all_dim)^2``.

YaRN (d = rope dim, b = rope_theta, s = factor, L0 = original positions):
``extra_i = b^(-2i/d)``, ``inter_i = extra_i / s``,
``corr(r) = d ln(L0 / (2 pi r)) / (2 ln b)``, ``lo = floor(corr(beta_fast))``,
``hi = ceil(corr(beta_slow))``, ``mask_i = 1 - clip((i - lo)/(hi - lo), 0, 1)``,
``inv_freq_i = inter_i (1 - mask_i) + extra_i mask_i``; cos and sin scaled by
``m(s, mscale) / m(s, mscale_all_dim)``, ``m(s, mu) = 0.1 mu ln s + 1``.
Rotate-half on contiguous halves: the published code first interleaves the
rope dimensions, a fixed permutation of the rope weight columns, which
seeded random weights do not tell apart.

FFN: layers below ``first_dense_layers`` a dense SwiGLU; the others MoE:
softmax router over all experts in float32, greedy top-k, weights the raw
probabilities unless ``norm_topk_prob``; each expert held here adds its
SwiGLU output times its gate, computed for every token and masked; the
shared experts are one SwiGLU added to every token.

An exit every ``exit_interval`` layers reads the raw last-token residual
against its head; the cascade's stage scores are their successive
differences.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def yarn_mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_band(cfg):
    d, base = cfg.rope_head_dim, cfg.rope_theta

    def corr(r):
        return d * math.log(cfg.rope_original_max_pos / (2 * math.pi * r)) / (2 * math.log(base))

    return max(math.floor(corr(cfg.rope_beta_fast)), 0), min(math.ceil(corr(cfg.rope_beta_slow)), d - 1)


def inv_freq(cfg):
    d, base = cfg.rope_head_dim, cfg.rope_theta
    i = np.arange(d // 2, dtype=np.float64)
    extra = base ** (-2.0 * i / d)
    if not cfg.rope_factor:
        return extra
    lo, hi = yarn_band(cfg)
    mask = 1.0 - np.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return (extra / cfg.rope_factor) * (1.0 - mask) + extra * mask


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, cfg):
    """x: (S, heads, d_rope) at positions 0..S-1."""
    f = jnp.asarray(inv_freq(cfg), jnp.float32)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * f[None, :]
    m = 1.0
    if cfg.rope_factor:
        m = yarn_mscale(cfg.rope_factor, cfg.rope_mscale) / yarn_mscale(
            cfg.rope_factor, cfg.rope_mscale_all_dim
        )
    cos, sin = (jnp.cos(ang) * m)[:, None, :], (jnp.sin(ang) * m)[:, None, :]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _mla(p, h, cfg):
    s = h.shape[0]
    H, dn, dr, dv, r = cfg.n_heads, cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    q = (h @ p["wq"]).reshape(s, H, dn + dr)
    q_n, q_r = q[..., :dn], _rope(q[..., dn:], cfg)
    lat = h @ p["wkv_a"]
    c = _rms(lat[:, :r], p["kv_norm"], cfg.norm_eps)
    k_r = _rope(lat[:, None, r:], cfg)[:, 0]
    k_n = (c @ p["wk_b"]).reshape(s, H, dn)
    v = (c @ p["wv_b"]).reshape(s, H, dv)
    scale = 1.0 / math.sqrt(dn + dr)
    if cfg.rope_factor:
        scale *= yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) ** 2
    logits = (jnp.einsum("qhd,khd->hqk", q_n, k_n) + jnp.einsum("qhd,kd->hqk", q_r, k_r)) * scale
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    w = jax.nn.softmax(jnp.where(causal[None], logits, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", w, v).reshape(s, H * dv) @ p["wo"]


def _swiglu(wi, wg, wo, h):
    return (jax.nn.silu(h @ wi) * (h @ wg)) @ wo


def moe(p, h, cfg):
    """The MoE FFN's part from the experts held here, plus the shared
    experts; ``p`` as the program's ``moe`` params (held experts only)."""
    probs = jax.nn.softmax(h @ p["router"], axis=-1)
    topw, topi = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        topw = topw / topw.sum(-1, keepdims=True)
    held = tuple(cfg.experts_held) or tuple(range(cfg.n_experts))
    out = jnp.zeros_like(h)
    for j, e in enumerate(held):
        gate = jnp.where(topi == e, topw, 0.0).sum(-1)
        out = out + gate[:, None] * _swiglu(p["wi"][j], p["wg"][j], p["wo"][j], h)
    if "shared" in p:
        sh = p["shared"]
        out = out + _swiglu(sh["wi"], sh["wg"], sh["wo"], h)
    return out


def layer(p, x, cfg):
    h = x + _mla(p["attn"], _rms(x, p["ln1"], cfg.norm_eps), cfg)
    z = _rms(h, p["ln2"], cfg.norm_eps)
    if "moe" in p:
        return h + moe(p["moe"], z, cfg)
    return h + _swiglu(p["mlp"]["wi"], p["mlp"]["wg"], p["mlp"]["wo"], z)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def layer_params(params, cfg, li):
    n_pre = cfg.first_dense_layers
    if li < n_pre:
        return _f32(params["pre_layers"][li])
    return _f32(jax.tree_util.tree_map(lambda a: a[li - n_pre], params["layers"]))


def exit_scores(params, cfg, tokens) -> np.ndarray:
    """(N, n_layers / exit_interval) float32 exit-head scores, one row at a
    time: head ``e`` reads the residual after layer ``(e + 1) * k - 1``."""
    tok = jnp.asarray(params["embed"]["tok"], jnp.float32)
    heads = jnp.asarray(params["exit_heads"], jnp.float32)
    scale = math.sqrt(cfg.d_model) if cfg.scale_embeddings else 1.0
    out = []
    with jax.default_matmul_precision("highest"):
        for row in np.asarray(tokens):
            x = tok[jnp.asarray(row)] * scale
            s = []
            k = cfg.exit_interval
            for li in range(cfg.n_layers):
                x = layer(layer_params(params, cfg, li), x, cfg)
                if (li + 1) % k == 0:
                    s.append(x[-1] @ heads[li // k])
            out.append(jnp.stack(s))
    return np.asarray(jnp.stack(out), np.float32)
