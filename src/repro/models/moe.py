"""Mixture-of-Experts FFN with dropless routing over the experts held here.

Routing is a float32 softmax over all ``n_experts`` and a greedy top-k; the
top-k weights are renormalised only where ``cfg.norm_topk_prob`` says so
(DeepSeek-V2 keeps the raw probabilities).  The layer holds the weights of
``cfg.held_experts()`` only — the chip's share under expert parallelism —
and computes their part of the result: a (token, slot) pair routed to an
expert held elsewhere adds nothing here.  On one chip there is no
exchange; the absent experts' part is simply not part of the result.

Dispatch is dropless.  The pairs routed to held experts are laid out in one
buffer, grouped by expert, each group starting on a kernel tile boundary
(``kernels/expert_kernel.py``).  The buffer is sized for the worst case —
every token's ``min(top_k, held)`` slots on held experts, plus a partial
tile per expert — so no pair is ever dropped, while the kernel's grid runs
only the tiles in use.  A token's output sums its slots in slot order, so
it depends on no other token in the batch.

Shared experts (DeepSeek) are one dense SwiGLU FFN always applied.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro import tracing
from repro.kernels.expert_kernel import TILE_M, expert_ffn
from repro.models.config import ModelConfig
from repro.models.layers import init_dense, init_mlp, apply_mlp

Params = dict[str, Any]


def init_moe(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff or cfg.d_ff
    held = len(cfg.held_experts())
    keys = jax.random.split(key, 5)
    scale_in = 1.0 / math.sqrt(d)
    scale_out = 1.0 / math.sqrt(f)
    p: Params = {
        "router": init_dense(keys[0], d, e, dtype=jnp.float32),  # fp32 router
        "wi": (jax.random.normal(keys[1], (held, d, f)) * scale_in).astype(dtype),
        "wg": (jax.random.normal(keys[2], (held, d, f)) * scale_in).astype(dtype),
        "wo": (jax.random.normal(keys[3], (held, f, d)) * scale_out).astype(dtype),
    }
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(
            keys[4], cfg, d_ff=(cfg.moe_d_ff or cfg.d_ff) * cfg.n_shared_experts, dtype=dtype
        )
    return p


def route(router: jax.Array, xt: jax.Array, cfg: ModelConfig):
    """(probs (n, E), top-k weights (n, k), top-k expert ids (n, k))."""
    probs = jax.nn.softmax(xt.astype(jnp.float32) @ router, axis=-1)
    topw, topi = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        topw = topw / jnp.clip(topw.sum(-1, keepdims=True), 1e-9)
    return probs, topw, topi


def dispatch(topi: jax.Array, cfg: ModelConfig):
    """Buffer row of every (token, slot) pair routed to a held expert.

    -> (dest (n, k): the pair's row, or ``rows`` where the expert is held
    elsewhere; group_rows (held,): each expert's rows, whole tiles; rows:
    the buffer's static worst-case size).
    """
    n, k = topi.shape
    held = cfg.held_experts()
    eh = len(held)
    local = jnp.full((cfg.n_experts,), -1, jnp.int32).at[jnp.asarray(held)].set(
        jnp.arange(eh, dtype=jnp.int32)
    )[topi].reshape(n * k)
    onehot = (local[:, None] == jnp.arange(eh, dtype=jnp.int32)[None, :]).astype(jnp.int32)
    counts = onehot.sum(0)
    rank = ((jnp.cumsum(onehot, axis=0) - onehot) * onehot).sum(1)
    tiles = -(-counts // TILE_M)
    first_row = (jnp.cumsum(tiles) - tiles) * TILE_M
    rows = -(-n * min(k, eh) // TILE_M) * TILE_M + eh * TILE_M
    dest = jnp.where(local >= 0, first_row[jnp.maximum(local, 0)] + rank, rows)
    return dest.reshape(n, k), (tiles * TILE_M).astype(jnp.int32), rows


def apply_moe(p: Params, x: jax.Array, cfg: ModelConfig) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out, aux_loss)."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    n = b * s
    xt = x.reshape(n, d)

    with jax.named_scope(tracing.MOE_ROUTE):
        probs, topw, topi = route(p["router"], xt, cfg)
        dest, group_rows, rows = dispatch(topi, cfg)
        token = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None], (n, k))
        # each buffer row's token; unused rows read the zero row n
        src = jnp.full((rows,), n, jnp.int32).at[dest.reshape(-1)].set(
            token.reshape(-1), mode="drop"
        )
        x_buf = jnp.take(jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)]), src, axis=0)
    with jax.named_scope(tracing.MOE_EXPERTS):
        y_buf = expert_ffn(x_buf, p["wi"], p["wg"], p["wo"], group_rows)
    with jax.named_scope(tracing.MOE_ROUTE):
        out = jnp.zeros((n, d), jnp.float32)
        for j in range(k):  # slot order: a token's sum is its own
            here = dest[:, j] < rows
            y_j = jnp.take(y_buf, jnp.minimum(dest[:, j], rows - 1), axis=0)
            out = out + jnp.where(here[:, None], topw[:, j : j + 1] * y_j, 0.0)
        out = out.astype(x.dtype).reshape(b, s, d)

        # switch-style load-balance aux loss over all experts
        routed = jnp.zeros((e,), jnp.float32).at[topi.reshape(-1)].add(1.0) / n
        aux = e * jnp.sum(probs.mean(0) * routed) * cfg.router_aux_weight

    if "shared" in p:
        with jax.named_scope(tracing.MOE_SHARED):
            out = out + apply_mlp(p["shared"], x, cfg)
    return out, aux
