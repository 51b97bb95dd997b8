"""DeepSeek-V2 read as a QWYC depth cascade: one exit head per layer.

Cascade position ``t`` is layer ``t`` (order pinned to depth); its base
score is ``f_t = s_t - s_{t-1}``, where ``s_t`` is exit head ``t`` against
the raw last-token residual after layer ``t``, so a row's partial sum is
its exit score.  The weights are random from ``weights_seed``; the chip
holds the first ``n_experts_held`` routed experts of every MoE layer, the
router still spans all ``n_routed_experts``, and the experts held on the
other chips add nothing here, in the program and in the reference alike.

The reference is the benchmark's own copy of the plain float32 model
(``tests/reference_deepseek_v2.py``), in ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, computed layer by layer on
the device in blocks of rows.  Each held expert runs on every token and
is masked by its gate.  Departure from the published code, as in the
program: rotate-half on contiguous rope halves, where the published code
first interleaves them, a fixed permutation of rope weight columns.

Exit heads: the last reads the direction in which the probe rows' final
last-token residuals vary most (their first principal axis); each earlier
one is a ridge probe that predicts the last exit's score from that layer's
last-token residual, fit on probe rows apart from the calibration rows.
``params`` keeps the model's settings (as JSON) and the heads, not the
5.8 GB of weights, which are drawn again from the seed on the device.

Reference results are kept in ``bench/.cache/deepseek_v2``: each layer's
last-token residuals and the rows' routed slots on held experts, keyed by
the weights and the rows, and the exit scores, keyed by those and the
heads.  A checkout computes them once.
"""

from __future__ import annotations

import functools
import hashlib
import json
import math
from pathlib import Path

import numpy as np

import world as worldgen

CACHE = Path(__file__).resolve().parents[1] / ".cache" / "deepseek_v2"
ROW_BLOCK = 16
EXPERT_KERNEL = "qwyc_moe_experts"  # the program's held-expert kernel, by name

# settings of the source that the model below does not implement otherwise
_FIXED = {
    "hidden_act": "silu", "scoring_func": "softmax", "topk_method": "greedy",
    "n_group": 1, "topk_group": 1, "routed_scaling_factor": 1, "moe_layer_freq": 1,
    "attention_bias": False, "q_lora_rank": None,
}
_MODEL_KEYS = (
    "hidden_size", "intermediate_size", "kv_lora_rank", "moe_intermediate_size",
    "n_routed_experts", "n_shared_experts", "norm_topk_prob", "num_attention_heads",
    "num_experts_per_tok", "num_hidden_layers", "qk_nope_head_dim", "qk_rope_head_dim",
    "rms_norm_eps", "rope_scaling", "rope_theta", "v_head_dim", "vocab_size",
    "first_k_dense_replace", "n_experts_held", "seq_len", "weights_seed", "init_std",
    "precision",
)


def _model(cfg: dict) -> dict:
    for k, v in _FIXED.items():
        if cfg.get(k, v) != v:
            raise ValueError(f"{cfg['name']}: {k}={cfg[k]!r} is not modelled (needs {v!r})")
    return {k: cfg[k] for k in _MODEL_KEYS}


def _m(params: dict) -> dict:
    return json.loads(str(params["model"]))


def _key(*parts) -> str:
    h = hashlib.sha256()
    for p in parts:
        h.update(p if isinstance(p, bytes) else json.dumps(p, sort_keys=True).encode())
    return h.hexdigest()[:16]


# --------------------------------------------------------------- rows


def world(cfg: dict) -> worldgen.World:
    """Calibration and pool rows of ``seq_len`` token ids, Zipf over the
    vocabulary."""
    n_train, n_pool = int(cfg["train_rows"]), int(cfg["pool_rows"])
    x = _tokens(cfg, int(cfg["world"]["seed"]), n_train + n_pool)
    return worldgen.World(x[:n_train], np.zeros(n_train, np.int64), x[n_train:])


def _probes(cfg: dict) -> np.ndarray:
    return _tokens(cfg, int(cfg["world"]["seed"]) + 1, int(cfg["probe_rows"]))


def _tokens(cfg: dict, seed: int, n: int) -> np.ndarray:
    V, S = int(cfg["vocab_size"]), int(cfg["seq_len"])
    p = 1.0 / np.arange(1, V + 1) ** float(cfg["world"]["zipf_a"])
    ids = np.random.default_rng(int(cfg["world"]["seed"])).permutation(V).astype(np.int32)
    rng = np.random.default_rng([seed, 7])
    return ids[rng.choice(V, size=(n, S), p=p / p.sum())]


# --------------------------------------------------------------- weights


def _dims(m: dict) -> dict:
    rs = m["rope_scaling"] or {}
    return dict(
        d=m["hidden_size"], H=m["num_attention_heads"], dn=m["qk_nope_head_dim"],
        dr=m["qk_rope_head_dim"], dv=m["v_head_dim"], r=m["kv_lora_rank"],
        ff=m["intermediate_size"], f=m["moe_intermediate_size"], E=m["n_routed_experts"],
        held=m["n_experts_held"], k=m["num_experts_per_tok"], shared=m["n_shared_experts"],
        L=m["num_hidden_layers"], n_dense=m["first_k_dense_replace"], V=m["vocab_size"],
        S=m["seq_len"], eps=m["rms_norm_eps"], theta=m["rope_theta"], rs=rs,
    )


def _shapes(m: dict) -> dict:
    """The program's parameter tree, as shapes: (shape, kind) leaves with
    kind ``w`` (drawn), ``one`` (a norm weight) or ``f32`` (drawn, the
    float32 router)."""
    g = _dims(m)
    d, H = g["d"], g["H"]

    def attn():
        return {
            "wq": ((d, H * (g["dn"] + g["dr"])), "w"), "wkv_a": ((d, g["r"] + g["dr"]), "w"),
            "kv_norm": ((g["r"],), "one"), "wk_b": ((g["r"], H * g["dn"]), "w"),
            "wv_b": ((g["r"], H * g["dv"]), "w"), "wo": ((H * g["dv"], d), "w"),
        }

    def mlp(f):
        return {"wi": ((d, f), "w"), "wg": ((d, f), "w"), "wo": ((f, d), "w")}

    def stacked(n, tree):
        return {k: stacked(n, v) if isinstance(v, dict) else ((n,) + v[0], v[1])
                for k, v in tree.items()}

    dense = {"ln1": ((d,), "one"), "ln2": ((d,), "one"), "attn": attn(), "mlp": mlp(g["ff"])}
    moe = {
        "router": ((d, g["E"]), "f32"),
        "wi": ((g["held"], d, g["f"]), "w"), "wg": ((g["held"], d, g["f"]), "w"),
        "wo": ((g["held"], g["f"], d), "w"), "shared": mlp(g["f"] * g["shared"]),
    }
    block = {"ln1": ((d,), "one"), "ln2": ((d,), "one"), "attn": attn(), "moe": moe}
    return {
        "embed": {"tok": ((g["V"], d), "w")},
        "pre_layers": [dense] * g["n_dense"],
        "layers": stacked(g["L"] - g["n_dense"], block),
    }


@functools.lru_cache(maxsize=1)
def _weights(model_json: str):
    """The weights on the device, in the program's tree: bfloat16 draws
    of N(0, init_std^2), the router in float32, norm weights 1."""
    import jax
    import jax.numpy as jnp

    m = json.loads(model_json)
    std = float(m["init_std"])
    leaves, tree = jax.tree_util.tree_flatten(_shapes(m), is_leaf=_is_leaf)
    root = jax.random.PRNGKey(int(m["weights_seed"]))

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def draw(key, shape, kind):
        if kind == "one":
            return jnp.ones(shape, jnp.bfloat16)
        w = jax.random.normal(key, shape, jnp.float32) * std
        return w if kind == "f32" else w.astype(jnp.bfloat16)

    out = [draw(jax.random.fold_in(root, i), shape, kind) for i, (shape, kind) in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(tree, out)


def _is_leaf(v) -> bool:
    return isinstance(v, tuple) and len(v) == 2 and isinstance(v[1], str)


# --------------------------------------------------------------- reference


def _yarn(g: dict):
    """(inv_freq, cos/sin factor, softmax scale) of the rope dims."""
    d, base, rs = g["dr"], g["theta"], g["rs"]
    i = np.arange(d // 2, dtype=np.float64)
    extra = base ** (-2.0 * i / d)
    scale = 1.0 / math.sqrt(g["dn"] + g["dr"])
    if not rs:
        return extra, 1.0, scale
    s, L0 = float(rs["factor"]), float(rs["original_max_position_embeddings"])

    def corr(rot):
        return d * math.log(L0 / (2 * math.pi * rot)) / (2 * math.log(base))

    def msc(mu):
        return 0.1 * mu * math.log(s) + 1.0 if s > 1 else 1.0

    lo = max(math.floor(corr(rs["beta_fast"])), 0)
    hi = min(math.ceil(corr(rs["beta_slow"])), d - 1)
    mask = 1.0 - np.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    inv = (extra / s) * (1.0 - mask) + extra * mask
    return inv, msc(rs["mscale"]) / msc(rs["mscale_all_dim"]), scale * msc(rs["mscale_all_dim"]) ** 2


def _layer_fn(g: dict, dense: bool, lower: bool):
    """One float32 layer on a (B, S, d) block -> (block, routed slots on
    held experts per row); ``lower`` first rounds the layer's weights to
    float8_e4m3fn (the control).  An MoE layer takes the stacked MoE
    layers and its index among them."""
    import jax
    import jax.numpy as jnp

    inv, msc, scale = _yarn(g)
    H, dn, dr, dv, r, eps = g["H"], g["dn"], g["dr"], g["dv"], g["r"], g["eps"]

    def rms(x, w):
        return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w

    def rope(x):  # (B, S, heads, dr)
        ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
        cos, sin = (jnp.cos(ang) * msc)[None, :, None], (jnp.sin(ang) * msc)[None, :, None]
        x1, x2 = x[..., : dr // 2], x[..., dr // 2 :]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    def swiglu(p, x):
        return (jax.nn.silu(x @ p["wi"]) * (x @ p["wg"])) @ p["wo"]

    def mla(p, x):
        b, s, _ = x.shape
        q = (x @ p["wq"]).reshape(b, s, H, dn + dr)
        q_n, q_r = q[..., :dn], rope(q[..., dn:])
        lat = x @ p["wkv_a"]
        c = rms(lat[..., :r], p["kv_norm"])
        k_r = rope(lat[..., None, r:])[:, :, 0]
        k_n = (c @ p["wk_b"]).reshape(b, s, H, dn)
        v = (c @ p["wv_b"]).reshape(b, s, H, dv)
        logits = (jnp.einsum("bqhd,bkhd->bhqk", q_n, k_n)
                  + jnp.einsum("bqhd,bkd->bhqk", q_r, k_r)) * scale
        causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
        w = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(b, s, H * dv) @ p["wo"]

    def moe(p, x):
        probs = jax.nn.softmax(x @ p["router"], axis=-1)
        topw, topi = jax.lax.top_k(probs, g["k"])
        if g["norm_topk"]:
            topw = topw / topw.sum(-1, keepdims=True)
        out = swiglu(p["shared"], x)
        slots = jnp.zeros(x.shape[0], jnp.int32)
        for e in range(g["held"]):
            gate = jnp.where(topi == e, topw, 0.0).sum(-1)
            out = out + gate[..., None] * swiglu({n: p[n][e] for n in ("wi", "wg", "wo")}, x)
            slots = slots + (topi == e).sum((1, 2))
        return out, slots

    def layer(p, i, x):
        if not dense:
            p = jax.tree_util.tree_map(lambda a: a[i], p)
        p = jax.tree_util.tree_map(lambda a: _round(a, lower).astype(jnp.float32), p)
        h = x + mla(p["attn"], rms(x, p["ln1"]))
        z = rms(h, p["ln2"])
        if dense:
            return h + swiglu(p["mlp"], z), jnp.zeros(x.shape[0], jnp.int32)
        y, slots = moe(p["moe"], z)
        return h + y, slots

    return jax.jit(layer)


def _round(a, lower: bool):
    import jax.numpy as jnp

    return a.astype(jnp.float8_e4m3fn).astype(a.dtype) if lower else a


def _reference(m: dict, lower: bool, rows: np.ndarray):
    """(last-token residual after every layer (N, L, d) float32, routed
    slots on held experts per row and layer (N, L) int32)."""
    import jax
    import jax.numpy as jnp

    g = dict(_dims(m), norm_topk=bool(m["norm_topk_prob"]))
    wts = _weights(json.dumps(m, sort_keys=True))
    moe_fn, dense_fn = _layer_fn(g, False, lower), _layer_fn(g, True, lower)
    n, L, B = rows.shape[0], g["L"], ROW_BLOCK
    feats = np.zeros((n, L, g["d"]), np.float32)
    slots = np.zeros((n, L), np.int32)
    with jax.default_matmul_precision("highest"):
        tok = wts["embed"]["tok"]
        for i in range(0, n, B):
            blk = np.zeros((B, rows.shape[1]), np.int32)
            blk[: min(B, n - i)] = rows[i : i + B]
            x = _round(jnp.take(tok, jnp.asarray(blk), axis=0), lower).astype(jnp.float32)
            for li in range(L):
                if li < g["n_dense"]:
                    x, sl = dense_fn(wts["pre_layers"][li], 0, x)
                else:
                    x, sl = moe_fn(wts["layers"], li - g["n_dense"], x)
                m_ = min(B, n - i)
                feats[i : i + m_, li] = np.asarray(x[:m_, -1])
                slots[i : i + m_, li] = np.asarray(sl[:m_])
    return feats, slots


def _features(m: dict, lower: bool, rows: np.ndarray):
    """``_reference``, kept in the cache by the weights and the rows (the
    residuals and the slot counts in files of their own)."""
    rows = np.ascontiguousarray(rows, np.int32)
    key = _key(m, lower, rows.tobytes())
    feats_path, slots_path = CACHE / f"feats-{key}.npy", CACHE / f"slots-{key}.npy"
    if feats_path.exists() and slots_path.exists():
        return np.load(feats_path), np.load(slots_path)
    feats, slots = _reference(m, lower, rows)
    _save(feats_path, feats)
    _save(slots_path, slots)
    return feats, slots


def _save(path: Path, a: np.ndarray) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(".partial.npy")
    np.save(tmp, a)
    tmp.replace(path)


# --------------------------------------------------------------- the kind


def scores(params: dict, x: np.ndarray) -> np.ndarray:
    """(N, L) float32 stage scores ``f_t = s_t - s_{t-1}`` of the plain
    float32 model; the exit scores ``s`` are kept in the cache."""
    m, lower = _m(params), "weights" in params
    rows = np.ascontiguousarray(x, np.int32)
    heads = np.asarray(params["exit_heads"], np.float32)
    path = CACHE / f"scores-{_key(m, lower, rows.tobytes(), heads.tobytes())}.npy"
    if path.exists():
        s = np.load(path)
    else:
        feats, _ = _features(m, lower, rows)
        s = np.einsum("nld,ld->nl", feats.astype(np.float64), heads).astype(np.float32)
        _save(path, s)
    return np.diff(s, axis=1, prepend=0.0).astype(np.float32)


def train(cfg: dict, w) -> tuple[dict, float]:
    """Exit heads fit on probe rows, and beta.  The program's scorer is
    built first, on the weights' shapes alone, so that a program that
    cannot serve the model stops before the reference runs."""
    import jax

    m = _model(cfg)
    g = _dims(m)
    _scorer(m, jax.tree_util.tree_map(
        lambda leaf: jax.ShapeDtypeStruct(leaf[0], jax.numpy.bfloat16), _shapes(m),
        is_leaf=_is_leaf,
    ), np.zeros((g["L"], g["d"]), np.float32))
    feats, _ = _features(m, False, _probes(cfg))
    heads = np.zeros((g["L"], g["d"]))
    # the last exit reads the probes' direction of largest variation
    last = feats[:, -1].astype(np.float64)
    heads[-1] = np.linalg.svd(last - last.mean(0), full_matrices=False)[2][0] / math.sqrt(g["d"])
    final = feats[:, -1].astype(np.float64) @ heads[-1]
    for li in range(g["L"] - 1):
        heads[li] = _probe(feats[:, li].astype(np.float64), final, float(cfg["ridge"]))
    params = {"model": np.str_(json.dumps(m, sort_keys=True)), "exit_heads": heads.astype(np.float32)}
    for rows in (w.x_train, w.pool):
        scores(params, rows)
    return params, float(np.median(final))


def _probe(f: np.ndarray, y: np.ndarray, ridge: float) -> np.ndarray:
    """Ridge regression of ``y`` on the rows ``f``, with no intercept: the
    score it gives predicts ``y`` itself (the program's heads have no bias)."""
    gram = f.T @ f
    lam = ridge * np.trace(gram) / f.shape[1]
    return np.linalg.solve(gram + lam * np.eye(f.shape[1]), f.T @ y)


def lower_precision(params: dict) -> dict:
    """The control: every bfloat16 weight rounded to float8_e4m3fn."""
    return {**params, "weights": np.str_("float8_e4m3fn")}


def _scorer(m: dict, tree, heads):
    from repro import api
    from repro.models.config import ModelConfig

    g = _dims(m)
    rs = g["rs"]
    cfg = ModelConfig(
        name="deepseek-v2-lite", arch_type="moe", n_layers=g["L"], d_model=g["d"],
        n_heads=g["H"], n_kv_heads=g["H"], d_ff=g["ff"], vocab_size=g["V"], attn_kind="mla",
        kv_lora_rank=g["r"], rope_head_dim=g["dr"], nope_head_dim=g["dn"], v_head_dim=g["dv"],
        rope_theta=float(g["theta"]), rope_factor=float(rs["factor"]),
        rope_original_max_pos=int(rs["original_max_position_embeddings"]),
        rope_beta_fast=float(rs["beta_fast"]), rope_beta_slow=float(rs["beta_slow"]),
        rope_mscale=float(rs["mscale"]), rope_mscale_all_dim=float(rs["mscale_all_dim"]),
        n_experts=g["E"], n_shared_experts=g["shared"], top_k=g["k"], moe_d_ff=g["f"],
        first_dense_layers=g["n_dense"], norm_topk_prob=bool(m["norm_topk_prob"]),
        experts_held=tuple(range(g["held"])), tie_embeddings=False, scale_embeddings=False,
        norm_eps=float(g["eps"]), exit_interval=1,
    )
    return api.NeuralScorer({**tree, "exit_heads": heads}, cfg, seq_len=g["S"])


def program_scorer(params: dict):
    import jax.numpy as jnp

    m = _m(params)
    wts = _weights(json.dumps(m, sort_keys=True))
    return _scorer(m, wts, jnp.asarray(params["exit_heads"], jnp.float32))


def fit_settings(cfg: dict, T: int) -> dict:
    """Depth order, each position costed by its operations: the dense
    layer 0 against an MoE layer."""
    return {"optimize_order": False, "order": np.arange(T), "costs": model_ops(cfg)}


def rounding_band(cfg: dict) -> tuple[float, float, str]:
    return BAND


# (rel_tol, max_ambiguous_share, why), set between two readings of
# bench/tools/rounding_readings.py on the pool at full size on a TPU v5e:
# the least sound band for the bfloat16 program is 0.00995 x sum|f| (and
# leaves out 15.8% of pool rows), for the float8 control 0.0771; at 0.015
# the pool's share left out is 22.3%, 18-25% of the rows a window serves
BAND = (
    0.015,
    0.30,
    "bfloat16 program against a float32 reference over 27 layers, two thresholds a layer: a "
    "row within 0.015 x sum|f| of a threshold it meets is not compared",
)


# --------------------------------------------------------------- work


def _per_token(cfg: dict) -> tuple[np.ndarray, float]:
    """(operations per token at each position, expected routed slots on
    held experts per token)."""
    g = _dims(_model(cfg))
    d, H, S = g["d"], g["H"], g["S"]
    keys = (S + 1) / 2  # causal: the mean number of keys a query sees
    mla = 2 * d * (H * (g["dn"] + g["dr"]) + g["r"] + g["dr"]) + 2 * g["r"] * H * (g["dn"] + g["dv"])
    mla += 2 * H * g["dv"] * d + 2 * H * (g["dn"] + g["dr"] + g["dv"]) * keys
    slots = g["k"] * g["held"] / g["E"]
    moe = 2 * d * g["E"] + 6 * d * g["f"] * (g["shared"] + slots)
    per = np.full(g["L"], mla + moe, np.float64)
    per[: g["n_dense"]] = mla + 6 * d * g["ff"]
    return per, slots


def model_ops(cfg: dict) -> np.ndarray:
    """Least operations of one row through each layer (multiply-adds
    count 2): MLA projections and causal attention over (S + 1) / 2 keys
    on average, then the dense SwiGLU (layer 0) or the router, the shared
    experts and the expected k x held / E routed slots on held experts."""
    per, _ = _per_token(cfg)
    return per * int(cfg["seq_len"])


def model_param_bytes(cfg: dict) -> np.ndarray:
    """Each layer's weights at bfloat16 (the router at float32)."""
    g = _dims(_model(cfg))
    d, H = g["d"], g["H"]
    mla = d * H * (g["dn"] + g["dr"]) + d * (g["r"] + g["dr"]) + g["r"] * H * (g["dn"] + g["dv"])
    mla += H * g["dv"] * d + g["r"] + 2 * d
    per = np.full(g["L"], 2.0 * (mla + 3 * d * g["f"] * (g["shared"] + g["held"])) + 4 * d * g["E"])
    per[: g["n_dense"]] = 2.0 * (mla + 3 * d * g["ff"])
    return per


def pool_slots(cfg: dict) -> np.ndarray | None:
    """(pool rows, layers) routed slots on held experts, from the cache, or
    None where the reference has not run in this checkout."""
    m = _model(cfg)
    rows = np.ascontiguousarray(world(cfg).pool, np.int32)
    path = CACHE / f"slots-{_key(m, False, rows.tobytes())}.npy"
    return np.load(path) if path.exists() else None


def expert_work(cfg: dict, idx: np.ndarray, exit_steps: np.ndarray, flush_max_exit) -> tuple | None:
    """(operations, HBM bytes) the held-expert kernel needs for the served
    rows: 3 matmuls of d x F per routed slot of each row's evaluated
    layers; per flush, the held experts' weights of every MoE layer up to
    its deepest exit, and each slot's row read and written once."""
    table = pool_slots(cfg)
    if table is None:
        return None
    g = _dims(_model(cfg))
    prefix = np.concatenate([np.zeros((table.shape[0], 1)), np.cumsum(table, 1)], 1)
    slots = float(prefix[np.asarray(idx), np.asarray(exit_steps, np.int64)].sum())
    flops = 6.0 * g["d"] * g["f"] * slots
    moe_layers = np.maximum(np.asarray(flush_max_exit, np.int64) - g["n_dense"], 0)
    nbytes = 2.0 * (3 * g["d"] * g["f"] * g["held"] * moe_layers.sum() + 2 * g["d"] * slots)
    return flops, nbytes
