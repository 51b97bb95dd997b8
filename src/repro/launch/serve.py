"""Serving launcher: batched QWYC ensemble serving end-to-end.

Trains (or loads) an ensemble, optimizes QWYC ordering+thresholds on the
train split, then serves the test split through the batched engine and
reports speedup / faithfulness — the paper's production scenario.

    PYTHONPATH=src python -m repro.launch.serve --dataset adult --ensemble gbt \
        --T 200 --alpha 0.005 --backend auto --policy sorted-kernel

``--backend`` names the EXECUTION backend from the registry
(``repro.api``): ``auto`` (default — negotiates sharded -> device -> host
from the available devices), ``host``, ``device``, or ``sharded``.
``--policy`` is the server's sorting/decide policy (what ``--backend``
used to mean).  The old ``--device`` / ``--shards N`` flags were retired
after their deprecation cycle: they now fail fast, naming the
``--backend device`` / ``--backend sharded --backend-shards N``
replacements.
"""

from __future__ import annotations

import argparse
import signal
import warnings

import jax.numpy as jnp
import numpy as np

from repro.api import scorers
from repro.api.registry import backend_names, resolve_backend
from repro.core import fit_qwyc
from repro.data.synthetic import make_dataset
from repro.ensembles.gbt import train_gbt
from repro.ensembles.lattice import init_lattice_ensemble, train_lattice_ensemble
from repro.kernels import ops
from repro.launch.compile_cache import setup_compile_cache
from repro.serving.engine import BACKENDS as POLICIES
from repro.serving.engine import QWYCServer, StreamingServer

# row-block size for the lazy chunked score kernels: survivors are padded
# up to a multiple of this, so smaller blocks waste less late-stage compute
# (billed honestly via score_block_n below)
SCORE_BLOCK_N = 64


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="adult", choices=["adult", "nomao", "rw1", "rw2"])
    ap.add_argument("--ensemble", default="gbt", choices=["gbt", "lattice"])
    ap.add_argument("--T", type=int, default=200)
    ap.add_argument("--depth", type=int, default=5)
    ap.add_argument("--alpha", type=float, default=0.005)
    ap.add_argument("--mode", default="both", choices=["both", "neg_only"])
    ap.add_argument(
        "--backend", default="auto",
        choices=("auto",) + backend_names() + POLICIES,
        help="execution backend from the repro.api registry (auto "
        "negotiates sharded -> device -> host from available devices); "
        "a policy name here is DEPRECATED — use --policy",
    )
    ap.add_argument(
        "--policy", default="sorted-kernel", choices=POLICIES,
        help="server sorting/decide policy (the pre-backend-registry "
        "meaning of --backend)",
    )
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--scale", type=float, default=0.5)
    ap.add_argument("--chunk-t", type=int, default=8)
    ap.add_argument(
        "--groups", type=int, default=None,
        help="serve RANKING queries (DESIGN.md §12): chop the splits into "
        "ragged query groups with this mean document count (seeded), fit "
        "GROUP-level exit thresholds (api.fit(groups=...)) and serve "
        "per-query top-k verdicts through the grouped cascade",
    )
    ap.add_argument(
        "--topk", type=int, default=10,
        help="ranking depth k for --groups serving (default 10)",
    )
    ap.add_argument(
        "--eager", action="store_true",
        help="precompute the full (N, T) score matrix per batch instead of "
        "the lazy chunked producer (DESIGN.md §4)",
    )
    ap.add_argument(
        "--device", action="store_true",
        help="REMOVED: use --backend device",
    )
    ap.add_argument(
        "--shards", type=int, default=None,
        help="REMOVED: use --backend sharded --backend-shards N",
    )
    ap.add_argument(
        "--backend-shards", type=int, default=None,
        help="data-parallel width for --backend sharded/auto (default: all "
        "devices; on CPU run under "
        "XLA_FLAGS=--xla_force_host_platform_device_count=N)",
    )
    ap.add_argument(
        "--model-shards", type=int, default=None,
        help="model-parallel width for --backend sharded/auto: shard every "
        "stage's param slab over a second 'model' mesh axis, one psum per "
        "stage step (DESIGN.md §13); total devices = data x model shards",
    )
    ap.add_argument(
        "--rebalance", action="store_true",
        help="sharded backend: all-gather repack of survivor buffers "
        "between stages when shard occupancy skews (DESIGN.md §6)",
    )
    ap.add_argument(
        "--audit", action="store_true",
        help="recompute early-exited rows' full scores to measure diff vs "
        "full ensemble (extra work that can exceed the lazy savings; off "
        "by default so the CLI reflects production serving cost)",
    )
    ap.add_argument(
        "--streaming", action="store_true",
        help="continuous batching (DESIGN.md §8): requests wait in an "
        "arrival-order queue and the device admission ring refills freed "
        "survivor slots mid-cascade; needs an on-device --backend",
    )
    ap.add_argument(
        "--max-wait", type=float, default=None,
        help="streaming admission deadline in stage steps: launch a "
        "partial wave once the oldest queued request has waited this long "
        "(default: wait for a full window)",
    )
    ap.add_argument(
        "--stream-window", type=int, default=None,
        help="streaming admission-ring size per device wave (default: "
        "4x the slot capacity)",
    )
    ap.add_argument(
        "--arrival-rate", type=float, default=4.0,
        help="streaming Poisson arrival rate in requests per stage step "
        "(fixed seed, so the trace — and the billing — is deterministic)",
    )
    # guarded serving (DESIGN.md §10)
    ap.add_argument(
        "--chaos-seed", type=int, default=None,
        help="arm a deterministic fault-injection plan "
        "(repro.testing.faults) around the serving loop; combine with "
        "the other --chaos-* flags to pick the faults",
    )
    ap.add_argument(
        "--chaos-poison", type=float, default=0.0,
        help="fraction of test rows poisoned with non-finite values "
        "under --chaos-seed (quarantine should catch every one)",
    )
    ap.add_argument(
        "--chaos-wave-failures", type=int, default=0,
        help="number of device waves to fail under --chaos-seed (drives "
        "the retry/degradation ladder)",
    )
    ap.add_argument(
        "--chaos-drop-device", action="store_true",
        help="report the sharded rung's devices as lost under "
        "--chaos-seed (ladder falls sharded -> device)",
    )
    ap.add_argument(
        "--watchdog", action="store_true",
        help="run the sequential drift watchdog over the audit stream "
        "and degrade the decide policy on alarm (implies --audit)",
    )
    ap.add_argument(
        "--no-quarantine", dest="quarantine", action="store_false",
        help="disable the submit-time validation guard (bad rows then "
        "raise instead of draining with a quarantined verdict)",
    )
    return ap


def resolve_backend_args(args) -> tuple[str, dict, str]:
    """(exec_backend_name, backend_opts, policy) from parsed CLI args.

    A policy name under ``--backend`` still emits ``DeprecationWarning``
    and forwards to ``--policy``.  The boolean-era ``--device`` /
    ``--shards N`` spellings were retired after their warning cycle:
    they raise ``ValueError`` naming the replacement (tests assert the
    pointed message).
    """
    if args.device:
        raise ValueError(
            "--device was removed after its deprecation cycle; "
            "use --backend device"
        )
    if args.shards is not None:
        raise ValueError(
            "--shards was removed after its deprecation cycle; "
            "use --backend sharded --backend-shards N"
        )
    backend, policy = args.backend, args.policy
    if backend in POLICIES:
        warnings.warn(
            f"--backend {backend} now names an execution backend; policy "
            f"names here are deprecated — use --policy {backend}",
            DeprecationWarning,
            stacklevel=2,
        )
        policy, backend = backend, "auto"
    opts: dict = {}
    if args.backend_shards is not None:
        opts["shards"] = int(args.backend_shards)
        if backend == "auto":
            # an explicit shard count IS a request for the sharded
            # backend — don't let auto negotiate down to device/host and
            # then reject the shards option
            backend = "sharded"
    if args.model_shards is not None:
        opts["model_shards"] = int(args.model_shards)
        if backend == "auto":
            # same contract as --backend-shards: an explicit model-axis
            # width IS a request for the (only) model-parallel backend
            backend = "sharded"
    if args.rebalance:
        opts["rebalance"] = True
    return backend, opts, policy


def _ragged_sizes(n: int, mean: int, rng) -> np.ndarray:
    """Partition ``n`` rows into ragged group sizes (Poisson around
    ``mean``, min 1, last group takes the remainder)."""
    sizes = []
    left = n
    while left > 0:
        s = int(min(left, max(1, rng.poisson(mean))))
        sizes.append(s)
        left -= s
    return np.asarray(sizes, dtype=np.int64)


def _serve_ranking(args, ds, score_fn, F_train, beta, backend_name, backend_opts):
    """``--groups`` mode: ragged ranking queries through the grouped
    cascade (fit group thresholds -> compile -> GroupedRankServer)."""
    from repro import api
    from repro.ranking import group_offsets, ndcg_at_k

    rng = np.random.default_rng(2031)
    sizes_tr = _ragged_sizes(len(ds.y_train), args.groups, rng)
    fitted = api.fit(
        F_train, groups=sizes_tr, topk=args.topk,
        alpha=args.alpha, beta=beta, mode=args.mode, chunk_t=args.chunk_t,
    )
    gp = fitted.grouped
    print(
        f"[serve] grouped fit: {sizes_tr.size} train queries "
        f"(mean {sizes_tr.mean():.1f} docs), S={gp.S}, k={gp.k}, "
        f"train disagreement {gp.train_disagreement:.4f} (alpha={args.alpha})"
    )
    compiled = fitted.compile(backend_name, **backend_opts)
    server = compiled.serve(
        score_fn=score_fn, streaming=args.streaming,
        batch_size=args.batch_size,
    )
    sizes_te = _ragged_sizes(len(ds.y_test), args.groups, rng)
    offsets = group_offsets(sizes_te)
    arr_rng = np.random.default_rng(2028)
    arrivals = np.cumsum(
        arr_rng.exponential(1.0 / args.arrival_rate, size=sizes_te.size)
    )
    for i in range(sizes_te.size):
        docs = ds.x_test[offsets[i] : offsets[i + 1]]
        if args.streaming:
            server.submit(docs, arrival=float(arrivals[i]))
        else:
            server.submit(docs)
    results = server.drain()
    st = server.stats
    # NDCG against the binary test labels as graded relevance (the
    # synthetic splits have no per-document grades)
    verd = np.full((sizes_te.size, gp.k), -1, dtype=np.int64)
    for i, r in enumerate(results):
        ids = np.asarray(r["ranking"], dtype=np.int64) + offsets[i]
        verd[i, : ids.size] = ids
    ndcg = ndcg_at_k(ds.y_test, verd, sizes_te, gp.k)
    print(
        f"[serve] ranking: {st.n_queries} queries / {st.n_docs} docs in "
        f"{st.n_waves} wave(s) ({compiled.backend_name} backend, "
        f"{'streaming' if args.streaming else 'batch'})\n"
        f"        mean exit stage {st.mean_exit_stage:.2f}/{gp.S}  "
        f"scores computed {st.scores_computed}/{st.scores_possible} "
        f"({st.compute_fraction:.1%} of eager)\n"
        f"        NDCG@{gp.k} {ndcg:.4f}"
    )


def main() -> None:
    ap = build_parser()
    args = ap.parse_args()
    setup_compile_cache()
    backend_name, backend_opts, policy = resolve_backend_args(args)
    backend = resolve_backend(backend_name)
    if backend_opts.get("rebalance") and not backend.capabilities.supports_rebalance:
        ap.error(
            f"--rebalance requires the sharded backend (resolved {backend.name!r})"
        )
    if backend_opts.get("model_shards", 1) > 1 and not getattr(
        backend.capabilities, "model_parallel", False
    ):
        ap.error(
            f"--model-shards requires a model-parallel backend "
            f"(resolved {backend.name!r}; use --backend sharded)"
        )
    on_device = backend.capabilities.on_device

    ds = make_dataset(args.dataset, scale=args.scale)
    print(f"[serve] dataset={args.dataset} train={len(ds.y_train)} test={len(ds.y_test)}")

    if args.ensemble == "gbt":
        gbt = train_gbt(ds.x_train, ds.y_train, n_trees=args.T, depth=args.depth)
        stacked = gbt.stacked()
        beta = -gbt.base_score

        def score_fn(x):
            return ops.gbt_scores(
                stacked["feats"], stacked["thrs"], stacked["leaves"], jnp.asarray(x)
            )

        def make_chunk_score_fn(order):
            # stacked params permuted to cascade order once, so a cascade
            # range is a contiguous slab for the model-range kernel
            of = jnp.asarray(np.asarray(stacked["feats"])[order])
            ot = jnp.asarray(np.asarray(stacked["thrs"])[order])
            ol = jnp.asarray(np.asarray(stacked["leaves"])[order])

            def chunk_score_fn(x, rows, t0, t1):
                return ops.gbt_scores(
                    of, ot, ol, x, t0=t0, t1=t1, rows=jnp.asarray(rows),
                    block_n=SCORE_BLOCK_N,
                )

            return chunk_score_fn

        def make_scorer():
            # StageScorer templates take ORIGINAL-order params; the bind
            # step applies the plan's cascade order itself (DESIGN.md §11)
            return scorers.TreeScorer(
                np.asarray(stacked["feats"]),
                np.asarray(stacked["thrs"]),
                np.asarray(stacked["leaves"]),
                block_n=SCORE_BLOCK_N,
            )

    else:
        lat = init_lattice_ensemble(args.T, ds.D, S=min(8, ds.D), seed=0)
        lat = train_lattice_ensemble(lat, ds.x_train, ds.y_train, mode="joint", steps=300)
        beta = 0.0

        def score_fn(x):
            return ops.lattice_scores(lat["theta"], lat["feats"], jnp.asarray(x))

        def make_chunk_score_fn(order):
            th = jnp.asarray(np.asarray(lat["theta"])[order])
            fe = jnp.asarray(np.asarray(lat["feats"])[order])

            def chunk_score_fn(x, rows, t0, t1):
                return ops.lattice_scores(
                    th, fe, x, t0=t0, t1=t1, rows=jnp.asarray(rows),
                    block_n=SCORE_BLOCK_N,
                )

            return chunk_score_fn

        def make_scorer():
            return scorers.LatticeScorer(
                np.asarray(lat["theta"]),
                np.asarray(lat["feats"]),
                block_n=SCORE_BLOCK_N,
            )

    F_train = np.asarray(score_fn(ds.x_train))
    if args.groups is not None:
        _serve_ranking(
            args, ds, score_fn, F_train, beta, backend_name, backend_opts
        )
        return
    qwyc = fit_qwyc(F_train, beta=beta, alpha=args.alpha, mode=args.mode)
    print(
        f"[serve] QWYC fit: train mean models {qwyc.train_mean_models:.2f}/{args.T} "
        f"diff {qwyc.train_diff_rate:.4f}"
    )

    producer_kw = (
        {"score_fn": score_fn}
        if args.eager
        else {"chunk_score_fn": make_chunk_score_fn(qwyc.order)}
    )
    if on_device and not args.eager:
        # fully lazy device path; chunk_score_fn stays as the audit reader
        producer_kw["scorer"] = make_scorer()
    audit = args.audit or args.eager or args.watchdog
    common_kw = dict(
        batch_size=args.batch_size,
        chunk_t=args.chunk_t, audit_full_scores=audit,
        score_block_n=1 if args.eager else SCORE_BLOCK_N,
        exec_backend=backend, backend_opts=backend_opts,
        quarantine=args.quarantine,
        watchdog=True if args.watchdog else None,
        **producer_kw,
    )
    if args.streaming:
        if not getattr(backend.capabilities, "streaming", False):
            ap.error(
                f"--streaming needs an on-device backend (resolved "
                f"{backend.name!r}; see Backend.capabilities.streaming)"
            )
        server = StreamingServer(
            qwyc, window=args.stream_window, max_wait=args.max_wait,
            **common_kw,
        )
        # deterministic Poisson arrival trace (stage-step units): the
        # same seed the streaming benchmark uses, so the CLI numbers are
        # reproducible run to run
        arr_rng = np.random.default_rng(2028)
        arrivals = np.cumsum(
            arr_rng.exponential(1.0 / args.arrival_rate, size=len(ds.y_test))
        )
    else:
        server = QWYCServer(qwyc, backend=policy, **common_kw)
        arrivals = None
    if server.mesh is not None:
        print(f"[serve] sharded serving mesh: {server.mesh}")

    # chaos plan (DESIGN.md §10): every fault below is derived from
    # --chaos-seed, so a run reproduces bit-for-bit
    x_test = ds.x_test
    chaos = None
    if args.chaos_seed is not None:
        from repro.testing import FaultPlan

        chaos = FaultPlan(
            seed=args.chaos_seed,
            poison_fraction=args.chaos_poison,
            poison_mode="mix",
            wave_failures=args.chaos_wave_failures,
            # device loss means the SHARDED rung's waves die; the rungs
            # below must stay healthy or there is nowhere to degrade to
            wave_fail_backend="sharded" if args.chaos_drop_device else None,
            drop_device=args.chaos_drop_device,
        )
        if args.chaos_poison > 0:
            x_test, poisoned = chaos.poison(x_test)
            print(
                f"[serve] chaos seed {args.chaos_seed}: poisoned "
                f"{int(poisoned.sum())}/{len(x_test)} rows"
            )
        chaos.__enter__()

    # a SIGINT/SIGTERM during the submit loop stops admission, drains the
    # queue (partial final flush) and still prints the final ServeStats
    stop: dict = {}
    prev_handlers = {}

    def _on_signal(signum, frame):
        stop["sig"] = signal.Signals(signum).name

    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            prev_handlers[sig] = signal.signal(sig, _on_signal)
        except ValueError:  # non-main thread (tests): run unguarded
            pass

    try:
        for i in range(len(ds.y_test)):
            if stop:
                print(
                    f"[serve] caught {stop['sig']} after {i} submit(s): "
                    f"draining queued requests"
                )
                break
            if arrivals is None:
                server.submit(x_test[i])
            else:
                server.submit(x_test[i], arrival=arrivals[i])
        results = server.drain()
    finally:
        for sig, h in prev_handlers.items():
            signal.signal(sig, h)
        if chaos is not None:
            chaos.__exit__(None, None, None)

    st = server.stats
    served = [
        (r, y)
        for r, y in zip(results, ds.y_test)
        if not r.get("quarantined", False)
    ]
    acc = (
        np.mean([r["decision"] == bool(y) for r, y in served])
        if served
        else float("nan")
    )
    if args.streaming:
        print(
            f"[serve] streaming: {st.admitted_rows} admitted over "
            f"{st.stream_steps} stage steps in {st.n_batches} wave(s)  "
            f"mean occupancy {st.mean_occupancy:.1%}\n"
            f"        latency (steps) mean {st.latency_mean:.1f}  "
            f"p50 {st.latency_p50:.0f}  p95 {st.latency_p95:.0f}  "
            f"p99 {st.latency_p99:.0f}"
            + (
                f"  (max_wait={args.max_wait})"
                if args.max_wait is not None
                else ""
            )
        )
    print(
        f"[serve] {st.n_requests} requests in {st.n_batches} batches "
        f"({server.exec.name} backend, "
        f"{'streaming' if args.streaming else policy + ' policy'}, "
        f"{'eager' if args.eager else 'lazy'}"
        f"{f', {server.n_shards} shards' if server.n_shards > 1 else ''})\n"
        f"        mean models {st.mean_models:.2f}/{args.T}  "
        f"modeled speedup {st.speedup:.2f}x\n"
        f"        scores computed {st.scores_computed}/{st.scores_possible} "
        f"({st.compute_fraction:.1%} of eager; +{st.audit_scores} audit)\n"
        f"        diff vs full "
        + (
            f"{st.diff_rate:.4f}"
            if (args.audit or args.eager)
            else "n/a (pass --audit)"
        )
        + f" (alpha={args.alpha})  test acc {acc:.4f}"
    )
    # guarded-serving counters (additive; not part of the perf-gate
    # baseline — see benchmarks/perf_gate.py)
    guard_bits = []
    if st.quarantined:
        guard_bits.append(f"quarantined {st.quarantined}")
    if st.degradation_events:
        falls = [
            f"{e.from_backend}->{e.to_backend}"
            for e in st.degradation_events
            if e.from_backend != e.to_backend
        ]
        recoveries = len(st.degradation_events) - len(falls)
        guard_bits.append(
            "ladder " + ", ".join(falls + ([f"{recoveries} same-rung recovery(ies)"] if recoveries else []))
        )
    if args.watchdog:
        guard_bits.append(
            f"watchdog {st.watchdog_state} (alarms {st.watchdog_alarms}, "
            f"llr {st.watchdog_stat:.2f}"
            + (
                f", recovered at flush {st.watchdog_recovery_step}"
                if st.watchdog_recovery_step is not None
                else ""
            )
            + ")"
        )
    if guard_bits:
        print("[serve] guards: " + "  |  ".join(guard_bits))


if __name__ == "__main__":
    main()
