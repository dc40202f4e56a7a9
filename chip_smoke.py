#!/usr/bin/env python3
"""Chip smoke test: the serving path on a TPU at a published model width.

Serves the paper's 2-layer Kipf GCN (``configs/gcn_paper.py``, hidden 16)
at citeseer width, 3703 -> 16 -> 6, through the normal entry points
(``InferenceEngine`` -> ``repro.compile`` -> ``Program`` -> the registered
kernels), checks every output against a plain float32 ``jax.numpy``
reference, and checks that both Pallas aggregation kernels ran on the chip.
It then takes one training step per kernel through ``repro.compile`` and
``Program.train_step``, against ``jax.value_and_grad`` of the reference.

    python chip_smoke.py             # one chip: the serving path
    python chip_smoke.py --chips 4   # only the paths across chips

With ``--chips 4`` it runs only what exists across chips: ``AsyncEngine``
spreading a mixed mutag/imdb-bin stream over four chips (compared with
the one-device sync engine), and the ``pp`` phase mesh on two chips
(compared with the ``seq`` path).

Any failure raises, so the exit code is non-zero; so does a host where JAX
finds no TPU, before anything runs.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

SEED = 0
HIDDEN = 16  # configs/gcn_paper.py
CITESEER_CLASSES = 6
GRAPH_CLASSES = 2  # mutag and imdb-bin are binary graph classification

#: Outputs must satisfy max|out - ref| <= TOL * max(1, max|ref|).  The
#: reference runs at "highest" matmul precision; the engine runs JAX's
#: default precision, which on a TPU rounds float32 matmul operands to
#: bfloat16 (2^-9 relative per operand).  On a TPU v5e every path reads
#: 7.19e-3 at citeseer width, as the rounding of layer 1's 3703-long
#: x @ W, which all paths share, would give.  Faults planted in the
#: kernels' inputs read well above the bound there: one wrong neighbour
#: index on row 0 of each batch 2.4e-1 (bound 3.9e-2), layer 1's first
#: 128 feature columns dropped 3.2e-1 (bound 5.7e-2).
TOL = 3e-2

#: Training-step gradients, as a share of the gradient's largest entry.
#: The Pallas step must equal the jnp path's step (``use_pallas=False``)
#: on the same chip at the same precision to GRAD_TOL: they round the same
#: operands to bfloat16 and differ only in summation order (the seq step
#: read 2.9e-5 on a TPU v5e).  Both must match the "highest"-precision
#: reference to GRAD_REF_TOL: at initialisation, with random labels, the
#: gradient is a sum over nodes that nearly cancels, which magnifies the
#: bf16 rounding (both paths read 3.3e-2 on a TPU v5e); a gradient that
#: does not flow through a kernel reads 1.
GRAD_TOL = 1e-3
GRAD_REF_TOL = 1e-1


def require_tpu(n_chips: int) -> list:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU; JAX found {devices[0].platform!r}"
        )
    if len(devices) < n_chips:
        raise SystemExit(
            f"chip_smoke: needs {n_chips} chips; JAX found {len(devices)}"
        )
    return devices


def dense_adjacency(graph) -> np.ndarray:
    """Â dense from the CSR values (the GCN normalisation the engine serves)."""
    n = graph.n_nodes
    a = np.zeros((n, n), np.float32)
    a[np.repeat(np.arange(n), np.diff(graph.row_ptr)), graph.col_idx] = (
        graph.values
    )
    return a


def describe_dir(d: Path) -> str:
    files = [p for p in d.rglob("*") if p.is_file()] if d.is_dir() else []
    return f"{len(files)} files, {sum(p.stat().st_size for p in files)} bytes"


def count_cache_events() -> dict:
    """Counts of JAX's persistent-cache lookups, hits and writes from now on."""
    names = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
             "/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "writes"}
    counts = dict.fromkeys(names.values(), 0)

    def listener(event, **_):
        if event in names:
            counts[names[event]] += 1

    jax.monitoring.register_event_listener(listener)
    return counts


def gcn_forward_ref(adj, x, params):
    """relu(Â·H·W + b) per layer in plain jax.numpy."""
    h = x
    for p in params:
        h = jax.nn.relu(adj @ (h @ p["w"]) + p["b"])
    return h


def gcn_reference(graph, x, params) -> np.ndarray:
    """:func:`gcn_forward_ref` in float32 at "highest" matmul precision."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(gcn_forward_ref(
            jnp.asarray(dense_adjacency(graph)), jnp.asarray(x), params
        ))


def check_close(name: str, out, ref) -> float:
    """max|out - ref|, which must be at most TOL * max(1, max|ref|)."""
    out, ref = np.asarray(out), np.asarray(ref)
    if out.shape != ref.shape:
        raise AssertionError(f"{name}: shape {out.shape} != {ref.shape}")
    if not np.isfinite(out).all():
        raise AssertionError(f"{name}: non-finite output")
    err = float(np.max(np.abs(out - ref)))
    bound = TOL * max(1.0, float(np.max(np.abs(ref))))
    if err > bound:
        raise AssertionError(f"{name}: max|out-ref| {err:.3e} > {bound:.3e}")
    return err


def induced_subgraph(graph, nodes: np.ndarray):
    """The subgraph on ``nodes`` (renormalised, self-loops re-added)."""
    from repro.graphs import from_edges

    pos = np.full(graph.n_nodes, -1)
    pos[nodes] = np.arange(len(nodes))
    src = np.repeat(np.arange(graph.n_nodes), np.diff(graph.row_ptr))
    dst = graph.col_idx
    keep = (pos[src] >= 0) & (pos[dst] >= 0) & (src != dst)
    return from_edges(len(nodes), pos[src[keep]], pos[dst[keep]])


def bfs_nodes(graph, root: int, size: int) -> np.ndarray:
    """Up to ``size`` nodes reached breadth-first from ``root``."""
    seen, frontier = {root}, [root]
    while frontier and len(seen) < size:
        nxt = []
        for v in frontier:
            for u in graph.col_idx[graph.row_ptr[v]:graph.row_ptr[v + 1]]:
                if u not in seen and len(seen) < size:
                    seen.add(int(u))
                    nxt.append(int(u))
        frontier = nxt
    return np.array(sorted(seen))


def citeseer_requests(f_in: int, sizes, seed: int = SEED):
    """The full citeseer graph plus subgraphs cut from it, with seeded
    ``f_in``-wide features; returns (requests, graphs, features)."""
    from repro.graphs.datasets import load_dataset
    from repro.runtime import Request

    g, _ = load_dataset("citeseer", seed=seed)
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(g.n_nodes, f_in)).astype(np.float32)
    graphs, feats = [g], [x]
    for size in sizes:
        nodes = bfs_nodes(g, int(rng.integers(g.n_nodes)), size)
        graphs.append(induced_subgraph(g, nodes))
        feats.append(x[nodes])
    reqs = [Request(graph=gi, x=xi, rid=i)
            for i, (gi, xi) in enumerate(zip(graphs, feats))]
    return reqs, graphs, feats


def layer_report(engine) -> list[str]:
    """The (policy, order, pallas?) each layer of each cached bucket
    Program resolved to in the kernel registry."""
    from repro.core import resolve_kernel_key

    lines = []
    for (v_bucket, v_total, d_bucket), prog in engine.programs():
        keys = [resolve_kernel_key(s.policy, s.order, s.use_pallas)
                for s in prog.specs]
        lines.append(f"  bucket V{v_bucket}xD{d_bucket} (rows {v_total}): "
                     + "; ".join(f"({p}, {o}, pallas={u})" for p, o, u in keys))
    return lines


def pallas_engine(dims, *, schedule=None, readout=None, **kw):
    from repro.runtime import InferenceEngine, Tier

    return InferenceEngine(
        dims, readout=readout, schedule=schedule, use_pallas=True,
        ladder=(Tier("pallas+searched", True, True),), **kw,
    )


def require_served(engine, results, tier: str = "pallas+searched") -> None:
    for r in results:
        if r.status != "ok" or r.tier != tier:
            raise AssertionError(
                f"request {r.rid}: status {r.status} tier {r.tier} "
                f"({r.error_type}: {r.error})"
            )
    if engine.stats().n_downgrades:
        raise AssertionError(f"{engine.stats().n_downgrades} downgrades")


def phase_searched(f_in: int, sizes) -> None:
    """The mapper picks each bucket's schedule; every request must be
    served ok on the Pallas tier and match the reference."""
    dims = [(f_in, HIDDEN), (HIDDEN, CITESEER_CLASSES)]
    reqs, graphs, feats = citeseer_requests(f_in, sizes)
    engine = pallas_engine(dims)
    params = engine.init(jax.random.PRNGKey(SEED))
    t0 = time.perf_counter()
    results = engine.submit(reqs)
    cold_s = time.perf_counter() - t0
    require_served(engine, results)
    st = engine.stats()
    if st.n_buckets < 2 or st.n_batches >= len(reqs):
        raise AssertionError(
            f"want >= 2 buckets and a multi-graph micro-batch; got "
            f"{st.n_buckets} buckets, {st.n_batches} batches for "
            f"{len(reqs)} requests"
        )
    t0 = time.perf_counter()
    warm = engine.submit(reqs)
    warm_s = time.perf_counter() - t0
    require_served(engine, warm)
    errs = []
    for r, w, g, x in zip(results, warm, graphs, feats):
        ref = gcn_reference(g, x, params)
        errs.append(check_close(f"searched rid {r.rid}", r.output, ref))
        check_close(f"searched warm rid {w.rid}", w.output, ref)
    print(f"searched: {len(reqs)} requests (nodes "
          f"{[g.n_nodes for g in graphs]}), {st.n_buckets} buckets, "
          f"{st.n_batches} micro-batches, all ok on pallas+searched, "
          f"0 downgrades")
    print(f"searched: cold submit {cold_s:.3f} s (mapper search "
          f"{st.search_s:.3f} s, trace+compile {st.trace_s:.3f} s); "
          f"warm submit {warm_s:.3f} s")
    print(f"searched: max|out-ref| {max(errs):.3e} (tol {TOL} x max(1, max|ref|))")
    for line in layer_report(engine):
        print(line)


def phase_each_kernel(f_in: int) -> None:
    """Pin the schedule so each Pallas kernel runs whatever the mapper
    would pick: seq (ELL SpMM) and sp_opt/AC (fused agg+cmb)."""
    from repro.core import ModelSchedule
    from repro.graphs import assemble

    dims = [(f_in, HIDDEN), (HIDDEN, CITESEER_CLASSES)]
    reqs, graphs, feats = citeseer_requests(f_in, ())
    for policy, kernel in (("seq", "spmm_ell"), ("sp_opt", "fused_agg_cmb")):
        sched = ModelSchedule.from_policies(policy, "AC", dims)
        engine = pallas_engine(dims, schedule=sched)
        params = engine.init(jax.random.PRNGKey(SEED))
        t0 = time.perf_counter()
        results = engine.submit(reqs)
        cold_s = time.perf_counter() - t0
        require_served(engine, results)
        t0 = time.perf_counter()
        require_served(engine, engine.submit(reqs))
        warm_s = time.perf_counter() - t0
        err = check_close(f"{policy} rid 0", results[0].output,
                          gcn_reference(graphs[0], feats[0], params))
        ((_, prog),) = engine.programs()
        batch = assemble([graphs[0]], engine.policy)
        bound = prog.bind(batch.graph, pad_degree=batch.d_bucket)
        hlo = bound.lowered(
            params, jnp.asarray(batch.batch_features(feats[:1]))
        ).compile().as_text()
        if "tpu_custom_call" not in hlo:
            raise AssertionError(f"{policy}: no tpu_custom_call in the executable")
        print(f"{policy}/AC+pallas: ok, max|out-ref| {err:.3e}; "
              f"tpu_custom_call in HLO: True ({kernel} named: "
              f"{kernel in hlo}); cold submit {cold_s:.3f} s, "
              f"warm submit {warm_s:.3f} s")
        for line in layer_report(engine):
            print(line)


def phase_train(f_in: int, lr: float = 0.05) -> None:
    """One SGD step on citeseer per Pallas kernel, through ``repro.compile``
    with its default ``use_pallas`` (the TPU's kernels) and
    ``Program.train_step``: the gradient flows back through each kernel.
    The same step is taken on the jnp path (``use_pallas=False``).  The
    loss and the gradient each step applied are compared with
    ``jax.value_and_grad`` of the reference forward, and the two paths'
    gradients with each other.

    Gradients are compared on the scale of the whole gradient (its largest
    entry over all parameters), not leaf by leaf: a bias gradient is a sum
    over nodes with cancellation, so its own maximum understates the size
    of the terms whose bf16 rounding it carries (see GRAD_TOL)."""
    import repro
    from repro.core import ModelSchedule, resolve_kernel_key
    from repro.gnn import GNNConfig
    from repro.gnn.model import make_node_classification_task, masked_xent_loss
    from repro.graphs.datasets import load_dataset

    leaves = jax.tree_util.tree_leaves
    g, _ = load_dataset("citeseer", seed=SEED)
    cfg = GNNConfig(kind="gcn", f_in=f_in, hidden=HIDDEN,
                    n_classes=CITESEER_CLASSES)
    x, labels, mask = make_node_classification_task(
        g, f_in, CITESEER_CLASSES, seed=SEED
    )
    adj = jnp.asarray(dense_adjacency(g))

    def ref_loss(params):
        return masked_xent_loss(gcn_forward_ref(adj, x, params), labels, mask)

    def grad_err(got, want) -> tuple[float, list[float]]:
        """max|got - want| over the whole gradient, and per leaf, both
        divided by the gradient's largest entry."""
        scale = max(float(np.max(np.abs(w))) for w in leaves(want))
        per_leaf = [float(np.max(np.abs(np.asarray(a) - np.asarray(b)))) / scale
                    for a, b in zip(leaves(got), leaves(want))]
        return max(per_leaf), per_leaf

    for policy, kernel in (("seq", "spmm_ell"), ("sp_opt", "fused_agg_cmb")):
        sched = ModelSchedule.from_policies(policy, "AC", list(cfg.dims))
        steps = {}
        for use_pallas in (None, False):  # None: the default, Pallas on a TPU
            prog = repro.compile(cfg, graph=g, schedule=sched,
                                 use_pallas=use_pallas)
            on_pallas = {resolve_kernel_key(s.policy, s.order, s.use_pallas)[2]
                         for s in prog.specs}
            if on_pallas != {use_pallas is None}:
                raise AssertionError(
                    f"train {policy} use_pallas={use_pallas}: resolved "
                    f"Pallas flags {on_pallas}"
                )
            params = prog.init(jax.random.PRNGKey(SEED))
            t0 = time.perf_counter()
            loss, new = jax.block_until_ready(
                prog.train_step(params, x, labels, mask, lr=lr)
            )
            step_s = time.perf_counter() - t0
            applied = [(p - q) / lr for p, q in zip(leaves(params), leaves(new))]
            steps[use_pallas] = (loss, applied, step_s)
        with jax.default_matmul_precision("highest"):
            ref_l, ref_g = jax.value_and_grad(ref_loss)(params)
        (loss, grad, step_s), (_, grad_jnp, _) = steps[None], steps[False]
        err_l = abs(float(loss) - float(ref_l))
        err_ref, leaf_ref = grad_err(grad, ref_g)
        err_jnp, _ = grad_err(grad, grad_jnp)
        err_jnp_ref, _ = grad_err(grad_jnp, ref_g)
        print(f"train {policy}/AC+pallas ({kernel}): loss {float(loss):.6f} "
              f"(ref {float(ref_l):.6f}, |diff| {err_l:.3e}); "
              f"max|grad-ref|/max|ref grad| {err_ref:.3e} (per leaf "
              f"{', '.join(f'{e:.2e}' for e in leaf_ref)}; jnp path "
              f"{err_jnp_ref:.3e}); max|grad-jnp grad| {err_jnp:.3e}; "
              f"first step {step_s:.3f} s (trace+compile+run)")
        if err_l > TOL * max(1.0, abs(float(ref_l))):
            raise AssertionError(f"train {policy}: loss off by {err_l:.3e}")
        if err_jnp > GRAD_TOL or max(err_ref, err_jnp_ref) > GRAD_REF_TOL:
            raise AssertionError(
                f"train {policy}: gradient off the jnp path's by {err_jnp:.3e} "
                f"(tol {GRAD_TOL}), off the reference by {err_ref:.3e} / "
                f"{err_jnp_ref:.3e} (tol {GRAD_REF_TOL}), of its scale"
            )


def phase_async(n: int = 48) -> None:
    """AsyncEngine over every device: one engine per graph width, fed one
    interleaved mutag/imdb-bin stream; outputs must match the one-device
    sync engine, with distinct buckets on distinct devices."""
    from repro.graphs import TABLE4, BucketPolicy
    from repro.graphs.datasets import make_graph
    from repro.runtime import AsyncEngine, InferenceEngine, Request

    policy = BucketPolicy(min_nodes=8, max_graphs=8)
    rng = np.random.default_rng(SEED)
    names = ("mutag", "imdb-bin")
    engines, syncs, params = {}, {}, {}
    streams = {name: [] for name in names}
    for i in range(n):
        name = names[i % 2]
        spec = TABLE4[name]
        g = make_graph(spec, rng)
        x = rng.normal(size=(g.n_nodes, spec.n_features)).astype(np.float32)
        streams[name].append(Request(graph=g, x=x, rid=i))
    for name in names:
        dims = [(TABLE4[name].n_features, HIDDEN), (HIDDEN, GRAPH_CLASSES)]
        syncs[name] = InferenceEngine(dims, policy=policy, readout="mean")
        params[name] = syncs[name].init(jax.random.PRNGKey(SEED))
        engines[name] = AsyncEngine(dims, params[name], window_ms=20.0,
                                    policy=policy, readout="mean")
    for e in engines.values():
        e.start()
    try:
        futs = []
        for i in range(n):  # one mixed arrival order across both engines
            name = names[i % 2]
            futs.append((name, engines[name].submit_async(streams[name][i // 2])))
        got = {name: [] for name in names}
        for name, f in futs:
            got[name].append(f.result(timeout=600))
    finally:
        for e in engines.values():
            e.close()
    for name in names:
        ref = syncs[name].submit(streams[name])
        require_served(syncs[name], ref)
        placement = engines[name].placement()
        homes = [devs[0] for devs in placement.values()]
        if len(set(homes)) != min(len(homes), len(engines[name].devices)):
            raise AssertionError(f"{name}: buckets share devices: {placement}")
        errs, same = [], 0
        for r, s in zip(got[name], ref):
            if r.status != "ok" or r.tier != "pallas+searched":
                raise AssertionError(f"{name} rid {r.rid}: {r.status} {r.tier} {r.error}")
            errs.append(check_close(f"{name} rid {r.rid}", r.output, s.output))
            same += int(np.array_equal(r.output, s.output))
        print(f"async {name} (f_in {TABLE4[name].n_features}): "
              f"{len(ref)} requests ok on pallas+searched over "
              f"{len({r.device for r in got[name]})} devices; "
              f"{len(placement)} buckets -> {placement}; max|async-sync| "
              f"{max(errs):.3e}, bit-identical {same}/{len(ref)}")


def phase_pp(f_in: int) -> None:
    """The pp_shard plan's phase mesh on two chips against the seq path."""
    from repro.core import ModelSchedule
    from repro.gnn import EllAdjacency
    from repro.gnn.model import forward_layers
    from repro.graphs.datasets import load_dataset
    from repro.graphs.partition import pp_shard_forward
    from repro.runtime import InferenceEngine

    dims = [(f_in, HIDDEN), (HIDDEN, CITESEER_CLASSES)]
    g, _ = load_dataset("citeseer", seed=SEED)
    x = np.random.default_rng(SEED).normal(size=(g.n_nodes, f_in)).astype(np.float32)
    params = InferenceEngine(dims).init(jax.random.PRNGKey(SEED))
    pp = pp_shard_forward(g, x, params, n_devices=2)
    specs = ModelSchedule.from_policies("seq", "AC", dims).lower(use_pallas=False)
    seq = np.asarray(forward_layers("gcn", params, EllAdjacency.from_csr(g),
                                    jnp.asarray(x), specs))
    ref = gcn_reference(g, x, params)
    e_pp = check_close("pp vs reference", pp, ref)
    e_seq = check_close("seq vs reference", seq, ref)
    e_pp_seq = check_close("pp vs seq", pp, seq)
    print(f"pp on 2 chips, citeseer {f_in}->{HIDDEN}->{CITESEER_CLASSES}: "
          f"max|pp-seq| {e_pp_seq:.3e}, max|pp-ref| {e_pp:.3e}, "
          f"max|seq-ref| {e_seq:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the paths across chips")
    args = ap.parse_args(argv)
    devices = require_tpu(args.chips)
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.graphs import TABLE4
    from repro.runtime import enable_persistent_compilation_cache

    dev = devices[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    cache_dir = enable_persistent_compilation_cache()
    events = count_cache_events()
    print(f"compile cache: {cache_dir}, {describe_dir(cache_dir)} at start")
    f_in = TABLE4["citeseer"].n_features
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            phase_async()
            phase_pp(f_in)
        else:
            phase_searched(f_in, sizes=(180, 200, 220, 240, 200, 230, 700, 900))
            phase_each_kernel(f_in)
            phase_train(f_in)
    finally:
        print(f"total {time.perf_counter() - t0:.1f} s")
        print(f"compile cache: {events['requests']} lookups, {events['hits']} "
              f"hits, {events['writes']} written; {describe_dir(cache_dir)} "
              f"at end")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
