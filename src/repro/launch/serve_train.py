"""Serve-while-train driver: training rounds interleaved with inference.

Every node fields a stream of decode requests while it trains.  Arrivals
(Poisson or Markov-modulated bursts, ``repro.serve.events``) pace the
gossip rounds — a backlogged node defers its exchange like a paper
straggler but keeps taking local steps — and between training dispatches
each node serves real batched greedy decode traffic against its *current
local* parameters (``repro.serve.serving``), with per-node latency /
throughput / staleness-of-served-model logged.

Elastic membership: ``--join STEP:N[:DEGREE]`` grows the node set
mid-run (``repro.serve.membership``) — genuinely new nodes attach to
uniform existing nodes, the Metropolis–Hastings weights are re-derived
over the grown graph (doubly stochastic ⇒ mean-preserving, checked at
every join), and each joiner catches up by cloning a trained neighbor
from the latest checkpoint (``--ckpt-dir``) or, absent one, the live
state.  Crash faults are refused when membership changes are scheduled —
their ``rejoin`` path assumes fixed m (see
``membership.check_membership_faults``).

Chaos timeline: ``--chaos "leave@20:2,partition@40:bridge,heal@80,
join@90:1"`` composes graceful departures (mass handoff to neighbors,
mean-preserving and conformance-asserted), scheduled network partitions
(persistent cross-component cuts realizing a block-doubly-stochastic
matrix per component, healed with drift reconciliation), and joins in
one run, with in-run invariant monitors (row/col stochasticity defect,
per-component mean preservation) at every event boundary.  An empty
timeline is bitwise identical to the plain serve_train path.  Serving
failover: ``--serve-policy consensus`` answers every request from the
node's *component's* PME-averaged model instead of its local copy.

    PYTHONPATH=src python -m repro.launch.serve_train --arch stablelm-1.6b \
        --steps 60 --nodes 8 --join 30:4 --arrival bursty \
        --prompt-len 8 --gen 4 --serve-batch 2
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import restore_checkpoint, save_checkpoint
from repro.checkpoint.store import latest_step
from repro.configs import get_config
from repro.core import engine
from repro.core.algorithms import get_algorithm, list_algorithms
from repro.core.faults import FaultModel
from repro.core import scenarios as scen_mod
from repro.core.scenarios import get_scenario, list_scenarios
from repro.core.topology import build_topology
from repro.data.synthetic import SyntheticTokens
from repro.launch.train import _hps_from_args, batch_stream_rng
from repro.models.model import init_params, train_loss
from repro.serve import events as ev_mod
from repro.serve import membership as mb_mod
from repro.serve.serving import ServeLoop


def _pacing_from_args(args) -> ev_mod.ServePacing:
    proc = ev_mod.get_arrival(args.arrival)
    overrides = {}
    if args.rate is not None:
        overrides["rate"] = args.rate
    if args.burst_rate is not None:
        overrides["burst_rate"] = args.burst_rate
    if overrides:
        proc = dataclasses.replace(proc, name=f"{proc.name}+custom",
                                   **overrides)
    proc = dataclasses.replace(proc, seed=args.seed)
    return ev_mod.ServePacing(
        process=proc, capacity=args.serve_capacity,
        defer_threshold=args.defer_threshold,
    )


def _make_batch_fn(args, cfg, m):
    """Per-node LM batch stream for the current node count.

    ``SyntheticTokens.make`` draws node corpora sequentially, so the
    first m_old shards are bitwise stable when m grows at a join — the
    incumbent nodes keep their data streams.
    """
    corpus = SyntheticTokens.make(m, 65536, cfg.vocab, seed=args.seed)
    node_ids = np.arange(m)[:, None, None]
    offsets = np.arange(args.seq)

    def make_batch(step: int):
        rng = batch_stream_rng(args.seed, step)
        starts = rng.integers(
            0, corpus.tokens.shape[1] - args.seq - 1, (m, args.batch)
        )
        toks = corpus.tokens[node_ids, starts[..., None] + offsets]
        batch = {"tokens": jnp.asarray(toks, jnp.int32)}
        if cfg.arch_type == "vlm":
            batch["patch_embeds"] = jnp.zeros(
                (m, args.batch, cfg.n_patches, cfg.vision_dim),
                jnp.dtype(cfg.dtype),
            )
        return batch

    return make_batch


def _bind_for(args, cfg, topo, pacing, faults, partitions=()):
    """(Re)bind the algorithm over the current topology — called at
    start and after every membership change (recompile is the price of a
    new node count; the compilation cache amortizes repeats).  Chaos
    partition windows fold into the scenario here, so the in-scan
    realization cuts cross-component edges while a window is open."""

    def grad_fn(p, b, k):
        del k
        return jax.value_and_grad(lambda pp: train_loss(pp, cfg, b))(p)

    alg = get_algorithm(args.algo)
    hps = _hps_from_args(args.algo, args)
    scen = get_scenario(args.scenario)
    scen = dataclasses.replace(scen, seed=args.seed)
    if partitions:
        scen = dataclasses.replace(scen, partitions=tuple(partitions))
    bound = alg.bind(
        grad_fn, topo, hps, mixing=args.mixing, seed=args.seed,
        scenario=None if scen.is_static else scen,
        faults=faults, pacing=pacing,
    )
    runner = engine.make_scan_runner(
        bound.step, chunk_size=args.chunk,
        step_takes_index=bound.dynamic, carries_aux=bound.carries_aux,
    )
    return bound, runner


def _join_conformance(topo_new: "object", m_old: int, kind="join") -> dict:
    """The membership conformance suite, run at every join/leave: the
    re-derived mixing matrix must stay doubly stochastic and
    mean-preserving over the changed node set."""
    w = topo_new.mixing
    rows_ok = bool(np.allclose(w.sum(axis=1), 1.0, atol=1e-9))
    cols_ok = bool(np.allclose(w.sum(axis=0), 1.0, atol=1e-9))
    x = np.random.default_rng(0).standard_normal((topo_new.m, 7))
    mean_ok = bool(np.allclose((w @ x).mean(axis=0), x.mean(axis=0),
                               atol=1e-9))
    ok = rows_ok and cols_ok and mean_ok
    if not ok:
        raise AssertionError(
            f"{kind} conformance FAILED at m={m_old}->{topo_new.m}: "
            f"rows={rows_ok} cols={cols_ok} mean={mean_ok}"
        )
    return {"rows": rows_ok, "cols": cols_ok, "mean": mean_ok}


def _params_mean(bound, state) -> np.ndarray:
    """Host copy of the global parameter mean (concatenated leaves) —
    the quantity graceful departures must preserve."""
    return np.concatenate([
        np.asarray(jnp.mean(leaf.astype(jnp.float32), axis=0)).ravel()
        for leaf in jax.tree_util.tree_leaves(bound.spec.params_of(state))
    ])


def _leave_conformance(pre_mean: np.ndarray, bound, state, m_old: int,
                       m_new: int) -> None:
    """Departure invariant: the survivors' parameter mean equals the
    pre-departure global mean to float32 tolerance (the β-weighted
    deviation handoff is mean-preserving by construction)."""
    post_mean = _params_mean(bound, state)
    scale = max(float(np.max(np.abs(pre_mean))), 1.0)
    if not np.allclose(post_mean, pre_mean, atol=1e-5 * scale, rtol=1e-5):
        worst = float(np.max(np.abs(post_mean - pre_mean)))
        raise AssertionError(
            f"leave conformance FAILED at m={m_old}->{m_new}: survivor "
            f"mean drifted by {worst:.3e} (float32 tolerance exceeded)"
        )


def _active_comp(bound, k):
    """Host copy of the step's component-id vector (None when the bind
    schedules no partitions — a single global component)."""
    arrays = getattr(bound, "scen_arrays", None)
    if arrays is None or arrays.part_comp is None:
        return None
    return np.asarray(scen_mod.active_components(arrays, jnp.int32(k)))


def _chaos_monitor(bound, k: int, tag: str) -> None:
    """In-run invariant monitor for chaos runs: realizes step k's matrix
    host-side and asserts the paper's Assumption-1 invariants — row/col
    stochasticity defect at float32 tolerance, zero cross-component mass
    while a partition window is open, and per-component (hence global)
    mean preservation."""
    if not bound.dynamic or getattr(bound, "temporal", False):
        return
    arrays = bound.scen_arrays
    r = scen_mod.realize(bound.scenario, arrays, jnp.int32(k))
    w = np.asarray(scen_mod.realization_matrix(arrays, r), np.float64)
    row_defect = float(np.max(np.abs(w.sum(axis=1) - 1.0)))
    col_defect = float(np.max(np.abs(w.sum(axis=0) - 1.0)))
    assert row_defect < 1e-4 and col_defect < 1e-4, (
        f"{tag}: stochasticity defect rows={row_defect:.2e} "
        f"cols={col_defect:.2e} at k={k}"
    )
    comp = _active_comp(bound, k)
    x = np.random.default_rng(1).standard_normal((w.shape[0], 5))
    if comp is not None and comp.max() > 0:
        cross = float(w[comp[:, None] != comp[None, :]].sum())
        assert cross == 0.0, (
            f"{tag}: {cross:.2e} cross-component mass inside an open "
            f"partition window at k={k}"
        )
        for c in np.unique(comp):
            sel = comp == c
            pre = x[sel].mean(axis=0)
            post = (w @ x)[sel].mean(axis=0)
            assert np.allclose(post, pre, atol=1e-5), (
                f"{tag}: component {c} mean not preserved at k={k}"
            )
    else:
        assert np.allclose((w @ x).mean(axis=0), x.mean(axis=0),
                           atol=1e-5), f"{tag}: global mean not preserved"
    print(
        f"[serve-train] monitor@{k} {tag}: stochasticity defect "
        f"{max(row_defect, col_defect):.1e}, mean-preserving (green)",
        flush=True,
    )


def _comp_drift(bound, state, comp) -> float:
    """Max ℓ2 gap between any component's parameter mean and the global
    mean — the drift a heal event hands back to gossip to reconcile."""
    x = np.concatenate([
        np.asarray(leaf).reshape(leaf.shape[0], -1).astype(np.float32)
        for leaf in jax.tree_util.tree_leaves(bound.spec.params_of(state))
    ], axis=1)
    gmean = x.mean(axis=0)
    return max(
        float(np.linalg.norm(x[comp == c].mean(axis=0) - gmean))
        for c in np.unique(comp)
    )


def _serve_report(tag, stats, es=None):
    """One per-node serving log line: decode throughput from the serve
    loop, queueing latency / staleness-of-served-model from the event
    clock (Little's law: wait_i / served_i rounds)."""
    for i, s in sorted(stats.items()):
        extra = ""
        if es is not None:
            served = max(int(np.asarray(es.served)[i]), 1)
            lat = float(np.asarray(es.wait)[i]) / served
            extra = (
                f" queue={int(np.asarray(es.queue)[i])}"
                f" latency={lat:.2f} rounds (model-staleness)"
            )
        print(
            f"{tag} node={i} prefill={s['prefill_ms']:.0f}ms "
            f"decode={s['decode_ms']:.0f}ms "
            f"tokens/s={s['tokens_per_s']:.1f}{extra}",
            flush=True,
        )


def make_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--variant", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--algo", default="pame", choices=list(list_algorithms()))
    ap.add_argument("--mixing", default="sparse", choices=["sparse", "dense"])
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=4, help="per-node batch")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--nodes", type=int, default=8)
    ap.add_argument("--topology", default="erdos_renyi")
    ap.add_argument("--scenario", default="static",
                    choices=list(list_scenarios()))
    ap.add_argument("--chunk", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    # training hps (shared with launch.train's _hps_from_args)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--rho", type=float, default=0.01)
    ap.add_argument("--nu", type=float, default=0.5)
    ap.add_argument("--p", type=float, default=0.2)
    ap.add_argument("--gamma", type=float, default=1.001)
    ap.add_argument("--sigma0", type=float, default=20.0)
    ap.add_argument("--kappa-lo", type=int, default=3)
    ap.add_argument("--kappa-hi", type=int, default=7)
    # serving: arrivals pace the rounds, decode traffic is served between
    # training dispatches
    ap.add_argument("--arrival", default="bursty",
                    choices=list(ev_mod.list_arrivals()),
                    help="request arrival preset (repro.serve.events)")
    ap.add_argument("--rate", type=float, default=None,
                    help="override: quiet-state arrivals/node/round")
    ap.add_argument("--burst-rate", type=float, default=None,
                    help="override: burst-state arrivals/node/round")
    ap.add_argument("--serve-capacity", type=int, default=4,
                    help="requests a node can serve per round")
    ap.add_argument("--defer-threshold", type=int, default=8,
                    help="backlog beyond which a node defers its gossip")
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--gen", type=int, default=4,
                    help="tokens generated per served request batch")
    ap.add_argument("--serve-batch", type=int, default=2,
                    help="requests batched into one decode call")
    ap.add_argument("--serve-every", type=int, default=None,
                    help="serve a decode round every N training steps "
                         "(chunk-aligned; default=chunk)")
    ap.add_argument("--serve-nodes", type=int, default=2,
                    help="nodes served per decode round (round-robin)")
    # elastic membership
    ap.add_argument("--join", default=None, metavar="STEP:N[:DEG],...",
                    help="membership joins: N new nodes at STEP, each "
                         "attached to DEG uniform existing nodes "
                         "(default --join-degree); catch-up clones a "
                         "trained neighbor from --ckpt-dir or live state")
    ap.add_argument("--join-degree", type=int, default=2)
    ap.add_argument("--chaos", default=None, metavar="KIND@STEP[:ARG],...",
                    help="chaos timeline composed with --join: leave@S:N "
                         "(N highest-id nodes depart gracefully), "
                         "partition@S:P|bridge (split into P components), "
                         "heal@S, join@S:N[:DEG].  Empty timeline keeps "
                         "the plain serve_train path bitwise identical")
    ap.add_argument("--serve-policy", default="local",
                    choices=["local", "consensus"],
                    help="what each node serves FROM: its own local model "
                         "(freshest) or its connected component's "
                         "PME-averaged model (coherent failover during "
                         "splits and departures)")
    # faults (to compose — and to demonstrate the crash+join refusal)
    ap.add_argument("--loss-rate", type=float, default=None,
                    help="P[a directed message is dropped] per step")
    ap.add_argument("--crash", default=None, metavar="RATE[,REJOIN]",
                    help="fixed-m transient crashes; refused when --join "
                         "is scheduled (membership.check_join_faults)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    return ap


def _faults_from_args(args):
    crash = None
    if args.crash is not None:
        parts = [float(x) for x in args.crash.split(",")]
        crash = (parts[0], parts[1] if len(parts) > 1 else 0.5)
    if args.loss_rate is None and crash is None:
        return None
    return FaultModel(
        name="cli",
        loss=args.loss_rate or 0.0,
        crash=crash[0] if crash else 0.0,
        rejoin=crash[1] if crash else 0.5,
        seed=args.seed,
    )


def main(argv=None):
    args = make_parser().parse_args(argv)
    cache_dir = engine.setup_compilation_cache()
    print(f"[serve-train] compilation cache at {cache_dir}", flush=True)

    timeline = mb_mod.parse_chaos_spec(args.chaos, args.join_degree)
    events = deque(sorted(
        timeline + tuple(
            mb_mod.ChaosEvent(step=e.step, kind="join", n=e.n_new,
                              degree=e.degree)
            for e in mb_mod.parse_join_spec(args.join, args.join_degree)
        ),
        key=lambda e: e.step,
    ))
    faults = _faults_from_args(args)
    if events:
        mb_mod.check_membership_faults(faults, tuple(events), m0=args.nodes)
    windows = mb_mod.chaos_partitions(tuple(events), args.steps,
                                      seed=args.seed)
    pacing = _pacing_from_args(args)

    cfg = get_config(args.arch, args.variant)
    m = args.nodes
    topo = build_topology(args.topology, m, p=0.5, seed=args.seed)
    bound, runner = _bind_for(args, cfg, topo, pacing, faults, windows)
    make_batch = _make_batch_fn(args, cfg, m)

    params0 = init_params(jax.random.PRNGKey(args.seed), cfg)
    stacked = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (m,) + x.shape), params0
    )
    batch0 = make_batch(0) if bound.spec.needs_batch0 else None
    state = bound.init(jax.random.PRNGKey(args.seed + 1), stacked, batch0)
    aux = bound.aux_init(state) if bound.carries_aux else None

    serve = ServeLoop(
        cfg, prompt_len=args.prompt_len, gen=args.gen,
        batch=args.serve_batch, seed=args.seed,
    )
    n_params = sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params0)
    )
    ev_summary = [
        f"{e.kind}@{e.step}" + (f":{e.n}" if e.n else "") for e in events
    ]
    print(
        f"[serve-train] algo={args.algo} nodes={m} "
        f"arrival={pacing.process.name} "
        f"(rate={pacing.process.rate}/{pacing.process.burst_rate} "
        f"cap={pacing.capacity} defer>{pacing.defer_threshold}) "
        f"events={ev_summary or 'none'} "
        f"serve-policy={args.serve_policy} "
        f"params={n_params / 1e6:.2f}M",
        flush=True,
    )
    if args.ckpt_dir:
        os.makedirs(args.ckpt_dir, exist_ok=True)

    serve_every = max(args.serve_every or args.chunk, 1)
    t0 = time.time()
    k = 0
    serve_cursor = 0  # round-robin over nodes
    next_serve = serve_every
    next_ckpt = args.ckpt_every
    deferred_total = 0.0
    while k < args.steps:
        boundary = args.steps
        if events:
            boundary = min(boundary, events[0].step)
        if k >= boundary:  # event scheduled at or before the current step
            boundary = min(args.steps, k + args.chunk)
        length = min(args.chunk, boundary - k)
        if length > 0:
            state, metrics, info = runner(
                state, make_batch, length, copy_state=False, k_start=k,
                aux=aux,
            )
            aux = info.get("aux") if bound.carries_aux else None
            k += info["steps_dispatched"]
            loss = float(np.mean(np.asarray(metrics["loss_mean"])))
            extra = ""
            if "deferred_nodes" in metrics:
                d = float(np.sum(np.asarray(metrics["deferred_nodes"])))
                deferred_total += d
                extra += (
                    f" deferred={d:.0f}/{length * m} node-rounds"
                    f" queue={float(np.asarray(metrics['queue_depth'])[-1]):.1f}"
                )
            if "comp_mean_gap" in metrics:
                gap = float(np.asarray(metrics["comp_mean_gap"])[-1])
                extra += f" comp-gap={gap:.2e}"
            print(
                f"[serve-train] step={k} m={m} loss={loss:.4f}{extra}"
                f" ({(time.time() - t0) / max(k, 1):.2f}s/step)",
                flush=True,
            )

        if k >= next_serve or k >= args.steps:
            ids = [(serve_cursor + i) % m
                   for i in range(min(args.serve_nodes, m))]
            serve_cursor = (serve_cursor + args.serve_nodes) % m
            comp = None
            if args.serve_policy == "consensus":
                comp = _active_comp(bound, max(k - 1, 0))
            stats = serve.serve_round(
                bound.spec.params_of(state), ids,
                policy=args.serve_policy, comp=comp,
            )
            es = aux.events if (aux is not None and bound.paced) else None
            _serve_report(f"[serve-train] serve@{k}", stats, es)
            next_serve += serve_every

        if args.ckpt_dir and k >= next_ckpt:
            payload = {"state": state}
            if aux is not None:
                payload["aux"] = aux
            save_checkpoint(args.ckpt_dir, k, payload)
            next_ckpt = (k // args.ckpt_every + 1) * args.ckpt_every

        while events and k >= events[0].step:
            ev = events.popleft()
            # future partition windows re-resolve against the current
            # topology at every rebind (check_membership_faults already
            # forbade membership changes inside an open window)
            future = tuple(w for w in windows if w.start >= k)

            if ev.kind == "partition":
                print(
                    f"[serve-train] partition@{k}: graph split into "
                    f"{ev.n} components (cross-component edges cut "
                    "until heal)",
                    flush=True,
                )
                _chaos_monitor(bound, k, f"partition@{ev.step}")
                continue

            if ev.kind == "heal":
                comp = _active_comp(bound, max(ev.step - 1, 0))
                drift = (
                    _comp_drift(bound, state, comp)
                    if comp is not None and comp.max() > 0 else 0.0
                )
                print(
                    f"[serve-train] heal@{k}: partition re-merged; "
                    f"component mean drift {drift:.3e} handed back to "
                    "gossip to reconcile",
                    flush=True,
                )
                _chaos_monitor(bound, k, f"heal@{ev.step}")
                continue

            if ev.kind == "leave":
                if ev.n == 0:
                    continue
                m_old = m
                # LIFO departure: the highest-id nodes retire, so state
                # rows stay contiguous and survivors keep their shards
                leavers = tuple(range(m - ev.n, m))
                pre_mean = _params_mean(bound, state)
                state = mb_mod.retire_state(state, topo, leavers)
                topo = mb_mod.shrunk_topology(topo, leavers)
                m = topo.m
                conf = _join_conformance(topo, m_old, kind="leave")
                old_events = (
                    aux.events if (aux is not None and bound.paced)
                    else None
                )
                bound, runner = _bind_for(args, cfg, topo, pacing, faults,
                                          future)
                make_batch = _make_batch_fn(args, cfg, m)
                if bound.carries_aux:
                    aux = bound.aux_init(state)
                    if bound.paced and old_events is not None:
                        # survivors keep their cumulative QPS/latency
                        aux = aux._replace(events=ev_mod.shrink_events(
                            old_events, list(range(m))))
                else:
                    aux = None
                _leave_conformance(pre_mean, bound, state, m_old, m)
                print(
                    f"[serve-train] leave@{k}: m={m_old}->{m} "
                    f"retired={list(leavers)} deviation mass handed to "
                    f"neighbors (mean-preserving) conformance: "
                    f"doubly-stochastic={conf['rows'] and conf['cols']} "
                    f"mean-preserving={conf['mean']} (green)",
                    flush=True,
                )
                continue

            # ev.kind == "join"
            if ev.n == 0:
                continue
            m_old = m
            topo = mb_mod.grown_topology(
                topo, ev.n, degree=ev.degree, seed=args.seed
            )
            m = topo.m
            donors = mb_mod.default_donors(topo, m_old)
            conf = _join_conformance(topo, m_old)
            # checkpoint catch-up: clone the donors' rows from the latest
            # checkpoint when one exists, else from the live state —
            # bitwise identical for a donor whose state has not moved
            # since the save (pinned by tests/test_membership.py)
            source = None
            src_tag = "live"
            if args.ckpt_dir:
                last = latest_step(args.ckpt_dir)
                if last is not None:
                    tmpl = {"state": state}
                    if aux is not None:
                        tmpl["aux"] = aux
                    try:
                        source = restore_checkpoint(
                            args.ckpt_dir, tmpl, last)["state"]
                        src_tag = f"ckpt@{last}"
                    except Exception:
                        source = None  # stale/mismatched ckpt: live donors
            state = mb_mod.expand_state(state, m_old, donors,
                                        source_state=source)
            old_events = (
                aux.events if (aux is not None and bound.paced) else None
            )
            bound, runner = _bind_for(args, cfg, topo, pacing, faults,
                                      future)
            make_batch = _make_batch_fn(args, cfg, m)
            if bound.carries_aux:
                aux = bound.aux_init(state)
                if bound.paced and old_events is not None:
                    # carry cumulative QPS/latency accounting through
                    # the join; fresh rows for the new nodes
                    aux = aux._replace(
                        events=ev_mod.expand_events(old_events, ev.n)
                    )
            else:
                aux = None
            print(
                f"[serve-train] join@{k}: m={m_old}->{m} "
                f"donors={donors.tolist()} catch-up={src_tag} "
                f"conformance: doubly-stochastic="
                f"{conf['rows'] and conf['cols']} "
                f"mean-preserving={conf['mean']} (green)",
                flush=True,
            )

    # run-level serving summary
    if aux is not None and bound.paced:
        es = aux.events
        arrived = np.asarray(es.arrived)
        served = np.asarray(es.served)
        wait = np.asarray(es.wait)
        lat = wait / np.maximum(served, 1)
        elapsed = max(time.time() - t0, 1e-9)
        qps = float(served.sum()) / elapsed
        print(
            f"[serve-train] served {int(served.sum())}/{int(arrived.sum())} "
            f"requests ({qps:.1f} req/s wall) "
            f"mean latency={float(lat.mean()):.2f} rounds "
            f"deferred={deferred_total:.0f} node-rounds",
            flush=True,
        )
        worst = int(np.argmax(lat))
        print(
            f"[serve-train] per-node latency (rounds): "
            + " ".join(f"{i}:{v:.1f}" for i, v in enumerate(lat))
            + f" (worst node {worst})",
            flush=True,
        )
    print("[serve-train] done")
    return state


if __name__ == "__main__":
    main()
