"""End-to-end DFL training driver.

Trains any registered architecture with any registered DFL algorithm
across m simulated nodes:

    PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b \
        --variant smoke --steps 100 --batch 8 --seq 128 --nodes 8 \
        --algo pame            # or dpsgd / dfedsam / choco / beer / anq_nids

Every algorithm runs through the scan-fused execution engine
(`repro.core.engine`): `--chunk` steps per dispatch with donated state and
device-side metric buffers, gossip routed through the sparse
neighbor-exchange mixer by default (`--mixing dense` for the bit-compatible
escape hatch), and per-step wire-cost accounting (Eq. 8 via the registry's
`wire_bits`) logged alongside the loss.

Dynamic-network scenarios (`--scenario flaky_links|churn|stragglers|harsh`
or explicit `--churn/--straggler/--edge-drop` probabilities) realize a
fresh doubly-stochastic mixing matrix every step inside the scan: links
fail, nodes drop out (state frozen for the step), stragglers miss the
exchange window, and only realized edges are charged on the wire.

All m nodes live on one device: the node-stacked state holds m full
copies of the model, so at published widths `--variant full --layers N`
cuts the depth (never the widths) until m copies fit the device.
Substrate exercised: synthetic non-IID corpus -> vectorized batch gather
-> registry-bound step inside `lax.scan` chunks -> metrics log +
checkpointing.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import restore_checkpoint, save_checkpoint
from repro.configs import get_config
from repro.core import engine
from repro.core.algorithms import (
    AnqNidsHp,
    BeerHp,
    ChocoHp,
    DFedSAMHp,
    DPSGDHp,
    PaMEHp,
    get_algorithm,
    list_algorithms,
)
from repro.core.faults import FaultModel
from repro.core.scenarios import get_scenario, list_scenarios
from repro.core.temporal import TemporalScenario
from repro.core.topology import build_topology
from repro.data.synthetic import SyntheticTokens
from repro.models.model import init_params, train_loss, train_loss_counted


def _hps_from_args(name: str, args):
    if name == "pame":
        p_leaf = None
        if getattr(args, "p_leaf", None):
            p_leaf = tuple(float(x) for x in args.p_leaf.split(","))
        return PaMEHp(
            nu=args.nu, p=args.p, gamma=args.gamma, sigma0=args.sigma0,
            kappa_lo=args.kappa_lo, kappa_hi=args.kappa_hi,
            mask_mode="bernoulli",
            partition=getattr(args, "partition", "flat"), p_leaf=p_leaf,
        )
    return {
        "dpsgd": lambda: DPSGDHp(lr=args.lr),
        "dfedsam": lambda: DFedSAMHp(lr=args.lr, rho=args.rho),
        "choco": lambda: ChocoHp(lr=args.lr),
        "beer": lambda: BeerHp(lr=args.lr),
        "anq_nids": lambda: AnqNidsHp(lr=args.lr),
    }[name]()


def batch_stream_rng(seed: int, step: int) -> np.random.Generator:
    """The per-step batch-window RNG: independent across steps AND runs.

    Seeding from the (seed, step) pair keeps every step's draw independent
    while giving different --seed runs genuinely different data streams —
    seeding from the step alone made every run sample identical windows,
    so cross-run mean±std understated the data variance.
    """
    return np.random.default_rng((int(seed), 1000 + int(step)))


def _parse_rate_pair(spec):
    """Parse "down[,up]" Markov-rate flags (e.g. --burst 0.1,0.3)."""
    if spec is None:
        return None
    parts = [float(x) for x in spec.split(",")]
    if len(parts) == 1:
        parts.append(0.5)
    if len(parts) != 2:
        raise ValueError(f"expected RATE or RATE_DOWN,RATE_UP, got {spec!r}")
    return tuple(parts)


def _scenario_from_args(args):
    """Resolve the --scenario preset, with per-probability overrides.

    Any temporal flag (--burst/--session/--staleness/--resample) upgrades
    the run to a `TemporalScenario`: explicit Markov rates win, and the
    i.i.d. churn/edge-drop probabilities lower to their degenerate Markov
    equivalents (leave=c, rejoin=1−c reproduces i.i.d. churn bitwise —
    see repro.core.temporal).
    """
    burst = _parse_rate_pair(args.burst)
    session = _parse_rate_pair(args.session)
    scen = get_scenario(args.scenario)
    overrides = {
        field: value
        for field, value in (
            ("churn", args.churn),
            ("straggler", args.straggler),
            ("edge_drop", args.edge_drop),
        )
        if value is not None
    }
    if overrides:
        scen = dataclasses.replace(scen, name=f"{scen.name}+custom", **overrides)
    scen = dataclasses.replace(scen, seed=args.seed)
    if not (burst or session or args.staleness > 0 or args.resample > 0):
        return scen
    if burst is None:
        burst = (scen.edge_drop, 1.0 - scen.edge_drop) \
            if scen.edge_drop > 0 else (0.0, 0.5)
    if session is None:
        session = (scen.churn, 1.0 - scen.churn) \
            if scen.churn > 0 else (0.0, 0.5)
    return TemporalScenario(
        name=f"{scen.name}+temporal",
        burst_down=burst[0], burst_up=burst[1],
        leave=session[0], rejoin=session[1],
        straggler=scen.straggler, staleness=args.staleness,
        resample_every=args.resample, mobility_keep=args.mobility_keep,
        seed=args.seed,
    )


def _faults_from_args(args):
    """Resolve the message-level fault flags into a FaultModel (or None).

    --loss-rate draws i.i.d. per-direction message drops; --loss-burst
    runs a Gilbert–Elliott lossy-link chain per directed slot; --crash
    is a transient node-crash chain (state frozen while down — the local
    checkpoint the node rejoins from); --msg-delay delays delivery only
    (local compute never waits).  All compose with the base --scenario.
    """
    burst = _parse_rate_pair(args.loss_burst)
    crash = _parse_rate_pair(args.crash)
    delay_p, delay_d = 0.0, 0
    if args.msg_delay is not None:
        parts = args.msg_delay.split(",")
        delay_p = float(parts[0])
        delay_d = int(parts[1]) if len(parts) > 1 else 2
    if args.loss_rate is None and burst is None and crash is None \
            and args.msg_delay is None:
        return None
    return FaultModel(
        name="cli",
        loss=args.loss_rate or 0.0,
        burst_down=burst[0] if burst else 0.0,
        burst_up=burst[1] if burst else 0.5,
        crash=crash[0] if crash else 0.0,
        rejoin=crash[1] if crash else 0.5,
        delay=delay_p,
        max_delay=delay_d,
        repair=args.repair,
        seed=args.seed,
    )


def model_config(args):
    """The registered config, with `--layers` replacing its depth only."""
    cfg = get_config(args.arch, args.variant)
    if args.layers is not None:
        cfg = cfg.replace(n_layers=args.layers)
    return cfg


def build_everything(args):
    cfg = model_config(args)
    if args.seq and cfg.arch_type == "vlm":
        assert args.seq > cfg.n_patches, "seq must exceed n_patches for vlm"
    m = args.nodes
    topo = build_topology(args.topology, m, p=0.5, seed=args.seed)

    corpus = SyntheticTokens.make(m, 65536, cfg.vocab, seed=args.seed)
    node_ids = np.arange(m)[:, None, None]
    offsets = np.arange(args.seq)

    def make_batch(step: int):
        rng = batch_stream_rng(args.seed, step)
        starts = rng.integers(0, corpus.tokens.shape[1] - args.seq - 1, (m, args.batch))
        # one fancy-indexed gather for all m x batch windows — the nested
        # python-loop version dominated step time on smoke configs
        toks = corpus.tokens[node_ids, starts[..., None] + offsets]
        batch = {"tokens": jnp.asarray(toks, jnp.int32)}
        if cfg.arch_type == "vlm":
            batch["patch_embeds"] = jnp.zeros(
                (m, args.batch, cfg.n_patches, cfg.vision_dim), jnp.dtype(cfg.dtype)
            )
        return batch

    alg = get_algorithm(args.algo)
    hps = _hps_from_args(args.algo, args)
    # a MoE model's counters (expert rows) join the round metrics of an
    # algorithm that takes them
    counted = alg.counts and cfg.arch_type == "moe"

    def grad_fn(p, b, k):
        del k
        if counted:
            return jax.value_and_grad(
                lambda pp: train_loss_counted(pp, cfg, b), has_aux=True)(p)
        return jax.value_and_grad(lambda pp: train_loss(pp, cfg, b))(p)

    scen = _scenario_from_args(args)
    faults = _faults_from_args(args)
    params0 = init_params(jax.random.PRNGKey(args.seed), cfg)
    batch0 = make_batch(0) if alg.needs_batch0 else None
    if args.seeds > 1:
        # vmap-over-lanes batched run: one jitted scan trains all seed
        # replicas together (lane s starts from PRNGKey(seed + 1 + s),
        # the key the unbatched run for that seed would use)
        bound = alg.bind_batched(
            grad_fn, topo, [hps],
            seeds=[args.seed + 1 + i for i in range(args.seeds)],
            mixing=args.mixing, seed=args.seed, scenario=scen,
            faults=faults, grad_counts=counted,
        )
        state = bound.init(params0, m, batch0)
    else:
        bound = alg.bind(
            grad_fn, topo, hps,
            mixing=args.mixing, seed=args.seed, scenario=scen,
            faults=faults, grad_counts=counted,
        )
        stacked = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (m,) + x.shape), params0
        )
        state = bound.init(jax.random.PRNGKey(args.seed + 1), stacked, batch0)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params0))
    return cfg, bound, state, make_batch, n_params, params0


def make_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--variant", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--layers", type=int, default=None, metavar="N",
                    help="depth cut for --variant full: train N layers at "
                         "the published widths (default: the full depth)")
    ap.add_argument("--algo", default="pame", choices=list(list_algorithms()))
    ap.add_argument("--mixing", default="sparse", choices=["sparse", "dense"],
                    help="gossip contraction: padded neighbor gather vs dense")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8, help="per-node batch")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--nodes", type=int, default=8)
    ap.add_argument("--topology", default="erdos_renyi")
    ap.add_argument("--scenario", default="static", choices=list(list_scenarios()),
                    help="dynamic-network preset: per-step link churn, node "
                         "dropout, stragglers (see repro.core.scenarios)")
    ap.add_argument("--churn", type=float, default=None,
                    help="override: P[node fully offline per step]")
    ap.add_argument("--straggler", type=float, default=None,
                    help="override: P[node misses the exchange per step]")
    ap.add_argument("--edge-drop", type=float, default=None,
                    help="override: P[link fails per step]")
    ap.add_argument("--burst", default=None, metavar="DOWN[,UP]",
                    help="Gilbert-Elliott per-link burst rates: P[good->bad]"
                         "[,P[bad->good]] per step (temporal scenario)")
    ap.add_argument("--session", default=None, metavar="LEAVE[,REJOIN]",
                    help="geometric node sessions: P[up->down][,P[down->up]]"
                         " per step (temporal scenario)")
    ap.add_argument("--staleness", type=int, default=0,
                    help="bounded staleness D: stragglers keep participating"
                         " through their <=D-step-old params from the scan-"
                         "carried snapshot ring (0 = miss the round)")
    ap.add_argument("--resample", type=int, default=0,
                    help="mobility: redraw the active edge subset every N "
                         "steps (0 = off)")
    ap.add_argument("--mobility-keep", type=float, default=0.7,
                    help="P[base edge active within a mobility epoch]")
    ap.add_argument("--loss-rate", type=float, default=None,
                    help="message-level faults: P[a directed message is "
                         "dropped] per step (asymmetric per direction)")
    ap.add_argument("--loss-burst", default=None, metavar="DOWN[,UP]",
                    help="Gilbert-Elliott lossy-link chain per directed "
                         "slot: P[good->lossy][,P[lossy->good]] per step")
    ap.add_argument("--crash", default=None, metavar="RATE[,REJOIN]",
                    help="transient node crashes: P[up->crashed]"
                         "[,P[crashed->recovered]] per step; crashed state "
                         "freezes (local-checkpoint catch-up on rejoin)")
    ap.add_argument("--msg-delay", default=None, metavar="P[,D]",
                    help="delayed delivery: P[a node's outgoing messages "
                         "are late][,staleness bound D (default 2)]; "
                         "message-only — local compute never waits")
    ap.add_argument("--repair", dest="repair", action="store_true",
                    default=True,
                    help="surrogate algorithms resync desynced per-receiver "
                         "replicas via full-surrogate retransmission, "
                         "charged on the wire (default)")
    ap.add_argument("--no-repair", dest="repair", action="store_false",
                    help="disable replica repair: lost innovations desync "
                         "surrogates permanently")
    ap.add_argument("--seeds", type=int, default=1,
                    help="train N seed replicas as lanes of ONE batched "
                         "jitted scan (vmap-over-lanes engine); the log "
                         "reports mean loss ± std across lanes")
    ap.add_argument("--chunk", type=int, default=16,
                    help="steps per scan dispatch (engine chunk length)")
    ap.add_argument("--lr", type=float, default=0.05, help="baseline step size")
    ap.add_argument("--rho", type=float, default=0.01, help="DFedSAM ascent radius")
    ap.add_argument("--nu", type=float, default=0.5)
    ap.add_argument("--p", type=float, default=0.2)
    ap.add_argument("--partition", default="flat", choices=["flat", "tree"],
                    help="PaME message format over the model pytree: 'flat' "
                         "prices one concatenated vector; 'tree' gives each "
                         "leaf its own segment — per-leaf rates and per-leaf "
                         "Eq.-(8) wire accounting")
    ap.add_argument("--p-leaf", default=None, metavar="R1,R2,...",
                    help="per-leaf transmission rates (tree partition), one "
                         "per pytree leaf in tree_flatten order; default "
                         "broadcasts --p")
    ap.add_argument("--gamma", type=float, default=1.001)
    ap.add_argument("--sigma0", type=float, default=20.0)
    ap.add_argument("--kappa-lo", type=int, default=3)
    ap.add_argument("--kappa-hi", type=int, default=7)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=None,
                    help="log cadence in steps (chunk-aligned; default=chunk)")
    return ap


def parse_args(argv=None) -> argparse.Namespace:
    ap = make_parser()
    args = ap.parse_args(argv)
    if args.layers is not None:
        if args.variant != "full":
            ap.error("--layers cuts the depth of --variant full only")
        if args.layers < 1:
            ap.error("--layers must be at least 1")
    return args


def main(argv=None) -> dict:
    """Run the CLI; returns the per-step node-mean losses and the
    parameter count per node (``{"loss": [steps] array, "n_params": int}``;
    batched runs give ``[steps, lanes]`` losses)."""
    args = parse_args(argv)

    cache_dir = engine.setup_compilation_cache()
    print(f"[train] compilation cache at {cache_dir}", flush=True)

    cfg, bound, state, make_batch, n_params, params0 = build_everything(args)
    lanes = bound.lanes if args.seeds > 1 else None
    # per-leaf Eq.-(8) accounting when the algorithm partitions over the
    # model pytree (--partition tree); flat formats price sum(sizes)
    wire_per_step = bound.wire_bits_for(params0)
    del params0  # the state holds the m replicas; free this extra copy
    scen_tag = bound.scenario.name if bound.dynamic else "static"
    if bound.faulty:
        fm = bound.faults
        scen_tag += (
            f"+faults(loss={fm.loss}, burst={fm.burst_down}/{fm.burst_up}, "
            f"crash={fm.crash}/{fm.rejoin}, delay={fm.delay}<= {fm.max_delay}, "
            f"repair={fm.repair})"
        )
    part_tag = f"partition={args.partition} " if args.algo == "pame" else ""
    print(
        f"[train] algo={args.algo} mixing={args.mixing} {part_tag}"
        f"nodes={args.nodes} arch={cfg.name} layers={cfg.n_layers} "
        f"scenario={scen_tag} "
        + (f"seeds={args.seeds} (batched lanes) " if lanes else "")
        + f"params={n_params/1e6:.2f}M wire_bits/step={wire_per_step:.3e} "
        f"({wire_per_step/8e6:.2f} MB/step network-wide"
        f"{'; full graph — realized bits logged per step' if bound.dynamic else ''})",
        flush=True,
    )

    carries_aux = bound.temporal or getattr(bound, "faulty", False)
    aux = bound.aux_init(state) if carries_aux else None
    start = 0
    resumed_bits = None
    if args.ckpt_dir:
        os.makedirs(args.ckpt_dir, exist_ok=True)
        from repro.checkpoint.store import latest_step

        last = latest_step(args.ckpt_dir)
        if last is not None:
            # the auxiliary carry (fault/temporal Markov state + staleness
            # ring) is checkpointed alongside the state, so a resumed run
            # continues the exact chains — the crash-rejoin catch-up path
            # restores from the same store.  The payload also carries the
            # realized cumulative wire bits: re-deriving them as
            # wire_per_step * start would charge the static full-graph
            # rate for steps that actually ran under dynamic/fault
            # accounting.
            payload = {"state": state, "cum_bits": np.zeros((), np.float64)}
            if carries_aux:
                payload["aux"] = aux
            try:
                restored = restore_checkpoint(args.ckpt_dir, payload, last)
                resumed_bits = float(restored["cum_bits"])
            except ValueError:
                # legacy checkpoint (no cum_bits leaf): restore the old
                # payload shape and fall back to the static estimate
                if carries_aux:
                    restored = restore_checkpoint(
                        args.ckpt_dir, {"state": state, "aux": aux}, last
                    )
                else:
                    restored = {"state": restore_checkpoint(
                        args.ckpt_dir, state, last)}
            state = restored["state"]
            if carries_aux:
                aux = restored["aux"]
            start = last
            print(f"[train] resumed from step {last}")

    runner = engine.make_scan_runner(
        bound.step, chunk_size=args.chunk, step_takes_index=bound.dynamic,
        carries_aux=carries_aux, lanes=lanes,
    )
    # chunk programs the engine builds (a first chunk, a shorter last one)
    # are named in the next log line: their wall time holds the compile
    built = []

    def on_event(event, **kwargs):
        if event == engine.CHUNK_BUILD_EVENT:
            built.append(kwargs["length"])

    jax.monitoring.register_event_listener(on_event)
    try:
        losses = _train_loop(
            args, runner, state, aux, make_batch, start, built,
            wire_per_step=wire_per_step, resumed_bits=resumed_bits,
            lanes=lanes, carries_aux=carries_aux,
        )
    finally:
        jax.monitoring.unregister_event_listener(on_event)
    print("[train] done")
    return {"loss": np.concatenate(losses) if losses else np.zeros((0,)),
            "n_params": n_params}


def _train_loop(args, runner, state, aux, make_batch, start, built, *,
                wire_per_step, resumed_bits, lanes, carries_aux) -> list:
    """Chunks from step ``start`` to ``args.steps``: log lines, wire bits,
    checkpoints; returns the per-chunk node-mean losses.  ``built`` holds
    the lengths of the chunk programs built since the last log line."""
    log_every = max(args.log_every or args.chunk, 1)
    k = start
    cum_bits = resumed_bits if resumed_bits is not None else wire_per_step * start
    stale_hist = None
    losses = []
    next_ckpt = (start // args.ckpt_every + 1) * args.ckpt_every
    while k < args.steps:
        length = min(args.chunk, args.steps - k)
        k0 = k
        # copy_state=False: we rebind to the returned state, so the engine
        # can donate our buffers without the per-chunk protective deep copy.
        # k_start keeps batches and scenario realizations aligned with the
        # *global* step index across chunk dispatches.
        chunk_start = time.perf_counter()
        state, metrics, info = runner(
            state, make_batch, length, copy_state=False, k_start=k0, aux=aux
        )
        # the runner returns after reading the chunk's metrics back
        chunk_s = time.perf_counter() - chunk_start
        aux = info["aux"]
        k += info["steps_dispatched"]
        losses.append(np.asarray(metrics["loss_mean"]))
        if "wire_bits" in metrics:  # realized (surviving-edge) accounting
            # batched rows are [steps, L]: report the per-lane average so
            # the log stays comparable with a single-seed run
            cum_bits += float(np.sum(metrics["wire_bits"])) / (lanes or 1)
        else:
            cum_bits += wire_per_step * info["steps_dispatched"]
        if "stale_hist" in metrics:  # per-run staleness occupancy histogram
            rows = np.asarray(metrics["stale_hist"])
            row = rows.reshape(-1, rows.shape[-1]).sum(axis=0)
            stale_hist = row if stale_hist is None else stale_hist + row
        if (k // log_every) != (k0 // log_every) or k >= args.steps:
            with jax.profiler.TraceAnnotation("train.log"):
                lm = np.asarray(metrics["loss_mean"])
                loss = float(np.mean(lm))
                extra = ""
                if lanes:  # spread of the seed replicas at the last step
                    extra += f" loss_std={float(np.std(lm[-1])):.4f}"
                last = lambda key: float(np.mean(np.asarray(metrics[key])[-1]))
                if "consensus" in metrics:
                    extra += f" consensus={last('consensus'):.3e}"
                if "comm_nodes" in metrics:
                    extra += f" comm_nodes={last('comm_nodes'):.0f}"
                if "alive_nodes" in metrics:
                    extra += f" alive={last('alive_nodes'):.0f}"
                if "stale_nodes" in metrics:
                    extra += f" stale={last('stale_nodes'):.0f}"
                if "crashed_nodes" in metrics:
                    extra += f" crashed={last('crashed_nodes'):.0f}"
                if "dropped_msgs" in metrics:
                    extra += f" dropped={last('dropped_msgs'):.0f}"
                if "mean_drift" in metrics:
                    extra += f" drift={last('mean_drift'):.3f}"
                if "surrogate_desync" in metrics:
                    extra += f" desync={last('surrogate_desync'):.3e}"
                if "sigma_mean" in metrics:
                    extra += f" sigma={last('sigma_mean'):.2f}"
                print(
                    f"[train] step={k} loss={loss:.4f}{extra}"
                    f" wire_gbits={cum_bits/1e9:.4f}"
                    f" ({chunk_s / info['steps_dispatched']:.2f}s/step"
                    f"{', compiling' if k0 == start else ''})"
                    + "".join(f" (built chunk program: {n} rounds)" for n in built),
                    flush=True,
                )
                built.clear()
        if args.ckpt_dir and k >= next_ckpt:
            with jax.profiler.TraceAnnotation("train.checkpoint"):
                payload = {"state": state,
                           "cum_bits": np.asarray(cum_bits, np.float64)}
                if carries_aux:
                    payload["aux"] = aux
                save_checkpoint(args.ckpt_dir, k, payload)
                next_ckpt = (k // args.ckpt_every + 1) * args.ckpt_every
    if stale_hist is not None:
        total = max(float(stale_hist.sum()), 1.0)
        cells = " ".join(
            f"tau={t}:{int(c)}({c / total:.0%})"
            for t, c in enumerate(stale_hist)
        )
        print(f"[train] staleness histogram (participant-steps): {cells}")
    return losses


if __name__ == "__main__":
    main()
