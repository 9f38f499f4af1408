"""Benchmark harness — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only NAME] [--quick]

Output: `name,us_per_call,derived` CSV rows (us_per_call = jitted step
wall time on this CPU host; derived = the figure's headline metric).
Full curves land in benchmarks/artifacts/bench_results.json for
EXPERIMENTS.md.

Figure map:
  bench_transmission_rate  Fig 2a & 3   (s/n sweep, Example 1; seeds batched)
  bench_participation      Fig 2b & 4   (nu sweep, Example 1; one batched
                                         nu x seed grid per m)
  bench_comm_period        Fig 2c/d,5,6 (kappa homo/hetero, Example 1; one
                                         batched kappa x seed grid each)
  bench_connectivity       Fig 7        (degree x s/n heatmap)
  bench_vs_baselines       Figs 8-10    (Example 2, registry race: PaME vs
                                         D-PSGD/DFedSAM/CHOCO/BEER/ANQ-NIDS,
                                         mean ± std over batched seed lanes)
  bench_faults             —            (graceful degradation: accuracy &
                                         realized gbits vs message-loss rate,
                                         replicated surrogates + repair traffic)
  bench_mixing             —            (dense einsum vs sparse neighbor gossip)
  bench_sweep              —            (batched lane engine vs per-cell loop;
                                         slots vs segment-sum gossip core;
                                         cold-vs-warm persistent compile cache;
                                         emits BENCH_sweep.json)
  bench_gossip             —            (slots vs segsum vs fused Pallas kernel
                                         across m × degree × n with bytes-moved
                                         roofline terms; emits BENCH_gossip.json)
  bench_scenarios          —            (dynamic networks: churn x topology race
                                         with realized per-step wire bits)
  bench_chaos              —            (network split + heal: post-heal
                                         consensus recovery, PaME vs surrogate-
                                         memory baselines; emits BENCH_chaos.json)
  bench_heterogeneity      Figs 11-12   (label-skew CNN / Dirichlet ResNet-20)
  bench_comm_volume        Eq. (8)      (bit accounting, 64/16/8-bit wires)
  bench_kernels            —            (Pallas kernels, interpret-mode checks)
  bench_engine             —            (host-loop vs scan-driver us_per_call)
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import PaMEConfig, build_topology, run_pame
from repro.core.algorithms import lane_finals
from repro.core.pame import make_pame_runner
from repro.core.pme import message_bits

from benchmarks.common import (
    benchmark,
    chunk_for,
    csv_row,
    linreg_problem,
    logreg_problem,
    mean_std,
)

HERE = os.path.dirname(os.path.abspath(__file__))
ART = os.path.join(HERE, "artifacts")
os.makedirs(ART, exist_ok=True)

RESULTS: Dict[str, object] = {}


SWEEP_SEEDS = 5  # >= 5 seeds behind every mean ± std table entry


def _pame_grid(m, n, cfgs, steps, seeds=None, topo_kind="erdos_renyi",
               topo_kwargs=None, spn=128, tol_std=1e-3):
    """Run a C-config × S-seed PaME grid as ONE batched scan (one compile).

    Configs may differ in any field `bind_batched` can thread (nu, gamma,
    sigma0, kappa_* — not p, which fixes the payload shape).  The problem
    instance and topology are fixed; lanes vary the algorithm's PRNG
    stream.  Returns per-config rows with mean ± std over the seed lanes.
    """
    from repro.core import algorithms as ALG

    seeds = list(range(SWEEP_SEEDS)) if seeds is None else list(seeds)
    topo = build_topology(topo_kind, m, **(topo_kwargs or dict(p=0.4, seed=0)))
    batch, grad_fn, objective = linreg_problem(m, n, spn=spn, seed=0)
    chunk = chunk_for(steps)
    ba = ALG.get_algorithm("pame").bind_batched(
        grad_fn, topo, cfgs, seeds=seeds
    )
    runner = ba.make_runner(
        objective_fn=objective, tol_std=tol_std, chunk_size=chunk
    )
    # warm-up: ONE compile covers the whole grid
    runner(jnp.zeros(n), m, lambda k: batch, chunk)
    t0 = time.perf_counter()
    state, hist = runner(jnp.zeros(n), m, lambda k: batch, steps)
    wall = time.perf_counter() - t0
    finals = lane_finals(hist)
    lane_steps = wall / max(int(hist["steps_dispatched"]) * ba.lanes, 1)
    rows = []
    for c, cfg in enumerate(cfgs):
        mask = hist["lane_config"] == c
        fm, fs = mean_std(finals[mask])
        rm, _ = mean_std(hist["steps_run"][mask])
        rows.append({
            "final_mean": fm, "final_std": fs, "rounds_mean": rm,
            "seeds": len(seeds), "us_per_lane_step": lane_steps * 1e6,
            "mean_t": float(np.mean(np.maximum(1, np.floor(cfg.nu * topo.degrees)))),
        })
    return rows


# ---------------------------------------------------------------------------
def bench_transmission_rate(quick=False):
    """Fig 2a/3: final objective & convergence vs s/n for m in {16,32,64}.

    p fixes the message payload shape (trace-static), so each (m, p) cell
    compiles once and its SWEEP_SEEDS seed replicas run as lanes of that
    one program."""
    n = 300
    rates = [0.1, 0.2, 0.4, 0.6, 1.0]
    ms = [16, 32] if quick else [16, 32, 64]
    table = {}
    for m in ms:
        for p in rates:
            cfg = PaMEConfig(nu=0.2, p=p, gamma=1.01, sigma0=8.0)
            (r,) = _pame_grid(m, n, [cfg], steps=300)
            table[f"m{m}_p{p}"] = r
            csv_row(
                f"transmission_rate/m={m}/s_over_n={p}", r["us_per_lane_step"],
                f"final_obj={r['final_mean']:.4f}±{r['final_std']:.4f}"
                f";rounds={r['rounds_mean']:.0f};seeds={r['seeds']}",
            )
    # paper claim C4: gains are marginal once s/n exceeds ~0.2
    for m in ms:
        p01 = table[f"m{m}_p0.1"]["final_mean"]
        p02 = table[f"m{m}_p0.2"]["final_mean"]
        hi = table[f"m{m}_p1.0"]["final_mean"]
        csv_row(
            f"transmission_rate/claimC4/m={m}", 0.0,
            f"final_p0.1={p01:.4f};final_p0.2={p02:.4f};final_p1.0={hi:.4f};"
            f"ratio_p0.2={p02/max(hi,1e-9):.3f}",
        )
    RESULTS["transmission_rate"] = table


def bench_participation(quick=False):
    """Fig 2b/4: nu sweep — per m, the whole nu × seed grid is ONE batched
    scan (nu reaches the trace through the stacked TopologyArrays, so the
    4 configs share a single compiled program)."""
    n = 300
    nus = [0.1, 0.2, 0.4, 0.6]
    ms = [16, 32] if quick else [16, 32, 64]
    table = {}
    for m in ms:
        cfgs = [PaMEConfig(nu=nu, p=0.2, gamma=1.01, sigma0=8.0) for nu in nus]
        rows = _pame_grid(m, n, cfgs, steps=300)
        for nu, r in zip(nus, rows):
            table[f"m{m}_nu{nu}"] = r
            csv_row(
                f"participation/m={m}/nu={nu}", r["us_per_lane_step"],
                f"final_obj={r['final_mean']:.4f}±{r['final_std']:.4f}"
                f";rounds={r['rounds_mean']:.0f};seeds={r['seeds']}",
            )
    RESULTS["participation"] = table


def bench_comm_period(quick=False):
    """Fig 2c/d + 5/6: homogeneous vs heterogeneous kappa.  Each family's
    kappa × seed grid is ONE batched scan — the per-node periods live in
    the stacked TopologyArrays, not the traced program."""
    n, m = 300, 32
    table = {}
    homo_ks = [1, 2, 4, 8, 16]
    cfgs = [
        PaMEConfig(nu=0.2, p=0.2, gamma=1.01, sigma0=8.0, homogeneous_kappa=k0)
        for k0 in homo_ks
    ]
    for k0, r in zip(homo_ks, _pame_grid(m, n, cfgs, steps=400)):
        table[f"homo_k{k0}"] = r
        csv_row(
            f"comm_period/homogeneous/k0={k0}", r["us_per_lane_step"],
            f"final_obj={r['final_mean']:.4f}±{r['final_std']:.4f}"
            f";rounds={r['rounds_mean']:.0f};seeds={r['seeds']}",
        )
    hetero = [(1, 3), (3, 7), (5, 10), (8, 16)]
    cfgs = [
        PaMEConfig(nu=0.2, p=0.2, gamma=1.01, sigma0=8.0, kappa_lo=lo, kappa_hi=hi)
        for lo, hi in hetero
    ]
    for (lo, hi), r in zip(hetero, _pame_grid(m, n, cfgs, steps=400)):
        table[f"hetero_k{lo}_{hi}"] = r
        csv_row(
            f"comm_period/heterogeneous/k=[{lo},{hi}]", r["us_per_lane_step"],
            f"final_obj={r['final_mean']:.4f}±{r['final_std']:.4f}"
            f";rounds={r['rounds_mean']:.0f};seeds={r['seeds']}",
        )
    RESULTS["comm_period"] = table


def bench_connectivity(quick=False):
    """Fig 7 heatmap: degree x transmission rate -> (final obj, iters).

    Each (degree, rate) cell's SWEEP_SEEDS seed replicas run as lanes of
    ONE batched scan (`_pame_grid` -> `bind_batched`) — the seed axis left
    the per-cell Python loop, so every table entry is a mean ± std."""
    n, m = 300, 32
    degrees = [2, 6, 14] if quick else [2, 4, 8, 14, 20]
    rates = [0.1, 0.3, 0.6]
    table = {}
    for d in degrees:
        for p in rates:
            cfg = PaMEConfig(nu=0.4, p=p, gamma=1.01, sigma0=8.0)
            (r,) = _pame_grid(
                m, n, [cfg], steps=300, topo_kind="regular",
                topo_kwargs=dict(degree=d, seed=0),
            )
            table[f"deg{d}_p{p}"] = r
            csv_row(
                f"connectivity/degree={d}/s_over_n={p}",
                r["us_per_lane_step"],
                f"final_obj={r['final_mean']:.4f}±{r['final_std']:.4f}"
                f";rounds={r['rounds_mean']:.0f};seeds={r['seeds']}",
            )
    RESULTS["connectivity"] = table


def bench_vs_baselines(quick=False):
    """Figs 8-10: Example 2 (logistic regression) — objective/accuracy vs
    rounds and total transmitted volume, PaME vs all five baselines, as a
    data-driven loop over the unified algorithm registry.  Each algorithm's
    SWEEP_SEEDS seed replicas run as lanes of one batched scan (one compile
    per algorithm, mean ± std columns), emitted into EXPERIMENTS.md."""
    from repro.core import algorithms as ALG

    m, n = 32, 1000
    steps = 150 if quick else 300
    seeds = list(range(SWEEP_SEEDS))
    topo = build_topology("erdos_renyi", m, p=0.4, seed=0)
    batch, grad_fn, objective, accuracy = logreg_problem(m, n, spn=128, seed=0)
    chunk = chunk_for(steps)
    race_hps = {
        "pame": PaMEConfig(nu=0.2, p=0.2, gamma=1.002, sigma0=1.0,
                           kappa_lo=3, kappa_hi=7),
        "dpsgd": ALG.DPSGDHp(lr=0.1),
        "dfedsam": ALG.DFedSAMHp(lr=0.1, rho=0.01),
        "choco": ALG.ChocoHp(lr=0.05, gossip_gamma=0.3, comp_frac=0.3),
        "beer": ALG.BeerHp(lr=0.05, gossip_gamma=0.4, comp_frac=0.2),
        "anq_nids": ALG.AnqNidsHp(lr=0.1, qsgd_levels=16),
    }
    table = {}
    md_rows = []
    for name in ALG.list_algorithms():
        # algorithms registered beyond the built-in six race on their
        # default hyperparameters
        ba = ALG.get_algorithm(name).bind_batched(
            grad_fn, topo, [race_hps.get(name)], seeds=seeds, mixing="sparse"
        )
        runner = ba.make_runner(
            objective_fn=objective, tol_std=1e-3, chunk_size=chunk
        )
        # warm-up: one chunk compiles the scan executable for ALL lanes
        runner(jnp.zeros(n), m, lambda k: batch, chunk)
        t0 = time.perf_counter()
        state, hist = runner(jnp.zeros(n), m, lambda k: batch, steps)
        wall = time.perf_counter() - t0
        # per-lane accuracy of the node-mean parameters
        mean_w = np.asarray(
            jax.tree_util.tree_map(
                lambda x: x.mean(axis=1), ba.params_of(state)
            )
        )
        accs = [accuracy(jnp.asarray(mean_w[l])) for l in range(ba.lanes)]
        fm, fs = mean_std(lane_finals(hist))
        am, a_s = mean_std(accs)
        bm, bs = mean_std(hist["wire_bits_total"])
        rm, _ = mean_std(hist["steps_run"])
        table[name] = {
            "steps_run": rm,
            "final": fm, "final_std": fs,
            "accuracy": am, "accuracy_std": a_s,
            "us_per_call": wall / max(
                int(hist["steps_dispatched"]) * ba.lanes, 1) * 1e6,
            "bits": bm, "bits_std": bs, "seeds": len(seeds),
        }
        rr = table[name]
        csv_row(
            f"vs_baselines/{name}", rr["us_per_call"],
            f"acc={rr['accuracy']:.4f}±{rr['accuracy_std']:.4f}"
            f";final_obj={rr['final']:.4f}±{rr['final_std']:.4f}"
            f";rounds={rr['steps_run']:.0f};gbits={rr['bits']/1e9:.3f}"
            f";seeds={rr['seeds']}",
        )
        md_rows.append((
            name, f"{rr['final']:.4f} ± {rr['final_std']:.4f}",
            f"{rr['accuracy']:.4f} ± {rr['accuracy_std']:.4f}",
            f"{rr['steps_run']:.0f}", f"{rr['bits']/1e9:.3f}",
            f"{rr['us_per_call']:.0f}",
        ))
    # claim C7: PaME's transmitted-volume reduction vs every dense/compressed
    # competitor (CHOCO included now that it races too)
    for name, rr in table.items():
        if name == "pame":
            continue
        red = 1.0 - table["pame"]["bits"] / rr["bits"]
        csv_row(
            f"vs_baselines/claimC7_volume_reduction_vs_{name}", 0.0,
            f"reduction={red:.2%}",
        )
    _update_experiments_md(
        "vs-baselines",
        "## PaME vs baselines: mean ± std over batched seed lanes\n\n"
        f"Example 2 logistic regression (m={m}, n={n}), erdos_renyi(p=0.4), "
        f"{steps} steps, tol_std=1e-3.  Each algorithm's {len(seeds)} seed "
        "replicas run as lanes of ONE jitted scan "
        "(`Algorithm.bind_batched`); mean gbits count the full run's "
        "transmitted volume.\n\n"
        + _fmt_md_table(
            ("algo", "final objective", "accuracy", "rounds", "gbits",
             "us/lane-step"),
            md_rows,
        ),
    )
    RESULTS["vs_baselines"] = table


def bench_faults(quick=False):
    """Graceful-degradation race: final accuracy and realized transmitted
    volume vs message-loss rate, PaME vs all five baselines under the
    message-level fault layer (`repro.core.faults`): asymmetric
    per-direction drops + transient crashes.  Surrogate-memory baselines
    (CHOCO/BEER/ANQ-NIDS) run their per-receiver replica variants with
    wire-charged repair; PaME consumes the delivery masks natively and
    its realized matrices stay row-stochastic by construction.  Each
    (algorithm, loss-rate) cell runs SWEEP_SEEDS seed lanes as one
    batched scan; the degradation curve is emitted into EXPERIMENTS.md."""
    from repro.core import algorithms as ALG
    from repro.core.faults import FaultModel

    m, n = 16, 300
    steps = 80 if quick else 200
    loss_grid = [0.0, 0.1, 0.2] if quick else [0.0, 0.05, 0.1, 0.2, 0.3]
    seeds = list(range(SWEEP_SEEDS))
    topo = build_topology("erdos_renyi", m, p=0.4, seed=0)
    batch, grad_fn, objective, accuracy = logreg_problem(m, n, spn=64, seed=0)
    chunk = chunk_for(steps)
    race_hps = {
        "pame": PaMEConfig(nu=0.2, p=0.2, gamma=1.002, sigma0=1.0,
                           kappa_lo=3, kappa_hi=7),
        "dpsgd": ALG.DPSGDHp(lr=0.1),
        "dfedsam": ALG.DFedSAMHp(lr=0.1, rho=0.01),
        "choco": ALG.ChocoHp(lr=0.05, gossip_gamma=0.3, comp_frac=0.3),
        "beer": ALG.BeerHp(lr=0.05, gossip_gamma=0.4, comp_frac=0.2),
        "anq_nids": ALG.AnqNidsHp(lr=0.1, qsgd_levels=16),
    }
    table = {}
    md_rows = []
    for name in ALG.list_algorithms():
        for loss in loss_grid:
            # loss=0.0 is a static FaultModel: bind_batched falls back to
            # the plain fault-free program — the curve's anchor point
            fm_model = FaultModel(loss=loss, crash=0.01, rejoin=0.3, seed=0)
            ba = ALG.get_algorithm(name).bind_batched(
                grad_fn, topo, [race_hps.get(name)], seeds=seeds,
                mixing="sparse", faults=fm_model,
            )
            runner = ba.make_runner(
                objective_fn=objective, tol_std=0.0, chunk_size=chunk
            )
            t0 = time.perf_counter()
            state, hist = runner(jnp.zeros(n), m, lambda k: batch, steps)
            wall = time.perf_counter() - t0
            mean_w = np.asarray(
                jax.tree_util.tree_map(
                    lambda x: x.mean(axis=1), ba.params_of(state)
                )
            )
            accs = [accuracy(jnp.asarray(mean_w[l])) for l in range(ba.lanes)]
            om, os_ = mean_std(lane_finals(hist))
            am, a_s = mean_std(accs)
            bm, _ = mean_std(hist["wire_bits_total"])
            rep = 0.0
            if "repair_bits" in hist:
                per = np.asarray(hist["repair_bits"])
                steps_run = np.asarray(hist["steps_run"])
                rep = float(np.mean([
                    per[: steps_run[l], l].sum() for l in range(ba.lanes)
                ]))
            table[f"{name}@{loss}"] = {
                "loss_rate": loss, "final": om, "final_std": os_,
                "accuracy": am, "accuracy_std": a_s,
                "bits": bm, "repair_bits": rep, "seeds": len(seeds),
            }
            csv_row(
                f"faults/{name}/loss={loss}",
                wall / max(int(hist["steps_dispatched"]) * ba.lanes, 1) * 1e6,
                f"acc={am:.4f}±{a_s:.4f};final_obj={om:.4f}±{os_:.4f}"
                f";gbits={bm/1e9:.3f};repair_gbits={rep/1e9:.4f}",
            )
            md_rows.append((
                name, f"{loss:.2f}", f"{am:.4f} ± {a_s:.4f}",
                f"{om:.4f} ± {os_:.4f}", f"{bm/1e9:.3f}",
                f"{rep/1e9:.4f}",
            ))
    # headline: PaME's accuracy drop from 0% to the worst raced loss rate
    worst = max(loss_grid)
    for name in ALG.list_algorithms():
        drop = (table[f"{name}@0.0"]["accuracy"]
                - table[f"{name}@{worst}"]["accuracy"])
        csv_row(f"faults/degradation_{name}", 0.0,
                f"acc_drop@{worst:.0%}={drop:.4f}")
    _update_experiments_md(
        "faults",
        "## Graceful degradation under message-level faults\n\n"
        f"Example 2 logistic regression (m={m}, n={n}), erdos_renyi(p=0.4), "
        f"{steps} steps, crash=0.01/rejoin=0.3 throughout, asymmetric "
        "per-direction message loss at the listed rate.  "
        f"Mean ± std over {len(seeds)} batched seed lanes "
        "(`bind_batched(faults=...)`).  CHOCO/BEER/ANQ-NIDS run "
        "per-receiver surrogate replicas with wire-charged full-surrogate "
        "repair (the repair gbits column); PaME's count-normalized "
        "averaging needs no repair traffic.\n\n"
        + _fmt_md_table(
            ("algo", "loss rate", "accuracy", "final objective", "gbits",
             "repair gbits"),
            md_rows,
        ),
    )
    RESULTS["faults"] = table


def bench_mixing(quick=False):
    """Sparse neighbor-exchange gossip vs the dense [m, m] einsum: mixing
    cost scales with the edge set, not m².  Sweeps m x topology on a
    model-layer-sized pytree and reports us_per_call for both paths, plus
    the dense/sparse bit-identity check on a short D-PSGD run."""
    from repro.core import algorithms as ALG
    from repro.core.mixing import make_mixer

    rng = np.random.default_rng(0)
    ms = [32, 128] if quick else [32, 128, 512]
    table = {}
    for m in ms:
        tree = {
            "w": jnp.asarray(rng.standard_normal((m, 64, 64)), jnp.float32),
            "b": jnp.asarray(rng.standard_normal((m, 256)), jnp.float32),
        }
        for kind, kwargs in (
            ("ring", {}),
            ("regular", dict(degree=4, seed=0)),
            ("erdos_renyi", dict(p=max(8.0 / m, float(np.log(m) + 1) / m), seed=0)),
        ):
            topo = build_topology(kind, m, **kwargs)
            mx_mat = make_mixer(topo, "matrix")   # legacy dense einsum
            mx_sp = make_mixer(topo, "sparse")    # padded neighbor gather
            dense_fn = jax.jit(mx_mat.mix)
            sparse_fn = jax.jit(mx_sp.mix)
            us_dense = benchmark(dense_fn, tree, iters=10)["us_median"]
            us_sparse = benchmark(sparse_fn, tree, iters=10)["us_median"]
            err = max(
                float(jnp.max(jnp.abs(a - b_)))
                for a, b_ in zip(
                    jax.tree_util.tree_leaves(dense_fn(tree)),
                    jax.tree_util.tree_leaves(sparse_fn(tree)),
                )
            )
            table[f"m{m}_{kind}"] = {
                "us_dense": us_dense, "us_sparse": us_sparse,
                "max_degree": topo.max_degree, "max_err": err,
            }
            csv_row(
                f"mixing/m={m}/{kind}", us_sparse,
                f"dense_us={us_dense:.1f};speedup={us_dense/max(us_sparse,1e-9):.2f}x"
                f";max_degree={topo.max_degree};max_err={err:.2e}",
            )
    # mixing="dense" (full-connectivity padded) vs "sparse": same-seed
    # D-PSGD curves must be bit-identical.  On a complete graph the two
    # modes lower to the *same* XLA program over the same arrays, so the
    # identity is compiler-proof; on sparse graphs it additionally holds
    # whenever LLVM contracts mul+add uniformly (reported, not asserted —
    # eager mode is always bit-identical, see tests/test_mixing.py).
    m, n = 16, 300
    batch, grad_fn, objective = linreg_problem(m, n, spn=64, seed=0)
    for kind in ("complete", "ring"):
        topo = build_topology(kind, m)
        curves = {}
        for mode in ("dense", "sparse"):
            bound = ALG.get_algorithm("dpsgd").bind(
                grad_fn, topo, ALG.DPSGDHp(lr=0.1), mixing=mode
            )
            _, hist = bound.run(
                jax.random.PRNGKey(0), jnp.zeros(n), m, lambda k: batch, 32,
                tol_std=0.0, chunk_size=16,
            )
            curves[mode] = hist["loss"]
        identical = curves["dense"] == curves["sparse"]
        table[f"dpsgd_bit_identity_{kind}"] = bool(identical)
        csv_row(f"mixing/dpsgd_bit_identity/{kind}", 0.0, f"identical={identical}")
    RESULTS["mixing"] = table


def _fmt_md_table(header, rows):
    lines = ["| " + " | ".join(header) + " |",
             "|" + "|".join("---" for _ in header) + "|"]
    lines += ["| " + " | ".join(str(c) for c in row) + " |" for row in rows]
    return "\n".join(lines)


def _merge_artifact(fname, key, value):
    """Read-modify-write one top-level key of a JSON artifact, so several
    benches can contribute sections to the same trajectory file."""
    path = os.path.join(ART, fname)
    data = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                data = json.load(f)
        except (json.JSONDecodeError, OSError):
            data = {}
    data[key] = value
    with open(path, "w") as f:
        json.dump(data, f, indent=1, default=float, sort_keys=True)
    print(f"# wrote {path} [{key}]")


def _update_experiments_md(tag, body):
    """Replace the marked section of EXPERIMENTS.md (idempotent emission —
    repeat benchmark runs rewrite their own block only)."""
    path = os.path.join(HERE, "..", "EXPERIMENTS.md")
    begin, end = f"<!-- BEGIN {tag} -->", f"<!-- END {tag} -->"
    block = f"{begin}\n{body}\n{end}"
    if os.path.exists(path):
        with open(path) as f:
            text = f.read()
    else:
        text = "# EXPERIMENTS\n\nGenerated tables from `benchmarks.run`.\n"
    if begin in text and end in text:
        head, rest = text.split(begin, 1)
        _, tail = rest.split(end, 1)
        text = head + block + tail
    else:
        text = text.rstrip() + "\n\n" + block + "\n"
    with open(path, "w") as f:
        f.write(text)


def bench_scenarios(quick=False):
    """Dynamic-network race: churn rate × topology for PaME + two baselines
    through the scan engine.  Every dynamic step realizes a fresh
    doubly-stochastic matrix on device (links fail, nodes drop, state of
    dropped nodes frozen) and only realized edges are charged, so the
    gbits column is the *surviving-traffic* volume.  churn=0.0 rows run
    the static fixed-Topology path — the baseline the dynamic rows are
    read against.  Then three temporal-dynamics sections: the edge_drop ×
    straggler sweep with its wall-clock-per-realized-gbit frontier
    (emitted into EXPERIMENTS.md), the i.i.d.-vs-Markov-vs-stale regime
    race at matched stationary rates, and the headline staleness-
    sensitivity row (PaME vs the gradient-tracking baselines as the
    bounded-staleness window D grows).  Closes with the sparse-vs-dense
    scenario-mixing check (same realizations, same realized wire bits,
    fp-tolerance params)."""
    from repro.core import algorithms as ALG
    from repro.core.scenarios import Scenario
    from repro.core.temporal import TemporalScenario

    m, n = 16, 300
    steps = 60 if quick else 120
    algos = ("pame", "dpsgd", "choco")
    churns = (0.0, 0.2) if quick else (0.0, 0.1, 0.3)
    topos = (("ring", {}), ("erdos_renyi", dict(p=0.4, seed=0)))
    batch, grad_fn, objective = linreg_problem(m, n, spn=64, seed=0)
    key = jax.random.PRNGKey(0)
    chunk = chunk_for(steps)
    hps = {
        "pame": PaMEConfig(nu=0.3, p=0.3, gamma=1.01, sigma0=8.0),
        "dpsgd": ALG.DPSGDHp(lr=0.1),
        "choco": ALG.ChocoHp(lr=0.05, gossip_gamma=0.3, comp_frac=0.3),
    }
    table = {}
    for kind, kwargs in topos:
        topo = build_topology(kind, m, **kwargs)
        for churn in churns:
            scen = Scenario(
                name=f"churn{churn}", churn=churn,
                edge_drop=0.1 if churn > 0 else 0.0, seed=1,
            )
            for name in algos:
                bound = ALG.get_algorithm(name).bind(
                    grad_fn, topo, hps[name], mixing="sparse", scenario=scen
                )
                runner = bound.make_runner(
                    objective_fn=objective, tol_std=1e-3, chunk_size=chunk
                )
                runner(key, jnp.zeros(n), m, lambda k: batch, chunk)  # warm-up
                t0 = time.perf_counter()
                _, hist = runner(key, jnp.zeros(n), m, lambda k: batch, steps)
                wall = time.perf_counter() - t0
                row = {
                    "final": hist["objective"][-1],
                    "steps_run": hist["steps_run"],
                    "gbits": hist["wire_bits_total"] / 1e9,
                    "us_per_call": wall / max(hist["steps_dispatched"], 1) * 1e6,
                }
                if "alive_nodes" in hist:
                    row["mean_alive"] = float(np.mean(hist["alive_nodes"]))
                table[f"{kind}_churn{churn}_{name}"] = row
                csv_row(
                    f"scenarios/{kind}/churn={churn}/{name}", row["us_per_call"],
                    f"final_obj={row['final']:.4f};rounds={row['steps_run']}"
                    f";gbits={row['gbits']:.4f}"
                    f";mean_alive={row.get('mean_alive', float(m)):.1f}",
                )
    def _race(name, scen, steps_, hp=None, topo_=None):
        """One warmed scan run; returns (final obj, realized gbits, wall s,
        us/call, steps dispatched)."""
        bound = ALG.get_algorithm(name).bind(
            grad_fn, topo_ if topo_ is not None else topo, hp or hps.get(name),
            mixing="sparse", scenario=scen,
        )
        runner = bound.make_runner(
            objective_fn=objective, tol_std=1e-3, chunk_size=chunk
        )
        runner(key, jnp.zeros(n), m, lambda k: batch, chunk)  # warm-up
        t0 = time.perf_counter()
        _, hist = runner(key, jnp.zeros(n), m, lambda k: batch, steps_)
        wall = time.perf_counter() - t0
        return {
            "final": hist["objective"][-1],
            "gbits": hist["wire_bits_total"] / 1e9,
            "wall_s": wall,
            "us_per_call": wall / max(hist["steps_dispatched"], 1) * 1e6,
            "steps_run": hist["steps_run"],
            "staleness_hist": hist.get("staleness_hist"),
        }

    # edge_drop × straggler sweep: the wall-clock-per-realized-gbit
    # frontier (how much wall time each surviving gigabit costs as links
    # fail and nodes straggle) — emitted as a table into EXPERIMENTS.md.
    topo = build_topology("erdos_renyi", m, p=0.4, seed=0)
    hps["beer"] = ALG.BeerHp(lr=0.05, gossip_gamma=0.4, comp_frac=0.2)
    hps["anq_nids"] = ALG.AnqNidsHp(lr=0.1, qsgd_levels=16)
    edge_drops = (0.0, 0.3) if quick else (0.0, 0.2, 0.4)
    stragglers = (0.0, 0.3) if quick else (0.0, 0.2, 0.4)
    frontier_rows = []
    for ed in edge_drops:
        for sg in stragglers:
            scen = Scenario(name=f"ed{ed}_sg{sg}", edge_drop=ed,
                            straggler=sg, seed=2)
            for name in ("pame", "dpsgd"):
                r = _race(name, scen, steps)
                tag = f"edge_drop{ed}_strag{sg}_{name}"
                s_per_gbit = r["wall_s"] / max(r["gbits"], 1e-12)
                table[tag] = {**r, "s_per_realized_gbit": s_per_gbit}
                frontier_rows.append(
                    (name, ed, sg, f"{r['final']:.4f}", f"{r['gbits']:.4f}",
                     f"{r['us_per_call']:.0f}", f"{s_per_gbit:.2f}")
                )
                csv_row(
                    f"scenarios/sweep/edge_drop={ed}/straggler={sg}/{name}",
                    r["us_per_call"],
                    f"final_obj={r['final']:.4f};gbits={r['gbits']:.4f}"
                    f";s_per_gbit={s_per_gbit:.2f}",
                )
    _update_experiments_md(
        "scenario-frontier",
        "## Dynamic-network frontier: wall-clock per realized gbit\n\n"
        f"edge_drop × straggler sweep on erdos_renyi(m={m}, p=0.4), "
        f"linreg n={n}, {steps} steps (scan engine, warmed).  gbits counts "
        "*surviving* traffic only, so the s/gbit column is the cost of the "
        "bits that actually moved.\n\n"
        + _fmt_md_table(
            ("algo", "edge_drop", "straggler", "final_obj", "realized_gbits",
             "us/step", "s_per_realized_gbit"),
            frontier_rows,
        ),
    )

    # i.i.d. vs Markov vs stale: same stationary link-failure rate (20%)
    # and straggler rate; the Markov rows replace the i.i.d. draw with a
    # bursty Gilbert–Elliott chain (mean bad burst 5 steps), and the
    # stale rows let stragglers keep participating at <= 3 steps delay.
    # Staleness delays the gradients too (the step runs on the delayed
    # stack), so the baseline stepsize must respect the delay bound —
    # lr = 0.05 here (lr = 0.1 diverges at D = 3, the classic
    # delayed-gradient stability shrinkage).
    regimes = {
        "iid": Scenario(name="iid", edge_drop=0.2, straggler=0.4, seed=3),
        "markov": TemporalScenario(
            name="markov", burst_down=0.05, burst_up=0.2, straggler=0.4,
            staleness=0, seed=3),
        "stale": TemporalScenario(
            name="stale", burst_down=0.05, burst_up=0.2, straggler=0.4,
            staleness=3, seed=3),
    }
    for regime, scen in regimes.items():
        for name in ("pame", "dpsgd"):
            r = _race(name, scen, steps,
                      hp=ALG.DPSGDHp(lr=0.05) if name == "dpsgd" else None)
            table[f"regime_{regime}_{name}"] = r
            csv_row(
                f"scenarios/regime/{regime}/{name}", r["us_per_call"],
                f"final_obj={r['final']:.4f};gbits={r['gbits']:.4f}",
            )

    # headline: staleness sensitivity, PaME vs the gradient-tracking
    # baselines — how much does each method pay as 40% of nodes run
    # late, when their t-delayed messages still count (D > 0) vs are
    # dropped (D = 0)?  Baselines race at the delay-stable lr = 0.02.
    stale_hps = {
        "dpsgd": ALG.DPSGDHp(lr=0.02),
        "beer": ALG.BeerHp(lr=0.02, gossip_gamma=0.4, comp_frac=0.2),
        "anq_nids": ALG.AnqNidsHp(lr=0.02, qsgd_levels=16),
    }
    stale_rows = []
    ds = (0, 1, 3) if quick else (0, 1, 2, 3)
    for name in ("pame", "dpsgd", "beer", "anq_nids"):
        finals = {}
        for d in ds:
            scen = TemporalScenario(
                name=f"stale{d}", straggler=0.4, staleness=d, seed=4
            )
            r = _race(name, scen, steps, hp=stale_hps.get(name))
            finals[d] = r["final"]
            table[f"staleness{d}_{name}"] = r
        degr = finals[max(ds)] / max(finals[0], 1e-12)
        stale_rows.append(
            (name,) + tuple(f"{finals[d]:.4f}" for d in ds)
            + (f"{degr:.3f}",)
        )
        csv_row(
            f"scenarios/staleness_sensitivity/{name}", 0.0,
            ";".join(f"final_D{d}={finals[d]:.4f}" for d in ds)
            + f";ratio_Dmax_over_D0={degr:.3f}",
        )
    _update_experiments_md(
        "staleness-sensitivity",
        "## Staleness sensitivity: PaME vs gradient tracking\n\n"
        "40% stragglers; D = 0 drops their round (self-loop, the old\n"
        "semantics), D > 0 mixes their <= D-step-old parameters from the\n"
        "scan-carried snapshot ring (gradients too are evaluated on the\n"
        "delayed stack — computation + communication staleness).  Final\n"
        f"objective after {steps} steps; last column is\n"
        "final(D=max)/final(D=0) — below 1 means delayed messages helped.\n"
        "PaME's decaying penalty stepsize absorbs the delay (ratio < 1),\n"
        "while the gradient-tracking baselines' correction memory\n"
        "amplifies it — the sensitivity gap the paper's robustness story\n"
        "predicts.\n\n"
        + _fmt_md_table(
            ("algo",) + tuple(f"final D={d}" for d in ds) + ("Dmax/D0",),
            stale_rows,
        ),
    )

    # sparse vs dense scenario mixing: identical realizations (same seed)
    # => identical realized wire bits; params agree to fp tolerance (the
    # two modes sum the node axis in different slot orders).
    topo = build_topology("erdos_renyi", m, p=0.4, seed=0)
    scen = Scenario(name="mix_eq", churn=0.2, edge_drop=0.2, seed=1)
    outs = {}
    for mode in ("sparse", "dense"):
        bound = ALG.get_algorithm("dpsgd").bind(
            grad_fn, topo, hps["dpsgd"], mixing=mode, scenario=scen
        )
        state, hist = bound.run(
            key, jnp.zeros(n), m, lambda k: batch, 32,
            tol_std=0.0, chunk_size=16,
        )
        outs[mode] = (np.asarray(state.params), hist["wire_bits"])
    delta = float(np.max(np.abs(outs["sparse"][0] - outs["dense"][0])))
    wire_equal = outs["sparse"][1] == outs["dense"][1]
    table["sparse_vs_dense"] = {"max_param_delta": delta, "wire_equal": wire_equal}
    csv_row(
        "scenarios/sparse_vs_dense", 0.0,
        f"max_param_delta={delta:.2e};wire_equal={wire_equal}",
    )
    RESULTS["scenarios"] = table


def bench_sweep(quick=False):
    """The batched-sweep headline: an S-seed × C-config grid through the
    vmap-over-lanes engine vs the per-cell Python loop (compile included),
    plus the slots-vs-segment-sum gossip core race across degrees.
    Everything lands in benchmarks/artifacts/BENCH_sweep.json so the perf
    trajectory is machine-readable, and in an EXPERIMENTS.md block."""
    from repro.core import algorithms as ALG

    m, n = 32, 300
    steps = 50 if quick else 100
    n_seeds = 4 if quick else 8
    seeds = list(range(n_seeds))
    topo = build_topology("erdos_renyi", m, p=0.4, seed=0)
    batch, grad_fn, objective = linreg_problem(m, n, spn=64, seed=0)
    chunk = chunk_for(steps)
    grids = {
        "dpsgd": [ALG.DPSGDHp(lr=0.1), ALG.DPSGDHp(lr=0.05)],
        "pame": [
            PaMEConfig(nu=0.2, p=0.2, gamma=1.01, sigma0=8.0),
            PaMEConfig(nu=0.4, p=0.2, gamma=1.02, sigma0=4.0),
        ],
    }
    sweep_table = {}
    for name, cfgs in grids.items():
        cells = len(cfgs) * len(seeds)
        # per-cell loop: fresh bind + runner per (config, seed) — every
        # cell re-traces and re-compiles its own scan executable
        t0 = time.perf_counter()
        loop_finals = []
        for cfg in cfgs:
            for s in seeds:
                bound = ALG.get_algorithm(name).bind(grad_fn, topo, cfg)
                _, hist = bound.run(
                    jax.random.PRNGKey(s), jnp.zeros(n), m, lambda k: batch,
                    steps, objective_fn=objective, tol_std=0.0,
                    chunk_size=chunk,
                )
                loop_finals.append(hist["objective"][-1])
        wall_loop = time.perf_counter() - t0
        # batched: the whole grid is ONE jitted scan (compile included)
        t0 = time.perf_counter()
        ba = ALG.get_algorithm(name).bind_batched(
            grad_fn, topo, cfgs, seeds=seeds
        )
        _, hist = ba.run(
            jnp.zeros(n), m, lambda k: batch, steps,
            objective_fn=objective, tol_std=0.0, chunk_size=chunk,
        )
        wall_batched = time.perf_counter() - t0
        finals = lane_finals(hist)
        max_dev = float(np.max(np.abs(finals - np.asarray(loop_finals))))
        speedup = wall_loop / max(wall_batched, 1e-9)
        sweep_table[name] = {
            "cells": cells, "steps": steps,
            "wall_loop_s": wall_loop, "wall_batched_s": wall_batched,
            "speedup": speedup,
            "us_per_cell_step_loop": wall_loop / (cells * steps) * 1e6,
            "us_per_cell_step_batched": wall_batched / (cells * steps) * 1e6,
            "max_final_dev": max_dev,
        }
        csv_row(
            f"sweep/batched_vs_loop/{name}",
            sweep_table[name]["us_per_cell_step_batched"],
            f"speedup={speedup:.1f}x;cells={cells};loop_s={wall_loop:.1f}"
            f";batched_s={wall_batched:.1f};max_final_dev={max_dev:.2e}",
        )

    # gossip core race: fused slot chain vs edge-list segment-sum, across
    # degrees, on a model-layer-sized pytree.  Compile (warmup) time and
    # steady state recorded separately — the segment-sum program is O(1)
    # traced ops at any degree, the slot chain O(d).
    from repro.core.mixing import default_impl, make_mixer

    rng = np.random.default_rng(0)
    gossip_table = {}
    degs = [(32, 4), (64, 8)] if quick else [(32, 4), (64, 8), (128, 32), (256, 64)]
    for m_, d_ in degs:
        topo_ = build_topology("regular", m_, degree=d_, seed=0)
        tree = {
            "w": jnp.asarray(rng.standard_normal((m_, 64, 64)), jnp.float32),
            "b": jnp.asarray(rng.standard_normal((m_, 256)), jnp.float32),
        }
        row = {}
        for impl in ("slots", "segsum"):
            fn = jax.jit(make_mixer(topo_, "sparse", impl=impl).mix)
            r = benchmark(fn, tree, warmup=1, iters=5)
            row[impl] = {
                "us_steady": r["us_min"], "us_median": r["us_median"],
                "compile_s": r["warmup_s"],
            }
        gossip_table[f"m{m_}_d{d_}"] = row
        csv_row(
            f"sweep/gossip/m={m_}/d={d_}", row["slots"]["us_steady"],
            f"slots_us={row['slots']['us_steady']:.0f}"
            f";segsum_us={row['segsum']['us_steady']:.0f}"
            f";slots_compile_s={row['slots']['compile_s']:.2f}"
            f";segsum_compile_s={row['segsum']['compile_s']:.2f}",
        )

    # persistent-compile-cache race: the SAME dpsgd grid dispatched twice
    # through fresh bind_batched closures against a fresh cache directory.
    # A fresh closure always re-traces AND re-compiles (that is the
    # per-dispatch fixed cost the cache attacks); with the cache on, the
    # warm dispatch re-traces but swaps the XLA compile for a disk read.
    import shutil

    from repro.core.engine import compilation_cache_dir, setup_compilation_cache

    def _grid_dispatch_s():
        t0 = time.perf_counter()
        ba_ = ALG.get_algorithm("dpsgd").bind_batched(
            grad_fn, topo, grids["dpsgd"], seeds=seeds
        )
        _, h = ba_.run(
            jnp.zeros(n), m, lambda k: batch, steps,
            objective_fn=objective, tol_std=0.0, chunk_size=chunk,
        )
        jax.block_until_ready(h["objective"])
        return time.perf_counter() - t0

    # a fixed subdirectory of the resolved cache, emptied first, so the
    # cold dispatch is cold
    cache_dir = os.path.join(compilation_cache_dir(), "sweep_race")
    shutil.rmtree(cache_dir, ignore_errors=True)
    setup_compilation_cache("sweep_race")
    try:
        cold_s = _grid_dispatch_s()
        warm_s = _grid_dispatch_s()
    finally:
        setup_compilation_cache()
    cache_saving = 1.0 - warm_s / max(cold_s, 1e-9)
    cache_table = {
        "cold_s": cold_s, "warm_s": warm_s, "saving": cache_saving,
        "cache_dir_entries": len(os.listdir(cache_dir)),
    }
    csv_row(
        "sweep/compile_cache/dpsgd_grid", warm_s * 1e6,
        f"cold_s={cold_s:.2f};warm_s={warm_s:.2f}"
        f";saving={cache_saving*100:.0f}%",
    )

    artifact = {
        "backend": jax.default_backend(),
        "default_gossip_impl": default_impl(),
        "batched_vs_loop": sweep_table,
        "gossip_core": gossip_table,
        "compile_cache": cache_table,
    }
    with open(os.path.join(ART, "BENCH_sweep.json"), "w") as f:
        json.dump(artifact, f, indent=1, default=float, sort_keys=True)
    print(f"# wrote {os.path.join(ART, 'BENCH_sweep.json')}")
    _merge_artifact(
        "BENCH_gossip.json", "compile_cache",
        {"backend": jax.default_backend(), **cache_table},
    )

    md_rows = [
        (name, r["cells"],
         f"{r['wall_loop_s']:.1f}", f"{r['wall_batched_s']:.1f}",
         f"{r['speedup']:.1f}x", f"{r['max_final_dev']:.1e}")
        for name, r in sweep_table.items()
    ]
    gossip_rows = [
        (key, f"{row['slots']['us_steady']:.0f}",
         f"{row['segsum']['us_steady']:.0f}",
         f"{row['slots']['compile_s']:.2f}",
         f"{row['segsum']['compile_s']:.2f}")
        for key, row in gossip_table.items()
    ]
    _update_experiments_md(
        "batched-sweep",
        "## Batched sweep engine: one compile for the whole grid\n\n"
        f"{n_seeds} seeds × 2 configs per algorithm on linreg "
        f"(m={m}, n={n}), {steps} steps, compile time included in both "
        "columns.  The per-cell loop re-traces and re-compiles every "
        "(config, seed) cell; the batched engine runs the grid as lanes "
        "of one jitted scan (`engine.run_batched`).  max_dev is the "
        "largest |batched − looped| final objective across cells.\n\n"
        + _fmt_md_table(
            ("algo", "cells", "loop_s", "batched_s", "speedup", "max_dev"),
            md_rows,
        )
        + "\n\n### Gossip core: fused slot chain vs edge-list segment-sum\n\n"
        f"`Mixer.mix` on a 64×64+256 pytree, backend={jax.default_backend()}"
        ", steady state = min over 5 reps; compile_s is the first-call "
        "(trace + compile) wall time.  The segment-sum program is O(1) "
        "traced ops at any degree — on CPU, XLA's serialized scatter "
        "keeps the fused slot chain ahead at runtime (hence the "
        "backend-gated default, `repro.core.mixing.default_impl`).\n\n"
        + _fmt_md_table(
            ("graph", "slots us/call", "segsum us/call",
             "slots compile s", "segsum compile s"),
            gossip_rows,
        )
        + "\n\n### Persistent compilation cache: cold vs warm grid dispatch\n\n"
        "The same dpsgd seed×config grid dispatched twice through *fresh* "
        "`bind_batched` closures (each dispatch re-traces and, without a "
        "cache, re-compiles) against a fresh "
        "`engine.setup_compilation_cache` directory.\n\n"
        + _fmt_md_table(
            ("cold s", "warm s", "saving"),
            [(f"{cold_s:.2f}", f"{warm_s:.2f}", f"{cache_saving*100:.0f}%")],
        ),
    )
    RESULTS["sweep"] = {
        **sweep_table, "gossip": gossip_table, "compile_cache": cache_table,
    }


def bench_gossip(quick=False):
    """The gossip-impl roofline race: slots vs segsum vs the fused Pallas
    kernel (`kernels/gossip`) across (m, degree, n) regimes, with
    bytes-moved roofline terms per impl (`roofline.gossip_roofline`).
    On CPU the kernel runs in interpret mode — the one-hot scatter build
    + single gemm lower to plain XLA, which beats the O(degree)
    serialized slot chain once the degree is high; on accelerators it is
    the fused-MXU form.  `default_impl` stays backend-gated, so a regime
    where pallas loses costs nothing — this bench is the evidence for
    flipping the gate per backend.  Emits the race into
    BENCH_gossip.json (shared with bench_sweep's compile-cache section)
    and an EXPERIMENTS.md block."""
    from benchmarks.roofline import gossip_roofline
    from repro.core.mixing import default_impl, make_mixer

    rng = np.random.default_rng(0)
    regimes = [
        (32, 4, 4096),      # low degree — slot chain territory
        (64, 32, 2048),     # mid: degree = m/2, close race
        (128, 64, 1024),    # high degree, one receiver tile
        (256, 120, 4096),   # high degree at the unroll ceiling, large n
    ]
    if quick:
        regimes = [(32, 4, 1024), (128, 64, 1024)]
    impls = ("slots", "segsum", "pallas")
    table = {}
    pallas_wins = []
    for m_, d_, n_ in regimes:
        topo_ = build_topology("regular", m_, degree=d_, seed=0)
        k_ = topo_.max_degree + 1
        x = jnp.asarray(rng.standard_normal((m_, n_)), jnp.float32)
        row = {}
        for impl in impls:
            fn = jax.jit(make_mixer(topo_, "sparse", impl=impl).mix)
            r = benchmark(fn, x, warmup=2, iters=7)
            row[impl] = {
                "us_steady": r["us_min"],
                "us_median": r["us_median"],
                "compile_s": r["compile_s"],
                "roofline": gossip_roofline(
                    m_, k_, n_, impl, measured_us=r["us_min"]
                ),
            }
        winner = min(impls, key=lambda i: row[i]["us_steady"])
        if winner == "pallas":
            pallas_wins.append(f"m{m_}_d{d_}_n{n_}")
        table[f"m{m_}_d{d_}_n{n_}"] = {**row, "winner": winner}
        csv_row(
            f"gossip/m={m_}/d={d_}/n={n_}", row["pallas"]["us_steady"],
            f"slots_us={row['slots']['us_steady']:.0f}"
            f";segsum_us={row['segsum']['us_steady']:.0f}"
            f";pallas_us={row['pallas']['us_steady']:.0f}"
            f";winner={winner}",
        )

    backend = jax.default_backend()
    race = {
        "backend": backend,
        "default_gossip_impl": default_impl(),
        "pallas_interpret": backend == "cpu",
        "regimes": table,
        "pallas_wins": pallas_wins,
    }
    _merge_artifact("BENCH_gossip.json", f"race_{backend}", race)

    md_rows = [
        (key,
         f"{row['slots']['us_steady']:.0f}",
         f"{row['segsum']['us_steady']:.0f}",
         f"{row['pallas']['us_steady']:.0f}",
         row["winner"],
         f"{row['pallas']['roofline']['intensity_flop_per_byte']:.1f}")
        for key, row in table.items()
    ]
    _update_experiments_md(
        "gossip-kernel",
        "## Gossip kernel race: slots vs segsum vs fused Pallas\n\n"
        f"`Mixer.mix` on an [m, n] stack, backend={backend} "
        f"(pallas {'interpret mode' if backend == 'cpu' else 'compiled'}), "
        "steady state = min over 7 reps.  The fused kernel builds the "
        "dense scatter matrix on-chip and contracts with one matmul per "
        "term — it trades O(degree) serialized gather passes for "
        "matrix-unit FLOPs, so it wins where the degree is high and "
        "loses to the fused slot chain at low degree (the backend-gated "
        "`default_impl` keeps slots/segsum the defaults; "
        "`REPRO_GOSSIP_IMPL=pallas` opts in).  `intensity` is the pallas "
        "roofline arithmetic intensity (flop/HBM-byte) from "
        "`roofline.gossip_roofline`.\n\n"
        + _fmt_md_table(
            ("regime", "slots us", "segsum us", "pallas us", "winner",
             "pallas intensity"),
            md_rows,
        ),
    )
    RESULTS["gossip"] = race


def bench_heterogeneity(quick=False):
    """Fig 11 (label skew, CNN) + Fig 12 (Dirichlet, ResNet-20), synthetic
    stand-in images (offline container; heterogeneity mechanism exact).

    Every cell's SWEEP_SEEDS seed replicas run as lanes of ONE batched scan
    (the seed axis moved from a per-cell Python loop onto `bind_batched`),
    so accuracies and losses report mean ± std.  The headline block races
    the flat vs tree-partitioned exchange on a >=1M-parameter wide CNN
    under label skew and emits the table into EXPERIMENTS.md."""
    from repro.core import algorithms as ALG
    from repro.data import (
        NodeBatcher,
        SyntheticClassification,
        dirichlet_partition,
        iid_partition,
        label_skew_partition,
    )
    from repro.models.cnn import ce_loss, cnn_apply, cnn_init, resnet20_apply, resnet20_init

    table = {}
    m = 4
    # quick trims: chunk-aligned step counts (one scan length = one
    # compile), 3 seed lanes on the figure cells; the EXPERIMENTS.md
    # headline always runs the full SWEEP_SEEDS lanes
    steps = 32 if quick else 100
    fig_seeds = list(range(3 if quick else SWEEP_SEEDS))
    hl_seeds = list(range(SWEEP_SEEDS))

    def run_fl(ds, parts, init_fn, apply_fn, steps, sigma0=10.0, cfg=None,
               seeds=None, batch_size=32):
        seeds = fig_seeds if seeds is None else seeds
        nb = NodeBatcher({"x": ds.images, "y": ds.labels}, parts,
                         batch_size=batch_size, seed=0)
        topo = build_topology("complete", m)
        if cfg is None:
            cfg = PaMEConfig(nu=0.7, p=0.3, gamma=1.002, sigma0=sigma0,
                             kappa_lo=2, kappa_hi=4)

        def grad_fn(params, batch, key):
            return jax.value_and_grad(
                lambda p: ce_loss(apply_fn(p, batch["x"]), batch["y"])
            )(params)

        def batch_fn(k):
            b = nb.next()
            return {"x": jnp.asarray(b["x"], jnp.float32), "y": jnp.asarray(b["y"], jnp.int32)}

        ba = ALG.get_algorithm("pame").bind_batched(
            grad_fn, topo, [cfg], seeds=seeds
        )
        t0 = time.perf_counter()
        state, hist = ba.run(
            init_fn(jax.random.PRNGKey(1)), m, batch_fn, steps, tol_std=0.0
        )
        wall = time.perf_counter() - t0
        # per-lane accuracy of the node-mean parameters (state leaves [L, m, ...])
        xs = jnp.asarray(ds.images[:512], jnp.float32)
        ys = jnp.asarray(ds.labels[:512])
        accs = []
        for l in range(ba.lanes):
            mean_params = jax.tree_util.tree_map(
                lambda x: x[l].mean(axis=0), state.params
            )
            logits = apply_fn(mean_params, xs)
            accs.append(float(jnp.mean(jnp.argmax(logits, -1) == ys)))
        am, astd = mean_std(accs)
        lm, lstd = mean_std(lane_finals(hist, "loss"))
        bm, _ = mean_std(hist["wire_bits_total"])
        return {
            "final_loss": lm, "final_loss_std": lstd,
            "accuracy": am, "accuracy_std": astd,
            "gbits": bm / 1e9, "seeds": len(seeds),
            "us_per_call": wall / max(
                int(hist["steps_dispatched"]) * ba.lanes, 1) * 1e6,
        }

    # Fig 11: label skew C in {1, 7, 10} on the CNN (quick: the extremes —
    # every cell pays a fresh lane-vmapped compile, so quick trims cells,
    # not steps)
    ds = SyntheticClassification.make(1024, (28, 28, 1), 10, seed=0, sep=3.0)
    for c in ((1, 10) if quick else (1, 7, 10)):
        parts = label_skew_partition(ds.labels, m, c, seed=0)
        r = run_fl(ds, parts, lambda k: cnn_init(k), cnn_apply, steps)
        table[f"cnn_labelskew_C{c}"] = r
        csv_row(
            f"heterogeneity/cnn/C={c}", r["us_per_call"],
            f"acc={r['accuracy']:.3f}±{r['accuracy_std']:.3f}"
            f";final_loss={r['final_loss']:.3f};seeds={r['seeds']}",
        )

    # Fig 12: Dirichlet beta in {0.3, 0.6} + iid on ResNet-20 (short run)
    ds2 = SyntheticClassification.make(512, (32, 32, 3), 10, seed=1, sep=2.0)
    rn_steps = 10 if quick else 40
    for beta in ((0.3,) if quick else (0.3, 0.6, None)):
        if beta is None:
            parts = iid_partition(ds2.labels, m, seed=0)
            tag = "iid"
        else:
            parts = dirichlet_partition(ds2.labels, m, beta, seed=0)
            tag = f"beta{beta}"
        r = run_fl(
            ds2, parts, lambda k: resnet20_init(k), resnet20_apply, rn_steps, sigma0=10.0
        )
        table[f"resnet20_{tag}"] = r
        csv_row(
            f"heterogeneity/resnet20/{tag}", r["us_per_call"],
            f"acc={r['accuracy']:.3f}±{r['accuracy_std']:.3f}"
            f";final_loss={r['final_loss']:.3f};seeds={r['seeds']}",
        )

    # Headline: flat vs tree-partitioned exchange on a >=1M-parameter wide
    # CNN (cnn_init width=2) under label skew.  The tree partition prices
    # each leaf as its own Eq.-(8) segment, and p_leaf throttles the
    # dominant fc1 matrix (~95% of the parameters) while the small conv /
    # head leaves keep exchanging densely.
    width = 2
    params0 = cnn_init(jax.random.PRNGKey(1), width=width)
    sizes = [int(np.prod(x.shape))
             for x in jax.tree_util.tree_leaves(params0)]
    n_wide = sum(sizes)
    hl_steps = 16 if quick else 60
    hl_bs = 16 if quick else 32
    hl_C = 3
    parts = label_skew_partition(ds.labels, m, hl_C, seed=0)
    base = dict(nu=0.7, gamma=1.002, sigma0=10.0, kappa_lo=2, kappa_hi=4,
                mask_mode="bernoulli")
    # leaf order (tree_flatten, sorted keys): b1 b2 c1 c2 fc1 fc2
    hl_cfgs = [
        ("flat p=0.3", PaMEConfig(p=0.3, **base)),
        ("tree p=0.3", PaMEConfig(p=0.3, partition="tree", **base)),
        ("tree p_leaf (fc1@0.15)", PaMEConfig(
            p=0.3, partition="tree",
            p_leaf=(1.0, 1.0, 0.8, 0.4, 0.15, 0.8), **base)),
    ]
    md_rows = []
    for label, cfg in hl_cfgs:
        r = run_fl(ds, parts, lambda k: cnn_init(k, width=width), cnn_apply,
                   hl_steps, cfg=cfg, seeds=hl_seeds, batch_size=hl_bs)
        table[f"wide_cnn_{label}"] = r
        csv_row(
            f"heterogeneity/wide_cnn/{label}", r["us_per_call"],
            f"acc={r['accuracy']:.3f}±{r['accuracy_std']:.3f}"
            f";final_loss={r['final_loss']:.3f};gbits={r['gbits']:.3f}"
            f";seeds={r['seeds']}",
        )
        md_rows.append((
            label,
            f"{r['accuracy']:.3f} ± {r['accuracy_std']:.3f}",
            f"{r['final_loss']:.3f} ± {r['final_loss_std']:.3f}",
            f"{r['gbits']:.3f}",
            f"{r['us_per_call']:.0f}",
        ))
    _update_experiments_md(
        "heterogeneity-real",
        "## Partitioned partial exchange on a real model workload\n\n"
        f"Wide CNN ({n_wide/1e6:.2f}M params, `cnn_init(width=2)`), "
        f"label-skew heterogeneity (C={hl_C} classes/node), m={m} nodes "
        f"(complete graph), {hl_steps} steps, per-node batch {hl_bs}; each "
        f"row's {len(hl_seeds)} seed replicas run as lanes of ONE batched scan "
        "(`bind_batched`).  `tree` partitions the exchange over the model "
        "pytree: per-leaf coordinate masks and per-leaf Eq.-(8) wire "
        "accounting; `p_leaf` throttles the dominant fc1 leaf "
        f"({sizes[4]/n_wide:.0%} of all parameters) to 0.15 while small "
        "conv/head leaves exchange at 0.4–1.0.\n\n"
        + _fmt_md_table(
            ("exchange", "accuracy", "final loss", "gbits on the wire",
             "us/lane-step"),
            md_rows,
        ),
    )
    RESULTS["heterogeneity"] = table


def bench_engine(quick=False):
    """Host-loop vs scan-driver step cost on the Fig 2a workload (m=32,
    n=300 linreg).  Three rows: the pre-engine host loop (one dispatch +
    three float() syncs per step), a cold scan run (compile included), and
    the warmed scan runner (steady state — what the other benches report)."""
    m, n = 32, 300
    steps = 100 if quick else 200
    cfg = PaMEConfig(nu=0.2, p=0.2, gamma=1.01, sigma0=8.0)
    topo = build_topology("erdos_renyi", m, p=0.4, seed=0)
    batch, grad_fn, objective = linreg_problem(m, n, spn=128, seed=0)
    key = jax.random.PRNGKey(0)
    table = {}

    t0 = time.perf_counter()
    _, hist = run_pame(
        key, jnp.zeros(n), m, grad_fn, lambda k: batch, topo, cfg,
        num_steps=steps, objective_fn=objective, tol_std=0.0, driver="host",
    )
    table["host_loop"] = (time.perf_counter() - t0) / hist["steps_run"] * 1e6

    t0 = time.perf_counter()
    _, hist = run_pame(
        key, jnp.zeros(n), m, grad_fn, lambda k: batch, topo, cfg,
        num_steps=steps, objective_fn=objective, tol_std=0.0, driver="scan",
        chunk_size=chunk_for(steps),
    )
    table["scan_cold"] = (time.perf_counter() - t0) / hist["steps_run"] * 1e6

    chunk = chunk_for(steps)
    runner = make_pame_runner(
        grad_fn, topo, cfg, objective_fn=objective, tol_std=0.0,
        chunk_size=chunk, seed=0,
    )
    runner(key, jnp.zeros(n), m, lambda k: batch, chunk)  # compile
    t0 = time.perf_counter()
    _, hist = runner(key, jnp.zeros(n), m, lambda k: batch, steps)
    table["scan_steady"] = (time.perf_counter() - t0) / hist["steps_run"] * 1e6

    for name, us in table.items():
        csv_row(f"engine/{name}", us, f"steps={steps}")
    csv_row(
        "engine/speedup", 0.0,
        f"host_over_steady={table['host_loop']/max(table['scan_steady'],1e-9):.1f}x;"
        f"host_over_cold={table['host_loop']/max(table['scan_cold'],1e-9):.1f}x",
    )
    RESULTS["engine"] = table


def bench_comm_volume(quick=False):
    """Eq. (8): bits per message, sparse vs dense; 64-/16-bit float payloads
    plus an int8 wire."""
    table = {}
    for n in (10_000, 100_000, 1_000_000):
        for frac in (0.01, 0.1, 0.2):
            s = int(frac * n)
            for vb in (64, 16, 8):
                sparse = message_bits(s, n, vb)
                dense = vb * n
                table[f"n{n}_s{s}_b{vb}"] = {"sparse": sparse, "dense": dense}
                csv_row(
                    f"comm_volume/n={n}/s={s}/bits={vb}", 0.0,
                    f"sparse_bits={sparse};dense_bits={dense};saving={1-sparse/dense:.2%}",
                )
    RESULTS["comm_volume"] = table


def bench_kernels(quick=False):
    """Pallas kernels in interpret mode (correctness-path timing only —
    real-TPU wall times are not measurable on this CPU host)."""
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.pme_average.ops import pme_average
    from repro.kernels.pme_average.ref import pme_average_ref
    from repro.kernels.ssd_scan.ops import ssd_intra_chunk
    from repro.kernels.ssd_scan.ref import ssd_intra_chunk_ref

    rng = np.random.default_rng(0)
    table = {}

    m, n = 16, 4096
    w = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    masks = jnp.asarray(rng.random((m, n)) < 0.2)
    a = jnp.asarray(((rng.random((m, m)) < 0.4) & ~np.eye(m, dtype=bool)), jnp.float32)
    us_k = benchmark(lambda: pme_average(w, masks, a), iters=3)["us_median"]
    us_r = benchmark(
        jax.jit(lambda: pme_average_ref(w, masks.astype(w.dtype), a)), iters=3
    )["us_median"]
    err = float(jnp.max(jnp.abs(pme_average(w, masks, a) - pme_average_ref(w, masks.astype(w.dtype), a))))
    table["pme_average"] = {"us_kernel": us_k, "us_ref": us_r, "max_err": err}
    csv_row("kernels/pme_average", us_k, f"ref_us={us_r:.1f};max_err={err:.2e}")

    b, s, h, kv, d = 1, 256, 4, 2, 32
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, kv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, kv, d)), jnp.float32)
    us_k = benchmark(
        lambda: flash_attention(q, k, v, block_q=64, block_k=64), iters=1
    )["us_median"]
    us_r = benchmark(jax.jit(lambda: attention_ref(q, k, v)), iters=3)["us_median"]
    err = float(jnp.max(jnp.abs(flash_attention(q, k, v, block_q=64, block_k=64) - attention_ref(q, k, v))))
    table["flash_attention"] = {"us_kernel": us_k, "us_ref": us_r, "max_err": err}
    csv_row("kernels/flash_attention", us_k, f"ref_us={us_r:.1f};max_err={err:.2e}")

    B_, Nc, L, H, P, G, N = 1, 4, 32, 4, 16, 2, 16
    xc = jnp.asarray(rng.standard_normal((B_, Nc, L, H, P)), jnp.float32)
    dtc = jnp.asarray(rng.random((B_, Nc, L, H)) * 0.2 + 0.01, jnp.float32)
    av = jnp.asarray(-np.exp(rng.standard_normal(H) * 0.2), jnp.float32)
    cum = jnp.cumsum(dtc * av[None, None, None], axis=2)
    bc = jnp.asarray(rng.standard_normal((B_, Nc, L, G, N)), jnp.float32)
    cc = jnp.asarray(rng.standard_normal((B_, Nc, L, G, N)), jnp.float32)
    us_k = benchmark(
        lambda: ssd_intra_chunk(xc, dtc, cum, bc, cc, H // G), iters=1
    )["us_median"]
    us_r = benchmark(
        jax.jit(lambda: ssd_intra_chunk_ref(xc, dtc, cum, bc, cc, H // G)),
        iters=3,
    )["us_median"]
    yk, _ = ssd_intra_chunk(xc, dtc, cum, bc, cc, H // G)
    yr, _ = ssd_intra_chunk_ref(xc, dtc, cum, bc, cc, H // G)
    err = float(jnp.max(jnp.abs(yk - yr)))
    table["ssd_scan"] = {"us_kernel": us_k, "us_ref": us_r, "max_err": err}
    csv_row("kernels/ssd_scan", us_k, f"ref_us={us_r:.1f};max_err={err:.2e}")
    RESULTS["kernels"] = table


def bench_serving(quick=False):
    """Serve-while-train frontier: final accuracy vs served QPS as the
    inference arrival process intensifies.  Each preset drives the event
    clock from `repro.serve.events` through `bind_batched(pacing=...)`:
    nodes whose request queue exceeds the defer threshold skip that
    round's exchange (a load-induced straggler — PaME's partial-exchange
    semantics absorb it natively) while still taking their local step.
    `off` is the anchor: a static pacing binds the plain program, so its
    row is the no-serving baseline.  Queueing latency is recovered from
    the histories by Little's law (mean queue depth / per-node service
    rate).  The frontier is emitted into EXPERIMENTS.md."""
    from repro.core import algorithms as ALG
    from repro.serve.events import ServePacing, get_arrival

    m, n = 16, 300
    steps = 80 if quick else 200
    seeds = list(range(SWEEP_SEEDS))
    presets = ("off", "quiet", "steady", "bursty", "rush")
    capacity, defer = 2, 4
    topo = build_topology("erdos_renyi", m, p=0.4, seed=0)
    batch, grad_fn, objective, accuracy = logreg_problem(m, n, spn=64, seed=0)
    chunk = chunk_for(steps)
    hps = {
        "pame": PaMEConfig(nu=0.2, p=0.2, gamma=1.002, sigma0=1.0,
                           kappa_lo=3, kappa_hi=7),
        "dpsgd": ALG.DPSGDHp(lr=0.1),
    }
    table = {}
    md_rows = []
    for name in ("pame", "dpsgd"):
        for preset in presets:
            pac = ServePacing(get_arrival(preset), capacity=capacity,
                              defer_threshold=defer)
            ba = ALG.get_algorithm(name).bind_batched(
                grad_fn, topo, [hps[name]], seeds=seeds,
                mixing="sparse", pacing=pac,
            )
            runner = ba.make_runner(
                objective_fn=objective, tol_std=0.0, chunk_size=chunk
            )
            t0 = time.perf_counter()
            state, hist = runner(jnp.zeros(n), m, lambda k: batch, steps)
            wall = time.perf_counter() - t0
            mean_w = np.asarray(
                jax.tree_util.tree_map(
                    lambda x: x.mean(axis=1), ba.params_of(state)
                )
            )
            accs = [accuracy(jnp.asarray(mean_w[l])) for l in range(ba.lanes)]
            am, a_s = mean_std(accs)
            if "served_reqs" in hist:
                served = np.asarray(hist["served_reqs"])  # [steps, lanes]
                queue = np.asarray(hist["queue_depth"])
                deferred = np.asarray(hist["deferred_nodes"])
                qps = float(served.sum(axis=0).mean()) / steps
                per_node_rate = qps / m
                # Little's law: W = L / lambda (sojourn in rounds)
                latency = (float(queue.mean()) / per_node_rate
                           if per_node_rate > 0 else 0.0)
                defer_frac = float(deferred.mean()) / m
            else:
                # static pacing was dropped at bind: nothing served
                qps, latency, defer_frac = 0.0, 0.0, 0.0
            table[f"{name}@{preset}"] = {
                "preset": preset, "accuracy": am, "accuracy_std": a_s,
                "served_qps": qps, "latency_rounds": latency,
                "defer_frac": defer_frac, "seeds": len(seeds),
            }
            csv_row(
                f"serving/{name}/{preset}",
                wall / max(int(hist["steps_dispatched"]) * ba.lanes, 1) * 1e6,
                f"acc={am:.4f}±{a_s:.4f};qps={qps:.2f}"
                f";latency_rounds={latency:.2f};defer_frac={defer_frac:.3f}",
            )
            md_rows.append((
                name, preset, f"{am:.4f} ± {a_s:.4f}", f"{qps:.2f}",
                f"{latency:.2f}", f"{defer_frac*100:.1f}%",
            ))
    for name in ("pame", "dpsgd"):
        drop = (table[f"{name}@off"]["accuracy"]
                - table[f"{name}@rush"]["accuracy"])
        csv_row(f"serving/acc_cost_{name}", 0.0,
                f"acc_drop@rush={drop:.4f}")
    _update_experiments_md(
        "serving",
        "## Serve while you train: accuracy vs served QPS\n\n"
        f"Example 2 logistic regression (m={m}, n={n}), erdos_renyi(p=0.4), "
        f"{steps} steps, per-node serve capacity {capacity} req/round, "
        f"defer threshold {defer}.  Overloaded nodes defer that round's "
        "gossip (self-loop in the realized matrix) but keep their local "
        "gradient step — the paper's straggler semantics, triggered by "
        f"inference load.  Mean ± std over {len(seeds)} batched seed "
        "lanes (`bind_batched(pacing=...)`); latency is queueing sojourn "
        "via Little's law in units of training rounds.\n\n"
        + _fmt_md_table(
            ("algo", "arrival", "accuracy", "served QPS (net)",
             "latency (rounds)", "deferred node-rounds"),
            md_rows,
        ),
    )
    RESULTS["serving"] = table


def bench_chaos(quick=False):
    """Partition-tolerance race: a scheduled network split opens at
    steps//4 (the realization turns block-doubly-stochastic — zero
    cross-component mass, Assumption 1 intact within each side) and
    heals at steps//2; 5% message loss runs throughout so the
    surrogate-memory baselines (CHOCO/BEER/ANQ-NIDS) race their
    per-receiver replica variants.  During the split each side converges
    internally while the component means drift apart; at heal that drift
    becomes global disagreement and the race is who reconciles it.
    PaME's count-normalized averaging is memoryless — the merged rounds
    mix correctly immediately — while the surrogates re-enter with
    replicas desynced across the cut.

    Each algorithm runs TWICE with identical faults/seeds: once with the
    partition window and once without (the no-split reference).  The
    headline is the *residual damage ratio* — final-state disagreement
    split / no-split — which isolates the lasting scar the partition
    leaves after the algorithm's own convergence behaviour is divided
    out (PaME ≈ 1.0: memoryless, no scar).  The *merge spike* (peak
    disagreement in the 10 steps after heal over the pre-heal level)
    shows the transient a desynced surrogate memory injects at
    reconnection.  Emits BENCH_chaos.json and the EXPERIMENTS.md
    block."""
    from repro.core import algorithms as ALG
    from repro.core.faults import FaultModel
    from repro.core.scenarios import PartitionWindow, Scenario

    m, n = 16, 300
    steps = 80 if quick else 200
    start, heal = steps // 4, steps // 2
    seeds = list(range(SWEEP_SEEDS))
    topo = build_topology("erdos_renyi", m, p=0.4, seed=0)
    batch, grad_fn, objective, accuracy = logreg_problem(m, n, spn=64, seed=0)
    chunk = chunk_for(steps)
    scen = Scenario(
        name="split", seed=0,
        partitions=(PartitionWindow(start=start, heal=heal, n_parts=2,
                                    seed=1),),
    )
    fm_model = FaultModel(loss=0.05, seed=0)
    race_hps = {
        "pame": PaMEConfig(nu=0.2, p=0.2, gamma=1.002, sigma0=1.0,
                           kappa_lo=3, kappa_hi=7),
        "choco": ALG.ChocoHp(lr=0.05, gossip_gamma=0.3, comp_frac=0.3),
        "beer": ALG.BeerHp(lr=0.05, gossip_gamma=0.4, comp_frac=0.2),
        "anq_nids": ALG.AnqNidsHp(lr=0.1, qsgd_levels=16),
    }
    def final_disagreement(ba, state):
        # batched leaves are [lanes, m, ...]: per-lane mean over the m
        # nodes of the squared distance to the lane's node-mean params
        w = np.asarray(ba.params_of(state), np.float64)  # [L, m, n]
        dev = w - w.mean(axis=1, keepdims=True)
        return float(np.mean(np.mean(np.sum(dev * dev, axis=-1), axis=1)))

    table = {}
    curves = {}
    md_rows = []
    for name, hp in race_hps.items():
        run = {}
        for variant, variant_scen in (("split", scen), ("nosplit", None)):
            ba = ALG.get_algorithm(name).bind_batched(
                grad_fn, topo, [hp], seeds=seeds,
                mixing="sparse", scenario=variant_scen, faults=fm_model,
            )
            runner = ba.make_runner(
                objective_fn=objective, tol_std=0.0, chunk_size=chunk
            )
            t0 = time.perf_counter()
            state, hist = runner(jnp.zeros(n), m, lambda k: batch, steps)
            wall = time.perf_counter() - t0
            mean_w = np.asarray(
                jax.tree_util.tree_map(
                    lambda x: x.mean(axis=1), ba.params_of(state)
                )
            )
            accs = [
                accuracy(jnp.asarray(mean_w[l])) for l in range(ba.lanes)
            ]
            am, a_s = mean_std(accs)
            run[variant] = {
                "disagreement": final_disagreement(ba, state),
                "accuracy": am, "accuracy_std": a_s,
                "hist": hist, "wall": wall, "lanes": ba.lanes,
            }
        # [steps, lanes]: per-component consensus defect; outside the
        # window the single global component makes it plain disagreement
        hist = run["split"]["hist"]
        cc = np.asarray(hist["comp_consensus"]).mean(axis=1)
        gap = np.asarray(hist["comp_mean_gap"]).mean(axis=1)
        drift_at_heal = float(gap[heal - 1])     # cross-component drift
        pre_heal = float(cc[heal - 1])           # within-component level
        merge_spike = float(cc[heal:heal + 10].max()) / max(pre_heal, 1e-12)
        residual = run["split"]["disagreement"] / max(
            run["nosplit"]["disagreement"], 1e-12
        )
        acc_cost = run["nosplit"]["accuracy"] - run["split"]["accuracy"]
        table[name] = {
            "drift_at_heal": drift_at_heal,
            "pre_heal_disagreement": pre_heal,
            "merge_spike": merge_spike,
            "disagreement_split": run["split"]["disagreement"],
            "disagreement_nosplit": run["nosplit"]["disagreement"],
            "residual_damage": residual,
            "accuracy_split": run["split"]["accuracy"],
            "accuracy_nosplit": run["nosplit"]["accuracy"],
            "accuracy_cost": acc_cost,
            "seeds": len(seeds),
        }
        curves[name] = {"comp_consensus": cc.tolist(),
                        "comp_mean_gap": gap.tolist()}
        csv_row(
            f"chaos/{name}",
            run["split"]["wall"]
            / max(int(hist["steps_dispatched"]) * run["split"]["lanes"], 1)
            * 1e6,
            f"residual={residual:.4f};spike={merge_spike:.2f}x;"
            f"drift@heal={drift_at_heal:.4f};acc_cost={acc_cost:+.4f}",
        )
        md_rows.append((
            name, f"{drift_at_heal:.4f}", f"{merge_spike:.2f}×",
            f"{run['split']['disagreement']:.4f}",
            f"{run['nosplit']['disagreement']:.4f}",
            f"{residual:.3f}", f"{acc_cost:+.4f}",
        ))
    # headline: the partition's lasting scar, PaME vs each surrogate
    for name in race_hps:
        if name == "pame":
            continue
        margin = table[name]["residual_damage"] - table["pame"]["residual_damage"]
        csv_row(f"chaos/residual_damage_vs_{name}", 0.0,
                f"{name}_minus_pame={margin:+.4f};"
                f"spike_ratio={table[name]['merge_spike'] / max(table['pame']['merge_spike'], 1e-12):.1f}x")
    payload = {"config": {"m": m, "n": n, "steps": steps, "start": start,
                          "heal": heal, "loss": fm_model.loss,
                          "seeds": len(seeds)},
               "table": table, "curves": curves}
    with open(os.path.join(ART, "BENCH_chaos.json"), "w") as f:
        json.dump(payload, f, indent=1, default=float, sort_keys=True)
    print(f"# wrote {os.path.join(ART, 'BENCH_chaos.json')}")
    _update_experiments_md(
        "chaos",
        "## Partition tolerance: post-heal consensus recovery\n\n"
        f"Example 2 logistic regression (m={m}, n={n}), erdos_renyi(p=0.4), "
        f"{steps} steps.  The graph splits into 2 components over steps "
        f"[{start}, {heal}) — the realized matrix is block-doubly-"
        "stochastic per component (zero cross mass) — then heals; 5% "
        "message loss runs throughout, so CHOCO/BEER/ANQ-NIDS race their "
        "per-receiver surrogate replicas.  Every algorithm also runs a "
        "*no-split* reference with identical faults and seeds; the "
        "**residual damage** column is final-state disagreement "
        "split/no-split (1.0 = the partition left no lasting scar), and "
        "**merge spike** is the peak disagreement in the 10 steps after "
        "heal over the pre-heal level (the transient a desynced "
        "surrogate memory injects at reconnection).  PaME's "
        "count-normalized averaging is memoryless, so both stay near "
        f"1.  Mean over {len(seeds)} batched seed lanes "
        "(`bind_batched(scenario=..., faults=...)`).\n\n"
        + _fmt_md_table(
            ("algo", "drift@heal", "merge spike", "final dis. (split)",
             "final dis. (no split)", "residual damage", "acc cost"),
            md_rows,
        ),
    )
    RESULTS["chaos"] = table


BENCHES = {
    "transmission_rate": bench_transmission_rate,
    "participation": bench_participation,
    "comm_period": bench_comm_period,
    "connectivity": bench_connectivity,
    "vs_baselines": bench_vs_baselines,
    "faults": bench_faults,
    "mixing": bench_mixing,
    "sweep": bench_sweep,
    "gossip": bench_gossip,
    "scenarios": bench_scenarios,
    "serving": bench_serving,
    "chaos": bench_chaos,
    "heterogeneity": bench_heterogeneity,
    "comm_volume": bench_comm_volume,
    "kernels": bench_kernels,
    "engine": bench_engine,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=list(BENCHES))
    ap.add_argument("--quick", action="store_true")
    args, _ = ap.parse_known_args()
    from repro.core.engine import setup_compilation_cache

    print(f"# compile cache: {setup_compilation_cache()}")
    print("name,us_per_call,derived")
    names = [args.only] if args.only else list(BENCHES)
    for name in names:
        t0 = time.perf_counter()
        BENCHES[name](quick=args.quick)
        print(f"# {name} done in {time.perf_counter()-t0:.1f}s", flush=True)
    out_path = os.path.join(ART, "bench_results.json")
    results = {}
    if args.only and os.path.exists(out_path):
        # --only runs refresh their own section without clobbering the
        # rest of the artifact
        try:
            with open(out_path) as f:
                results = json.load(f)
        except (json.JSONDecodeError, OSError):
            results = {}
    results.update(RESULTS)
    # sort_keys gives byte-stable artifacts: section order no longer
    # depends on which benches ran (or in what order), so repeat runs
    # and --only refreshes diff cleanly
    with open(out_path, "w") as f:
        json.dump(results, f, indent=1, default=float, sort_keys=True)
    print(f"# wrote {out_path}")


if __name__ == "__main__":
    main()
