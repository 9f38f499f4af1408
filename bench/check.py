"""The comparison that decides ``correct``.

What the timed path produced in the set-up's first chunk (the window's own
compiled chunk, on the run's own weights and rows) is held against the
plain reference (``bench.reference.pame``):

- ``first_loss_gap``: the gap of the first round's node-mean loss, in
  nats.  Every node communicates in round 0, so the loss is taken after
  an exchange between nodes that start apart, on the same rows;
- ``loss_gap``: the largest gap of a round's node-mean loss over the
  rounds the reference follows (the first ``REFERENCE_ROUNDS``), in nats:
  the local step's forward, backward and update, before rounding has had
  rounds to grow;
- ``exchange_gap``: the exchange's selection, masks and average.  At a
  sample of coordinates drawn from the seed, node by node and leaf by
  leaf, the distance between the program's parameters after the chunk
  and the reference's after the chunk's exchanges alone, as a share of
  how far those exchanges moved the node from its start; the worst pair.
  The program's local steps are in the distance and move the parameters
  far less than the exchanges between nodes that start apart.  Leaves
  whose nodes start equal (the norm scales) are left out: the exchange
  cannot move them;
- ``comm_mismatch``: the rounds of the chunk whose number of
  communicating nodes differs from the kappa schedule (exact).

Each has its limit in ``bench/limits/<workload>.json``.
"""
from __future__ import annotations

import numpy as np

REFERENCE_ROUNDS = 4  # the losses of rounds 0-3: three local steps
SAMPLE = 1 << 16      # coordinates of each leaf a node holds, drawn from the seed


def exchange_gap(observed: dict, exchanged: dict) -> float:
    """``observed``: path -> the program's [m, S] parameters at the sample;
    ``exchanged``: path -> (start, end) of the reference's exchange alone."""
    worst = 0.0
    for path, (start, end) in exchanged.items():
        if np.all(start == start[:1]):
            continue
        got = np.asarray(observed[path], np.float64)
        moved = np.linalg.norm(end - start, axis=1)
        gap = np.linalg.norm(got - end, axis=1) / np.maximum(moved, 1e-30)
        worst = max(worst, float(np.max(gap)))
    return worst


def readings(observed: dict, reference: dict) -> dict:
    """``reference``: its per-round ``loss``, the chunk's ``comm_nodes`` and
    the ``exchanged`` sample of its exchanges alone."""
    loss_o = np.asarray(observed["loss"], np.float64)
    loss_r = np.asarray(reference["loss"], np.float64)
    rounds = len(loss_r)
    return {
        "first_loss_gap": float(abs(loss_o[0] - loss_r[0])),
        "loss_gap": float(np.max(np.abs(loss_o[:rounds] - loss_r))),
        "exchange_gap": exchange_gap(observed["sample"], reference["exchanged"]),
        "comm_mismatch": float(np.sum(np.asarray(observed["comm_nodes"])
                                      != np.asarray(reference["comm_nodes"]))),
    }


def verdict(values: dict, limits: dict) -> tuple:
    """(correct, [{name, value, limit}]) — every number at or under its
    limit; a number that is not finite fails."""
    rows = []
    correct = True
    for name, spec in limits.items():
        value = values[name]
        ok = bool(np.isfinite(value) and value <= spec["limit"])
        correct &= ok
        rows.append({"name": name, "value": value, "limit": spec["limit"]})
    return correct, rows
