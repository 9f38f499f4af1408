"""PaME exchange: the least HBM traffic any implementation of a round's
exchange must move, over the chip's HBM bandwidth, as a share of the
measured ``exchange_ms``.  The least traffic is reading and writing the m
replicas once (2 m n b bytes, n b the bytes of one replica) plus, for each
communicating receiver, the p n b bytes of each of its t_i selected
messages; the communicating receivers follow the kappa schedule over the
rounds the probe measured."""
import numpy as np


def least_bytes(replica_bytes: float, m: int, p: float, messages: float) -> float:
    """Bytes a round must move: 2 m n b + messages * p n b, ``messages``
    being sum over communicating receivers of t_i."""
    return 2.0 * m * replica_bytes + messages * p * replica_bytes


def read(ctx):
    from bench.reference import pame

    probe = ctx.probe("exchange_alone")
    if probe["seconds_per_round"] is None:
        return None
    dep = pame.deployment(ctx.cell.traffic, ctx.program.m)
    rounds = range(probe["first_round"], probe["first_round"] + probe["rounds"])
    messages = np.mean([dep.t[dep.communicating(k)].sum() for k in rounds])
    moved = least_bytes(probe["replica_bytes"], ctx.program.m, ctx.cell.traffic["p"],
                        float(messages))
    least_s = moved / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / probe["seconds_per_round"]
