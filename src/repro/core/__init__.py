"""Core of the reproduction: the PaME algorithm and its substrate.

  topology     — communication graphs, doubly-stochastic mixing matrices
  mixing       — gossip operators: dense einsum vs padded neighbor exchange
  pme          — Partial Message Exchange (Algorithm 2)
  pame         — the PaME step (Algorithm 1)
  baselines    — D-PSGD / DFedSAM / CHOCO-SGD / BEER / (AN)Q-NIDS
  algorithms   — unified registry binding all of the above to one contract
  scenarios    — dynamic networks: per-step link churn, dropout, stragglers
  temporal     — Markov link/node processes + bounded-staleness gossip
  compression  — rand-k / top-k / QSGD / one-bit operators
"""
from repro.core.topology import Topology, build_topology  # noqa: F401
from repro.core.mixing import (  # noqa: F401
    Mixer,
    gather_terms,
    make_mixer,
    mix_padded,
)
from repro.core.engine import run_batched  # noqa: F401
from repro.core.pme import (  # noqa: F401
    pme_average,
    pme_average_pytree,
    naive_average,
    sample_coordinate_masks,
    sample_neighbor_selection,
    message_bits,
)
from repro.core.pame import (  # noqa: F401
    PaMEConfig,
    PaMEState,
    pame_init,
    pame_step,
    run_pame,
    make_topology_arrays,
)
from repro.core.algorithms import (  # noqa: F401
    Algorithm,
    BatchedAlgorithm,
    BoundAlgorithm,
    get_algorithm,
    lane_finals,
    list_algorithms,
    register,
)
from repro.core.scenarios import (  # noqa: F401
    Scenario,
    get_scenario,
    list_scenarios,
    make_scenario_arrays,
    realize,
)
from repro.core.temporal import (  # noqa: F401
    TemporalScenario,
    get_temporal_scenario,
    list_temporal_scenarios,
)
