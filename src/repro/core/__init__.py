# The paper's primary contribution: distributed unconstrained local search
# (Jet) + probabilistic rebalancing inside a multilevel graph partitioner.
from repro.core.config import UNSET, PartitionConfig, resolve_config  # noqa: F401
from repro.core.graph import PAD, Graph, from_coo, pad_graph, to_padded, to_padded_fast  # noqa: F401
from repro.core.jet import jet_round  # noqa: F401
from repro.core.multilevel import PartitionResult, partition, partition_batch  # noqa: F401
from repro.core.partition import (  # noqa: F401
    best_moves,
    block_weights,
    conn_dense,
    edge_cut,
    exact_edge_cut,
    imbalance,
    l_max,
    total_overload,
)
from repro.core.rebalance import greedy_epoch, probabilistic_pass, rebalance  # noqa: F401
from repro.core.refine import jet_refine, lp_refine_balanced, temperature_schedule  # noqa: F401
