"""Distributed layer (L3 analog): comms verb set over mesh collectives +
in-tree sharded search.

See ``SURVEY.md`` §2.5 (``/root/reference/cpp/include/raft/{core/comms.hpp,comms}``).
"""
from raft_tpu.parallel.comms import (
    DEFAULT_AXIS,
    allgather,
    allreduce,
    barrier,
    bcast,
    comm_rank,
    comm_size,
    comm_split,
    init_comms,
    make_mesh,
    ppermute,
    reduce,
    reducescatter,
    replicated,
    row_sharded,
)
from raft_tpu.parallel.sharded_ann import (
    sharded_cagra_search,
    sharded_ivf_flat_search,
    sharded_ivf_pq_search,
)
from raft_tpu.parallel.sharded_knn import sharded_knn

__all__ = [
    "sharded_cagra_search",
    "sharded_ivf_flat_search",
    "sharded_ivf_pq_search",
    "DEFAULT_AXIS",
    "allgather",
    "allreduce",
    "barrier",
    "bcast",
    "comm_rank",
    "comm_size",
    "comm_split",
    "init_comms",
    "make_mesh",
    "ppermute",
    "reduce",
    "reducescatter",
    "replicated",
    "row_sharded",
    "sharded_knn",
]
