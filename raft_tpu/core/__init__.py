"""Core runtime layer (L1 analog): resources, errors, logging,
serialization, bitsets, interruptible cancellation, array ingestion.

See ``SURVEY.md`` §2.1 for the reference component map
(``/root/reference/cpp/include/raft/core``).
"""
from raft_tpu.core.array import as_array, check_dtype_one_of, check_matching_dims
from raft_tpu.core.bitset import Bitmap, Bitset, popcount32
from raft_tpu.core.errors import LogicError, RaftError, expects, fail
from raft_tpu.core.resources import Resources, default_resources, ensure_resources
from raft_tpu.core import interruptible, logging, serialize

__all__ = [
    "as_array",
    "check_dtype_one_of",
    "check_matching_dims",
    "Bitmap",
    "Bitset",
    "popcount32",
    "LogicError",
    "RaftError",
    "expects",
    "fail",
    "Resources",
    "default_resources",
    "ensure_resources",
    "interruptible",
    "logging",
    "serialize",
]
