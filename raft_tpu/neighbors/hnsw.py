"""HNSW interop — analog of ``raft::neighbors::hnsw``
(``neighbors/hnsw.hpp:62`` ``from_cagra``, serializer
``neighbors/detail/cagra/cagra_serialize.cuh`` ``serialize_to_hnswlib``).

Writes a CAGRA index as a base-layer-only hnswlib file (bit-compatible
with the reference's writer, which hnswlib's ``loadIndex`` accepts with
``max_level=1`` and all points on level 0), and provides a CPU-light
reader + search so round-trips work without the hnswlib package.
"""
from __future__ import annotations

import dataclasses
import struct
from typing import BinaryIO, Tuple, Union

import numpy as np

from raft_tpu import obs
from raft_tpu.core.errors import expects
from raft_tpu.neighbors.cagra import CagraIndex, CagraSearchParams, from_graph, search as cagra_search
from raft_tpu.ops.distance import DistanceType


def serialize_to_hnswlib(index: CagraIndex, stream: BinaryIO) -> None:
    """Write the hnswlib ``HierarchicalNSW`` file layout
    (``cagra_serialize.cuh`` serialize_to_hnswlib — same field order and
    widths: size_t header fields, per-element
    [link_count:int][links:IdxT*deg][data:T*dim][label:size_t], then one
    int 0 per element for the upper link lists)."""
    dataset = np.ascontiguousarray(np.asarray(index.dataset))
    graph = np.ascontiguousarray(np.asarray(index.graph, np.uint32))
    n, dim = dataset.shape
    deg = graph.shape[1]
    itemsize = dataset.dtype.itemsize

    size_data_per_element = deg * 4 + 4 + dim * itemsize + 8
    header = struct.pack(
        "<QQQQQQiiQQQdQ",
        0,  # offset_level_0
        n,  # max_element
        n,  # curr_element_count
        size_data_per_element,
        size_data_per_element - 8,  # label_offset
        deg * 4 + 4,  # offset_data
        1,  # max_level
        n // 2,  # entrypoint_node
        deg // 2,  # max_M
        deg,  # max_M0
        deg // 2,  # M
        0.42424242,  # mult (unused by the loader)
        500,  # efConstruction (unused)
    )
    stream.write(header)

    # vectorized per-element records via a structured dtype
    rec = np.dtype(
        [
            ("cnt", "<i4"),
            ("links", "<u4", (deg,)),
            ("data", dataset.dtype, (dim,)),
            ("label", "<u8"),
        ]
    )
    out = np.empty(n, rec)
    out["cnt"] = deg
    # -1 pads are not representable in hnswlib links; point them at self
    links = graph.astype(np.int64)
    rows = np.arange(n, dtype=np.int64)[:, None]
    links = np.where(np.asarray(index.graph) < 0, rows, links)
    out["links"] = links.astype(np.uint32)
    out["data"] = dataset
    out["label"] = np.arange(n, dtype=np.uint64)
    stream.write(out.tobytes())
    stream.write(np.zeros(n, "<i4").tobytes())


@dataclasses.dataclass
class HnswIndex:
    """Loaded base-layer hnsw graph (``hnsw::index`` analog,
    ``neighbors/detail/hnsw_types.hpp``)."""

    dataset: np.ndarray
    graph: np.ndarray
    entrypoint: int
    metric: DistanceType

    @property
    def dim(self) -> int:
        return self.dataset.shape[1]

    def to_cagra(self) -> CagraIndex:
        return from_graph(self.dataset, self.graph, self.metric)


def from_cagra(index: CagraIndex) -> HnswIndex:
    """``hnsw::from_cagra`` (``neighbors/hnsw.hpp:62``): view the CAGRA
    graph as a base-layer hnsw index."""
    return HnswIndex(
        dataset=np.asarray(index.dataset),
        graph=np.asarray(index.graph),
        entrypoint=index.size // 2,
        metric=index.metric,
    )


def load_hnswlib(stream: BinaryIO, dtype=np.float32, metric=DistanceType.L2Expanded) -> HnswIndex:
    """Parse an hnswlib file written by :func:`serialize_to_hnswlib`
    (reader counterpart of ``hnsw_types.hpp``'s hnswlib loadIndex)."""
    head = stream.read(8 * 6)
    _, n, count, size_per, label_off, offset_data = struct.unpack("<QQQQQQ", head)
    max_level, entry = struct.unpack("<ii", stream.read(8))
    _max_m, max_m0, _m = struct.unpack("<QQQ", stream.read(24))
    _mult, _efc = struct.unpack("<dQ", stream.read(16))
    expects(max_level == 1, "only base-layer-only files supported")
    deg = (offset_data - 4) // 4
    itemsize = np.dtype(dtype).itemsize
    dim = (label_off - offset_data) // itemsize
    rec = np.dtype(
        [
            ("cnt", "<i4"),
            ("links", "<u4", (deg,)),
            ("data", np.dtype(dtype).newbyteorder("<"), (dim,)),
            ("label", "<u8"),
        ]
    )
    expects(rec.itemsize == size_per, "record size mismatch: corrupt file?")
    raw = np.frombuffer(stream.read(size_per * count), rec, count=count)
    # order rows by label (our writer emits them in order already)
    order = np.argsort(raw["label"])
    graph = raw["links"][order].astype(np.int32)
    data = np.ascontiguousarray(raw["data"][order])
    return HnswIndex(dataset=data, graph=graph, entrypoint=int(entry), metric=metric)


def search(
    index: HnswIndex, queries, k: int, ef: int = 64
) -> Tuple[np.ndarray, np.ndarray]:
    """Base-layer greedy search (the reference delegates to hnswlib's CPU
    searchKnn; here the same graph runs through the batched beam search —
    ``ef`` maps to ``itopk_size``).

    With :mod:`raft_tpu.obs` enabled the call is wrapped in an
    ``hnsw.search`` span (the nested ``cagra.search`` span shows the
    delegated traversal) with call/query counters."""
    if not obs.active():
        v, i = cagra_search(
            index.to_cagra(), queries, k, CagraSearchParams(itopk_size=max(ef, k))
        )
        return np.asarray(v), np.asarray(i)
    nq = int(np.shape(queries)[0]) if np.ndim(queries) == 2 else 1
    obs.inc("hnsw.search.calls", ef=str(ef))
    obs.inc("hnsw.search.queries", float(nq))
    with obs.span("hnsw.search", k=k, nq=nq, ef=ef) as sp:
        v, i = cagra_search(
            index.to_cagra(), queries, k, CagraSearchParams(itopk_size=max(ef, k))
        )
        sp.sync((v, i))
    return np.asarray(v), np.asarray(i)
