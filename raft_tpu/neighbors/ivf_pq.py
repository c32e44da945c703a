"""IVF-PQ index — analog of ``raft::neighbors::ivf_pq``.

Reference: params/index ``neighbors/ivf_pq_types.hpp:47-139,293``, build
``neighbors/detail/ivf_pq_build.cuh:1681`` (rotation ``:122``, residual
transforms ``:162-230``, codebook training ``train_per_subset`` /
``train_per_cluster``), search ``neighbors/detail/ivf_pq_search.cuh:588``
(coarse ``select_clusters:67``, LUT scan worker ``ivfpq_search_worker:252``,
similarity kernel ``detail/ivf_pq_compute_similarity-inl.cuh``).

TPU-first redesign:

* **Codebook training is a batched (vmapped) Lloyd**: all ``pq_dim``
  subspace codebooks share shapes, so one ``vmap`` trains them
  simultaneously as a single stack of MXU matmuls — replacing the
  reference's sequential per-subspace kernel loop (``train_per_subset``).
* **Codes are stored one byte per sub-quantizer** in a dense padded
  ``[n_lists, max_list, pq_dim]`` uint8 tensor (+ parallel id tensor), not
  the reference's bit-packed interleaved groups
  (``ivf_pq_types.hpp: list_data`` 16-byte chunk layout): TPU vector memory
  wants byte-aligned lanes, and XLA can tile a dense uint8 tensor directly.
  ``pq_bits < 8`` therefore saves codebook space but not code storage
  (documented trade-off).
* **Search LUT is built per (query, probe) with one einsum** and applied
  with a lane-wise gather; probes are processed by a ``lax.scan`` carrying a
  running top-k (same structure as IVF-Flat), instead of the CUDA
  shared-memory LUT kernel.
* fp8 LUTs (``detail/ivf_pq_fp_8bit.cuh``) are replaced by an optional
  bf16 LUT mode — the TPU-native reduced-precision path.
* **Fused Pallas search** (``mode="fused"``, round 4): scalar-prefetch DMA
  of only the probed code blocks + an in-kernel multi-hot-matmul LUT
  apply — the work-proportional fast path mirroring the reference's
  ``compute_similarity`` kernel. See :mod:`raft_tpu.ops.pallas.pq_scan`.
  Every ``per_subspace`` width is eligible: ``ksub <= 64`` decodes in a
  single multi-hot pass; ``ksub = 128/256`` (including the DEFAULT
  ``pq_bits=8`` kmeans config) via **column-chunked decode** (round 5) —
  the work-proportional answer to the LUT-cost problem the reference
  solves with fp8 LUTs. ``pq_kind="nibble"`` remains the cheap 8-bit
  point: **additive nibble codebooks** — each subspace quantized by the
  SUM of two 16-entry codebooks (A[hi] + B[lo], one byte per code) — 256
  effective centers at 32-column LUT cost (2-level residual quantization
  instead of low-precision table entries).
* ``pq_bits < 8`` codes are **bit-packed** whenever the row bitstream is
  byte-aligned: two per byte for 4-bit, spanning little-endian layouts
  for 5/6/7 (``ivf_pq_types.hpp:129-164`` /
  ``detail/ivf_pq_codepacking.cuh`` analog — plain contiguous bytes, not
  16-byte interleave: TPU DMA wants flat rows), cutting code storage and
  scan DMA to ``pq_bits/8`` of a byte per code.
* ``pq_kind="rabitq"`` (round 7): **RaBitQ binary quantization** ("GPU-
  Native Approximate Nearest Neighbor Search with IVF-RaBitQ",
  PAPERS.md) — each list residual is reduced to its D sign bits under a
  FORCED random rotation plus two per-vector f32 corrections, and scored
  with the unbiased bitwise estimator
  ``est = ||q-c||^2 + ||r||^2 - g*(b.q_rot - Σq_rot/2) + const(b, c)``
  where ``g = 4||r|| / (sqrt(D) * <o, x̄>)`` folds the estimator's
  normalization. One bit per dimension (16 bytes/row at d=128 — the same
  DMA footprint as the nibble config) but the scan's per-row decode is a
  single D-wide sign matmul instead of a ``pq_dim * ksub``-column
  multi-hot decode: ~4x cheaper per scanned row at equal bits. The
  center-dependent part of the estimator is folded into the per-slot
  constant channel (``rot_sqnorms`` stores it; ``corrections`` stores
  ``g``) so the fused kernel's bit matmul is query-only. Rescoring runs
  through the same integrated ``refine`` re-rank; see
  :mod:`raft_tpu.ops.pallas.rabitq_scan` for the fused Pallas path.

Supported metrics: L2Expanded, L2SqrtExpanded, InnerProduct.
"""
from __future__ import annotations

import dataclasses
import functools
import io
import warnings
from typing import BinaryIO, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from raft_tpu import obs
from raft_tpu.cluster import kmeans_balanced
from raft_tpu.cluster.kmeans_balanced import BalancedKMeansParams
from raft_tpu.core import serialize as ser
from raft_tpu.core.bitset import Bitset
from raft_tpu.core.errors import expects
from raft_tpu.core.logging import logger
from raft_tpu.core.resources import Resources, ensure_resources
from raft_tpu.neighbors import ivf_common
from raft_tpu.ops.distance import DistanceType, resolve_metric
from raft_tpu.ops.fused_1nn import min_cluster_and_distance
from raft_tpu.ops.select_k import running_merge, select_k, worst_value
from raft_tpu.random.rng import as_key
from raft_tpu.robust import fallback as _fallback, faults as _faults
from raft_tpu.utils.math import round_up

_SUPPORTED = (
    DistanceType.L2Expanded,
    DistanceType.L2SqrtExpanded,
    DistanceType.InnerProduct,
)

PER_SUBSPACE = "per_subspace"
PER_CLUSTER = "per_cluster"

#: Trainset rows a per-subspace codebook entry is trained on (the
#: reference's ``max_train_points_per_pq_code`` default): books see a
#: seeded subsample of at most this x 2^pq_bits rows. The residuals are
#: held ``[rows, pq_dim, pq_len]``, whose short minor axis pads to a
#: 128-lane tile on a TPU: DEEP-10M's whole 999,000-row trainset would
#: need 32 GB there.
MAX_TRAIN_POINTS_PER_PQ_CODE = 256


def _default_pq_dim(dim: int) -> int:
    """Reference heuristic (``ivf_pq_types.hpp:588-601 calculate_pq_dim``):
    halve large dims, round down to a multiple of 32, else nearest pow2."""
    d = dim // 2 if dim >= 128 else dim
    r = (d // 32) * 32
    if r > 0:
        return r
    r = 1
    while r * 2 <= d:
        r *= 2
    return r


@dataclasses.dataclass
class IvfPqIndexParams:
    """``ivf_pq::index_params`` analog (``neighbors/ivf_pq_types.hpp:47``)."""

    n_lists: int = 1024
    metric: DistanceType = DistanceType.L2Expanded
    kmeans_n_iters: int = 20
    kmeans_trainset_fraction: float = 0.5
    pq_bits: int = 8
    pq_dim: int = 0  # 0 = auto (calculate_pq_dim)
    codebook_kind: str = PER_SUBSPACE
    force_random_rotation: bool = False
    seed: int = 0
    # Dense-layout list capacity cap (see ivf_common.assign_slots).
    # Default OFF for PQ: a spilled row's residual is taken against its
    # second-nearest center, which measurably degrades code quality —
    # unlike IVF-Flat, where spill only affects which probe finds the row.
    list_cap_factor: float = 0.0
    # "kmeans" = one 2^pq_bits-center codebook per subspace (reference
    # semantics). "nibble" = additive nibble pairs (requires pq_bits=8,
    # per_subspace): subspace j is quantized by A[j][hi] + B[j][lo] — 256
    # effective centers whose fused-scan LUT costs only 32 columns.
    # "rabitq" = 1-bit RaBitQ sign codes with per-vector correction
    # factors (pq_bits is forced to 1; pq_dim/codebook knobs are ignored;
    # the rotation is always random — the estimator's guarantees need it).
    # "auto" (default) = "rabitq" when pq_bits=1 is requested, else
    # "nibble" whenever representable (pq_bits=8 + per_subspace — i.e.
    # the out-of-box config), else "kmeans": the nibble+refine operating
    # point was the measured Pareto frontier (BENCH_r05: 15.7k QPS
    # @ 0.947 vs 4.6k @ 0.56 for kmeans-256); rabitq+refine beats it at
    # equal code bytes (BENCH_r06).
    pq_kind: str = "auto"


@dataclasses.dataclass
class IvfPqSearchParams:
    """``ivf_pq::search_params`` analog (``ivf_pq_types.hpp:120``).

    The ``fused_*`` knobs tune the Pallas fused scan (``mode="fused"``);
    they mirror :class:`raft_tpu.neighbors.ivf_flat.IvfFlatSearchParams`.

    The defaults sit on the measured Pareto frontier (BENCH_r05: nibble
    codes, ``n_probes=30``, 8x exact refine → ~15.7k QPS @ 0.947 on
    1M x 128): pass the raw ``dataset`` to :func:`search` and the default
    ``refine_ratio`` re-ranks ``k * refine_ratio`` PQ candidates with
    exact distances."""

    n_probes: int = 30
    # Exact re-rank depth: search keeps k * refine_ratio PQ candidates and
    # re-scores them against the raw dataset (refine.refine) when search()
    # is given ``dataset=``; without a dataset this knob is inert. 1 = off.
    refine_ratio: int = 8
    # LUT precision (the reference's ``lut_dtype``, ivf_pq_types.hpp:120).
    # None = auto: float32 on the scan/probe paths, bf16 on the fused
    # Pallas path (whose LUT matmul is MXU-bf16 by construction).
    # Explicitly requesting float32 makes ``mode="auto"`` route to the
    # scan path, which honors it; ``mode="fused"`` always computes the
    # LUT in bf16 regardless.
    lut_dtype: Optional[jnp.dtype] = None
    # rows of a fused kernel tile: queries for RaBitQ, (query, probe)
    # pairs for PQ
    fused_qt: int = 128
    # RaBitQ only: a query tile's probe table holds
    # fused_probe_factor * n_probes lists. The PQ fused path ignores it:
    # it scores every (query, probe) pair, so no probe can be dropped.
    fused_probe_factor: int = 32
    fused_group: int = 8
    fused_merge: str = "bank8"
    # RaBitQ only: bank merges every this many units of a tile's table
    # (0 = once at the end). The PQ fused path ignores it: a pair row
    # sees one list, so its banks merge once.
    fused_extract_every: int = 0
    # max multi-hot columns materialized per decode chunk (VMEM bound for
    # wide codebooks: K = pq_dim * ksub columns total); 0 = single pass.
    # Always further capped by a VMEM model of the kernel
    # (pq_scan.vmem_decode_cols) so long lists cannot blow its
    # scoped-VMEM stack.
    fused_decode_cols: int = 2048


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class IvfPqIndex:
    """Product-quantized inverted-file index (``ivf_pq_types.hpp:293``)."""

    centers: jax.Array  # [n_lists, d] f32 raw coarse centers
    centers_rot: jax.Array  # [n_lists, rot_dim] f32 rotated centers
    rotation: jax.Array  # [rot_dim, d] f32 orthonormal transform
    pq_centers: jax.Array  # per_subspace: [pq_dim, ksub, pq_len]
    #                         per_cluster:  [n_lists, ksub, pq_len]
    #   For additive nibble codebooks, the MATERIALIZED 256-entry sum grid
    #   pq_centers[j, hi*16+lo] = A[j, hi] + B[j, lo] — every XLA path
    #   (scan/probe/sqnorms/encode) works on it unchanged; the fused
    #   kernel re-derives A/B via nibble_books().
    codes: jax.Array  # [n_lists, max_list, pq_dim] uint8 (pq_dim/2 when packed)
    list_indices: jax.Array  # [n_lists, max_list] i32, -1 = empty
    list_sizes: jax.Array  # [n_lists] i32
    rot_sqnorms: jax.Array  # [n_lists, max_list] f32 ||c_rot + resid||^2
    #   rabitq: the per-slot additive constant of the distance estimator
    #   (center-dependent terms folded at build time; see _rabitq docs).
    metric: DistanceType
    codebook_kind: str
    pq_bits: int
    size: int
    list_cap_factor: float = 0.0  # build-time cap; honored by extend()
    additive: bool = False  # nibble-pair codebooks (pq_kind="nibble")
    packed: bool = False  # 4-bit codes packed two per byte
    center_rank: Optional[jax.Array] = None  # [n_lists] spatial rank (v3+)
    rabitq: bool = False  # 1-bit sign codes + corrections (pq_kind="rabitq")
    corrections: Optional[jax.Array] = None  # [n_lists, max_list] f32 rabitq g
    # inner product only: the one norm every indexed row has, 0.0 when
    # they differ (v5+); it picks the coarse ranking
    # (ivf_common.coarse_from_dots)
    row_norm: float = 0.0

    def tree_flatten(self):
        return (
            (
                self.centers,
                self.centers_rot,
                self.rotation,
                self.pq_centers,
                self.codes,
                self.list_indices,
                self.list_sizes,
                self.rot_sqnorms,
                self.center_rank,
                self.corrections,
            ),
            (
                self.metric, self.codebook_kind, self.pq_bits, self.size,
                self.list_cap_factor, self.additive, self.packed, self.rabitq,
                self.row_norm,
            ),
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(
            *children[:8],
            metric=aux[0],
            codebook_kind=aux[1],
            pq_bits=aux[2],
            size=aux[3],
            list_cap_factor=aux[4],
            additive=aux[5],
            packed=aux[6],
            center_rank=children[8],
            rabitq=aux[7],
            corrections=children[9],
            row_norm=aux[8],
        )

    @property
    def n_lists(self) -> int:
        return self.centers.shape[0]

    @property
    def dim(self) -> int:
        return self.centers.shape[1]

    @property
    def rot_dim(self) -> int:
        return self.rotation.shape[0]

    @property
    def pq_dim(self) -> int:
        return self.codes.shape[2] * 8 // self.pq_bits if self.packed else self.codes.shape[2]

    @property
    def pq_len(self) -> int:
        return self.pq_centers.shape[-1]

    @property
    def ksub(self) -> int:
        return self.pq_centers.shape[-2]

    @property
    def max_list(self) -> int:
        return self.codes.shape[1]

    def codes_unpacked(self) -> jax.Array:
        """[n_lists, max_list, pq_dim] u8 view for the XLA decode paths."""
        if not self.packed:
            return self.codes
        return unpack_codes_bits(self.codes, self.pq_bits, self.pq_dim)


# ---------------------------------------------------------------------------
# build helpers
# ---------------------------------------------------------------------------


def _make_rotation(key, rot_dim: int, dim: int, force: bool) -> jax.Array:
    """Orthonormal [rot_dim, dim] transform (``make_rotation_matrix``,
    ``ivf_pq_build.cuh:122``): identity when square and not forced, else the
    Q factor of a Gaussian matrix (rows are orthonormal)."""
    if not force and rot_dim == dim:
        return jnp.eye(dim, dtype=jnp.float32)
    n = max(rot_dim, dim)
    g = jax.random.normal(key, (n, n), jnp.float32)
    q, _ = jnp.linalg.qr(g)
    return q[:rot_dim, :dim]


def _nearest_sub(x, c):
    """Nearest sub-center ``c [..., k, l]`` (the leading axes broadcast)
    of each sub-vector ``x [..., l]``: ``[...]`` indices, by exact
    elementwise f32 distances. The norm expansion's dot contracts only
    ``l`` (2-16) elements, and on a v5e neither of its precisions is
    exact: at the default one it encoded 1.4% of a 1M-row set off a
    float64 encode, at ``HIGHEST`` 87% (PERF.md, PR 21)."""
    diff = x[..., None, :] - c
    return jnp.argmin(jnp.sum(diff * diff, axis=-1), axis=-1)


@functools.partial(jax.jit, static_argnames=("k", "n_iters"))
def _batched_lloyd(X, mask, init, *, k: int, n_iters: int):
    """Vmapped masked Lloyd: ``X [B, n, d]``, 0/1 ``mask [B, n]``,
    ``init [B, k, d]`` → centers ``[B, k, d]``.

    The batched replacement for the reference's per-subspace /
    per-cluster sequential codebook loops (``train_per_subset``,
    ``train_per_cluster``, ``ivf_pq_build.cuh``): every subspace trains in
    the same stack of ops.
    """

    def one(Xb, mb, cb):
        def body(_, centers):
            labels = _nearest_sub(Xb, centers)
            w = mb
            sums = jax.ops.segment_sum(Xb * w[:, None], labels, num_segments=k)
            counts = jax.ops.segment_sum(w, labels, num_segments=k)
            means = sums / jnp.maximum(counts[:, None], 1e-9)
            return jnp.where(counts[:, None] > 0, means, centers)

        return lax.fori_loop(0, n_iters, body, cb)

    return jax.vmap(one)(X, mask, init)


@functools.partial(jax.jit, static_argnames=("per_cluster",))
def _encode_chunk(resid_rot, labels, pq_centers, *, per_cluster: bool):
    """Encode rotated residuals ``[c, pq_dim, pq_len]`` to uint8 codes
    (``process_and_fill_codes`` analog): nearest sub-center per subspace,
    by exact distances (:func:`_nearest_sub`)."""
    # per-cluster books [c, 1, ksub, pq_len]; per-subspace [pq_dim, ksub, pq_len]
    pqc = pq_centers[labels][:, None] if per_cluster else pq_centers
    return _nearest_sub(resid_rot, pqc).astype(jnp.uint8)


def _rotated_residuals(X, labels, centers, rotation, pq_dim: int):
    """R @ (x - c[label]) reshaped to [n, pq_dim, pq_len]."""
    resid = X - centers[labels]
    rr = jnp.dot(resid, rotation.T, precision=lax.Precision.HIGHEST)  # [n, rot_dim]
    return rr.reshape(X.shape[0], pq_dim, -1)


def pack_codes(codes) -> jax.Array:
    """Pack 4-bit codes pairwise: byte b = code[2b] | (code[2b+1] << 4).
    (``detail/ivf_pq_codepacking.cuh`` analog; contiguous pairs instead of
    the reference's 16-byte interleave — TPU DMA wants plain bytes.)"""
    lo = codes[..., 0::2]
    hi = codes[..., 1::2]
    return (lo | (hi << 4)).astype(jnp.uint8)


def unpack_codes(packed) -> jax.Array:
    """Inverse of :func:`pack_codes`: [..., bpr] u8 -> [..., 2*bpr] u8."""
    lo = packed & jnp.uint8(15)
    hi = packed >> 4
    return jnp.stack([lo, hi], axis=-1).reshape(*packed.shape[:-1], -1)


def pack_codes_bits(codes, bits: int) -> jax.Array:
    """Bit-pack ``bits``-wide codes as a little-endian bitstream per row:
    code j occupies global bits ``[j*bits, (j+1)*bits)``, bit t of byte s
    is global bit ``s*8 + t``. Requires ``pq_dim * bits % 8 == 0`` (the
    row bitstream is byte-aligned, so codes never span rows). For
    ``bits=4`` this is exactly :func:`pack_codes`'s pairwise layout.
    Spanning-width analog of the reference's per-width chunk packing
    (``ivf_pq_types.hpp:129-164``, ``detail/ivf_pq_codepacking.cuh``)."""
    if bits == 4:
        return pack_codes(codes)
    pq_dim = codes.shape[-1]
    expects(pq_dim * bits % 8 == 0, "pq_dim*bits must be byte-aligned to pack")
    bpr = pq_dim * bits // 8
    c = codes.astype(jnp.uint32)
    bit = (c[..., None] >> jnp.arange(bits, dtype=jnp.uint32)) & 1
    by = bit.reshape(*codes.shape[:-1], bpr, 8)
    w = jnp.uint32(1) << jnp.arange(8, dtype=jnp.uint32)
    return jnp.sum(by * w, axis=-1).astype(jnp.uint8)


def unpack_codes_bits(packed, bits: int, pq_dim: int) -> jax.Array:
    """Inverse of :func:`pack_codes_bits`."""
    if bits == 4:
        return unpack_codes(packed)
    p = packed.astype(jnp.uint32)
    bit = (p[..., None] >> jnp.arange(8, dtype=jnp.uint32)) & 1
    co = bit.reshape(*packed.shape[:-1], pq_dim, bits)
    w = jnp.uint32(1) << jnp.arange(bits, dtype=jnp.uint32)
    return jnp.sum(co * w, axis=-1).astype(jnp.uint8)


def nibble_books(pq_centers) -> jax.Array:
    """Derive the fused-scan nibble codebooks [pq_dim, 32, pq_len] from the
    materialized additive grid ``pq_centers[j, hi*16+lo] = A[hi] + B[lo]``:
    A'[hi] = grid[hi*16], B'[lo] = grid[lo] - grid[0] reproduces every sum
    exactly (A' absorbs B[0])."""
    pq_dim, ksub, pq_len = pq_centers.shape
    a = pq_centers[:, 0::16, :]  # [pq_dim, 16, pq_len] = A + B[0]
    b = pq_centers[:, 0:16, :] - pq_centers[:, 0:1, :]  # B - B[0]
    return jnp.concatenate([a, b], axis=1)  # hi-half then lo-half


def _seed_books(key, Xs, k: int):
    """Initial ``[b, k, l]`` codebooks for the ``b`` stacked subspaces of
    ``Xs [b, nt, l]``: greedy k-means++ per subspace (a random pick of
    seeds left the books at the mercy of the random stream); a trainset
    smaller than ``k`` tiles its rows."""
    nt = Xs.shape[1]
    if nt < k:
        return jnp.tile(Xs, (1, -(-k // nt), 1))[:, :k, :]
    from raft_tpu.cluster.kmeans import kmeans_plus_plus

    return jax.vmap(lambda kk, x: kmeans_plus_plus(kk, x, k))(
        jax.random.split(key, Xs.shape[0]), Xs)


def _train_nibble_books(t_resid, key, n_iters: int):
    """Additive nibble codebooks: A = 16-center Lloyd on the residuals,
    B = 16-center Lloyd on the second-level residuals, then alternating
    joint re-encode / re-fit. Returns the materialized 256-entry sum grid
    [pq_dim, 256, pq_len] (every non-fused path consumes that directly).

    A 2-level per-subspace residual quantizer: same decode cost as
    pq_bits=4 but 256 effective centers — the accuracy/FLOP point the
    reference reaches with fp8 LUTs (``detail/ivf_pq_fp_8bit.cuh``)."""
    pq_dim = t_resid.shape[1]
    nt = t_resid.shape[0]
    Xs = jnp.transpose(t_resid, (1, 0, 2))  # [pq_dim, nt, pq_len]
    mask = jnp.ones((pq_dim, nt), jnp.float32)
    ka, kb = jax.random.split(key)
    A = _batched_lloyd(Xs, mask, _seed_books(ka, Xs, 16), k=16, n_iters=n_iters)

    def assign(X, books):  # [pq_dim, nt, pq_len] x [pq_dim, 16, pq_len]
        return _nearest_sub(X, books[:, None])  # [pq_dim, nt]

    hi = assign(Xs, A)
    R2 = Xs - jnp.take_along_axis(A, hi[:, :, None], axis=1)
    B = _batched_lloyd(R2, mask, _seed_books(kb, R2, 16), k=16, n_iters=n_iters)

    def refit(X, labels, k):
        def one(Xb, lb):
            sums = jax.ops.segment_sum(Xb, lb, num_segments=k)
            counts = jax.ops.segment_sum(jnp.ones_like(lb, jnp.float32), lb, num_segments=k)
            return sums / jnp.maximum(counts[:, None], 1e-9), counts

        return jax.vmap(one)(X, labels)

    for _ in range(2):  # coordinate descent on (A, B)
        lo = assign(Xs - jnp.take_along_axis(A, hi[:, :, None], axis=1), B)
        Anew, ca = refit(Xs - jnp.take_along_axis(B, lo[:, :, None], axis=1), hi, 16)
        A = jnp.where(ca[:, :, None] > 0, Anew, A)
        hi = assign(Xs - jnp.take_along_axis(B, lo[:, :, None], axis=1), A)
        Bnew, cb = refit(Xs - jnp.take_along_axis(A, hi[:, :, None], axis=1), lo, 16)
        B = jnp.where(cb[:, :, None] > 0, Bnew, B)

    # materialize the sum grid: grid[j, hi*16+lo] = A[j,hi] + B[j,lo]
    grid = A[:, :, None, :] + B[:, None, :, :]  # [pq_dim, 16, 16, pq_len]
    return grid.reshape(pq_dim, 256, -1)


@functools.partial(jax.jit, static_argnames=("per_cluster", "chunk_lists"))
def _decoded_sqnorms(codes, centers_rot, pq_centers, *, per_cluster: bool, chunk_lists: int):
    """Precompute ||c_rot[l] + decode(code)||^2 per slot [n_lists, max_list]
    — the constant term of the scan path's score epilogue (decoded once at
    build instead of on every search batch)."""
    n_lists, M, pq_dim = codes.shape
    ksub = pq_centers.shape[-2]
    rot_dim = centers_rot.shape[1]
    G = chunk_lists
    n_chunks = n_lists // G
    # f32 one-hot decode: build-time one-off, and the CPU backend has no
    # bf16 dot support
    books = pq_centers.astype(jnp.float32)

    def body(_, inp):
        cod, crot, bks = inp
        if per_cluster:
            onehot = (
                cod[:, :, :, None].astype(jnp.int32)
                == jnp.arange(ksub, dtype=jnp.int32)[None, None, None, :]
            ).astype(jnp.float32)
            resid = jnp.einsum(
                "gmjc,gcs->gmjs", onehot, bks, preferred_element_type=jnp.float32
            )
        else:
            onehot = (
                cod.reshape(G * M, pq_dim)[:, :, None].astype(jnp.int32)
                == jnp.arange(ksub, dtype=jnp.int32)[None, None, :]
            ).astype(jnp.float32)
            resid = jnp.einsum(
                "tjc,jcs->tjs", onehot, books, preferred_element_type=jnp.float32
            )
        dec = resid.reshape(G, M, rot_dim) + crot[:, None, :]
        return None, jnp.sum(dec * dec, axis=-1)

    crot_c = centers_rot.reshape(n_chunks, G, rot_dim)
    bks_c = (
        pq_centers.astype(jnp.float32).reshape(n_chunks, G, ksub, -1)
        if per_cluster
        else jnp.zeros((n_chunks, 1), jnp.float32)
    )
    _, sqn = lax.scan(body, None, (codes.reshape(n_chunks, G, M, pq_dim), crot_c, bks_c))
    return sqn.reshape(n_lists, M)


def _sqnorms_for(codes, centers_rot, pq_centers, per_cluster: bool):
    g = max(1, 262144 // max(codes.shape[1], 1))
    while codes.shape[0] % g:
        g -= 1
    return _decoded_sqnorms(
        codes, centers_rot, pq_centers, per_cluster=per_cluster, chunk_lists=g
    )


def _encode_all(ds_f32, labels, centers, rotation, pq_centers, pq_dim, per_cluster, chunk=16384):
    """Encode every row against its (final) list's center — fully on
    device, chunked so the [chunk, pq_dim, ksub] temporaries stay bounded."""
    outs = []
    n = ds_f32.shape[0]
    for s in range(0, n, chunk):
        lab = labels[s : s + chunk]
        rr = _rotated_residuals(ds_f32[s : s + chunk], lab, centers, rotation, pq_dim)
        outs.append(_encode_chunk(rr, lab, pq_centers, per_cluster=per_cluster))
    if not outs:
        return jnp.zeros((0, pq_dim), jnp.uint8)
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)


@functools.partial(jax.jit, static_argnames=("metric",))
def _rabitq_encode_chunk(X, labels, centers, rotation, centers_rot, *, metric):
    """RaBitQ-encode a chunk of rows against their lists' centers.

    Per row with rotated residual ``r = R(x - c_l)`` (``R`` orthonormal,
    ``D = rot_dim``), the stored code is the D sign bits ``b = [r > 0]``
    (the quantized direction is ``x̄ = (2b-1)/sqrt(D)``, a unit vector) and
    the RaBitQ estimator of ``<r/||r||, u>`` for any query-side ``u`` is
    ``<x̄, u> / <x̄, o>`` with ``o = r/||r||``. Expanding ``<x̄, u> =
    (2 b·u - Σu)/sqrt(D)`` and folding every center-dependent term at
    build time gives one per-slot affine form shared by both metrics:

        min-score      = C1 - coef·(q·c_l) - g·(b·q_rot - Σq_rot/2)
        L2   estimate  = ||q||² + min-score          (coef = 2)
        IP   estimate  = -min-score                  (coef = 1)

    with the two per-row scalars stored in the index:

        g_L2 = 4||r|| / (sqrt(D)·<x̄,o>)      g_IP = g_L2 / 2
        C1_L2 = ||c_rot||² + ||r||² + g_L2·(b·c_rot - Σc_rot/2)
        C1_IP = 0

    (``<x̄, o> = ||r||₁ / (sqrt(D)·||r||₂)``, computable from the residual
    alone.) Returns ``(packed_bits [c, D/8] u8, aux [c, 2] f32)`` with
    ``aux = [C1, g]``.
    """
    rr = (X - centers[labels]) @ rotation.T  # [c, D]
    D = rr.shape[1]
    r2 = jnp.sum(rr * rr, axis=1)
    r = jnp.sqrt(r2)
    sd = lax.rsqrt(jnp.float32(D))
    # <x̄, o> = sd * ||r||1 / ||r||2, in [sd, 1]; guard the zero residual.
    ood = sd * jnp.sum(jnp.abs(rr), axis=1) / jnp.maximum(r, 1e-30)
    g = jnp.where(r > 0, 4.0 * r * sd / jnp.maximum(ood, 1e-12), 0.0)
    if metric == DistanceType.InnerProduct:
        g = 0.5 * g
    signs = (rr > 0).astype(jnp.uint8)  # [c, D]
    crot = centers_rot[labels]  # [c, D]
    if metric == DistanceType.InnerProduct:
        # IP decomposes <x,q> = <c,q> + <r, q_rot>: no center term inside
        # the estimator argument, so the additive constant is zero.
        c1 = jnp.zeros_like(g)
    else:
        bdotc = jnp.sum(jnp.where(rr > 0, crot, 0.0), axis=1)
        c1 = jnp.sum(crot * crot, axis=1) + r2 + g * (bdotc - 0.5 * jnp.sum(crot, axis=1))
    return pack_codes_bits(signs, 1), jnp.stack([c1, g], axis=1)


def _rabitq_encode_all(ds_f32, labels, centers, rotation, centers_rot, metric, chunk=65536):
    """Chunked :func:`_rabitq_encode_chunk` over the full dataset."""
    n = ds_f32.shape[0]
    D = rotation.shape[0]
    codes, auxs = [], []
    for s in range(0, n, chunk):
        cod, aux = _rabitq_encode_chunk(
            ds_f32[s : s + chunk], labels[s : s + chunk], centers, rotation, centers_rot,
            metric=metric,
        )
        codes.append(cod)
        auxs.append(aux)
    if not codes:
        return jnp.zeros((0, D // 8), jnp.uint8), jnp.zeros((0, 2), jnp.float32)
    if len(codes) == 1:
        return codes[0], auxs[0]
    return jnp.concatenate(codes, axis=0), jnp.concatenate(auxs, axis=0)


def _assign(ds_f32, centers, n: int, n_lists: int, cap_factor: float):
    """``(slot [n], max_list)``: each row's place in the capped list
    layout, from its 8 nearest centers (``ivf_common``)."""
    with obs.span("ivf_pq.build.assign", n=n, n_lists=n_lists) as sp:
        cand = ivf_common.topk_labels(ds_f32, centers, k=8)
        max_list = ivf_common.choose_max_list(cand[:, 0], n, n_lists, cap_factor)
        slot = sp.sync(ivf_common.assign_slots(cand, n_lists=n_lists, max_list=max_list))
    return slot, max_list


def _train_codebooks(trainset, centers, rotation, params, *, pq_dim: int, pq_len: int,
                     ksub: int, n_lists: int, per_cluster: bool, nibble: bool, k_cb):
    """PQ codebooks trained on the trainset's rotated residuals: nibble
    sum grids, one ``ksub``-center book per subspace, or one per cluster.
    Per-subspace books see at most ``MAX_TRAIN_POINTS_PER_PQ_CODE *
    ksub`` rows of the trainset, a seeded subsample."""
    n_pq = MAX_TRAIN_POINTS_PER_PQ_CODE * ksub
    if not per_cluster and n_pq < trainset.shape[0]:
        rng = np.random.default_rng(params.seed + 1)
        trainset = trainset[jnp.asarray(np.sort(rng.permutation(trainset.shape[0])[:n_pq]))]
    t_labels, _ = min_cluster_and_distance(trainset, centers, metric=DistanceType.L2Expanded)
    t_resid = _rotated_residuals(trainset, t_labels, centers, rotation, pq_dim)  # [nt, pq_dim, pq_len]
    nt = t_resid.shape[0]

    if nibble:
        pq_centers = _train_nibble_books(t_resid, k_cb, params.kmeans_n_iters)
    elif not per_cluster:
        # [pq_dim, nt, pq_len] stacks; one vmapped Lloyd trains all subspaces.
        Xs = jnp.transpose(t_resid, (1, 0, 2))
        mask = jnp.ones((pq_dim, nt), jnp.float32)
        init = _seed_books(k_cb, Xs, ksub)
        pq_centers = _batched_lloyd(Xs, mask, init, k=ksub, n_iters=params.kmeans_n_iters)
    else:
        # Pool each cluster's residual subvectors (all subspaces), pad to a
        # fixed per-cluster budget, and train all clusters in vmapped chunks.
        lab_np = np.asarray(t_labels)
        flat = np.asarray(t_resid).reshape(nt * pq_dim, pq_len)
        row_cluster = np.repeat(lab_np, pq_dim)
        order = np.argsort(row_cluster, kind="stable")
        counts = np.bincount(row_cluster, minlength=n_lists)
        budget = max(ksub, min(int(counts.max()) if n_lists else ksub, 4096))
        n_trunc = int((counts > budget).sum())
        if n_trunc:
            logger.info(
                "ivf_pq per-cluster codebooks: %d/%d clusters exceed the %d-row "
                "training budget; a seeded random subsample of each is used "
                "(lower kmeans_trainset_fraction or raise n_lists to avoid it)",
                n_trunc,
                n_lists,
                budget,
            )
        sub_rng = np.random.default_rng(params.seed + 0x5EED)
        Xc = np.zeros((n_lists, budget, pq_len), np.float32)
        Mc = np.zeros((n_lists, budget), np.float32)
        starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
        for c in range(n_lists):
            cnt = int(counts[c])
            take = min(cnt, budget)
            sel = order[starts[c] : starts[c] + cnt]
            if cnt > budget:
                # unbiased subsample instead of the first rows (which are
                # ordered by training-set position, not representative)
                sel = sel[sub_rng.choice(cnt, size=budget, replace=False)]
            rows = flat[sel]
            Xc[c, :take] = rows
            Mc[c, :take] = 1.0
            if take < ksub and take > 0:  # ensure >= ksub seed rows
                Xc[c, take:ksub] = rows[np.arange(ksub - take) % take]
        init = jnp.asarray(Xc[:, :ksub, :])
        chunk = max(1, 128 // max(1, budget // 1024))
        parts = []
        Xc_j, Mc_j = jnp.asarray(Xc), jnp.asarray(Mc)
        for s in range(0, n_lists, chunk):
            parts.append(
                _batched_lloyd(
                    Xc_j[s : s + chunk],
                    Mc_j[s : s + chunk],
                    init[s : s + chunk],
                    k=ksub,
                    n_iters=params.kmeans_n_iters,
                )
            )
        pq_centers = jnp.concatenate(parts, axis=0)
    return pq_centers


def build(
    dataset,
    params: Optional[IvfPqIndexParams] = None,
    res: Optional[Resources] = None,
    **kwargs,
) -> IvfPqIndex:
    """Train the coarse quantizer + PQ codebooks and encode the dataset
    (``ivf_pq::build``, ``detail/ivf_pq_build.cuh:1681``)."""
    res = ensure_resources(res)
    if params is None:
        params = IvfPqIndexParams(**kwargs)
    metric = resolve_metric(params.metric)
    expects(metric in _SUPPORTED, "IVF-PQ does not support metric %s", metric)
    expects(params.codebook_kind in (PER_SUBSPACE, PER_CLUSTER), "bad codebook_kind")
    expects(
        params.pq_kind in ("auto", "kmeans", "nibble", "rabitq"),
        "pq_kind must be auto|kmeans|nibble|rabitq",
    )
    pq_kind = params.pq_kind
    if pq_kind == "auto":  # default: nibble whenever representable
        from raft_tpu import plan as _plan

        pq_kind = _plan.plan_pq_kind(params.pq_bits, params.codebook_kind == PER_SUBSPACE)
    nibble = pq_kind == "nibble"
    rabitq = pq_kind == "rabitq"
    if rabitq:
        # pq_bits is definitionally 1 (sign bit per rotated dimension);
        # accept the dataclass default (8) or an explicit 1, reject the
        # rest as probable configuration mistakes.
        expects(
            params.pq_bits in (1, 8),
            "pq_kind='rabitq' is 1 bit/dim; pq_bits=%d conflicts", params.pq_bits,
        )
    else:
        expects(3 <= params.pq_bits <= 8, "pq_bits must be in [3, 8], got %d", params.pq_bits)
    if nibble:
        expects(
            params.pq_bits == 8 and params.codebook_kind == PER_SUBSPACE,
            "pq_kind='nibble' requires pq_bits=8 and per_subspace codebooks",
        )
    dataset = jnp.asarray(dataset)
    expects(dataset.ndim == 2, "dataset must be [n_rows, dim]")
    n, d = dataset.shape
    n_lists = min(params.n_lists, n)
    if rabitq:
        # one sign bit per rotated dimension; the rotation pads d up to a
        # byte-aligned D so rows pack to D/8 contiguous bytes.
        pq_dim = round_up(d, 8)
        rot_dim = pq_dim
        pq_len = 1
        ksub = 2
    else:
        pq_dim = params.pq_dim or _default_pq_dim(d)
        expects(pq_dim <= d, "pq_dim=%d larger than dim=%d", pq_dim, d)
        rot_dim = round_up(d, pq_dim)
        pq_len = rot_dim // pq_dim
        ksub = 1 << params.pq_bits

    key = as_key(params.seed)
    k_rot, k_cb = jax.random.split(key)

    ds_f32 = dataset.astype(jnp.float32)
    row_norm = ivf_common.one_norm(ds_f32) if metric == DistanceType.InnerProduct else 0.0
    train_n = max(n_lists, int(n * params.kmeans_trainset_fraction))
    trainset = ds_f32
    if train_n < n:
        rng = np.random.default_rng(params.seed)
        trainset = ds_f32[jnp.asarray(rng.permutation(n)[:train_n])]

    # -- coarse quantizer (kmeans_balanced, as in the reference) ------------
    # Each phase of the build is a span (``ivf_pq.build.<phase>``): its
    # outputs are synced at its end when observability records it.
    with obs.span("ivf_pq.build.coarse", n=n, n_lists=n_lists, n_train=train_n) as sp:
        centers = kmeans_balanced.fit(
            trainset,
            BalancedKMeansParams(
                n_clusters=n_lists,
                n_iters=params.kmeans_n_iters,
                metric=DistanceType.L2Expanded,
                seed=params.seed,
            ),
        )
        # Physically order the lists by the PCA-bisection spatial rank of
        # their centers (same as IVF-Flat v3): the fused Pallas scan's
        # probe-coherent query tiles and group-granular DMA both assume
        # spatially nearby lists sit next to each other.
        from raft_tpu.ops.pallas.ivf_scan import spatial_center_rank

        srank = spatial_center_rank(np.asarray(centers))
        centers = sp.sync(jnp.asarray(np.asarray(centers)[np.argsort(srank)]))
    center_rank = jnp.arange(n_lists, dtype=jnp.int32)

    # -- rotation + rotated centers ----------------------------------------
    # RaBitQ's estimator is only unbiased under a RANDOM rotation (the sign
    # quantizer needs the residual direction uniformly distributed on the
    # sphere), so rabitq always forces one.
    rotation = _make_rotation(k_rot, rot_dim, d, params.force_random_rotation or rabitq)
    centers_rot = jnp.dot(centers, rotation.T, precision=lax.Precision.HIGHEST)

    per_cluster = params.codebook_kind == PER_CLUSTER
    if rabitq:
        # No codebook to train: the "codebook" is the sign function.
        # pq_centers stays a [1, 1, 1] placeholder (pq_len/ksub properties
        # are meaningless for this kind and never consulted).
        pq_centers = jnp.zeros((1, 1, 1), jnp.float32)
        slot, max_list = _assign(ds_f32, centers, n, n_lists, params.list_cap_factor)
        final_labels = (slot // max_list).astype(jnp.int32)
        with obs.span("ivf_pq.build.encode", n=n) as sp:
            codes_dev, aux_dev = sp.sync(_rabitq_encode_all(
                ds_f32, final_labels, centers, rotation, centers_rot, metric
            ))
        with obs.span("ivf_pq.build.layout", n=n, max_list=max_list) as sp:
            codes, list_indices, list_sizes = ivf_common.scatter_rows(
                codes_dev, jnp.arange(n, dtype=jnp.int32), slot, n_lists=n_lists,
                max_list=max_list,
            )
            aux, _, _ = sp.sync(ivf_common.scatter_rows(
                aux_dev, jnp.arange(n, dtype=jnp.int32), slot, n_lists=n_lists,
                max_list=max_list,
            ))
        return IvfPqIndex(
            centers=centers,
            centers_rot=centers_rot,
            rotation=rotation,
            pq_centers=pq_centers,
            codes=codes,
            list_indices=list_indices,
            list_sizes=list_sizes,
            rot_sqnorms=aux[..., 0],
            metric=metric,
            codebook_kind=params.codebook_kind,
            pq_bits=1,
            size=n,
            list_cap_factor=params.list_cap_factor,
            additive=False,
            packed=True,
            center_rank=center_rank,
            rabitq=True,
            corrections=aux[..., 1],
            row_norm=row_norm,
        )

    # -- codebook training on trainset residuals ---------------------------
    with obs.span("ivf_pq.build.codebooks", pq_dim=pq_dim, ksub=ksub) as sp:
        pq_centers = sp.sync(_train_codebooks(
            trainset, centers, rotation, params, pq_dim=pq_dim, pq_len=pq_len, ksub=ksub,
            n_lists=n_lists, per_cluster=per_cluster, nibble=nibble, k_cb=k_cb))

    # -- encode + pack the full dataset (on device) -------------------------
    # Capacity-capped assignment first (spilled rows encode against their
    # FINAL list's center so ADC distances stay consistent), then encode,
    # then one scatter into the padded layout. See ivf_common.py.
    slot, max_list = _assign(ds_f32, centers, n, n_lists, params.list_cap_factor)
    final_labels = (slot // max_list).astype(jnp.int32)
    with obs.span("ivf_pq.build.encode", n=n) as sp:
        codes_dev = sp.sync(_encode_all(
            ds_f32, final_labels, centers, rotation, pq_centers, pq_dim, per_cluster))
    # bit-pack sub-byte widths whenever the row bitstream is byte-aligned
    # (4: two per byte; 3/5/6/7: spanning little-endian — all decoded by
    # the fused kernel's generic b-mode). Reference:
    # ivf_pq_types.hpp:129-164.
    packed = not nibble and params.pq_bits < 8 and (pq_dim * params.pq_bits) % 8 == 0
    with obs.span("ivf_pq.build.layout", n=n, max_list=max_list) as sp:
        codes, list_indices, list_sizes = ivf_common.scatter_rows(
            codes_dev, jnp.arange(n, dtype=jnp.int32), slot, n_lists=n_lists, max_list=max_list
        )
        rot_sqnorms = _sqnorms_for(codes, centers_rot, pq_centers, per_cluster)
        if packed:
            codes = pack_codes_bits(codes, params.pq_bits)
        sp.sync((codes, list_indices, list_sizes, rot_sqnorms))

    return IvfPqIndex(
        centers=centers,
        centers_rot=centers_rot,
        rotation=rotation,
        pq_centers=pq_centers,
        codes=codes,
        list_indices=list_indices,
        list_sizes=list_sizes,
        rot_sqnorms=rot_sqnorms,
        metric=metric,
        codebook_kind=params.codebook_kind,
        pq_bits=params.pq_bits,
        size=n,
        list_cap_factor=params.list_cap_factor,
        additive=nibble,
        packed=packed,
        center_rank=center_rank,
        row_norm=row_norm,
    )


def extend(index: IvfPqIndex, new_vectors, new_ids=None) -> IvfPqIndex:
    """Encode new vectors with the existing quantizers and repack
    (``ivf_pq::extend``, ``detail/ivf_pq_build.cuh:1219``)."""
    new_vectors = jnp.asarray(new_vectors)
    expects(new_vectors.ndim == 2 and new_vectors.shape[1] == index.dim, "bad extend shape")
    n_new = new_vectors.shape[0]
    if new_ids is None:
        new_ids = np.arange(index.size, index.size + n_new, dtype=np.int32)
    else:
        new_ids = np.asarray(new_ids, np.int32)

    vec_f32 = new_vectors.astype(jnp.float32)
    per_cluster = index.codebook_kind == PER_CLUSTER
    n_lists = index.n_lists
    row_norm = index.row_norm
    if row_norm:  # rows of another norm end the one-norm ranking
        new_norm = ivf_common.one_norm(vec_f32)
        if abs(new_norm - row_norm) > ivf_common.ONE_NORM_RTOL * row_norm:
            row_norm = 0.0

    # Existing codes keep their list assignment (their residuals were
    # encoded against that center); compact them to the front on device.
    flat_ids = index.list_indices.reshape(-1)
    n_old = int(index.size)
    keep_order = jnp.argsort(flat_ids < 0)[:n_old]
    if index.rabitq:
        # sign-bit rows stay packed (one u8 row per vector); carry the
        # per-row [C1, g] estimator scalars alongside.
        old_codes = index.codes.reshape(-1, index.codes.shape[2])[keep_order]
        old_aux = jnp.stack(
            [index.rot_sqnorms.reshape(-1), index.corrections.reshape(-1)], axis=1
        )[keep_order]
    else:
        old_codes = index.codes_unpacked().reshape(-1, index.pq_dim)[keep_order]
    old_ids = flat_ids[keep_order]
    old_l1 = (keep_order // index.max_list).astype(jnp.int32)

    new_cand = ivf_common.topk_labels(vec_f32, index.centers, k=8)
    all_ids = jnp.concatenate([old_ids, new_ids])
    # old rows never spill past their current list (their codes are
    # residuals against that center): all their candidates are that list
    old_cand = jnp.broadcast_to(old_l1[:, None], (n_old, new_cand.shape[1]))
    cand = jnp.concatenate([old_cand, new_cand], axis=0)
    n_total = n_old + n_new
    # never shrink below the current stride so old rows keep their list
    max_list = max(
        ivf_common.choose_max_list(cand[:, 0], n_total, n_lists, index.list_cap_factor),
        index.max_list,
    )
    slot = ivf_common.assign_slots(cand, n_lists=n_lists, max_list=max_list)
    final_labels = (slot // max_list).astype(jnp.int32)
    if index.rabitq:
        new_codes, new_aux = _rabitq_encode_all(
            vec_f32,
            final_labels[n_old:],
            index.centers,
            index.rotation,
            index.centers_rot,
            index.metric,
        )
        all_codes = jnp.concatenate([old_codes, new_codes], axis=0)
        all_aux = jnp.concatenate([old_aux, new_aux], axis=0)
        codes, list_indices, list_sizes = ivf_common.scatter_rows(
            all_codes, all_ids, slot, n_lists=n_lists, max_list=max_list
        )
        aux, _, _ = ivf_common.scatter_rows(
            all_aux, all_ids, slot, n_lists=n_lists, max_list=max_list
        )
        return dataclasses.replace(
            index,
            codes=codes,
            list_indices=list_indices,
            list_sizes=list_sizes,
            rot_sqnorms=aux[..., 0],
            corrections=aux[..., 1],
            size=index.size + n_new,
            row_norm=row_norm,
        )
    new_codes = _encode_all(
        vec_f32,
        final_labels[n_old:],
        index.centers,
        index.rotation,
        index.pq_centers,
        index.pq_dim,
        per_cluster,
    )
    all_codes = jnp.concatenate([old_codes, new_codes], axis=0)
    codes, list_indices, list_sizes = ivf_common.scatter_rows(
        all_codes, all_ids, slot, n_lists=n_lists, max_list=max_list
    )
    sqn = _sqnorms_for(codes, index.centers_rot, index.pq_centers, per_cluster)
    return dataclasses.replace(
        index,
        codes=pack_codes_bits(codes, index.pq_bits) if index.packed else codes,
        list_indices=list_indices,
        list_sizes=list_sizes,
        rot_sqnorms=sqn,
        size=index.size + n_new,
        row_norm=row_norm,
    )


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=(
        "k",
        "n_probes",
        "metric",
        "per_cluster",
        "has_filter",
        "chunk_lists",
        "bf16",
        "row_norm",
    ),
)
def _ivf_pq_scan_impl(
    centers,
    centers_rot,
    rotation,
    pq_centers,
    codes,
    list_indices,
    rot_sqnorms,
    queries,
    filter_bits,
    *,
    k: int,
    n_probes: int,
    metric: DistanceType,
    per_cluster: bool,
    has_filter: bool,
    chunk_lists: int,
    bf16: bool,
    row_norm: float = 0.0,
):
    """Dense decode-and-score scan — the TPU replacement for the reference's
    shared-memory LUT kernel (``detail/ivf_pq_compute_similarity-inl.cuh``).

    TPUs have no fast per-lane gather, so ADC's ``sum_j LUT[j, code_j]``
    (an XLA gather) runs ~1000x off the roofline. Instead each chunk of
    lists is **decoded on the fly with a one-hot MXU matmul**
    (``onehot(codes) @ codebook`` — FLOP-heavy but systolic-array-shaped),
    scored against the rotated queries with a second matmul, masked to the
    probed lists (elementwise, fused), and fed to the fused approximate
    top-k. Probe semantics are exactly the reference's — the same
    candidate set as the LUT kernel — only the *schedule* differs.
    Measured at SIFT-1M shapes this is ~1000x faster than the gather
    formulation on TPU v5e.
    """
    nq, d = queries.shape
    qf = queries.astype(jnp.float32)

    with obs.span("ivf_pq.search.coarse_probe", nq=nq, n_probes=n_probes) as sp:
        # coarse scores double as the probe selector AND the q.c_l term
        q_dot_c = qf @ centers.T  # [nq, n_lists]
        coarse = ivf_common.coarse_from_dots(q_dot_c, centers, qf, metric, row_norm)
        n_lists = centers.shape[0]
        probed = jnp.zeros((nq, n_lists), bool)
        if n_probes < n_lists:
            _, probes = select_k(coarse, n_probes, select_min=True)
            probed = probed.at[jnp.arange(nq)[:, None], probes].set(True)
        else:
            probed = jnp.ones((nq, n_lists), bool)
        sp.sync(probed)

    q_rot = qf @ rotation.T  # [nq, rot_dim]
    with obs.span("ivf_pq.search.pq_scan", nq=nq, k=k) as sp:
        return sp.sync(
            pq_scan_core(
                pq_centers, codes, list_indices, rot_sqnorms, q_rot, q_dot_c,
                probed, filter_bits,
                k=k, metric=metric, per_cluster=per_cluster, has_filter=has_filter,
                chunk_lists=chunk_lists, bf16=bf16,
            )
        )


@functools.partial(
    jax.jit,
    static_argnames=("k", "metric", "per_cluster", "has_filter", "chunk_lists", "bf16"),
)
def pq_scan_core(
    pq_centers,
    codes,
    list_indices,
    rot_sqnorms,
    q_rot,
    q_dot_c,
    probed,
    filter_bits,
    *,
    k: int,
    metric: DistanceType,
    per_cluster: bool,
    has_filter: bool,
    chunk_lists: int,
    bf16: bool,
):
    """Decode-and-score over a (possibly LOCAL slice of the) list set with
    a precomputed probe mask — the shardable core of the dense PQ scan,
    mirroring :func:`raft_tpu.neighbors.ivf_flat.flat_scan_core`:
    ``codes/list_indices/rot_sqnorms/q_dot_c/probed`` may all be sliced to
    a shard's lists (list_indices carry GLOBAL row ids, so per-shard
    results merge with one allgather + k-way merge)."""
    nq = q_rot.shape[0]
    n_lists, max_list, pq_dim = codes.shape
    ksub = pq_centers.shape[-2]
    rot_dim = q_rot.shape[1]

    cdtype = jnp.bfloat16 if bf16 else jnp.float32
    qc = q_rot.astype(cdtype)
    books = pq_centers.astype(cdtype)

    n_chunks = n_lists // chunk_lists
    G, M = chunk_lists, max_list
    codes_c = codes.reshape(n_chunks, G, M, pq_dim)
    ids_c = list_indices.reshape(n_chunks, G * M)
    sqn_c = rot_sqnorms.reshape(n_chunks, G * M)
    probed_c = probed.reshape(nq, n_chunks, G)
    # 2*q.c_l per (query, list): reuses the coarse matmul (q.c is metric-
    # invariant under the orthonormal rotation, so q_rot.c_rot == q.c)
    qdotc_c = jnp.moveaxis(q_dot_c.reshape(nq, n_chunks, G), 1, 0)
    if per_cluster:
        books_c = books.reshape(n_chunks, G, ksub, -1)

    init = (
        jnp.full((nq, k), -jnp.inf, jnp.float32),
        jnp.zeros((nq, k), jnp.int32),  # flat slot ids
    )

    def body(carry, inp):
        acc_v, acc_i = carry
        if per_cluster:
            cod, ids, sqn, pmask, qdc, bks, ci = inp
            onehot = (
                cod[:, :, :, None].astype(jnp.int32)
                == jnp.arange(ksub, dtype=jnp.int32)[None, None, None, :]
            ).astype(cdtype)  # [G, M, pq_dim, ksub]
            resid = jnp.einsum(
                "gmjc,gcs->gmjs", onehot, bks, preferred_element_type=cdtype
            )
        else:
            cod, ids, sqn, pmask, qdc, ci = inp
            codf = cod.reshape(G * M, pq_dim)
            onehot = (
                codf[:, :, None].astype(jnp.int32)
                == jnp.arange(ksub, dtype=jnp.int32)[None, None, :]
            ).astype(cdtype)
            resid = jnp.einsum(
                "tjc,jcs->tjs", onehot, books, preferred_element_type=cdtype
            )
        # score(q, x) for L2: 2 q_rot.(c_rot+r) - ||c_rot+r||^2
        #   = 2 q_rot.r  +  2 q.c_l  -  sqn   (sqn precomputed at build);
        # for IP: q_rot.r + q.c_l. The residual matmul is the einsum
        # output's only consumer, keeping the decode inside one fusion.
        # Masking is ADDITIVE on the small axes (a [G*M] pad penalty and an
        # [nq, G] probe penalty, broadcast into the epilogue) — a boolean
        # [nq, G*M] keep-mask defeats XLA's matmul fusion and costs ~10x.
        dots_r = (qc @ resid.reshape(G * M, rot_dim).T).astype(jnp.float32)
        pad_pen = jnp.where(ids >= 0, 0.0, -jnp.inf)  # [G*M]
        if has_filter:
            word = filter_bits[jnp.clip(ids, 0, None) // 32]
            bit = (word >> (jnp.clip(ids, 0, None) % 32).astype(jnp.uint32)) & 1
            pad_pen = jnp.where(bit == 1, pad_pen, -jnp.inf)
        if metric == DistanceType.InnerProduct:
            probe_pen = jnp.where(pmask, qdc, -jnp.inf)  # [nq, G]
            score = (
                dots_r
                + jnp.broadcast_to(probe_pen[:, :, None], (nq, G, M)).reshape(nq, G * M)
                + pad_pen[None, :]
            )
        else:
            probe_pen = jnp.where(pmask, 2.0 * qdc, -jnp.inf)
            score = (
                2.0 * dots_r
                - (sqn - pad_pen)[None, :]
                + jnp.broadcast_to(probe_pen[:, :, None], (nq, G, M)).reshape(nq, G * M)
            )
        # shortlist 2k per chunk (see _ivf_flat_scan_impl)
        kk = min(max(2 * k, 16), G * M)
        v, i = lax.approx_max_k(score, kk, recall_target=0.99)
        nv, ni = lax.top_k(jnp.concatenate([acc_v, v], axis=1), k)
        na = jnp.take_along_axis(
            jnp.concatenate([acc_i, i + ci * (G * M)], axis=1), ni, axis=1
        )
        return (nv, na), None

    xs = (codes_c, ids_c, sqn_c, jnp.moveaxis(probed_c, 1, 0), qdotc_c)
    if per_cluster:
        xs = xs + (books_c,)
    xs = xs + (jnp.arange(n_chunks, dtype=jnp.int32),)
    (vals, slots), _ = lax.scan(body, init, xs)

    idx = list_indices.reshape(-1)[slots.reshape(-1)].reshape(nq, k)
    idx = jnp.where(jnp.isfinite(vals), idx, -1)
    if metric == DistanceType.InnerProduct:
        out = vals
    else:
        qn = jnp.sum(q_rot * q_rot, axis=1)
        out = jnp.maximum(qn[:, None] - vals, 0.0)
        if metric == DistanceType.L2SqrtExpanded:
            out = jnp.sqrt(out)
        out = jnp.where(idx >= 0, out, jnp.inf)
    return out, idx


@functools.partial(
    jax.jit,
    static_argnames=("k", "n_probes", "metric", "per_cluster", "has_filter", "lut_dtype",
                     "row_norm"),
)
def _ivf_pq_search_impl(
    centers,
    centers_rot,
    rotation,
    pq_centers,
    codes,
    list_indices,
    queries,
    filter_bits,
    *,
    k: int,
    n_probes: int,
    metric: DistanceType,
    per_cluster: bool,
    has_filter: bool,
    lut_dtype,
    row_norm: float = 0.0,
):
    nq, d = queries.shape
    n_lists = centers.shape[0]
    pq_dim = codes.shape[2]
    qf = queries.astype(jnp.float32)

    # -- coarse: nearest centers (select_clusters, ivf_pq_search.cuh:67) ----
    q_dot_c = qf @ centers.T
    coarse = ivf_common.coarse_from_dots(q_dot_c, centers, qf, metric, row_norm)
    _, probes = select_k(coarse, n_probes, select_min=True)  # [nq, n_probes]

    q_rot = qf @ rotation.T  # [nq, rot_dim]
    q_sub = q_rot.reshape(nq, pq_dim, -1)  # [nq, pq_dim, pq_len]

    select_min = metric != DistanceType.InnerProduct
    worst = jnp.float32(worst_value(jnp.float32, select_min))
    init = (
        jnp.full((nq, k), worst, jnp.float32),
        jnp.full((nq, k), -1, jnp.int32),
    )

    pqc_norm = jnp.sum(pq_centers * pq_centers, axis=-1)  # [pq_dim|n_lists, ksub]

    def body(carry, p):
        acc_v, acc_i = carry
        list_id = probes[:, p]  # [nq]
        codes_p = codes[list_id]  # [nq, max_list, pq_dim]
        ids_p = list_indices[list_id]  # [nq, max_list]

        # -- LUT build (compute_similarity kernel's smem LUT) ---------------
        if metric == DistanceType.InnerProduct:
            # score = q . c  +  sum_j q_sub[j] . pq_c[j, code_j]
            if per_cluster:
                pqc = pq_centers[list_id]  # [nq, ksub, pq_len]
                lut = jnp.einsum("npl,nkl->npk", q_sub, pqc, preferred_element_type=jnp.float32, precision=lax.Precision.HIGHEST)
            else:
                lut = jnp.einsum("npl,pkl->npk", q_sub, pq_centers, preferred_element_type=jnp.float32, precision=lax.Precision.HIGHEST)
            base = jnp.take_along_axis(q_dot_c, list_id[:, None], axis=1)[:, 0]
        else:
            # dist = sum_j || (q_rot - c_rot)[j] - pq_c[j, code_j] ||^2
            diff = q_sub - centers_rot[list_id].reshape(nq, pq_dim, -1)
            dn = jnp.sum(diff * diff, axis=-1)  # [nq, pq_dim]
            if per_cluster:
                pqc = pq_centers[list_id]
                dots = jnp.einsum("npl,nkl->npk", diff, pqc, preferred_element_type=jnp.float32, precision=lax.Precision.HIGHEST)
                cn = pqc_norm[list_id][:, None, :]  # [nq, 1, ksub]
            else:
                dots = jnp.einsum("npl,pkl->npk", diff, pq_centers, preferred_element_type=jnp.float32, precision=lax.Precision.HIGHEST)
                cn = pqc_norm[None, :, :]
            lut = dn[:, :, None] - 2.0 * dots + cn  # [nq, pq_dim, ksub]
            base = jnp.float32(0.0)

        if lut_dtype != "float32":
            lut = lut.astype(lut_dtype).astype(jnp.float32)

        # -- apply LUT to codes (the scan part of the similarity kernel) ----
        codes_t = jnp.transpose(codes_p, (0, 2, 1)).astype(jnp.int32)  # [nq, pq_dim, max_list]
        gathered = jnp.take_along_axis(lut, codes_t, axis=2)  # [nq, pq_dim, max_list]
        dist = jnp.sum(gathered, axis=1)  # [nq, max_list]
        if metric == DistanceType.InnerProduct:
            dist = dist + base[:, None]

        valid = ids_p >= 0
        if has_filter:
            word = filter_bits[jnp.clip(ids_p, 0, None) // 32]
            bit = (word >> (jnp.clip(ids_p, 0, None) % 32).astype(jnp.uint32)) & 1
            valid = valid & (bit == 1)
        dist = jnp.where(valid, dist, worst)
        ids_masked = jnp.where(valid, ids_p, -1)
        return running_merge(acc_v, acc_i, dist, ids_masked, select_min=select_min), None

    (vals, idx), _ = lax.scan(body, init, jnp.arange(n_probes))

    if metric == DistanceType.L2SqrtExpanded:
        vals = jnp.where(idx >= 0, jnp.sqrt(jnp.maximum(vals, 0.0)), vals)
    return vals, idx


@functools.partial(
    jax.jit,
    static_argnames=("k", "n_probes", "metric", "has_filter", "chunk_lists", "row_norm"),
)
def _ivf_rabitq_scan_impl(
    centers,
    rotation,
    codes,
    corrections,
    list_indices,
    rot_sqnorms,
    queries,
    filter_bits,
    *,
    k: int,
    n_probes: int,
    metric: DistanceType,
    has_filter: bool,
    chunk_lists: int,
    row_norm: float = 0.0,
):
    """Dense RaBitQ scan: the same probe/schedule skeleton as
    :func:`_ivf_pq_scan_impl` with the one-hot decode matmul replaced by a
    single sign-bit matmul per chunk (see :func:`_rabitq_encode_chunk` for
    the estimator algebra)."""
    nq, d = queries.shape
    qf = queries.astype(jnp.float32)

    with obs.span("ivf_pq.search.coarse_probe", nq=nq, n_probes=n_probes) as sp:
        q_dot_c = qf @ centers.T  # [nq, n_lists]
        coarse = ivf_common.coarse_from_dots(q_dot_c, centers, qf, metric, row_norm)
        n_lists = centers.shape[0]
        probed = jnp.zeros((nq, n_lists), bool)
        if n_probes < n_lists:
            _, probes = select_k(coarse, n_probes, select_min=True)
            probed = probed.at[jnp.arange(nq)[:, None], probes].set(True)
        else:
            probed = jnp.ones((nq, n_lists), bool)
        sp.sync(probed)

    q_rot = qf @ rotation.T  # [nq, rot_dim]
    with obs.span("ivf_pq.search.rabitq_xla", nq=nq, k=k) as sp:
        return sp.sync(
            rabitq_scan_core(
                codes, corrections, list_indices, rot_sqnorms, q_rot, q_dot_c,
                probed, filter_bits,
                k=k, metric=metric, has_filter=has_filter, chunk_lists=chunk_lists,
            )
        )


@functools.partial(
    jax.jit, static_argnames=("k", "metric", "has_filter", "chunk_lists")
)
def rabitq_scan_core(
    codes,
    corrections,
    list_indices,
    rot_sqnorms,
    q_rot,
    q_dot_c,
    probed,
    filter_bits,
    *,
    k: int,
    metric: DistanceType,
    has_filter: bool,
    chunk_lists: int,
):
    """Shardable RaBitQ scan core (mirrors :func:`pq_scan_core`): per
    chunk, unpack the sign bits and evaluate the estimator as ONE
    [nq, rot_dim] x [rot_dim, G*M] matmul plus an elementwise epilogue.
    Keeps the maximize-score convention so the approx-top-k shortlist,
    pad/probe penalties, and the distance epilogue are shared with the PQ
    scan verbatim:

        mscore = coef*(q.c_l) + g*(b.q_rot - sum(q_rot)/2) - C1
        L2 out = max(||q||^2 - mscore, 0)      IP out = mscore
    """
    nq = q_rot.shape[0]
    n_lists, max_list, bpr = codes.shape
    D = q_rot.shape[1]

    sq = jnp.sum(q_rot, axis=1)  # [nq]
    coef = 1.0 if metric == DistanceType.InnerProduct else 2.0

    n_chunks = n_lists // chunk_lists
    G, M = chunk_lists, max_list
    codes_c = codes.reshape(n_chunks, G * M, bpr)
    ids_c = list_indices.reshape(n_chunks, G * M)
    c1_c = rot_sqnorms.reshape(n_chunks, G * M)
    g_c = corrections.reshape(n_chunks, G * M)
    probed_c = probed.reshape(nq, n_chunks, G)
    qdotc_c = jnp.moveaxis(q_dot_c.reshape(nq, n_chunks, G), 1, 0)

    init = (
        jnp.full((nq, k), -jnp.inf, jnp.float32),
        jnp.zeros((nq, k), jnp.int32),  # flat slot ids
    )

    def body(carry, inp):
        acc_v, acc_i = carry
        cod, ids, c1, gg, pmask, qdc, ci = inp
        # sign bits as f32 {0,1}: the bit dot is exact in f32 (each term is
        # a masked add of a query lane), matching the fused kernel's
        # arithmetic bit for bit.
        bits = unpack_codes_bits(cod, 1, D).astype(jnp.float32)  # [G*M, D]
        bq = jax.lax.dot_general(
            q_rot, bits,
            (((1,), (1,)), ((), ())),
            precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )  # [nq, G*M]
        part = gg[None, :] * (bq - 0.5 * sq[:, None]) - c1[None, :]
        pad_pen = jnp.where(ids >= 0, 0.0, -jnp.inf)  # [G*M]
        if has_filter:
            word = filter_bits[jnp.clip(ids, 0, None) // 32]
            bit = (word >> (jnp.clip(ids, 0, None) % 32).astype(jnp.uint32)) & 1
            pad_pen = jnp.where(bit == 1, pad_pen, -jnp.inf)
        probe_pen = jnp.where(pmask, coef * qdc, -jnp.inf)  # [nq, G]
        score = (
            part
            + jnp.broadcast_to(probe_pen[:, :, None], (nq, G, M)).reshape(nq, G * M)
            + pad_pen[None, :]
        )
        kk = min(max(2 * k, 16), G * M)
        v, i = lax.approx_max_k(score, kk, recall_target=0.99)
        nv, ni = lax.top_k(jnp.concatenate([acc_v, v], axis=1), k)
        na = jnp.take_along_axis(
            jnp.concatenate([acc_i, i + ci * (G * M)], axis=1), ni, axis=1
        )
        return (nv, na), None

    xs = (
        codes_c, ids_c, c1_c, g_c, jnp.moveaxis(probed_c, 1, 0), qdotc_c,
        jnp.arange(n_chunks, dtype=jnp.int32),
    )
    (vals, slots), _ = lax.scan(body, init, xs)

    idx = list_indices.reshape(-1)[slots.reshape(-1)].reshape(nq, k)
    idx = jnp.where(jnp.isfinite(vals), idx, -1)
    if metric == DistanceType.InnerProduct:
        out = vals
    else:
        qn = jnp.sum(q_rot * q_rot, axis=1)
        out = jnp.maximum(qn[:, None] - vals, 0.0)
        if metric == DistanceType.L2SqrtExpanded:
            out = jnp.sqrt(out)
        out = jnp.where(idx >= 0, out, jnp.inf)
    return out, idx


@functools.partial(
    jax.jit, static_argnames=("k", "n_probes", "metric", "has_filter", "row_norm")
)
def _ivf_rabitq_probe_impl(
    centers,
    rotation,
    codes,
    corrections,
    list_indices,
    rot_sqnorms,
    queries,
    filter_bits,
    *,
    k: int,
    n_probes: int,
    metric: DistanceType,
    has_filter: bool,
    row_norm: float = 0.0,
):
    """Probe-at-a-time RaBitQ estimator (memory-lean analog of
    :func:`_ivf_pq_search_impl`): gathers one list per query per step and
    evaluates the estimator with a per-query bit dot."""
    nq, d = queries.shape
    qf = queries.astype(jnp.float32)
    bpr = codes.shape[2]
    D = bpr * 8

    q_dot_c = qf @ centers.T
    coarse = ivf_common.coarse_from_dots(q_dot_c, centers, qf, metric, row_norm)
    _, probes = select_k(coarse, n_probes, select_min=True)  # [nq, n_probes]

    q_rot = qf @ rotation.T  # [nq, D]
    sq = jnp.sum(q_rot, axis=1)  # [nq]
    qn = jnp.sum(q_rot * q_rot, axis=1)
    coef = 1.0 if metric == DistanceType.InnerProduct else 2.0

    select_min = metric != DistanceType.InnerProduct
    worst = jnp.float32(worst_value(jnp.float32, select_min))
    init = (
        jnp.full((nq, k), worst, jnp.float32),
        jnp.full((nq, k), -1, jnp.int32),
    )

    def body(carry, p):
        acc_v, acc_i = carry
        list_id = probes[:, p]  # [nq]
        cod = codes[list_id]  # [nq, max_list, bpr]
        ids_p = list_indices[list_id]  # [nq, max_list]
        c1 = rot_sqnorms[list_id]
        gg = corrections[list_id]
        qdc = jnp.take_along_axis(q_dot_c, list_id[:, None], axis=1)  # [nq, 1]

        bits = unpack_codes_bits(cod, 1, D).astype(jnp.float32)  # [nq, max_list, D]
        bq = jnp.einsum(
            "nd,nmd->nm", q_rot, bits,
            preferred_element_type=jnp.float32, precision=lax.Precision.HIGHEST,
        )
        mscore = coef * qdc + gg * (bq - 0.5 * sq[:, None]) - c1
        if metric == DistanceType.InnerProduct:
            dist = mscore
        else:
            dist = jnp.maximum(qn[:, None] - mscore, 0.0)

        valid = ids_p >= 0
        if has_filter:
            word = filter_bits[jnp.clip(ids_p, 0, None) // 32]
            bit = (word >> (jnp.clip(ids_p, 0, None) % 32).astype(jnp.uint32)) & 1
            valid = valid & (bit == 1)
        dist = jnp.where(valid, dist, worst)
        ids_masked = jnp.where(valid, ids_p, -1)
        return running_merge(acc_v, acc_i, dist, ids_masked, select_min=select_min), None

    (vals, idx), _ = lax.scan(body, init, jnp.arange(n_probes))

    if metric == DistanceType.L2SqrtExpanded:
        vals = jnp.where(idx >= 0, jnp.sqrt(jnp.maximum(vals, 0.0)), vals)
    return vals, idx


def scan_chunk_lists(n_lists: int, max_list: int) -> int:
    """Chunk size for the decode scan: ~256k rows (decode temporaries are
    [rows, pq_dim, ksub]-shaped, so PQ chunks stay smaller than the flat
    scan's), constrained to divide n_lists."""
    g = max(1, 262144 // max(max_list, 1))
    while n_lists % g:
        g -= 1
    return g


def scan_bf16(lut_dtype) -> bool:
    """Reduced-precision decode/score is a TPU-only mode (the CPU dot
    thunk has no bf16 support)."""
    return (
        lut_dtype is not None
        and jnp.dtype(lut_dtype) == jnp.dtype(jnp.bfloat16)
        and jax.default_backend() == "tpu"
    )



def _fused_code_layout(index) -> tuple:
    """(code_mode, ksub) the fused kernel would use for this index — the
    ONE mapping shared by the VMEM feasibility gate and the fused call
    (drift here would make auto-mode model feasibility with the wrong
    layout)."""
    if index.additive:
        return "nib8", 16
    if index.packed and index.pq_bits == 4:
        return "p4", 16
    if index.packed:
        return f"b{index.pq_bits}", index.ksub
    return "u8", index.ksub


def search(
    index: IvfPqIndex,
    queries,
    k: int,
    params: Optional[IvfPqSearchParams] = None,
    prefilter: Optional[Bitset] = None,
    query_batch: int = 1024,
    mode: str = "auto",
    res: Optional[Resources] = None,
    dataset=None,
    **kwargs,
) -> Tuple[jax.Array, jax.Array]:
    """ADC search over probed lists (``ivf_pq::search``,
    ``detail/ivf_pq_search.cuh:588``). Returns best-first
    ``(distances [nq, k] f32, indices [nq, k] i32)``; unfilled slots get
    id -1. Distances are PQ approximations — pass the raw ``dataset`` and
    the default ``params.refine_ratio=8`` re-ranks ``k * refine_ratio``
    candidates with exact distances (:func:`raft_tpu.neighbors.refine`),
    the measured out-of-box Pareto point (~15.7k QPS @ 0.947 on 1M x 128).

    ``mode``: ``"fused"`` = the Pallas fused probed-list scan (DMAs only
    the probed CODE blocks — the work-proportional TPU fast path, see
    :mod:`raft_tpu.ops.pallas.pq_scan`; needs per_subspace codebooks and
    a supported metric; any ksub <= 256 including the default 8-bit
    config, wide books via column-chunked decode); ``"scan"`` =
    dense decode-and-score over list chunks (see
    :func:`_ivf_pq_scan_impl` — same probed candidate set, selected with
    the fused APPROXIMATE top-k so results can differ slightly from the
    deterministic probe path); ``"probe"`` = per-probe LUT gather (the
    literal analog of the reference's kernel schedule; better for
    single-digit query batches); ``"auto"`` picks fused on TPU when
    eligible for batches >= 128, else scan/probe by batch size.

    With observability on (:mod:`raft_tpu.obs`, ``RAFT_TPU_OBS=1``) the
    call records a sync-aware ``ivf_pq.search`` span with per-phase
    children (``put`` / ``coarse_probe`` / ``pq_scan`` / ``probe_scan`` /
    ``fused`` / ``refine``) plus counters for mode, n_probes, LUT dtype
    and refine candidates; under a profiler session the same spans are
    annotations that never block; with neither (the default) it costs
    one check."""
    if not obs.active():
        return _search_dispatch(
            index, queries, k, params, prefilter, query_batch, mode, res, dataset, **kwargs
        )
    with obs.span("ivf_pq.search", k=k, nq=int(np.shape(queries)[0])) as sp:
        return sp.sync(
            _search_dispatch(
                index, queries, k, params, prefilter, query_batch, mode, res, dataset, **kwargs
            )
        )


def _search_dispatch(
    index: IvfPqIndex,
    queries,
    k: int,
    params: Optional[IvfPqSearchParams],
    prefilter: Optional[Bitset],
    query_batch: int,
    mode: str,
    res: Optional[Resources],
    dataset,
    **kwargs,
) -> Tuple[jax.Array, jax.Array]:
    """Mode routing + query batching behind :func:`search` (split out so
    the observability-off path costs a single flag check)."""
    ensure_resources(res)
    if params is None:
        params = IvfPqSearchParams(**kwargs)
    with obs.span("ivf_pq.search.put"):
        queries = jnp.asarray(queries)
    expects(queries.ndim == 2 and queries.shape[1] == index.dim, "bad query shape")
    expects(k >= 1, "k must be >= 1")
    if dataset is not None and params.refine_ratio > 1:
        from raft_tpu.neighbors.refine import check_refine_dataset, refine

        # Validate the dataset/index agreement BEFORE the scan runs: a
        # short dataset used to surface only as an out-of-bounds gather
        # deep inside refine's jit.
        check_refine_dataset(dataset, index.size, "ivf_pq")
        inner = dataclasses.replace(params, refine_ratio=1)
        kk = min(k * params.refine_ratio, index.size)
        _, cand = search(
            index, queries, kk, inner,
            prefilter=prefilter, query_batch=query_batch, mode=mode, res=res,
        )
        if obs.is_enabled():
            obs.observe("ivf_pq.search.refine_candidates_per_query", float(kk))
        with obs.span("ivf_pq.search.refine", k=k, candidates=int(kk)) as sp:
            return sp.sync(
                refine(dataset, queries, cand, k, metric=resolve_metric(index.metric))
            )
    if prefilter is not None:
        expects(prefilter.size >= index.size, "prefilter smaller than index")
    n_probes = min(params.n_probes, index.n_lists)
    nq = queries.shape[0]
    filter_bits = prefilter.bits if prefilter is not None else None

    if index.rabitq:
        return _rabitq_modes(
            index, queries, k, params, filter_bits, n_probes, query_batch, mode
        )

    # every per_subspace width is fused-eligible: ksub <= 64 decodes in one
    # multi-hot pass, 128/256 (the reference's DEFAULT pq_bits=8 config)
    # via column-chunked decode — the work-proportional answer to the LUT
    # cost the reference solves with fp8 LUTs (detail/ivf_pq_fp_8bit.cuh)
    fused_ok = (
        index.codebook_kind == PER_SUBSPACE
        and (index.additive or index.ksub <= 256)
        and index.metric in _SUPPORTED
    )
    if fused_ok:
        # very long lists with wide codebooks cannot fit even one decode
        # group in VMEM — auto must route them to the scan path
        from raft_tpu.ops.pallas.pq_scan import decode_feasible

        _cm, _ks = _fused_code_layout(index)
        fused_ok = decode_feasible(
            m=index.codes.shape[1], code_mode=_cm, ksub=_ks,
            bpr=index.codes.shape[2],
            qt=params.fused_qt, k=k, merge=params.fused_merge,
        )
    # the fused kernel's LUT is bf16 by construction; an explicit float32
    # request is a precision demand auto must honor via the scan path
    wants_f32_lut = (
        params.lut_dtype is not None
        and jnp.dtype(params.lut_dtype) == jnp.dtype(jnp.float32)
    )
    requested_mode = mode
    if mode == "auto":
        from raft_tpu import plan as _plan

        mode = _plan.plan_search_mode(
            nq, on_tpu=jax.default_backend() == "tpu", fused_ok=fused_ok,
            wants_f32_lut=wants_f32_lut,
        )
    expects(
        mode in ("scan", "probe", "fused"), "mode must be auto|scan|probe|fused, got %r", mode
    )
    if obs.is_enabled():
        lut = jnp.dtype(params.lut_dtype).name if params.lut_dtype is not None else "default"
        obs.inc("ivf_pq.search.calls", mode=mode, lut=lut)
        obs.inc("ivf_pq.search.queries", float(nq))
        obs.observe("ivf_pq.search.n_probes", float(n_probes))

    if mode == "fused":
        from raft_tpu.ops.pallas.pq_scan import ivf_pq_fused_search

        if wants_f32_lut:
            # auto routes f32-LUT requests to the scan path; an EXPLICIT
            # mode="fused" overrides that, so say so instead of silently
            # dropping the precision request (Python's warning registry
            # dedups this to once per process)
            warnings.warn(
                "mode='fused' computes the LUT in bf16 by construction; the "
                "explicit lut_dtype=float32 request is ignored (use "
                "mode='scan' or mode='auto' to honor it)",
                UserWarning,
                stacklevel=2,
            )
        expects(
            fused_ok,
            "fused mode needs per_subspace + (ksub<=256 | nibble) + a "
            "VMEM-feasible list length (long lists with wide codebooks "
            "must use mode='scan' or more n_lists)",
        )
        code_mode, ksub = _fused_code_layout(index)
        # nib8: additive nibble books, W columns = [A-hot | B-hot] per
        # byte; p4: W's natural [nq, pq_dim, 16] flattening is exactly
        # the kernel's per-byte [lo-hot | hi-hot] order; b3/5/6/7:
        # spanning bitstream peeled from (low, high) byte pairs, W in
        # natural j-major order
        books = nibble_books(index.pq_centers) if index.additive else index.pq_centers
        group = max(1, min(params.fused_group, index.n_lists))
        while index.n_lists % group:
            group -= 1

        def run_fused(qc):
            stats = obs.is_enabled()
            out = ivf_pq_fused_search(
                index.centers,
                index.centers_rot,
                index.rotation,
                books,
                index.codes,
                index.list_indices,
                index.rot_sqnorms,
                qc,
                filter_bits,
                k=k,
                n_probes=n_probes,
                metric=index.metric,
                row_norm=index.row_norm,
                qt=params.fused_qt,
                group=group,
                has_filter=filter_bits is not None,
                merge=params.fused_merge,
                code_mode=code_mode,
                ksub=ksub,
                # capped inside to what the VMEM model leaves at this shape
                decode_cols=params.fused_decode_cols,
                interpret=jax.default_backend() != "tpu",
                stats=stats,
            )
            if not stats:
                return out
            # the walk's MXU passes, read back only with obs on
            dist, ind, passes, units = out
            obs.inc("ivf_pq.search.list_passes", float(passes))
            for u in np.asarray(units):
                obs.observe("ivf_pq.search.tile_units", float(u))
            return dist, ind

        from raft_tpu.neighbors.ivf_flat import _batched_search

        try:
            # host-level fault point: fires even when the jitted kernel
            # program below is cache-hit
            _faults.fire("pallas.pq_scan", nq=int(nq))
            with obs.span("ivf_pq.search.fused", nq=nq, k=k, n_probes=n_probes) as sp:
                # sync inside the try: runtime kernel failures surface at
                # block_until_ready and must reach the fallback handler
                return sp.sync(_batched_search(run_fused, queries, query_batch))
        except _fallback.FALLBACK_ERRORS as e:
            if requested_mode == "fused":
                raise  # the caller pinned the engine; do not mask
            _fallback.record_fallback("ivf_pq", e)
            mode = "scan"  # identical candidate set, decode-scan engine

    if mode == "scan":
        g = scan_chunk_lists(index.n_lists, index.max_list)
        codes_u = index.codes_unpacked()
        out_v, out_i = [], []
        for start in range(0, nq, query_batch):
            qc = queries[start : start + query_batch]
            bpad = 0
            if qc.shape[0] < query_batch and nq > query_batch:
                bpad = query_batch - qc.shape[0]
                qc = jnp.pad(qc, ((0, bpad), (0, 0)))
            v, i = _ivf_pq_scan_impl(
                index.centers,
                index.centers_rot,
                index.rotation,
                index.pq_centers,
                codes_u,
                index.list_indices,
                index.rot_sqnorms,
                qc.astype(jnp.float32),
                filter_bits,
                k=k,
                n_probes=n_probes,
                metric=index.metric,
                per_cluster=index.codebook_kind == PER_CLUSTER,
                has_filter=filter_bits is not None,
                chunk_lists=g,
                bf16=scan_bf16(params.lut_dtype),
                row_norm=index.row_norm,
            )
            if bpad:
                v, i = v[:-bpad], i[:-bpad]
            out_v.append(v)
            out_i.append(i)
        if len(out_v) == 1:
            return out_v[0], out_i[0]
        return jnp.concatenate(out_v, axis=0), jnp.concatenate(out_i, axis=0)

    # Probe mode gathers [qb, pq_dim, max_list] f32 LUT lanes per step; cap
    # the batch so that temporary stays under ~512 MB (an uncapped 1024-
    # query batch against 4k-row lists allocates gigabytes per probe and
    # can OOM the chip — the scan path is the right tool there).
    per_q = max(1, index.pq_dim * index.max_list * 4)
    query_batch = max(1, min(query_batch, (512 << 20) // per_q))

    codes_u = index.codes_unpacked()
    out_v, out_i = [], []
    for start in range(0, nq, query_batch):
        qc = queries[start : start + query_batch]
        bpad = 0
        if qc.shape[0] < query_batch and nq > query_batch:
            bpad = query_batch - qc.shape[0]
            qc = jnp.pad(qc, ((0, bpad), (0, 0)))
        # the per-probe LUT gather fuses coarse probing and the scan in one
        # jitted program — the span covers both phases
        with obs.span("ivf_pq.search.probe_scan", nq=qc.shape[0], k=k) as sp:
            v, i = sp.sync(
                _ivf_pq_search_impl(
                    index.centers,
                    index.centers_rot,
                    index.rotation,
                    index.pq_centers,
                    codes_u,
                    index.list_indices,
                    qc,
                    filter_bits,
                    k=k,
                    n_probes=n_probes,
                    metric=index.metric,
                    per_cluster=index.codebook_kind == PER_CLUSTER,
                    has_filter=filter_bits is not None,
                    lut_dtype=jnp.dtype(params.lut_dtype or jnp.float32).name,
                    row_norm=index.row_norm,
                )
            )
        if bpad:
            v, i = v[:-bpad], i[:-bpad]
        out_v.append(v)
        out_i.append(i)
    if len(out_v) == 1:
        return out_v[0], out_i[0]
    return jnp.concatenate(out_v, axis=0), jnp.concatenate(out_i, axis=0)


def _rabitq_modes(
    index: IvfPqIndex,
    queries,
    k: int,
    params: IvfPqSearchParams,
    filter_bits,
    n_probes: int,
    query_batch: int,
    mode: str,
) -> Tuple[jax.Array, jax.Array]:
    """Mode routing for ``pq_kind="rabitq"`` — same fused/scan/probe
    trio as the PQ dispatch, backed by the rabitq estimator paths (the
    refine/prefilter/batching plumbing upstream is shared verbatim)."""
    from raft_tpu.ops.pallas.rabitq_scan import (
        ivf_rabitq_fused_search,
        rabitq_feasible,
        vmem_decode_rows,
    )

    nq = queries.shape[0]
    fused_ok = index.metric in _SUPPORTED and rabitq_feasible(
        m=index.max_list,
        bpr=index.codes.shape[2],
        qt=params.fused_qt,
        k=k,
        g_lists=params.fused_group,
        rot_dim=index.rot_dim,
        merge=params.fused_merge,
    )
    requested_mode = mode
    if mode == "auto":
        from raft_tpu import plan as _plan

        mode = _plan.plan_search_mode(
            nq, on_tpu=jax.default_backend() == "tpu", fused_ok=fused_ok
        )
    expects(
        mode in ("scan", "probe", "fused"), "mode must be auto|scan|probe|fused, got %r", mode
    )
    if obs.is_enabled():
        obs.inc("ivf_pq.search.calls", mode=mode, lut="rabitq")
        obs.inc("ivf_pq.search.queries", float(nq))
        obs.inc("ivf_pq.search.rabitq.queries", float(nq))
        obs.observe("ivf_pq.search.n_probes", float(n_probes))

    if mode == "fused":
        expects(
            fused_ok,
            "fused rabitq mode needs a supported metric and a VMEM-feasible "
            "list length (use mode='scan' or more n_lists)",
        )
        rank = index.center_rank
        group = params.fused_group
        if rank is None:
            from raft_tpu.neighbors.ivf_flat import _legacy_rank_cache

            rank = _legacy_rank_cache(index.centers)
            group = 1
        group = max(1, min(group, index.n_lists))
        while index.n_lists % group:
            group -= 1

        def run_fused(qc):
            return ivf_rabitq_fused_search(
                index.centers,
                index.centers_rot,
                rank,
                index.rotation,
                index.codes,
                index.list_indices,
                index.rot_sqnorms,
                index.corrections,
                qc,
                filter_bits,
                k=k,
                n_probes=n_probes,
                metric=index.metric,
                row_norm=index.row_norm,
                qt=params.fused_qt,
                probe_factor=params.fused_probe_factor,
                group=group,
                has_filter=filter_bits is not None,
                merge=params.fused_merge,
                extract_every=params.fused_extract_every,
                # VMEM-model cap on rows decoded per pass (the rabitq
                # analog of pq_scan's decode_cols chunking).
                decode_rows=vmem_decode_rows(
                    m=index.max_list,
                    bpr=index.codes.shape[2],
                    qt=params.fused_qt,
                    k=k,
                    g_lists=group,
                    rot_dim=index.rot_dim,
                    merge=params.fused_merge,
                ),
                interpret=jax.default_backend() != "tpu",
            )

        from raft_tpu.neighbors.ivf_flat import _batched_search

        try:
            # same host-level fault seam as the PQ fused path: the robust
            # layer's chaos hooks cover both kernels with one point
            _faults.fire("pallas.pq_scan", nq=int(nq))
            with obs.span("ivf_pq.search.rabitq_scan", nq=nq, k=k, n_probes=n_probes) as sp:
                return sp.sync(_batched_search(run_fused, queries, query_batch))
        except _fallback.FALLBACK_ERRORS as e:
            if requested_mode == "fused":
                raise  # the caller pinned the engine; do not mask
            _fallback.record_fallback("ivf_pq", e)
            mode = "scan"

    if mode == "scan":
        g = scan_chunk_lists(index.n_lists, index.max_list)
        out_v, out_i = [], []
        for start in range(0, nq, query_batch):
            qc = queries[start : start + query_batch]
            bpad = 0
            if qc.shape[0] < query_batch and nq > query_batch:
                bpad = query_batch - qc.shape[0]
                qc = jnp.pad(qc, ((0, bpad), (0, 0)))
            v, i = _ivf_rabitq_scan_impl(
                index.centers,
                index.rotation,
                index.codes,
                index.corrections,
                index.list_indices,
                index.rot_sqnorms,
                qc.astype(jnp.float32),
                filter_bits,
                k=k,
                n_probes=n_probes,
                metric=index.metric,
                has_filter=filter_bits is not None,
                chunk_lists=g,
                row_norm=index.row_norm,
            )
            if bpad:
                v, i = v[:-bpad], i[:-bpad]
            out_v.append(v)
            out_i.append(i)
        if len(out_v) == 1:
            return out_v[0], out_i[0]
        return jnp.concatenate(out_v, axis=0), jnp.concatenate(out_i, axis=0)

    # probe mode: the unpacked-bit temporary is [qb, max_list, D] f32 — cap
    # the batch the same way the PQ probe path caps its LUT gather.
    per_q = max(1, index.rot_dim * index.max_list * 4)
    query_batch = max(1, min(query_batch, (512 << 20) // per_q))
    out_v, out_i = [], []
    for start in range(0, nq, query_batch):
        qc = queries[start : start + query_batch]
        bpad = 0
        if qc.shape[0] < query_batch and nq > query_batch:
            bpad = query_batch - qc.shape[0]
            qc = jnp.pad(qc, ((0, bpad), (0, 0)))
        with obs.span("ivf_pq.search.probe_scan", nq=qc.shape[0], k=k) as sp:
            v, i = sp.sync(
                _ivf_rabitq_probe_impl(
                    index.centers,
                    index.rotation,
                    index.codes,
                    index.corrections,
                    index.list_indices,
                    index.rot_sqnorms,
                    qc.astype(jnp.float32),
                    filter_bits,
                    k=k,
                    n_probes=n_probes,
                    metric=index.metric,
                    has_filter=filter_bits is not None,
                    row_norm=index.row_norm,
                )
            )
        if bpad:
            v, i = v[:-bpad], i[:-bpad]
        out_v.append(v)
        out_i.append(i)
    if len(out_v) == 1:
        return out_v[0], out_i[0]
    return jnp.concatenate(out_v, axis=0), jnp.concatenate(out_i, axis=0)


# ---------------------------------------------------------------------------
# serialization (neighbors/ivf_pq_serialize.cuh analog)
# ---------------------------------------------------------------------------

_KIND = "ivf_pq"
_VERSION = 5  # v4 adds the rabitq flag + corrections array, v5 row_norm


def _write_body(index: IvfPqIndex, stream: BinaryIO) -> None:
    ser.serialize_scalar(stream, int(index.metric), "int32")
    ser.serialize_scalar(stream, int(index.size), "int64")
    ser.serialize_scalar(stream, int(index.pq_bits), "int32")
    ser.serialize_scalar(stream, int(index.codebook_kind == PER_CLUSTER), "int32")
    ser.serialize_scalar(stream, float(index.list_cap_factor), "float64")
    ser.serialize_scalar(stream, int(index.additive), "int32")
    ser.serialize_scalar(stream, int(index.packed), "int32")
    ser.serialize_scalar(stream, int(index.center_rank is not None), "int32")
    ser.serialize_scalar(stream, int(index.rabitq), "int32")
    ser.serialize_scalar(stream, float(index.row_norm), "float64")
    ser.serialize_array(stream, index.centers)
    ser.serialize_array(stream, index.centers_rot)
    ser.serialize_array(stream, index.rotation)
    ser.serialize_array(stream, index.pq_centers)
    ser.serialize_array(stream, index.codes)
    ser.serialize_array(stream, index.list_indices)
    ser.serialize_array(stream, index.list_sizes)
    ser.serialize_array(stream, index.rot_sqnorms)
    if index.rabitq:
        ser.serialize_array(stream, index.corrections)
    if index.center_rank is not None:
        ser.serialize_array(stream, index.center_rank)


def save(index: IvfPqIndex, stream: BinaryIO) -> None:
    body = io.BytesIO()
    _write_body(index, body)
    ser.save_stream(stream, _KIND, _VERSION, body.getvalue())


def load(stream: BinaryIO, res: Optional[Resources] = None) -> IvfPqIndex:
    ensure_resources(res)
    version, stream = ser.load_stream(stream, _KIND)
    metric = DistanceType(ser.deserialize_scalar(stream, "int32"))
    size = int(ser.deserialize_scalar(stream, "int64"))
    pq_bits = int(ser.deserialize_scalar(stream, "int32"))
    per_cluster = bool(ser.deserialize_scalar(stream, "int32"))
    cap_factor = float(ser.deserialize_scalar(stream, "float64")) if version >= 2 else 0.0
    additive = packed = False
    has_rank = rabitq = False
    if version >= 3:
        additive = bool(ser.deserialize_scalar(stream, "int32"))
        packed = bool(ser.deserialize_scalar(stream, "int32"))
        has_rank = bool(ser.deserialize_scalar(stream, "int32"))
    if version >= 4:
        rabitq = bool(ser.deserialize_scalar(stream, "int32"))
    row_norm = float(ser.deserialize_scalar(stream, "float64")) if version >= 5 else 0.0
    centers = ser.deserialize_array(stream)
    centers_rot = ser.deserialize_array(stream)
    rotation = ser.deserialize_array(stream)
    pq_centers = ser.deserialize_array(stream)
    codes = ser.deserialize_array(stream)
    list_indices = ser.deserialize_array(stream)
    list_sizes = ser.deserialize_array(stream)
    if version >= 2:
        rot_sqnorms = ser.deserialize_array(stream)
    else:
        rot_sqnorms = _sqnorms_for(codes, centers_rot, pq_centers, per_cluster)
    corrections = ser.deserialize_array(stream) if rabitq else None
    center_rank = ser.deserialize_array(stream) if has_rank else None
    return IvfPqIndex(
        centers=centers,
        centers_rot=centers_rot,
        rotation=rotation,
        pq_centers=pq_centers,
        codes=codes,
        list_indices=list_indices,
        list_sizes=list_sizes,
        rot_sqnorms=rot_sqnorms,
        metric=metric,
        codebook_kind=PER_CLUSTER if per_cluster else PER_SUBSPACE,
        pq_bits=pq_bits,
        size=size,
        list_cap_factor=cap_factor,
        additive=additive,
        packed=packed,
        center_rank=center_rank,
        rabitq=rabitq,
        corrections=corrections,
        row_norm=row_norm,
    )


def save_path(index: IvfPqIndex, path: str) -> str:
    """Atomic (temp-then-rename) checksummed snapshot at ``path``."""
    return ser.atomic_write(path, lambda f: save(index, f))


def load_path(path: str, res: Optional[Resources] = None) -> IvfPqIndex:
    with open(path, "rb") as f:
        return load(f, res=res)
