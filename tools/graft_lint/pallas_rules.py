"""Pallas VMEM resource-model checkers.

Parses each kernel module's ``pl.BlockSpec``/``pltpu.VMEM``
declarations out of the AST, evaluates their shape expressions under
registered representative bindings (:data:`KERNEL_SHAPE_BINDINGS`), and
checks the resulting residency against the TPU's lane/sublane layout
and the scoped-VMEM budget of :mod:`raft_tpu.ops.pallas.vmem_model`
(the 16 MiB default scope, or the raised limit a kernel compiles
under, :data:`KERNEL_VMEM_LIMITS`). Rules:

* ``tile-align``     — a tile whose lane (last) dim is not a multiple
  of 128 or whose sublane (second-minor) dim is not a multiple of the
  dtype's sublane count gets physically padded; flagged when the
  padding wastes more than 256 KiB of VMEM per buffer.
* ``vmem-budget``    — the summed residency of all tiles (double-
  buffered when their index map varies along the inner grid axis) and
  scratch exceeds ``VMEM_HEADROOM`` x the module's scoped limit.
* ``stale-budget``   — a module-level hard-coded ``*_BUDGET`` integer
  that disagrees (>25%) with the budget derived from the same module's
  declarations, i.e. a calibrated constant that drifted from the
  shapes it was calibrated against (the failure mode that motivated
  graft-lint: ``pq_scan._DECODE_CHUNK_BUDGET``).
* ``vmem-unmodeled`` — a ``pallas_call`` module whose shape
  expressions cannot be resolved and which has no entry in
  :data:`KERNEL_SHAPE_BINDINGS`: the kernel runs outside the resource
  model's sight.

The AST model intentionally assumes 4 B/element for tiles whose dtype
it cannot see (BlockSpecs carry no dtype) — a conservative
overestimate for the bf16/u8 tiles. The byte-accurate accounting,
including kernel-body intermediates, lives in
``raft_tpu.ops.pallas.vmem_model`` and is asserted against the kernels
in tests; these checkers are the coarse always-on guardrail.
"""
from __future__ import annotations

import ast
import dataclasses
import math
import os
import re
from typing import Dict, Iterator, List, Optional, Set, Tuple

from raft_tpu.ops.pallas.vmem_model import (
    PQ_SCAN_VMEM_LIMIT_BYTES,
    VMEM_HEADROOM,
    VMEM_LIMIT_BYTES,
)
from tools.graft_lint.core import Checker, LintModule, Violation

#: Representative shape bindings per kernel module (file stem). These
#: are the shapes the kernels are calibrated/benched at (the 1M-row
#: bench config); the checkers evaluate BlockSpec/scratch shape
#: expressions under them. A new kernel module must either use literal
#: shapes or register its bindings here — otherwise ``vmem-unmodeled``
#: fires.
KERNEL_SHAPE_BINDINGS: Dict[str, Dict[str, object]] = {
    # the glove200-pq benchmark cell: nibble codes (bpr 64, Kp 2048),
    # lists capped at 1,280 rows, k x refine 80, tiles of 128 (query,
    # probe) pairs
    "pq_scan": dict(
        qt=128, k=80, Kp=2048, g_lists=8, m=1280, gm=10240, bpr=64,
        banks=8,
    ),
    # the fused RaBitQ sign-bit kernel at the same 1M-row bench shape
    # (bpr = rot_dim/8 = 16 B/row of packed sign codes)
    "rabitq_scan": dict(
        qt=128, k=10, rot_dim=128, g_lists=8, m=1152, gm=9216, bpr=16,
        banks=8,
    ),
    "ivf_scan": dict(qt=128, k=10, d=128, m=1152, w=1024),
    # the fused CAGRA beam kernel at the 1M-row bench shape
    # (vmem_model.cagra_search_residency defaults)
    "cagra_search": dict(qt=32, itopk=160, width=8, deg=16, d=128,
                         # cagra_search.table_rows: deg + 3 id rows, tiled
                         table_rows=lambda deg: -(-(deg + 3) // 8) * 8),
    # the ICI ring top-k exchange at the 8-chip serving shape
    # (vmem_model.ring_topk_residency: n devices, B block rows, w = k;
    # kc = the scan-fused variant's candidate-tile width — 2k is the
    # widest that fits the 75% VMEM plan, see scan_ring_topk_residency)
    "ring_topk": dict(n=8, B=128, w=128, qt=32, kc=256,
                      # ring_topk.lanes: DMA slots hold whole lane tiles
                      lanes=lambda w: -(-w // 128) * 128),
    # tools/micro_layout.py — the layout microbench kernel
    "micro_layout": dict(QT=128, D=128, M=8704, block=(1, 8704, 128)),
}

#: Scoped-VMEM limits of the kernel modules that raise theirs past the
#: 16 MiB default through ``pltpu.CompilerParams(vmem_limit_bytes=...)``
#: and plan against the raised one.
KERNEL_VMEM_LIMITS: Dict[str, int] = {"pq_scan": PQ_SCAN_VMEM_LIMIT_BYTES}


def _module_limit(module: LintModule) -> int:
    stem = os.path.splitext(os.path.basename(module.path))[0]
    return KERNEL_VMEM_LIMITS.get(stem, VMEM_LIMIT_BYTES)


#: Padding waste (bytes, per buffer) below which a misaligned tile is
#: tolerated — k-sized top-k accumulators pad to a lane but cost a few
#: tens of KiB, which is not worth contorting the API over.
ALIGN_WASTE_THRESHOLD = 256 * 1024

_DTYPE_BYTES = {
    "float64": 8, "int64": 8, "uint64": 8,
    "float32": 4, "int32": 4, "uint32": 4,
    "bfloat16": 2, "float16": 2, "int16": 2, "uint16": 2,
    "int8": 1, "uint8": 1, "bool_": 1, "float8_e4m3fn": 1, "float8_e5m2": 1,
}

_BUDGET_NAME_RE = re.compile(r"BUDGET", re.IGNORECASE)

_EVAL_GLOBALS = {"__builtins__": {}, "min": min, "max": max, "len": len,
                 "int": int, "sum": sum, "abs": abs}


def _sublanes_for(itemsize: int) -> int:
    """Minimum sublane count of one physical tile: (8, 128) for 4-byte
    dtypes, (16, 128) for 2-byte, (32, 128) for 1-byte."""
    return max(8, 32 // max(itemsize, 1))


@dataclasses.dataclass
class SpecInfo:
    """One parsed BlockSpec/VMEM declaration."""

    node: ast.Call
    kind: str                    # "block" | "scratch"
    shape: Optional[Tuple[int, ...]]
    itemsize: int
    dtype_known: bool
    buffers: int                 # 2 when the index map tracks the inner grid axis
    unresolved: Optional[str] = None  # NameError detail when shape is None

    @property
    def nbytes(self) -> int:
        if not self.shape:
            return 0
        return int(math.prod(self.shape)) * self.itemsize * self.buffers

    def padded_nbytes(self) -> int:
        if not self.shape:
            return 0
        dims = list(self.shape)
        lane = dims[-1] if dims else 1
        sub = dims[-2] if len(dims) >= 2 else 1
        lead = int(math.prod(dims[:-2])) if len(dims) > 2 else 1
        sublanes = _sublanes_for(self.itemsize)
        plane = math.ceil(lane / 128) * 128
        # size-1 second-minor dims broadcast into one sublane group
        psub = sub if sub == 1 else math.ceil(sub / sublanes) * sublanes
        return lead * psub * plane * self.itemsize * self.buffers


class _PallasAliases(ast.NodeVisitor):
    def __init__(self) -> None:
        self.pl: Set[str] = set()
        self.pltpu: Set[str] = set()
        self.has_pallas_call = False

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        for a in node.names:
            bound = a.asname or a.name
            if node.module == "jax.experimental" and a.name == "pallas":
                self.pl.add(bound)
            elif node.module == "jax.experimental.pallas" and a.name == "tpu":
                self.pltpu.add(bound)

    def visit_Import(self, node: ast.Import) -> None:
        for a in node.names:
            if a.name == "jax.experimental.pallas":
                self.pl.add(a.asname or "pallas")
            elif a.name == "jax.experimental.pallas.tpu":
                self.pltpu.add(a.asname or "tpu")


def _aliases(module: LintModule) -> _PallasAliases:
    cached = getattr(module, "_graft_pallas", None)
    if cached is None:
        cached = _PallasAliases()
        cached.visit(module.tree)
        for node in ast.walk(module.tree):
            if (
                isinstance(node, ast.Attribute)
                and node.attr == "pallas_call"
            ):
                cached.has_pallas_call = True
                break
        module._graft_pallas = cached  # type: ignore[attr-defined]
    return cached


def _rooted_attr(node: ast.AST, roots: Set[str], attr: str) -> bool:
    return (
        isinstance(node, ast.Attribute)
        and node.attr == attr
        and isinstance(node.value, ast.Name)
        and node.value.id in roots
    )


def _eval_shape(
    node: ast.AST, bindings: Dict[str, int]
) -> Tuple[Optional[Tuple[int, ...]], Optional[str]]:
    """Evaluate a shape expression under restricted bindings. Returns
    (shape, unresolved-name) — exactly one is non-None."""
    try:
        code = compile(ast.Expression(body=node), "<graft-lint-shape>", "eval")
        val = eval(code, _EVAL_GLOBALS, dict(bindings))  # noqa: S307 — restricted
    except NameError as e:
        return None, str(e)
    except Exception as e:  # noqa: BLE001 — any non-shape expr: unresolved
        return None, f"{type(e).__name__}: {e}"
    if isinstance(val, int):
        val = (val,)
    if not (
        isinstance(val, tuple)
        and val
        and all(isinstance(d, int) and d > 0 for d in val)
    ):
        return None, f"not a positive int tuple: {val!r}"
    return tuple(val), None


def _lambda_tracks_inner_grid(node: ast.AST) -> bool:
    """True when an index_map lambda reads its second positional
    parameter (the inner grid axis) — Mosaic double-buffers that
    tile's DMA."""
    if not isinstance(node, ast.Lambda):
        return True  # unknown callable: assume the conservative 2x
    params = [p.arg for p in node.args.posonlyargs + node.args.args]
    if len(params) < 2:
        return False
    inner = params[1]
    return any(
        isinstance(n, ast.Name) and n.id == inner for n in ast.walk(node.body)
    )


def _dtype_itemsize(node: Optional[ast.AST]) -> Tuple[int, bool]:
    """(itemsize, known) from a dtype expression like ``jnp.float32``."""
    if isinstance(node, ast.Attribute) and node.attr in _DTYPE_BYTES:
        return _DTYPE_BYTES[node.attr], True
    if (
        isinstance(node, ast.Constant)
        and isinstance(node.value, str)
        and node.value in _DTYPE_BYTES
    ):
        return _DTYPE_BYTES[node.value], True
    return 4, False


def collect_specs(module: LintModule) -> List[SpecInfo]:
    """All BlockSpec / pltpu.VMEM declarations with evaluated shapes."""
    al = _aliases(module)
    if not (al.pl or al.pltpu):
        return []
    stem = os.path.splitext(os.path.basename(module.path))[0]
    bindings = KERNEL_SHAPE_BINDINGS.get(stem, {})
    out: List[SpecInfo] = []
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call):
            continue
        if _rooted_attr(node.func, al.pl, "BlockSpec"):
            shape_node = node.args[0] if node.args else None
            for kw in node.keywords:
                if kw.arg == "block_shape":
                    shape_node = kw.value
            if shape_node is None or isinstance(shape_node, ast.Constant):
                continue  # memory-space-only spec
            index_map = node.args[1] if len(node.args) > 1 else None
            for kw in node.keywords:
                if kw.arg == "index_map":
                    index_map = kw.value
            shape, unresolved = _eval_shape(shape_node, bindings)
            out.append(
                SpecInfo(
                    node=node, kind="block", shape=shape, itemsize=4,
                    dtype_known=False,
                    buffers=2 if index_map is not None and
                    _lambda_tracks_inner_grid(index_map) else 1,
                    unresolved=unresolved,
                )
            )
        elif _rooted_attr(node.func, al.pltpu, "VMEM"):
            if not node.args:
                continue
            shape, unresolved = _eval_shape(node.args[0], bindings)
            itemsize, known = _dtype_itemsize(
                node.args[1] if len(node.args) > 1 else None
            )
            out.append(
                SpecInfo(
                    node=node, kind="scratch", shape=shape, itemsize=itemsize,
                    dtype_known=known, buffers=1, unresolved=unresolved,
                )
            )
    return out


class TileAlignChecker(Checker):
    rule = "tile-align"
    doc = (
        "tile shape misaligned with the TPU (sublane x 128-lane) layout, "
        "wasting >256 KiB of padded VMEM per buffer."
    )

    def check(self, module: LintModule) -> Iterator[Violation]:
        for spec in collect_specs(module):
            if spec.shape is None:
                continue
            waste = spec.padded_nbytes() - spec.nbytes
            if waste > ALIGN_WASTE_THRESHOLD * spec.buffers:
                lane = spec.shape[-1]
                sub = spec.shape[-2] if len(spec.shape) >= 2 else 1
                hint = (
                    f"lane dim {lane} is not a multiple of 128"
                    if lane % 128
                    else f"sublane dim {sub} is not a multiple of "
                    f"{_sublanes_for(spec.itemsize)}"
                )
                yield self.violation(
                    module, spec.node,
                    f"{spec.kind} tile {'x'.join(map(str, spec.shape))} pads "
                    f"to the ({_sublanes_for(spec.itemsize)}, 128) layout "
                    f"wasting {waste // 1024} KiB of VMEM ({hint})"
                    + ("" if spec.dtype_known else "; assuming 4 B/elem"),
                )


class VmemBudgetChecker(Checker):
    rule = "vmem-budget"
    doc = (
        "summed tile+scratch residency (double-buffered along the inner "
        "grid axis) exceeds the headroom-adjusted scoped VMEM limit."
    )

    def check(self, module: LintModule) -> Iterator[Violation]:
        specs = collect_specs(module)
        if not specs:
            return
        total = sum(s.nbytes for s in specs)
        scope = _module_limit(module)
        limit = int(scope * VMEM_HEADROOM)
        if total > limit:
            al = _aliases(module)
            anchor = specs[0].node
            yield self.violation(
                module, anchor,
                f"modeled tile+scratch residency {total} B "
                f"({total / 2**20:.2f} MiB) exceeds the "
                f"{VMEM_HEADROOM:.0%} x {scope // 2**20} MiB budget ({limit} B) at the "
                "registered calibration shapes — shrink a block or chunk "
                "the kernel"
                + ("" if al.has_pallas_call else " (no pallas_call found)"),
            )


class StaleBudgetChecker(Checker):
    rule = "stale-budget"
    doc = (
        "hard-coded *_BUDGET byte constant disagrees >25% with the "
        "budget derived from the module's own tile/scratch declarations "
        "— derive it (see raft_tpu.ops.pallas.vmem_model) instead."
    )

    TOLERANCE = 0.25

    def check(self, module: LintModule) -> Iterator[Violation]:
        specs = collect_specs(module)
        if not any(s.shape for s in specs):
            return
        fixed = sum(s.nbytes for s in specs)
        derived = int(_module_limit(module) * VMEM_HEADROOM) - fixed
        if derived <= 0:
            return  # vmem-budget already covers this
        for node in module.tree.body:
            if not (
                isinstance(node, ast.Assign)
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and _BUDGET_NAME_RE.search(node.targets[0].id)
                and isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, int)
            ):
                continue
            hard = node.value.value
            if abs(hard - derived) / derived > self.TOLERANCE:
                yield self.violation(
                    module, node,
                    f"hard-coded {node.targets[0].id} = {hard} disagrees "
                    f"with the derived VMEM budget {derived} (limit x "
                    f"{VMEM_HEADROOM:.0%} minus {fixed} B of modeled "
                    "residents) — derive it from the resource model so "
                    "shape drift moves the cap instead of breaking the "
                    "compile",
                )


class VmemUnmodeledChecker(Checker):
    rule = "vmem-unmodeled"
    doc = (
        "pallas_call module whose tile shapes cannot be resolved and "
        "which has no entry in KERNEL_SHAPE_BINDINGS — the kernel runs "
        "outside the VMEM resource model."
    )

    def check(self, module: LintModule) -> Iterator[Violation]:
        al = _aliases(module)
        if not al.has_pallas_call:
            return
        specs = collect_specs(module)
        unresolved = [s for s in specs if s.shape is None]
        if not unresolved:
            return
        stem = os.path.splitext(os.path.basename(module.path))[0]
        registered = stem in KERNEL_SHAPE_BINDINGS
        s = unresolved[0]
        yield self.violation(
            module, s.node,
            f"{len(unresolved)} tile shape(s) could not be resolved "
            f"({s.unresolved}) — "
            + (
                "extend the module's entry in "
                if registered
                else "register representative shapes in "
            )
            + "tools/graft_lint/pallas_rules.py:KERNEL_SHAPE_BINDINGS so "
            "the VMEM model covers this kernel",
        )


CHECKERS = [
    TileAlignChecker(),
    VmemBudgetChecker(),
    StaleBudgetChecker(),
    VmemUnmodeledChecker(),
]
