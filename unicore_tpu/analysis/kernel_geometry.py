"""The Pallas auditor's kernel-geometry model (`unicore-tpu-lint --kernels`).

One captured ``pallas_call`` (see ``pallas_audit.py`` for how captures are
made) is a grid plus a list of :class:`BlockUse` rows — one per operand,
output, and scratch buffer.  Index maps are tiny pure lambdas, so rather
than symbolically reasoning about them this module **concretely enumerates
the grid**: every index map is executed at every program id (capped; see
``GRID_ENUM_CAP``) and the resulting block origins are checked against the
operand extents.  A map that READS a scalar-prefetch operand (a block map's
ids: ``ops/flash_attention.py``) is handed the values the audit case passed
(``pallas_audit.py`` closes each captured map over them), so the blocks a
map can name are checked like any computed index.  The constants the
checks price against (``LANE``, ``SUBLANE_BY_ITEMSIZE``, ``VMEM_BUDGET``)
are imported from ``ops/_pallas.py`` — the SAME values the dispatch gates
use, so the auditor and the runtime can never disagree about what a legal
block is.

Checks implemented here (findings are plain strings; ``pallas_audit.py``
attaches them to the call site as lint violations):

``check_block_bounds``  (a) every index map's block origin x block shape
                        stays inside the operand for every program id;
``check_tiling``        (b) last-dim %128 and dtype-correct sublane
                        multiples on every operand/output block.  Scratch
                        is exempt: whole VMEM arrays are padded to native
                        tiles by Mosaic, the sharp constraints bind on the
                        HBM<->VMEM block pipeline;
``check_vmem``          (c) per-program resident bytes — operand/output
                        blocks double-buffered plus scratch — against the
                        shared budget;
``revisit_axes``        (d, model half) grid axes a multi-step output
                        ignores: the same output block is revisited, so
                        the kernel body must guard or accumulate (the AST
                        half lives in ``pallas_audit.py``);
``input_axes``          (e, model half) grid axes on which any INPUT
                        block varies — the axes a per-block PRNG seed must
                        cover (per-axis generalization of the PR-10
                        constant-seed taint rule).
"""

import dataclasses
import itertools
from typing import Callable, Iterable, List, Optional, Sequence, Set, Tuple

from unicore_tpu.ops._pallas import (
    LANE,
    VMEM_BUDGET,
    sublane_multiple,
    vmem_footprint,
)

#: refuse to enumerate grids beyond this many program ids (a kernel with a
#: bigger grid gets an "opaque" finding instead of a silent pass)
GRID_ENUM_CAP = 200_000


@dataclasses.dataclass(frozen=True)
class BlockUse:
    """One operand/output/scratch buffer of a captured ``pallas_call``."""

    kind: str  # "in" | "out" | "scratch"
    #: position within its kind (operand 0, 1, ... / output 0, 1, ...)
    index: int
    block_shape: Tuple[int, ...]
    dtype: object
    #: full array extents; equals ``block_shape`` for scratch
    array_shape: Tuple[int, ...]
    #: program ids -> block indices; None for scratch
    index_map: Optional[Callable] = None

    @property
    def label(self) -> str:
        return f"{self.kind}[{self.index}]"


@dataclasses.dataclass(frozen=True)
class CapturedKernel:
    """One intercepted ``pallas_call`` at representative shapes."""

    case: str  # audit-case name that triggered it
    path: str  # abspath of the module holding the call site
    line: int  # first line of the call expression
    grid: Tuple[int, ...]
    uses: Tuple[BlockUse, ...]
    #: the kernel's ``name=``: what a profiler trace calls it
    name: str = ""

    def inputs(self) -> List[BlockUse]:
        return [u for u in self.uses if u.kind == "in"]

    def outputs(self) -> List[BlockUse]:
        return [u for u in self.uses if u.kind == "out"]

    def scratch(self) -> List[BlockUse]:
        return [u for u in self.uses if u.kind == "scratch"]


class OpaqueGeometry(Exception):
    """An index map could not be concretely enumerated (non-integer
    result, wrong arity, grid beyond :data:`GRID_ENUM_CAP`, ...)."""


def _grid_points(grid: Sequence[int]) -> Iterable[Tuple[int, ...]]:
    total = 1
    for g in grid:
        total *= int(g)
    if total > GRID_ENUM_CAP:
        raise OpaqueGeometry(
            f"grid {tuple(grid)} has {total} program ids, beyond the "
            f"enumeration cap {GRID_ENUM_CAP}"
        )
    return itertools.product(*(range(int(g)) for g in grid))


def _call_map(use: BlockUse, pid: Tuple[int, ...]) -> Tuple[int, ...]:
    try:
        out = use.index_map(*pid)
    except Exception as exc:  # arity mismatch, traced op, ...
        raise OpaqueGeometry(
            f"{use.label} index map failed at program id {pid}: {exc!r}"
        )
    if not isinstance(out, tuple):
        out = (out,)
    try:
        return tuple(int(v) for v in out)
    except Exception:
        raise OpaqueGeometry(
            f"{use.label} index map returned non-integer block indices "
            f"{out!r} at program id {pid}"
        )


def check_block_bounds(cap: CapturedKernel) -> List[str]:
    """(a) ``index * block + block <= extent`` per dim, per program id."""
    findings: List[str] = []
    for use in cap.inputs() + cap.outputs():
        if use.index_map is None:
            continue
        for pid in _grid_points(cap.grid):
            idx = _call_map(use, pid)
            if len(idx) != len(use.block_shape):
                findings.append(
                    f"{use.label} index map yields {len(idx)} indices for "
                    f"a rank-{len(use.block_shape)} block"
                )
                break
            bad = None
            for d, (i, b, ext) in enumerate(
                zip(idx, use.block_shape, use.array_shape)
            ):
                if i < 0 or (i * b) + b > ext:
                    bad = (d, i)
                    break
            if bad is not None:
                d, i = bad
                findings.append(
                    f"{use.label} block {use.block_shape} at program id "
                    f"{pid} maps to block index {idx}: dim {d} spans "
                    f"[{i * use.block_shape[d]}, "
                    f"{(i + 1) * use.block_shape[d]}) outside extent "
                    f"{use.array_shape[d]}"
                )
                break  # one finding per use is enough
    return findings


def check_tiling(cap: CapturedKernel) -> List[str]:
    """(b) lane/sublane legality of every operand/output block.

    A last dim is legal when it is a 128-multiple OR covers the operand's
    full last dim (Mosaic pads short trailing dims).  A sublane dim is
    legal when it is a multiple of the dtype tile (8 fp32 / 16 bf16 /
    32 int8), covers the full dim, or is 1 (a broadcast/stat row).
    """
    findings: List[str] = []
    for use in cap.inputs() + cap.outputs():
        blk = use.block_shape
        if not blk:
            continue
        last = blk[-1]
        if last % LANE != 0 and last != use.array_shape[-1]:
            findings.append(
                f"{use.label} block {blk} last dim {last} is neither a "
                f"{LANE}-multiple nor the full operand dim "
                f"{use.array_shape[-1]}"
            )
        if len(blk) >= 2:
            sub = blk[-2]
            mult = sublane_multiple(use.dtype)
            if sub % mult != 0 and sub != use.array_shape[-2] and sub != 1:
                findings.append(
                    f"{use.label} block {blk} sublane dim {sub} is not a "
                    f"multiple of {mult} required for "
                    f"{_dtype_name(use.dtype)} (nor the full dim or 1)"
                )
    return findings


def check_vmem(cap: CapturedKernel, budget: int = VMEM_BUDGET) -> List[str]:
    """(c) double-buffered io blocks + scratch vs the shared budget."""
    io = [(u.block_shape, u.dtype) for u in cap.inputs() + cap.outputs()]
    scratch = [(u.block_shape, u.dtype) for u in cap.scratch()]
    total = vmem_footprint(io, scratch)
    if total > budget:
        return [
            f"modeled VMEM footprint {total} B (2x {len(io)} io blocks "
            f"+ {len(scratch)} scratch) exceeds the {budget} B budget"
        ]
    return []


def varying_axes(use: BlockUse, grid: Sequence[int]) -> Set[int]:
    """Grid axes along which ``use``'s block index varies, by exhaustive
    comparison of the enumerated map against its axis-0 projection."""
    if use.index_map is None:
        return set()
    axes: Set[int] = set()
    for pid in _grid_points(grid):
        base = _call_map(use, pid)
        for a in range(len(grid)):
            if a in axes or pid[a] == 0:
                continue
            proj = list(pid)
            proj[a] = 0
            if _call_map(use, tuple(proj)) != base:
                axes.add(a)
        if len(axes) == len(grid):
            break
    return axes


def revisit_axes(cap: CapturedKernel, use: BlockUse) -> Set[int]:
    """(d) multi-step grid axes this OUTPUT ignores — each such axis
    revisits the same output block on every step."""
    varying = varying_axes(use, cap.grid)
    return {
        a for a, g in enumerate(cap.grid) if int(g) > 1 and a not in varying
    }


def input_axes(cap: CapturedKernel) -> Set[int]:
    """(e) multi-step grid axes on which any INPUT block varies — the
    axes that deliver fresh data, hence the axes a per-block PRNG seed
    must be mixed with."""
    axes: Set[int] = set()
    for use in cap.inputs():
        axes |= varying_axes(use, cap.grid)
    return {a for a in axes if int(cap.grid[a]) > 1}


def _dtype_name(dtype) -> str:
    import numpy as np

    try:
        return np.dtype(dtype).name
    except Exception:
        return str(dtype)
