"""Shared Pallas plumbing: the interpret-mode switch used by every kernel
in ops/ (interpret=True runs kernels on any backend, e.g. the CPU test
platform; env: UNICORE_TPU_PALLAS_INTERPRET=1).

The gate resolves LAZILY per call, same discipline as the mode gates in
``softmax_dropout.py``: an env var set AFTER this module imported still
takes effect (tests and CLI subprocesses routinely import ops/ before
deciding on interpret mode — an import-time read silently ignored them).
An explicit :func:`set_interpret` call overrides the env either way;
``set_interpret(None)`` returns control to the env var.

This module also owns the ONE copy of the TPU kernel-geometry model —
tiling constants, the VMEM budget, the block pickers, and the
:class:`KernelGeometryError` every geometry refusal raises.  The static
auditor (``analysis/kernel_geometry.py``) reads the SAME constants, so
the dispatch gates and the auditor can never disagree about what a legal
block is.  Kernel modules declare their representative audit shapes here
too, via :func:`audit_case` — the contract ``unicore-tpu-lint --kernels``
enumerates (docs/lint.md, "Pallas kernel audit").
"""

import dataclasses
import os
from typing import Callable, Dict, Optional

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: TPU vector lane count — every block's last dim is tiled in 128s.
LANE = 128

#: Sublane (second-minor dim) tile multiple by element size: fp32/int32
#: tile as (8, 128), bf16/fp16 as (16, 128), int8/fp8 as (32, 128) — the
#: PR-12-round-5 bug class was exactly an int8 block on the 8-row grid.
SUBLANE_BY_ITEMSIZE = {8: 8, 4: 8, 2: 16, 1: 32}

#: Per-core VMEM we budget for one grid step's resident IO BLOCKS, double-
#: buffering included.  Every kernel prices its blocks against this number
#: (moved here from attention_fullrow.py so they share it).  It does not
#: model what Mosaic keeps beside the blocks; ``VMEM_LIMIT`` covers that.
VMEM_BUDGET = 12 * 1024 * 1024

#: The scoped-VMEM limit every kernel DECLARES to Mosaic and XLA
#: (``vmem_limit_bytes``; a v5e core has 128 MiB of VMEM, the default
#: scoped limit is 16 MiB).  It is the one mechanism for what the budget
#: above does not model — kernel-body temporaries and XLA's own scoped
#: use around the call: the exact-GELU int8 matmul took a modeled 10 MiB
#: plan to 21.06 MiB, and the full-row attention backward — 12 MiB
#: modeled, compiles alone — was refused at 16.70 MiB inside the whole
#: four-chip BERT train program.  2.7x the budget.
VMEM_LIMIT = 32 * 1024 * 1024

#: Longest row the full-row attention family will take resident
#: (attention_fullrow.py refuses beyond it; flash tiles instead).
MAX_ROW = 1024


class KernelGeometryError(ValueError):
    """A kernel refused a shape/tiling/budget it cannot run correctly.

    Raised instead of ``assert`` for user-facing geometry validation:
    asserts vanish under ``python -O``, and a geometry refusal must name
    the offending shape like every other refusal in this tree.
    """


def sublane_multiple(dtype) -> int:
    """The sublane tile multiple for ``dtype`` ((8, 128) fp32 → 8, ...)."""
    import numpy as np

    itemsize = np.dtype(dtype).itemsize
    return SUBLANE_BY_ITEMSIZE.get(itemsize, 8)


def pick_block(length: int, preferred: int, *, step: int = LANE) -> int:
    """Largest ``step``-multiple block <= ``preferred`` dividing ``length``
    (the flash-attention discipline; falls through to ``length`` itself
    when it is already <= ``preferred``)."""
    b = min(preferred, length)
    while b > step and length % b != 0:
        b -= step
    if b <= 0 or length % b != 0:
        raise KernelGeometryError(
            f"no {step}-multiple block <= {preferred} divides length "
            f"{length}; pad the dim to a {step} multiple first"
        )
    return b


def pick_block_pow2(length: int, limit: int) -> int:
    """Largest block <= ``limit`` dividing ``length`` reachable by halving
    (the quant-matmul discipline; worst case 1 — never raises)."""
    b = min(limit, length)
    while b > 1 and length % b != 0:
        b //= 2
    return b if length % b == 0 else 1


def block_bytes(shape, dtype) -> int:
    """Bytes of one resident block of ``shape``/``dtype``."""
    import numpy as np

    n = 1
    for d in shape:
        n *= int(d)
    return n * np.dtype(dtype).itemsize


def vmem_footprint(io_blocks, scratch_blocks=()) -> int:
    """The auditor's VMEM model: operand/output blocks are double-
    buffered by the Pallas pipeline (x2), scratch is single-buffered.
    ``*_blocks`` are ``(shape, dtype)`` pairs."""
    io = sum(block_bytes(s, d) for s, d in io_blocks)
    scratch = sum(block_bytes(s, d) for s, d in scratch_blocks)
    return 2 * io + scratch


def check_vmem_budget(kernel: str, io_blocks, scratch_blocks=(),
                      budget: int = VMEM_BUDGET) -> int:
    """Refuse (``KernelGeometryError``) when the modeled footprint
    exceeds ``budget``; returns the footprint in bytes otherwise."""
    total = vmem_footprint(io_blocks, scratch_blocks)
    if total > budget:
        raise KernelGeometryError(
            f"{kernel}: modeled VMEM footprint {total} B "
            f"(2x {len(list(io_blocks))} io blocks + scratch) exceeds the "
            f"{budget} B budget; shrink the block shapes"
        )
    return total


# ---------------------------------------------------------------------------
# Representative-shape audit cases (docs/lint.md, "Pallas kernel audit")
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AuditCase:
    """One representative invocation of a kernel's dispatch path.

    ``fn`` takes no arguments and calls the kernel entry point at the
    shapes the dispatch gate declares representative; the auditor runs it
    with ``pallas_call`` intercepted (the kernel body never executes), so
    cases are cheap enough for CPU CI.
    """

    name: str
    fn: Callable[[], object]
    path: str  # abspath of the module that registered it


#: name -> case; populated at import of each kernel module.
AUDIT_CASES: Dict[str, AuditCase] = {}


def audit_case(name: str):
    """Register a representative-shape audit case for ``--kernels``."""

    def deco(fn):
        path = os.path.abspath(fn.__code__.co_filename)
        AUDIT_CASES[name] = AuditCase(name, fn, path)
        return fn

    return deco

#: explicit override; None = follow UNICORE_TPU_PALLAS_INTERPRET
_override: Optional[bool] = None


def set_interpret(enabled: Optional[bool]):
    global _override
    _override = None if enabled is None else bool(enabled)


def interpret_enabled() -> bool:
    if _override is not None:
        return _override
    return os.environ.get("UNICORE_TPU_PALLAS_INTERPRET", "0") == "1"


def pallas_call(kernel, *, name: str, **kwargs):
    """``pl.pallas_call`` with this tree's interpret switch and VMEM
    limit.  ``name`` is required, stable and distinct per call site
    (``flash_fwd``, ``flash_bwd_dq``, ...): it names the Mosaic custom
    call in the compiled program, so a profiler trace says which kernel
    an event is, and a refactor of the kernel body does not rename it."""
    if not name:
        raise ValueError("pallas_call needs a non-empty name=")
    kwargs.setdefault(
        "compiler_params", pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT)
    )
    return pl.pallas_call(
        kernel, name=name, interpret=interpret_enabled(), **kwargs
    )


class ModeGate:
    """One ``auto``/``on``/``off`` dispatch gate (the ``softmax_dropout.py``
    pattern), shared by every gated kernel in ops/ so the resolution
    discipline can't drift between copies.  Resolved LAZILY per call:
    env var > setter > ``auto``; non-mode env values coerce to on/off
    (``0``/``false``/empty = off, anything else = on)."""

    MODES = ("auto", "on", "off")

    #: every constructed gate, in import order — the kernel auditor forces
    #: all gates "on" while running audit cases, then restores
    instances: list = []

    def __init__(self, name: str, env_var: str):
        self.name = name
        self.env_var = env_var
        self._mode: Optional[str] = None
        ModeGate.instances.append(self)

    def set(self, mode: Optional[str]) -> None:
        if mode is not None and mode not in self.MODES:
            raise ValueError(
                f"{self.name} mode {mode!r} not in {self.MODES}"
            )
        self._mode = mode

    def resolved(self) -> str:
        env = os.environ.get(self.env_var)
        if env is not None:
            if env in self.MODES:
                return env
            return "off" if env in ("0", "false", "") else "on"
        return self._mode or "auto"
