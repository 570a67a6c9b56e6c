"""``acc[index[r]] += rows[r]`` for rows that are TOLD to lie at distinct
indices: the combine of a sorted, tiled expert layer (``modules/
latent_moe.py``), where a trip's rows are all one expert's and a token
pairs with an expert at most once.

XLA's scatter-add is told nothing about its indices, so each row's
read-add-write waits for the one before it: 0.27 us a row of 9 KB on a
v5e, a tenth of the memory bandwidth, and what it can be told does not
help (PERF.md, PR 44).  Distinct rows can move together.  The kernel
leaves ``acc`` in HBM, aliased to its result, and walks the rows in groups
of ``GROUP``: a grid step starts the NEXT group's row copies HBM -> VMEM,
waits for its own group's, adds the group's block of updates (which the
Pallas pipeline brought in), and starts the copies back, which a later
step waits for before their slot is read into again.  So reads, adds and writes of neighbouring groups
overlap, and what makes that safe is exactly what the caller promises: no
index occurs twice in a call, so no copy meets another's row.

An index outside ``0 .. n - 1`` is skipped (the layout's rows without a
pair carry ``n + row``); with ``mode="drop"`` XLA's scatter-add does the
same, and it is the plain form: off the chip, at shapes the kernel does
not take and where a caller has too few rows to pay for the kernel's
layout (:func:`add_rows_at`).

The accumulator's layout is the kernel's: ``(n, width / 128, 128)``, so a
row is whole ``(8, 128)`` tiles that one copy moves (a row of a 2-D
``(n, width)`` array is one sublane of ``width / 128`` tiles, which Mosaic
will not slice).  A caller in a loop keeps that shape for the whole loop
and reshapes once after it (:func:`open_rows`, :func:`close_rows`):
reshaping around every call would relayout the whole array each time.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from unicore_tpu.platform_utils import on_tpu

from ._pallas import (
    LANE, KernelGeometryError, audit_case, interpret_enabled, pallas_call,
)

#: rows a grid step moves each way, and the groups whose copies may be in
#: flight at once.  On the chip (PERF.md, PR 44: 1,024 rows of 2,304, in a
#: loop by themselves, a group's copies written out) groups of 16 / 32 /
#: 64 take 86 / 76 / 73 us against the scatter-add's 281, and a third slot
#: nothing
GROUP = 32
SLOTS = 2

#: rows of a group that one trip of the kernel's inner loops handles: a
#: whole group written out (76 us) cost Mosaic two seconds at each of a
#: step's sixteen call sites, a row to the trip runs at 106 us, four at 84
UNROLL = 4


def kernel_takes(acc):
    """Whether the kernel can add rows to ``acc`` (n, width): on a TPU (or
    interpreted), float32 rows of whole lanes."""
    return (
        (on_tpu() or interpret_enabled())
        and acc.dtype == jnp.float32
        and acc.shape[-1] % LANE == 0
    )


def open_rows(acc):
    """``acc`` (n, width) in the kernel's layout, (n, width / 128, 128)."""
    return acc.reshape(acc.shape[0], -1, LANE)


def close_rows(acc3):
    """The kernel's layout back to (n, width)."""
    return acc3.reshape(acc3.shape[0], -1)


def _kernel(index_ref, rows_ref, _, acc_ref, buf, read_sem, write_sem, *,
            group, slots, n, steps):
    """Grid step ``j`` of ``steps + slots``: free the slot group ``j``
    will read into (wait for the writes of group ``j - slots``), start
    group ``j``'s reads, then finish group ``j - 1``: wait for its reads,
    add its block of rows, start its writes.  The ``slots`` steps past the
    last group only wait for the writes still in flight."""
    j = pl.program_id(0)

    def each(g, write, what):
        """Start, or wait for, the copies of group ``g``'s rows, ``UNROLL``
        rows to the trip of a loop."""
        slot = g % slots

        def some(k, carry):
            for u in range(UNROLL):
                r = k * UNROLL + u
                at = index_ref[g * group + r]
                hbm, vmem = acc_ref.at[at], buf.at[slot, r]
                copy = (pltpu.make_async_copy(vmem, hbm, write_sem.at[slot])
                        if write else
                        pltpu.make_async_copy(hbm, vmem, read_sem.at[slot]))
                pl.when(at < n)(getattr(copy, what))
            return carry

        jax.lax.fori_loop(0, group // UNROLL, some, 0)

    @pl.when(j >= slots)
    def _():
        each(j - slots, True, "wait")

    @pl.when(j < steps)
    def _():
        each(j, False, "start")

    @pl.when((j >= 1) & (j <= steps))
    def _():
        each(j - 1, False, "wait")
        slot = (j - 1) % slots
        buf[slot] = buf[slot] + rows_ref[...]
        each(j - 1, True, "start")


def _add_rows_kernel(acc3, index, rows3, group, slots):
    n, sub, _ = acc3.shape
    count = index.shape[0]
    if group % UNROLL:
        raise KernelGeometryError(
            f"moe_rows_add: a group of {group} rows is no multiple of "
            f"{UNROLL}")
    steps = -(-count // group)
    if steps * group != count:
        # the last group's missing rows: skipped like a row without a pair
        index = jnp.pad(index, (0, steps * group - count), constant_values=n)
    return pallas_call(
        functools.partial(_kernel, group=group, slots=slots, n=n,
                          steps=steps),
        name="moe_rows_add",
        out_shape=jax.ShapeDtypeStruct(acc3.shape, acc3.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(steps + slots,),
            in_specs=[
                # step j adds group j - 1's block
                pl.BlockSpec(
                    (group, sub, LANE),
                    lambda j, index: (jnp.clip(j - 1, 0, steps - 1), 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((slots, group, sub, LANE), acc3.dtype),
                pltpu.SemaphoreType.DMA((slots,)),
                pltpu.SemaphoreType.DMA((slots,)),
            ],
        ),
        # operand 2 (after the prefetched indices and the rows) is the
        # accumulator: updated in place
        input_output_aliases={2: 0},
    )(index, rows3, acc3)


def add_rows_at(acc, index, rows, *, group=GROUP, slots=SLOTS):
    """``acc`` with ``rows[r]`` added at ``index[r]``, for indices the
    caller PROMISES distinct and ascending; one outside ``acc`` is
    skipped.  ``acc`` (n, width / 128, 128) (:func:`open_rows`, where
    :func:`kernel_takes`) goes through the kernel, ``acc`` (n, width)
    through XLA's scatter-add; ``index`` (R,) int32; ``rows`` (R, width)
    float32.  The same float32 adds either way.

    The scatter is told nothing of the promise, on the chip's word
    (PERF.md, PR 44): ``unique_indices`` buys nothing there (0.275 us a
    row with it and without), and ``indices_are_sorted`` makes the TPU
    compiler pick a form twelve times slower (3.3 us a row)."""
    if acc.ndim == 3:
        return _add_rows_kernel(acc, index, open_rows(rows), group, slots)
    return acc.at[index].add(rows, mode="drop")


@audit_case("moe-rows-add")
def _audit_rows_add():
    """A wide trip of the gated experts at Mellum2's width: 1,024 rows of
    2,304 float32 (18 sublanes a row, padded to 24 in VMEM) in groups of
    ``GROUP``, the last index out of bounds."""
    acc = jnp.zeros((4096, 18, LANE), jnp.float32)
    index = jnp.arange(1024, dtype=jnp.int32).at[-1].set(4096 + 7)
    add_rows_at(acc, index, jnp.zeros((1024, 18 * LANE), jnp.float32))
