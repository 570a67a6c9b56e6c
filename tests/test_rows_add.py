"""``ops/rows_add.py``: rows added at indices the caller promises distinct,
by the kernel (interpreted here) and by XLA's scatter under the same
promise, each against the scatter-add that is told nothing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from unicore_tpu.ops import _pallas, rows_add


def _case(n, count, width, seed, beyond):
    """``count`` distinct ascending indices among ``n`` rows, the last
    ``beyond`` of them out of bounds (distinct too), and rows to add."""
    rng = np.random.default_rng(seed)
    index = np.sort(rng.choice(n, count, replace=False)).astype(np.int32)
    if beyond:
        index[-beyond:] = n + 5 + np.arange(beyond)
    acc = jax.random.normal(jax.random.key(seed), (n, width), jnp.float32)
    rows = jax.random.normal(jax.random.key(seed + 1), (count, width),
                             jnp.float32)
    return acc, jnp.asarray(index), rows


def _plain(acc, index, rows):
    return acc.at[index].add(rows, mode="drop")


@pytest.mark.parametrize("width", [1024, 2304, 3072])
@pytest.mark.parametrize("count,group,slots", [
    (64, 16, 2),   # whole groups, two in flight
    (40, 12, 2),   # the group does not divide the trip: a short last group
    (40, 16, 3),   # three groups in flight, more slots than the tail needs
    (8, 16, 2),    # one short group: nothing to overlap with
], ids=["whole", "short-last-group", "three-slots", "one-group"])
def test_the_kernel_adds_what_the_scatter_adds(width, count, group, slots):
    """Interpreted, at the three cells' row widths (8, 18 and 24 sublanes a
    row): the same float32 adds as ``acc.at[index].add``, bit for bit, a
    row out of bounds skipped, every other row of ``acc`` untouched."""
    acc, index, rows = _case(96, count, width, seed=count + group, beyond=3)
    assert rows_add.kernel_takes(acc)
    got = rows_add.close_rows(rows_add.add_rows_at(
        rows_add.open_rows(acc), index, rows, group=group, slots=slots))
    np.testing.assert_array_equal(got, _plain(acc, index, rows))
    untouched = np.setdiff1d(np.arange(96), np.asarray(index))
    np.testing.assert_array_equal(got[untouched], acc[untouched])


@pytest.mark.parametrize("beyond", [0, 5], ids=["in-bounds", "some-dropped"])
def test_a_flat_accumulator_takes_the_scatter(beyond):
    """A 2-D accumulator goes through XLA's scatter-add, a row out of
    bounds dropped: the plain form's values."""
    acc, index, rows = _case(50, 24, 48, seed=3, beyond=beyond)
    got = rows_add.add_rows_at(acc, index, rows)
    np.testing.assert_array_equal(got, _plain(acc, index, rows))


def test_the_kernel_is_for_float32_rows_of_whole_lanes_on_a_chip():
    assert rows_add.kernel_takes(jnp.zeros((8, 256), jnp.float32))
    assert not rows_add.kernel_takes(jnp.zeros((8, 48), jnp.float32))
    assert not rows_add.kernel_takes(jnp.zeros((8, 256), jnp.bfloat16))
    was = _pallas._override
    _pallas.set_interpret(False)  # off the chip and not interpreted: XLA's
    try:
        assert not rows_add.kernel_takes(jnp.zeros((8, 256), jnp.float32))
    finally:
        _pallas.set_interpret(was)
