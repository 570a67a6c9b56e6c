"""How ``correct`` is decided, at a size a test run can hold: the plain
references follow the program's own compiled step; a timed path broken
underneath comes out as not correct; so does the control (the reference in
the nearest lower precision in the program's place: bfloat16 for these
float32 copies of the cells; int8 for the bfloat16 cells themselves, on the
chip — PERF.md section 2)."""

import json

import pytest

import bench_tiny
from bench_tiny import fake_chip, load, tiny_checkout
from benchmark import control, harness

CELLS = ["bert_base.train_mlm512", "unimol.train_mol256"]


def checks_of(out):
    return {c["name"]: c["value"] for c in out["checks"]}


@pytest.mark.parametrize("cell", CELLS)
def test_reference_follows_the_program_in_float32(cell, run_tiny):
    """Program and reference both in float32: what is left is summation
    order, so the two agree to a few parts in a million — the references'
    equations, the optimizer and the driver's bookkeeping are the
    program's."""
    out, last = run_tiny(cell, float32=True)
    got = checks_of(out)
    assert last["correct"] is True and last["failed"] == 0
    for step in (1, 2, 3):
        assert got[f"loss_rel_gap.step{step}"] < 2e-6
    assert got["first_grad_norm_gap.worst_leaf"] < 5e-5
    assert got["param_change_norm_gap.worst_leaf"] < 5e-5
    assert got["recompiles_in_window"] == 0
    assert set(last["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert last["metrics"]["train_tokens_per_s"]["value"] > 0
    assert set(last["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.mark.parametrize("cell", CELLS)
def test_sound_bfloat16_run_is_correct_on_a_large_seed(cell, run_tiny):
    out, last = run_tiny(cell, seed=2 ** 31 + 977)
    assert last["correct"] is True, out["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_tiny_epoch_holds_whole_batches(cell, tmp_path):
    """No batch of a tiny cell's epoch is short (a shape of its own, which
    a window that reaches it compiles), and the feed counts its epochs."""
    bench_tiny.assert_whole_batches(tmp_path, cell)


def _state_unchanged(monkeypatch):
    from unicore_tpu.trainer import Trainer

    real = Trainer._apply_update

    def frozen(self, state, *args, **kwargs):
        _new_state, step_metrics = real(self, state, *args, **kwargs)
        return state, step_metrics

    monkeypatch.setattr(Trainer, "_apply_update", frozen)
    return {"first_grad_norm_gap.worst_leaf", "param_change_norm_gap.worst_leaf"}


def _half_batch(monkeypatch):
    import jax

    from unicore_tpu.trainer import Trainer

    real = Trainer.train_step

    def halved(self, samples):
        def front(a):
            return a[: max(1, a.shape[0] // 2)]

        return real(self, [jax.tree_util.tree_map(front, s) for s in samples])

    monkeypatch.setattr(Trainer, "train_step", halved)
    return {"loss_rel_gap.step1"}


@pytest.mark.parametrize("cell,fault", [
    (CELLS[0], _state_unchanged), (CELLS[0], _half_batch),
    (CELLS[1], _state_unchanged),
])
def test_a_broken_timed_path_is_not_correct(cell, fault, run_tiny, monkeypatch):
    """The rest of a run with the timed path broken underneath: a step that
    returns its state unchanged, and a step that leaves out half of the
    batch.  ``correct`` comes out false, by the number that is there to
    catch that fault."""
    must_fail = fault(monkeypatch)
    out, last = run_tiny(cell, float32=True)
    assert last["correct"] is False
    failed = {c["name"] for c in out["checks"] if not c["value"] <= c["limit"]}
    assert must_fail <= failed, (must_fail, failed)


@pytest.mark.parametrize("cell", CELLS)
def test_the_lower_precision_control_is_not_correct(cell, tmp_path):
    """The reference with every dense product in the precision below the
    configuration's, in the program's place, held to the limits a sound
    run of the same cell passes (the float32 test above)."""
    root, base = tiny_checkout(tmp_path, cell, float32=True)
    c = harness.Cell(load(root + "/BENCHMARK.json"), cell, base, root)
    checks = control.control_checks(c, seed=2 ** 31 + 3, precision="bfloat16")
    assert harness.report_checks(checks) is False
    # int8 is a lower precision still, and fails the same limits
    assert harness.report_checks(
        control.control_checks(c, seed=2 ** 31 + 3, precision="int8")
    ) is False
