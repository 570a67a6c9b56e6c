"""HLO fusion audit (analysis/fusion_audit.py, --fusion-audit).

Parser units on canned HLO, a real compiled-program audit, the
fused-adam-shrinks-the-program claim (the audit proving a device-side win
without a device), and the CLI e2e the CI "Kernel parity smoke" greps.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from unicore_tpu.analysis import fusion_audit as fa

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CANNED = """\
HloModule jit_step

%fused_computation (param_0: f32[8,16]) -> f32[8,16] {
  %param_0 = f32[8,16]{1,0} parameter(0)
  %e = f32[8,16]{1,0} exponential(f32[8,16]{1,0} %param_0)
  ROOT %m = f32[8,16]{1,0} multiply(f32[8,16]{1,0} %e, f32[8,16]{1,0} %e)
}

%region_0.18 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.0 = f32[] add(f32[] %a, f32[] %b)
}

ENTRY %main (x: f32[8,16], w: f32[16,16]) -> f32[8,16] {
  %x = f32[8,16]{1,0} parameter(0)
  %w = f32[16,16]{1,0} parameter(1)
  %dot.1 = f32[8,16]{1,0} dot(f32[8,16]{1,0} %x, f32[16,16]{1,0} %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %t = f32[8,16]{1,0} tanh(f32[8,16]{1,0} %dot.1)
  %n = f32[8,16]{1,0} negate(f32[8,16]{1,0} %t)
  %c = f32[] constant(0)
  %r = f32[8]{0} reduce(f32[8,16]{1,0} %n, f32[] %c), dimensions={1}, to_apply=%region_0.18
  ROOT %fus = f32[8,16]{1,0} fusion(f32[8,16]{1,0} %n), kind=kLoop, calls=%fused_computation
}
"""


def test_audit_canned_hlo_counts():
    report = fa.audit_hlo(_CANNED)
    # ENTRY only: dot, tanh, negate, reduce, fusion are kernels; the two
    # parameters and the constant are not; called bodies are excluded
    assert report["kernels"] == 5
    assert report["instructions"] == 8
    assert report["fusions"] == 1
    assert report["fusion_kinds"] == {"kLoop": 1}
    # fusion bytes: one f32[8,16] operand + one f32[8,16] result = 1024
    assert report["fused_bytes_total"] == 1024
    assert report["top_fusions"][0]["name"] == "fus"
    # tanh -> negate is the one unfused elementwise chain (length 2)
    assert report["unfused_elementwise"] == 2
    assert report["top_unfused_chains"][0]["length"] == 2
    assert report["top_unfused_chains"][0]["ops"] == ["negate", "tanh"]


def test_audit_tolerates_garbage():
    assert fa.audit_hlo("")["kernels"] == 0
    assert fa.audit_hlo("not hlo at all\n{}\n")["fusions"] == 0
    assert fa.audit_hlo("")["comm"]["collectives"] == 0


_CANNED_COMM = """\
HloModule jit_reduce

%region_0.4 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.0 = f32[] add(f32[] %a, f32[] %b)
}

ENTRY %main (x: f32[4096]) -> f32[4096] {
  %x = f32[4096]{0} parameter(0)
  %reduce-scatter.1 = f32[2048]{0} reduce-scatter(f32[4096]{0} %x), channel_id=1, replica_groups={{0,1},{2,3}}, use_global_device_ids=true, dimensions={0}, to_apply=%region_0.4
  %all-reduce.1 = f32[2048]{0} all-reduce(f32[2048]{0} %reduce-scatter.1), channel_id=2, replica_groups={{0,2},{1,3}}, use_global_device_ids=true, to_apply=%region_0.4
  %cp = f32[2048]{0} collective-permute(f32[2048]{0} %all-reduce.1), channel_id=4, source_target_pairs={{0,1},{1,0}}
  ROOT %all-gather.1 = f32[4096]{0} all-gather(f32[2048]{0} %cp), channel_id=3, replica_groups={{0,1},{2,3}}, dimensions={0}, use_global_device_ids=true
}
"""


def test_comm_section_counts_and_tiers():
    """comm section: per-op counts, operand/result bytes, and the
    ici/dcn tier split keyed on whether a replica group spans pods
    (devices_per_pod=2: {0,1} is one pod, {0,2} crosses)."""
    report = fa.audit_hlo(_CANNED_COMM, devices_per_pod=2)
    comm = report["comm"]
    assert comm["collectives"] == 4
    assert comm["by_op"] == {
        "reduce-scatter": 1, "all-reduce": 1, "all-gather": 1,
        "collective-permute": 1,
    }
    tiers = comm["tiers"]
    # reduce-scatter (16384 in) + all-gather (8192 in) + the in-pod
    # collective-permute (8192 in) stay on ICI; the all-reduce crosses
    assert tiers["ici"]["ops"] == 3
    assert tiers["ici"]["operand_bytes"] == 16384 + 8192 + 8192
    assert tiers["dcn"] == {
        "ops": 1, "operand_bytes": 8192, "result_bytes": 8192,
    }
    assert comm["top"][0]["op"] == "reduce-scatter"
    assert comm["top"][0]["operand_bytes"] == 16384
    assert comm["top"][0]["result_bytes"] == 8192


def test_comm_section_async_start_and_multi_operand():
    """Async '-start' collectives carry a TUPLE result shape before the
    opcode — operand bytes must come from the operand list after the
    opcode's '(', never the result tuple; multi-operand reduces sum
    their operands."""
    hlo = (
        "HloModule jit_async\n\n"
        "ENTRY %main (x: f32[1024]) -> f32[1024] {\n"
        "  %x = f32[1024]{0} parameter(0)\n"
        "  %s = (f32[1024]{0}, f32[1024]{0}) all-reduce-start("
        "f32[1024]{0} %x), channel_id=1, replica_groups={{0,1}}, "
        "to_apply=%r\n"
        "  %t = f32[4]{0} all-reduce(f32[4]{0} %x, f32[4]{0} %x, "
        "f32[4]{0} %x), channel_id=2, replica_groups={{0,2}}, "
        "to_apply=%r\n"
        "  ROOT %d = f32[1024]{0} all-reduce-done((f32[1024]{0}, "
        "f32[1024]{0}) %s)\n"
        "}\n"
    )
    comm = fa.audit_hlo(hlo, devices_per_pod=2)["comm"]
    # the -done half carries no payload of its own and is not counted
    assert comm["by_op"] == {"all-reduce": 2}
    start = next(c for c in comm["top"] if c["name"] == "s")
    assert start["op"] == "all-reduce"
    assert start["operand_bytes"] == 4096  # ONE operand, not the tuple
    assert start["tier"] == "ici"
    multi = next(c for c in comm["top"] if c["name"] == "t")
    assert multi["operand_bytes"] == 3 * 16
    assert multi["tier"] == "dcn"


@pytest.mark.parametrize("attr,expected", [
    # the explicit list
    ("replica_groups={{0,1},{2,3}}", [[0, 1], [2, 3]]),
    # iota: 8 devices, 2 groups of 4 — the flat all-reduce of a pod=2 mesh
    ("replica_groups=[2,4]<=[8]", [[0, 1, 2, 3], [4, 5, 6, 7]]),
    ("replica_groups=[1,8]<=[8]", [[0, 1, 2, 3, 4, 5, 6, 7]]),
    # transposed iota: iota(8).reshape(2,4).T.reshape(4,2) — every group
    # pairs the same slot of both pods, i.e. crosses them
    ("replica_groups=[4,2]<=[2,4]T(1,0)",
     [[0, 4], [1, 5], [2, 6], [3, 7]]),
    ("replica_groups=[2,4]<=[2,2,2]T(1,0,2)",
     [[0, 1, 4, 5], [2, 3, 6, 7]]),
    ("source_target_pairs={{0,1},{1,0}}", [[0, 1], [1, 0]]),
    ("dimensions={0}", []),
])
def test_parse_groups_explicit_and_iota_forms(attr, expected):
    line = (
        "  %ar = f32[4096]{0} all-reduce(f32[4096]{0} %x), channel_id=1, "
        + attr + ", use_global_device_ids=true, to_apply=%r"
    )
    assert fa._parse_groups(line) == expected


def test_comm_section_classifies_iota_groups_by_tier():
    """The installed XLA prints regular groups in the iota form; a flat
    all-reduce over both pods must land on the dcn tier (it read as
    'unknown' -> 0 dcn bytes before), the in-pod one on ici."""
    hlo = (
        "HloModule jit_iota\n\n"
        "ENTRY %main (x: f32[1024]) -> f32[1024] {\n"
        "  %x = f32[1024]{0} parameter(0)\n"
        "  %a = f32[1024]{0} all-reduce(f32[1024]{0} %x), channel_id=1, "
        "replica_groups=[1,8]<=[8], use_global_device_ids=true, "
        "to_apply=%r\n"
        "  %b = f32[1024]{0} all-reduce(f32[1024]{0} %a), channel_id=2, "
        "replica_groups=[2,4]<=[8], use_global_device_ids=true, "
        "to_apply=%r\n"
        "  ROOT %c = f32[1024]{0} all-reduce(f32[1024]{0} %b), "
        "channel_id=3, replica_groups=[4,2]<=[2,4]T(1,0), "
        "use_global_device_ids=true, to_apply=%r\n"
        "}\n"
    )
    tiers = fa.audit_hlo(hlo, devices_per_pod=4)["comm"]["tiers"]
    assert tiers["dcn"]["ops"] == 2 and tiers["ici"]["ops"] == 1
    assert tiers["dcn"]["operand_bytes"] == 2 * 4096
    assert "unknown" not in tiers


def test_comm_section_untyped_operands_resolve_through_definitions():
    """The installed XLA prints operands as bare %names (no shape
    literal): operand bytes come from the defining instruction —
    parameters included — not 0."""
    hlo = (
        "HloModule jit_untyped\n\n"
        "ENTRY %main (x: f32[4096]) -> f32[4096] {\n"
        "  %x = f32[4,1024]{1,0} parameter(0)\n"
        "  %bitcast = f32[4096]{0} bitcast(%x)\n"
        "  ROOT %psum.7 = f32[4096]{0} all-reduce(%bitcast), channel_id=1, "
        "replica_groups={{0,1,2,3}}, use_global_device_ids=true, "
        "to_apply=%region_0.0\n"
        "}\n"
    )
    comm = fa.audit_hlo(hlo, devices_per_pod=2)["comm"]
    assert comm["tiers"]["dcn"] == {
        "ops": 1, "operand_bytes": 16384, "result_bytes": 16384,
    }


def test_comm_section_unknown_without_pod_info():
    """No devices_per_pod -> no tier claims: everything rolls up under
    'unknown' instead of guessing."""
    comm = fa.audit_hlo(_CANNED_COMM)["comm"]
    assert set(comm["tiers"]) == {"unknown"}
    assert comm["tiers"]["unknown"]["ops"] == 4


def test_audit_compiled_real_program():
    def step(x, w):
        h = jnp.tanh(x @ w)
        p = jax.nn.softmax(h, -1)
        return jnp.sum(p * h)

    compiled = (
        jax.jit(jax.grad(step, argnums=1))
        .lower(jnp.ones((8, 16)), jnp.ones((16, 16)))
        .compile()
    )
    report = fa.audit_compiled(compiled)
    assert report is not None
    assert report["fusions"] > 0
    assert report["kernels"] >= report["fusions"]
    assert report["fused_bytes_total"] > 0
    assert "memory" in report and report["memory"]["argument_bytes"] > 0
    # the grep-able block round-trips as JSON
    line = fa.format_report(report)
    assert line.startswith("FUSION-AUDIT ")
    assert json.loads(line[len("FUSION-AUDIT "):]) == json.loads(
        json.dumps(report)
    )


# ---------------------------------------------------------------------------
# trainer integration
# ---------------------------------------------------------------------------

def _tiny_trainer(**over):
    from argparse import Namespace

    from unicore_tpu.losses import LOSS_REGISTRY
    from unicore_tpu.models.bert import BertModel
    from unicore_tpu.tasks.unicore_task import UnicoreTask
    from unicore_tpu.trainer import Trainer

    kw = dict(
        seed=1, bf16=False, fp16=False, bf16_sr=False,
        allreduce_fp32_grad=False, fp16_init_scale=4, fp16_scale_window=None,
        min_loss_scale=1e-4, clip_norm=1.0, per_sample_clip_norm=0.0,
        data_parallel_size=-1, model_parallel_size=1, seq_parallel_size=1,
        pipeline_parallel_size=1, expert_parallel_size=1,
        zero_shard_optimizer=False, optimizer="adam", lr_scheduler="fixed",
        lr=[1e-3], adam_betas="(0.9, 0.999)", adam_eps=1e-8,
        weight_decay=0.01, force_anneal=None, lr_shrink=0.1,
        warmup_updates=0, ema_decay=-1.0, validate_with_ema=False,
        max_update=100, update_freq=[1], donate_train_state=False,
        fused_adam=False, fusion_audit=False,
    )
    kw.update(over)
    args = Namespace(**kw)

    class T(UnicoreTask):
        class _D:
            def pad(self):
                return 1

        dictionary = _D()

    model = BertModel(
        vocab_size=64, padding_idx=1, encoder_layers=2,
        encoder_embed_dim=32, encoder_ffn_embed_dim=64,
        encoder_attention_heads=4, max_seq_len=32, post_ln=True,
        dropout=0.0, emb_dropout=0.0, attention_dropout=0.0,
    )
    return Trainer(args, T(args), model, LOSS_REGISTRY["masked_lm"](T(args)))


def _batch(seed):
    r = np.random.RandomState(seed)
    tok = r.randint(4, 64, size=(8, 32)).astype(np.int64)
    tgt = np.where(r.rand(8, 32) < 0.2, tok, 1).astype(np.int64)
    return {"net_input": {"src_tokens": tok}, "target": tgt}


def test_trainer_one_shot_audit_logs_and_journals(caplog, tmp_path):
    """--fusion-audit runs ONCE after the first update, logs the grep-able
    block, and journals a fusion-audit event through telemetry."""
    import logging
    from argparse import Namespace

    from unicore_tpu import telemetry

    telemetry.reset()
    telemetry.configure(
        Namespace(
            save_dir=None, telemetry_dir=str(tmp_path),
            telemetry_sample_interval=0, profile_steps=None,
        ),
        rank=0, role="trainer",
    )
    try:
        tr = _tiny_trainer(fusion_audit=True)
        tr.init_state(_batch(1))
        with caplog.at_level(logging.INFO, logger="unicore_tpu.trainer"):
            tr.train_step([_batch(1)])
            tr.train_step([_batch(2)])
        lines = [
            r.message for r in caplog.records
            if r.message.startswith("FUSION-AUDIT ")
        ]
        assert len(lines) == 1, "the audit is one-shot"
        report = json.loads(lines[0][len("FUSION-AUDIT "):])
        assert report["fusions"] > 0 and report["kernels"] > 0
        journal = telemetry.journal_path()
        events = [
            json.loads(ln)
            for ln in open(journal, encoding="utf-8")
            if ln.strip()
        ]
        audits = [e for e in events if e.get("kind") == "fusion-audit"]
        assert len(audits) == 1 and audits[0]["fusions"] == report["fusions"]
    finally:
        telemetry.reset()


def test_audit_proves_fused_adam_shrinks_program():
    """The device-side claim, checked without a device: --fused-adam
    replaces O(leaves) optimizer ops with O(buffers), so the optimized
    train-step program has FEWER schedulable kernels and instructions."""
    counts = {}
    for fused in (False, True):
        tr = _tiny_trainer(fused_adam=fused)
        tr.init_state(_batch(1))
        tr.train_step([_batch(1)])
        sample, w = tr._prepare_sample_or_dummy(_batch(1))
        counts[fused] = tr.fusion_audit(sample, w)
    assert counts[True]["kernels"] < counts[False]["kernels"]
    assert counts[True]["instructions"] < counts[False]["instructions"]


def test_audit_reads_the_program_the_update_ran():
    """The audit's arguments come from the method that dispatches the
    update: after a flush (no running sums on the host) it still audits
    the one train-step program, not a second one that starts from None."""
    tr = _tiny_trainer()
    tr.train_step([_batch(1)])
    sample, w = tr._prepare_sample_or_dummy(_batch(1))
    with_sums = tr.fusion_audit(sample, w)
    tr.flush_metrics()
    assert tr._macc is None
    after_flush = tr.fusion_audit(sample, w)
    assert after_flush["program"] == with_sums["program"] == "train_step"
    for key in ("kernels", "fusions", "instructions"):
        assert after_flush[key] == with_sums[key]
    tr.train_step([_batch(2)])
    assert tr._compiled_programs() == {"train_step": 1}


# ---------------------------------------------------------------------------
# CLI e2e (the CI "Kernel parity smoke" greps this test's -s output)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_cli_fusion_audit(tmp_path, capsys):
    """Tiny BERT CPU run with --fusion-audit --fused-adam: the log must
    carry one FUSION-AUDIT block with a NONZERO fusion count and ZERO
    'recompile after warmup' warnings (the audit's AOT compile must not
    disturb the jit-cache recompile watch)."""
    from test_e2e_train import _JAX_CACHE, CLI_TIMEOUT, RUNNER

    data = tmp_path / "data"
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "examples", "bert", "make_example_data.py"),
         str(data), "256", "16"],
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr
    argv = [
        str(data),
        "--task", "bert", "--loss", "masked_lm", "--arch", "bert_tiny",
        "--optimizer", "adam", "--lr-scheduler", "fixed", "--lr", "1e-3",
        "--fused-adam", "--fusion-audit", "--fused-norm", "auto",
        "--max-update", "8", "--max-epoch", "4", "--batch-size", "8",
        "--max-seq-len", "64", "--compile-warmup-updates", "4",
        "--log-interval", "1", "--log-format", "simple",
        "--disable-validation", "--no-progress-bar",
        "--save-dir", str(tmp_path / "ckpt"),
        "--tmp-save-dir", str(tmp_path / "tmp"),
        "--num-workers", "0", "--seed", "1",
        "--required-batch-size-multiple", "1",
    ]
    proc = subprocess.run(
        [sys.executable, "-c",
         RUNNER.format(repo=REPO, argv=argv, cache=_JAX_CACHE)],
        capture_output=True, text=True, timeout=CLI_TIMEOUT, cwd=REPO,
    )
    out = proc.stdout + proc.stderr
    with capsys.disabled():
        print(out)
    assert proc.returncode == 0, out[-4000:]
    audit_lines = [
        ln for ln in out.splitlines() if "FUSION-AUDIT " in ln
    ]
    assert len(audit_lines) == 1, "one-shot audit in the training log"
    report = json.loads(
        audit_lines[0].split("FUSION-AUDIT ", 1)[1]
    )
    assert report["fusions"] > 0, "audit must report a nonzero fusion count"
    assert "recompile after warmup" not in out
