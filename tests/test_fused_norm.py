"""Pallas fused LayerNorm/RMSNorm vs jnp reference — the dim/dtype sweep
analogue of the reference's LN kernel coverage (FUSED_LAYER_NORM_SUPPORT_DIM,
modules/layer_norm.py:48 — here any dim works, no whitelist)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from unicore_tpu.ops.fused_norm import fused_layer_norm, fused_rms_norm


def ln_ref(x, w, b, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    return (((xf - mu) * jax.lax.rsqrt(var + eps)) * w + b).astype(x.dtype)


def rms_ref(x, w, eps=1e-6):
    xf = x.astype(jnp.float32)
    ms = (xf ** 2).mean(-1, keepdims=True)
    return ((xf * jax.lax.rsqrt(ms + eps)) * w).astype(x.dtype)


@pytest.mark.parametrize("D", [64, 192, 768, 1024])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_layer_norm_forward(D, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 24, D), dtype) * 3 + 1
    w = jax.random.normal(jax.random.PRNGKey(1), (D,), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(2), (D,), jnp.float32)
    out = fused_layer_norm(x, w, b)
    ref = ln_ref(x, w, b)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    assert float(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)).max()) < tol


def test_layer_norm_gradients():
    D = 256
    x = jax.random.normal(jax.random.PRNGKey(0), (8, D)) * 2
    w = jax.random.normal(jax.random.PRNGKey(1), (D,))
    b = jax.random.normal(jax.random.PRNGKey(2), (D,))

    g1 = jax.grad(lambda *a: jnp.sum(fused_layer_norm(*a) ** 2), argnums=(0, 1, 2))(
        x, w, b
    )
    g2 = jax.grad(lambda *a: jnp.sum(ln_ref(*a) ** 2), argnums=(0, 1, 2))(x, w, b)
    for name, a, r in zip(["dx", "dw", "db"], g1, g2):
        scale = max(1.0, float(jnp.abs(r).max()))
        assert float(jnp.abs(a - r).max()) / scale < 1e-5, name


def test_rms_norm_forward_and_grad():
    D = 512
    x = jax.random.normal(jax.random.PRNGKey(0), (16, D)) * 2
    w = jax.random.normal(jax.random.PRNGKey(1), (D,))
    out = fused_rms_norm(x, w)
    ref = rms_ref(x, w)
    assert float(jnp.abs(out - ref).max()) < 1e-5

    g1 = jax.grad(lambda *a: jnp.sum(fused_rms_norm(*a) ** 2), argnums=(0, 1))(x, w)
    g2 = jax.grad(lambda *a: jnp.sum(rms_ref(*a) ** 2), argnums=(0, 1))(x, w)
    for name, a, r in zip(["dx", "dw"], g1, g2):
        scale = max(1.0, float(jnp.abs(r).max()))
        assert float(jnp.abs(a - r).max()) / scale < 1e-5, name


def test_odd_row_counts():
    # N not divisible by the preferred row block: falls back to smaller blocks
    D = 128
    x = jax.random.normal(jax.random.PRNGKey(0), (7, D))
    w = jnp.ones((D,))
    b = jnp.zeros((D,))
    out = fused_layer_norm(x, w, b)
    ref = ln_ref(x, w, b)
    assert float(jnp.abs(out - ref).max()) < 1e-5


@pytest.mark.parametrize("module_cls", ["ln", "rms"])
def test_module_use_pallas_matches_xla(module_cls):
    from unicore_tpu.modules import LayerNorm, RMSNorm

    D = 192
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 7, D)) * 2
    if module_cls == "ln":
        m_p, m_x = LayerNorm(D, use_pallas=True), LayerNorm(D, use_pallas=False)
    else:
        m_p, m_x = RMSNorm(D, use_pallas=True), RMSNorm(D, use_pallas=False)
    p = m_p.init(jax.random.PRNGKey(1), x)
    o1, o2 = m_p.apply(p, x), m_x.apply(p, x)
    assert float(jnp.abs(o1 - o2).max()) < 1e-5
    g1 = jax.grad(lambda pp: jnp.sum(m_p.apply(pp, x) ** 2))(p)
    g2 = jax.grad(lambda pp: jnp.sum(m_x.apply(pp, x) ** 2))(p)
    for a, b in zip(jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g2)):
        assert float(jnp.abs(a - b).max()) < 1e-4


# ---------------------------------------------------------------------------
# --fused-norm flag wiring (modules/layer_norm.py): one documented flag
# drives the kernel selection, each module instance journals its path once
# ---------------------------------------------------------------------------


@pytest.fixture
def norm_flag():
    import unicore_tpu.modules.layer_norm as ln_mod

    prev_journal = set(ln_mod._journaled)
    try:
        yield ln_mod
    finally:
        ln_mod.configure_fused_norm(None)
        ln_mod._journaled.clear()
        ln_mod._journaled.update(prev_journal)


def test_fused_norm_flag_selects_path(norm_flag, monkeypatch):
    ln_mod = norm_flag
    monkeypatch.delenv("UNICORE_TPU_PALLAS_NORM", raising=False)
    calls = []
    monkeypatch.setattr(
        ln_mod, "_journal_choice",
        lambda kind, dim, pallas, source: calls.append(
            (kind, dim, pallas, source)
        ),
    )
    ln_mod.configure_fused_norm("auto")
    assert ln_mod._use_pallas(None, "LayerNorm", 64) is False
    ln_mod.configure_fused_norm("on")
    assert ln_mod._use_pallas(None, "LayerNorm", 64) is True
    ln_mod.configure_fused_norm("off")
    assert ln_mod._use_pallas(None, "LayerNorm", 64) is False
    # explicit module attribute beats the flag; env beats both
    assert ln_mod._use_pallas(True, "LayerNorm", 64) is True
    monkeypatch.setenv("UNICORE_TPU_PALLAS_NORM", "0")
    assert ln_mod._use_pallas(True, "LayerNorm", 64) is False
    assert [c[3] for c in calls] == [
        "flag:auto", "flag:on", "flag:off", "module", "env"
    ]
    with pytest.raises(ValueError):
        ln_mod.configure_fused_norm("sometimes")


def test_fused_norm_flag_end_to_end(norm_flag, monkeypatch):
    """'on' routes the real module through the Pallas kernel and matches
    the jnp path numerically."""
    from unicore_tpu.modules import LayerNorm

    ln_mod = norm_flag
    monkeypatch.delenv("UNICORE_TPU_PALLAS_NORM", raising=False)
    D = 128
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 8, D))
    m = LayerNorm(D)
    p = m.init(jax.random.PRNGKey(1), x)
    ln_mod.configure_fused_norm("off")
    ref = m.apply(p, x)
    ln_mod.configure_fused_norm("on")
    out = m.apply(p, x)
    assert float(jnp.abs(out - ref).max()) < 1e-5


def test_fused_norm_choice_journals_once(norm_flag, monkeypatch, tmp_path):
    """One telemetry event per (kind, dim, path), not one per trace."""
    import json
    from argparse import Namespace

    from unicore_tpu import telemetry

    ln_mod = norm_flag
    monkeypatch.delenv("UNICORE_TPU_PALLAS_NORM", raising=False)
    telemetry.reset()
    telemetry.configure(
        Namespace(save_dir=None, telemetry_dir=str(tmp_path),
                  telemetry_sample_interval=0, profile_steps=None),
        rank=0, role="trainer",
    )
    try:
        ln_mod._journaled.clear()
        ln_mod.configure_fused_norm("auto")
        for _ in range(3):
            ln_mod._use_pallas(None, "LayerNorm", 77)
        ln_mod._use_pallas(None, "RMSNorm", 77)
        events = [
            json.loads(ln)
            for ln in open(telemetry.journal_path(), encoding="utf-8")
            if ln.strip()
        ]
        norm_events = [e for e in events if e.get("kind") == "fused-norm-path"]
        assert len(norm_events) == 2
        assert {e["module"] for e in norm_events} == {"LayerNorm", "RMSNorm"}
        assert all(e["path"] == "jnp" for e in norm_events)
        assert all(e["source"] == "flag:auto" for e in norm_events)
    finally:
        telemetry.reset()
