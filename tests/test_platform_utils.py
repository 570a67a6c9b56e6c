"""The one TPU predicate and the compile-cache resolver."""

import json
import os

import jax
import pytest

from unicore_tpu import platform_utils

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("backend,expected", [
    ("tpu", True), ("cpu", False), ("gpu", False),
])
def test_on_tpu_is_the_default_backend_being_tpu(monkeypatch, backend,
                                                 expected):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert platform_utils.on_tpu() is expected


def test_describe_devices_reports_what_jax_reports():
    dev = platform_utils.describe_devices()
    first = jax.devices()[0]
    assert dev == {
        "platform": first.platform, "kind": first.device_kind,
        "count": len(jax.devices()),
    }


def test_force_host_cpu_raises_instead_of_running_on_what_came_up(
        monkeypatch):
    def refuse(name, value):
        raise RuntimeError("backend already initialized")

    monkeypatch.setattr(jax.config, "update", refuse)
    with pytest.raises(RuntimeError, match="already initialized"):
        platform_utils.force_host_cpu(8)


@pytest.fixture
def cache_config(monkeypatch):
    """Run a resolver case against a clean slate and put the suite's own
    cache settings back afterwards."""
    keep = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    yield
    jax.config.update("jax_compilation_cache_dir", keep[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", keep[1])


@pytest.mark.parametrize("case", ["env", "flag", "default", "embedded"])
def test_compilation_cache_resolver(cache_config, monkeypatch, tmp_path,
                                    case):
    flag = str(tmp_path / "flag")
    if case == "env":
        # the variable is JAX's own: the program sets NOTHING in code,
        # even when the flag is given too
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "e"))
        assert platform_utils.configure_compilation_cache(flag) is None
        assert jax.config.jax_compilation_cache_dir is None
    elif case == "flag":
        assert platform_utils.configure_compilation_cache(flag) == flag
        assert jax.config.jax_compilation_cache_dir == flag
    elif case == "default":
        first = platform_utils.configure_compilation_cache()
        assert first == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
        jax.config.update("jax_compilation_cache_dir", None)
        # fixed: no temporary name, pid or time — the same path every call
        assert platform_utils.configure_compilation_cache() == first
    else:
        # an embedding program (the tests' own fixed /tmp caches) already
        # configured a directory: the default does not move it
        mine = str(tmp_path / "mine")
        jax.config.update("jax_compilation_cache_dir", mine)
        assert platform_utils.configure_compilation_cache() is None
        assert jax.config.jax_compilation_cache_dir == mine
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_default_cache_dir_is_gitignored():
    with open(os.path.join(REPO, ".gitignore")) as f:
        ignored = f.read().split()
    assert ".jax_cache/" in ignored and "chiprun_out/" in ignored


# ---------------------------------------------------------------------------
# one benchmark: what the documents send a reader to exists
# ---------------------------------------------------------------------------

def _documents():
    """The repository's own prose and scripts, history apart (the records
    of past PRs name what those PRs had)."""
    history = {"CHANGES.md", "PERF.md", "ROADMAP.md", "ISSUE.md", "REVIEW.md"}
    for top in ("", "docs", "scripts", "examples", "unicore_tpu",
                "unicore_tpu_cli", "benchmark", os.path.join(".claude", "skills")):
        for dirpath, dirs, files in os.walk(os.path.join(REPO, top)):
            if not top:
                dirs[:] = []
            for f in files:
                if f.endswith((".md", ".py", ".sh")) and f not in history:
                    yield os.path.join(dirpath, f)


def test_no_document_points_at_the_deleted_benchmark():
    # spelled in pieces, so that this file is no hit of its own search
    gone = ("bench" + ".py", "BENCH_" + "PARTIAL", "MULTICHIP_" + "r0",
            "BENCH_" + "CONFIG", "bench_" + "attention", "copy" + "sweep",
            "bench_input_" + "pipeline")
    hits = []
    for path in _documents():
        with open(path, encoding="utf-8") as f:
            text = f.read()
        hits += [(os.path.relpath(path, REPO), g) for g in gone if g in text]
    assert not hits, hits


def test_documented_benchmark_commands_name_a_cell():
    """Every ``benchmark.run --workload <name>`` a document spells out is a
    cell of the manifest (``<cell>`` stands for any of them)."""
    import re

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        cells = {w["name"] for w in json.load(f)["workloads"]}
    named = set()
    for path in _documents():
        with open(path, encoding="utf-8") as f:
            named |= set(re.findall(
                r"benchmark\.run\s+--workload\s+([\w.]+)", f.read()
            ))
    assert "bert_base.train_mlm512" in named  # the README's
    assert named <= cells, named - cells
