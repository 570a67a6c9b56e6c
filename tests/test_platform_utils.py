"""The one TPU predicate, the compile-cache resolver, and bench.py's
no-fallback contract (a measurement path that finds no chip fails)."""

import json
import os
import subprocess
import sys

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
# bench.py: no chip -> non-zero at once; one failed config -> non-zero
# ---------------------------------------------------------------------------

def test_bench_exits_nonzero_at_once_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=120, cwd=REPO, env=env,
    )
    assert proc.returncode == 3, proc.stderr[-2000:]
    assert "no TPU" in proc.stderr
    assert proc.stdout.strip() == ""  # no row: a CPU is never reported


@pytest.fixture
def bench(monkeypatch):
    monkeypatch.syspath_prepend(REPO)
    import bench as bench_mod

    monkeypatch.setattr(bench_mod, "_require_tpu", lambda: None)
    monkeypatch.setattr(
        platform_utils, "configure_compilation_cache", lambda *a: None
    )
    return bench_mod


def test_bench_one_failed_config_fails_the_run(bench, monkeypatch, capsys):
    def boom():
        raise RuntimeError("kernel refused")

    monkeypatch.setenv("BENCH_CONFIG", "all")
    monkeypatch.setattr(bench, "run_config", lambda c: {"metric": c})
    monkeypatch.setitem(bench._RUNNERS, "serve", lambda: {"metric": "serve"})
    monkeypatch.setitem(bench._RUNNERS, "kernels", boom)
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code == 4
    out, err = capsys.readouterr()
    # the configs that worked still printed their rows
    assert [json.loads(line)["metric"] for line in out.splitlines()] == [
        "bert", "unimol", "evoformer", "moe", "serve",
    ]
    assert "config kernels failed" in err and "['kernels']" in err


def test_bench_all_configs_passing_exits_zero(bench, monkeypatch):
    monkeypatch.setenv("BENCH_CONFIG", "bert")
    monkeypatch.setattr(bench, "run_config", lambda c: {"metric": c})
    bench.main()  # no SystemExit


@pytest.mark.parametrize("kind,peak", [
    ("TPU v5 lite", 197e12), ("TPU v5", None), ("cpu", None), ("", None),
])
def test_bench_peak_flops_unknown_kind_is_an_error(bench, kind, peak):
    if peak is None:
        with pytest.raises(ValueError, match="not in the peaks table"):
            bench._peak_flops(kind)
    else:
        assert bench._peak_flops(kind) == peak


def test_bench_device_kind_lookup_failure_raises(bench, monkeypatch):
    def no_devices():
        raise RuntimeError("no backend")

    monkeypatch.setattr(jax, "devices", no_devices)
    with pytest.raises(RuntimeError, match="no backend"):
        bench._device_kind()
