"""unicore-tpu-lint: rule fixtures (>=2 positive + >=1 negative each),
suppression comments, the registry plugin surface, the CLI, and the
framework tree itself staying lint-clean."""

import os
import subprocess
import sys
import textwrap

import pytest

from unicore_tpu.analysis import (
    LINT_RULE_REGISTRY,
    LintRule,
    ModuleInfo,
    Violation,
    build_rules,
    lint_paths,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_lint(tmp_path, source, select=None, name="fixture.py"):
    path = tmp_path / name
    path.write_text(textwrap.dedent(source))
    return lint_paths([str(path)], rules=build_rules(select))


def rule_names(violations):
    return [v.rule for v in violations]


# ---------------------------------------------------------------------------
# host-sync-in-jit
# ---------------------------------------------------------------------------


def test_host_sync_item_in_jit(tmp_path):
    vs = run_lint(
        tmp_path,
        """
        import jax

        @jax.jit
        def step(x):
            return x.sum().item()
        """,
        select=["host-sync-in-jit"],
    )
    assert rule_names(vs) == ["host-sync-in-jit"]
    assert ".item()" in vs[0].message


def test_host_sync_np_asarray_reachable_from_scan(tmp_path):
    """np.asarray in a helper REACHED from a scan body is still caught."""
    vs = run_lint(
        tmp_path,
        """
        import jax
        import numpy as np

        def leak(x):
            return np.asarray(x)

        def body(carry, x):
            return carry + leak(x), None

        def outer(xs):
            return jax.lax.scan(body, 0.0, xs)
        """,
        select=["host-sync-in-jit"],
    )
    assert rule_names(vs) == ["host-sync-in-jit"]
    assert "np.asarray" in vs[0].message


def test_host_sync_float_coercion_and_device_get(tmp_path):
    vs = run_lint(
        tmp_path,
        """
        import jax

        @jax.jit
        def step(x):
            y = x * 2
            host = jax.device_get(y)
            return float(y) + host
        """,
        select=["host-sync-in-jit"],
    )
    assert sorted(rule_names(vs)) == ["host-sync-in-jit"] * 2


def test_host_sync_negative_outside_jit_and_static(tmp_path):
    """Host syncs OUTSIDE traced regions are fine, as are float() of
    closure config and int() of shape metadata inside them."""
    vs = run_lint(
        tmp_path,
        """
        import jax
        import numpy as np

        SCALE = 2

        class Cfg:
            lr = 0.1

        cfg = Cfg()

        @jax.jit
        def step(x):
            n = int(x.shape[0])
            s = float(SCALE)
            return x * s * float(cfg.lr) + n

        def host_eval(fn, batch):
            out = jax.device_get(fn(batch))
            return float(np.asarray(out).mean())
        """,
        select=["host-sync-in-jit"],
    )
    assert vs == []


# ---------------------------------------------------------------------------
# recompile-hazard
# ---------------------------------------------------------------------------


def test_recompile_branch_on_traced_arg(tmp_path):
    vs = run_lint(
        tmp_path,
        """
        import jax

        @jax.jit
        def step(x):
            if x > 0:
                return x
            return -x
        """,
        select=["recompile-hazard"],
    )
    assert rule_names(vs) == ["recompile-hazard"]


def test_recompile_while_on_scan_carry(tmp_path):
    vs = run_lint(
        tmp_path,
        """
        import jax

        def body(carry, x):
            while carry < x:
                carry = carry + 1
            return carry, None

        def outer(xs):
            return jax.lax.scan(body, 0, xs)
        """,
        select=["recompile-hazard"],
    )
    assert rule_names(vs) == ["recompile-hazard"]


def test_recompile_unhashable_static_default(tmp_path):
    vs = run_lint(
        tmp_path,
        """
        from functools import partial
        import jax

        @partial(jax.jit, static_argnums=(1,))
        def step(x, cfg=[1, 2]):
            return x
        """,
        select=["recompile-hazard"],
    )
    assert rule_names(vs) == ["recompile-hazard"]
    assert "unhashable" in vs[0].message


def test_recompile_negative_static_patterns(tmp_path):
    """Shape branching, is-None checks, static_argnums-declared params and
    constant-default config flags are all legitimate compile-time dispatch."""
    vs = run_lint(
        tmp_path,
        """
        from functools import partial
        import jax

        @partial(jax.jit, static_argnums=(1,))
        def step(x, training, mask=None, eps=1e-6):
            if training:
                x = x * 2
            if mask is not None:
                x = x + mask
            if x.shape[0] > 8:
                x = x[:8]
            if len(x.shape) == 3:
                x = x.sum(0)
            if eps > 0:
                x = x + eps
            return x
        """,
        select=["recompile-hazard"],
    )
    assert vs == []


# ---------------------------------------------------------------------------
# impure-callable
# ---------------------------------------------------------------------------


def test_impure_np_random_in_jit(tmp_path):
    vs = run_lint(
        tmp_path,
        """
        import jax
        import numpy as np

        @jax.jit
        def step(x):
            noise = np.random.randn(*x.shape)
            return x + noise
        """,
        select=["impure-callable"],
    )
    assert rule_names(vs) == ["impure-callable"]
    assert "np.random" in vs[0].message


def test_impure_logging_print_and_self_mutation(tmp_path):
    vs = run_lint(
        tmp_path,
        """
        import logging
        import jax
        import flax.linen as nn

        logger = logging.getLogger(__name__)

        class Layer(nn.Module):
            @nn.compact
            def __call__(self, x):
                self.call_count = 1
                logger.info("tracing!")
                print(x)
                return x
        """,
        select=["impure-callable"],
    )
    assert sorted(rule_names(vs)) == ["impure-callable"] * 3


def test_impure_negative_flax_setup_and_host_code(tmp_path):
    """setup()'s self-assignment is the flax contract; host-side RNG and
    logging outside traced regions are untouched."""
    vs = run_lint(
        tmp_path,
        """
        import logging
        import numpy as np
        import flax.linen as nn

        logger = logging.getLogger(__name__)

        class Encoder(nn.Module):
            def setup(self):
                self.dense = nn.Dense(8)

            def __call__(self, x):
                return self.dense(x)

        def make_batch(seed):
            logger.info("building host batch")
            return np.random.RandomState(seed).randn(4, 8)
        """,
        select=["impure-callable"],
    )
    assert vs == []


# ---------------------------------------------------------------------------
# unsafe-shard-map
# ---------------------------------------------------------------------------


def test_unsafe_shard_map_check_vma_false(tmp_path):
    vs = run_lint(
        tmp_path,
        """
        import jax

        def run(mesh, f, x):
            return jax.shard_map(f, mesh=mesh, in_specs=(None,),
                                 out_specs=None, check_vma=False)(x)
        """,
        select=["unsafe-shard-map"],
    )
    assert rule_names(vs) == ["unsafe-shard-map"]
    assert "check_vma" in vs[0].message


def test_unsafe_shard_map_empty_axis_names(tmp_path):
    vs = run_lint(
        tmp_path,
        """
        import jax

        def run(mesh, f, x):
            return jax.shard_map(f, mesh=mesh, in_specs=(None,),
                                 out_specs=None,
                                 axis_names=frozenset())(x)
        """,
        select=["unsafe-shard-map"],
    )
    assert rule_names(vs) == ["unsafe-shard-map"]
    assert "axis_names" in vs[0].message


def test_unsafe_shard_map_negative_and_justified(tmp_path):
    """Explicit axis names, non-literal check_vma, and the
    jax-version-pinned justification comment all pass."""
    vs = run_lint(
        tmp_path,
        """
        import jax

        def run(mesh, f, x, manual_axes=None):
            a = jax.shard_map(f, mesh=mesh, in_specs=(None,),
                              out_specs=None,
                              axis_names=frozenset(mesh.shape),
                              check_vma=manual_axes is not None)(x)
            b = jax.shard_map(f, mesh=mesh, in_specs=(None,),
                              out_specs=None,
                              check_vma=False,  # lint: jax-version-pinned
                              )(x)
            return a + b
        """,
        select=["unsafe-shard-map"],
    )
    assert vs == []


# ---------------------------------------------------------------------------
# prng-key-reuse
# ---------------------------------------------------------------------------


def test_prng_reuse_two_draws_same_key(tmp_path):
    vs = run_lint(
        tmp_path,
        """
        import jax

        def sample(key):
            a = jax.random.normal(key, (4,))
            b = jax.random.uniform(key, (4,))
            return a + b
        """,
        select=["prng-key-reuse"],
    )
    assert rule_names(vs) == ["prng-key-reuse"]
    assert "IDENTICAL" in vs[0].message


def test_prng_reuse_after_partial_rename(tmp_path):
    """Splitting into NEW names doesn't sanitize further draws from the
    original key."""
    vs = run_lint(
        tmp_path,
        """
        import jax

        def sample(key):
            noise = jax.random.normal(key, (4,))
            k1, k2 = jax.random.split(key)
            mask = jax.random.bernoulli(key, 0.5, (4,))
            return noise + mask + jax.random.normal(k1, (4,))
        """,
        select=["prng-key-reuse"],
    )
    assert rule_names(vs) == ["prng-key-reuse"]


def test_prng_negative_exclusive_branches(tmp_path):
    """Consumes in mutually exclusive if/else arms can't both execute, so
    they are not reuse; a consume straddling the arms still is."""
    vs = run_lint(
        tmp_path,
        """
        import jax

        def sample(key, training):
            if training:
                out = jax.random.bernoulli(key, 0.5, (4,))
            else:
                out = jax.random.normal(key, (4,))
            return out

        def reuse_across_arm(key, training):
            a = jax.random.normal(key, (4,))
            if training:
                a = a + jax.random.uniform(key, (4,))
            return a
        """,
        select=["prng-key-reuse"],
    )
    assert rule_names(vs) == ["prng-key-reuse"]
    assert vs[0].line == 14  # only the straddling consume


def test_prng_negative_split_between_draws(tmp_path):
    vs = run_lint(
        tmp_path,
        """
        import jax

        def sample(key):
            a = jax.random.normal(key, (4,))
            key = jax.random.fold_in(key, 1)
            b = jax.random.uniform(key, (4,))
            k1, k2 = jax.random.split(jax.random.PRNGKey(0))
            c = jax.random.normal(k1, (4,))
            d = jax.random.normal(k2, (4,))
            return a + b + c + d
        """,
        select=["prng-key-reuse"],
    )
    assert vs == []


def test_prng_pallas_invariant_seed_flagged(tmp_path):
    """In-kernel seeding (the PR-9 ring-kernel bug class): a prng_seed
    whose seed reaches only constants / *_ref operands is loop-invariant
    across grid steps — every block draws the same bits."""
    vs = run_lint(
        tmp_path,
        """
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def kernel(seed_ref, x_ref, o_ref):
            i = pl.program_id(0)
            pltpu.prng_seed(seed_ref[0])
            bits = pltpu.prng_random_bits(x_ref.shape)
            o_ref[...] = pltpu.bitcast(bits, jnp.uint32)

        def kernel_const(x_ref, o_ref):
            pltpu.prng_seed(42)
            o_ref[...] = pltpu.prng_random_bits(x_ref.shape)
        """,
        select=["prng-key-reuse"],
    )
    assert rule_names(vs) == ["prng-key-reuse", "prng-key-reuse"]
    assert all("loop-invariant" in v.message for v in vs)


def test_prng_pallas_mixed_seed_negative(tmp_path):
    """Seeds mixed with program ids (directly or via a derived local, the
    flash-attention idiom) vary per block — not flagged; a single-block
    grid justifies the invariant seed with the escape."""
    vs = run_lint(
        tmp_path,
        """
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def kernel(seed_ref, x_ref, o_ref):
            pltpu.prng_seed(seed_ref[0] + pl.program_id(0))
            o_ref[...] = pltpu.prng_random_bits(x_ref.shape)

        def kernel_mixed(seed_ref, b, h, o_ref):
            mix = seed_ref[0]
            for coord in (b, h):
                mix = mix * jnp.int32(1000003) + coord
            pltpu.prng_seed(mix)
            o_ref[...] = pltpu.prng_random_bits(o_ref.shape)

        def single_block(seed_ref, o_ref):
            # lint: single-block-grid
            pltpu.prng_seed(seed_ref[0])
            o_ref[...] = pltpu.prng_random_bits(o_ref.shape)
        """,
        select=["prng-key-reuse"],
    )
    assert vs == []


def test_prng_pallas_seed_reuse_across_calls(tmp_path):
    """One seed feeding two pallas_calls in one function = two kernels on
    one stream; the deliberate fwd/bwd mask-recompute escape clears it,
    and a non-seed first operand shared by two calls is not confused for
    one."""
    vs = run_lint(
        tmp_path,
        """
        import jax
        from jax.experimental import pallas as pl

        def fwd_bwd(kernel, x, seed):
            a = pl.pallas_call(kernel, grid=(4,))(seed, x)
            b = pl.pallas_call(kernel, grid=(4,))(seed, x)
            return a + b

        def recompute(kernel, x, seed):
            a = pl.pallas_call(kernel, grid=(4,))(seed, x)
            # lint: shared-prng-stream
            b = pl.pallas_call(kernel, grid=(4,))(seed, x)
            return a + b

        def not_a_seed(kernel, x):
            a = pl.pallas_call(kernel, grid=(4,))(x)
            b = pl.pallas_call(kernel, grid=(4,))(x)
            return a + b
        """,
        select=["prng-key-reuse"],
    )
    assert rule_names(vs) == ["prng-key-reuse"]
    assert vs[0].line == 7 and "second pallas_call" in vs[0].message


# ---------------------------------------------------------------------------
# dead-flag
# ---------------------------------------------------------------------------


def test_dead_flag_detected(tmp_path):
    vs = run_lint(
        tmp_path,
        """
        def add_args(parser):
            parser.add_argument("--learning-rate", type=float, default=0.1)
            parser.add_argument("--mystery-knob", type=int, default=3)
            parser.add_argument("--other-dead", action="store_true")

        def consume(args):
            return args.learning_rate
        """,
        select=["dead-flag"],
    )
    assert rule_names(vs) == ["dead-flag", "dead-flag"]
    assert "--mystery-knob" in vs[0].message
    assert "--other-dead" in vs[1].message


def test_dead_flag_explicit_dest(tmp_path):
    vs = run_lint(
        tmp_path,
        """
        def add_args(parser):
            parser.add_argument("--knob", dest="renamed_knob", type=int)

        def consume(args):
            return args.knob  # reads the WRONG name; dest is renamed_knob
        """,
        select=["dead-flag"],
    )
    assert rule_names(vs) == ["dead-flag"]
    assert "renamed_knob" in vs[0].message


def test_dead_flag_negative_read_variants(tmp_path):
    """getattr-string reads, f-string getattr patterns, compat-table dict
    keys, and the compat-flag annotation all count as consumption."""
    vs = run_lint(
        tmp_path,
        """
        NOOP_TABLE = {"legacy_knob": "accepted for compat"}

        def add_args(parser):
            parser.add_argument("--plain", type=int)
            parser.add_argument("--via-getattr", type=int)
            parser.add_argument("--legacy-knob", type=int)
            parser.add_argument("--reset-optimizer", action="store_true")
            parser.add_argument("--reset-meters", action="store_true")
            # lint: compat-flag
            parser.add_argument("--reserved-for-later", type=str)

        def consume(args):
            use(args.plain)
            use(getattr(args, "via_getattr", None))
            for kind in ("optimizer", "meters"):
                use(getattr(args, f"reset_{kind}"))
        """,
        select=["dead-flag"],
    )
    assert vs == []


# ---------------------------------------------------------------------------
# untimed-collective
# ---------------------------------------------------------------------------


def test_untimed_collective_module_attribute_calls(tmp_path):
    """Raw multihost_utils collectives outside distributed/utils.py are
    flagged — they have no watchdog timeout, so a desynced peer hangs them
    forever (positive fixture 1)."""
    vs = run_lint(
        tmp_path,
        """
        from jax.experimental import multihost_utils

        def gather_stats(arr):
            return multihost_utils.process_allgather(arr)

        def checkpoint_barrier():
            multihost_utils.sync_global_devices("pre_save")
        """,
        select=["untimed-collective"],
    )
    assert rule_names(vs) == ["untimed-collective"] * 2
    assert "process_allgather" in vs[0].message
    assert "watchdog" in vs[0].message


def test_untimed_collective_member_import_and_alias(tmp_path):
    """Members imported straight off multihost_utils (with or without an
    alias) are still caught (positive fixture 2)."""
    vs = run_lint(
        tmp_path,
        """
        from jax.experimental.multihost_utils import broadcast_one_to_all as b1a

        def push_config(buf, is_source):
            return b1a(buf, is_source=is_source)
        """,
        select=["untimed-collective"],
    )
    assert rule_names(vs) == ["untimed-collective"]
    assert "b1a" in vs[0].message


def test_untimed_collective_negative_wrappers_and_lookalikes(tmp_path):
    """The timed wrappers are the sanctioned path, and a local function that
    merely SHARES a collective's name (no multihost_utils import) is not a
    collective (negative fixture)."""
    vs = run_lint(
        tmp_path,
        """
        from unicore_tpu.distributed import utils as distributed_utils

        def process_allgather(xs):
            return list(xs)  # local helper, not jax's

        def gather(data):
            stats = process_allgather([data])
            return distributed_utils.all_gather_list(stats)
        """,
        select=["untimed-collective"],
    )
    assert vs == []


# ---------------------------------------------------------------------------
# raw-checkpoint-write
# ---------------------------------------------------------------------------


def test_raw_checkpoint_write_open_and_pickle_dump(tmp_path):
    """A with-open of a .pt path in write mode, and the pickle.dump into
    it, both bypass the durable path (positive fixture 1: both shapes)."""
    vs = run_lint(
        tmp_path,
        """
        import pickle

        def save(state, save_dir):
            with open(save_dir + "/checkpoint_best.pt", "wb") as f:
                pickle.dump(state, f)
        """,
        select=["raw-checkpoint-write"],
    )
    assert rule_names(vs) == ["raw-checkpoint-write"] * 2
    assert "persistent_save" in vs[0].message


def test_raw_checkpoint_write_fstring_and_assigned_handle(tmp_path):
    """f-string .pt tails and handles assigned (not with-bound) from a
    flagged open are still caught (positive fixture 2)."""
    vs = run_lint(
        tmp_path,
        """
        import pickle

        def save(state, step):
            f = open(f"ckpts/checkpoint_{step}.pt", mode="wb")
            pickle.dump(state, f)
            f.close()
        """,
        select=["raw-checkpoint-write"],
    )
    assert rule_names(vs) == ["raw-checkpoint-write"] * 2


def test_raw_checkpoint_write_negatives(tmp_path):
    """Reads of .pt files, writes of non-checkpoint extensions, and
    pickle.dump into non-.pt streams are all fine (negative fixture)."""
    vs = run_lint(
        tmp_path,
        """
        import pickle

        def fine(state, path):
            with open(path + ".bin", "wb") as f:   # not a checkpoint
                f.write(b"data")
            with open("checkpoint_last.pt", "rb") as f:  # a READ
                state = pickle.load(f)
            with open(path + ".log", "w") as f:
                pickle.dump(state, f)  # pickle, but not into a .pt
            return state
        """,
        select=["raw-checkpoint-write"],
    )
    assert vs == []


def test_raw_checkpoint_write_home_modules_exempt(tmp_path):
    """unicore_tpu/checkpoint_utils.py and the unicore_tpu/checkpoint/
    package ARE the durable write path — their raw writes are the
    implementation.  The exemption is anchored at the unicore_tpu/
    component: a stray tools/checkpoint/ module or a vendored
    checkpoint_utils.py copy must NOT ride it."""
    import textwrap as _tw

    src = _tw.dedent(
        """
        import pickle

        def persistent_save(obj, filename):
            with open(filename + ".pt", "wb") as f:
                pickle.dump(obj, f)
        """
    )
    home = tmp_path / "unicore_tpu"
    pkg = home / "checkpoint"
    pkg.mkdir(parents=True)
    (home / "checkpoint_utils.py").write_text(src)
    (pkg / "format.py").write_text(src)
    vs = lint_paths([str(home)], rules=build_rules(["raw-checkpoint-write"]))
    assert vs == []

    lookalike = tmp_path / "tools" / "checkpoint"
    lookalike.mkdir(parents=True)
    (lookalike / "export.py").write_text(src)
    (tmp_path / "tools" / "checkpoint_utils.py").write_text(src)
    vs = lint_paths(
        [str(tmp_path / "tools")], rules=build_rules(["raw-checkpoint-write"])
    )
    assert rule_names(vs) == ["raw-checkpoint-write"] * 4  # 2 files x 2 shapes


def test_raw_checkpoint_write_justification_comment(tmp_path):
    vs = run_lint(
        tmp_path,
        """
        def export_table(rows):
            # lint: not-a-checkpoint
            with open("lookup_table.pt", "wb") as f:
                f.write(rows)
        """,
        select=["raw-checkpoint-write"],
    )
    assert vs == []


def test_untimed_collective_home_module_exempt(tmp_path):
    """distributed/utils.py itself must touch the raw collectives — that is
    where the watchdog wrappers live."""
    home = tmp_path / "distributed"
    home.mkdir()
    import textwrap as _tw

    (home / "utils.py").write_text(
        _tw.dedent(
            """
            from jax.experimental import multihost_utils

            def all_gather_list(data):
                return multihost_utils.process_allgather(data)
            """
        )
    )
    vs = lint_paths([str(home)], rules=build_rules(["untimed-collective"]))
    assert vs == []


def test_untimed_collective_lookalike_path_not_exempt(tmp_path):
    """The home exemption is a path-COMPONENT match: 'foodistributed/'
    must not ride it."""
    import textwrap as _tw

    home = tmp_path / "foodistributed"
    home.mkdir()
    (home / "utils.py").write_text(
        _tw.dedent(
            """
            from jax.experimental import multihost_utils

            def gather(data):
                return multihost_utils.process_allgather(data)
            """
        )
    )
    vs = lint_paths([str(home)], rules=build_rules(["untimed-collective"]))
    assert rule_names(vs) == ["untimed-collective"]


def test_untimed_collective_suppression_comment(tmp_path):
    vs = run_lint(
        tmp_path,
        """
        from jax.experimental import multihost_utils

        def startup_probe(x):
            # lint: untimed-collective
            return multihost_utils.process_allgather(x)
        """,
        select=["untimed-collective"],
    )
    assert vs == []


# ---------------------------------------------------------------------------
# suppression + registry + CLI + the tree itself
# ---------------------------------------------------------------------------


def test_suppression_comment_on_line_above(tmp_path):
    vs = run_lint(
        tmp_path,
        """
        import jax

        @jax.jit
        def step(x):
            # lint: host-sync-in-jit
            return x.sum().item()
        """,
        select=["host-sync-in-jit"],
    )
    assert vs == []


def test_custom_rule_registry_roundtrip(tmp_path):
    """Plugins register rules with the same decorator idiom as
    optimizers/losses; build_rules picks them up by name."""
    import ast as ast_mod

    name = "no-todo-comments-test"
    if name not in LINT_RULE_REGISTRY.classes:

        @LINT_RULE_REGISTRY.register(name)
        class NoTodo(LintRule):
            def __init__(self):
                self.name = name

            def check(self, module):
                for node in ast_mod.walk(module.tree):
                    if isinstance(node, ast_mod.Constant) and node.value == "TODO":
                        yield Violation(
                            self.name, module.path, node.lineno,
                            node.col_offset, "TODO marker",
                        )

    try:
        path = tmp_path / "todo.py"
        path.write_text('x = "TODO"\n')
        vs = lint_paths([str(path)], rules=build_rules([name]))
        assert rule_names(vs) == [name]
    finally:
        LINT_RULE_REGISTRY.classes.pop(name, None)


def test_parse_error_reported(tmp_path):
    path = tmp_path / "broken.py"
    path.write_text("def broken(:\n")
    vs = lint_paths([str(path)], rules=build_rules(["host-sync-in-jit"]))
    assert rule_names(vs) == ["parse-error"]


def test_seeded_violations_of_every_rule(tmp_path):
    """Acceptance: one fixture seeding all seven rules at once — each is
    detected by the full default rule set."""
    vs = run_lint(
        tmp_path,
        """
        import jax
        import numpy as np
        from jax.experimental import multihost_utils

        def add_args(parser):
            parser.add_argument("--never-read", type=int)

        @jax.jit
        def step(x, key):
            if x > 0:                                 # recompile-hazard
                x = -x
            noise = np.random.randn(4)                # impure-callable
            a = jax.random.normal(key, (4,))
            b = jax.random.uniform(key, (4,))         # prng-key-reuse
            return float(x) + a + b + noise           # host-sync-in-jit

        def gather(stats):
            return multihost_utils.process_allgather(stats)  # untimed-collective

        def run(mesh, f, x):
            return jax.shard_map(f, mesh=mesh, in_specs=(None,),
                                 out_specs=None,
                                 check_vma=False)(x)  # unsafe-shard-map
        """,
    )
    assert set(rule_names(vs)) == {
        "host-sync-in-jit",
        "recompile-hazard",
        "impure-callable",
        "prng-key-reuse",
        "unsafe-shard-map",
        "dead-flag",
        "untimed-collective",
    }


def test_cli_exit_codes(tmp_path):
    from unicore_tpu_cli.lint import cli_main

    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    assert cli_main([str(clean)]) == 0

    # a typo'd path must NOT report a clean tree (the CI gate would go
    # green while linting nothing)
    assert cli_main([str(tmp_path / "no_such_dir")]) == 2

    dirty = tmp_path / "dirty.py"
    dirty.write_text(
        "import jax\n\n@jax.jit\ndef f(x):\n    return x.item()\n"
    )
    assert cli_main([str(dirty)]) == 1
    assert cli_main([str(dirty), "--select", "no-such-rule"]) == 2
    assert cli_main(["--list-rules"]) == 0


def test_framework_tree_is_lint_clean():
    """Acceptance criterion: `unicore-tpu-lint unicore_tpu/
    unicore_tpu_cli/` exits 0 on the current tree (run in-process; the
    console script is exercised separately below)."""
    from unicore_tpu_cli.lint import cli_main

    rc = cli_main(
        [os.path.join(REPO, "unicore_tpu"), os.path.join(REPO, "unicore_tpu_cli")]
    )
    assert rc == 0


@pytest.mark.slow
def test_module_entry_point_subprocess():
    """`python -m unicore_tpu.analysis` mirrors the console script."""
    proc = subprocess.run(
        [sys.executable, "-m", "unicore_tpu.analysis",
         "unicore_tpu/", "unicore_tpu_cli/"],
        cwd=REPO,
        capture_output=True,
        text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# sync-transfer-in-step
# ---------------------------------------------------------------------------


def test_sync_transfer_device_get_in_train_step(tmp_path):
    """jax.device_get directly inside train_step blocks the training
    thread between dispatches (positive fixture 1)."""
    vs = run_lint(
        tmp_path,
        """
        import jax

        def train_step(self, samples):
            out = self._dispatch(samples)
            return float(jax.device_get(out)["loss"])
        """,
        select=["sync-transfer-in-step"],
    )
    assert rule_names(vs) == ["sync-transfer-in-step"]
    assert "jax.device_get" in vs[0].message
    assert "train_step" in vs[0].message


def test_sync_transfer_reachable_helper_chain(tmp_path):
    """A bare jax.device_put and a .block_until_ready() in helpers REACHED
    from train_step are both caught — the transfer doesn't have to be
    lexically inside the step (positive fixture 2)."""
    vs = run_lint(
        tmp_path,
        """
        import jax

        def _stage(batch):
            return jax.device_put(batch)

        def _drain(state):
            state.block_until_ready()

        def _prepare(samples):
            staged = [_stage(s) for s in samples]
            return staged

        def train_step(self, samples):
            staged = _prepare(samples)
            out = self.step(staged)
            _drain(out)
            return out
        """,
        select=["sync-transfer-in-step"],
    )
    assert rule_names(vs) == ["sync-transfer-in-step"] * 2
    joined = " ".join(v.message for v in vs)
    assert "jax.device_put" in joined
    assert ".block_until_ready()" in joined


def test_sync_transfer_negative_unreachable_and_annotated(tmp_path):
    """Transfers NOT reachable from train_step (checkpoint/eval paths) are
    fine, and an annotated opt-in sync (e.g. the --nan-rerun fetch) is
    suppressed by '# lint: explicit-sync' (negative fixture)."""
    vs = run_lint(
        tmp_path,
        """
        import jax

        def save_checkpoint(state, path):
            host = jax.device_get(state)  # not on the train path
            return host

        def train_step(self, samples):
            out = self.step(samples)
            if self.nan_rerun:
                seen = jax.device_get(self._macc)  # lint: explicit-sync
                self._check(seen)
            return out
        """,
        select=["sync-transfer-in-step"],
    )
    assert vs == []


def test_sync_transfer_negative_prefetcher_home(tmp_path):
    """data/prefetch.py is the sanctioned home for transfers — its whole
    job is issuing them off the hot thread (negative fixture 2)."""
    home = tmp_path / "data"
    home.mkdir()
    (home / "prefetch.py").write_text(
        "import jax\n\n"
        "def train_step(batch):\n"
        "    return jax.device_put(batch)\n"
    )
    vs = lint_paths([str(home / "prefetch.py")],
                    rules=build_rules(["sync-transfer-in-step"]))
    assert vs == []


# ---------------------------------------------------------------------------
# unguarded-kv-wait
# ---------------------------------------------------------------------------


def test_unguarded_kv_wait_blocking_get(tmp_path):
    """A raw blocking_key_value_get outside utils/retry.py blocks the full
    client timeout on a dead peer, with no shutdown predicate and no
    kv-outage chaos coverage (positive fixture 1)."""
    vs = run_lint(
        tmp_path,
        """
        def exchange(client, key):
            return client.blocking_key_value_get(key, 600000)
        """,
        select=["unguarded-kv-wait"],
    )
    assert rule_names(vs) == ["unguarded-kv-wait"]
    assert "blocking_key_value_get" in vs[0].message
    assert "retry.kv_wait" in vs[0].message


def test_unguarded_kv_wait_barrier_and_bytes_variant(tmp_path):
    """wait_at_barrier and the _bytes get variant are blocking too — both
    shapes are caught in one module (positive fixture 2)."""
    vs = run_lint(
        tmp_path,
        """
        def rendezvous(client, tag, payload_key):
            client.wait_at_barrier(tag, 300000)
            return client.blocking_key_value_get_bytes(payload_key, 300000)
        """,
        select=["unguarded-kv-wait"],
    )
    assert sorted(rule_names(vs)) == ["unguarded-kv-wait"] * 2
    joined = " ".join(v.message for v in vs)
    assert "wait_at_barrier" in joined
    assert "blocking_key_value_get_bytes" in joined


def test_unguarded_kv_wait_negatives(tmp_path):
    """Non-blocking KV calls (set/delete/dir_get), the retry.kv_wait
    consumer idiom, and a '# lint: kv-deadline-bounded' justification all
    stay un-flagged (negative fixture)."""
    vs = run_lint(
        tmp_path,
        """
        from unicore_tpu.utils import retry

        def publish(client, key, value):
            client.key_value_set(key, value, allow_overwrite=True)
            client.key_value_delete(key)
            return client.key_value_dir_get(key)

        def wait_through_helper(client, key):
            return retry.kv_wait(client, key, timeout=60.0)

        def own_deadline(client, key):
            # this caller carries its own bounded deadline end to end
            return client.blocking_key_value_get(key, 50)  # lint: kv-deadline-bounded
        """,
        select=["unguarded-kv-wait"],
    )
    assert vs == []


def test_unguarded_kv_wait_home_module_exempt(tmp_path):
    """utils/retry.py is the sanctioned home (its kv_wait/kv_fetch ARE the
    deadline wrappers); a lookalike path does not ride the exemption
    (negative fixture 2)."""
    home = tmp_path / "utils"
    home.mkdir()
    src = (
        "def kv_wait(client, key, timeout):\n"
        "    return client.blocking_key_value_get(key, 1000)\n"
    )
    (home / "retry.py").write_text(src)
    assert lint_paths(
        [str(home / "retry.py")], rules=build_rules(["unguarded-kv-wait"])
    ) == []
    lookalike = tmp_path / "myutils"
    lookalike.mkdir()
    (lookalike / "notretry.py").write_text(src)
    vs = lint_paths(
        [str(lookalike / "notretry.py")],
        rules=build_rules(["unguarded-kv-wait"]),
    )
    assert rule_names(vs) == ["unguarded-kv-wait"]


# ---------------------------------------------------------------------------
# unbounded-serve-wait
# ---------------------------------------------------------------------------


def _lint_serve_module(tmp_path, source):
    home = tmp_path / "serve"
    home.mkdir(exist_ok=True)
    path = home / "module.py"
    path.write_text(textwrap.dedent(source))
    return lint_paths([str(path)], rules=build_rules(["unbounded-serve-wait"]))


def test_unbounded_serve_wait_queue_get_and_put(tmp_path):
    """A no-timeout queue pop and a blocking put inside serve/ can wait
    forever on a wedged consumer / full queue (positive fixture 1)."""
    vs = _lint_serve_module(
        tmp_path,
        """
        def pump(q, out_q):
            item = q.get()
            out_q.put(item)
        """,
    )
    assert rule_names(vs) == ["unbounded-serve-wait"] * 2
    joined = " ".join(v.message for v in vs)
    assert ".get()" in joined and ".put(item)" in joined
    assert "retry.bounded_wait" in vs[0].message


def test_unbounded_serve_wait_event_join_accept(tmp_path):
    """Timeout-less Event.wait, thread join, and socket accept are the
    other unbounded shapes (positive fixture 2)."""
    vs = _lint_serve_module(
        tmp_path,
        """
        def shutdown(done_event, worker, listener, q):
            done_event.wait()
            worker.join()
            q.get(timeout=None)  # queue's explicitly-unbounded spelling
            return listener.accept()
        """,
    )
    assert rule_names(vs) == ["unbounded-serve-wait"] * 4


def test_unbounded_serve_wait_bounded_forms_pass(tmp_path):
    """Deadline-bounded waits, dict lookups, non-blocking pops, the
    retry-helper idiom, and the justification comment all stay un-flagged
    (negative fixture 1)."""
    vs = _lint_serve_module(
        tmp_path,
        """
        from unicore_tpu.utils import retry

        def pump(q, out_q, d, done_event, worker):
            x = d.get("key")
            y = d.get("key", None)
            item = q.get(timeout=0.5)
            q.get(block=False)
            out_q.put(item, timeout=0.5)
            done_event.wait(timeout=1.0)
            done_event.wait(0.1)
            worker.join(2.0)
            retry.bounded_wait(done_event.is_set, timeout=5.0)
            return q.get()  # lint: serve-deadline-bounded
        """,
    )
    assert vs == []


def test_unbounded_serve_wait_only_in_serve_package(tmp_path):
    """The same unbounded waits OUTSIDE a serve/ directory are not this
    rule's business — other subsystems have their own disciplines
    (negative fixture 2)."""
    other = tmp_path / "data"
    other.mkdir()
    path = other / "module.py"
    path.write_text(
        "def pump(q):\n"
        "    return q.get()\n"
    )
    assert lint_paths(
        [str(path)], rules=build_rules(["unbounded-serve-wait"])
    ) == []


def test_unbounded_serve_wait_covers_decode_scheduler(tmp_path):
    """serve/decode.py (the decode-step scheduler) is in scope: an
    unbounded wait there stalls EVERY in-flight generation at once, so
    the incremental-decode plane inherits the same bounded-wait
    discipline (positive fixture: decode scope)."""
    home = tmp_path / "serve"
    home.mkdir()
    path = home / "decode.py"
    path.write_text(textwrap.dedent(
        """
        def step(ready_queue, pool_freed_event):
            seq = ready_queue.get()
            pool_freed_event.wait()
            return seq
        """
    ))
    vs = lint_paths(
        [str(path)], rules=build_rules(["unbounded-serve-wait"])
    )
    assert rule_names(vs) == ["unbounded-serve-wait"] * 2


def test_unbounded_serve_wait_covers_router_cli(tmp_path):
    """unicore_tpu_cli/router.py is the serving plane's front door: a
    timeout-less queue pop or event wait there is the exact slow-loris
    class the rule polices in the replica (positive fixture: router
    scope)."""
    home = tmp_path / "unicore_tpu_cli"
    home.mkdir()
    path = home / "router.py"
    path.write_text(textwrap.dedent(
        """
        def route(q, stop_event):
            item = q.get()
            stop_event.wait()
            return item
        """
    ))
    vs = lint_paths(
        [str(path)], rules=build_rules(["unbounded-serve-wait"])
    )
    assert rule_names(vs) == ["unbounded-serve-wait"] * 2


def test_unbounded_serve_wait_covers_fleet_subpackage(tmp_path):
    """serve/fleet/ modules ride the serve-package scope: the router's
    membership/proxy threads hold the same promise (positive fixture:
    fleet scope)."""
    home = tmp_path / "serve" / "fleet"
    home.mkdir(parents=True)
    path = home / "membershiplike.py"
    path.write_text(textwrap.dedent(
        """
        def wait_round(worker, listener):
            worker.join()
            return listener.accept()
        """
    ))
    vs = lint_paths(
        [str(path)], rules=build_rules(["unbounded-serve-wait"])
    )
    assert rule_names(vs) == ["unbounded-serve-wait"] * 2


def test_unbounded_serve_wait_router_scope_is_precise(tmp_path):
    """Only router.py directly under unicore_tpu_cli rides the new
    scope: a sibling CLI module and a router.py elsewhere keep their own
    disciplines (negative fixture: router scope)."""
    cli = tmp_path / "unicore_tpu_cli"
    cli.mkdir()
    sibling = cli / "train.py"
    sibling.write_text("def pump(q):\n    return q.get()\n")
    elsewhere = tmp_path / "tools"
    elsewhere.mkdir()
    lookalike = elsewhere / "router.py"
    lookalike.write_text("def pump(q):\n    return q.get()\n")
    assert lint_paths(
        [str(sibling), str(lookalike)],
        rules=build_rules(["unbounded-serve-wait"]),
    ) == []


def test_unbounded_serve_wait_router_bounded_forms_pass(tmp_path):
    """Deadline-bounded waits inside the router CLI stay un-flagged —
    the scope extension polices the unbounded SHAPE, not the file
    (negative fixture: router scope)."""
    home = tmp_path / "unicore_tpu_cli"
    home.mkdir()
    path = home / "router.py"
    path.write_text(textwrap.dedent(
        """
        from unicore_tpu.utils import retry

        def route(q, stop_event, worker):
            item = q.get(timeout=0.5)
            stop_event.wait(timeout=0.2)
            worker.join(2.0)
            retry.bounded_wait(stop_event.is_set, timeout=5.0)
            return item
        """
    ))
    assert lint_paths(
        [str(path)], rules=build_rules(["unbounded-serve-wait"])
    ) == []


# ---------------------------------------------------------------------------
# untracked-verdict-event
# ---------------------------------------------------------------------------


def test_untracked_verdict_marker_without_emit(tmp_path):
    """logger.error/.warning lines carrying verdict-class markers with no
    journal emission in the same function are exactly the ad-hoc
    narration the telemetry plane replaces (positive fixture 1)."""
    vs = run_lint(
        tmp_path,
        """
        import logging
        logger = logging.getLogger(__name__)

        def diagnose(rank):
            logger.error(f"rank {rank} VERDICT: lease expired")

        def recover(step):
            logger.warning("SENTINEL REWIND to update %d", step)
        """,
        select=["untracked-verdict-event"],
    )
    assert rule_names(vs) == ["untracked-verdict-event"] * 2
    assert "'VERDICT'" in vs[0].message
    assert "telemetry" in vs[0].message


def test_untracked_verdict_all_markers_and_module_level(tmp_path):
    """Every documented marker trips the rule, including at module level
    where no enclosing function could ever emit (positive fixture 2)."""
    vs = run_lint(
        tmp_path,
        """
        import logging
        logger = logging.getLogger(__name__)

        logger.error("startup ROLLBACK of the staged config")

        def shed(req):
            logger.warning(f"SHED request {req}: queue-full")

        def fall_back(a, b):
            logger.warning(f"CHECKPOINT FALLBACK: {a} -> {b}")

        def name_culprit(msg):
            logger.error("cross-host DIAGNOSIS: " + msg)
        """,
        select=["untracked-verdict-event"],
    )
    assert rule_names(vs) == ["untracked-verdict-event"] * 4


def test_untracked_verdict_emit_in_same_function_passes(tmp_path):
    """A journal emission in the same function satisfies the rule — both
    the `telemetry.emit(...)` and bare `emit(...)` spellings — and the
    justification comment covers paths that journal one level up
    (negative fixture 1)."""
    vs = run_lint(
        tmp_path,
        """
        import logging
        from unicore_tpu import telemetry
        logger = logging.getLogger(__name__)

        def diagnose(rank):
            telemetry.emit("guard-diagnosis", rank=rank)
            logger.error(f"rank {rank} VERDICT: lease expired")

        def recover(step, emit):
            emit("sentinel-rewind", step=step)
            logger.warning("SENTINEL REWIND to update %d", step)

        def relay(msg):
            logger.error(f"adopted VERDICT: {msg}")  # lint: journal-emitted
        """,
        select=["untracked-verdict-event"],
    )
    assert vs == []


def test_untracked_verdict_benign_lines_and_telemetry_home_pass(tmp_path):
    """Ordinary warnings without a marker never trip the rule, lowercase
    prose mentions don't count as markers, and the telemetry package
    itself is exempt — it IS the journal (negative fixture 2)."""
    src = """
    import logging
    logger = logging.getLogger(__name__)

    def warn(step):
        logger.warning(f"training slow at update {step}")
        logger.error("data pipeline stalled; will rewind the reader soon")
        logger.error("lowercase rollback talk never counts as a marker")
    """
    vs = run_lint(tmp_path, src, select=["untracked-verdict-event"])
    assert vs == []
    home = tmp_path / "unicore_tpu" / "telemetry"
    home.mkdir(parents=True)
    (home / "journal.py").write_text(
        "import logging\n"
        "logger = logging.getLogger(__name__)\n"
        "def warn():\n"
        "    logger.error('journal VERDICT bookkeeping failed')\n"
    )
    assert lint_paths(
        [str(home / "journal.py")],
        rules=build_rules(["untracked-verdict-event"]),
    ) == []


def test_untracked_verdict_nested_helper_does_not_excuse_parent(tmp_path):
    """An emit() inside a NESTED function does not satisfy the enclosing
    function's verdict line — the emission must be on the same code
    path."""
    vs = run_lint(
        tmp_path,
        """
        import logging
        logger = logging.getLogger(__name__)

        def outer(rank):
            def helper():
                from unicore_tpu import telemetry
                telemetry.emit("x")
            logger.error(f"rank {rank} VERDICT: lost")
        """,
        select=["untracked-verdict-event"],
    )
    assert rule_names(vs) == ["untracked-verdict-event"]


# ---------------------------------------------------------------------------
# whole-program engine: project call graph + dataflow (ISSUE 9 tentpole)
# ---------------------------------------------------------------------------


def _modules(tmp_path, **files):
    import textwrap as _tw

    from unicore_tpu.analysis import ModuleInfo

    mods = []
    for name, src in files.items():
        path = tmp_path / f"{name}.py"
        path.write_text(_tw.dedent(src))
        mods.append(ModuleInfo(str(path), path.read_text()))
    return mods


def test_callgraph_resolves_methods_and_decorators(tmp_path):
    """self.helper() prefers the caller's own class; decorated defs are
    indexed like any other (a decorator never hides a function)."""
    from unicore_tpu.analysis.callgraph import ProjectCallGraph

    mods = _modules(
        tmp_path,
        a="""
        import functools

        def helper():
            return 1

        class A:
            def helper(self):
                return 2

            @functools.lru_cache(None)
            def run(self):
                return self.helper()

        def outer():
            return helper()
        """,
    )
    g = ProjectCallGraph(mods)
    run = next(f for f in g.functions if f.name == "run")
    outer = next(f for f in g.functions if f.name == "outer")
    (callee,) = g.resolve_call(run, next(iter(
        n for n in __import__("ast").walk(run.node)
        if isinstance(n, __import__("ast").Call)
        and n.func.attr == "helper"
    )))
    assert callee.class_name == "A"
    import ast as _ast

    call = next(
        n for n in _ast.walk(outer.node) if isinstance(n, _ast.Call)
    )
    # bare-name resolution is a deliberate over-approximation: the
    # module-level def is a candidate (same-name methods may ride along)
    candidates = g.resolve_call(outer, call)
    assert any(c.class_name is None for c in candidates)


def test_callgraph_reachability_crosses_files(tmp_path):
    from unicore_tpu.analysis.callgraph import ProjectCallGraph

    mods = _modules(
        tmp_path,
        x="""
        def entry():
            middle()

        def middle():
            from . import y
            leaf()
        """,
        y="""
        def leaf():
            return 42
        """,
    )
    g = ProjectCallGraph(mods)
    entry = next(f for f in g.functions if f.name == "entry")
    names = {f.name for f in g.reachable([entry])}
    assert names == {"entry", "middle", "leaf"}


def test_callgraph_thread_roots_direct_and_forwarded(tmp_path):
    """Thread targets resolve both directly (target=self._loop) and when
    forwarded through a spawn helper's PARAMETER — the elastic runtime's
    idiom (closures-passed-to-Thread corner case)."""
    from unicore_tpu.analysis.callgraph import ProjectCallGraph

    mods = _modules(
        tmp_path,
        t="""
        import threading

        class Direct:
            def start(self):
                threading.Thread(target=self._loop).start()

            def _loop(self):
                pass

        class Forwarded:
            def _spawn(self, target, name):
                t = threading.Thread(target=target, name=name, daemon=True)
                t.start()
                return t

            def start(self):
                self._spawn(self._monitor, "monitor")

            def _monitor(self):
                pass
        """,
    )
    g = ProjectCallGraph(mods)
    targets = {t.name for _, t, _ in g.thread_roots()}
    assert "_loop" in targets
    assert "_monitor" in targets


def test_dataflow_reaching_functions_transitive(tmp_path):
    from unicore_tpu.analysis import dataflow
    from unicore_tpu.analysis.callgraph import ProjectCallGraph
    from unicore_tpu.analysis.core import terminal_name

    mods = _modules(
        tmp_path,
        d="""
        def sink():
            dangerous()

        def via():
            sink()

        def far():
            via()

        def clean():
            print("hi")
        """,
    )
    g = ProjectCallGraph(mods)
    reaching, witness = dataflow.reaching_functions(
        g, lambda fn, call: terminal_name(call.func) == "dangerous"
    )
    names = {f.name for f in reaching}
    assert names == {"sink", "via", "far"}
    assert {f.name for f in witness} == {"sink"}  # seed carries the site


# ---------------------------------------------------------------------------
# collective-divergence
# ---------------------------------------------------------------------------


def test_collective_divergence_one_sided_arm(tmp_path):
    vs = run_lint(
        tmp_path,
        """
        import jax
        from unicore_tpu.distributed import utils as du

        def save(args, meta):
            if jax.process_index() == 0:
                du.broadcast_object(meta)
        """,
        select=["collective-divergence"],
    )
    assert rule_names(vs) == ["collective-divergence"]
    assert "broadcast_object" in vs[0].message
    assert "process_index()" in vs[0].message


def test_collective_divergence_guard_clause_via_helper(tmp_path):
    """The arm that EXITS strands its peers from a collective reached
    later in the block — through a transitive helper two frames down."""
    vs = run_lint(
        tmp_path,
        """
        from unicore_tpu.distributed import utils as du

        def publish(args, meta):
            if args.distributed_rank != 0:
                return
            finish(meta)

        def finish(meta):
            checkpoint_sync(meta)

        def checkpoint_sync(meta):
            du.barrier("after-save")
        """,
        select=["collective-divergence"],
    )
    assert rule_names(vs) == ["collective-divergence"]
    assert "non-taken" in vs[0].message


def test_collective_divergence_both_sides_different_collectives(tmp_path):
    """Both arms reach A collective but DIFFERENT ones: rank 0 enters
    broadcast_object while everyone else enters barrier — mismatched
    collectives pair across hosts (the reorder variant)."""
    vs = run_lint(
        tmp_path,
        """
        import jax
        from unicore_tpu.distributed import utils as du

        def publish(args, meta):
            if jax.process_index() == 0:
                du.broadcast_object(meta)
            else:
                du.barrier("x")
        """,
        select=["collective-divergence"],
    )
    assert rule_names(vs) == ["collective-divergence"]
    assert "DIFFERENT host collectives" in vs[0].message
    assert "broadcast_object" in vs[0].message and "barrier" in vs[0].message


def test_collective_divergence_negative_both_sides_and_lax(tmp_path):
    """Collectives on BOTH arms are order-coherent; jax.lax device
    collectives inside shard_map bodies are SPMD, not host collectives;
    non-rank conditions never diverge across hosts."""
    vs = run_lint(
        tmp_path,
        """
        import jax
        from unicore_tpu.distributed import utils as du

        def both(args, meta):
            if jax.process_index() == 0:
                du.broadcast_object(meta)
            else:
                du.broadcast_object(None)

        def device_side(x, seq_axis):
            r = jax.lax.axis_index(seq_axis)
            if r == 0:
                pass
            return jax.lax.all_to_all(x, seq_axis, 1, 2)

        def world_size_gate(data):
            if jax.process_count() == 1:
                return [data]
            return du.all_gather_list(data)
        """,
        select=["collective-divergence"],
    )
    assert vs == []


def test_collective_divergence_rank_scoped_escape(tmp_path):
    vs = run_lint(
        tmp_path,
        """
        import jax
        from unicore_tpu.distributed import utils as du

        def save(args, meta):
            # the sanctioned rank-0 writer path: peers wait elsewhere
            if jax.process_index() == 0:  # lint: rank-scoped
                du.broadcast_object(meta)
        """,
        select=["collective-divergence"],
    )
    assert vs == []


# ---------------------------------------------------------------------------
# sharding-legality
# ---------------------------------------------------------------------------

_MESH_FIXTURE = """
DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
ALL_AXES = (DATA_AXIS, MODEL_AXIS, SEQ_AXIS)
"""


def _lint_dir(tmp_path, select=None):
    from unicore_tpu.analysis import build_rules, lint_paths

    return lint_paths([str(tmp_path)], rules=build_rules(select))


def test_sharding_legality_undeclared_axis(tmp_path):
    import textwrap

    (tmp_path / "mesh.py").write_text(_MESH_FIXTURE)
    (tmp_path / "code.py").write_text(
        textwrap.dedent(
            """
            import jax
            from jax.sharding import PartitionSpec as P
            from .mesh import DATA_AXIS

            def f():
                good = P(DATA_AXIS, "model")
                typo = P(DATA_AXIS, "modle")
                undeclared = jax.lax.psum(1, "rows")
                return good, typo, undeclared
            """
        )
    )
    vs = _lint_dir(tmp_path, select=["sharding-legality"])
    assert rule_names(vs) == ["sharding-legality"] * 2
    assert "'modle'" in vs[0].message
    assert "'rows'" in vs[1].message


def test_sharding_legality_reused_axis(tmp_path):
    import textwrap

    (tmp_path / "mesh.py").write_text(_MESH_FIXTURE)
    (tmp_path / "code.py").write_text(
        textwrap.dedent(
            """
            from jax.sharding import PartitionSpec as P

            def f():
                return P("data", "data")

            def composite_ok():
                # one DIM sharded over two axes is legal; reuse is not
                return P(("data", "seq"), "model")
            """
        )
    )
    vs = _lint_dir(tmp_path, select=["sharding-legality"])
    assert rule_names(vs) == ["sharding-legality"]
    assert "reuses axis 'data'" in vs[0].message


def test_sharding_legality_shard_map_arity(tmp_path):
    import textwrap

    (tmp_path / "mesh.py").write_text(_MESH_FIXTURE)
    (tmp_path / "code.py").write_text(
        textwrap.dedent(
            """
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            def local(x, y):
                return x

            def run(mesh, x):
                fn = shard_map(
                    local, mesh=mesh, in_specs=(P("data"),),
                    out_specs=P("data"),
                )
                return fn(x)
            """
        )
    )
    vs = _lint_dir(tmp_path, select=["sharding-legality"])
    assert rule_names(vs) == ["sharding-legality"]
    assert "1 spec(s)" in vs[0].message and "2 positional" in vs[0].message


def test_sharding_legality_zero_buffer_axis(tmp_path):
    """Flat optimizer buffers (optim/ modules) shard over 'data' only:
    a PartitionSpec naming a model-parallel axis there is flagged."""
    import textwrap

    (tmp_path / "mesh.py").write_text(_MESH_FIXTURE)
    optim = tmp_path / "optim"
    optim.mkdir()
    (optim / "flat.py").write_text(
        textwrap.dedent(
            """
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            from ..mesh import DATA_AXIS, MODEL_AXIS

            def shard_flat(bufs, mesh):
                bad = NamedSharding(mesh, P(MODEL_AXIS))
                return [
                    jax.lax.with_sharding_constraint(b, bad) for b in bufs
                ]
            """
        )
    )
    vs = _lint_dir(tmp_path, select=["sharding-legality"])
    assert rule_names(vs) == ["sharding-legality"]
    assert "flat optimizer buffer" in vs[0].message
    assert "'model'" in vs[0].message


def test_sharding_legality_zero_buffer_data_axis_ok(tmp_path):
    """The sanctioned P('data') flat-buffer sharding passes, and the same
    model-parallel spec OUTSIDE optim/ stays legal (it's how params
    shard)."""
    import textwrap

    (tmp_path / "mesh.py").write_text(_MESH_FIXTURE)
    optim = tmp_path / "optim"
    optim.mkdir()
    code = textwrap.dedent(
        """
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..mesh import DATA_AXIS, MODEL_AXIS

        def shard_flat(bufs, mesh):
            good = NamedSharding(mesh, P(DATA_AXIS))
            return [
                jax.lax.with_sharding_constraint(b, good) for b in bufs
            ]
        """
    )
    (optim / "flat.py").write_text(code)
    (tmp_path / "layers.py").write_text(
        textwrap.dedent(
            """
            from jax.sharding import PartitionSpec as P
            from .mesh import MODEL_AXIS

            TP_RULE = P(None, MODEL_AXIS)
            """
        )
    )
    vs = _lint_dir(tmp_path, select=["sharding-legality"])
    assert vs == []


def test_sharding_legality_negatives(tmp_path):
    """Clean declared-axis usage, unresolvable axis expressions, and a
    lint set WITHOUT mesh.py (nothing to check against) all pass."""
    import textwrap

    code = textwrap.dedent(
        """
        import jax
        from jax.sharding import PartitionSpec as P

        def f(axis_name):
            spec = P("data", None, "seq")
            dynamic = jax.lax.psum(1, axis_name)  # unresolvable: skipped
            return spec, dynamic

        def starred(mesh, *xs):
            from jax import shard_map

            def local(*args):
                return args[0]

            # *args absorbs any arity: no rank check possible
            return shard_map(local, mesh=mesh, in_specs=(P("data"),),
                             out_specs=P("data"))(*xs)
        """
    )
    (tmp_path / "code.py").write_text(code)
    assert _lint_dir(tmp_path, select=["sharding-legality"]) == []
    (tmp_path / "mesh.py").write_text(_MESH_FIXTURE)
    assert _lint_dir(tmp_path, select=["sharding-legality"]) == []


def test_sharding_legality_kv_cache_axes_ok(tmp_path):
    """The KV-cache pool PartitionSpec (pages replica-local, heads on the
    declared model axis — serve/kv_cache.py's layout through
    plan.kv_cache_axes) is legal: every named axis resolves to a declared
    mesh axis."""
    import textwrap

    (tmp_path / "mesh.py").write_text(_MESH_FIXTURE)
    (tmp_path / "cache.py").write_text(
        textwrap.dedent(
            """
            from jax.sharding import NamedSharding, PartitionSpec as P
            from .mesh import MODEL_AXIS

            # pool layout (num_pages, n_layers, heads, page_size, head_dim):
            # pages replica-local, heads sharded on the model axis
            KV_POOL_SPEC = P(None, None, MODEL_AXIS, None, None)

            def shard_pools(mesh, k_pool, v_pool):
                import jax

                s = NamedSharding(mesh, KV_POOL_SPEC)
                return jax.device_put(k_pool, s), jax.device_put(v_pool, s)
            """
        )
    )
    assert _lint_dir(tmp_path, select=["sharding-legality"]) == []


def test_sharding_legality_kv_cache_undeclared_axis(tmp_path):
    """A KV-cache spec inventing its own 'cache_page' axis (not declared
    in the mesh constants) is flagged — cache arrays shard through the
    SAME declared axes as everything else, or the plan's legality story
    falls apart."""
    import textwrap

    (tmp_path / "mesh.py").write_text(_MESH_FIXTURE)
    (tmp_path / "cache.py").write_text(
        textwrap.dedent(
            """
            from jax.sharding import PartitionSpec as P
            from .mesh import MODEL_AXIS

            BAD_KV_POOL_SPEC = P("cache_page", None, MODEL_AXIS, None, None)
            """
        )
    )
    vs = _lint_dir(tmp_path, select=["sharding-legality"])
    assert rule_names(vs) == ["sharding-legality"]
    assert "'cache_page'" in vs[0].message


# ---------------------------------------------------------------------------
# hardcoded-mesh-axis
# ---------------------------------------------------------------------------


def test_hardcoded_axis_pspec_literal(tmp_path):
    """A declared axis name spelled as a string literal in a
    PartitionSpec outside parallel/ is flagged; the imported-constant
    spelling and non-axis strings pass."""
    import textwrap

    (tmp_path / "mesh.py").write_text(_MESH_FIXTURE)
    (tmp_path / "layers.py").write_text(
        textwrap.dedent(
            """
            from jax.sharding import PartitionSpec as P
            from .mesh import DATA_AXIS

            def specs():
                bad = P("data", None)
                bad_tuple = P((DATA_AXIS, "model"))
                good = P(DATA_AXIS, None)
                not_an_axis = P("rows")  # undeclared: sharding-legality's job
                return bad, bad_tuple, good, not_an_axis
            """
        )
    )
    vs = _lint_dir(tmp_path, select=["hardcoded-mesh-axis"])
    assert rule_names(vs) == ["hardcoded-mesh-axis"] * 2
    assert "'data'" in vs[0].message and "DATA_AXIS" in vs[0].message
    assert "'model'" in vs[1].message


def test_hardcoded_axis_collective_and_shard_map(tmp_path):
    """The axis argument of named collectives (positional and axis_name=)
    and shard_map manual_axes/auto sets are covered."""
    import textwrap

    (tmp_path / "mesh.py").write_text(_MESH_FIXTURE)
    (tmp_path / "comms.py").write_text(
        textwrap.dedent(
            """
            import jax

            def reduce_all(x, fn, mesh):
                a = jax.lax.psum(x, "data")
                b = jax.lax.all_gather(x, axis_name="seq")
                fn2 = jax.shard_map(
                    fn, mesh=mesh, in_specs=(), out_specs=(),
                    manual_axes=frozenset({"model"}), check_vma=True,
                )
                return a, b, fn2
            """
        )
    )
    vs = _lint_dir(tmp_path, select=["hardcoded-mesh-axis"])
    assert rule_names(vs) == ["hardcoded-mesh-axis"] * 3
    assert "'data'" in vs[0].message
    assert "'seq'" in vs[1].message
    assert "'model'" in vs[2].message


def test_hardcoded_axis_negatives(tmp_path):
    """parallel/ modules (the declaration layer) may spell literals, the
    '# lint: axis-literal-ok' escape works, and a tree with no plan/mesh
    declaration leaves the rule inert."""
    import textwrap

    code_no_decl = textwrap.dedent(
        """
        from jax.sharding import PartitionSpec as P

        SPEC = P("data")
        """
    )
    (tmp_path / "code.py").write_text(code_no_decl)
    assert _lint_dir(tmp_path, select=["hardcoded-mesh-axis"]) == []

    (tmp_path / "mesh.py").write_text(_MESH_FIXTURE)
    par = tmp_path / "parallel"
    par.mkdir()
    (par / "presets.py").write_text(
        textwrap.dedent(
            """
            from jax.sharding import PartitionSpec as P

            BATCH = P(("data",))  # declaration layer: literals allowed
            """
        )
    )
    (tmp_path / "escaped.py").write_text(
        textwrap.dedent(
            """
            import jax

            def toy_mesh_sum(x):
                # fixture mesh with its own axis vocabulary
                return jax.lax.psum(x, "data")  # lint: axis-literal-ok
            """
        )
    )
    vs = _lint_dir(tmp_path, select=["hardcoded-mesh-axis"])
    assert [v.rule for v in vs if "code.py" not in v.path] == []
    # code.py's literal IS now flagged (a declaration exists)
    assert all("code.py" in v.path for v in vs) and len(vs) == 1


# ---------------------------------------------------------------------------
# unsynchronized-shared-state
# ---------------------------------------------------------------------------


def test_shared_state_write_write_race(tmp_path):
    vs = run_lint(
        tmp_path,
        """
        import threading

        class Counter:
            def __init__(self):
                self.count = 0

            def start(self):
                self._t = threading.Thread(target=self._loop)
                self._t.start()

            def _loop(self):
                while True:
                    self.count += 1

            def reset(self):
                self.count = 0
        """,
        select=["unsynchronized-shared-state"],
    )
    assert rule_names(vs) == ["unsynchronized-shared-state"]
    assert "'count'" in vs[0].message
    assert "_loop" in vs[0].message and "reset" in vs[0].message


def test_shared_state_race_through_spawn_helper_and_callee(tmp_path):
    """The thread side is the target's CALL GRAPH (a helper the loop
    calls), and the target resolves through a spawn helper's parameter."""
    vs = run_lint(
        tmp_path,
        """
        import threading

        class Engine:
            def __init__(self):
                self.phase = "idle"

            def _spawn(self, target):
                t = threading.Thread(target=target, daemon=True)
                t.start()

            def start(self):
                self._spawn(self._run)

            def _run(self):
                self._step()

            def _step(self):
                self.phase = "running"

            def stop(self):
                self.phase = "stopped"
        """,
        select=["unsynchronized-shared-state"],
    )
    assert rule_names(vs) == ["unsynchronized-shared-state"]
    assert "'phase'" in vs[0].message


def test_shared_state_negatives_lock_init_and_single_side(tmp_path):
    """A common lock on both writes passes; __init__ and the spawning
    function are construct-then-publish territory; thread-side-only
    writers race nobody."""
    vs = run_lint(
        tmp_path,
        """
        import threading

        class Locked:
            def __init__(self):
                self._lock = threading.Lock()
                self.state = "new"      # pre-start: exempt

            def start(self):
                self.state = "starting"  # spawner: exempt
                threading.Thread(target=self._loop).start()

            def _loop(self):
                with self._lock:
                    self.state = "running"

            def stop(self):
                with self._lock:
                    self.state = "stopped"

        class OneSide:
            def start(self):
                threading.Thread(target=self._loop).start()

            def _loop(self):
                self.ticks = 0

            def read(self):
                return getattr(self, "ticks", None)
        """,
        select=["unsynchronized-shared-state"],
    )
    assert vs == []


def test_shared_state_single_writer_escape(tmp_path):
    vs = run_lint(
        tmp_path,
        """
        import threading

        class Flag:
            def start(self):
                threading.Thread(target=self._loop).start()

            def _loop(self):
                self.done = True  # lint: single-writer

            def arm(self):
                self.done = False
        """,
        select=["unsynchronized-shared-state"],
    )
    assert vs == []


# ---------------------------------------------------------------------------
# stale-lint-escape
# ---------------------------------------------------------------------------


def test_stale_escape_unknown_token(tmp_path):
    vs = run_lint(
        tmp_path,
        """
        x = 1  # lint: no-such-rule-ever
        """,
    )
    assert rule_names(vs) == ["stale-lint-escape"]
    assert "no-such-rule-ever" in vs[0].message
    assert "renamed" in vs[0].message


def test_stale_escape_suppresses_nothing(tmp_path):
    """A valid token on clean code: the violation it once waived was
    fixed (or the annotation drifted) — flagged for removal."""
    vs = run_lint(
        tmp_path,
        """
        import jax

        def plain(x):
            return x + 1  # lint: host-sync-in-jit
        """,
    )
    assert rule_names(vs) == ["stale-lint-escape"]
    assert "stale escape" in vs[0].message


def test_stale_escape_live_annotation_passes(tmp_path):
    """An escape that REALLY suppresses a finding is live, and prose
    comments mentioning 'lint:' mid-sentence are not annotations."""
    vs = run_lint(
        tmp_path,
        """
        import jax

        # Suppression comments use the form `# lint: <rule>` on the line.
        @jax.jit
        def step(x):
            return x.sum().item()  # lint: host-sync-in-jit
        """,
    )
    assert vs == []


def test_stale_escape_cannot_self_suppress(tmp_path):
    """A rotten escape carrying the audit's own token must still be
    flagged — audit findings are not suppressible, else any stale escape
    could hide from the audit forever."""
    vs = run_lint(
        tmp_path,
        """
        x = 1  # lint: stale-lint-escape
        """,
    )
    assert rule_names(vs) == ["stale-lint-escape"]


def test_stale_escape_select_subset_cannot_judge(tmp_path):
    """Running a rule SUBSET must not mass-flag escapes owned by the
    excluded rules — the audit skips tokens it cannot verify."""
    vs = run_lint(
        tmp_path,
        """
        def plain(x):
            return x  # lint: host-sync-in-jit
        """,
        select=["stale-lint-escape", "untimed-collective"],
    )
    assert vs == []


# ---------------------------------------------------------------------------
# unsafe-shard-map: the deprecated experimental spelling (check_rep)
# ---------------------------------------------------------------------------


def test_unsafe_shard_map_check_rep_false(tmp_path):
    from jax import __version__ as _  # noqa: F401  (import parity)

    vs = run_lint(
        tmp_path,
        """
        from jax.experimental import shard_map as legacy

        def run(mesh, f, x):
            return legacy.shard_map(f, mesh=mesh, in_specs=(None,),
                                    out_specs=None, check_rep=False)(x)
        """,
        select=["unsafe-shard-map"],
    )
    assert rule_names(vs) == ["unsafe-shard-map"]
    assert "check_rep" in vs[0].message


# ---------------------------------------------------------------------------
# SARIF output
# ---------------------------------------------------------------------------


def test_sarif_structure_and_locations(tmp_path):
    import json

    from unicore_tpu.analysis import build_rules, lint_paths
    from unicore_tpu.analysis.sarif import to_sarif

    path = tmp_path / "dirty.py"
    path.write_text(
        "import jax\n\n@jax.jit\ndef f(x):\n    return x.item()\n"
    )
    rules = build_rules()
    vs = lint_paths([str(path)], rules=rules)
    assert vs, "fixture must produce at least one finding"
    log = to_sarif(vs, rules)
    # round-trips as JSON and carries the schema envelope
    log = json.loads(json.dumps(log))
    assert log["version"] == "2.1.0"
    run = log["runs"][0]
    assert run["tool"]["driver"]["name"] == "unicore-tpu-lint"
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert "host-sync-in-jit" in rule_ids
    result = run["results"][0]
    assert result["ruleId"] == "host-sync-in-jit"
    assert result["ruleIndex"] == [
        r["id"] for r in run["tool"]["driver"]["rules"]
    ].index("host-sync-in-jit")
    region = result["locations"][0]["physicalLocation"]["region"]
    assert region["startLine"] == 5
    assert region["startColumn"] >= 1  # SARIF columns are 1-based
    uri = result["locations"][0]["physicalLocation"]["artifactLocation"][
        "uri"
    ]
    assert "\\" not in uri


def test_sarif_cli_format(tmp_path):
    import json

    from unicore_tpu_cli.lint import cli_main

    dirty = tmp_path / "dirty.py"
    dirty.write_text(
        "import jax\n\n@jax.jit\ndef f(x):\n    return x.item()\n"
    )
    out_path = tmp_path / "out.sarif"
    import contextlib
    import io

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = cli_main([str(dirty), "--format", "sarif"])
    assert rc == 1  # exit codes identical to text mode
    log = json.loads(buf.getvalue())
    assert log["runs"][0]["results"]
    out_path.write_text(buf.getvalue())

    buf = io.StringIO()
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    with contextlib.redirect_stdout(buf):
        rc = cli_main([str(clean), "--format", "sarif"])
    assert rc == 0
    log = json.loads(buf.getvalue())
    assert log["runs"][0]["results"] == []
    # a clean run still publishes the rule inventory for code scanning
    assert log["runs"][0]["tool"]["driver"]["rules"]
