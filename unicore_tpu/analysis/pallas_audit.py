"""Pallas kernel auditor: grid-enumeration verification of every TPU
kernel, before Mosaic ever sees it (`unicore-tpu-lint --kernels`).

Every bug class this tree has actually shipped in its ~2,600 lines of
hand-written kernels lived BELOW the AST — the ring kernel's
loop-invariant scalar-prefetch seed (PR 9), the int8 sublane hole
``auto`` mode could hand Mosaic on the path CPU CI never runs (PR 12
round 5).  This pass closes that layer: it runs each kernel module's
registered representative shapes (``@audit_case`` in ``ops/_pallas.py``)
with ``pallas_call`` INTERCEPTED — the grid, ``BlockSpec``\\ s, scratch
shapes, and index-map lambdas are captured and the kernel body never
executes — then concretely enumerates the grid and checks the captured
geometry (``kernel_geometry.py``): block bounds, tiling legality, the
VMEM budget, output write races, and per-axis PRNG-seed coverage.

Two layers, matching the lint driver's two costs:

* **always on** (pure AST, the default run): ``pallas-kernel-coverage``
  — every module containing a ``pallas_call`` site must register at
  least one ``@audit_case``, so a new kernel cannot silently dodge the
  auditor.
* **--kernels** (opt-in, the CI "Kernel audit smoke" step): the audit
  cases actually run.  This is the ONE deliberate exception to the
  driver's "linting never imports the code under analysis" rule — the
  kernel modules are imported and their dispatch entry points called on
  CPU with every dispatch ``ModeGate`` forced ``on`` (restored after),
  which is safe because the interceptor returns zeros instead of
  lowering anything.

Site discovery is AST-first: direct sites are ``pallas_call`` /
``_pallas_call`` call expressions; dispatch sites are cross-module calls
that resolve (PR-9 ``ProjectCallGraph``) to a kernel-reaching function
defined under ``ops/`` — the inventory a test pins so the site count can
only grow.  Captured kernels are attributed back to their direct site's
line, so the house ``# lint:`` escape discipline applies unchanged.

The write-race (d) and seed (e) checks pair the captured geometry with a
module-level AST analysis: ``pl.when`` guard predicates and
``prng_seed`` argument expressions are resolved to the grid axes they
mention, through the tree's program-id binding idioms (tuple unpacking,
``(pl.program_id(i) for i in range(n))``, derived scalars like
``b = g * r_per_g + r``) and through seed-helper calls (``_seed_block``,
``_mix_seed``) followed cross-module by name.  The analysis is
module-scoped — one function's guard can vouch for a sibling kernel in
the same file — which is coarse but sound for this tree's one-kernel-
family-per-file layout; the fixture suite pins exact behavior per check.
"""

import ast
import dataclasses
import os
import traceback
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from unicore_tpu.analysis.core import (
    LintRule,
    ModuleInfo,
    Violation,
    register_lint_rule,
    terminal_name,
)
from unicore_tpu.analysis.callgraph import body_calls, shared_graph

#: set by ``unicore-tpu-lint --kernels``; the five geometry rules no-op
#: (and nothing below imports jax) while this is False
KERNEL_AUDIT_ENABLED = False

_CALL_NAMES = ("pallas_call", "_pallas_call")


# ---------------------------------------------------------------------------
# AST site discovery
# ---------------------------------------------------------------------------

def direct_sites(module: ModuleInfo) -> List[int]:
    """Linenos of ``pallas_call`` call expressions in ``module``, the
    wrapper def in ``ops/_pallas.py`` itself excluded."""
    lines: List[int] = []

    def visit(node, in_wrapper):
        for child in ast.iter_child_nodes(node):
            wrapper = in_wrapper or (
                isinstance(child, ast.FunctionDef)
                and child.name == "pallas_call"
            )
            if (
                not wrapper
                and isinstance(child, ast.Call)
                and terminal_name(child.func) in _CALL_NAMES
            ):
                lines.append(child.lineno)
            visit(child, wrapper)

    visit(module.tree, False)
    return sorted(set(lines))


def has_audit_case(module: ModuleInfo) -> bool:
    """Pure-AST: does the module register at least one ``@audit_case``?"""
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.FunctionDef):
            continue
        for dec in node.decorator_list:
            if (
                isinstance(dec, ast.Call)
                and terminal_name(dec.func) == "audit_case"
            ):
                return True
    return False


def dispatch_sites(modules: Sequence[ModuleInfo]) -> Dict[str, List[int]]:
    """Cross-module calls that resolve to a kernel-reaching function
    defined under ``ops/`` — the places the rest of the tree enters a
    kernel's dispatch path.  Keyed by module path."""
    graph = shared_graph(modules)
    kernel_paths = {m.path for m in modules if direct_sites(m)}

    bearing = set()
    for fn in graph.functions:
        for call in body_calls(fn.node):
            if (
                terminal_name(call.func) in _CALL_NAMES
                and fn.name != "pallas_call"
                and fn.module.path in kernel_paths
            ):
                bearing.add(fn)
                break
    # reverse-BFS: everything from which a kernel-bearing fn is reachable
    callers: Dict[object, Set[object]] = {}
    for fn in graph.functions:
        for call in body_calls(fn.node):
            for callee in graph.resolve_call(fn, call):
                callers.setdefault(callee, set()).add(fn)
    reaching = set(bearing)
    stack = list(bearing)
    while stack:
        fn = stack.pop()
        for caller in callers.get(fn, ()):
            if caller not in reaching:
                reaching.add(caller)
                stack.append(caller)

    sites: Dict[str, List[int]] = {}
    for fn in graph.functions:
        for call in body_calls(fn.node):
            if terminal_name(call.func) in _CALL_NAMES:
                continue  # direct sites counted separately
            for callee in graph.resolve_call(fn, call):
                if (
                    callee in reaching
                    and callee.module.path != fn.module.path
                    and os.sep + "ops" + os.sep in callee.module.path
                ):
                    sites.setdefault(fn.module.path, []).append(call.lineno)
                    break
    return {p: sorted(set(ls)) for p, ls in sites.items()}


def audit_inventory(modules: Sequence[ModuleInfo]) -> Dict[str, Dict[str, List[int]]]:
    """The site inventory the acceptance test pins: every direct
    ``pallas_call`` site and every dispatch site, per module path."""
    return {
        "direct": {
            m.path: direct_sites(m) for m in modules if direct_sites(m)
        },
        "dispatch": dispatch_sites(modules),
    }


# ---------------------------------------------------------------------------
# module kernel facts: guard axes, seed axes (AST half of checks d/e)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ModuleKernelFacts:
    #: grid axes some ``pl.when`` predicate in the module mentions
    guarded_axes: Set[int]
    #: grid axes flowing into some ``prng_seed`` (or seed-helper) call
    seed_axes: Set[int]
    #: the module seeds a PRNG at all (check (e) applies)
    has_seed_calls: bool
    #: some kernel accumulates via ``ref[...] += ...`` (read-modify-write)
    has_augassign_store: bool


def seed_sink_names(modules: Sequence[ModuleInfo]) -> Set[str]:
    """Names of functions that (transitively, by terminal name, across
    every linted module) call ``pltpu.prng_seed`` — calling one of these
    with program-id arguments counts as mixing those axes into the seed."""
    sinks = {"prng_seed"}
    fns = [
        node
        for m in modules
        for node in ast.walk(m.tree)
        if isinstance(node, ast.FunctionDef)
    ]
    changed = True
    while changed:
        changed = False
        for fn in fns:
            if fn.name in sinks:
                continue
            for node in ast.walk(fn):
                if (
                    isinstance(node, ast.Call)
                    and terminal_name(node.func) in sinks
                ):
                    sinks.add(fn.name)
                    changed = True
                    break
    return sinks


def _is_program_id(node) -> Optional[int]:
    if (
        isinstance(node, ast.Call)
        and terminal_name(node.func) == "program_id"
        and node.args
        and isinstance(node.args[0], ast.Constant)
        and isinstance(node.args[0].value, int)
    ):
        return node.args[0].value
    return None


def _record_assign(node: ast.Assign, bindings: Dict[str, object]) -> None:
    if len(node.targets) != 1:
        return
    t, v = node.targets[0], node.value
    if isinstance(t, ast.Name):
        bindings[t.id] = v
    elif isinstance(t, ast.Tuple) and all(
        isinstance(e, ast.Name) for e in t.elts
    ):
        if isinstance(v, ast.Tuple) and len(v.elts) == len(t.elts):
            for e, val in zip(t.elts, v.elts):
                bindings[e.id] = val
        elif isinstance(v, ast.GeneratorExp) and (
            terminal_name(getattr(v.elt, "func", None)) == "program_id"
        ):
            # b, h, iq, ik = (pl.program_id(i) for i in range(4))
            for axis, e in enumerate(t.elts):
                bindings[e.id] = ("axis", axis)


def _extract_axes(
    expr, bindings: Dict[str, object], visited: Optional[Set[str]] = None
) -> Set[int]:
    """Grid axes an expression mentions, through program_id calls and
    (recursively) through names bound to program-id-derived scalars."""
    if visited is None:
        visited = set()
    axes: Set[int] = set()
    for node in ast.walk(expr):
        axis = _is_program_id(node)
        if axis is not None:
            axes.add(axis)
        elif (
            isinstance(node, ast.Name)
            and node.id in bindings
            and node.id not in visited
        ):
            visited.add(node.id)
            bound = bindings[node.id]
            if isinstance(bound, tuple) and bound[0] == "axis":
                axes.add(bound[1])
            else:
                axes |= _extract_axes(bound, bindings, visited)
    return axes


def module_kernel_facts(
    module: ModuleInfo, sinks: Set[str]
) -> ModuleKernelFacts:
    facts = ModuleKernelFacts(set(), set(), False, False)

    def scope_nodes(fn: ast.FunctionDef):
        """Nodes of ``fn``'s own scope; nested defs are recursed into
        separately but their DECORATORS evaluate in this scope."""
        own, nested = [], []
        stack = list(fn.body)
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                nested.append(node)
                stack.extend(node.decorator_list)
                continue
            own.append(node)
            stack.extend(ast.iter_child_nodes(node))
        return own, nested

    def analyze(fn: ast.FunctionDef, inherited: Dict[str, object]):
        own, nested = scope_nodes(fn)
        bindings = dict(inherited)
        for node in own:
            if isinstance(node, ast.Assign):
                _record_assign(node, bindings)
        for node in own:
            if isinstance(node, ast.AugAssign) and isinstance(
                node.target, ast.Subscript
            ):
                facts.has_augassign_store = True
            if not isinstance(node, ast.Call):
                continue
            name = terminal_name(node.func)
            if name == "when" and node.args:
                facts.guarded_axes |= _extract_axes(node.args[0], bindings)
            if name in sinks:
                facts.has_seed_calls = True
                for arg in node.args:
                    facts.seed_axes |= _extract_axes(arg, bindings)
        for sub in nested:
            analyze(sub, bindings)

    for node in module.tree.body:
        if isinstance(node, ast.FunctionDef):
            analyze(node, {})
    return facts


# ---------------------------------------------------------------------------
# capture harness (--kernels only; imports jax)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class AuditResult:
    findings: Dict[str, List[Violation]]
    inventory: Dict[str, Dict[str, List[int]]]
    captures: int
    cases: int


def _import_kernel_module(real_path: str):
    """Import a kernel module: dotted import for files inside the
    ``unicore_tpu`` package (so ops modules keep their identity), spec
    loading for fixture files anywhere else."""
    import importlib
    import importlib.util

    parts = real_path.split(os.sep)
    if "unicore_tpu" in parts:
        i = parts.index("unicore_tpu")
        dotted = ".".join(parts[i:])[: -len(".py")]
        return importlib.import_module(dotted)
    name = "ut_kernel_fixture_" + str(abs(hash(real_path)))
    spec = importlib.util.spec_from_file_location(name, real_path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _find_site(kernel_paths: Set[str]) -> Tuple[Optional[str], int]:
    for frame in reversed(traceback.extract_stack()):
        p = os.path.realpath(frame.filename)
        if p in kernel_paths:
            return p, frame.lineno
    return None, 0


def _normalize_call(pos, kw):
    """Resolve one intercepted ``pallas_call`` construction to
    (num_scalar_prefetch, grid, in_specs, out_specs list, out_shape tree,
    out_shapes list, scratch list)."""
    out_shape = kw.get("out_shape", pos[0] if pos else None)
    gs = kw.get("grid_spec")
    if gs is not None:
        nsp = int(getattr(gs, "num_scalar_prefetch", 0) or 0)
        grid = tuple(getattr(gs, "grid", ()) or ())
        in_specs = list(getattr(gs, "in_specs", ()) or ())
        out_specs = getattr(gs, "out_specs", None)
        scratch = list(getattr(gs, "scratch_shapes", ()) or ())
    else:
        nsp = 0
        grid = kw.get("grid", ())
        grid = (grid,) if isinstance(grid, int) else tuple(grid or ())
        in_specs = list(kw.get("in_specs", ()) or ())
        out_specs = kw.get("out_specs")
        scratch = list(kw.get("scratch_shapes", ()) or ())
    if out_specs is None:
        out_specs_list = []
    elif isinstance(out_specs, (list, tuple)):
        out_specs_list = list(out_specs)
    else:
        out_specs_list = [out_specs]
    if isinstance(out_shape, (list, tuple)):
        out_shapes_list = list(out_shape)
    else:
        out_shapes_list = [out_shape]
    return nsp, grid, in_specs, out_specs_list, out_shape, out_shapes_list, scratch


def run_audit_cases(kernel_paths: Set[str]):
    """Import the kernel modules, run every audit case they registered
    with ``pallas_call`` intercepted and all dispatch gates forced on.

    Returns ``(captures, case_errors)`` — :class:`CapturedKernel` rows
    (kernel bodies never execute; each interception returns zeros of the
    declared out_shape) and ``(AuditCase, exception)`` pairs."""
    import jax
    import jax.numpy as jnp
    import jax.experimental.pallas as pl_mod

    from unicore_tpu.analysis.kernel_geometry import BlockUse, CapturedKernel
    from unicore_tpu.ops._pallas import AUDIT_CASES, ModeGate

    import_errors: List[Tuple[str, Exception]] = []
    for path in sorted(kernel_paths):
        try:
            _import_kernel_module(path)
        except Exception as exc:
            import_errors.append((path, exc))

    cases = sorted(
        (
            c
            for c in AUDIT_CASES.values()
            if os.path.realpath(c.path) in kernel_paths
        ),
        key=lambda c: c.name,
    )

    captures: List[CapturedKernel] = []
    errors: List[Tuple[object, Exception]] = list(import_errors)
    current_case = [""]
    real_call = pl_mod.pallas_call

    def intercept(kernel, *pos, **kw):
        kw.pop("interpret", None)
        site_path, site_line = _find_site(kernel_paths)
        (nsp, grid, in_specs, out_specs_list, out_shape,
         out_shapes_list, scratch) = _normalize_call(pos, kw)
        case_name = current_case[0]

        def runner(*operands):
            uses: List[BlockUse] = []
            arrays = operands[nsp:]
            prefetch = _concrete(operands[:nsp])
            for i, (spec, arr) in enumerate(zip(in_specs, arrays)):
                uses.append(_block_use("in", i, spec, tuple(arr.shape),
                                       arr.dtype, prefetch))
            for i, (spec, sd) in enumerate(
                zip(out_specs_list, out_shapes_list)
            ):
                uses.append(_block_use("out", i, spec, tuple(sd.shape),
                                       sd.dtype, prefetch))
            for i, s in enumerate(scratch):
                if "sem" in str(getattr(s, "dtype", "sem")):
                    continue  # a semaphore takes no VMEM
                shape = tuple(int(d) for d in s.shape)
                uses.append(BlockUse("scratch", i, shape, s.dtype, shape))
            if site_path is not None:
                captures.append(CapturedKernel(
                    case=case_name, path=site_path, line=site_line,
                    grid=tuple(int(g) for g in grid), uses=tuple(uses),
                    name=kw.get("name") or "",
                ))
            return jax.tree_util.tree_map(
                lambda sd: jnp.zeros(sd.shape, sd.dtype), out_shape
            )

        return runner

    def _left_in_hbm(spec):
        """An operand the spec leaves where it lies (``pl.ANY``, HBM)."""
        from jax.experimental.pallas import tpu as pltpu

        return getattr(spec, "memory_space", None) in (pl_mod.ANY, pltpu.HBM)

    def _concrete(prefetch):
        """The scalar-prefetch operands as numpy arrays, so an index map
        that READS one (a block map: ``ids_ref[...]``) is evaluated on the
        values the case passed; ``()`` when any is a tracer (a case run
        under ``jit``), which leaves such a map to fail as opaque."""
        import numpy as np

        if any(isinstance(x, jax.core.Tracer) for x in prefetch):
            return ()
        return tuple(np.asarray(x) for x in prefetch)

    def _block_use(kind, index, spec, array_shape, dtype, prefetch):
        if _left_in_hbm(spec):
            # the kernel copies what it needs itself: no block is resident
            return BlockUse(kind, index, (), dtype, array_shape, None)
        if spec is None or getattr(spec, "block_shape", None) is None:
            return BlockUse(kind, index, array_shape, dtype, array_shape,
                            None)
        blk = tuple(
            int(b) if b is not None else int(d)
            for b, d in zip(spec.block_shape, array_shape)
        )
        imap = None
        if None not in spec.block_shape:
            # Pallas hands an index map the program ids, then the prefetch refs
            def imap(*pid, _map=spec.index_map):
                return _map(*pid, *prefetch)
        return BlockUse(kind, index, blk, dtype, array_shape, imap)

    saved_gates = []
    for gate in ModeGate.instances:
        saved_gates.append(
            (gate, gate._mode, os.environ.pop(gate.env_var, None))
        )
        gate._mode = "on"
    pl_mod.pallas_call = intercept
    try:
        for case in cases:
            current_case[0] = case.name
            try:
                case.fn()
            except Exception as exc:
                errors.append((case, exc))
    finally:
        pl_mod.pallas_call = real_call
        for gate, mode, env in saved_gates:
            gate._mode = mode
            if env is not None:
                os.environ[gate.env_var] = env
    return captures, errors


# ---------------------------------------------------------------------------
# the audit proper (memoized per lint run)
# ---------------------------------------------------------------------------

RULE_BOUNDS = "kernel-block-bounds"
RULE_TILING = "kernel-tiling"
RULE_VMEM = "kernel-vmem-budget"
RULE_REVISIT = "kernel-revisit-race"
RULE_SEED = "kernel-seed-axis"
RULE_COVERAGE = "pallas-kernel-coverage"

_memo: Tuple[Optional[tuple], Optional[AuditResult]] = (None, None)


def run_kernel_audit(modules: Sequence[ModuleInfo]) -> AuditResult:
    global _memo
    key = tuple(id(m) for m in modules)
    if _memo[0] == key:
        return _memo[1]

    from unicore_tpu.analysis import kernel_geometry as kg

    by_real: Dict[str, ModuleInfo] = {}
    kernel_mods: Dict[str, ModuleInfo] = {}
    for m in modules:
        real = os.path.realpath(m.path)
        by_real[real] = m
        if direct_sites(m):
            kernel_mods[real] = m

    captures, errors = run_audit_cases(set(kernel_mods))

    sinks = seed_sink_names(modules)
    facts = {
        real: module_kernel_facts(m, sinks)
        for real, m in kernel_mods.items()
    }

    findings: Dict[str, List[Violation]] = {}

    def add(rule: str, real_path: str, line: int, message: str):
        m = by_real[real_path]
        findings.setdefault(rule, []).append(
            Violation(rule, m.path, line, 0, message)
        )

    covered: Set[Tuple[str, int]] = set()
    for cap in captures:
        sites = direct_sites(by_real[cap.path])
        line = cap.line
        if line not in sites and sites:
            near = min(sites, key=lambda s: abs(s - line))
            if abs(near - line) <= 60:
                line = near
        covered.add((cap.path, line))
        label = f"kernel at {os.path.basename(cap.path)}:{line} (case {cap.case}, grid {cap.grid})"
        try:
            for msg in kg.check_block_bounds(cap):
                add(RULE_BOUNDS, cap.path, line, f"{label}: {msg}")
            for msg in kg.check_tiling(cap):
                add(RULE_TILING, cap.path, line, f"{label}: {msg}")
            for msg in kg.check_vmem(cap):
                add(RULE_VMEM, cap.path, line, f"{label}: {msg}")
            mod_facts = facts[cap.path]
            for out in cap.outputs():
                if out.index_map is None:
                    continue
                for axis in sorted(kg.revisit_axes(cap, out)):
                    if (
                        axis in mod_facts.guarded_axes
                        or mod_facts.has_augassign_store
                    ):
                        continue
                    add(
                        RULE_REVISIT, cap.path, line,
                        f"{label}: {out.label} index map ignores grid "
                        f"axis {axis} (size {cap.grid[axis]}) — the block "
                        f"is revisited with no when(program_id) guard or "
                        f"read-modify-write accumulation in the module",
                    )
            if mod_facts.has_seed_calls:
                missing = sorted(
                    kg.input_axes(cap) - mod_facts.seed_axes
                )
                if missing:
                    add(
                        RULE_SEED, cap.path, line,
                        f"{label}: prng_seed inputs never mix grid "
                        f"axes {missing} although input blocks vary "
                        f"along them — the PRNG stream repeats across "
                        f"revisited data (the PR-9 ring-seed bug class)",
                    )
        except kg.OpaqueGeometry as exc:
            add(
                RULE_COVERAGE, cap.path, line,
                f"{label}: geometry not enumerable: {exc}",
            )

    for real, m in kernel_mods.items():
        for site in direct_sites(m):
            if (real, site) not in covered:
                add(
                    RULE_COVERAGE, real, site,
                    f"pallas_call site never captured by any @audit_case "
                    f"run — register a representative-shape case in "
                    f"{os.path.basename(real)} that reaches it",
                )
    for origin, exc in errors:
        if isinstance(origin, str):  # module import failure
            real = os.path.realpath(origin)
            add(
                RULE_COVERAGE, real, 1,
                f"kernel module failed to import for the audit: {exc!r}",
            )
        else:
            real = os.path.realpath(origin.path)
            line = origin.fn.__code__.co_firstlineno
            add(
                RULE_COVERAGE, real, line,
                f"audit case {origin.name!r} raised {exc!r}",
            )

    result = AuditResult(
        findings=findings,
        inventory=audit_inventory(modules),
        captures=len(captures),
        cases=len(set(c.case for c in captures)),
    )
    _memo = (key, result)

    try:
        from unicore_tpu.telemetry.journal import emit

        emit(
            "kernel-audit",
            sites=sum(len(v) for v in result.inventory["direct"].values()),
            dispatch_sites=sum(
                len(v) for v in result.inventory["dispatch"].values()
            ),
            captures=result.captures,
            findings=sum(len(v) for v in findings.values()),
        )
    except Exception:
        pass
    return result


# ---------------------------------------------------------------------------
# lint rules
# ---------------------------------------------------------------------------

class _KernelAuditRule(LintRule):
    """Base for the five geometry rules: no-op unless ``--kernels``."""

    scope = "project"

    def check_project(self, modules):
        if not KERNEL_AUDIT_ENABLED:
            return []
        return run_kernel_audit(modules).findings.get(self.name, [])


@register_lint_rule(RULE_BOUNDS)
class KernelBlockBounds(_KernelAuditRule):
    name = RULE_BOUNDS
    description = (
        "an index map sends some program id to a block origin x block "
        "shape outside the operand array (--kernels; enumerated at the "
        "module's @audit_case shapes)"
    )


@register_lint_rule(RULE_TILING)
class KernelTiling(_KernelAuditRule):
    name = RULE_TILING
    description = (
        "an operand/output block violates TPU tiling: last dim neither a "
        "128-multiple nor the full dim, or a sublane dim off the dtype "
        "tile (8 fp32 / 16 bf16 / 32 int8) (--kernels)"
    )


@register_lint_rule(RULE_VMEM)
class KernelVmemBudget(_KernelAuditRule):
    name = RULE_VMEM
    description = (
        "one grid step's resident bytes (double-buffered io blocks + "
        "scratch) exceed the shared VMEM budget from ops/_pallas.py "
        "(--kernels)"
    )


@register_lint_rule(RULE_REVISIT)
class KernelRevisitRace(_KernelAuditRule):
    name = RULE_REVISIT
    justifications = ("sequential-grid-accumulation",)
    description = (
        "an output's index map ignores a multi-step grid axis — the "
        "block is revisited — and the kernel neither guards with "
        "when(program_id...) nor accumulates read-modify-write "
        "(--kernels)"
    )


@register_lint_rule(RULE_SEED)
class KernelSeedAxis(_KernelAuditRule):
    name = RULE_SEED
    justifications = ("shared-prng-stream",)
    description = (
        "prng_seed inputs do not mix every grid axis that delivers "
        "fresh data — the per-axis generalization of the constant-seed "
        "taint rule (--kernels)"
    )


@register_lint_rule(RULE_COVERAGE)
class PallasKernelCoverage(LintRule):
    name = RULE_COVERAGE
    scope = "project"
    justifications = ("kernel-audit-exempt",)
    description = (
        "every module with a pallas_call site must register an "
        "@audit_case (pure AST, always on); under --kernels also flags "
        "sites no case captures, failing cases, and non-enumerable "
        "geometry"
    )

    def check_project(self, modules):
        out: List[Violation] = []
        for m in modules:
            sites = direct_sites(m)
            if sites and not has_audit_case(m):
                out.append(Violation(
                    self.name, m.path, sites[0], 0,
                    "module contains %d pallas_call site(s) but registers "
                    "no @audit_case representative shapes — the kernel "
                    "auditor cannot see it" % len(sites),
                ))
        if KERNEL_AUDIT_ENABLED:
            out.extend(
                run_kernel_audit(modules).findings.get(self.name, [])
            )
        return out
