"""Which module owns each device operation: the join from a profiler
trace's op events to the program's own scopes.

A device trace names each executed operation by its optimized-HLO
instruction (``%fusion.2067``), a compiler-made label nobody can plan
from.  The same instruction in the compiled program's HLO text carries
``metadata={op_name="jit(train_step)/.../layers_3/self_attn/..."}``: the
name stack Flax pushes for every module plus the trainer's phase scopes
(``forward``, ``multiply-grads``, ``clip-grads``, ``optimizer``).  This
module keeps that text for the programs launched inside a profiler
capture and turns it into a table ``{instruction name -> op_name path}``.

Tracing is "on" exactly when a ``jax.profiler`` capture is running
(``--profile-steps``, or whoever started one around the trainer: the
benchmark's ``--trace 1``).  :func:`note_launch` is called before every
launch of a train program; outside a capture it reads one boolean.  Inside
one it keeps ``fn.lower(*args).compile().as_text()`` once per program and
argument geometry.  For a program that has run, that is an in-memory
cache hit (0.48 s for the 7 MB of the BERT-base step on a v5e host); for
one that has not (a capture from update 0) it is the compilation the call
would have made, which the call then finds.  It comes before the launch,
so the capture's first launch starts late and no later one waits: the
device never idles for it inside the traced window.  Only text is kept: no reference to the trainer,
the jitted function or a device array, so whoever reads the trace may do
so after the trainer is gone.  ``ProfileWindow._finish`` writes the tables
beside an operator's capture (:func:`write_tables`);
``benchmark/trace_scopes.py`` reduces a trace with them.
"""

import collections
import json
import logging
import math
import os
import re
import time
from typing import Dict, List

logger = logging.getLogger(__name__)

_MODULE_RE = re.compile(r"^HloModule\s+([\w.\-]+)", re.MULTILINE)
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
#: an instruction's name alone (the walker's own pattern also wants the
#: result type, and gives up on a long tuple's ``/*index=5*/`` comments)
_NAME_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=")

#: HLO text of the programs launched in the running (or the last) capture
_texts: List[str] = []
#: (program, argument geometry) already kept in the running capture
_noted = set()
#: the scope tables of ``_texts[:len(_tables)]``: each text is parsed once
_tables: List[Dict] = []


def capture_running() -> bool:
    from jax.profiler import TraceAnnotation

    return TraceAnnotation.is_enabled()


def note_launch(program: str, fn, args) -> None:
    """Before a launch of the jitted ``fn(*args)``: inside a profiler
    capture, keep the compiled program's HLO text, once per program and
    argument geometry per capture (a geometry first seen later in the
    capture is kept then)."""
    if not capture_running():
        _noted.clear()  # the next capture keeps its programs anew
        return
    import jax

    key = (program, tuple(
        (getattr(x, "shape", None), str(getattr(x, "dtype", type(x))))
        for x in jax.tree_util.tree_leaves(args)
    ))
    if key in _noted:
        return
    if not _noted:
        _texts.clear()  # a new capture: the last one's programs go
        _tables.clear()
    _noted.add(key)
    t0 = time.perf_counter()
    try:
        _texts.append(fn.lower(*args).compile().as_text())
    except Exception as err:  # a diagnostic must not stop an update
        logger.warning(f"hlo-scopes: no HLO text for {program}: {err}")
        return
    logger.info(
        f"hlo-scopes: kept the scope table of {program} "
        f"({len(_texts[-1])} bytes of HLO, "
        f"{time.perf_counter() - t0:.3f}s)"
    )


def _audit():
    """The HLO walker of ``--fusion-audit`` (its computations, shapes and
    operand names); imported late because the analysis package registers
    every lint rule as it is imported."""
    from unicore_tpu.analysis import fusion_audit

    return fusion_audit


def scope_table(hlo: str) -> Dict:
    """``{"module": <HloModule name>, "instructions": {name: op_name},
    "work": {name: {"flops": int, "bytes": int, "pass": str}}}`` for
    every instruction the device can execute as an operation of its own:
    those of the entry computation and of the bodies it runs (``while``,
    ``conditional``, ``call``), a fusion by its own line; the insides of
    fusions and reducers are their caller's.  An instruction without
    metadata maps to ``""``.  ``work`` (:class:`_Work`) leaves out the
    instructions that have none: no product, no bytes and no pass."""
    audit = _audit()
    comps = audit._split_computations(hlo)
    inner = set()
    for comp in comps:
        for line in comp["lines"]:
            inner.update(audit._CALLED_RE.findall(line))
    try:
        work = _Work(comps)
    except Exception as err:  # a diagnostic must not stop the trainer
        logger.warning(f"hlo-scopes: the program's work was not read: {err}")
        work = None
    instructions, stated, unread = {}, {}, []
    for comp in comps:
        if comp["name"] in inner:
            continue
        rows = work.named[comp["name"]] if work else {}
        for line in comp["lines"]:
            name = _NAME_RE.match(line)
            if not name:
                continue
            scope = _OP_NAME_RE.search(line)
            path = instructions[name.group(1)] = scope.group(1) if scope else ""
            try:
                row = rows[name.group(1)]
                does = {
                    "flops": work.flops_of(comp["name"], row),
                    "bytes": work.bytes_of(comp["name"], row),
                    "pass": pass_of(path),
                }
            except Exception:
                # a line, a form of product or a type this parser has not
                # met: the instruction keeps its path and states no work
                unread.append(name.group(1))
                continue
            if any(does.values()):
                stated[name.group(1)] = does
    module = _MODULE_RE.search(hlo)
    module = module.group(1) if module else ""
    if unread and work:
        logger.warning(
            f"hlo-scopes: no work read for {len(unread)} instruction(s) of "
            f"{module} (the first: {unread[0]})"
        )
    return {"module": module, "instructions": instructions, "work": stated}


# -- what each operation has to do --------------------------------------------

#: opcodes that move nothing of their own: plumbing, the wrappers whose
#: bodies' operations are the device's own events, and the second half of
#: an asynchronous pair (its first half moved the bytes)
_MOVES_NOTHING = frozenset({
    "bitcast", "get-tuple-element", "tuple", "parameter", "constant",
    "while", "call", "conditional", "after-all",
})
#: opcodes that read of their first operand what they return, not all of it
_READS_ITS_RESULT = frozenset({"dynamic-slice", "slice", "gather"})
#: opcodes that write into their first operand in place: where the update is
_UPDATE_AT = {"dynamic-update-slice": 1, "scatter": 2}
#: the TPU compiler's own custom calls that name or reserve a buffer and
#: move nothing (their events last no time: 7,306 ``ConcatBitcast`` of
#: BERT's traced updates took 4 us together, PR 37)
_BOOKKEEPING_RE = re.compile(
    r'custom_call_target="(AllocateBuffer|ConcatBitcast|'
    r'AssumeGatherIndicesInBound)"'
)
_FUSION_CALLS_RE = re.compile(r"\bcalls=%([\w.\-]+)")
_CONTRACTING_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_DIM_LABELS_RE = re.compile(r"dim_labels=(\w+)_(\w+)->(\w+)")
_WINDOW_RE = re.compile(r"window=\{([^}]*)\}")

#: one instruction line: ``args`` is the text between the opcode's
#: brackets, ``operands`` the ``%names`` in it
_Row = collections.namedtuple(
    "_Row", "name rtype opcode args operands attrs is_root"
)


def pass_of(path: str) -> str:
    """``remat`` / ``bwd`` / ``fwd`` / ``""`` from an ``op_name`` path.
    What this JAX (0.9.0) writes: the first forward of a differentiated
    function is ``jit(f)/jvp(forward)/...`` and its backward
    ``jit(f)/transpose(jvp(forward))/...``; inside ``jax.checkpoint`` the
    backward's products read ``.../transpose(jvp(..))/checkpoint/fc2/
    dot_general`` and the second forward's ``.../transpose(jvp(..))/
    checkpoint/rematted_computation/fc1/dot_general``.  Both are under
    ``transpose(``, so ``rematted_computation`` is looked for first, and a
    bare ``checkpoint`` component is backward.  A forward nobody
    differentiates (validation) is under the trainer's plain ``forward``
    scope; the optimizer's and the clip's operations are under neither
    and read ``""``, as does an instruction without metadata.  Where XLA
    merges a recomputed product with the first forward's, the table says
    what the device runs."""
    parts = path.split("/")
    if "rematted_computation" in parts:
        return "remat"
    if any(p.startswith("transpose(") for p in parts):
        return "bwd"
    if "forward" in parts or any(p.startswith("jvp(") for p in parts):
        return "fwd"
    return ""


def _closing(text: str, start: int) -> int:
    """Index of the bracket that closes the ``(`` at ``text[start]``."""
    depth = 0
    for i in range(start, len(text)):
        depth += (text[i] == "(") - (text[i] == ")")
        if not depth:
            return i
    return len(text)


def _parse(line: str):
    """One instruction line as a :class:`_Row`, or None.  The result type
    may be a tuple with ``/*index=5*/`` comments; the operands are bare
    ``%names`` in what this XLA prints and carry their shapes in what an
    older one does (and in a trace event's name): either way the names."""
    m = _NAME_RE.match(line)
    if not m:
        return None
    rest = line[m.end():].lstrip()
    if rest.startswith("("):
        end = _closing(rest, 0) + 1
        rtype, rest = rest[:end], rest[end:].lstrip()
    else:
        rtype, _, rest = rest.partition(" ")
    opcode, paren, _ = rest.partition("(")
    if not paren:
        return None
    end = _closing(rest, len(opcode))
    args = rest[len(opcode) + 1:end]
    return _Row(
        m.group(1), rtype, opcode.strip(), args,
        _audit()._OPERAND_RE.findall(args), rest[end + 1:],
        line.lstrip().startswith("ROOT "),
    )


def _dims(type_text: str) -> List[int]:
    """The dimensions of an array type, ``bf16[16384,3072]{1,0:T(8,128)
    (2,1)}`` -> ``[16384, 3072]``: the layout and its tiling are passed
    over."""
    m = _audit()._SHAPE_RE.search(type_text)
    return [int(d) for d in m.group(2).split(",") if d] if m else []


def _hbm_bytes(type_text: str) -> int:
    """Bytes of a type's leaves that live in HBM (``fusion_audit``'s shape
    parser; a layout with ``S(1)`` is the core's own memory)."""
    return _audit()._shape_bytes(type_text, hbm_only=True)


def _leaves(type_text: str) -> List[str]:
    """The elements of a tuple type, each with its layout; an array type
    is its own one element."""
    if not type_text.startswith("("):
        return [type_text]
    out, depth, start = [], 0, 1
    for i, ch in enumerate(type_text):
        depth += (ch in "([{") - (ch in ")]}")
        if (ch == "," and depth == 1) or (ch == ")" and depth == 0):
            out.append(type_text[start:i].strip())
            start = i + 1
    return [leaf for leaf in out if leaf]


def _window(attrs: str, n: int) -> Dict[str, List]:
    """``window={size=64x1 stride=63x1 pad=0_0x0_0 lhs_dilate=64x1}`` as
    lists over the ``n`` spatial dimensions, defaults filled in."""
    out = {"size": [1] * n, "stride": [1] * n, "pad": [(0, 0)] * n,
           "lhs_dilate": [1] * n, "rhs_dilate": [1] * n}
    m = _WINDOW_RE.search(attrs)
    for field in (m.group(1).split() if m else ()):
        key, _, value = field.partition("=")
        if key == "pad":
            out[key] = [tuple(int(x) for x in v.split("_"))
                        for v in value.split("x")]
        elif key in out:
            out[key] = [int(v) for v in value.split("x")]
    return out


def _window_pairs(lhs, out, size, stride, pad_low, lhs_dilate, rhs_dilate):
    """Along one spatial dimension: how many (output position, window
    position) pairs land on an element of the input, not on padding and
    not in a dilation hole.  Only those are multiplied.  The TPU compiler
    writes the batch dimension of a batched product as a window of the
    batch's size over an input dilated by it (``size=64 stride=63
    lhs_dilate=64``): 64 pairs, one per entry, not 64 x 64.  Window
    position ``k`` and output position ``o`` meet the dilated input at
    ``p = k * rhs_dilate - pad_low + o * stride``; for each ``k`` the ``o``
    with ``0 <= p <= (lhs - 1) * lhs_dilate`` and ``lhs_dilate | p`` are
    counted in closed form (every ``step``-th of a range), so a large
    output costs no more than a small one."""
    top = (lhs - 1) * lhs_dilate
    if stride <= 0 or lhs_dilate <= 0 or top < 0:
        return 0
    g = math.gcd(stride, lhs_dilate)
    step = lhs_dilate // g
    pairs = 0
    for k in range(size):
        first = k * rhs_dilate - pad_low
        if first % g:
            continue  # no o puts p on an element: all fall in the holes
        low = max(0, -(first // stride))           # ceil(-first / stride)
        high = min(out - 1, (top - first) // stride)
        # the least o >= 0 with lhs_dilate | first + o * stride
        o = (-(first // g) * pow(stride // g, -1, step)) % step if step > 1 else 0
        if low > o:
            o += -((o - low) // step) * step       # the first such o >= low
        if o <= high:
            pairs += (high - o) // step + 1
    return pairs


class _Work:
    """What each instruction of one module has to do, from the text.

    ``flops``: 2 x the multiply-adds of every ``dot`` and ``convolution``
    in the instruction or in the computation a fusion ``calls=``, to any
    depth (a TPU product sits in a ``kind=kOutput`` fusion whose body calls
    further ``bitcast`` fusions).  Off the TPU a product stays a ``dot``:
    result elements x the extents of ``lhs_contracting_dims``.  On it the
    optimized HLO writes ``convolution(..), window={..}, dim_labels=
    bf_io->bf``: result batch x result features x the kernel's ``i`` extent
    x, per spatial dimension, the pairs of :func:`_window_pairs`.  The
    kernel's ``i`` extent is already the input features over
    ``feature_group_count``, and ``batch_group_count`` splits the result's
    features the same way, so neither count appears.  A Mosaic
    ``custom-call`` reads 0: the program cannot see inside it.

    ``bytes``: the operands' and the result's (a tuple's leaves summed), by
    the defining lines' types; an operand used twice counts once, and an
    array whose layout names another memory space than HBM (``S(1)``: it
    lives in the core's own memory) counts nothing where an operation made
    it there.  Where an asynchronous ``copy-start`` / ``slice-start`` brought
    it there from HBM (the compiler prefetches Adam's moments a quarter at a
    time while the fusion before runs), those bytes cross the memory's
    interface for the operation that waits for them: they count for the
    first operation in the computation's order that uses the array, and the
    ``-start`` states none of them (:meth:`_fetched_for`).  Where a
    fusion's body reads an operand only through ``dynamic-slice`` (a scan's
    body takes the whole stacked parameter and cuts its layer out), the
    slices count and not the buffer (a static ``slice`` and a ``gather``
    likewise read what they return); where it writes through
    ``dynamic-update-slice`` (a layer into the stacked output, in place) or
    ``scatter``, the update counts, and of the buffer it is written into
    only the rows a ``scatter`` adds to are read.  The first half of any
    other asynchronous pair (a copy within HBM) states the bytes it sets
    moving; its event lasts no time (the transfer runs behind other
    operations), so it is no operation to hold against a roofline."""

    def __init__(self, comps):
        #: computation -> its rows, in order
        self.rows = {
            c["name"]: [r for r in map(_parse, c["lines"]) if r]
            for c in comps
        }
        #: computation -> {instruction: row}
        self.named = {
            c: {r.name: r for r in rows} for c, rows in self.rows.items()
        }
        #: computation -> its root's name; {parameter number: its name}
        self.root = {
            c: next((r.name for r in rows if r.is_root), None)
            for c, rows in self.rows.items()
        }
        self.params = {
            c: {int(r.args): r.name for r in rows
                if r.opcode == "parameter" and r.args.strip().isdigit()}
            for c, rows in self.rows.items()
        }
        self._flops = {}
        self._users = {}
        self._fetched = {}

    # -- products --------------------------------------------------------

    def flops_of(self, comp: str, row: _Row) -> int:
        if row.opcode == "fusion":
            return self._comp_flops(self._called(row))
        if row.opcode not in ("dot", "convolution"):
            return 0
        out = _dims(row.rtype)
        lhs, rhs = (_dims(self._type(comp, o)) for o in row.operands[:2])
        if row.opcode == "dot":
            m = _CONTRACTING_RE.search(row.attrs)
            contracted = [lhs[int(d)] for d in m.group(1).split(",") if d]
            return 2 * math.prod(out) * math.prod(contracted)
        m = _DIM_LABELS_RE.search(row.attrs)
        if not m:
            return 0
        lhs_l, rhs_l, out_l = m.groups()
        macs = rhs[rhs_l.index("i")]
        window = _window(row.attrs, sum(ch.isdigit() for ch in out_l))
        for ch, extent in zip(out_l, out):
            if not ch.isdigit():
                macs *= extent
                continue
            d = int(ch)
            macs *= _window_pairs(
                lhs[lhs_l.index(ch)], extent, window["size"][d],
                window["stride"][d], window["pad"][d][0],
                window["lhs_dilate"][d], window["rhs_dilate"][d],
            )
        return 2 * macs

    def _comp_flops(self, comp: str) -> int:
        if comp not in self._flops:
            self._flops[comp] = sum(
                self.flops_of(comp, row) for row in self.rows.get(comp, ())
            )
        return self._flops[comp]

    # -- bytes -----------------------------------------------------------

    def bytes_of(self, comp: str, row: _Row) -> int:
        if row.opcode in _MOVES_NOTHING or row.opcode.endswith("-done"):
            return 0
        if row.opcode == "custom-call" and _BOOKKEEPING_RE.search(row.attrs):
            return 0
        fetched = self._fetched_for(comp)
        if row.name in fetched["starts"]:
            return 0
        if row.opcode.endswith("-start"):
            done = self._done_of(comp, row)
            if done is not None:
                # read what lands, and what lands in HBM is written there
                # (the result's tuple names the source a second time)
                moved = _audit()._shape_bytes(done.rtype)
                return _hbm_bytes(done.rtype) + sum(
                    min(moved, _hbm_bytes(self._type(comp, o)))
                    for o in dict.fromkeys(row.operands)
                )
        total = fetched["users"].get(row.name, 0)
        for operand in dict.fromkeys(row.operands):
            whole = _hbm_bytes(self._type(comp, operand))
            if whole:
                part = self._read_by(comp, row, operand)
                total += whole if part is None else min(whole, part)
        return total + self._written(comp, row, row.rtype)

    def _fetched_for(self, comp: str) -> Dict:
        """``{"users": {instruction: bytes}, "starts": {names}}``: what the
        asynchronous pairs of ``comp`` bring from HBM into the core's
        memory, each charged to the first instruction in the computation's
        order that uses the ``-done``'s array (through ``bitcast``,
        ``get-tuple-element`` and the compiler's ``ConcatBitcast`` of a
        transfer made in parts), and the ``-start`` halves so charged.  A
        pair whose array no instruction here uses (it leaves in a tuple)
        keeps its bytes on the ``-start``."""
        if comp in self._fetched:
            return self._fetched[comp]
        out = self._fetched[comp] = {
            "users": collections.Counter(), "starts": set()
        }
        users = self._users_of(comp)
        order = {r.name: i for i, r in enumerate(self.rows.get(comp, ()))}
        for start in self.rows.get(comp, ()):
            done = self._done_of(comp, start)
            if done is None:
                continue
            moved = _audit()._shape_bytes(done.rtype) - _hbm_bytes(done.rtype)
            if not moved or not any(
                _hbm_bytes(self._type(comp, o)) for o in start.operands
            ):
                continue
            first, seen, stack = None, set(), [done.name]
            while stack:
                for user in users.get(stack.pop(), ()):
                    if user.name in seen:
                        continue
                    seen.add(user.name)
                    if user.opcode in ("bitcast", "get-tuple-element") or (
                        user.opcode == "custom-call"
                        and _BOOKKEEPING_RE.search(user.attrs)
                    ):
                        stack.append(user.name)
                    elif user.opcode not in _MOVES_NOTHING and (
                        first is None or order[user.name] < order[first]
                    ):
                        first = user.name
            if first is not None:
                out["users"][first] += moved
                out["starts"].add(start.name)
        return out

    def _read_by(self, comp: str, row: _Row, value: str):
        """Bytes ``row`` reads of its operand ``value`` where it reads a
        slice of it (or updates it in place); None for all of it."""
        at = [i for i, o in enumerate(row.operands) if o == value]
        if row.opcode in _READS_ITS_RESULT and at == [0]:
            return _audit()._shape_bytes(row.rtype)
        if row.opcode in _UPDATE_AT and at == [0]:
            # written in place: a dynamic-update-slice reads none of it, a
            # scatter the rows it adds to
            return self._update_bytes(comp, row) * (row.opcode == "scatter")
        if row.opcode == "bitcast":
            return self._read(comp, row.name)
        if row.opcode == "fusion":
            called = self._called(row)
            params = self.params.get(called, {})
            parts = [self._read(called, params.get(i)) for i in at]
            return None if None in parts else sum(parts)
        return None

    def _read(self, comp: str, value):
        """Bytes the body ``comp`` reads of its value ``value``, where
        every use of it is a slice; None for all of it."""
        if value is None or value == self.root.get(comp):
            return None
        total = 0
        for row in self._users_of(comp).get(value, ()):
            part = self._read_by(comp, row, value)
            if part is None:
                return None
            total += part
        return total

    def _written(self, comp: str, row: _Row, rtype: str) -> int:
        """Bytes written to HBM for ``row``'s result, which the instruction
        the device executes declares as ``rtype`` (a fusion's own line
        says where each leaf of its body's root lives)."""
        named = self.named.get(comp, {})
        if row.opcode == "tuple":
            return sum(
                self._written(comp, named[o], leaf)
                for o, leaf in zip(row.operands, _leaves(rtype))
                if o in named
            )
        whole = _hbm_bytes(rtype)
        if row.opcode == "bitcast" and row.operands[0:1] and \
                row.operands[0] in named:
            return self._written(comp, named[row.operands[0]], rtype)
        if row.opcode in _UPDATE_AT:
            return min(whole, self._update_bytes(comp, row))
        if row.opcode == "fusion":
            called = self._called(row)
            root = self.named.get(called, {}).get(self.root.get(called))
            if root is not None:
                return self._written(called, root, rtype)
        return whole

    def _done_of(self, comp: str, row: _Row):
        """The ``-done`` half of the asynchronous pair ``row`` starts."""
        if not row.opcode.endswith("-start"):
            return None
        return next((u for u in self._users_of(comp).get(row.name, ())
                     if u.opcode.endswith("-done")), None)

    def _update_bytes(self, comp: str, row: _Row) -> int:
        """Bytes of what an in-place write puts into its buffer."""
        return _audit()._shape_bytes(
            self._type(comp, row.operands[_UPDATE_AT[row.opcode]])
        )

    # -- one computation's names -------------------------------------------

    @staticmethod
    def _called(row: _Row):
        m = _FUSION_CALLS_RE.search(row.attrs)
        return m.group(1) if m else None

    def _type(self, comp, name) -> str:
        row = self.named.get(comp, {}).get(name)
        return row.rtype if row else ""

    def _users_of(self, comp):
        if comp not in self._users:
            users = self._users[comp] = collections.defaultdict(list)
            for row in self.rows.get(comp, ()):
                for operand in dict.fromkeys(row.operands):
                    users[operand].append(row)
        return self._users[comp]


def tables() -> List[Dict]:
    """The scope tables of the programs kept in the running (or the last)
    capture: each text is parsed at the first call after it was kept, and
    whoever asks again (a traced benchmark run asks once a pass) gets the
    same tables."""
    while len(_tables) < len(_texts):
        _tables.append(scope_table(_texts[len(_tables)]))
    return list(_tables)


def write_tables(out_dir: str) -> List[str]:
    """``<out_dir>/hlo_scopes_<module>.json`` per kept program (a second
    program of the same module name — another batch geometry — gets
    ``hlo_scopes_<module>.<n>.json``); returns the paths written."""
    paths, seen = [], {}
    for table in tables():
        n = seen[table["module"]] = seen.get(table["module"], -1) + 1
        name = f"hlo_scopes_{table['module']}" + (f".{n}" if n else "")
        path = os.path.join(out_dir, name + ".json")
        with open(path, "w") as f:
            json.dump(table, f)
        paths.append(path)
    return paths


def reset() -> None:
    """Forget every kept program (tests)."""
    _texts.clear()
    _tables.clear()
    _noted.clear()
