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

import json
import logging
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


def scope_table(hlo: str) -> Dict:
    """``{"module": <HloModule name>, "instructions": {name: op_name}}``
    for every instruction the device can execute as an operation of its
    own: those of the entry computation and of the bodies it runs
    (``while``, ``conditional``, ``call``), a fusion by its own metadata;
    the insides of fusions and reducers are their caller's.  An
    instruction without metadata maps to ``""``."""
    # the HLO walker of --fusion-audit; imported here because the
    # analysis package registers every lint rule as it is imported
    from unicore_tpu.analysis.fusion_audit import (
        _CALLED_RE,
        _split_computations,
    )

    comps = _split_computations(hlo)
    inner = set()
    for comp in comps:
        for line in comp["lines"]:
            inner.update(_CALLED_RE.findall(line))
    instructions = {}
    for comp in comps:
        if comp["name"] in inner:
            continue
        for line in comp["lines"]:
            m = _NAME_RE.match(line)
            if m:
                scope = _OP_NAME_RE.search(line)
                instructions[m.group(1)] = scope.group(1) if scope else ""
    module = _MODULE_RE.search(hlo)
    return {
        "module": module.group(1) if module else "",
        "instructions": instructions,
    }


def tables() -> List[Dict]:
    """The scope tables of the programs kept in the running (or the last)
    capture, parsed now."""
    return [scope_table(text) for text in _texts]


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
    _noted.clear()
