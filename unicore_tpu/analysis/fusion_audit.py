"""Operation-fusion audit of compiled XLA programs (``--fusion-audit``).

Per "LLM Inference Acceleration via Efficient Operation Fusion" (PAPERS.md,
arXiv 2502.17728), the wins the device-side kernel suite claims — fewer
kernels, elementwise chains folded into their producers — are PROGRAM
STRUCTURE properties, checkable without a device: compile the train step,
walk the optimized HLO, and report

- **kernel count**: schedulable instructions (everything an executor
  launches — parameters/constants/tuple plumbing excluded),
- **fusion count** (+ per-``kind`` breakdown) and **bytes touched** per
  fused region (operand + result bytes — the HBM traffic one fused launch
  replaces N unfused launches of),
- the **top-N unfused elementwise chains**: connected groups of elementwise
  ops still sitting at computation level, i.e. fusion opportunities XLA
  declined — the first place to look when a "fused" change didn't shrink
  the program,
- a **dequant section** (``dequant``): materialized dequantization
  intermediates in a quantized program — computation-level ``convert``
  instructions from a quantized storage dtype (s8/s32 accumulator/f8) up
  to a float compute dtype, and the worse form, such a convert whose
  result feeds a computation-level ``multiply`` (the classic unfused
  dequant chain: write the fp32 tensor to HBM, read it back to scale it).
  The quantized serving path's contract (arXiv 2502.17728; docs/serving.md
  "Quantized inference") is ``unfused_chains == 0``: every dequant
  multiply lives INSIDE the fusion that consumes it — regression-checked
  device-free by tests/test_quant.py,
- a **comm section** (``comm``): every collective op in the program
  (all-reduce / reduce-scatter / all-gather / all-to-all /
  collective-permute, sync or async-start form) with its operand and
  result bytes and its ``replica_groups``, rolled up by TOPOLOGY TIER
  when the caller supplies ``devices_per_pod`` (the ParallelPlan's pod
  extent): a group whose members all share ``id // devices_per_pod``
  stays inside one pod (``ici``); a group spanning pods crosses the slow
  tier (``dcn``).  This is the device-free proof surface for the
  two-level gradient reduction (parallel/hierarchy.py): with a 2-pod
  plan the ``dcn`` tier's operand bytes must be at most ``1/pod_size``
  of the flat-buffer bytes (tests/test_hierarchy.py regression-checks
  it against the flat all-reduce program),
- a **peak-memory section** (``memory``): the compiler's own per-device
  allocation stats — argument / output / temp / aliased bytes plus
  ``peak_bytes`` (argument + output + temp − alias, the static upper bound
  XLA budgets for one execution).  This is the device-free number the
  memory-headroom tier regression-checks: ZeRO-2/3 + AdamA accumulation
  must shrink ``temp_bytes``/``peak_bytes`` of the grad-accum scan program
  vs the zero1+buffer baseline (tests/test_memory_headroom.py,
  docs/performance.md "Memory headroom").

The parser is text-based (``compiled.as_text()``) and intentionally
tolerant: unknown shapes/opcodes degrade to zero-byte entries, never a
crash — an audit must not take down a training run.  Numbers are exact for
the common HLO shapes and are meant for BEFORE/AFTER comparison of the same
model, not cross-backend absolutes.

``trainer.fusion_audit()`` journals the report through the telemetry plane
(kind ``fusion-audit``) and logs it as one BENCH-comparable JSON block.
"""

import json
import re
from typing import Dict, List, Optional

import numpy as np

#: dtype prefix -> bytes per element (unknown prefixes parse as 0)
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

#: opcodes that never launch device work on their own
_NON_KERNEL_OPS = frozenset({
    "parameter", "constant", "tuple", "get-tuple-element", "iota",
    "after-all", "partition-id", "replica-id",
})

#: elementwise HLO opcodes (the fusible-by-definition set)
_ELEMENTWISE_OPS = frozenset({
    "abs", "add", "and", "atan2", "cbrt", "ceil", "clamp", "compare",
    "convert", "cosine", "divide", "exponential", "exponential-minus-one",
    "floor", "is-finite", "log", "log-plus-one", "logistic", "maximum",
    "minimum", "multiply", "negate", "not", "or", "popcnt", "power",
    "remainder", "round-nearest-afz", "round-nearest-even", "rsqrt",
    "select", "shift-left", "shift-right-arithmetic", "shift-right-logical",
    "sign", "sine", "sqrt", "subtract", "tan", "tanh", "xor",
})

#: collective opcodes (async ``-start`` halves normalize to the sync name;
#: the ``-done`` halves carry no payload of their own)
_COLLECTIVE_OPS = frozenset({
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
})
_COLLECTIVE_START_SUFFIX = "-start"

_SHAPE_RE = re.compile(r"\b([a-z]+\d*(?:e\d+m\d+(?:fn)?)?)\[([\d,]*)\]")
#: a layout's memory space, ``S(<n>)``; none written is HBM
_MEMORY_SPACE_RE = re.compile(r"S\((\d+)\)")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(\([^=]*?\)|\S+)\s+([\w\-]+)\("
)
# the parameter list may nest brackets: a loop body takes one tuple
_COMP_RE = re.compile(r"^\s*(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->.*{\s*$")
_CALLED_RE = re.compile(r"(?:calls|to_apply)=%([\w.\-]+)")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_REPLICA_GROUPS_RE = re.compile(r"replica_groups=\{((?:\{[\d,]*\},?)+)\}")
_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{[\d,]*\},?)+)\}")
_GROUP_RE = re.compile(r"\{([\d,]*)\}")
#: the iota encoding: ``replica_groups=[G,S]<=[d0,d1,..]`` with an optional
#: ``T(p0,p1,..)`` — iota(prod(d)) reshaped to ``d``, transposed by ``p``,
#: reshaped to G groups of S devices
_IOTA_GROUPS_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?"
)


def _iota_groups(m) -> List[List[int]]:
    n_groups, group_size = int(m.group(1)), int(m.group(2))
    dims = [int(t) for t in m.group(3).split(",")]
    ids = np.arange(int(np.prod(dims))).reshape(dims)
    if m.group(4):
        ids = ids.transpose([int(t) for t in m.group(4).split(",")])
    return ids.reshape(n_groups, group_size).tolist()


def _parse_groups(line: str) -> List[List[int]]:
    """Device-id groups out of ``replica_groups`` — the explicit
    ``{{..},..}`` list or the iota ``[g,s]<=[..]T(..)`` encoding the
    installed XLA prints for regular groups — or ``source_target_pairs``
    for collective-permute.  Empty when the line carries none of them, in
    which case the tier stays unknown."""
    m = _IOTA_GROUPS_RE.search(line)
    if m:
        return _iota_groups(m)
    m = _REPLICA_GROUPS_RE.search(line) or _PAIRS_RE.search(line)
    if not m:
        return []
    groups = []
    for body in _GROUP_RE.findall(m.group(1)):
        ids = [int(t) for t in body.split(",") if t]
        if ids:
            groups.append(ids)
    return groups


def _comm_tier(groups: List[List[int]], devices_per_pod: Optional[int]):
    """'ici' when every group stays inside one pod, 'dcn' when any group
    spans pods, None (unknown) without classification info."""
    if not groups or not devices_per_pod or devices_per_pod <= 0:
        return None
    for ids in groups:
        pods = {i // devices_per_pod for i in ids}
        if len(pods) > 1:
            return "dcn"
    return "ici"


def _shape_bytes(text: str, hbm_only: bool = False) -> int:
    """Total bytes of every ``dtype[dims]`` shape literal in ``text``;
    with ``hbm_only``, of those whose layout names no other memory space
    (``{1,0:T(8,128)S(1)}`` is an array the TPU compiler placed in the
    core's own memory: using it moves nothing to or from HBM)."""
    total = 0
    for m in _SHAPE_RE.finditer(text):
        dtype, dims = m.groups()
        if hbm_only and text.startswith("{", m.end()):
            space = _MEMORY_SPACE_RE.search(
                text, m.end(), text.find("}", m.end()) + 1
            )
            if space and space.group(1) != "0":
                continue
        per = _DTYPE_BYTES.get(dtype, 0)
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += per * n
    return total


def _split_computations(hlo: str) -> List[dict]:
    """[{name, entry, lines}] per computation in the module text."""
    comps, cur = [], None
    for line in hlo.splitlines():
        m = _COMP_RE.match(line)
        if m and cur is None:
            cur = {"name": m.group(2), "entry": bool(m.group(1)), "lines": []}
            continue
        if cur is not None:
            if line.strip() == "}":
                comps.append(cur)
                cur = None
            else:
                cur["lines"].append(line)
    return comps


def audit_hlo(
    hlo: str, top_n: int = 5, devices_per_pod: Optional[int] = None
) -> Dict:
    """Walk one optimized HLO module; return the audit report dict.
    ``devices_per_pod`` (from the ParallelPlan) lets the ``comm``
    section classify each collective's replica groups by topology
    tier."""
    comps = _split_computations(hlo)
    # computations referenced via calls=/to_apply= are bodies of their
    # caller (fusion regions, reduce combiners): their instructions are
    # already accounted for at the call site
    called = set()
    for c in comps:
        for line in c["lines"]:
            called.update(_CALLED_RE.findall(line))

    kernels = 0
    instructions = 0
    fusions = []
    fusion_kinds: Dict[str, int] = {}
    chains: List[Dict] = []
    dequant_converts: List[str] = []
    dequant_chains: List[str] = []
    collectives: List[Dict] = []

    for comp in comps:
        if comp["name"] in called:
            continue
        instrs = []  # (name, opcode, line)
        result_shapes: Dict[str, str] = {}  # name -> result shape text
        for line in comp["lines"]:
            m = _INSTR_RE.match(line)
            if not m:
                continue
            name, shape, opcode = m.groups()
            result_shapes[name] = shape
            instrs.append((name, opcode, line))
            instructions += 1
            if opcode not in _NON_KERNEL_OPS:
                kernels += 1
            if opcode == "fusion":
                km = re.search(r"kind=(\w+)", line)
                kind = km.group(1) if km else "unknown"
                fusion_kinds[kind] = fusion_kinds.get(kind, 0) + 1
                fusions.append({
                    "name": name,
                    "kind": kind,
                    "bytes": _shape_bytes(line.split(", kind=")[0]),
                })
            base_op = (
                opcode[: -len(_COLLECTIVE_START_SUFFIX)]
                if opcode.endswith(_COLLECTIVE_START_SUFFIX)
                else opcode
            )
            if base_op in _COLLECTIVE_OPS:
                collectives.append(
                    _collective_entry(
                        name, base_op, line, devices_per_pod, result_shapes
                    )
                )
        chains.extend(_elementwise_chains(instrs))
        cv, ch = _dequant_chains(instrs)
        dequant_converts.extend(cv)
        dequant_chains.extend(ch)

    fusions.sort(key=lambda f: -f["bytes"])
    chains.sort(key=lambda c: -c["length"])
    comm = _comm_rollup(collectives, top_n)
    return {
        "comm": comm,
        "instructions": instructions,
        "kernels": kernels,
        "fusions": len(fusions),
        "fusion_kinds": fusion_kinds,
        "fused_bytes_total": sum(f["bytes"] for f in fusions),
        "top_fusions": fusions[:top_n],
        "unfused_elementwise": sum(c["length"] for c in chains),
        "top_unfused_chains": chains[:top_n],
        "dequant": {
            "materialized_converts": len(dequant_converts),
            "unfused_chains": len(dequant_chains),
            "examples": sorted(dequant_chains)[:top_n],
        },
    }


def _collective_entry(
    name: str, op: str, line: str, devices_per_pod: Optional[int],
    result_shapes: Dict[str, str],
) -> Dict:
    """One comm-section row: operand/result bytes + tier for one
    collective instruction line.  ``result_shapes`` maps the
    computation's earlier instructions to their result shapes: the
    installed XLA prints operands as bare ``%name`` references, so their
    bytes come from the defining instruction."""
    m = _INSTR_RE.match(line)
    result_bytes = _shape_bytes(m.group(2)) if m else 0
    # operand shapes sit between the OPCODE's '(' — which is exactly
    # where _INSTR_RE's match ends — and the next ')'.  Searching from
    # the line's first '(' would land on the result shape for
    # tuple-result collectives (the async '-start' forms emit
    # '(f32[..], f32[..]) all-reduce-start(...)') and misread the tuple
    # contents as operands.  Array operand shapes use square/curly
    # brackets only, so the first ')' past the opcode closes the list.
    operand_bytes = 0
    if m:
        close = line.find(")", m.end())
        if close > m.end():
            operands = line[m.end():close]
            operand_bytes = _shape_bytes(operands) or sum(
                _shape_bytes(result_shapes.get(ref, ""))
                for ref in _OPERAND_RE.findall(operands)
            )
    groups = _parse_groups(line)
    tier = _comm_tier(groups, devices_per_pod)
    return {
        "name": name,
        "op": op,
        "operand_bytes": operand_bytes,
        "result_bytes": result_bytes,
        "groups": len(groups),
        "group_size": max((len(g) for g in groups), default=0),
        "tier": tier or "unknown",
    }


def _comm_rollup(collectives: List[Dict], top_n: int) -> Dict:
    """The ``comm`` report section: per-op counts, per-tier byte
    rollups, and the top collectives by operand bytes."""
    by_op: Dict[str, int] = {}
    tiers = {
        t: {"ops": 0, "operand_bytes": 0, "result_bytes": 0}
        for t in ("ici", "dcn", "unknown")
    }
    for c in collectives:
        by_op[c["op"]] = by_op.get(c["op"], 0) + 1
        t = tiers[c["tier"]]
        t["ops"] += 1
        t["operand_bytes"] += c["operand_bytes"]
        t["result_bytes"] += c["result_bytes"]
    top = sorted(collectives, key=lambda c: -c["operand_bytes"])[:top_n]
    return {
        "collectives": len(collectives),
        "by_op": by_op,
        "operand_bytes_total": sum(c["operand_bytes"] for c in collectives),
        "tiers": {t: v for t, v in tiers.items() if v["ops"]},
        "top": top,
    }


#: quantized storage/accumulator dtypes whose upcast IS a dequantization
_QUANT_SRC_DTYPES = frozenset({"s8", "u8", "s32", "f8e4m3fn", "f8e5m2"})
_FLOAT_DST_DTYPES = frozenset({"f32", "bf16", "f16"})


def _result_dtype(shape_text: str) -> Optional[str]:
    m = _SHAPE_RE.search(shape_text)
    return m.group(1) if m else None


def _dequant_chains(instrs) -> tuple:
    """Materialized dequant intermediates among computation-level
    instructions: ``converts`` — unfused quantized->float converts
    (each one writes a full float tensor to HBM); ``chains`` — the worse
    form, a convert whose result then feeds a computation-level
    ``multiply`` (the textbook dequantize-then-scale pair the quantized
    kernels exist to eliminate).  Fused programs keep both inside fusion
    bodies, which live in called computations and never reach here."""
    by_name = {}
    for name, opcode, line in instrs:
        m = _INSTR_RE.match(line)
        by_name[name] = (opcode, m.group(2) if m else "", line)
    converts = []
    for name, opcode, line in instrs:
        if opcode != "convert":
            continue
        dst = _result_dtype(by_name[name][1])
        if dst not in _FLOAT_DST_DTYPES:
            continue
        paren = line[line.index("(") + 1:]
        src_dtypes = [
            _result_dtype(by_name[ref][1])
            for ref in _OPERAND_RE.findall(paren)
            if ref in by_name
        ]
        if any(d in _QUANT_SRC_DTYPES for d in src_dtypes):
            converts.append(name)
    chains = []
    if converts:
        conv_set = set(converts)
        for name, opcode, line in instrs:
            if opcode != "multiply":
                continue
            paren = line[line.index("(") + 1:]
            hits = [r for r in _OPERAND_RE.findall(paren) if r in conv_set]
            chains.extend(f"{h}->{name}" for h in hits)
    return converts, chains


def _elementwise_chains(instrs) -> List[Dict]:
    """Connected groups of computation-level elementwise instructions —
    each one is a fusion XLA declined (or was legally barred from)."""
    elem = {name: (opcode, line) for name, opcode, line in instrs
            if opcode in _ELEMENTWISE_OPS}
    if not elem:
        return []
    # undirected adjacency over def-use edges between elementwise ops
    adj: Dict[str, set] = {n: set() for n in elem}
    for name, (_op, line) in elem.items():
        # operands: names inside the outermost call parens
        paren = line[line.index("(") + 1:]
        for ref in _OPERAND_RE.findall(paren):
            if ref in elem and ref != name:
                adj[name].add(ref)
                adj[ref].add(name)
    seen, out = set(), []
    for start in elem:
        if start in seen:
            continue
        stack, comp = [start], []
        seen.add(start)
        while stack:
            n = stack.pop()
            comp.append(n)
            for nb in adj[n]:
                if nb not in seen:
                    seen.add(nb)
                    stack.append(nb)
        out.append({
            "length": len(comp),
            "ops": sorted(elem[n][0] for n in comp),
        })
    return out


def audit_compiled(
    compiled, top_n: int = 5, devices_per_pod: Optional[int] = None
) -> Optional[Dict]:
    """Audit a ``jax`` compiled executable (``lowered.compile()`` result).
    Adds the compiler's own memory analysis when available.  Returns None
    when the executable exposes no HLO text (audits must never raise)."""
    try:
        hlo = compiled.as_text()
    except Exception:
        return None
    if not hlo:
        return None
    report = audit_hlo(hlo, top_n=top_n, devices_per_pod=devices_per_pod)
    try:
        mem = compiled.memory_analysis()
        arg_b = int(mem.argument_size_in_bytes)
        out_b = int(mem.output_size_in_bytes)
        tmp_b = int(mem.temp_size_in_bytes)
        alias_b = int(getattr(mem, "alias_size_in_bytes", 0) or 0)
        report["memory"] = {
            "argument_bytes": arg_b,
            "output_bytes": out_b,
            "temp_bytes": tmp_b,
            "alias_bytes": alias_b,
            "generated_code_bytes": int(
                getattr(mem, "generated_code_size_in_bytes", 0) or 0
            ),
            # the static per-device upper bound XLA budgets for one
            # execution (aliased output bytes overlap arguments, so they
            # subtract out)
            "peak_bytes": arg_b + out_b + tmp_b - alias_b,
        }
    except Exception:
        pass
    return report


def format_report(report: Dict) -> str:
    """One grep-able JSON block (the BENCH-comparable form)."""
    return "FUSION-AUDIT " + json.dumps(report, sort_keys=True)
