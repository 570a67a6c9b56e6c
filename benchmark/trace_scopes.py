"""A profiler trace in the program's own names: device time by module
group and by named kernel, who owns the ``copy`` time, idle gaps by the
innermost host span, and the host phases of an update.

``reduce.py`` answers "how busy was the device" from compiler-made labels.
This file joins the same trace to what the program leaves in it:

* ``unicore:<span>`` annotations (``unicore_tpu/telemetry/spans.py``):
  ``train_step`` (stat ``update``) with ``prepare`` / ``plan_exchange`` /
  ``h2d`` / ``launch`` nested in it, ``data_next`` (stat ``depth``),
  ``data_produce``, ``data_wait``, ``recompiled``;
* kernel names: a Mosaic custom call's instruction is named by the
  kernel's ``name=`` (``%fullrow_attn_fwd.3``, ``%flash_bwd_dq.7``);
* the scope table of each traced program
  (``unicore_tpu/telemetry/hlo_scopes.py``): instruction name ->
  ``op_name`` path, e.g. ``%fusion.2067 ->
  jit(train_step)/optimizer/convert_element_type``.  Taken from the
  program's own stash when the trainer ran in this process, else from the
  ``hlo_scopes_*.json`` files beside the trace (``--profile-steps`` writes
  them).

A program that leaves none of these (the commit before they existed)
gives a reduction whose parts are empty; every reader then returns None.

    python3 -m benchmark.trace_scopes <file.xplane.pb>
"""

import bisect
import collections
import glob
import json
import os
import re
import statistics
import sys

from benchmark import harness, reduce

PROGRAM = "unicore:"
HARNESS = "bench:"
MODULES_LINE = "XLA Modules"

#: the trainer's phase scopes that make up the optimizer's share
OPTIMIZER_SCOPES = ("optimizer", "clip-grads", "multiply-grads")
#: device op time is partitioned into these, in this order of precedence
GROUPS = ("optimizer", "attention", "ffn", "lm_head_loss", "rest",
          "unattributed")
#: the attention kernels by direction, whichever family the router took
#: (``ops/flash_attention.py`` tiles the row, ``ops/attention_fullrow.py``
#: holds it whole; BERT-base at 512 takes the full-row pair)
ATTENTION_FWD = ("flash_fwd", "fullrow_attn_fwd")
ATTENTION_BWD = ("flash_bwd_dq", "flash_bwd_dkv", "flash_bwd_dbias",
                 "fullrow_attn_bwd")
_LAYER = re.compile(r"layers_\d+")
_SHAPE = re.compile(r"\b[a-z]+\d*\[([\d,]*)\]")


def group_of(path):
    """The module group that owns a device operation, from its ``op_name``
    path (``jit(train_step)/transpose(jvp(forward))/Model/sentence_encoder/
    layers_3/self_attn/out_proj/dot_general``).  ``rest`` is what has a
    path but none of the named owners: embeddings, norms, residuals."""
    if not path:
        return "unattributed"
    parts = path.split("/")
    if any(p in OPTIMIZER_SCOPES for p in parts):
        return "optimizer"
    if "self_attn" in parts:
        return "attention"
    if "fc1" in parts or "fc2" in parts:
        return "ffn"
    if "lm_head" in parts or "loss" in parts:
        return "lm_head_loss"
    return "rest"


def scope_label(path):
    """A path as a reader wants it: without the jit wrapper and the
    primitive's name, the layers of a stack as one."""
    parts = path.split("/")[1:-1]
    return _LAYER.sub("layers_*", "/".join(parts)) or "(top level)"


# -- reading the trace --------------------------------------------------------

def find_trace(root=harness.ROOT):
    """The trace ``harness.Tracer`` wrote last under ``<root>/.bench_trace``."""
    paths = glob.glob(
        os.path.join(root, ".bench_trace", "**", "*.xplane.pb"), recursive=True
    )
    return max(paths, key=os.path.getmtime) if paths else None


def scope_tables(trace_path):
    """``(tables, source)``: the program's stash if this process ran the
    program under the capture (``program``), else the ``hlo_scopes_*.json``
    files in the trace's directory or one of its three parents (``files``:
    ``--profile-steps`` writes them at the capture's root), else nothing."""
    try:
        from unicore_tpu.telemetry import hlo_scopes
    except ImportError:
        hlo_scopes = None
    if hlo_scopes is not None:
        tables = hlo_scopes.tables()
        if tables:
            return tables, "program"
    here = os.path.dirname(os.path.abspath(trace_path))
    for _ in range(4):
        found = sorted(glob.glob(os.path.join(here, "hlo_scopes_*.json")))
        if found:
            return [harness.load_json(p) for p in found], "files"
        here = os.path.dirname(here)
    return [], None


def module_events(profile):
    """{device plane: [(start_ns, end_ns, module name)]}: one event per run
    of a program, named ``<module>(<program id>)``."""
    out = {}
    for plane in profile.planes:
        if not plane.name.startswith(reduce.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name == MODULES_LINE:
                out[plane.name] = sorted(
                    (float(e.start_ns), float(e.start_ns + e.duration_ns),
                     e.name.partition("(")[0])
                    for e in line.events
                )
    return out


def host_spans(profile):
    """{thread: [(start_ns, end_ns, name, stats)]} of the program's and the
    harness's annotations; a thread is a (plane, line) of the trace."""
    threads = {}
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for i, line in enumerate(plane.lines):
            spans = [
                (float(e.start_ns), float(e.start_ns + e.duration_ns),
                 e.name, {k: v for k, v in e.stats})
                for e in line.events
                if e.name.startswith((PROGRAM, HARNESS))
            ]
            if spans:
                threads[(plane.name, i, line.name)] = sorted(
                    spans, key=lambda s: (s[0], -s[1])
                )
    return threads


def innermost(spans):
    """Disjoint ``(start, end, name)`` pieces of one thread's nested spans,
    each piece named by the innermost span open over it."""
    pieces, stack = [], []  # stack of [end, name]

    def close_until(t):
        # pop spans that ended before t, giving the parent what is left
        nonlocal at
        while stack and stack[-1][0] <= t:
            end, name = stack.pop()
            if end > at:
                pieces.append((at, end, name))
                at = end

    at = 0.0
    for start, end, name, _stats in spans:
        close_until(start)
        if stack and start > at:
            pieces.append((at, start, stack[-1][1]))
        at = max(at, start)
        stack.append([end, name])
    close_until(float("inf"))
    return pieces


def training_thread(threads):
    """The thread that runs the updates: the one with most
    ``unicore:train_step`` spans, else with most harness spans."""
    def score(key):
        names = collections.Counter(s[2] for s in threads[key])
        return (names[PROGRAM + "train_step"],
                sum(n for k, n in names.items() if k.startswith(HARNESS)))
    return max(threads, key=score) if threads else None


# -- the reduction ------------------------------------------------------------

def instruction_name(text):
    return text.partition(" = ")[0].strip().lstrip("%")


def operand_shapes(text, opcode):
    """The dimensions of each operand of the operation an event names,
    from its own HLO line: ``custom-call(s32[1] %seed, bf16[32,12,512,64]
    %q, ...)`` -> ``((1,), (32, 12, 512, 64), ...)``."""
    start = text.find(f" {opcode}(") + len(opcode) + 2
    depth, end = 1, start
    while end < len(text) and depth:
        depth += (text[end] == "(") - (text[end] == ")")
        end += 1
    operands = text[start:end]
    return tuple(
        tuple(int(d) for d in dims.split(",") if d)
        for dims in _SHAPE.findall(operands)
    )


def table_for(module, name, tables):
    """The scope table that holds instruction ``name`` of a run of
    ``module`` (several step programs share one module name: each batch
    geometry of a cell is a ``jit_train_step``)."""
    fallback = None
    for t in tables:
        if name in t["instructions"]:
            if t["module"] == module:
                return t
            fallback = fallback or t
    return fallback


def reduce_events(per_device, modules, threads, tables):
    """The reduction proper, on plain lists (so a test can hand-build one):
    ``per_device`` as ``reduce.device_events`` gives it, ``modules`` as
    :func:`module_events`, ``threads`` as :func:`host_spans`, ``tables`` as
    ``hlo_scopes.tables()``."""
    n = max(len(per_device), 1)
    groups = collections.Counter()
    scopes = collections.Counter()
    copies = collections.Counter()
    kernels = collections.Counter()
    kernel_calls = collections.defaultdict(collections.Counter)
    idle = collections.Counter()
    total = 0.0
    main = training_thread(threads)
    pieces = innermost(threads[main]) if main else []
    piece_starts = [p[0] for p in pieces]
    runs = 0
    for device, events in per_device.items():
        runs_here = modules.get(device, [])
        runs += len(runs_here)
        run_starts = [r[0] for r in runs_here]
        leaf = []
        for start, end, text in events:
            # a Mosaic kernel's label is its ``name=`` (``%flash_fwd.12``)
            label, opcode, mosaic = reduce.parse_op(text)
            if opcode in reduce.WRAPPERS:
                continue
            leaf.append((start, end))
            dur = (end - start) / 1e9
            total += dur
            name = instruction_name(text)
            i = bisect.bisect_right(run_starts, start) - 1
            module = runs_here[i][2] if i >= 0 else ""
            table = table_for(module, name, tables)
            path = table["instructions"][name] if table else ""
            groups[group_of(path)] += dur
            if path:
                scopes[scope_label(path)] += dur
            if mosaic:
                kernels[label] += dur
                kernel_calls[label][operand_shapes(text, opcode)] += 1
            if opcode == "copy":
                copies[scope_label(path) if path else "(no scope)"] += dur
        merged = reduce.union(leaf)
        for (_, gap_start), (gap_end, _) in zip(merged, merged[1:]):
            left = gap_end - gap_start
            j = max(bisect.bisect_right(piece_starts, gap_start) - 1, 0)
            while j < len(pieces) and pieces[j][0] < gap_end:
                a, b, name = pieces[j]
                cover = min(b, gap_end) - max(a, gap_start)
                if cover > 0:
                    idle[name] += cover / 1e9
                    left -= cover
                j += 1
            idle["(no span)"] += max(left, 0.0) / 1e9
    return {
        "device_op_s": total / n,
        "program_runs": runs // n,
        "groups_s": {g: groups[g] / n for g in GROUPS} if tables else {},
        "top_scopes": [[k, v / n] for k, v in scopes.most_common(8)],
        "copy_owners": [[k, v / n] for k, v in copies.most_common(5)],
        "kernels_s": {k: v / n for k, v in kernels.items()},
        # per kernel, its calls by the shapes of their operands
        "kernel_calls": {
            k: [[list(map(list, shapes)), c // n] for shapes, c in by.items()]
            for k, by in kernel_calls.items()
        },
        "idle_s": {k: v / n for k, v in idle.most_common()},
        "host": host_phases(threads, main),
        "mapped_pairs": mapped_pairs(threads),
    }


def mapped_pairs(threads):
    """The (query, key) pairs a head of one mapped attention call scores,
    as the program states them: the ``keys_computed`` stat of whichever of
    its annotations holds one (``unicore:eva_keys``; a later map states the
    same stat under a mark of its own), the mean over the traced updates'
    marks (a cell has one step shape, so they agree).  None where no mark
    holds it: ``flops/kernels.py`` then counts no mapped call."""
    stated = [
        float(s[3]["keys_computed"]) for spans in threads.values()
        for s in spans
        if s[2].startswith(PROGRAM) and "keys_computed" in s[3]
    ]
    return statistics.mean(stated) if stated else None


def host_phases(threads, main):
    """Per-update medians of the program's host spans: ``train_step`` and,
    inside each, ``prepare`` / ``h2d`` / ``launch`` on the training thread;
    the data buffer's depth as ``data_next`` found it; the time one batch
    takes to build (``data_produce``, on whichever thread built it)."""
    out = {}
    if main is not None:
        spans = threads[main]
        steps = [s for s in spans if s[2] == PROGRAM + "train_step"]
        if steps:
            out["updates"] = len(steps)
            out["train_step_ms"] = statistics.median(
                (s[1] - s[0]) / 1e6 for s in steps
            )
            starts = [s[0] for s in spans]
            names = {s[2] for s in spans}
            for phase in ("prepare", "plan_exchange", "h2d", "launch"):
                if PROGRAM + phase not in names:
                    continue
                per_update = []
                for a, b, _n, _st in steps:
                    j = bisect.bisect_left(starts, a)
                    inside = 0.0
                    while j < len(spans) and spans[j][0] < b:
                        if spans[j][2] == PROGRAM + phase:
                            inside += spans[j][1] - spans[j][0]
                        j += 1
                    per_update.append(inside / 1e6)
                out[phase + "_ms"] = statistics.median(per_update)
    every = [s for spans in threads.values() for s in spans]
    depths = [s[3]["depth"] for s in every
              if s[2] == PROGRAM + "data_next" and "depth" in s[3]]
    if depths:
        out["data_depth"] = statistics.median(depths)
    built = [(s[1] - s[0]) / 1e6 for s in every
             if s[2] == PROGRAM + "data_produce"]
    if built:
        out["data_produce_ms"] = statistics.median(built)
    recompiled = sum(1 for s in every if s[2] == PROGRAM + "recompiled")
    if recompiled:
        out["recompiled"] = recompiled
    return out


def reduce_trace(path):
    profile = reduce._load(path)
    per_device = reduce.device_events(profile)
    if not per_device:
        raise ValueError(f"{path}: no device operations in the trace")
    tables, source = scope_tables(path)
    out = reduce_events(
        per_device, module_events(profile), host_spans(profile), tables
    )
    out["scope_source"] = source
    return out


def of(run):
    """The reduction of the trace this run just wrote, made once per run
    (kept on ``run``, which every reader of the run is handed); None when
    the run was not traced."""
    if "program_trace" not in run:
        path = find_trace() if run.get("trace") else None
        run["program_trace"] = reduce_trace(path) if path else None
        if path:  # one line for the run's log, in seconds
            harness.say("program_trace: " + json.dumps(run["program_trace"]))
    return run["program_trace"]


# -- what the per-layer readers share -------------------------------------------

def group_pct(run, group):
    """Share of device op time under ``group``, in %."""
    trace = of(run)
    if not trace or not trace["groups_s"] or not trace["device_op_s"]:
        return None
    return 100.0 * trace["groups_s"][group] / trace["device_op_s"]


def kernels_pct(run, names):
    """Share of device op time in the kernels named ``names``, in %."""
    trace = of(run)
    if not trace or not any(k in trace["kernels_s"] for k in names):
        return None
    return 100.0 * sum(
        trace["kernels_s"].get(k, 0.0) for k in names
    ) / trace["device_op_s"]


def kernels_roofline_pct(run, names):
    """Matmul operations the kernels ``names`` performed (``flops/kernels``
    from each event's own operand shapes, times its calls; a call under a
    block map by the pairs the program states it scores) over their device
    time and the chip's bf16 peak, in %.  None where a mapped call ran and
    nothing states its pairs."""
    trace = of(run)
    if not trace or not any(k in trace["kernels_s"] for k in names):
        return None
    count = harness.load_module("flops", "kernels", run["base"])
    flops = seconds = 0.0
    for k in names:
        if k in trace["kernels_s"]:
            for shapes, calls in trace["kernel_calls"][k]:
                one = count.matmul_flops(k, shapes, trace.get("mapped_pairs"))
                if one is None:
                    return None
                flops += one * calls
            seconds += trace["kernels_s"][k]
    peak = run["peaks"]["bf16_flops_per_s"]
    return 100.0 * flops / seconds / peak if seconds else None


def host_value(run, key):
    trace = of(run)
    return trace["host"].get(key) if trace else None


if __name__ == "__main__":
    print(json.dumps(reduce_trace(sys.argv[1]), indent=1))
