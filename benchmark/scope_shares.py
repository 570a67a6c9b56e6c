"""Device time by the program's own scope names, for the per-layer metrics
of layers ``trace_scopes.GROUPS`` does not know (``mamba``, ``ssd_scan``,
``moe``, ``moe_routed``, ...), and the stats of the program's
``unicore:moe_route`` annotations.

One more pass over the trace ``trace_scopes`` has already reduced, with
its join (device event -> instruction -> ``op_name`` path from the traced
program's scope table; ``trace_scopes.reduce_events`` keeps its eight
largest scopes only): every component of an operation's path is credited
with the operation's time, so ``seconds["moe"]`` is all device time under a
module or ``jax.named_scope`` named ``moe``, forward, rematerialized
forward and backward alike.  What no scope owns is ``trace_scopes``'
``unattributed`` group (``unattributed_device_pct``), not counted again
here.  A program that names its operations but has no such layer reads 0
under it; a program that leaves no scope table (or a run that was not
traced) gives nothing, and every reader returns None.
"""

import bisect
import collections
import json
import statistics

from benchmark import harness, reduce, trace_scopes

#: scopes whose device time is also given primitive by primitive
DETAILED = ("moe_routed", "ssd_scan")
#: the stats of a ``unicore:moe_route`` mark
ROUTE_STATS = ("pairs_here", "load_max", "load_mean")


def reduce_scopes(per_device, modules, tables, threads):
    """``{"device_op_s", "program_runs" (runs of the step program: the
    updates traced), "seconds": {scope: s}, "inside": {scope: [[primitive,
    s]]}, "route": {stat: mean over the marks}, "route_marks"}`` per
    device, on plain lists (as ``trace_scopes.reduce_events`` takes
    them)."""
    n = max(len(per_device), 1)
    seconds = collections.Counter()
    inside = {scope: collections.Counter() for scope in DETAILED}
    total = 0.0
    runs = 0
    for device, events in per_device.items():
        runs_here = modules.get(device, [])
        # updates: the runs of the program that took most of the device's
        # time (the step; a small program of the trainer's own runs beside
        # it once an update)
        by_name = collections.Counter()
        for start, end, module in runs_here:
            by_name[module] += end - start
        step = by_name.most_common(1)[0][0] if by_name else None
        runs += sum(1 for r in runs_here if r[2] == step)
        starts = [r[0] for r in runs_here]
        for start, end, text in events:
            _label, opcode, _mosaic = reduce.parse_op(text)
            if opcode in reduce.WRAPPERS:
                continue
            dur = (end - start) / 1e9
            total += dur
            name = trace_scopes.instruction_name(text)
            i = bisect.bisect_right(starts, start) - 1
            table = trace_scopes.table_for(
                runs_here[i][2] if i >= 0 else "", name, tables
            )
            path = table["instructions"][name] if table else ""
            parts = set(path.split("/"))
            for part in parts:
                seconds[part] += dur
            for scope in parts & set(DETAILED):
                # what runs under the scope, by the primitive (and the
                # loop it is in) that the operation's path ends with
                tail = path.split(scope + "/", 1)[1]
                inside[scope][tail] += dur
    marks = [
        s[3] for spans in threads.values() for s in spans
        if s[2] == trace_scopes.PROGRAM + "moe_route"
    ]
    route = {
        k: statistics.fmean(float(m[k]) for m in marks)
        for k in ROUTE_STATS if marks and all(k in m for m in marks)
    }
    return {
        "device_op_s": total / n, "program_runs": runs // n,
        "seconds": {k: v / n for k, v in seconds.items() if k},
        "inside": {scope: [[k, v / n] for k, v in c.most_common(12)]
                   for scope, c in inside.items() if c},
        "route": route,
        "route_marks": len(marks),
    }


def of(run):
    """This run's reduction; None when the run was not traced or the
    program left no scope table (``trace_scopes.of`` says: its groups are
    empty then).  The pass over the trace file is made once per run.  With
    a scope table but no trace file at hand (a reduction handed in
    ready-made, as ``tests/benchmark/test_trace_scopes.py`` hands every
    reader a recorded run's) the seconds are what ``trace_scopes``' own top
    scopes show, and there are no routing stats."""
    named = trace_scopes.of(run)
    if not named or not named["groups_s"]:
        return None
    if "scope_pass" not in run:
        path = trace_scopes.find_trace() if run.get("trace") else None
        run["scope_pass"] = None
        if path:
            profile = reduce._load(path)
            out = run["scope_pass"] = reduce_scopes(
                reduce.device_events(profile),
                trace_scopes.module_events(profile),
                trace_scopes.scope_tables(path)[0],
                trace_scopes.host_spans(profile),
            )
            # one line for the run's log: the 40 largest components
            shown = {k: round(v, 6) for k, v in sorted(
                out["seconds"].items(), key=lambda kv: -kv[1])[:40]}
            harness.say("scope_shares: " + json.dumps(dict(out, seconds=shown)))
    out = run["scope_pass"]
    if out is None:
        seconds = collections.Counter()
        for label, sec in named["top_scopes"]:
            for part in set(label.split("/")):
                seconds[part] += sec
        out = {"device_op_s": named["device_op_s"],
               "program_runs": named["program_runs"],
               "seconds": dict(seconds), "route": {}}
    return dict(out, host_spans=bool(named["host"]))


def scope_pct(run, scope):
    """Share of device op time under ``scope``, in %: 0 where the program
    named its operations and none ran under ``scope``."""
    shares = of(run)
    if not shares:
        return None
    return 100.0 * shares["seconds"].get(scope, 0.0) / shares["device_op_s"]


def scope_roofline_pct(run, scope, count):
    """The least time the chip could take for what ``scope`` has to do in
    one update (``count(run) -> (operations, bytes)``: the larger of
    operations over the bf16 peak and bytes over the memory bandwidth), over
    the device time under ``scope`` per update, in %; 0 where nothing ran
    under ``scope``."""
    shares = of(run)
    if not shares:
        return None
    if not shares["seconds"].get(scope) or not shares["program_runs"]:
        return 0.0
    ops, nbytes = count(run)
    least = max(ops / run["peaks"]["bf16_flops_per_s"],
                nbytes / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least * shares["program_runs"] / shares["seconds"][scope]


def route_stat(run, key):
    """A stat of the ``unicore:moe_route`` marks, its mean over the traced
    updates that left one; 0 where the program wrote its annotations and no
    update routed anything; None where it wrote none."""
    shares = of(run)
    if not shares or not shares.get("host_spans"):
        return None
    return shares["route"].get(key, 0.0)
