"""Device time beside the work the program states for it: each executed
operation's seconds, the components of its ``op_name`` path, and the
``work`` its scope table gives it (``unicore_tpu/telemetry/hlo_scopes.py``:
``flops``, the products fused into the instruction; ``bytes``, what it
moves; ``pass``, forward, rematerialized forward or backward), and the
stats and durations of every ``unicore:`` annotation by name.

One pass over the trace with ``trace_scopes``' join (device event ->
instruction -> the traced program's scope table), kept on the run.  Every
execution of an instruction has the same path and the same work, so the
pass keeps one row per instruction with its calls and its seconds: loops,
scans and every extra pass count themselves by their events.  Nothing is
written per model: what a product costs is read from the step's own
optimized HLO, so a roofline share cannot go stale when a PR changes the
program.  A program whose tables state no ``work`` (the commit before the
field existed, or a recorded ``hlo_scopes_*.json``) makes every reader
return None; one that states it and has nothing under a selection reads 0.

Eight metrics read it: ``layer_metrics/xla_matmul_*``, ``ffn_roofline_pct``,
``attn_proj_roofline_pct``, ``moe_shared_roofline_pct``,
``optimizer_roofline_pct``, ``remat_device_pct`` and ``data_pack_ms``.

    python3 -m benchmark.scope_work <file.xplane.pb>
"""

import bisect
import collections
import json
import statistics
import sys

from benchmark import harness, reduce, trace_scopes

#: what an instruction the table does not state has to do
NOTHING = {"flops": 0, "bytes": 0, "pass": ""}
#: scope components shown in the run's log line
SHOWN = 40


def reduce_work(per_device, modules, tables, threads):
    """``{"device_op_s", "program_runs", "stated" (a table gave ``work``),
    "rows": [{"path", "flops", "bytes", "pass", "calls", "seconds"}] (one
    per executed instruction; flops and bytes of ONE execution), "marks":
    {annotation name: {"n", "ms": [durations], "stats": {key: [values]}}}}``
    per device, on plain lists (as ``trace_scopes.reduce_events`` takes
    them)."""
    n = max(len(per_device), 1)
    rows = {}
    total = 0.0
    runs = 0
    for device, events in per_device.items():
        runs_here = modules.get(device, [])
        # updates: the runs of the program that took most of the device's
        # time (``scope_shares.reduce_scopes`` counts them the same way)
        by_name = collections.Counter()
        for start, end, module in runs_here:
            by_name[module] += end - start
        step = by_name.most_common(1)[0][0] if by_name else None
        runs += sum(1 for r in runs_here if r[2] == step)
        starts = [r[0] for r in runs_here]
        for start, end, text in events:
            if reduce.parse_op(text)[1] in reduce.WRAPPERS:
                continue
            dur = (end - start) / 1e9
            total += dur
            name = trace_scopes.instruction_name(text)
            i = bisect.bisect_right(starts, start) - 1
            table = trace_scopes.table_for(
                runs_here[i][2] if i >= 0 else "", name, tables
            )
            row = rows.get((id(table), name))
            if row is None:
                path = table["instructions"][name] if table else ""
                work = (table or {}).get("work", {}).get(name, NOTHING)
                row = rows[(id(table), name)] = dict(
                    work, path=path, calls=0, seconds=0.0
                )
            row["calls"] += 1
            row["seconds"] += dur
    marks = {}
    for spans in threads.values():
        for start, end, name, stats in spans:
            if not name.startswith(trace_scopes.PROGRAM):
                continue
            mark = marks.setdefault(
                name[len(trace_scopes.PROGRAM):],
                {"n": 0, "ms": [], "stats": collections.defaultdict(list)},
            )
            mark["n"] += 1
            mark["ms"].append((end - start) / 1e6)
            for key, value in stats.items():
                mark["stats"][key].append(value)
    for row in rows.values():
        row["calls"] /= n
        row["seconds"] /= n
    return {
        "device_op_s": total / n, "program_runs": runs // n,
        "stated": any(t.get("work") for t in tables),
        "rows": list(rows.values()),
        "marks": marks,
    }


def least_seconds(row, peaks):
    """The least time the chip could take for one execution of ``row``:
    its products at the bf16 peak or its bytes at the memory bandwidth,
    whichever is longer."""
    return max(row["flops"] / peaks["bf16_flops_per_s"],
               row["bytes"] / peaks["hbm_bytes_per_s"])


def summary(work, peaks):
    """What the run's log shows of the pass: for each of the ``SHOWN``
    largest scope components its seconds, flops and bytes an update and
    the least seconds its work could take; device seconds by pass; the
    annotations by name (count, median ms, means of their numeric
    stats)."""
    per = max(work["program_runs"], 1)
    scopes = collections.defaultdict(lambda: [0.0, 0, 0, 0.0])
    by_pass = collections.Counter()
    for row in work["rows"]:
        by_pass[row["pass"] or "none"] += row["seconds"] / per
        for part in set(filter(None, row["path"].split("/"))):
            s = scopes[part]
            s[0] += row["seconds"] / per
            s[1] += row["flops"] * row["calls"] / per
            s[2] += row["bytes"] * row["calls"] / per
            s[3] += least_seconds(row, peaks) * row["calls"] / per
    largest = sorted(scopes.items(), key=lambda kv: -kv[1][0])[:SHOWN]
    marks = {}
    for name, mark in work["marks"].items():
        stats = {
            k: statistics.fmean(map(float, v)) for k, v in mark["stats"].items()
            if all(isinstance(x, (int, float)) for x in v)
        }
        marks[name] = {"n": mark["n"],
                       "median_ms": statistics.median(mark["ms"]), **stats}
    return {
        "device_op_s": work["device_op_s"],
        "program_runs": work["program_runs"], "stated": work["stated"],
        "per_update": {
            k: {"s": round(s, 6), "flops": int(f), "bytes": int(b),
                "least_s": round(least, 6)}
            for k, (s, f, b, least) in largest
        },
        "pass_s_per_update": {k: round(v, 6) for k, v in by_pass.items()},
        "marks": marks,
    }


def of(run):
    """This run's pass, made once per run and kept on it; None when the
    run was not traced or the program left no scope table
    (``trace_scopes.of`` says: its groups are empty then).  With a
    reduction handed in ready-made and no trace file to pass over
    (``tests/benchmark/test_trace_scopes.py`` hands every listed reader a
    recorded run's) there are no rows and no annotations: every share
    reads 0."""
    named = trace_scopes.of(run)
    if not named or not named["groups_s"]:
        return None
    if "scope_work" not in run:
        path = trace_scopes.find_trace() if run.get("trace") else None
        run["scope_work"] = None
        if path:
            profile = reduce._load(path)
            out = run["scope_work"] = reduce_work(
                reduce.device_events(profile),
                trace_scopes.module_events(profile),
                trace_scopes.scope_tables(path)[0],
                trace_scopes.host_spans(profile),
            )
            harness.say("scope_work: " + json.dumps(
                summary(out, run["peaks"])
            ))
    return dict(run["scope_work"] or {
        "device_op_s": named["device_op_s"],
        "program_runs": named["program_runs"],
        "stated": True, "rows": [], "marks": {},
    }, host_spans=bool(named["host"]))


# -- what the per-layer readers share -------------------------------------------

def _selected(run, select):
    work = of(run)
    if not work or not work["stated"]:
        return None, None
    return work, [r for r in work["rows"] if select(r["path"].split("/"), r)]


def roofline_pct(run, select):
    """Over the operations ``select(parts, row)`` takes (the components of
    an operation's path, and its row): the least time the chip could take
    for them (:func:`least_seconds`, each execution on its own) over the
    device time they took, in %.  None when the run was not traced or no
    table states ``work``; 0 where ``select`` took none."""
    work, rows = _selected(run, select)
    if work is None:
        return None
    seconds = sum(r["seconds"] for r in rows)
    if not seconds:
        return 0.0
    least = sum(least_seconds(r, run["peaks"]) * r["calls"] for r in rows)
    return 100.0 * least / seconds


def device_pct(run, select):
    """The selected operations' time over device op time (control-flow
    wrappers skipped, as ``scope_shares`` counts it), in %."""
    work, rows = _selected(run, select)
    if work is None or not work["device_op_s"]:
        return None
    return 100.0 * sum(r["seconds"] for r in rows) / work["device_op_s"]


def span_median_ms(run, name):
    """The median duration of the program's ``unicore:<name>`` spans, on
    whichever threads wrote them, in ms; 0 where the program wrote its
    annotations and none was such a span; None where it wrote none."""
    work = of(run)
    if not work or not work["host_spans"]:
        return None
    mark = work["marks"].get(name)
    return statistics.median(mark["ms"]) if mark else 0.0


if __name__ == "__main__":
    _profile = reduce._load(sys.argv[1])
    print(json.dumps(summary(reduce_work(
        reduce.device_events(_profile), trace_scopes.module_events(_profile),
        trace_scopes.scope_tables(sys.argv[1])[0],
        trace_scopes.host_spans(_profile),
    ), harness.peaks_for("TPU v5 lite")), indent=1))
