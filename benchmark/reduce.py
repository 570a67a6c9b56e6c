"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: device busy time as the union of the intervals in which an
operation ran, the idle share, the operations that took most time, the
longest idle gaps by what the host was doing, and the share of device
operation time in Mosaic (Pallas) kernels.

What a v5e trace holds (read by hand from PR 24's first traced run): one
plane ``/device:TPU:<n>`` per chip with the lines ``Steps`` (one event per
program run), ``XLA Modules``, ``XLA Ops`` (one event per executed HLO
operation, named by its HLO line) and ``Async XLA Ops`` (DMA starts, which
overlap the operations and are not counted as busy).  Event stats carry
only device offsets: no ``jax.named_scope`` reaches them.

Read with nothing but JAX (``jax.profiler.ProfileData``).  The same code
reduces every PR's trace; ``tests/benchmark/test_reduce.py`` checks it
against a hand reading of a small recorded trace.

    python3 -m benchmark.reduce <file.xplane.pb> [--describe]
"""

import collections
import json
import sys

#: device planes are named ``/device:TPU:<n>``; the line that holds one
#: event per executed HLO operation
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
#: the harness's own host spans (``harness.Spans`` in a traced run)
SPAN_PREFIX = "bench:"
#: control-flow wrappers contain their bodies' operations as events of
#: their own; counting both would count that time twice
WRAPPERS = ("while", "conditional", "call")


def _load(path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _stats(event):
    return {k: v for k, v in event.stats}


def parse_op(text):
    """An op event's name is its HLO line: ``%name.N = <type> opcode(...),
    attrs``.  Returns (label, opcode, is_mosaic): the label is the name
    without ``%`` and the trailing number, so the same operation of every
    layer adds up under one label."""
    head, _, rest = text.partition(" = ")
    label = head.lstrip("%")
    stem, dot, tail = label.rpartition(".")
    if dot and tail.isdigit():
        label = stem
    rest = rest.lstrip()
    if rest.startswith("("):  # a tuple type: skip to its closing bracket
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:].lstrip()
                break
    else:
        rest = rest.partition(" ")[2]
    opcode = rest.partition("(")[0].strip()
    mosaic = opcode == "custom-call" and 'custom_call_target="tpu_custom_call"' in text
    return label, opcode, mosaic


def device_events(profile):
    """{plane name: [(start_ns, end_ns, HLO text)]} of the op line."""
    out = {}
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        events = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for e in line.events:
                start = float(e.start_ns)
                events.append((start, start + float(e.duration_ns), e.name))
        if events:
            out[plane.name] = sorted(events)
    return out


def host_spans(profile):
    """[(start_ns, end_ns, name)] of the harness's annotations."""
    spans = []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    start = float(e.start_ns)
                    spans.append((start, start + float(e.duration_ns),
                                  e.name[len(SPAN_PREFIX):]))
    return sorted(spans)


def is_wrapper(text):
    return parse_op(text)[1] in WRAPPERS


def union(intervals):
    """Merged, sorted intervals."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def attribute(gap, spans):
    """The host span that covers most of ``gap``, or ``(no span)``."""
    best, best_cover = "(no span)", 0.0
    for a, b, name in spans:
        if b <= gap[0]:
            continue
        if a >= gap[1]:
            break
        cover = min(b, gap[1]) - max(a, gap[0])
        if cover > best_cover:
            best, best_cover = name, cover
    return best


def reduce_events(per_device, spans):
    """The reduction proper, on plain lists (so a test can hand-build one)."""
    busy_s, window_s = [], []
    ops = collections.Counter()
    gaps = collections.Counter()
    mosaic_ns = total_op_ns = 0.0
    for events in per_device.values():
        leaf = [e for e in events if not is_wrapper(e[2])]
        merged = union((a, b) for a, b, _n in leaf)
        lo, hi = merged[0][0], merged[-1][1]
        busy_s.append(sum(b - a for a, b in merged) / 1e9)
        window_s.append((hi - lo) / 1e9)
        for (_, end), (start, _) in zip(merged, merged[1:]):
            gaps[attribute((end, start), spans)] += (start - end) / 1e9
        for a, b, text in leaf:
            label, _opcode, mosaic = parse_op(text)
            ops[("pallas:" if mosaic else "") + label] += (b - a) / 1e9
            total_op_ns += b - a
            mosaic_ns += (b - a) if mosaic else 0.0
    n = max(len(per_device), 1)
    return {
        "busy_s": sum(busy_s) / n,
        "window_s": sum(window_s) / n,
        "device_ops": [[k, v / n] for k, v in ops.most_common(10)],
        "idle_gaps": [[k, v / n] for k, v in gaps.most_common(10)],
        "pallas_share": mosaic_ns / total_op_ns if total_op_ns else None,
        "devices": len(per_device),
    }


def reduce(path):
    profile = _load(path)
    per_device = device_events(profile)
    if not per_device:
        raise ValueError(f"{path}: no device operations in the trace")
    return reduce_events(per_device, host_spans(profile))


def describe(path, limit=12):
    """What a hand reading starts from: planes, lines, a few events."""
    profile = _load(path)
    for plane in profile.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  line {line.name!r}: {len(events)} events")
            for e in events[:limit]:
                print(f"    {e.name!r} start_ns={e.start_ns} "
                      f"dur_ns={e.duration_ns} stats={_stats(e)}")


if __name__ == "__main__":
    if "--describe" in sys.argv:
        describe(sys.argv[1])
    else:
        print(json.dumps(reduce(sys.argv[1]), indent=1))
