"""The data-driven core: finds a cell's files by the names in
``BENCHMARK.json``, runs its driver, hands what the run left behind to the
per-layer readers, and prints the one result line.

Nothing here knows a configuration, a cell or a metric by name.  Adding one
means adding files (see ``benchmark/README.md``) and manifest entries.
"""

import contextlib
import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

#: process start, as close as Python lets us see it: ``setup_s`` is counted
#: from here to the first measured instant
T_START = time.perf_counter()


class Refused(SystemExit):
    """The run cannot be a measurement (no chip, unknown chip, bad cell):
    exit non-zero and print no result line."""

    def __init__(self, msg, code=3):
        sys.stderr.write(f"benchmark: {msg}\n")
        super().__init__(code)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_manifest(root=ROOT):
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.isfile(path):
        raise Refused(f"no BENCHMARK.json in {root}")
    return load_json(path)


def find(kind, filename, base=HERE):
    """``<base>/<kind>/<filename>``, else the benchmark's own: a later PR
    (or a test) that brings a directory of new files need not copy the
    files that are there."""
    for root in (base, HERE):
        path = os.path.join(root, kind, filename)
        if os.path.isfile(path):
            return path
    raise Refused(f"{kind}: no file {filename} under {base}")


def load_module(kind, name, base=HERE):
    """``benchmark/<kind>/<name>.py`` as a module, found by file name (a
    metric's name may hold dots, so this is not an import statement)."""
    path = find(kind, name + ".py", base)
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name.replace('.', '_')}", path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with its configuration and the files the
    manifest names for them."""

    def __init__(self, manifest, name, base=HERE, root=ROOT):
        try:
            self.entry = next(
                w for w in manifest["workloads"] if w["name"] == name
            )
        except StopIteration:
            raise Refused(
                f"unknown workload {name!r}; BENCHMARK.json has "
                f"{[w['name'] for w in manifest['workloads']]}"
            ) from None
        self.name = name
        self.chips = int(self.entry["chips"])
        cfg = next(
            c for c in manifest["configs"] if c["name"] == self.entry["config"]
        )
        self.config = load_json(os.path.join(root, cfg["file"]))
        self.traffic = load_json(find("workloads", name + ".json", base))
        self.base = base
        self.manifest = manifest

    def metrics(self, group):
        """The manifest's metrics of ``group`` that this cell reports: those
        that list it, and those that list no cell (an end-to-end metric is
        then every cell's; a per-layer metric belongs to every cell that
        reports the end-to-end metric it moves)."""
        mine = [
            m for m in self.manifest["end_to_end"]
            if "workloads" not in m or self.name in m["workloads"]
        ]
        if group == "end_to_end":
            return mine
        moved = {m["name"] for m in mine}
        return [
            m for m in self.manifest[group]
            if self.name in m.get("workloads", ()) or (
                "workloads" not in m and m["moves"] in moved
            )
        ]


def peaks_for(device_kind, base=HERE):
    table = load_json(os.path.join(base, "peaks.json"))
    if device_kind not in table or device_kind.startswith("_"):
        raise Refused(
            f"device_kind {device_kind!r} is not in benchmark/peaks.json "
            f"({sorted(k for k in table if not k.startswith('_'))}); add it "
            "with its source"
        )
    return table[device_kind]


def require_chips(chips):
    """The devices as JAX reports them, or no run at all: a measurement
    path that finds no TPU, an unknown TPU or too few chips fails."""
    import jax

    from unicore_tpu.platform_utils import describe_devices, on_tpu

    if not on_tpu():
        raise Refused(
            f"no TPU (default backend {jax.default_backend()!r}); the "
            "benchmark measures the chip and never falls back to the CPU"
        )
    dev = describe_devices()
    peaks = peaks_for(dev["kind"])
    if dev["count"] < chips:
        raise Refused(
            f"the cell needs {chips} chip(s), JAX found {dev['count']}"
        )
    return dev, peaks


# -- spans -------------------------------------------------------------------

class Spans:
    """Host spans of the harness's own, around its calls into each layer
    (``data``: the batch iterator's ``next``; ``dispatch``: the program's
    step call; ``fetch``: the barrier).  Kept in memory; in a traced run
    each span is also a ``TraceAnnotation``, so it lands in the profiler's
    trace on the device events' clock and ``reduce.py`` can say what the
    host was doing in an idle gap."""

    def __init__(self):
        self.records = []  # (name, start_s, end_s) on perf_counter
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name):
        ann = None
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation("bench:" + name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.records.append((name, t0, t1))

    def durations(self, name, since=0.0):
        return [b - a for n, a, b in self.records if n == name and a >= since]


class Phases:
    """Says how long each part of set-up took (an earlier line of the
    output; ``setup_s`` is their sum)."""

    def __init__(self, first):
        self.at = time.perf_counter()
        say(f"setup: {first} {self.at - T_START:.1f}s")

    def __call__(self, name):
        now = time.perf_counter()
        say(f"setup: {name} {now - self.at:.1f}s")
        self.at = now


class Tracer:
    """The profiler, around a few seconds of the cell's own loop.  A traced
    run is a run of its own: end-to-end numbers come from untraced runs."""

    def __init__(self, out_dir):
        self.dir = out_dir

    def start(self):
        import shutil

        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir)

    def stop(self):
        """Closes the trace; returns the ``.xplane.pb`` it wrote."""
        import jax

        jax.profiler.stop_trace()
        for dirpath, _dirs, files in os.walk(self.dir):
            for f in files:
                if f.endswith(".xplane.pb"):
                    return os.path.join(dirpath, f)
        raise Refused(f"the profiler wrote no trace under {self.dir}", 4)


# -- the result line ---------------------------------------------------------

def memory_peak_bytes():
    import jax

    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def say(msg):
    print(msg, flush=True)


def report_checks(checks):
    """Every number compared, beside its limit; returns ``correct``."""
    ok = True
    for c in checks:
        passed = bool(c["value"] <= c["limit"]) and c["value"] == c["value"]
        ok = ok and passed
        say(
            f"check {c['name']}: value={c['value']:.6g} limit={c['limit']:.6g} "
            f"{'ok' if passed else 'FAIL'}"
            + (f" ({c['note']})" if c.get("note") else "")
        )
    return ok


def layer_values(cell, run):
    """Each per-layer metric of this cell through its reader; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell.metrics("per_layer"):
        reader = load_module("layer_metrics", m["name"], cell.base)
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(cell, run, trace):
    """The one JSON object the driver reads, as the last line of stdout."""
    if trace:
        metrics = layer_values(cell, run)
    else:
        metrics = {}
        for m in cell.metrics("end_to_end"):
            if m["name"] not in run["end_to_end"]:
                raise Refused(
                    f"driver reported no {m['name']} for {cell.name}", 4
                )
            metrics[m["name"]] = {
                "value": float(run["end_to_end"][m["name"]]),
                "unit": m["unit"],
            }
    device = dict(run["device"])
    device["memory_peak_bytes"] = int(run["memory_peak_bytes"])
    line = {
        "correct": bool(run["correct"]),
        "attempted": int(run["attempted"]),
        "failed": int(run["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if trace and run.get("trace") is not None:
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        line["breakdown"] = {
            "device_ops": run["trace"]["device_ops"][:10],
            "idle_gaps": run["trace"]["idle_gaps"][:10],
        }
    return json.dumps(line)
