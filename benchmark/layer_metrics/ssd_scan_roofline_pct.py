"""The least time the chip could take for the state-space scan of one
update (``flops/nemotron_scopes.py``: its products over the bf16 peak or
its bytes over the memory bandwidth, whichever is larger; at head size 64
and state 128 the bytes bound it) over the device time under ``ssd_scan``
per update, in %."""

from benchmark import harness, scope_shares


def count(run):
    return harness.load_module(
        "flops", "nemotron_scopes", run["base"]
    ).ssd_scan(run)


def read(run):
    return scope_shares.scope_roofline_pct(run, "ssd_scan", count)
