"""Device op time in the second forward of rematerialized blocks
(``work.pass`` == ``remat`` in the traced program's scope table: a
``rematted_computation`` component in the operation's path, the Mosaic
kernels' too) over device op time, in %; 0 where nothing is
rematerialized."""

from benchmark import scope_work


def read(run):
    return scope_work.device_pct(run, lambda parts, row: row["pass"] == "remat")
