"""Share of device operation time in Mosaic (Pallas) custom calls."""


def read(run):
    trace = run.get("trace")
    if not trace or trace.get("pallas_share") is None:
        return None
    return 100.0 * trace["pallas_share"]
