"""Window seconds over updates, in ms; the window is closed by a fetch
that depends on the last update."""


def read(run):
    if not run.get("updates"):
        return None
    return 1e3 * run["window_s"] / run["updates"]
