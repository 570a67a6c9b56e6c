"""1 - union of device-operation intervals over the traced window: the
same reading the ``device`` key of the result line carries."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
