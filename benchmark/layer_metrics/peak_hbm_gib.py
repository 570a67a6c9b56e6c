"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip, read after
the window and before the reference runs, in GiB."""


def read(run):
    if not run.get("memory_peak_bytes"):
        return None
    return run["memory_peak_bytes"] / 2 ** 30
