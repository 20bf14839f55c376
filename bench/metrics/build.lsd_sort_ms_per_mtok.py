"""Device milliseconds of the window-sort programs (`_lsd_argsort`) per
million tokens of the traced shard build."""
from bench.trace.reduce import program_time


def read(run):
    if run.trace is None:
        return None
    secs, runs = program_time(run.trace, "_lsd_argsort")
    if runs == 0:
        return None
    return secs * 1e3 / (run.records["traced_tokens"] / 1e6)
