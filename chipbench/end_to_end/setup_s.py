"""Process start to window open: imports, building and placing the net and
its batches, the first call (compilation, or a read of the persistent
cache) and the warm-up steps.  Source: host clock."""


def read(run):
    return run["window_open"] - run["process_start"]
