"""Peak device memory taken on the fullest chip, read after the window:
``memory_stats()`` ``peak_bytes_in_use`` (buffers the process holds) plus
``peak_bytes_reserved`` (running programs' temporaries).  Layer: model step.
Source: program counter."""


def read(run):
    return run["memory_peak_bytes"] / 2**30 or None
