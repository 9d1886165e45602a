"""Steps completed in the window x global batch / (last completion - window
open): images for a vision model, sequences for a language model; on several
chips the whole mesh's rate.  Source: host clock."""
from chipbench import stats


def read(run):
    return stats.samples_per_s(run)
