"""95th percentile of the time between successive step completions, in ms a
step: the stalls a job feels (a host hiccup, a recompile, a late collective).
Source: host clock."""
from chipbench import stats


def read(run):
    ms = stats.step_intervals_ms(run)
    print(f"[step_ms] median {stats.median(ms):.4f} ms, p95 "
          f"{stats.percentile(ms, 95):.4f} ms, max {max(ms):.4f} ms over "
          f"{len(ms)} intervals", flush=True)
    return stats.percentile(ms, 95)
