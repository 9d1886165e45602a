"""Device time a step of the instructions that ran under given names: the
kernel scopes and block keys the program writes into every instruction's
``op_name`` (``jit(step)/<phase>/<block keys>/jit(<op>)/<kernel
scope>/...``), read from the traced run's own ``.xplane.pb`` with
``scope_reduce``'s reader and self-time arithmetic.  ``scope_reduce``'s
printed tables keep a fixed list of kernel names and the 15 largest
scopes; the readers of newer names come here.

``ms_under(run, names)`` returns None where the run was not traced, the
trace holds no device operation (a CPU rehearsal), or no instruction with a
phase ran under any of the names (a program that does not write them)."""
import collections
import glob
import os

from chipbench import scope_reduce

_TIMES = {}         # path -> {segment: ns summed over devices}, n devices


def _by_segment(path):
    ops, _, _ = scope_reduce.read_trace(path)
    by_device = collections.defaultdict(list)
    for op in ops:
        by_device[op.device].append(op)
    times = collections.Counter()
    for mine in by_device.values():
        for op, ns in scope_reduce.self_times(mine):
            name = next((one for one in op.op_name.split(";")
                         if scope_reduce.parse(one).phase), None)
            for segment in set(name.split("/")) if name else ():
                times[segment] += ns
    return times, len(by_device)


def ms_under(run, names):
    """Milliseconds a step (mean over the devices) of the instructions
    whose ``op_name`` holds one of ``names`` as a whole segment."""
    if not run.get("trace"):
        return None
    found = glob.glob(os.path.join(
        scope_reduce.HERE, "out", run["cell"]["name"], "**", "*.xplane.pb"),
        recursive=True)
    if not found:
        return None
    path = max(found, key=os.path.getmtime)
    if path not in _TIMES:
        _TIMES[path] = _by_segment(path)
    times, devices = _TIMES[path]
    ns = sum(times[name] for name in names)
    if not devices or not ns:
        return None
    return ns / 1e6 / run["cell"]["traffic"]["trace_steps"] / devices


def roofline_pct(run, work, names):
    """100 x the least time the chip could take for ``work = (operations,
    bytes)`` a step (the larger of operations over the peak FLOP/s and
    bytes over the peak bytes/s) over the traced device time a step under
    ``names``; None where either is missing."""
    ms = ms_under(run, names)
    if ms is None or not run["peaks"] or work is None:
        return None
    ops, moved = work
    least_s = max(ops / run["peaks"]["bf16_flops_per_s"],
                  moved / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)


def config_work(run, function):
    """``function(config, traffic)`` of the cell's configuration module, or
    None where the module has no such function."""
    module = run.get("model")
    fn = getattr(module, function, None) if module else None
    return fn(run["config"], run["cell"]["traffic"]) if fn else None
