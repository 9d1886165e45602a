"""From a profiler trace (``.xplane.pb``) to device busy and idle time, device
time by operation, collective time hidden or exposed, and idle gaps by what
the host was doing.

Two parts, tested apart:

* ``read_events(path)``: a thin reader, ``jax.profiler.ProfileData`` -> a flat
  list of ``Event(plane, line, name, start, dur)`` in integer nanoseconds.
* the arithmetic over such a list (``union``, ``subtract``, ``gaps``,
  ``reduce_trace`` ...), which needs no JAX and is held to hand-computed
  values in tests/chipbench/test_chipbench.py.

``python chipbench/trace_reduce.py <file.xplane.pb>`` prints what a trace
holds (planes, lines, most frequent names): look at one by hand before
changing how operations are told apart below.
"""
import collections
import re
import sys

Event = collections.namedtuple("Event", "plane line name start dur")

# How a TPU trace is laid out (read by hand from a v5e trace, jax 0.9.0,
# PR 25): one plane per chip named "/device:TPU:<id>".  Its line "XLA Ops" holds
# one event per executed HLO instruction, named by the instruction's whole text
# ("%fusion.12 = bf16[256,64,56,56]{...} fusion(...), kind=kOutput, ..."); its
# line "XLA Modules" holds one event per program run, "Steps" the same by
# step, and "Async XLA Ops" the spans of asynchronous copies and collectives
# from their -start to their -done, which overlap the operations of "XLA Ops".
# Host threads are lines of the plane "/host:CPU", and
# ``jax.profiler.TraceAnnotation`` spans are events of the line "python3"
# there.  The device's clock and the host's agree to within a few
# milliseconds only (the first traced program starts on the device 0.7 ms
# before the host span that dispatched it), so a gap of microseconds cannot be
# given to a host span, and one of many milliseconds can.
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
HOST_PLANE = "/host:CPU"
HOST_SPANS = ("bench.dispatch", "bench.wait")
COLLECTIVE = re.compile(r"\s(all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute)(-start|-done)?\(")
PALLAS = 'custom_call_target="tpu_custom_call"'
LAYOUT = re.compile(r"\{[^{}]*\}")


# ------------------------------------------------------------------ reader

def read_events(path):
    """Every event of every line of every plane, flat."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    events = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                events.append(Event(plane.name, line.name, ev.name,
                                    int(ev.start_ns), int(ev.duration_ns)))
    return events


# -------------------------------------------------------------- arithmetic

def union(intervals):
    """Merged, sorted ``(start, end)`` intervals covering the same points."""
    merged = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def total(intervals):
    return sum(end - start for start, end in intervals)


def subtract(a, b):
    """The parts of the merged intervals ``a`` that no interval of the
    merged ``b`` covers."""
    out, j = [], 0
    for start, end in a:
        cur = start
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < end:
            out.append((cur, end))
    return out


def gaps(merged, lo, hi):
    """The intervals of ``[lo, hi]`` that the merged intervals leave."""
    return subtract([(lo, hi)], merged)


def covering_span(gap, spans):
    """The name of the span ``(name, start, end)`` that covers most of the
    gap, or "none"."""
    best, best_overlap = "none", 0
    for name, start, end in spans:
        overlap = min(end, gap[1]) - max(start, gap[0])
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return best


def is_collective(text):
    """By the instruction's opcode; an operand that is one ("%all-reduce.3")
    does not count."""
    return bool(COLLECTIVE.search(text))


def is_pallas(text):
    """A Mosaic (Pallas) kernel runs as this custom call."""
    return PALLAS in text


def family(text):
    """An instruction's name without its number: "%fusion.12 = ..." and
    "%fusion.7 = ..." are of the family "fusion"."""
    return re.sub(r"\.\d+$", "", text.split(" = ")[0].lstrip("%"))


def short_name(text, width=160):
    """An instruction's text without its "%" and layouts, cut to ``width``:
    its name, the shapes it produces, its opcode and first operands."""
    return LAYOUT.sub("", text.lstrip("%"))[:width]


def intervals_of(events):
    return [(e.start, e.start + e.dur) for e in events]


def reduce_device(ops, async_ops, host_spans):
    """One device's events -> its window (from its first operation's start
    to its last one's end: the device's own clock, whatever the host's says),
    busy time, time by instruction, Pallas time, collective time (the union of
    the collectives' intervals, synchronous or from -start to -done) and the
    part of it exposed (while no other operation runs there), and the idle
    gaps, longest first, each with the host span that covers most of it."""
    busy = union(intervals_of(ops))
    lo, hi = busy[0][0], busy[-1][1]
    by_name = collections.Counter()
    for e in ops:
        by_name[e.name] += e.dur
    coll = union(intervals_of(e for e in ops + async_ops
                              if is_collective(e.name)))
    other = union(intervals_of(e for e in ops if not is_collective(e.name)))
    idle = sorted(gaps(busy, lo, hi), key=lambda g: g[0] - g[1])
    return {
        "window": hi - lo, "busy": total(busy), "by_name": by_name,
        "collective": total(coll), "exposed": total(subtract(coll, other)),
        "pallas": sum(e.dur for e in ops if is_pallas(e.name)),
        "gaps": [(covering_span(g, host_spans), g[1] - g[0])
                 for g in idle[:10]],
    }


def reduce_trace(events, steps, device_ids):
    """The whole reduction, in seconds: means over the devices used, the
    worst device's idle share, and the contract's ``breakdown``.  None where
    the trace holds no device operations (a CPU rehearsal)."""
    ops, async_ops = {}, {}
    for e in events:
        m = DEVICE_PLANE.match(e.plane)
        if m and int(m.group(1)) in device_ids:
            if e.line == OPS_LINE:
                ops.setdefault(int(m.group(1)), []).append(e)
            elif e.line == ASYNC_LINE:
                async_ops.setdefault(int(m.group(1)), []).append(e)
    if not ops:
        return None
    spans = [(e.name, e.start, e.start + e.dur) for e in events
             if e.plane == HOST_PLANE and e.name in HOST_SPANS]
    reduced = [reduce_device(ops[d], async_ops.get(d, []), spans)
               for d in sorted(ops)]
    n = len(reduced)
    mean_s = lambda key: 1e-9 * sum(r[key] for r in reduced) / n
    by_name = collections.Counter()
    for r in reduced:
        by_name.update(r["by_name"])
    by_family, members = collections.Counter(), collections.Counter()
    for name, dur in by_name.items():
        by_family[family(name)] += dur
        members[family(name)] += 1
    worst = max(reduced, key=lambda r: 1 - r["busy"] / r["window"])
    has_collectives = any(r["collective"] for r in reduced)
    return {
        "steps": steps, "devices": n,
        "window_s": mean_s("window"), "busy_s": mean_s("busy"),
        "idle_share_worst": 1 - worst["busy"] / worst["window"],
        "pallas_s": mean_s("pallas"),
        "collective_s": mean_s("collective") if has_collectives else None,
        "collective_exposed_s": (mean_s("exposed") if has_collectives
                                 else None),
        "top_instructions": [[short_name(name), 1e-9 * dur / n]
                             for name, dur in by_name.most_common(10)],
        # single instructions are a percent or two each, so the breakdown
        # names families: every instruction of one name but for its number
        "breakdown": {
            "device_ops": [[f"{fam} ({members[fam]} instructions)",
                            1e-9 * dur / n]
                           for fam, dur in by_family.most_common(10)],
            "idle_gaps": [[name, 1e-9 * dur] for name, dur in worst["gaps"]],
        },
    }


# ------------------------------------------------------------ look by hand

def summary(events, top=25):
    lines = collections.defaultdict(list)
    for e in events:
        lines[(e.plane, e.line)].append(e)
    out = []
    for (plane, line), evs in sorted(lines.items()):
        span = max(e.start + e.dur for e in evs) - min(e.start for e in evs)
        out.append(f"{plane} | {line}: {len(evs)} events, "
                   f"{sum(e.dur for e in evs) / 1e6:.3f} ms in "
                   f"{span / 1e6:.3f} ms, first start "
                   f"{min(e.start for e in evs)}")
        names, counts = collections.Counter(), collections.Counter()
        for e in evs:
            names[e.name] += e.dur
            counts[e.name] += 1
        for name, dur in names.most_common(top):
            out.append(f"    {dur / 1e6:12.3f} ms  x{counts[name]:<6d} "
                       f"{short_name(name, 120)}")
    return "\n".join(out)


if __name__ == "__main__":
    print(summary(read_events(sys.argv[1])))
