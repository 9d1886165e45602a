"""From a profiler trace (``.xplane.pb``) to device time by the program's own
names: by phase (forward, backward, optimizer), by block path, by kernel; the
program's host spans beside the device's operations on one clock; and the
program's compile counters cut at the window's opening.

    python3 chipbench/scope_reduce.py <file.xplane.pb> [steps]

prints what ``--trace 1`` prints before its last line: the 15 scopes, the
10 registered ops and every named kernel with most device time, the
unscoped instructions, the
host/device clock bracket and the ten longest idle gaps with the span the host
was in.  (``profiler.set_config(xprof_dir=...)`` writes such a file for any
training loop: docs/observability.md.)

What is read.  The program names its instructions through
``jax.named_scope`` (``fuse.py``, ``gluon/block.py``,
``ops/pallas_kernels.dispatch``), and an instruction's ``op_name`` reads

    jit(step)/<phase>/<block keys ...>/jit(<op>)/<kernel>/<primitive>

with ``<phase>`` one of ``jvp(forward)``, ``transpose(jvp(forward))`` (the
backward pass: no scope of its own) and ``optimizer``; the block keys those a
block is registered under in its parent; ``<kernel>`` the name both sides of a
kernel's dispatch run under.  Known error: a fusion has one ``op_name``, its
root's, so an instruction XLA fused across two phases or two blocks counts
whole for the root's.

Three parts, tested apart (tests/chipbench/test_scope_reduce.py):

* ``read_trace(path)``: a thin reader, ``jax.profiler.ProfileData`` -> device
  operations ``Op(device, name, op_name, start, dur)`` and host spans
  ``Span(line, name, start, end)``, in integer nanoseconds;
* the arithmetic over such lists (``parse``, ``self_times``, ``by_scope``,
  ``span_self_times``, ``clock_bracket``, ``attribute_gaps`` ...), which needs
  no JAX;
* ``of_run(run)``: what a reader under ``layer_metrics/`` calls: finds the
  traced run's file, reduces it once a process, prints the tables, and
  returns None where the trace holds no device plane (a CPU rehearsal) or the
  program wrote no names (a parent of the PR that added them).
"""
import collections
import glob
import os
import re
import sys

if __name__ == "__main__":      # run as a file: the package is the parent's
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from chipbench import stats, trace_reduce  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))

Op = collections.namedtuple("Op", "device name op_name start dur")
Span = collections.namedtuple("Span", "line name start end")
Parsed = collections.namedtuple("Parsed", "phase blocks op kernel call")
NO_PHASE = Parsed(None, (), None, None, None)

# the names ``ops/pallas_kernels.kernel_name`` gives the kernels' wrappers
# (tests/test_step_scopes.py holds the two lists together)
KERNELS = ("layer_norm", "rms_norm", "softmax", "softmax_xent",
           "flash_attention", "matmul_bn", "conv3_bn")
PHASES = ("forward", "backward", "optimizer")
WRAPPER = re.compile(r"^(?:\w+\()+([^()]*)\)+$")     # jvp(forward) -> forward
SPAN_NAME = re.compile(r"^[a-z_]+\.[a-z_.]+$")       # layer.action
STEP_SITE = "fused_step:"
# the spans that begin a program's dispatch, innermost first
DISPATCH_SPANS = ("executor.call", "fused_step.call", "bench.dispatch")
WAIT_SPAN = "bench.wait"
MODULES_LINE = "XLA Modules"
DEPTH = 4


# ------------------------------------------------------------- the grammar

def parse(op_name):
    """An instruction's ``op_name`` -> its phase (or None), the tuple of
    block keys below the phase, the registered op it was traced in (the
    first ``jit(<op>)`` below the blocks, or None), the kernel whose scope
    it ran under (or None) and that kernel's own call (``layer_norm_fwd``: the
    ``pl.pallas_call``'s name, which stands before the primitive, below the
    ``cond/branch_0_fun`` of the dispatch's ``platform_dependent``).  Of the
    names an instruction merged from several carries, joined by ``;``, the
    first that has a phase counts."""
    for one in op_name.split(";"):
        parsed = _parse_one(one)
        if parsed.phase:
            return parsed
    return NO_PHASE


def _parse_one(op_name):
    segments = op_name.split("/")
    phase = at = None
    for i, seg in enumerate(segments):
        inner = WRAPPER.match(seg)
        core = inner.group(1) if inner else seg
        if core == "forward":
            phase = "backward" if "transpose(" in seg else "forward"
        elif core == "optimizer":
            phase = "optimizer"
        if phase:
            at = i
            break
    if phase is None:
        return NO_PHASE
    below = segments[at + 1:-1]         # the last segment is the primitive
    blocks = []
    for seg in below:
        if "(" in seg or seg in KERNELS:
            break
        blocks.append(seg)
    op = next((seg[4:-1] for seg in below[len(blocks):]
               if seg.startswith("jit(")), None)
    kernel = call = None
    for i, seg in enumerate(below[len(blocks):], len(blocks)):
        if seg in KERNELS:
            kernel = seg
            call = next((s for s in reversed(below[i + 1:])
                         if s.startswith(seg + "_")), None)
            break
    return Parsed(phase, tuple(blocks), op, kernel, call)


def scope_of(parsed, depth=DEPTH):
    """The phase and block path cut to ``depth`` segments."""
    return "/".join(((parsed.phase,) + parsed.blocks)[:depth])


def kernel_row(parsed):
    """The name a kernel's time is listed under: its own call's
    (``layer_norm_bwd``), else the kernel's with the phase it ran in."""
    if parsed.call:
        return parsed.call
    return parsed.kernel + ("_bwd" if parsed.phase == "backward" else "_fwd")


# -------------------------------------------------------------- the reader

# Where ``op_name`` sits (read by hand from a v5e trace, jax 0.9.0, PR 27):
# not in the event's name (the instruction's text, without its metadata) and
# not in the event's own stats, which are all ``jax.profiler.ProfileData``
# iterates (``device_offset_ps``, ``device_duration_ps``), but in the stats of
# the event's *metadata* (``XEventMetadata.stats``), as ``tf_op``, with a
# trailing ``:``, beside ``flops``, ``bytes_accessed``, ``hlo_category`` and
# ``source``.  So the map instruction text -> ``op_name`` is taken once a file
# from the protobuf's wire format (tsl/profiler/protobuf/xplane.proto):
#   XSpace:         planes = 1
#   XPlane:         name = 2, lines = 3 (skipped), event_metadata = 4,
#                   stat_metadata = 5   (maps: entry key = 1, value = 2)
#   XEventMetadata: name = 2, stats = 5
#   XStat:          metadata_id = 1, str_value = 5, ref_value = 7 (the id of
#                   a stat metadata whose name is the value)
#   XStatMetadata:  name = 2
OP_NAME_STAT = "tf_op"


def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: a varint as an
    int, a length-delimited field as a slice, fixed widths as slices."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"wire type {wire} in an xplane file")
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def _map_value(entry):
    return next((v for f, v in _fields(entry) if f == 2), b"")


def op_names(data):
    """``{plane name: {event name: op_name}}`` from an ``.xplane.pb``'s
    bytes: of every event metadata that carries the stat ``tf_op``."""
    out = {}
    for field, plane in _fields(memoryview(data)):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, value in _fields(plane):
            if f == 2:
                name = bytes(value).decode()
            elif f == 4:
                events.append(_map_value(value))
            elif f == 5:
                meta = dict(_fields(_map_value(value)))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        wanted = {i for i, n in stat_names.items() if n == OP_NAME_STAT}
        names = {}
        for event in events:
            event_name, op_name = "", None
            for f, value in _fields(event):
                if f == 2:
                    event_name = bytes(value).decode()
                elif f == 5:
                    stat = dict(_fields(value))
                    if stat.get(1) in wanted:
                        op_name = (bytes(stat[5]).decode() if 5 in stat
                                   else stat_names.get(stat.get(7), ""))
            if op_name is not None:
                names[event_name] = op_name.rstrip(":")
        if names:
            out[name] = names
    return out


def read_trace(path):
    """The file -> ``(ops, runs, spans)``: the device operations with their
    ``op_name`` ("" where the file has none), each device's program runs
    ``(device, name, start, end)`` from its line "XLA Modules", and the host
    plane's spans whose names are of the program's or the benchmark's
    vocabulary (``layer.action``)."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        data = f.read()
    names = op_names(data)
    ops, runs, spans = [], [], []
    for plane in ProfileData.from_serialized_xspace(data).planes:
        device = trace_reduce.DEVICE_PLANE.match(plane.name)
        if device:
            known = names.get(plane.name, {})
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    ops.extend(Op(int(device.group(1)), e.name,
                                  known.get(e.name, ""), int(e.start_ns),
                                  int(e.duration_ns)) for e in line.events)
                elif line.name == MODULES_LINE:
                    runs.extend((int(device.group(1)), e.name,
                                 int(e.start_ns),
                                 int(e.start_ns + e.duration_ns))
                                for e in line.events)
        elif plane.name == trace_reduce.HOST_PLANE:
            for line in plane.lines:
                spans.extend(Span(line.name, e.name, int(e.start_ns),
                                  int(e.start_ns + e.duration_ns))
                             for e in line.events if SPAN_NAME.match(e.name))
    return ops, runs, spans


# ------------------------------------------------------------ the device

def self_times(ops):
    """``(op, self time)`` for one device's operations: an operation's
    duration less that of the operations nested in it on the same line (a
    ``while`` and its body's instructions are both events), so that the self
    times sum to the union of the intervals, the device's busy time."""
    out, stack = [], []          # stack: [op, end, time of its children]
    for op in sorted(ops, key=lambda o: (o.start, -o.dur)):
        while stack and stack[-1][1] <= op.start:
            done = stack.pop()
            out.append((done[0], done[0].dur - done[2]))
        if stack:
            stack[-1][2] += op.dur
        stack.append([op, op.start + op.dur, 0])
    out.extend((done[0], done[0].dur - done[2]) for done in stack)
    return out


def by_scope(ops):
    """One device's operations -> nanoseconds by phase, by scope (phase and
    block path cut to ``DEPTH``), by phase and registered op, by kernel row
    (all of it, and the part in Mosaic custom calls), of instructions with no
    phase by family, and the time that resolves to a phase and at least one
    block or kernel segment."""
    out = {"busy": 0, "covered": 0, "phase": collections.Counter(),
           "scope": collections.Counter(), "op": collections.Counter(),
           "kernel": collections.Counter(),
           "kernel_pallas": collections.Counter(),
           "unscoped": collections.Counter()}
    for op, ns in self_times(ops):
        parsed = parse(op.op_name)
        out["busy"] += ns
        if parsed.phase is None:
            out["unscoped"][trace_reduce.family(op.name)] += ns
            continue
        out["phase"][parsed.phase] += ns
        out["scope"][scope_of(parsed)] += ns
        out["op"][f"{parsed.phase} {parsed.op or '(no op)'}"] += ns
        if parsed.blocks or parsed.kernel:
            out["covered"] += ns
        if parsed.kernel:
            out["kernel"][kernel_row(parsed)] += ns
            if trace_reduce.is_pallas(op.name):
                out["kernel_pallas"][kernel_row(parsed)] += ns
    return out


# -------------------------------------------------------------- the host

def span_self_times(spans, parent, child):
    """For each ``parent`` span, its duration less the part that ``child``
    spans of the same line inside it cover: the parent's own time."""
    out = []
    for p in (s for s in spans if s.name == parent):
        inside = trace_reduce.union(
            (max(c.start, p.start), min(c.end, p.end)) for c in spans
            if c.name == child and c.line == p.line
            and c.start < p.end and c.end > p.start)
        out.append(p.end - p.start - trace_reduce.total(inside))
    return out


def clock_bracket(runs, dispatches, waits):
    """The bracket ``(low, high)`` of the offset device clock - host clock,
    from one device's runs of the step's program ``(start, end)``, the host
    spans that began each run's dispatch and the host spans that waited for
    each run's result, all in order.  A program cannot start on the device
    before the host began to dispatch it (``high``: the least of the runs'
    start - their dispatch's start, tightest at the first traced step, which
    finds the pipeline empty), and ``block_until_ready`` cannot return before
    the program it waits for ended on the device (``low``: the greatest of
    the runs' end - their wait's end, tight wherever the host really
    blocked).  None where the spans do not match the runs one to one."""
    if not runs or not len(dispatches) == len(waits) == len(runs):
        return None
    high = min(r[0] - d.start for r, d in zip(runs, dispatches))
    low = max(r[1] - w.end for r, w in zip(runs, waits))
    return low, high


def innermost_span(point, spans):
    """The name of the shortest span that holds the point, or "none"."""
    holding = [s for s in spans if s.start <= point <= s.end]
    return min(holding, key=lambda s: s.end - s.start).name \
        if holding else "none"


def attribute_gaps(gaps, spans, bracket):
    """The ten longest of a device's idle gaps, each with the innermost host
    span over its middle once the host's spans are moved onto the device's
    clock by the bracket's midpoint.  A gap shorter than the bracket is
    wide cannot be placed: ``unattributed (< clock bracket)``; with no
    bracket none can."""
    out = []
    for start, end in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        if bracket is None:
            name = "unattributed (no clock bracket)"
        elif end - start < bracket[1] - bracket[0]:
            name = "unattributed (< clock bracket)"
        else:
            shift = (bracket[0] + bracket[1]) // 2
            name = innermost_span((start + end) // 2 - shift, spans)
        out.append((name, end - start))
    return out


# ------------------------------------------------- the whole reduction

def main_program(runs):
    """One device's runs ``(start, end)`` of the program with most device
    time (the step; the key split's programs are microseconds), in order."""
    time_of = collections.Counter()
    for _, name, start, end in runs:
        time_of[name] += end - start
    if not time_of:
        return []
    step = time_of.most_common(1)[0][0]
    return sorted((start, end) for _, name, start, end in runs
                  if name == step)


def reduce_trace(ops, runs, spans, steps):
    """Everything the readers and the printed tables take, in milliseconds a
    step (means over the devices) or nanoseconds (the clock), from what
    ``read_trace`` returns.  None where there is no device operation."""
    by_device = collections.defaultdict(list)
    for op in ops:
        by_device[op.device].append(op)
    if not by_device:
        return None
    per = {d: by_scope(mine) for d, mine in by_device.items()}
    n = len(per)

    def ms(ns):
        return ns / 1e6 / steps / n

    def summed(key):
        total = collections.Counter()
        for r in per.values():
            total.update(r[key])
        return total

    busy = sum(r["busy"] for r in per.values())
    phase, kernel = summed("phase"), summed("kernel")
    pallas = summed("kernel_pallas")
    out = {
        "steps": steps, "devices": n, "busy_ms": ms(busy),
        # no instruction with a phase: the program wrote no scopes
        "phase_ms": ({p: ms(phase[p]) for p in PHASES} if phase else None),
        "unscoped_ms": ms(sum(summed("unscoped").values())),
        "coverage": (sum(r["covered"] for r in per.values()) / busy
                     if phase else None),
        "scopes": [(k, ms(v)) for k, v in summed("scope").most_common(15)],
        "ops": [(k, ms(v)) for k, v in summed("op").most_common(10)],
        "kernels": [(k, ms(v), ms(pallas[k]))
                    for k, v in kernel.most_common()],
        "unscoped": [(k, ms(v))
                     for k, v in summed("unscoped").most_common(10)],
    }
    own = span_self_times(spans, "fused_step.call", "executor.call")
    out["call_self_ms"] = stats.median(own) / 1e6 if own else None
    # the clock and the gaps, on the device that idles most
    busy_at = {d: trace_reduce.union(trace_reduce.intervals_of(mine))
               for d, mine in by_device.items()}
    worst = max(per, key=lambda d: 1 - per[d]["busy"] / (
        busy_at[d][-1][1] - busy_at[d][0][0]))
    busy_at = busy_at[worst]
    program = main_program([r for r in runs if r[0] == worst])
    by_name = collections.defaultdict(list)
    for span in sorted(spans, key=lambda s: s.start):
        by_name[span.name].append(span)
    dispatches = next((by_name[name] for name in DISPATCH_SPANS
                       if len(by_name[name]) == len(program)), [])
    out["dispatch_span"] = dispatches[0].name if dispatches else None
    out["bracket_ns"] = clock_bracket(program, dispatches,
                                      by_name[WAIT_SPAN])
    out["gaps"] = attribute_gaps(
        trace_reduce.gaps(busy_at, busy_at[0][0], busy_at[-1][1]), spans,
        out["bracket_ns"])
    out["device"] = worst
    return out


def report(reduced, say=print):
    """The tables a traced run prints before its last line."""
    busy, steps = reduced["busy_ms"], reduced["steps"]

    def share(v):
        return 100 * v / busy if busy else 0.0

    if reduced["phase_ms"] is None:
        say("[scope] no instruction carries a phase: the program under "
            "test wrote no scopes; every metric read from them is left out")
    else:
        phases = "  ".join(f"{p} {v:.3f}"
                           for p, v in reduced["phase_ms"].items())
        say(f"[scope] {busy:.3f} ms busy a step over {steps} steps on "
            f"{reduced['devices']} device(s): {phases}  unscoped "
            f"{reduced['unscoped_ms']:.3f} ms; "
            f"{100 * reduced['coverage']:.2f} % resolves to a phase and a "
            "block or kernel")
        for name, v in reduced["scopes"]:
            say(f"[scope]   {v:8.3f} ms a step {share(v):5.1f} %  {name}")
        for name, v in reduced["ops"]:
            say(f"[scope]   op {v:8.3f} ms a step {share(v):5.1f} %  {name}")
        for name, v, pallas in reduced["kernels"]:
            say(f"[scope]   kernel {v:8.3f} ms a step {share(v):5.2f} %  "
                f"{name}  ({pallas:.3f} ms in Mosaic custom calls)")
        for name, v in reduced["unscoped"]:
            say(f"[scope]   unscoped {v:8.3f} ms a step {share(v):5.2f} %  "
                f"{name}")
    if reduced["call_self_ms"] is not None:
        say(f"[span] fused_step.call less its executor.call: median "
            f"{reduced['call_self_ms']:.3f} ms")
    bracket = reduced["bracket_ns"]
    if bracket is None:
        say("[clock] no bracket: the host's dispatch and wait spans do not "
            "match the device's runs of the step one to one")
    else:
        say(f"[clock] device {reduced['device']} clock - host clock between "
            f"{bracket[0] / 1e6:.3f} and {bracket[1] / 1e6:.3f} ms "
            f"({(bracket[1] - bracket[0]) / 1e6:.3f} ms wide; lower bound "
            f"from {WAIT_SPAN}, upper from {reduced['dispatch_span']}); "
            "host spans moved by the midpoint")
    for name, ns in reduced["gaps"]:
        say(f"[clock]   idle gap {ns / 1e6:8.3f} ms  {name}")


_REDUCED = {}        # path -> the reduction, once a process


def of_run(run, say=print):
    """The reduction of the traced run's own file, found under
    ``chipbench/out/<cell>/``; printed the first time.  None where the run
    was not traced or its trace holds no device operation."""
    if not run.get("trace"):
        return None
    found = glob.glob(os.path.join(HERE, "out", run["cell"]["name"], "**",
                                   "*.xplane.pb"), recursive=True)
    if not found:
        return None
    path = max(found, key=os.path.getmtime)
    if path not in _REDUCED:
        _REDUCED[path] = reduce_trace(
            *read_trace(path), steps=run["cell"]["traffic"]["trace_steps"])
        if _REDUCED[path] is not None:
            report(_REDUCED[path], say)
    return _REDUCED[path]


# --------------------------------------------------- the compile counters

def program_compile_log():
    """The program's record of its compiles (one dict a compile: ``site``,
    ``at`` on ``time.perf_counter()``, ``trace_s``, ``lower_s``,
    ``backend_compile_s`` ...), or None where the program keeps none."""
    try:
        from incubator_mxnet_tpu import executor_cache
    except ImportError:
        return None
    log = getattr(executor_cache, "compile_log", None)
    return log() if log else None


def compile_seconds(log, until, step, fields):
    """Seconds summed over ``fields`` of the compiles that ended by the
    host time ``until``: those of the fused train step (``step`` true: the
    records whose site starts with ``STEP_SITE``) or of every other jitted
    function.  None where there is no log."""
    if log is None:
        return None
    return sum(r[f] for r in log for f in fields
               if r["at"] <= until
               and (r.get("site") or "").startswith(STEP_SITE) == step)


if __name__ == "__main__":
    reduced = reduce_trace(*read_trace(sys.argv[1]),
                           steps=int(sys.argv[2]) if len(sys.argv) > 2 else 1)
    if reduced is None:
        print("no device plane with operations in the trace")
    else:
        report(reduced)
