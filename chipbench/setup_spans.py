"""Where set-up's seconds go: the program's process trace, cut at the window.

The program records what happens once a process, once a net or once a compiled
signature as *process spans* (``incubator_mxnet_tpu/trace.py``): always, with
sampling off and no profiler session, on ``time.perf_counter()``'s clock, which
is the clock of the run's ``process_start`` and ``window_open``.  This file asks
the program for them as ``scope_reduce.program_compile_log()`` asks for the
compile log, cuts them to set-up (``process_start`` to ``window_open``) and
gives each ``*.setup`` reader under ``layer_metrics/`` its seconds.  A program
that keeps no process trace reads None and every such metric is left out.

All arithmetic is on unions of intervals (``trace_reduce``'s), so that a leaf
initialised inside the eager pass is counted once, a span that straddles the
window's opening counts up to it, and a difference can never be negative:

    import_s            process.import
    param_init_s        the union of gluon.param_init
    first_forward_s     gluon.first_forward less the gluon.param_init and
                        jit.compile time inside it: the eager pass itself
    amp_convert_s       amp.convert_block
    step_build_s        fused_step.build, whole
    state_place_s       its child fused_step.place
    first_call_rest_s   fused_step.first_call less the step's own jit.compile
                        (trace + lower + backend compile)
    coverage            the union of every process span over set-up

The phases but ``state_place_s`` (a part of ``step_build_s``) are disjoint in
time, so they sum to no more than ``setup_s``.
"""
from chipbench import scope_reduce
from chipbench.trace_reduce import gaps, subtract, total, union

PHASES = (
    # key, the spans it is the union of, the spans taken out of it
    ("import_s", ("process.import",), ()),
    ("param_init_s", ("gluon.param_init",), ()),
    ("first_forward_s", ("gluon.first_forward",),
     ("gluon.param_init", "jit.compile")),
    ("amp_convert_s", ("amp.convert_block",), ()),
    ("step_build_s", ("fused_step.build",), ()),
    ("state_place_s", ("fused_step.place",), ()),
    ("first_call_rest_s", ("fused_step.first_call",), ("jit.compile.step",)),
)
_REDUCED = {}


def program_process_spans():
    """The program's process trace as plain records (``name``, ``parent``,
    ``t0`` and ``t1`` on ``time.perf_counter()``, ``args``), or None where the
    program keeps none."""
    try:
        from incubator_mxnet_tpu import trace
    except ImportError:
        return None
    spans = getattr(trace, "process_spans", None)
    return spans() if spans else None


def cut(spans, since, until):
    """``name -> intervals`` of the spans, each cut to ``since``..``until``;
    the fused step's own compiles also under ``jit.compile.step``."""
    by_name = {}
    for s in spans:
        a, b = max(s["t0"], since), min(s["t1"], until)
        if b <= a:
            continue
        by_name.setdefault(s["name"], []).append((a, b))
        if s["name"] == "jit.compile" and (s["args"].get("site") or "") \
                .startswith(scope_reduce.STEP_SITE):
            by_name.setdefault("jit.compile.step", []).append((a, b))
    return by_name


# ------------------------------------------------------------ the reduction

def reduce_spans(spans, since, until):
    """Seconds of each phase of set-up and the share of it that any process
    span covers, from ``spans`` cut to ``since``..``until``; None where there
    is no process trace."""
    if spans is None:
        return None
    by_name = cut(spans, since, until)
    out = {}
    for key, names, less in PHASES:
        whole = union(i for n in names for i in by_name.get(n, ()))
        taken = union(i for n in less for i in by_name.get(n, ()))
        out[key] = total(subtract(whole, taken))
    covered = union(i for n, v in by_name.items()
                    if n != "jit.compile.step" for i in v)
    out["covered_s"] = total(covered)
    out["setup_s"] = until - since
    out["coverage"] = out["covered_s"] / out["setup_s"] if until > since \
        else None
    out["gaps"] = sorted(((b - a, a - since)
                          for a, b in gaps(covered, since, until)),
                         reverse=True)
    out["by_name"] = {
        n: (len(v), total(union(v)), min(a for a, _ in v) - since,
            max(b for _, b in v) - since) for n, v in by_name.items()}
    return out


def report(reduced, say=print):
    """The tables a traced run prints before its last line."""
    say(f"[setup] {reduced['setup_s']:.3f} s of set-up, "
        f"{reduced['covered_s']:.3f} s under a process span")
    say("[setup]    count   seconds  first..last (s after process start)")
    for name, (count, seconds, first, last) in sorted(
            reduced["by_name"].items(), key=lambda kv: kv[1][2]):
        say(f"[setup] {count:8d} {seconds:9.3f}  {first:8.3f}..{last:8.3f}"
            f"  {name}")
    for seconds, at in reduced["gaps"][:6]:
        say(f"[setup]   under no span: {seconds:8.3f} s from {at:8.3f} s")


def of_run(run, say=print):
    """The reduction of this run's set-up, ``process_start`` to
    ``window_open``; printed the first time.  None where the program keeps no
    process trace."""
    key = (run["process_start"], run["window_open"])
    if key not in _REDUCED:
        _REDUCED[key] = reduce_spans(program_process_spans(), *key)
        if _REDUCED[key] is not None:
            report(_REDUCED[key], say)
    return _REDUCED[key]


def seconds(run, key):
    """One phase's seconds for its reader, or None."""
    reduced = of_run(run)
    return None if reduced is None else reduced[key]
