"""chipbench/scope_reduce.py on the CPU: the grammar of an instruction's
``op_name``, the device-time arithmetic, span self time and the host/device
clock bracket against hand-computed values; the wire-format reader and every
reader of a metric this file's PR added on a handmade ``.xplane.pb``; and the
new manifest entries against their readers and ``PERF.md``'s layers.
"""
import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from chipbench import run as harness  # noqa: E402
from chipbench import scope_reduce as sr  # noqa: E402

NEW = ("phase_ms.forward", "phase_ms.backward", "phase_ms.optimizer",
       "scope_coverage", "step_call_self_ms.train", "jit_trace_s.train",
       "backend_compile_s.train", "eager_compile_s.setup")

# op_names as a v5e trace of the step holds them (my chip run, PR 27)
CONV = ("jit(step)/jvp(forward)/features/4/0/body/0/jit(convolution)/"
        "conv_general_dilated")
CONV_DW = ("jit(step)/transpose(jvp(forward))/features/4/0/body/0/"
           "jit(convolution)/conv_general_dilated")
LN_FWD = ("jit(step)/jvp(forward)/encoder/layer9/ln1/jit(layer_norm)/"
          "layer_norm/cond/branch_0_fun/layer_norm_fwd/pallas_call")
LN_BWD = ("jit(step)/transpose(jvp(forward))/embed_ln/jit(layer_norm)/"
          "layer_norm/cond/layer_norm/cond/branch_0_fun/layer_norm_bwd/"
          "pallas_call")
XENT_XLA = ("jit(step)/jvp(forward)/loss/jit(softmax_xent)/softmax_xent/"
            "jit(log_softmax)/reduce_max")
XENT_BWD = ("jit(step)/transpose(jvp(forward))/loss/jit(softmax_xent)/"
            "softmax_xent/cond/jit(step)/transpose(jvp(forward))/loss/"
            "jit(softmax_xent)/softmax_xent/cond/branch_0_fun/"
            "softmax_xent_bwd/pallas_call")
ADAM = "jit(step)/optimizer/sub"
ROOT = "jit(step)/jvp(forward)/jit(take)/gather"
SPLIT = "jit(_threefry_split)/add"
SCAN = "jit(loop)/while/body/jvp(forward)/features/0/jit(convolution)/conv"


# ------------------------------------------------------------- the grammar

@pytest.mark.parametrize("op_name,phase,blocks,op,kernel,call", [
    (CONV, "forward", ("features", "4", "0", "body", "0"), "convolution",
     None, None),
    (CONV_DW, "backward", ("features", "4", "0", "body", "0"), "convolution",
     None, None),
    (LN_FWD, "forward", ("encoder", "layer9", "ln1"), "layer_norm",
     "layer_norm", "layer_norm_fwd"),
    (LN_BWD, "backward", ("embed_ln",), "layer_norm", "layer_norm",
     "layer_norm_bwd"),
    (XENT_XLA, "forward", ("loss",), "softmax_xent", "softmax_xent", None),
    (XENT_BWD, "backward", ("loss",), "softmax_xent", "softmax_xent",
     "softmax_xent_bwd"),
    (ADAM, "optimizer", (), None, None, None),
    (ROOT, "forward", (), "take", None, None),
    (SPLIT, None, (), None, None, None),
    ("", None, (), None, None, None),
    (SCAN, "forward", ("features", "0"), "convolution", None, None),
    # of the names an instruction merged from several, the first with a phase
    (SPLIT + ";" + ADAM, "optimizer", (), None, None, None),
    # interpret mode: the kernel's body is unrolled below its call's name
    ("jit(step)/jvp(forward)/ln1/jit(layer_norm)/layer_norm/layer_norm_fwd/"
     "while/body/add", "forward", ("ln1",), "layer_norm", "layer_norm",
     "layer_norm_fwd"),
])
def test_an_op_name_is_parsed_into_phase_blocks_op_and_kernel(
        op_name, phase, blocks, op, kernel, call):
    assert sr.parse(op_name) == sr.Parsed(phase, blocks, op, kernel, call)


def test_scope_is_cut_to_depth_and_kernels_are_listed_by_call_or_phase():
    assert sr.scope_of(sr.parse(CONV)) == "forward/features/4/0"
    assert sr.scope_of(sr.parse(CONV_DW), depth=2) == "backward/features"
    assert sr.scope_of(sr.parse(ADAM)) == "optimizer"
    assert sr.kernel_row(sr.parse(LN_BWD)) == "layer_norm_bwd"
    # the XLA side of a dispatch is listed under the kernel's name too
    assert sr.kernel_row(sr.parse(XENT_XLA)) == "softmax_xent_fwd"
    assert sr.kernel_row(sr.parse(XENT_XLA.replace(
        "jvp(forward)", "transpose(jvp(forward))"))) == "softmax_xent_bwd"


# -------------------------------------------------- the device arithmetic

PALLAS = ('%layer_norm_fwd.3 = bf16[8,128]{1,0} custom-call(bf16[8,128] %p), '
          'custom_call_target="tpu_custom_call"')


def _ops(device=0):
    """Two steps' worth: 100 forward conv, 40 forward LayerNorm kernel, 200
    backward, a 30 ns while whose 10 + 10 body instructions are events too,
    60 optimizer, 20 of a copy-done without a name, 50 forward at the root."""
    op = lambda name, op_name, start, dur: sr.Op(  # noqa: E731
        device, name, op_name, start, dur)
    return [
        op("%fusion.1 = f32[8] fusion(...)", CONV, 0, 100),
        op(PALLAS, LN_FWD, 100, 40),
        op("%fusion.2 = f32[8] fusion(...)", CONV_DW, 150, 200),
        op("%while.1 = (s32[]) while(...)", ADAM, 400, 30),
        op("%add.5 = f32[8] add(...)", ADAM, 405, 10),
        op("%add.6 = f32[8] add(...)", ADAM, 415, 10),
        op("%fusion.9 = f32[8] fusion(...)", ADAM, 430, 30),
        op("%copy-done.7 = f32[8] copy-done(...)", "", 470, 20),
        op("%fusion.4 = f32[8] fusion(...)", ROOT, 500, 50),
    ]


def test_self_times_sum_to_the_union_of_the_intervals():
    selfs = {op.name.split(" ")[0]: ns for op, ns in sr.self_times(_ops())}
    assert selfs["%while.1"] == 10 and selfs["%add.5"] == 10
    assert sum(selfs.values()) == 100 + 40 + 200 + 30 + 30 + 20 + 50


def test_device_time_by_phase_scope_and_kernel():
    r = sr.by_scope(_ops())
    assert r["busy"] == 470
    assert r["phase"] == {"forward": 190, "backward": 200, "optimizer": 60}
    assert r["unscoped"] == {"copy-done": 20}
    assert sum(r["phase"].values()) + sum(r["unscoped"].values()) == r["busy"]
    # a phase alone (the optimizer, the root block's own ops) is not covered
    assert r["covered"] == 100 + 40 + 200
    assert r["scope"]["forward/features/4/0"] == 100
    assert r["scope"]["forward/encoder/layer9/ln1"] == 40
    assert r["scope"]["forward"] == 50 and r["scope"]["optimizer"] == 60
    assert r["op"] == {"forward convolution": 100, "forward layer_norm": 40,
                       "backward convolution": 200, "forward take": 50,
                       "optimizer (no op)": 60}
    assert r["kernel"] == {"layer_norm_fwd": 40}
    assert r["kernel_pallas"] == {"layer_norm_fwd": 40}


# ------------------------------------------------------- spans, the clock

def test_span_self_time_is_the_parent_less_its_children():
    S = sr.Span
    spans = [S("python3", "fused_step.call", 0, 100),
             S("python3", "fused_step.key_split", 5, 30),
             S("python3", "executor.call", 40, 95),
             S("python3", "fused_step.call", 200, 260),
             S("python3", "executor.call", 230, 300),     # cut to the parent
             S("worker", "executor.call", 0, 100)]        # another thread's
    assert sr.span_self_times(spans, "fused_step.call", "executor.call") == \
        [45, 30]
    assert sr.span_self_times(spans, "train.chunk", "executor.call") == []


def test_clock_bracket_from_dispatch_and_wait():
    S = sr.Span
    # the device's clock runs 1,000 behind the host's: offset -1000
    runs = [(5_200, 9_000), (9_010, 13_000)]
    dispatches = [S("python3", "executor.call", 6_000, 6_500),
                  S("python3", "executor.call", 6_600, 7_000)]
    waits = [S("python3", "bench.wait", 7_000, 10_300),
             S("python3", "bench.wait", 10_400, 14_050)]
    # high: min(5200 - 6000, 9010 - 6600); low: max(9000 - 10300, 13000 - 14050)
    assert sr.clock_bracket(runs, dispatches, waits) == (-1_050, -800)
    assert sr.clock_bracket(runs, dispatches[:1], waits) is None
    assert sr.clock_bracket(runs, dispatches, waits[:1]) is None
    assert sr.clock_bracket([], [], []) is None


def test_gaps_are_attributed_to_the_innermost_span_or_not_at_all():
    S = sr.Span
    spans = [S("python3", "bench.dispatch", 1_000, 9_000),
             S("python3", "fused_step.call", 1_100, 8_900),
             S("python3", "fused_step.key_split", 1_200, 3_000),
             S("python3", "bench.wait", 9_000, 20_000)]
    bracket = (-1_050, -800)              # 250 wide; host moved by -925
    gaps = [(1_000, 2_000), (10_000, 10_100), (14_000, 16_000),
            (50_000, 51_000)]
    assert sr.attribute_gaps(gaps, spans, bracket) == [
        ("bench.wait", 2_000),                      # middle 15000 -> 15925
        ("fused_step.key_split", 1_000),            # middle 1500 -> 2425
        ("none", 1_000),
        ("unattributed (< clock bracket)", 100)]
    assert sr.attribute_gaps(gaps[:1], spans, None) == [
        ("unattributed (no clock bracket)", 1_000)]
    assert sr.innermost_span(5_000, spans) == "fused_step.call"


# ---------------------------------------------------- a handmade .xplane.pb

def _varint(n):
    out = b""
    while True:
        out += bytes([n & 0x7F | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _f(number, value):
    """One protobuf field: an int as a varint, bytes or text delimited."""
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _plane(name, lines, with_names=True):
    """``lines``: ``{line name: [(event name, op_name, start ns, dur ns)]}``;
    every distinct event name is one event metadata, whose stat ``tf_op``
    (stat metadata 7) holds the op_name with the trailing colon of a trace."""
    ids, body = {}, _f(2, name)
    for line, events in lines.items():
        packed = b""
        for event, op_name, start, dur in events:
            if event not in ids:
                ids[event] = (len(ids) + 1, op_name)
            packed += _f(4, _f(1, ids[event][0]) + _f(2, start * 1000)
                         + _f(3, dur * 1000))
        body += _f(3, _f(2, line) + packed)
    for event, (i, op_name) in ids.items():
        stat = _f(5, _f(1, 7) + _f(5, op_name + ":")) \
            if with_names and op_name else b""
        body += _f(4, _f(1, i) + _f(2, _f(1, i) + _f(2, event) + stat))
    body += _f(5, _f(1, 7) + _f(2, _f(1, 7) + _f(2, "tf_op")))
    body += _f(5, _f(1, 8) + _f(2, _f(1, 8) + _f(2, "flops")))
    return _f(1, body)


def _xspace(with_device=True, with_names=True, program_spans=True):
    """Two traced steps of 500 ns each on one device, 1,000 ns apart, and
    the host's side of them 1,000 ns later by its own clock."""
    ops, runs, host = [], [], []
    for k in (0, 1):
        t = 10_000 + 1_000 * k
        ops += [(o.name, o.op_name, t + o.start, o.dur) for o in _ops()]
        runs.append(("jit_step(123)", "", t, 550))
        runs.append(("jit__threefry_split(9)", "", t - 40, 5))
        h = t + 1_000 - 300                 # dispatch begins 300 before
        host.append(("bench.dispatch", "", h - 150, 400))
        if program_spans:
            host += [("fused_step.call", "", h - 100, 300),
                     ("fused_step.key_split", "", h - 90, 60),
                     ("executor.call", "", h, 180),
                     ("PjitFunction(step)", "", h + 5, 170)]
        host.append(("bench.wait", "", t + 1_000 + 300, 320))
    planes = _plane("/host:CPU", {"python3": host})
    if with_device:
        planes += _plane("/device:TPU:0", {"XLA Ops": ops,
                                           "XLA Modules": runs},
                         with_names=with_names)
    return planes


def test_op_names_come_from_the_event_metadatas_stat():
    names = sr.op_names(_xspace())
    assert set(names) == {"/device:TPU:0"}
    assert names["/device:TPU:0"][PALLAS] == LN_FWD          # colon stripped
    assert "%copy-done.7 = f32[8] copy-done(...)" not in names["/device:TPU:0"]
    assert sr.op_names(_xspace(with_names=False)) == {}
    assert sr.op_names(b"") == {}


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """A run whose trace is the handmade file, where ``of_run`` looks."""
    def make(**how):
        out = tmp_path / "out" / "handmade" / "plugins" / "profile" / "t"
        out.mkdir(parents=True, exist_ok=True)
        (out / "vm.xplane.pb").write_bytes(_xspace(**how))
        monkeypatch.setattr(sr, "HERE", str(tmp_path))
        monkeypatch.setattr(sr, "_REDUCED", {})
        return {"cell": {"name": "handmade", "traffic": {"trace_steps": 2}},
                "trace": {"steps": 2}, "window_open": 100.0}
    return make


def _read(name, run):
    return harness.load_module(os.path.join(
        REPO, "chipbench", "layer_metrics", name + ".py")).read(run)


def test_trace_readers_on_a_handmade_file(traced, capsys):
    run = traced()
    # 2 steps x the handmade operations, a step: forward 190, backward 200 ...
    assert _read("phase_ms.forward", run) == pytest.approx(190e-6)
    assert _read("phase_ms.backward", run) == pytest.approx(200e-6)
    assert _read("phase_ms.optimizer", run) == pytest.approx(60e-6)
    assert _read("scope_coverage", run) == pytest.approx(100 * 340 / 470)
    # fused_step.call 300 less its executor.call 180
    assert _read("step_call_self_ms.train", run) == pytest.approx(120e-6)
    out = capsys.readouterr().out
    # reduced and printed once: 1 head, 5 scopes, 5 ops, 1 kernel, 1 unscoped
    assert out.count("[scope] ") == 1 + 5 + 5 + 1 + 1
    assert "forward 0.000  backward 0.000" in out and "72.34 %" in out
    assert "kernel" in out and "layer_norm_fwd" in out
    assert "branch_0_fun" not in out
    assert "unscoped" in out and "copy-done" in out
    # offset -1000: high = 10000 - 10700, low = 10550 - 11620
    assert ("clock - host clock between -0.001 and -0.001 ms (0.000 ms wide; "
            "lower bound from bench.wait, upper from executor.call)") in out
    reduced = sr.of_run(run)
    assert reduced["bracket_ns"] == (-1_070, -700)
    assert reduced["busy_ms"] == pytest.approx(470e-6)
    # the 450 ns between the two steps' operations: wider than the bracket;
    # its middle, 10775 on the device, is 11660 on the host, where the second
    # step's key split (11610 to 11670) is the innermost span
    assert reduced["gaps"][0] == ("fused_step.key_split", 450)
    assert reduced["gaps"][1][0] == "unattributed (< clock bracket)"


def test_trace_readers_return_none_where_there_is_nothing_to_read(traced):
    trace_metrics = NEW[:5]
    untraced = dict(traced(), trace=None)
    assert [_read(m, untraced) for m in trace_metrics] == [None] * 5
    # a CPU rehearsal: no device plane (the runner's reduction is None too)
    no_device = traced(with_device=False)
    assert sr.of_run(no_device) is None
    assert [_read(m, no_device) for m in trace_metrics] == [None] * 5
    # a program that writes no scopes and opens no spans: the parent of the
    # PR that added them, under this PR's benchmark files
    parent = traced(with_names=False, program_spans=False)
    assert [_read(m, parent) for m in trace_metrics] == [None] * 5
    reduced = sr.of_run(parent)
    assert reduced["busy_ms"] == pytest.approx(470e-6)
    assert reduced["dispatch_span"] == "bench.dispatch"
    assert reduced["bracket_ns"] == (-1_070, -550)


LOG = [
    {"site": None, "fun": "convolution", "at": 50.0, "trace_s": 0.01,
     "lower_s": 0.02, "backend_compile_s": 0.3},
    {"site": "fused_step:ResNetV1", "fun": "step", "at": 90.0,
     "trace_s": 2.0, "lower_s": 0.5, "backend_compile_s": 40.0},
    {"site": "op:add", "fun": "add", "at": 95.0, "trace_s": 0.001,
     "lower_s": 0.002, "backend_compile_s": 0.03},
    # after the window opened: the reference check's own steps and ops
    {"site": "fused_step:ResNetV1", "fun": "step", "at": 150.0,
     "trace_s": 1.0, "lower_s": 0.25, "backend_compile_s": 9.0},
    {"site": None, "fun": "convolution", "at": 151.0, "trace_s": 0.01,
     "lower_s": 0.02, "backend_compile_s": 0.3},
]


def test_counter_readers_cut_the_compile_log_at_the_windows_opening(
        monkeypatch):
    run = {"window_open": 100.0}
    monkeypatch.setattr(sr, "program_compile_log", lambda: LOG)
    assert _read("jit_trace_s.train", run) == pytest.approx(2.5)
    assert _read("backend_compile_s.train", run) == pytest.approx(40.0)
    assert _read("eager_compile_s.setup", run) == pytest.approx(0.363)
    # a program that keeps no such log: nothing to read
    monkeypatch.setattr(sr, "program_compile_log", lambda: None)
    assert [_read(m, run) for m in NEW[5:]] == [None] * 3


def test_the_programs_compile_log_is_found_and_holds_the_fields():
    log = sr.program_compile_log()
    assert isinstance(log, list)
    for record in log:
        assert {"site", "at", "trace_s", "lower_s",
                "backend_compile_s"} <= set(record)


# ------------------------------------------------------------ the manifest

def test_every_new_entry_finds_its_reader_and_names_a_layer_of_perf_md():
    manifest = harness.Manifest(os.path.join(REPO, "BENCHMARK.json"))
    entries = {m["name"]: m for m in manifest.data["per_layer"]}
    assert [m["name"] for m in manifest.data["per_layer"]][-8:] == list(NEW)
    with open(os.path.join(REPO, "PERF.md")) as f:
        section = f.read().split("## 3. Layers")[1].split("\n## ")[0]
    layers = set(re.findall(r"^\| ([a-z][a-z ]+?) \|", section, re.M))
    assert {"entry points", "jit choke point", "model step",
            "ops and kernels"} <= layers
    for name in NEW:
        entry = entries[name]
        assert "workloads" not in entry          # every cell reports it
        assert entry["layer"] in layers
        reader = harness.load_module(
            manifest.find("layer_metrics", name + ".py"))
        assert callable(reader.read)
        doc = " ".join(reader.__doc__.split())
        assert f"Layer: {entry['layer']}." in doc
        assert "Source: " + entry["source"].replace("_", " ") + "." in doc
        # PERF.md names the metric in its layer's row
        assert f"`{name}`" in section
    json.dumps(manifest.data)
