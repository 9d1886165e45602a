"""Set-up's process trace through the benchmark's readers, on the CPU: the toy
cells of ``tests/chipbench/toy/`` (reused by path) under this directory's
manifest, which lists the eight metrics that read the trace; the readers'
arithmetic on handmade span lists -- the union, the cut at the window's
opening, what is taken out of the eager pass and out of the first call; a
program that keeps no process trace; and the real manifest's new entries.  The
manifest and ``rehearse.py`` of this directory stand beside those of
``tests/chipbench/toy/`` and the other toy directories, which are not edited."""
import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, REPO)

from chipbench import run as harness, setup_spans  # noqa: E402

DURATIONS = ["import_s.setup", "param_init_s.setup", "first_forward_s.setup",
             "amp_convert_s.setup", "step_build_s.setup",
             "state_place_s.setup", "first_call_rest_s.train"]
NEW = DURATIONS + ["setup_span_coverage"]
KEYS = dict(zip(DURATIONS, (k for k, _, _ in setup_spans.PHASES)))
CELLS = ["toy-resnet-train", "toy-bert-mlm"]


@pytest.fixture(scope="module", params=CELLS)
def traced(request):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse.py"), "--workload",
         request.param, "--seed", "3000000019", "--seconds", "1", "--trace",
         "1"], env=env, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


def test_traced_rehearsal_prints_all_eight_metrics(traced):
    line, out = traced
    assert line["correct"] is True, out[-3000:]
    assert set(NEW) <= set(line["metrics"])
    for name in NEW:
        assert f"[metric] {name} = " in out
    assert all(line["metrics"][n]["unit"] == "s" for n in DURATIONS)
    assert line["metrics"]["setup_span_coverage"]["unit"] == "%"
    # the four that moved setup_s before are read as before
    assert {"compile_s.train", "jit_trace_s.train", "backend_compile_s.train",
            "eager_compile_s.setup"} <= set(line["metrics"])


def test_the_durations_are_disjoint_parts_of_set_up(traced):
    line, out = traced
    value = {n: line["metrics"][n]["value"] for n in NEW}
    setup_s = float(re.search(r"\[setup\] ([0-9.]+) s of set-up", out)[1])
    assert all(value[n] >= 0.0 for n in DURATIONS)
    # state_place_s is a part of step_build_s; the rest never overlap
    assert value["state_place_s.setup"] <= value["step_build_s.setup"]
    parts = sum(value[n] for n in DURATIONS if n != "state_place_s.setup")
    assert 0.0 < parts <= setup_s
    assert 0.0 < value["setup_span_coverage"] <= 100.0
    assert parts <= value["setup_span_coverage"] / 100.0 * setup_s + 1e-3
    # every cell imports the package, draws its leaves, builds a step and
    # calls it; the first call holds more than its compile
    for name in ("import_s.setup", "param_init_s.setup",
                 "step_build_s.setup", "first_call_rest_s.train"):
        assert value[name] > 0.0, name
    assert value["first_call_rest_s.train"] \
        < line["metrics"]["compile_s.train"]["value"]


def test_the_table_of_set_up_names_every_phase(traced):
    _, out = traced
    rows = re.findall(r"^\[setup\] +(\d+) +[0-9.]+ +[0-9.]+\.\. *[0-9.]+  (\S+)$",
                      out, re.M)
    counts = {name: int(n) for n, name in rows}
    for name in ("process.import", "fused_step.build", "fused_step.place",
                 "fused_step.first_call", "jit.compile.step"):
        assert counts[name] == 1, (name, counts)
    assert counts["gluon.param_init"] > 10
    assert counts["jit.compile"] > counts["jit.compile.step"]
    assert "under no span:" in out


# ---------------------------------------------------- handmade span lists

def _span(name, t0, t1, **args):
    return {"name": name, "parent": None, "t0": t0, "t1": t1, "args": args}


STEP = "fused_step:ResNetV1"
SPANS = [
    _span("process.import", 101.0, 103.0),
    _span("gluon.initialize", 103.0, 108.0),
    _span("gluon.param_init", 103.0, 105.0),
    _span("gluon.param_init", 105.5, 107.5),
    _span("gluon.first_forward", 110.0, 120.0),
    _span("gluon.param_init", 111.0, 112.0),           # a deferred leaf
    _span("jit.compile", 111.5, 113.0, site=None),     # half inside that leaf
    _span("jit.compile", 115.0, 116.0, site=None),
    _span("amp.convert_block", 120.0, 120.5),
    _span("fused_step.build", 121.0, 125.0),
    _span("fused_step.place", 122.0, 124.5),
    _span("fused_step.first_call", 130.0, 150.0),
    _span("jit.compile", 130.5, 131.0, site=None),     # the key split's
    _span("jit.compile", 132.0, 144.0, site=STEP),
    # after the window opened: the reference check builds a second step
    _span("gluon.param_init", 161.0, 163.0),
    _span("fused_step.build", 165.0, 166.0),
    _span("fused_step.first_call", 166.0, 170.0),
    _span("jit.compile", 166.5, 169.0, site=STEP),
]


@pytest.mark.parametrize("key,expected", [
    ("import_s", 2.0),
    ("param_init_s", 5.0),              # 2 + 2 + the deferred leaf's 1
    ("first_forward_s", 10.0 - 1.0 - 1.0 - 1.0),   # leaf, 1 s more of its
    ("amp_convert_s", 0.5),             # compile, the second compile
    ("step_build_s", 4.0),
    ("state_place_s", 2.5),
    ("first_call_rest_s", 20.0 - 12.0),     # the key split's compile stays
])
def test_phase_seconds_on_a_handmade_trace(key, expected):
    reduced = setup_spans.reduce_spans(SPANS, 100.0, 160.0)
    assert reduced[key] == pytest.approx(expected)


def test_coverage_is_the_union_over_set_up():
    reduced = setup_spans.reduce_spans(SPANS, 100.0, 160.0)
    # import 2, initialize 5, first forward 10, amp 0.5, build 4, call 20
    assert reduced["covered_s"] == pytest.approx(41.5)
    assert reduced["setup_s"] == 60.0
    assert reduced["coverage"] == pytest.approx(41.5 / 60.0)
    assert reduced["gaps"][0] == (pytest.approx(10.0), pytest.approx(50.0))
    count, seconds, first, last = reduced["by_name"]["gluon.param_init"]
    assert (count, seconds, first, last) == (3, 5.0, 3.0, 12.0)


def test_spans_are_cut_at_the_windows_opening_and_at_process_start():
    early = setup_spans.reduce_spans(SPANS, 100.0, 135.0)
    # the first call straddles the opening: 5 s of it, 3 of them compile
    assert early["first_call_rest_s"] == pytest.approx(5.0 - 3.0 - 0.0)
    assert early["covered_s"] == pytest.approx(21.5 + 5.0)
    late = setup_spans.reduce_spans(SPANS, 102.0, 160.0)
    assert late["import_s"] == pytest.approx(1.0)
    # nothing began before the window opened: every phase reads 0
    assert setup_spans.reduce_spans(SPANS, 90.0, 100.0)["covered_s"] == 0.0


def test_gaps_are_what_no_span_covers_largest_first():
    reduced = setup_spans.reduce_spans(SPANS, 100.0, 160.0)
    assert [(round(s, 6), round(at, 6)) for s, at in reduced["gaps"]] == [
        (10.0, 50.0), (5.0, 25.0), (2.0, 8.0), (1.0, 0.0), (0.5, 20.5)]
    assert sum(s for s, _ in reduced["gaps"]) + reduced["covered_s"] \
        == pytest.approx(reduced["setup_s"])


def test_a_program_without_a_process_trace_reads_nothing(monkeypatch):
    monkeypatch.setattr(setup_spans, "program_process_spans", lambda: None)
    monkeypatch.setattr(setup_spans, "_REDUCED", {})
    run = {"process_start": 100.0, "window_open": 160.0}
    manifest = harness.Manifest(os.path.join(REPO, "BENCHMARK.json"))
    for name in NEW:
        reader = harness.load_module(
            manifest.find("layer_metrics", name + ".py"))
        assert reader.read(run) is None


@pytest.mark.parametrize("name", NEW)
def test_readers_read_the_programs_own_trace(name, monkeypatch):
    monkeypatch.setattr(setup_spans, "program_process_spans", lambda: SPANS)
    monkeypatch.setattr(setup_spans, "_REDUCED", {})
    run = {"process_start": 100.0, "window_open": 160.0}
    reader = harness.load_module(os.path.join(
        REPO, "chipbench", "layer_metrics", name + ".py"))
    reduced = setup_spans.reduce_spans(SPANS, 100.0, 160.0)
    expected = 100.0 * reduced["coverage"] if name == "setup_span_coverage" \
        else reduced[KEYS[name]]
    assert reader.read(run) == pytest.approx(expected)


def test_the_program_keeps_a_process_trace_with_the_fields_read():
    spans = setup_spans.program_process_spans()
    assert isinstance(spans, list)
    for record in spans:
        assert {"name", "parent", "t0", "t1", "args"} <= set(record)
        assert record["t0"] <= record["t1"]


# ------------------------------------------------------------ the manifest

def _the_eight(per_layer):
    """The eight entries, found by name wherever a later PR's additions have
    left them (a test that pins the list's tail fails for every PR that
    appends a metric: PERF.md section 7)."""
    names = [m["name"] for m in per_layer]
    first = names.index(NEW[0])
    assert names[first:first + 8] == NEW
    return per_layer[first:first + 8]


def test_the_manifest_gained_the_eight_entries_together_and_in_order():
    manifest = harness.Manifest(os.path.join(REPO, "BENCHMARK.json"))
    with open(os.path.join(REPO, "PERF.md")) as f:
        section = f.read().split("## 3. Layers")[1].split("\n## ")[0]
    layers = set(re.findall(r"^\| ([a-z][a-z ]+?) \|", section, re.M))
    for entry in _the_eight(manifest.data["per_layer"]):
        assert "workloads" not in entry          # every cell reports it
        assert entry["moves"] == "setup_s"
        assert entry["source"] == "program_span"
        assert entry["layer"] in layers
        assert (entry["unit"], entry["better"]) == (
            ("%", "higher") if entry["name"] == "setup_span_coverage"
            else ("s", "lower"))
        reader = harness.load_module(
            manifest.find("layer_metrics", entry["name"] + ".py"))
        assert callable(reader.read)
        doc = " ".join(reader.__doc__.split())
        assert f"Layer: {entry['layer']}." in doc
        assert "Source: program span." in doc
        # PERF.md names the metric in its layer's row
        assert f"`{entry['name']}`" in section
    # the toy manifest of this directory lists the same eight
    toy = harness.Manifest(os.path.join(HERE, "BENCHMARK.json"))
    assert _the_eight(toy.data["per_layer"]) \
        == _the_eight(manifest.data["per_layer"])
