"""Device seconds by the program's named scopes (``chipbench.scopes``): the
scope path of an HLO ``op_name``, the HLO a trace carries, the count over a
hand-made and a recorded trace, and the readers of the scope metrics."""
import glob
import json
import os

import jax
import jax.numpy as jnp
import pytest

from chipbench import harness, scopes, traces

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SCOPED = sorted(p for p in glob.glob(os.path.join(DATA, "trace_*.json"))
                if "programs" in json.load(open(p)))
READERS = ["walk_transition_ms_per_step.fleet", "loss_eval_ms_per_step.fleet"]


@pytest.mark.parametrize("op_name,path", [
    ("jit(f)/while/body/closed_call/walk_transition/jit(_uniform)/add", "walk_transition"),
    ("jit(_fleet_scan)/while/body/closed_call/fleet_loss_eval/vmap()/dot_general",
     "fleet_loss_eval"),
    ("jit(f)/while/body/closed_call/walk_transition/pallas_call", "walk_transition"),
    ("jit(f)/outer/inner/mul", "outer/inner"),
    ("jit(f)/jit(_threefry_split)/f/while", ""),
    ("jit(f)/while/body/dynamic_update_slice", ""),
    ("x", ""),
])
def test_scope_path_of_an_op_name(op_name, path):
    assert scopes.scope_path(op_name) == path
    assert scopes.top_scope(path) == (path.split("/")[0] or scopes.UNSCOPED)


def _cpu_trace(trace_dir):
    @jax.jit
    def f(x):
        with jax.named_scope("outer"):
            with jax.named_scope("inner"):
                y = jnp.sin(x) @ x
        return y + 1.0

    x = jnp.ones((64, 64))
    jax.block_until_ready(f(x))
    jax.profiler.start_trace(str(trace_dir))
    try:
        jax.block_until_ready(f(x))
    finally:
        jax.profiler.stop_trace()
    return traces.find_xplane(str(trace_dir))


def test_the_trace_carries_each_programs_hlo_scopes(tmp_path):
    programs = scopes.program_scopes(_cpu_trace(tmp_path))
    (name,) = [n for n in programs if n.startswith("jit_f(")]
    table = programs[name]
    assert "outer/inner" in table.values()
    assert {scopes.top_scope(p) for p in table.values()} == {"outer", scopes.UNSCOPED}


def test_hlo_op_names_reads_every_computation():
    # a serialized HloProto by hand: module "m", two computations
    def field(number, payload):
        return bytes([number << 3 | 2, len(payload)]) + payload

    def inst(name, op_name=None):
        body = field(1, name.encode()) + field(2, b"add")
        if op_name is not None:
            body += field(7, field(1, b"add") + field(2, op_name.encode()))
        return field(2, body)

    module = (field(1, b"m")
              + field(3, field(1, b"body") + inst("add.1", "jit(m)/s/add"))
              + field(3, field(1, b"main") + inst("fusion.2", "jit(m)/t/u/mul")
                      + inst("copy.3")))
    assert scopes.hlo_op_names(field(1, module)) == (
        "m", {"add.1": "jit(m)/s/add", "fusion.2": "jit(m)/t/u/mul", "copy.3": ""})


def _scoped_trace():
    # two runs of jit_call(5), a run of another program between them whose
    # operation names collide with the call's; device stamps 7 ns early
    return traces.Trace(
        device={"/device:TPU:0": [("while.1", 3, 40), ("k", 3, 30), ("f", 30, 38),
                                  ("g", 38, 40), ("k", 41, 44), ("while.1", 53, 88),
                                  ("k", 53, 80), ("f", 80, 86), ("c", 86, 88)]},
        modules={"/device:TPU:0": [("jit_call(5)", 3, 40), ("jit_small(6)", 41, 44),
                                   ("jit_call(5)", 53, 88)]},
        host=[("window", 0, 100), ("dispatch", 2, 10), ("block", 10, 45),
              ("next_input", 46, 49), ("dispatch", 50, 60), ("block", 60, 95)],
    )


PROGRAMS = {"jit_call(5)": {"k": "walk_transition", "f": "fleet_loss_eval/inner",
                            "g": "fleet_sgd", "c": "", "while.1": ""},
            "jit_small(6)": {"k": ""}}


def test_scope_seconds_of_a_hand_made_trace():
    t = _scoped_trace()
    # the count of three programs for two dispatch spans leaves the device
    # clock as traces.summarize leaves it, unshifted: k [3,30] f [30,38]
    # g [38,40] small k [41,44] k [53,80] f [80,86] c [86,88]; the while ops
    # enclose their bodies and count nothing
    got = scopes.scope_seconds(t, PROGRAMS)
    assert got == pytest.approx({"walk_transition": 54e-9, "fleet_loss_eval": 14e-9,
                                 "fleet_sgd": 2e-9, "unscoped": 5e-9})
    s = traces.summarize(t)
    assert sum(got.values()) == pytest.approx(sum(sec for _, sec in s.top_ops))
    assert sum(got.values()) == pytest.approx(s.busy_s)


def test_scope_seconds_follow_the_clock_offset_and_the_window():
    t = _scoped_trace()
    t.device["/device:TPU:0"] = [op for op in t.device["/device:TPU:0"] if op[1] != 41]
    t.modules["/device:TPU:0"] = [m for m in t.modules["/device:TPU:0"]
                                  if m[0] != "jit_small(6)"]
    t.host[0] = ("window", 15, 90)  # shifted +7: k [10,37] ... c [93,95]
    got = scopes.scope_seconds(t, PROGRAMS)
    # k [15,37] + [60,87], f [37,45] + [87,90]; g [45,47]; c outside
    assert got == pytest.approx({"walk_transition": 49e-9, "fleet_loss_eval": 11e-9,
                                 "fleet_sgd": 2e-9})
    assert sum(got.values()) == pytest.approx(traces.summarize(t).busy_s)


def test_programs_without_scopes_leave_everything_unscoped():
    t = _scoped_trace()
    assert set(scopes.scope_seconds(t, {})) == {scopes.UNSCOPED}
    assert scopes.scope_seconds(traces.Trace({}, {}, t.host), PROGRAMS) == {}


@pytest.mark.parametrize("path", SCOPED, ids=[os.path.basename(p) for p in SCOPED])
def test_recorded_trace_by_scope(path):
    """A cell's milliseconds around one gap, recorded on a TPU v5e with the
    scope map of the program that ran; the expected seconds of each scope
    were counted apart, leaf by leaf."""
    with open(path) as f:
        rec = json.load(f)
    trace = traces.Trace.from_json(rec["trace"])
    got = scopes.scope_seconds(trace, rec["programs"])
    assert got == pytest.approx(rec["expected"]["scope_s"], rel=1e-9)
    busy = traces.summarize(trace).busy_s
    assert sum(got.values()) == pytest.approx(busy, rel=1e-3)
    assert got["unscoped"] <= 0.02 * busy
    if rec["cell"].startswith("walk."):
        assert got["walk_transition"] >= 0.98 * busy


def _write_window(trace_dir, trace):
    with open(os.path.join(trace_dir, "intervals.json"), "w") as f:
        json.dump(trace.to_json(), f)


def test_window_scopes_read_the_runs_trace_directory(tmp_path):
    xplane = _cpu_trace(tmp_path)
    (name,) = [n for n in scopes.program_scopes(xplane) if n.startswith("jit_f(")]
    # the CPU trace has no device line: put the program's run on a hand-made one
    _write_window(tmp_path, traces.Trace(
        {"/device:TPU:0": [("dot_general.1", 10, 40), ("x", 40, 50)]},
        {"/device:TPU:0": [(name, 10, 50)]}, [("window", 0, 100)]))
    table = scopes.program_scopes(xplane)[name]
    assert scopes.top_scope(table.get("dot_general.1")) == "outer"
    got = scopes.window_scopes(str(tmp_path))
    assert got["outer"] == pytest.approx(30e-9)
    assert sum(got.values()) == pytest.approx(40e-9)
    assert scopes.window_scopes(str(tmp_path / "none")) == {}


@pytest.mark.parametrize("name", READERS)
def test_scope_readers_read_nothing_without_their_inputs(name, tmp_path, monkeypatch):
    read = harness._load_module("metrics", name).read
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    assert read({"summary": None, "counts": {"fleet_steps": 16}, "peak": None}) is None
    monkeypatch.setattr(scopes, "window_scopes",
                        lambda trace_dir=None: {"unscoped": 1e-3})  # a program without scopes
    assert read({"summary": None, "counts": {"fleet_steps": 16}, "peak": None}) is None
    monkeypatch.setattr(scopes, "window_scopes",
                        lambda trace_dir=None: {"walk_transition": 1e-3,
                                                "fleet_loss_eval": 1e-3})
    assert read({"summary": None, "counts": {}, "peak": None}) is None


def test_scope_readers_read_ms_per_fleet_step(monkeypatch):
    monkeypatch.setattr(scopes, "window_scopes",
                        lambda trace_dir=None: {"walk_transition": 0.8, "fleet_loss_eval": 0.05,
                                                "unscoped": 0.01})
    ctx = {"summary": None, "counts": {"fleet_steps": 16}, "peak": None}
    walk, loss = (harness._load_module("metrics", n).read(ctx) for n in READERS)
    assert walk == pytest.approx(50.0)
    assert loss == pytest.approx(3.125)
