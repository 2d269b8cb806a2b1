"""The XSpace reader (bench/xplane.py) on recorded TPU traces and on
hand-built planes, and the reader of ``engine_host_ms_per_round``."""
import importlib.util
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import trace as T
from bench import xplane as X

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).parent / "data"


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, ROOT / "bench" / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_reads_op_metadata_and_module_runs():
    planes = X.device_planes(X.read_space(str(DATA / "small.xplane.pb")))
    (plane,) = planes
    assert plane.name == "/device:TPU:0"
    fusions = [op for op in plane.ops if " fusion(" in op.name]
    assert fusions and all(op.tf_op == "jit(<lambda>)/dot_general:"
                           for op in fusions)
    assert all(op.flops > 0 and op.bytes_accessed > 0 for op in fusions)
    # three calls of one program, each op inside one of its runs
    assert [m[2] for m in plane.modules] == ["jit__lambda"] * 3
    assert all(s < e for s, e, _ in plane.modules)
    assert all(op.module == "jit__lambda" for op in plane.ops)


def test_intervals_agree_with_jax_profile_data():
    import jax

    data = jax.profiler.ProfileData.from_file(str(DATA / "small.xplane.pb"))
    want = sorted(t for p in data.planes if p.name == "/device:TPU:0"
                  for line in p.lines if line.name == "XLA Ops"
                  for e in line.events for t in (e.start_ns, e.end_ns))
    (plane,) = X.device_planes(X.read_space(str(DATA / "small.xplane.pb")))
    got = sorted(t for op in plane.ops for t in (op.start_ns, op.end_ns))
    assert got == pytest.approx(want, abs=2.0)   # ns, floored there


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(<lambda>)/dot_general:", ""),
    ("jit(paged_engine_step)/engine.decode/while/body/paged_decode_attn/"
     "dot_general:", "engine.decode/paged_decode_attn"),
    ("jit(paged_engine_step)/engine.prefill/cond/branch_1_fun/mul:",
     "engine.prefill"),
    ("jit(learner_step)/transpose(jvp(learner.loss))/dot_general:",
     "learner.loss"),
    ("jit(learner_step)/learner.optimizer/jit(adamw)/sqrt:",
     "learner.optimizer"),
    ("", ""),
])
def test_scope_of_keeps_only_the_programs_scopes(tf_op, scope):
    assert X.scope_of(tf_op) == scope


def _op(s, e, tf_op, module="jit_step"):
    return X.Op(s, e, "op", tf_op, module, 0, 0)


def test_scope_times_nest_like_self_times():
    # a while (engine.decode) holding a scoped op and a copy with no tf_op;
    # a copy outside any op; times in ns
    ops = [_op(0, 100, "jit(step)/engine.decode/while:"),
           _op(10, 40, "jit(step)/engine.decode/while/body/a/dot:"),
           _op(50, 70, ""),
           _op(120, 130, "", module="jit_other")]
    plane = X.Plane("/device:TPU:0", ops, [])
    times = X.scope_times(plane, 0, 200)
    assert times == {"jit_step|engine.decode": pytest.approx(70e-9),
                     "jit_step|engine.decode/a": pytest.approx(30e-9),
                     "jit_other|(unscoped)": pytest.approx(10e-9)}
    assert X.under(times, "engine.decode") == pytest.approx(100e-9)
    assert X.under(times, "engine.decode", exclude=("a",)) == \
        pytest.approx(70e-9)
    assert X.under(times, "a", module="jit_other") == 0
    # the window clips: self time of the while inside [0, 50]
    assert X.scope_times(plane, 0, 50)["jit_step|engine.decode"] == \
        pytest.approx(20e-9)
    assert X.module_times(X.Plane("p", ops, [(0, 100, "jit_step")]),
                          50, 200) == {"jit_step": pytest.approx(50e-9)}


def test_an_op_without_metadata_takes_its_nested_ops_scope():
    # a while the compiler left without a tf_op: it and a copy inside it
    # take the scope its scoped body ops share, not (unscoped)
    ops = [_op(0, 100, ""),
           _op(10, 40, "jit(step)/engine.decode/while/body/a/dot:"),
           _op(40, 50, ""),
           _op(50, 90, "jit(step)/engine.decode/while/body/b/add:")]
    times = X.scope_times(X.Plane("p", ops, []), 0, 100)
    assert times == {"jit_step|engine.decode": pytest.approx(30e-9),
                     "jit_step|engine.decode/a": pytest.approx(30e-9),
                     "jit_step|engine.decode/b": pytest.approx(40e-9)}
    assert X._common(["x/y/z", "x/y", "x/y/w"]) == "x/y"
    assert X._common([]) == "" and X._common(["x", ""]) == ""


def test_module_name_drops_the_fingerprint():
    assert X.module_name("jit_learner_step(1234)") == "jit_learner_step"
    assert X.module_name("jit__lambda") == "jit__lambda"


SCOPED = DATA / "scoped.xplane.pb"


def test_scoped_trace_attributes_device_time_to_scopes():
    space = X.read_space(str(SCOPED))
    (plane,) = X.device_planes(space)
    spans = X.host_spans(space)
    (lo, hi), = [(s, e) for s, e, n in spans if n == "bench.traced_steps"]
    times = X.scope_times(plane, lo, hi)
    busy = sum(times.values())
    loop = X.under(times, "demo.loop", module="jit_step")
    body = X.under(times, "demo.loop", "demo.body", module="jit_step")
    tail = X.under(times, "demo.tail", module="jit_step")
    assert 0 < body <= loop and tail > 0
    assert loop > 2 * tail                  # four products against one
    assert (loop + tail) / busy > 0.9
    assert X.module_times(plane, lo, hi)["jit_step"] >= 0.9 * busy
    assert {n for _, _, n in spans} >= {"nat.demo.step", "nat.demo.host",
                                        "bench.select", "bench.train_step"}


def test_nat_spans_win_gaps_over_enclosing_bench_spans(monkeypatch):
    gaps = dict(T.reduce(str(SCOPED))["idle_gaps"])
    assert max(gaps, key=gaps.get) == "bench.select"
    monkeypatch.setattr(T, "SPAN_PREFIX", ("bench.", "nat."))
    gaps = dict(T.reduce(str(SCOPED))["idle_gaps"])
    assert gaps.get("nat.demo.host", 0) >= 0.055
    assert "bench.select" not in gaps or gaps["bench.select"] < 0.005


def test_engine_host_reader():
    read = _reader("engine_host_ms_per_round")
    step = dict(rollout_rounds=100, rollout_host_s=0.5, rollout_sync_s=9.0)
    assert read(SimpleNamespace(window=[step, step])) == pytest.approx(5.0)
    assert read(SimpleNamespace(window=[])) is None
    # a program without the counters (before they existed) reads nothing
    assert read(SimpleNamespace(window=[{"rollout_decode_steps": 4}])) is None
