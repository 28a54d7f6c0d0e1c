"""The step program's phases and parts, read from the op_names of its
operations (``bench/scopes.py``): on a small hand-made trace whose
numbers are worked out below, and on one step of
``bert-mlm-350m.train.resident`` recorded on a TPU v5e with ``bench/run.py
--trace 1 --keep-trace`` and trimmed by ``make_trace_fixture.py``
(``data/trace_350m_scoped_step.json.gz``)."""
from pathlib import Path

import pytest

from bench import scopes
from bench import trace as tr
from bench.harness import load_module

FWD = "jit(step)/jvp(step_forward)/while/body/closed_call/"
BWD = "jit(step)/transpose(jvp(step_forward))/while/body/closed_call/"
# window [0, 200) ns on one chip, 2 steps; (name, start, end, category,
# op_name, phase, part)
OPS = [
    ("fusion.1", 0, 20, "fusion:matmul", FWD + "attention/dot_general",
     "forward", "attention"),
    ("fusion.2", 20, 30, "fusion:matmul", FWD + "ffn/dot_general",
     "forward", "ffn"),
    ("fusion.3", 30, 40, "fusion:matmul",
     "jit(step)/jvp(step_forward)/loss_head/while/body/dot_general",
     "forward", "loss_head"),
    ("fusion.4", 40, 50, "fusion:matmul",
     BWD + "checkpoint/rematted_computation/attention/dot_general",
     "recompute", "attention"),
    ("fusion.5", 50, 70, "fusion:matmul",
     BWD + "checkpoint/ffn/dot_general", "backward", "ffn"),
    ("scatter.6", 70, 75, "scatter",
     "jit(step)/transpose(jvp(step_forward))/scatter-add", "backward", None),
    ("all-reduce.7", 80, 90, "all-reduce",
     "jit(step)/shard_map/gradsync_bucket_1.0mb/psum", "gradsync", None),
    ("fusion.8", 90, 95, "fusion",
     "jit(step)/shard_map/gradsync_bucket_1.0mb/concatenate",
     "gradsync", None),
    ("fusion.9", 100, 120, "fusion", "jit(step)/optimizer/mul",
     "optimizer", None),
    ("divide.10", 120, 125, "divide", "jit(step)/jvp()/div", None, None),
    # a component that only starts with a part's name is not that part
    ("select.11", 125, 130, "select",
     FWD + "attention_mask/select_n", "forward", None),
]
# not busy: the layer loop, whose body's operations are events of their
# own, and an async copy in flight throughout
NOT_BUSY = [tr.Op("while.12", 0, 75, "while", FWD[:-1]),
            tr.Op("copy-start.13", 0, 200, "async:copy-start", "jit(step)")]
# per step: forward 0-40 and 125-130, recompute 40-50, backward 50-75,
# optimizer 100-120; attention 0-20 and 40-50, ffn 20-30 and 50-70,
# loss head 30-40 (ns over 2 steps, in ms)
EXPECTED = {"forward_ms": 22.5e-6, "recompute_ms": 5e-6,
            "backward_ms": 12.5e-6, "optimizer_ms": 10e-6,
            "attention_ms": 15e-6, "ffn_ms": 15e-6, "loss_head_ms": 5e-6}


def context(ops):
    return tr.Context(config={}, batch=2, chips=1, steps=2, peak={},
                      trace=tr.Trace((0, 200), {"/device:TPU:0": ops}, []))


@pytest.fixture
def ctx():
    return context([tr.Op(*o[:5]) for o in OPS] + NOT_BUSY)


def read(name, ctx):
    return load_module("metrics", name).read(ctx)


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_scope_readers(ctx, metric):
    assert read(metric, ctx) == pytest.approx(EXPECTED[metric])


def test_each_operation_in_one_phase_and_its_part():
    for name, _, _, _, scope, phase, part in OPS:
        assert scopes.phase_of(scope) == phase, name
        assert [p for p, has in scopes.PARTS.items() if has(scope)] \
            == ([part] if part else []), name


@pytest.mark.parametrize("scope, phase", [
    # first match wins: gradsync > optimizer > recompute > backward >
    # forward
    ("jit(step)/transpose(jvp(step_forward))/gradsync_bucket_2.0mb/add",
     "gradsync"),
    ("jit(step)/optimizer/transpose(jvp(step_forward))/mul", "optimizer"),
    (BWD + "checkpoint/rematted_computation/ffn/dot", "recompute"),
    ("jit(step)/transpose(jvp(step_forward))/dot", "backward"),
    ("jit(step)/jvp(step_forward)/dot", "forward"),
    # a jitted function of another name is in no phase
    ("jit(forward)/jvp(forward)/dot", None),
    ("jit(step)/optimizer_state/mul", None),
])
def test_phase_precedence(scope, phase):
    assert scopes.phase_of(scope) == phase


def test_scope_readers_find_nothing_without_scopes():
    bare = context([tr.Op(o[0], o[1], o[2], o[3], "jit(step)/" + o[0])
                    for o in OPS] + NOT_BUSY)
    for metric in EXPECTED:
        assert read(metric, bare) is None, metric


RECORDED = Path(__file__).parent / "data" / "trace_350m_scoped_step.json.gz"


@pytest.fixture(scope="module")
def recorded():
    profile, spans, hlo = tr.load_saved(RECORDED)
    return tr.Context(config={}, batch=48, chips=1, steps=1, peak={},
                      trace=tr.reduce(profile, spans, hlo))


def test_recorded_step_phases_cover_its_busy_time(recorded):
    busy = 1e3 * recorded.busy_s
    phases = {p: scopes.phase_ms(recorded, p) for p, _ in scopes.PHASES}
    # one chip: no gradient sync; every other phase ran
    assert phases.pop("gradsync") is None
    assert all(phases.values())
    assert 0.95 * busy <= sum(phases.values()) <= 1.005 * busy
    # the backward pass is the longest phase, the optimizer the shortest
    assert max(phases, key=phases.get) == "backward"
    assert min(phases, key=phases.get) == "optimizer"
    parts = [scopes.part_ms(recorded, p) for p in scopes.PARTS]
    assert all(parts) and sum(parts) <= busy


# what the three parts read on the recorded step (ms) while part_ms took
# only the names in PARTS: reading any scope by name moves none of them
RECORDED_PARTS = {"attention": 504.48695899999996, "ffn": 266.519797,
                  "loss_head": 93.73465999999999}


@pytest.mark.parametrize("part", sorted(RECORDED_PARTS))
def test_recorded_parts_read_as_before(recorded, part):
    assert scopes.part_ms(recorded, part) == RECORDED_PARTS[part]


@pytest.mark.parametrize("scope, reads", [
    # a scope outside PARTS is read as a part: the whole component only
    ("checkpoint", True), ("jit(log_softmax)", True),
    ("check", False),
    # a scope the step does not have reads nothing, and raises nothing
    ("router", False)])
def test_any_scope_is_a_part(recorded, scope, reads):
    assert (scopes.part_ms(recorded, scope) is not None) is reads
