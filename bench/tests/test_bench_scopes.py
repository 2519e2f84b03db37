"""Per-phase device time (``bench/scopes.py``): the scope parser, the
bucketing of leaf ops, the readers on a cell, and the trace-read metrics
pinned on the recorded trace, which program spans must not move."""
import json
import types
from pathlib import Path

import pytest

from bench import harness, scopes, tracing
from bench.tests.tiny_cells import tiny

DATA = Path(__file__).parent / "data"
SCOPED = DATA / "train_trace_scoped.json"


@pytest.mark.parametrize("stack,scope", [
    ("jit(_run_impl)/while/body/closed_call/vmap(draw)/jit(draw_batch)/lt",
     "draw"),
    ("jit(_run_impl)/while/body/vmap()/d_step/jvp(gradient_penalty)/mul",
     "gradient_penalty"),
    ("jit(_run_impl)/while/body/g_step/transpose(jvp(kernel_layout))/pad",
     "kernel_layout"),
    ("d_step/transpose(jvp())/reduce_sum", "d_step"),
    ("g_step/transpose(g_step)/jvp(transpose(jvp()))/reduce_sum", "g_step"),
    ("jit(_run_impl)/while/body/merge/jit(weighted_agg)/pallas_call",
     "merge"),
    ("d_step/jvp(jit(segment_activations))/kernel_layout/reshape",
     "kernel_layout"),
    ("jit(merge)/add", None),              # a function, not a scope
    ("jit(_run_impl)/while/body/slice", None),
])
def test_scope_of_innermost_listed(stack, scope):
    assert scopes.scope_of(stack) == scope


HLO = """\
ENTRY %main {
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(f)/while/body/vmap(draw)/jit(draw_batch)/eq" source_file="x.py" source_line=3}
  ROOT %jvp_jit_segment_activations__.15 = f32[5,19,504,18]{3,2,1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/d_step/jvp(jit(segment_activations))/pallas_call"}
  %copy.7 = f32[4]{0} copy(%b)
  %add.2 = f32[4]{0} add(%b, %b), metadata={op_name="jit(f)/while/body/add"}
}
"""


def test_op_scopes_from_compiled_text():
    assert scopes.op_scopes(HLO) == {
        "fusion.1": "draw", "jvp_jit_segment_activations__.15": "d_step"}


def test_scope_seconds_buckets_every_leaf():
    op_s = {"fusion.1 f32[8]": 2.0, "copy.7 f32[4]": 0.5, "add.2": 0.25,
            "jvp_jit_segment_activations__.15 f32[5,19,504,18]": 1.0}
    phases = scopes.scope_seconds(op_s, scopes.op_scopes(HLO))
    assert phases == {"draw": 2.0, "d_step": 1.0, "gradient_penalty": 0.0,
                      "g_step": 0.0, "merge": 0.0, "kernel_layout": 0.0,
                      "unscoped": 0.75}


def _adult_ctx(summary, attempted, rounds):
    m = json.loads((Path(harness.BENCH) / "configs" /
                    "ctgan-adult.json").read_text())
    widths = [1, 10] * 5 + [9, 16, 7, 15, 6, 5, 2, 47, 2]
    cell = types.SimpleNamespace(
        m=m, n_clients=5, cond_dim=109, data_dim=sum(widths),
        span_widths=widths, span_kinds=["tanh", "softmax"] * 5 +
        ["softmax"] * 9, n_params=1238401)
    return {"cell": cell, "trace": summary,
            "spec": {"traffic_mix": {"kind": "train",
                                     "rounds_per_dispatch": rounds}},
            "window": {"attempted": attempted,
                       "rows": attempted * rounds * 5 * m["batch_size"]},
            "peaks": harness.peaks_for("TPU v5 lite")}


TRACE_READ = {"device_idle.train": 53.802031096439286,
              "mfu.train": 2.538903780887203,
              "segment_activations_roofline.train": 6.85794503352831,
              "weighted_agg_roofline.train": 93.60102680430384}


@pytest.mark.parametrize("far", [False, True])
def test_trace_read_metrics_pinned(far):
    """The recorded trace reads what it read before the program had spans
    and scopes; program spans far outside the window move nothing."""
    form = json.loads((DATA / "train_trace.json").read_text())
    if far:
        t0 = form["device"][0][0][1]
        form["program"] = [["repro.setup.vgm_fit", t0 - 9e10, 5e9],
                           ["repro.fed.run", t0 + 9e10, 1e6]]
    s = tracing.reduce(form)
    assert (s.window_s, s.busy_s) == (0.04563545, 0.021082651)
    ctx = _adult_ctx(s, attempted=2, rounds=5)
    for name, value in TRACE_READ.items():
        assert harness.reader(name).read(ctx) == pytest.approx(value,
                                                               rel=1e-12)


@pytest.fixture(scope="module")
def scoped():
    if not SCOPED.exists():
        pytest.fail(f"missing {SCOPED.name}")
    form = json.loads(SCOPED.read_text())
    return form, tracing.reduce(form)


def test_scoped_trace_buckets_sum_to_leaf_time(scoped):
    form, s = scoped
    phases = scopes.scope_seconds(s.op_s, form["op_scope"])
    leaf = sum(s.op_s.values())
    assert sum(phases.values()) == pytest.approx(leaf, rel=1e-3)
    assert all(phases[k] > 0 for k in scopes.SCOPES)
    kernels = [(tracing.stable_name(k), v) for k, v in form["op_scope"].items()
               if "segment_activations" in k or "weighted_agg" in k]
    assert sorted(kernels) == [
        ("jvp_jit_segment_activations__", "d_step"),
        ("jvp_jit_segment_activations__", "g_step"),
        ("weighted_agg", "merge")]


def test_scoped_trace_keeps_kernels_and_program_spans(scoped):
    form, s = scoped
    act, merge = s.kernel("segment_activations"), s.kernel("weighted_agg")
    assert act is not None and merge is not None
    assert act[1] == 2 * merge[1]       # the old trace's ratio, 2 : 1
    assert [p[0] for p in form["program"]] == ["repro.fed.run"] * 2
    assert set(form["op_scope"].values()) == set(scopes.SCOPES)


def test_us_per_round_on_the_scoped_trace(scoped, monkeypatch):
    form, s = scoped
    monkeypatch.setattr(scopes, "program_text", lambda cell: "")
    monkeypatch.setattr(scopes, "op_scopes", lambda text: form["op_scope"])
    ctx = _adult_ctx(s, attempted=2, rounds=5)
    phases = scopes.scope_seconds(s.op_s, form["op_scope"])
    for name in scopes.SCOPES:
        value = harness.reader(f"{name}_us_per_round.train").read(ctx)
        assert value == pytest.approx(phases[name] * 1e6 / 10)


def test_no_scopes_read_nothing(monkeypatch):
    """A program without the scopes (or without compiled text) maps no op:
    every per-phase reader returns None and none raises."""
    s = tracing.reduce(json.loads((DATA / "train_trace.json").read_text()))
    monkeypatch.setattr(scopes, "program_text", lambda cell: "ENTRY {}")
    ctx = _adult_ctx(s, attempted=2, rounds=5)
    assert scopes.us_per_round(ctx, "merge") is None

    def fails(cell):
        raise RuntimeError("no program")
    monkeypatch.setattr(scopes, "program_text", fails)
    ctx = _adult_ctx(s, attempted=2, rounds=5)
    for name in scopes.SCOPES:
        assert harness.reader(f"{name}_us_per_round.train").read(ctx) is None


@pytest.fixture(scope="module")
def tiny_cell():
    import jax
    spec = harness.cell_spec(harness.load_benchmark(), "adult-train-p5")
    tiny(spec)
    from repro import obs
    with jax.default_matmul_precision("float32"):
        cell = harness.kind_module("train").Cell(spec, 3614000099)
        cell.setup()
        before = obs.counter("fed")["rounds"]
        window = cell.window(0.3, False)
        rounds = obs.counter("fed")["rounds"] - before
    cell.free()
    return spec, cell, window, rounds


def test_window_rounds_match_the_program_counter(tiny_cell):
    """The readers divide by attempted x rounds_per_dispatch: the rounds
    the program's ``fed.rounds`` counter saw in the window."""
    spec, _, window, rounds = tiny_cell
    assert window["attempted"] > 0
    assert rounds == window["attempted"] * spec["traffic_mix"][
        "rounds_per_dispatch"]


def test_program_text_maps_every_phase(tiny_cell):
    _, cell, _, _ = tiny_cell
    op_scope = scopes.op_scopes(scopes.program_text(cell))
    assert set(op_scope.values()) == set(scopes.SCOPES)
