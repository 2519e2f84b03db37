"""The training cells' check at CPU-test size: the program agrees with the
plain reference, every compared number starts from the program's own
state at a dispatch's start, and the lower-precision control and each fault planted in
the reference in the program's place come out not correct under the
cell's limits (the faults planted in the program are in
test_bench_train_faults.py)."""
import jax
import numpy as np
import pytest

from bench import compare
from bench.tests.tiny_cells import run_tiny, tiny_cell

CELL = "adult-train-p5"


@pytest.fixture(scope="module")
def cell():
    with jax.default_matmul_precision("float32"):
        yield tiny_cell(CELL, 3)


def test_program_agrees_with_reference():
    line = run_tiny(CELL)
    assert line["correct"], line["limits"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"train_rows_per_s", "setup_s"}
    # the result line: the contract's keys first, the compared numbers last
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "limits"
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])
    assert all(set(v) == {"value", "limit"} for v in line["limits"].values())
    assert {"loss", "change", "change_med", "merge", "slots"} == set(
        line["limits"])


def test_each_compared_dispatch_starts_from_program_state(cell, monkeypatch):
    """The reference takes dispatch k from the program's own state at
    dispatch k's start: its parameters, Adam moments, counts and streams."""
    spec, c = cell
    firsts = []
    local = c.reference_round
    monkeypatch.setattr(c, "_sound", {})
    monkeypatch.setattr(c, "reference_round", lambda fn, k, *a, **kw: (
        firsts.append(k), local(fn, k, *a, **kw))[1])
    with jax.default_matmul_precision("float32"):
        readings = c.readings()
    assert firsts == list(range(spec["traffic_mix"]["check_dispatches"]))
    assert len(firsts) >= 2
    assert readings["loss"] <= spec["limits"]["loss"]


def test_nothing_compared_follows_a_round_taken_apart(cell):
    """Every number the program side gives comes from the dispatch's start
    state through at most one round, or from one merge of the program's
    own inputs: the first round's losses and local results, and the
    traced replay's last merge with the round it merged; and the replay
    ends where ``run`` ended."""
    from bench.reference import ctgan as ref
    spec, c = cell
    for k in range(spec["traffic_mix"]["check_dispatches"]):
        prog = c.program_dispatch(k)
        tr = c.traced[k]
        np.testing.assert_array_equal(prog["losses"], c.prog_losses[k][0])
        for client, start in zip(prog["start"], prog["post"]):
            assert jax.tree.structure(client) == jax.tree.structure(start)
        want = ref.merge(prog["merge_in"], tr["weights"])
        for slot in prog["merged"] + prog["slots"]:
            for a, b in zip(jax.tree.leaves(slot), jax.tree.leaves(want)):
                np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
        tmpl = {"g": c.snapshots[k].g_params, "d": c.snapshots[k].d_params}
        from repro.fed.merge import unflatten_merged
        before = ref.merge([unflatten_merged(f, tmpl)
                            for f in tr["before_last"]], tr["weights"])
        for a, b in zip(jax.tree.leaves(before),
                        jax.tree.leaves(prog["merge_from"])):
            np.testing.assert_array_equal(a, b)


def test_lower_precision_control_is_not_correct(cell):
    """The reference computed in three bf16 passes, in the program's place,
    fails the cell's limits."""
    spec, c = cell
    with jax.default_matmul_precision("float32"):
        got = compare.limited(c.readings("control", "bf16_3x"),
                              spec["limits"])
    assert any(v > lim for v, lim in got.values()), got


@pytest.mark.parametrize("fault", compare.REFERENCE_FAULTS)
def test_fault_in_reference_is_not_correct(cell, fault):
    """Each fault planted in the reference, put in the program's place,
    fails the cell's limits."""
    spec, c = cell
    with jax.default_matmul_precision("float32"):
        got = compare.limited(c.readings(fault), spec["limits"])
    assert any(v > lim for v, lim in got.values()), got


def test_setup_agrees_with_reference(cell):
    """The program's §4.1 encoding and §4.2 weights against the plain
    reference set-up from the same raw tables (read, not compared, on the
    chip: no control or listed fault moves them)."""
    from bench.reference import ctgan as ref
    from bench.reference import setup as ref_setup
    spec, c = cell
    rs = ref_setup.federation(c.parts, c.kinds, c.seed & 0x7FFFFFFF,
                              max_modes=c.m["max_modes"])
    got = compare.setup_numbers(c.traced[0]["weights"], rs["weights"],
                                c.encoded, rs["encoded"], c.kinds,
                                rs["widths"])
    assert rs["widths"] == ref.column_widths(c.parts, c.kinds,
                                             c.m["max_modes"])
    assert got["setup_weights"] < 1e-2, got
    assert got["setup_cells"] < 1e-2 and got["setup_alpha"] < 1e-2, got
