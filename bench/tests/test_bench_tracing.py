"""The trace reduction on a small trace recorded on a TPU v5e (two
dispatches of the adult training program) and on made-up nestings."""
import json
from pathlib import Path

import pytest

from bench import tracing

TRACE = Path(__file__).parent / "data" / "train_trace.json"


@pytest.fixture(scope="module")
def summary():
    return tracing.reduce(json.loads(TRACE.read_text()))


def test_busy_is_within_the_window(summary):
    assert 0 < summary.busy_s <= summary.window_s


def test_device_ops_are_leaves(summary):
    ops = summary.breakdown()["device_ops"]
    assert len(ops) == 10
    names = [tracing.stable_name(name.split()[0]) for name, _ in ops]
    assert not {"while", "conditional", "call"} & set(names)
    loop = sum(e[2] for e in json.loads(TRACE.read_text())["device"][0]
               if e[0].startswith("%while")) * 1e-9
    assert ops[0][1] < loop / 2      # the loop's own span is not an op


def test_kernels_found_by_stable_name(summary):
    act = summary.kernel("segment_activations")
    merge = summary.kernel("weighted_agg")
    assert act is not None and merge is not None
    # two activation calls (critic and generator update) per merge
    assert act[1] == 2 * merge[1] - 1 or act[1] == 2 * merge[1]
    assert summary.kernel("no_such_kernel") is None


def test_idle_gaps_named_by_host_annotations(summary):
    gaps = summary.breakdown()["idle_gaps"]
    assert gaps and {g[0] for g in gaps} <= {"dispatch", "device_get",
                                             "host"}
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps),
                                          reverse=True)


def test_nesting_and_union():
    ev = [["%while.1 = (f32[2])", 0, 100],        # encloses three ops
          ["%fusion.2 = f32[8]{0} fusion()", 10, 20],
          ["%copy.3 = f32[8]{0} copy()", 40, 10],
          ["%weighted_agg.4 = f32[8]{0} custom-call()", 60, 30],
          ["%fusion.5 = f32[8]{0} fusion()", 150, 50]]
    s = tracing.reduce({"device": [ev], "host": [["dispatch", 95, 60]]})
    assert s.busy_s == pytest.approx(150e-9)
    assert s.window_s == pytest.approx(200e-9)
    assert sum(s.op_s.values()) == pytest.approx(110e-9)
    assert s.kernel("weighted_agg") == (pytest.approx(30e-9), 1)
    assert s.gaps == [("dispatch", pytest.approx(50e-9))]


def test_names():
    name = "%copy.435 = f32[5,40000,164]{2,1,0:T(8,128)} copy(f32[5] %x)"
    assert tracing.op_name(name) == "copy.435"
    assert tracing.stable_name(name) == "copy"
    assert tracing.label(name) == "copy.435 f32[5,40000,164]"
    assert tracing.stable_name("weighted_agg.7") == "weighted_agg"
