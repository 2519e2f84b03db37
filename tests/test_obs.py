"""The program's tracing system (``repro.obs``): spans record nothing
while off and nested spans with their parents while on; counters group,
snapshot and reset; the kernel route counters are obs counters; the
federated entry points and the set-up count and span what they do."""
import json

import jax
import numpy as np
import pytest

from repro import obs
from repro.fed import FederatedProgram, setup_federation
from repro.gan.ctgan import CTGANConfig
from repro.kernels import ops
from repro.tabular import ColumnSpec


@pytest.fixture(autouse=True)
def clean():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def test_off_is_one_shared_no_op():
    a, b = obs.span("repro.test.a"), obs.span("repro.test.b")
    assert a is b
    with a:
        with b:
            pass
    assert obs.snapshot()["spans"] == []


def test_nested_spans_record_parents():
    obs.enable()
    with obs.span("repro.test.outer"):
        with obs.span("repro.test.first"):
            pass
        with obs.span("repro.test.second"):
            with obs.span("repro.test.inner"):
                pass
    with obs.span("repro.test.after"):
        pass
    spans = obs.snapshot()["spans"]
    assert [(s[0], s[3]) for s in spans] == [
        ("repro.test.outer", None), ("repro.test.first", 0),
        ("repro.test.second", 0), ("repro.test.inner", 2),
        ("repro.test.after", None)]
    for name, start, end, parent in spans:
        assert start <= end
        if parent is not None:
            assert spans[parent][1] <= start and end <= spans[parent][2]


def test_span_names_carry_the_program_prefix():
    obs.enable()
    with pytest.raises(ValueError, match="repro."):
        obs.span("setup")


def test_counters_group_snapshot_and_reset():
    c = obs.counter("test_group")
    assert obs.counter("test_group") is c
    c["a"] += 2
    obs.counter("test_other")["b"] += 1
    snap = obs.snapshot()
    assert snap["counters"]["test_group"] == {"a": 2}
    assert snap["counters"]["test_other"] == {"b": 1}
    json.dumps(snap)
    obs.reset()
    assert obs.counter("test_group") is c and not c


def test_kernel_route_counters_are_obs_counters():
    assert ops.DISPATCH_COUNTS is obs.counter("kernel_routes")
    assert ops.INTERPRET_COUNTS is obs.counter("kernel_interpret")


def _federation():
    rng = np.random.default_rng(0)
    schema = [ColumnSpec("x", "continuous", max_modes=2),
              ColumnSpec("c", "categorical")]
    parts = [np.stack([rng.normal(size=40 + 8 * i),
                       rng.integers(0, 3, 40 + 8 * i)], 1) for i in range(2)]
    cfg = CTGANConfig(batch_size=8, gen_hidden=(16,), disc_hidden=(16,),
                      pac=2, z_dim=4)
    return cfg, parts, schema


def test_setup_spans_in_order():
    cfg, parts, schema = _federation()
    obs.enable()
    setup_federation(parts, schema, cfg, seed=0)
    names = [s[0] for s in obs.snapshot()["spans"]]
    assert names == ["repro.setup.vgm_fit", "repro.setup.encoder_init",
                     "repro.setup.divergence", "repro.setup.encode",
                     "repro.setup.states"]


def test_entry_points_count_dispatches_rounds_and_traces():
    cfg, parts, schema = _federation()
    fe = setup_federation(parts, schema, cfg, seed=0, weighting="uniform")
    prog = FederatedProgram(cfg, fe.spans, fe.cond_spans, batch=8,
                            local_steps=1, weighting="uniform")
    keys = prog.fold_round_keys(jax.random.PRNGKey(1), 0, 3)
    args = (fe.states, fe.tables, fe.S, fe.n_rows)
    obs.reset()
    obs.enable()
    states, _ = prog.run(*args, keys)
    prog.run(states, *args[1:], keys)
    prog.round(states, *args[1:], keys[0])
    snap = obs.snapshot()
    assert snap["counters"]["fed"] == {"dispatches": 3, "rounds": 7}
    # the second run reuses the first one's trace
    assert snap["counters"]["jit_traces"] == {"fed.run": 1, "fed.round": 1}
    assert [s[0] for s in snap["spans"]] == ["repro.fed.run"] * 2 + [
        "repro.fed.round"]
    # lowering reuses the trace and dispatches nothing
    assert "while" in prog.run.lower(*args, keys).as_text()
    assert obs.counter("jit_traces")["fed.run"] == 1
    assert obs.counter("fed")["dispatches"] == 3


def test_vgm_fit_counts_its_traces():
    from repro.tabular.vgm import fit_vgm
    x = np.linspace(0, 1, 37, dtype=np.float32)
    fit_vgm(x, jax.random.PRNGKey(0), max_modes=3)
    fit_vgm(x + 1, jax.random.PRNGKey(1), max_modes=3)
    assert obs.counter("jit_traces")["vgm.fit"] == 1
