"""CPU tests of the readers of the program's own marks (bench/scopes.py)
on hand-made traces.

  JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import scopes  # noqa: E402
from reduce_trace import Device, Trace  # noqa: E402

COUNTERS = {"jobs": 2, "epochs": 5}


class Run:
    def __init__(self, trace):
        self.trace, self.counters = trace, COUNTERS


def epoch_trace(extra_ops=()) -> Trace:
    """One fleet program run, 100-200 ns, whose ``%while.1`` holds an
    env-step op (20 ns) and an update op (30 ns)."""
    ops = [(100, 200, "%while.1"), (110, 130, "%fusion.1"),
           (140, 170, "%fusion.2"), *extra_ops]
    dev = Device("/device:TPU:0", ops=ops,
                 modules=[(100, 200, "jit__fleet_fn(123)")])
    return Trace(devices=[dev], host=[(0, 1000, "bench.window")])


TABLE = {"while.1": None, "fusion.1": "env_step", "fusion.2": "agent_update"}


@pytest.fixture
def table(monkeypatch):
    import repro.diagnostics
    monkeypatch.setattr(repro.diagnostics, "scope_tables",
                        lambda: {"jit__fleet_fn": dict(TABLE)})


def test_self_time_counts_nested_ops_once(table):
    run = Run(epoch_trace())
    per = COUNTERS["jobs"] * COUNTERS["epochs"]
    assert scopes.layer_ms(run, "env_step") == pytest.approx(20 / 1e6 / per)
    assert scopes.layer_ms(run, "agent_update") == pytest.approx(30 / 1e6 / per)
    assert scopes.layer_ms(run, "agent_select") == 0.0
    # the while keeps only its own 50 ns, outside every layer
    assert scopes.program_self_ns(run.trace, TABLE) == {
        None: 50, "env_step": 20, "agent_update": 30}


def test_ops_outside_the_program_are_left_out(table):
    run = Run(epoch_trace(extra_ops=[(300, 400, "%fusion.1")]))
    assert scopes.program_self_ns(run.trace, TABLE)["env_step"] == 20


def test_table_naming_under_99_percent_gives_none(table):
    # an op the table does not know takes 2 of the program's 100 ns
    run = Run(epoch_trace(extra_ops=[(180, 182, "%fusion.9")]))
    assert scopes.program_self_ns(run.trace, TABLE) is None
    assert scopes.layer_ms(run, "env_step") is None


def test_a_program_without_the_table_gives_none(monkeypatch):
    import repro.diagnostics
    monkeypatch.setattr(repro.diagnostics, "scope_tables", lambda: {})
    assert scopes.layer_ms(Run(epoch_trace()), "env_step") is None


def idle_trace(host) -> Trace:
    """The device busy 0-300 and 400-1000 ns: one gap, 300-400."""
    dev = Device("/device:TPU:0", ops=[(0, 300, "%a"), (400, 1000, "%b")])
    return Trace(devices=[dev], host=[(0, 1000, "bench.window"), *host])


def test_gap_goes_to_the_innermost_repro_span():
    host = [(0, 900, "repro.fleet.job"), (300, 400, "repro.fleet.pull"),
            (310, 390, "$_array.py:631 _value"), (305, 395, "bench.job")]
    run = Run(idle_trace(host))
    assert scopes.span_idle_ms(run, "repro.fleet.pull") == pytest.approx(
        100 / 1e6 / COUNTERS["jobs"])
    assert scopes.span_idle_ms(run, "repro.fleet.job") == 0.0


def test_gap_outside_a_nested_span_goes_to_its_parent():
    host = [(0, 900, "repro.fleet.job"), (100, 200, "repro.fleet.prepare")]
    assert scopes.idle_by_span(idle_trace(host)) == {"repro.fleet.job": 100}


def test_gap_across_a_span_edge_is_split_there():
    # prepare ends and the dispatch starts inside the 300-400 gap, whose
    # last 20 ns lie under no repro span at all
    host = [(0, 380, "repro.fleet.job"), (100, 330, "repro.fleet.prepare"),
            (330, 370, "repro.fleet.dispatch"), (320, 340, "$api.py:1 f")]
    assert scopes.idle_by_span(idle_trace(host)) == {
        "repro.fleet.prepare": 30, "repro.fleet.dispatch": 40,
        "repro.fleet.job": 10, None: 20}


def test_gap_before_the_first_span_goes_to_none():
    host = [(350, 900, "repro.fleet.pull")]
    assert scopes.idle_by_span(idle_trace(host)) == {
        None: 50, "repro.fleet.pull": 50}


def test_no_repro_spans_gives_none():
    run = Run(idle_trace([(310, 390, "$_array.py:631 _value")]))
    assert scopes.span_idle_ms(run, "repro.fleet.pull") is None
