"""Profiling marks of the fleet runner (repro.diagnostics.spans).

The fused epoch's layer scopes reach the compiled program, and
``scope_tables()`` maps its instructions to them from the program that
ran; ``subscope_tables()`` does the same for the DDPG sub-scopes, which
no DQN instruction carries; the compile registry records a compile once
and a cache hit never; a profiled job nests its prepare, dispatch and
pull spans inside one job span on one host thread."""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import make_agent
from repro.core.agent import prepare_fleet, run_online_fleet
from repro.diagnostics import (LAYERS, SUBSCOPES, note_compile,
                               scope_tables, spans, subscope_tables)
from repro.dsdps import SchedulingEnv, apps, scenarios
from repro.launch import compile_cache

F, T = 2, 3


@pytest.fixture
def registry(monkeypatch):
    """A fresh compile registry, so programs other tests compiled in this
    process are neither counted nor compiled again here."""
    fresh: dict = {}
    monkeypatch.setattr(spans, "_COMPILED", fresh)
    return fresh


def _job(agent_name):
    topo = apps.continuous_queries("small")
    env = SchedulingEnv(topo, apps.default_workload(topo))
    agent = make_agent(agent_name, env)
    fleet = scenarios.build("mixed", env, F, seed=0)
    states = agent.init_fleet(jax.random.PRNGKey(0), F, env_params=fleet,
                              env=env)

    def run(seed, states):
        keys = jax.random.split(jax.random.PRNGKey(seed), F)
        return run_online_fleet(keys, env, agent, states, T=T,
                                env_params=fleet)[0]

    return env, agent, fleet, states, run


@pytest.fixture(scope="module")
def job():
    return _job("dqn")


@pytest.fixture(scope="module")
def ddpg_job():
    return _job("ddpg")


def test_one_table_maps_every_layer(registry, job):
    *_, states, run = job
    jax.clear_caches()                     # the call below must compile
    run(1, states)
    assert len(registry) == 1
    tables = scope_tables()
    assert list(tables) == ["jit__fleet_fn"]
    found = set(tables["jit__fleet_fn"].values())
    assert set(LAYERS) <= found
    assert None in found                   # the key split stays unscoped


def test_subscope_table_names_the_knn_beam_and_the_target(registry,
                                                          ddpg_job):
    *_, states, run = ddpg_job
    jax.clear_caches()
    run(1, states)
    layers = scope_tables()["jit__fleet_fn"]
    subs = subscope_tables()["jit__fleet_fn"]
    assert subs.keys() == layers.keys()
    pairs = {(layers[n], s) for n, s in subs.items() if s}
    # the select's beam, the target's beam inside the target, the rest of
    # the target
    assert {("agent_select", "knn_projection"),
            ("agent_update", "critic_target/knn_projection"),
            ("agent_update", "critic_target")} <= pairs
    assert {layer for layer, _ in pairs} <= {"agent_select",
                                             "agent_update", None}


def test_dqn_program_carries_no_subscope(registry, job):
    """The DQN fleet program reaches neither the K-NN beam nor a critic
    target, so the sub-scopes leave its instructions, and its layer
    table, as they were."""
    env, agent, fleet, states, run = job
    jax.clear_caches()
    run(1, states)
    (program, statics, _, _), = registry
    keys = jax.random.split(jax.random.PRNGKey(3), F)
    args = prepare_fleet(keys, env, states, None, fleet, None)[:4]
    text = program.lower(*args, **dict(statics)).compile().as_text()
    assert not any(name in text for name in SUBSCOPES)
    assert set(subscope_tables()["jit__fleet_fn"].values()) == {None}
    assert scope_tables()["jit__fleet_fn"] == spans.parse_hlo(text)[1]


def test_same_shapes_record_nothing_new(registry, job):
    *_, states, run = job
    jax.clear_caches()
    states = run(1, states)
    assert len(registry) == 1
    run(2, states)
    assert len(registry) == 1


def test_table_comes_from_the_program_that_ran(registry, job):
    """The registry lowers from recorded shapes; the executed program,
    lowered from the real arguments, has the same instructions."""
    env, agent, fleet, states, run = job
    jax.clear_caches()
    run(1, states)
    (program, statics, _, _), = registry
    keys = jax.random.split(jax.random.PRNGKey(3), F)
    args = prepare_fleet(keys, env, states, None, fleet, None)[:4]
    text = program.lower(*args, **dict(statics)).compile().as_text()
    assert spans.parse_hlo(text) == ("jit__fleet_fn",
                                     scope_tables()["jit__fleet_fn"])


@pytest.fixture
def persistent_cache(tmp_path, monkeypatch):
    """JAX's persistent compile cache in ``tmp_path``, caching every
    program, for the test's extent."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_compilation_cache_include_metadata_in_key",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cc.reset_cache()
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_table_reads_scopes_past_a_stale_cache_entry(registry,
                                                    persistent_cache):
    """An unscoped lowering of the same program fills a cache entry first.
    Without the metadata in the key the scoped program would load that
    executable; with the cache on as the entry points turn it on, the
    executable that runs, and so the table, has the scopes."""
    def program(scoped):
        def f(x):
            with (jax.named_scope("env_step") if scoped
                  else contextlib.nullcontext()):
                return jnp.sin(x) * 2
        return jax.jit(f)

    x = jnp.ones(8)
    program(False)(x)                      # fills the cache entry
    compile_cache.enable_compile_cache()
    scoped = program(True)
    before = spans._cache_size(scoped)
    scoped(x)
    note_compile(scoped, before, (x,), {})
    assert "env_step" in scoped.lower(x).compile().as_text()
    assert "env_step" in scope_tables()["jit_f"].values()


class _Stub:
    """A program whose trace cache holds ``size`` entries."""

    def __init__(self, size):
        self.size = size

    def _cache_size(self):
        return self.size


def test_cache_hit_records_nothing(registry):
    stub = _Stub(3)
    args = (np.zeros((2, 3), np.float32),)
    note_compile(stub, 3, args, {"T": 5})
    assert registry == {}
    stub.size = 4                          # the call compiled
    note_compile(stub, 3, args, {"T": 5})
    assert len(registry) == 1
    (key,) = registry
    assert key[0] is stub and key[1] == (("T", 5),)
    assert key[3] == (jax.ShapeDtypeStruct((2, 3), np.float32),)


def test_layer_is_the_first_scope_on_the_path():
    path = "jit(f)/vmap()/while/body/{}/transpose(jvp(agent_select))/dot"
    assert spans.layer_of(path.format("agent_update")) == "agent_update"
    assert spans.layer_of("jit(f)/vmap()/while/body/add") is None
    assert spans.layer_of("jit(f)/env_stepper/add") is None


def test_subscope_is_the_path_of_subscopes():
    body = "jit(f)/vmap()/while/body/agent_update/"
    assert spans.subscope_of(
        body + "critic_target/vmap(jit(knn_actions_jax))/knn_projection/"
        "top_k") == "critic_target/knn_projection"
    assert spans.subscope_of(body + "critic_target/dot") == "critic_target"
    assert spans.subscope_of(
        "jit(f)/agent_select/jit(knn_actions_jax)/knn_projection/eq"
    ) == "knn_projection"
    assert spans.subscope_of(body + "transpose(jvp(dot))") is None


def test_parse_hlo_reads_names_and_scopes():
    text = "\n".join([
        "HloModule jit__fleet_fn, is_scheduled=true, "
        "entry_computation_layout={(f32[2]{0})->f32[2]{0}}",
        "%fused_computation.1 (param_0: f32[2]) -> f32[2] {",
        '  ROOT %add.3 = f32[2]{0} add(%p, %p), metadata={op_name='
        '"jit(f)/while/body/env_step/add"}',
        "}",
        "ENTRY %main.5 (x.1: f32[2]) -> f32[2] {",
        '  %x.1 = f32[2]{0} parameter(0), metadata={op_name="x"}',
        "  %fusion.12 = f32[2]{0} fusion(%x.1), kind=kLoop, "
        'calls=%fused_computation.1, metadata={op_name='
        '"jit(f)/while/body/agent_update/mul"}',
        "  ROOT %copy.2 = f32[2]{0} copy(%fusion.12)",
        "}",
    ])
    assert spans.parse_hlo(text) == ("jit__fleet_fn", {
        "add.3": "env_step", "x.1": None, "fusion.12": "agent_update",
        "copy.2": None})


def test_profiled_job_nests_its_spans(tmp_path, job):
    *_, states, run = job
    run(1, states)                         # compile outside the profile
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(run(2, states))
    (path,) = tmp_path.rglob("*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(path))
    lines = [[(e.start_ns, e.end_ns, e.name) for e in line.events]
             for plane in pd.planes if plane.name == "/host:CPU"
             for line in plane.lines]
    (events,) = [ev for ev in lines
                 if any(n == "repro.fleet.job" for *_, n in ev)]
    (job_span,) = [(s, e) for s, e, n in events if n == "repro.fleet.job"]
    for name in ("repro.fleet.prepare", "repro.fleet.dispatch",
                 "repro.fleet.pull"):
        inner = [(s, e) for s, e, n in events if n == name]
        assert inner, name
        assert all(job_span[0] <= s and e <= job_span[1] for s, e in inner)

