#!/usr/bin/env python3
"""Smoke run of the control loop on a TPU: train, resume, serve, kernel.

Everything runs in this one process, through the launcher users call
(``repro.launch.drl_control.main``) or the fleet runner it wraps:

  A  train a ``cq_large`` ddpg fleet (N=100 executors on M=10 machines,
     128 lanes of the ``mixed`` scenario, default offline pretraining,
     100 online epochs) sharded over the chip mesh (the donated
     shard_map program), snapshotting asynchronously every 50 epochs;
     then ``--resume`` from the newest snapshot and run to epoch 150;
  B  in that resumed call, serve 256 decision requests from the trained
     policy through the batched ``ControlPlane`` (buffers donated);
  C  one ddpg fleet epoch at the same width with the Pallas K-NN kernel:
     its compiled program holds ``tpu_custom_call`` and selects the same
     actions as the XLA path (two row reductions) on the same inputs;
  D  a short ``graph_policy`` fleet over the structural ``dag_shapes``
     env.

``--chips 4`` runs only this: a 512-lane ``cq_large`` ddpg fleet sharded
over four chips, and one chip running every fourth lane, once from the
same per-lane keys and once from the same initial states.  The compared
lanes' reward traces over the first chunk must agree within rtol 1e-5,
and every chip must show device memory in use.

Each phase prints its wall time, its compile time (XLA's backend
compiles only: jax reports a nested jit's tracing inside its caller's,
so summing trace events would count them twice) and the devices'
``peak_bytes_in_use``.  The
last line of stdout is ``{"ok": true, "device": {...}}``.  The script
exits non-zero without that line when no TPU is visible, when the
repository's ``src/`` is not next to it, or when any phase fails.
Checkpoints go to ``.chip_smoke/`` next to this file, which the script
empties first and deletes at the end.

  python3 chip_smoke.py              # one chip
  python3 chip_smoke.py --chips 4    # a four-chip host
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import shutil
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent
OUT = ROOT / ".chip_smoke"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_s = [0.0]


class SmokeError(RuntimeError):
    """A phase ran but its output is wrong."""


def _on_duration(event: str, duration: float, **_) -> None:
    if event == COMPILE_EVENT:
        _compile_s[0] += duration


def _check_run(summary: dict, lanes: int, sharded: bool) -> None:
    """A launcher run trained every lane (sharded over every local device
    when asked) and scored finite latencies."""
    import jax
    if sharded and summary.get("sharded_devices") != len(jax.local_devices()):
        raise SmokeError(f"fleet ran over {summary.get('sharded_devices')} "
                         f"device(s), not the {len(jax.local_devices())} "
                         f"local one(s): the runner fell back to vmap")
    finals, rrs = summary["final_ms"], summary["round_robin_ms"]
    if len(finals) != lanes or len(rrs) != lanes:
        raise SmokeError(f"scored {len(finals)} lanes, expected {lanes}")
    if not all(math.isfinite(x) and x > 0 for x in finals + rrs):
        raise SmokeError("non-finite or non-positive lane latency")


def _latency(summary: dict) -> dict:
    final = sum(summary["final_ms"]) / len(summary["final_ms"])
    rr = sum(summary["round_robin_ms"]) / len(summary["round_robin_ms"])
    return {"final_ms": final, "round_robin_ms": rr,
            "improvement": 1.0 - final / rr}


def _launch(app: str, agent: str, scenario: str, fleet: int, epochs: int,
            seed: int, extra) -> dict:
    from repro.launch import drl_control
    return drl_control.main(
        ["--app", app, "--agent", agent, "--scenario", scenario,
         "--fleet", str(fleet), "--epochs", str(epochs), "--seed", str(seed),
         *extra])


def phase_train(ck_dir, *, app="cq_large", fleet=128, epochs=100, every=50,
                seed=0, extra=()) -> dict:
    """A: a fresh sharded, checkpointed ddpg fleet run."""
    from repro.launch.multihost import published_epochs
    s = _launch(app, "ddpg", "mixed", fleet, epochs, seed,
                ["--sharded", "--checkpoint-dir", str(ck_dir),
                 "--checkpoint-every", str(every), *extra])
    _check_run(s, fleet, sharded=True)
    snaps = published_epochs(ck_dir)
    if len(snaps) < 2 or snaps[-1] != epochs:
        raise SmokeError(f"expected >= 2 snapshots ending at epoch {epochs}, "
                         f"found {snaps}")
    return {**_latency(s), "snapshots": snaps}


def phase_resume_serve(ck_dir, *, app="cq_large", fleet=128, start=100,
                       epochs=150, every=50, serve=256, seed=0,
                       extra=()) -> dict:
    """A (resume) + B: continue from the newest snapshot, then serve
    ``serve`` decisions from the trained policy."""
    s = _launch(app, "ddpg", "mixed", fleet, epochs, seed,
                ["--sharded", "--checkpoint-dir", str(ck_dir),
                 "--checkpoint-every", str(every), "--resume",
                 "--serve", str(serve), *extra])
    if s.get("start_epoch") != start:
        raise SmokeError(f"resumed at epoch {s.get('start_epoch')}, "
                         f"expected the newest snapshot {start}")
    _check_run(s, fleet, sharded=True)
    stats = s["serve"]
    if sum(v["n"] for v in stats.values()) != serve:
        raise SmokeError(f"served {stats}, expected {serve} decisions")
    for v in stats.values():
        if not (math.isfinite(v["p50_ms"]) and math.isfinite(v["p99_ms"])):
            raise SmokeError(f"non-finite serving latency {v}")
    return {**_latency(s), "serve_s": s["serve_s"],
            "serve": {k: {"n": v["n"], "p50_ms": v["p50_ms"],
                          "p99_ms": v["p99_ms"]} for k, v in stats.items()}}


def phase_pallas(*, app="cq_large", fleet=128, seed=0,
                 require_custom_call=True) -> dict:
    """C: one fleet epoch through the Pallas K-NN kernel against the same
    epoch through the XLA row top-2, from the same states and keys.  A CPU test
    swaps in the interpreted kernel and passes
    ``require_custom_call=False``."""
    import jax
    import numpy as np
    from repro.core import make_agent
    from repro.core.agent import _fleet_program, prepare_fleet
    from repro.launch.drl_control import build_env
    env = build_env(app)
    key = jax.random.PRNGKey(seed)
    runs = {}
    for name, pallas in (("xla", False), ("pallas", True)):
        agent = make_agent("ddpg", env, use_pallas_knn=pallas)
        keys = jax.random.split(jax.random.fold_in(key, 2), fleet)
        keys, states, env_states, params, _, axes, _ = prepare_fleet(
            keys, env, agent.init_fleet(key, fleet), None, None, None)
        compiled = _fleet_program.lower(
            keys, states, env_states, params, env=env, agent=agent, T=1,
            updates_per_epoch=1, explore=True, params_axes=axes).compile()
        _, env_states, _, rewards, _, _ = compiled(keys, states, env_states,
                                                   params)
        runs[name] = ("tpu_custom_call" in compiled.as_text(),
                      np.asarray(env_states.X), np.asarray(rewards))
    kernel_in = runs["pallas"][0]
    if require_custom_call and not kernel_in:
        raise SmokeError("no tpu_custom_call in the Pallas fleet program")
    if not np.array_equal(runs["pallas"][1], runs["xla"][1]):
        raise SmokeError("Pallas and XLA K-NN selected different actions")
    # the two programs fuse differently, so rewards may differ in the
    # last bits even for equal actions
    if not np.allclose(runs["pallas"][2], runs["xla"][2], rtol=1e-5):
        raise SmokeError("Pallas and XLA K-NN rewards differ")
    return {"tpu_custom_call": kernel_in, "lanes": fleet,
            "actions_equal": True}


def phase_structural(*, fleet=12, epochs=40, seed=0) -> dict:
    """D: the graph_policy agent over the structural DAG-shape fleet."""
    s = _launch("structural", "graph_policy", "dag_shapes", fleet, epochs,
                seed, [])
    _check_run(s, fleet, sharded=False)
    return _latency(s)


def phase_four_chip(*, app="cq_large", fleet=512, compare=128, epochs=25,
                    seed=0) -> dict:
    """The sharded fleet over every local device against one device
    running every ``fleet // compare``-th lane, twice: from the same
    per-lane keys (each side computes its lanes' initial states with the
    same jitted init, at its own width and placement), and from the same
    initial states (the one device starts from the sharded side's), so
    that only the fleet program's placement differs."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding
    from repro.core import make_agent, reset_fleet_states, run_online_fleet
    from repro.launch.drl_control import build_env
    from repro.launch.mesh import make_fleet_mesh
    from repro.sharding.fleet import fleet_spec
    env = build_env(app)
    agent = make_agent("ddpg", env)
    params = env.default_params()
    k_init, k_env, k_run = (jax.random.split(k, fleet) for k in
                            jax.random.split(jax.random.PRNGKey(seed), 3))
    lanes = np.arange(0, fleet, fleet // compare)   # spans every shard
    wide_mesh, narrow_mesh = make_fleet_mesh(), make_fleet_mesh(1)

    def start(mesh, ki, ke):
        return jax.jit(  # jaxguard: disable=JG002  (one init per mesh)
            lambda a, b: (jax.vmap(agent.init)(a),
                          reset_fleet_states(b, env, params)),
            out_shardings=NamedSharding(mesh, fleet_spec(mesh)))(ki, ke)

    def run(mesh, keys, states, env_states):
        t0 = time.perf_counter()
        _, h = run_online_fleet(keys, env, agent, states, T=epochs,
                                env_states=env_states, mesh=mesh)
        return np.asarray(h.rewards), time.perf_counter() - t0

    def rel_diff(narrow, wide):
        ref = wide[lanes]
        return np.abs(narrow - ref) / np.maximum(np.abs(ref), 1e-30)

    wide, wide_s = run(wide_mesh, k_run, *start(wide_mesh, k_init, k_env))
    narrow, narrow_s = run(narrow_mesh, k_run[lanes],
                           *start(narrow_mesh, k_init[lanes], k_env[lanes]))
    from_keys = rel_diff(narrow, wide)
    states, env_states = start(wide_mesh, k_init, k_env)
    # sliced before the sharded run, which donates its carries
    pick = lambda tree: jax.tree.map(lambda x: x[lanes], tree)  # noqa: E731
    narrow_in = (k_run[lanes], pick(states), pick(env_states))
    wide, _ = run(wide_mesh, k_run, states, env_states)
    narrow, _ = run(narrow_mesh, *narrow_in)
    from_states = rel_diff(narrow, wide)
    for name, rel in (("keys", from_keys), ("states", from_states)):
        if not rel.max() <= 1e-5:
            raise SmokeError(
                f"from the same {name}: "
                f"{int((rel > 1e-5).any(axis=1).sum())} of {len(lanes)} "
                f"lanes differ from the sharded run (max relative "
                f"difference {rel.max()}, first at epoch "
                f"{int(np.argmax((rel > 1e-5).any(axis=0)))}; from the "
                f"same keys {from_keys.max()}, states {from_states.max()})")
    stats = [d.memory_stats() for d in wide_mesh.devices.flat]
    peaks = [s["peak_bytes_in_use"] if s else None for s in stats]
    # the CPU reports no memory stats; a chip must report its own use
    if wide_mesh.devices.flat[0].platform == "tpu" and \
            not all(p and p > 0 for p in peaks):
        raise SmokeError(f"a chip held no memory: peaks {peaks}")
    return {"devices": int(wide_mesh.devices.size), "lanes": fleet,
            "compared_lanes": len(lanes), "epochs": epochs,
            "max_rel_diff_from_keys": float(from_keys.max()),
            "max_rel_diff_from_states": float(from_states.max()),
            "per_device_peak_bytes": peaks,
            "sharded_wall_s": wide_s, "one_chip_wall_s": narrow_s}


def _peak_bytes() -> list:
    import jax
    stats = [d.memory_stats() for d in jax.local_devices()]
    return [s.get("peak_bytes_in_use") if s else None for s in stats]


def _run_phase(name: str, fn) -> bool:
    print(f"--- phase {name} ...", flush=True)
    c0, t0 = _compile_s[0], time.perf_counter()
    try:
        info = fn()
    except (Exception, SystemExit):
        traceback.print_exc()
        print(f"phase {name}: FAILED after "
              f"{time.perf_counter() - t0:.3f} s", flush=True)
        return False
    print(f"phase {name}: ok  wall {time.perf_counter() - t0:.3f} s  "
          f"compile {_compile_s[0] - c0:.3f} s  "
          f"peak_bytes_in_use {_peak_bytes()}  {json.dumps(info)}",
          flush=True)
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases A-D on one chip; 4: only the sharded "
                         "fleet across four chips against one chip")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke.py needs the repository's src/repro next to it "
              f"(looked in {ROOT})", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke.py needs {args.chips} TPU chip(s); jax sees "
              f"{len(devices)} {dev.platform} device(s)", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}")
    print(f"compile cache: {cache}")
    shutil.rmtree(OUT, ignore_errors=True)
    OUT.mkdir()
    ck = OUT / "fleet_ck"
    if args.chips == 4:
        phases = [("four-chip sharded fleet vs one chip", phase_four_chip)]
    else:
        phases = [("A train", lambda: phase_train(ck)),
                  ("A resume + B serve", lambda: phase_resume_serve(ck)),
                  ("C pallas knn", phase_pallas),
                  ("D structural", phase_structural)]
    ok = [_run_phase(name, fn) for name, fn in phases]
    shutil.rmtree(OUT, ignore_errors=True)
    if not all(ok):
        print(f"chip smoke failed: {ok.count(False)} phase(s)",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
