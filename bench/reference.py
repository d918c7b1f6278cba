"""Plain reference of the scheduler under test, written from the paper.

Nothing here imports the program.  The deployment (topology, cluster,
workload, measurement) is read from the configuration file under
``bench/configs/``; the agent is Algorithm 1 of arXiv:1803.01016 §3.2.1
(DDPG over the K nearest feasible assignments) with the hyper-parameters
the configuration states.  Everything runs in float32 with matrix products
at ``highest`` precision.

The reference is teacher-forced where the program makes a discrete
choice: it is given the assignment the program chose in each decision
epoch, scores that choice against its own K nearest candidates and its
own critic, and then applies the program's choice to its own simulator and
its own agent.  A near-tie between two candidates then costs a small gap
and not a diverged trajectory.
"""
from __future__ import annotations

import itertools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


# --------------------------------------------------------------------------
# The deployment: a queueing-network model of a Storm cluster.
# --------------------------------------------------------------------------
class Deployment:
    """Executor-level arrays of one configuration's deployment (numpy)."""

    def __init__(self, cfg: dict):
        d = cfg["deployment"]
        comps, edges = d["components"], d["edges"]
        index = {c["name"]: i for i, c in enumerate(comps)}
        starts, n = [], 0
        for c in comps:
            starts.append(n)
            n += c["executors"]
        self.N = n
        self.members = [list(range(s, s + c["executors"]))
                        for s, c in zip(starts, comps)]
        cal = d["calibration"]
        rng = np.random.default_rng(cal["seed"])
        R = np.zeros((n, n))
        for e in edges:
            src, dst = index[e["src"]], index[e["dst"]]
            p = comps[dst]["executors"]
            if e["grouping"] == "shuffle":
                frac = np.full(p, 1.0 / p)
            elif e["grouping"] == "fields":
                w = np.arange(1, p + 1, dtype=np.float64) ** (-e["skew"])
                w = rng.permutation(w)
                frac = w / w.sum()
            else:
                raise ValueError(f"grouping {e['grouping']!r}")
            for i in self.members[src]:
                R[i, self.members[dst]] += comps[src]["selectivity"] * frac
        self.R = R
        self.flow = np.linalg.inv(np.eye(n) - R.T)
        nominal = np.concatenate([[c["cpu_ms_per_tuple"]] * c["executors"]
                                  for c in comps])
        sig = cal["executor_jitter_sigma"]
        jitter = np.exp(np.random.default_rng(cal["seed"] + 104729).normal(
            -sig ** 2 / 2, sig, size=n))
        self.service = nominal * jitter
        self.tuple_bytes = np.concatenate([[c["tuple_bytes"]] * c["executors"]
                                           for c in comps]).astype(float)
        self.spouts = np.concatenate([self.members[i] for i, c
                                      in enumerate(comps) if c.get("spout")])
        self.comp_of = np.concatenate([[i] * c["executors"]
                                       for i, c in enumerate(comps)])
        self.n_comp = len(comps)
        down = [set() for _ in comps]
        for e in edges:
            down[index[e["src"]]].add(index[e["dst"]])
        self.down = [sorted(s) for s in down]
        self.rev_order = _reverse_topological(self.down)
        self.acker_ms = cal["acker_ms"]
        self.cluster = d["cluster"]
        self.M = self.cluster["machines"]
        self.S = len(self.spouts)
        self.base_rates = np.full(self.S, d["spout_rate_per_executor"])
        self.speed = np.asarray(self.cluster["speeds"], float)
        self.noise_sigma = d["measurement"]["noise_sigma"]
        self.readings = d["measurement"]["readings"]
        self.jitter = d["workload_jitter"]
        self.revert = d["workload_revert"]

    @property
    def state_dim(self) -> int:
        return self.N * self.M + self.S

    def round_robin(self) -> np.ndarray:
        return np.eye(self.M, dtype=np.float32)[np.arange(self.N) % self.M]


def _reverse_topological(down) -> list[int]:
    seen, order = set(), []

    def visit(c):
        if c in seen:
            return
        seen.add(c)
        for d in down[c]:
            visit(d)
        order.append(c)          # every downstream component comes first
    for c in range(len(down)):
        visit(c)
    return order


def lane_params(dep: Deployment, scenario: dict, fleet: int) -> dict:
    """Per-lane numeric parameters of the 'mixed' scenario fleet: service
    and rate jitter on every lane, and lane i % 4 == 1 / 2 / 3 adds a
    straggler / a diurnal rate scale / high measurement noise."""
    if scenario["name"] != "mixed":
        raise ValueError(f"scenario {scenario['name']!r}")
    key = jax.random.PRNGKey(scenario["seed"])
    svc_sig, rate_sig = scenario["service_sigma"], scenario["rate_sigma"]
    service, rates, speed, noise = [], [], [], []
    for i in range(fleet):
        k_svc, k_rate = jax.random.split(jax.random.fold_in(key, i))
        z_s = jax.random.normal(k_svc, (dep.N,))
        z_r = jax.random.normal(k_rate, (dep.S,))
        s = jnp.asarray(dep.service, jnp.float32) * jnp.exp(
            z_s * svc_sig - 0.5 * svc_sig ** 2)
        r = jnp.asarray(dep.base_rates, jnp.float32) * jnp.exp(
            z_r * rate_sig - 0.5 * rate_sig ** 2)
        sp = np.asarray(dep.speed, np.float32).copy()
        nz = dep.noise_sigma
        if i % 4 == 1:
            sp[i % dep.M] = scenario["straggler_factor"]
        elif i % 4 == 2:
            r = r * (1.0 + scenario["diurnal_amplitude"]
                     * jnp.sin(2.0 * jnp.pi * i / max(fleet, 1)))
        elif i % 4 == 3:
            nz = scenario["high_noise_sigma"]
        service.append(s)
        rates.append(r)
        speed.append(sp)
        noise.append(nz)
    return {"service": jnp.stack(service), "base_rates": jnp.stack(rates),
            "speed": jnp.asarray(np.stack(speed)),
            "noise_sigma": jnp.asarray(noise, jnp.float32)}


def _congestion(rho):
    cap = 0.97
    return 1.0 / (1.0 - cap * jnp.tanh(rho / cap))


def latency_ms(dep: Deployment, X, w, service, speed):
    """Steady-state mean end-to-end tuple time (ms) of assignment X [N, M]
    under spout rates w [S]: flow solve, CPU contention with ser/deser
    burn and component-mixing interference, processor-sharing sojourn,
    NIC-contended transfers, and the completion-time recursion with a max
    over downstream branches."""
    c = dep.cluster
    dot = partial(jnp.matmul, precision=HI)
    R = jnp.asarray(dep.R, jnp.float32)
    tb = jnp.asarray(dep.tuple_bytes, jnp.float32)
    w_full = jnp.zeros(dep.N).at[dep.spouts].set(w)
    lam = dot(jnp.asarray(dep.flow, jnp.float32), w_full)
    same = dot(X, X.T)
    edge = lam[:, None] * R
    cross = edge * (1.0 - same)
    ser = c["ser_base_ms"] + tb * c["ser_ms_per_kb"] / 1024.0
    on = lambda v: (X * v[:, None]).sum(0)      # noqa: E731  per machine
    demand = (on(lam * service / 1e3) + on(cross.sum(1) * ser / 1e3)
              + on((cross * ser[:, None]).sum(0) / 1e3))
    used = (X.sum(0) > 0).astype(jnp.float32)
    comp = jax.nn.one_hot(dep.comp_of, dep.n_comp)
    kinds = jnp.clip(dot(comp.T, X), 0.0, 1.0).sum(0)
    mix = 1.0 + c["mix_penalty"] * jnp.maximum(kinds - 1.0, 0.0)
    demand = demand * mix / speed + used * c["proc_overhead_cores"]
    g = _congestion(demand / c["cores"])
    s_eff = service * dot(X, g / speed)
    sojourn = s_eff * _congestion(lam * s_eff / 1e3)
    nic = c["nic_gbps"] * 1e9 / 8.0 / 1e3                 # bytes per ms
    bps = cross * tb[:, None]
    load = jnp.maximum(on(bps.sum(1)), on(bps.sum(0)))
    nic_g = dot(X, _congestion(load / (nic * 1e3)))
    d_edge = jnp.where(
        same > 0.5, c["local_base_ms"],
        c["net_base_ms"] + 2.0 * ser[:, None]
        + tb[:, None] / nic * 0.5 * (nic_g[:, None] + nic_g[None, :]))
    done = sojourn
    for ci in dep.rev_order:
        if not dep.down[ci]:
            continue
        src = np.asarray(dep.members[ci])
        best = None
        for dc in dep.down[ci]:
            dst = np.asarray(dep.members[dc])
            p = R[np.ix_(src, dst)]
            p = p / jnp.maximum(p.sum(1, keepdims=True), 1e-12)
            cost = (p * (d_edge[np.ix_(src, dst)] + done[dst][None, :])).sum(1)
            best = cost if best is None else jnp.maximum(best, cost)
        done = done.at[src].add(best)
    wp = jnp.maximum(w, 0.0)
    return (wp * done[dep.spouts]).sum() / jnp.maximum(wp.sum(), 1e-9) \
        + dep.acker_ms


# --------------------------------------------------------------------------
# The agent: actor-critic over the K nearest feasible assignments.
# --------------------------------------------------------------------------
def init_mlp(key, sizes):
    layers = []
    for k, (i, o) in zip(jax.random.split(key, len(sizes) - 1),
                         zip(sizes[:-1], sizes[1:])):
        lim = jnp.sqrt(6.0 / (i + o))
        layers.append((jax.random.uniform(k, (i, o), jnp.float32, -lim, lim),
                       jnp.zeros((o,), jnp.float32)))
    return layers


def mlp(layers, x):
    for li, (W, b) in enumerate(layers):
        x = jnp.matmul(x, W, precision=HI) + b
        if li < len(layers) - 1:
            x = jnp.tanh(x)
    return x


def init_ddpg(key, dep: Deployment, agent: dict) -> dict:
    ka, kc = jax.random.split(key)
    hid = tuple(agent["hidden"])
    na = dep.N * dep.M
    actor = init_mlp(ka, (dep.state_dim, *hid, na))
    critic = init_mlp(kc, (dep.state_dim + na, *hid, 1))
    zeros = lambda t: jax.tree.map(jnp.zeros_like, t)   # noqa: E731
    cap = agent["buffer"]
    return {"actor": actor, "critic": critic, "t_actor": actor,
            "t_critic": critic,
            "adam_a": (jnp.zeros((), jnp.int32), zeros(actor), zeros(actor)),
            "adam_c": (jnp.zeros((), jnp.int32), zeros(critic),
                       zeros(critic)),
            "S": jnp.zeros((cap, dep.state_dim)), "A": jnp.zeros((cap, na)),
            "Rw": jnp.zeros((cap,)), "S2": jnp.zeros((cap, dep.state_dim)),
            "ptr": jnp.zeros((), jnp.int32), "size": jnp.zeros((), jnp.int32),
            "epoch": jnp.zeros((), jnp.int32),
            "r_mean": jnp.zeros(()), "r_var": jnp.ones(()),
            "r_count": jnp.zeros((), jnp.int32)}


def actor_proto(actor, s, N, M):
    return jax.nn.sigmoid(mlp(actor, s)).reshape(N, M)


def critic_q(critic, s, a_flat):
    s = jnp.broadcast_to(s, a_flat.shape[:-1] + s.shape[-1:])
    return mlp(critic, jnp.concatenate([s, a_flat], -1))[..., 0]


def _flip_sets(pool: int, pair_pool: int, triple_pool: int) -> np.ndarray:
    """Candidate flip sets over the ``pool`` rows with the cheapest flips,
    in the configuration's order: none, every single row, every pair of
    the ``pair_pool`` cheapest, every triple of the ``triple_pool``
    cheapest; padded with -1."""
    sets = [()] + [(i,) for i in range(pool)]
    sets += list(itertools.combinations(range(min(pair_pool, pool)), 2))
    sets += list(itertools.combinations(range(min(triple_pool, pool)), 3))
    return np.asarray([c + (-1,) * (3 - len(c)) for c in sets], np.int32)


def knn_candidates(proto, k: int, pair_pool: int, triple_pool: int):
    """The configuration's K nearest feasible assignments to ``proto``
    [N, M] and their distance above the nearest one (the 'regret').

    A row's flip moves it from its best machine to its second best, at a
    regret of twice the gap between the two.  Candidates are the nearest
    assignment and the flip sets of ``_flip_sets`` over the rows with the
    cheapest flips; the k with the smallest summed regret are kept, in
    that order (ties: enumeration order)."""
    N, M = proto.shape
    order = jnp.argsort(-proto, axis=1)
    vals = jnp.take_along_axis(proto, order, axis=1)
    flip = 2.0 * (vals[:, 0] - vals[:, 1])
    pool = min(max(pair_pool, triple_pool, k), N)
    rows = jnp.argsort(flip)[:pool]                      # cheapest first
    table = jnp.asarray(_flip_sets(pool, pair_pool, triple_pool))
    valid = table >= 0
    cost = jnp.where(valid, flip[rows][jnp.maximum(table, 0)], 0.0).sum(1)
    kk = min(k, table.shape[0])
    neg, pick = jax.lax.top_k(-cost, kk)

    def build(sel):
        flipped = jnp.zeros((N,), bool).at[
            jnp.where(table[sel] >= 0, rows[jnp.maximum(table[sel], 0)], N)
        ].set(True, mode="drop")
        cols = jnp.where(flipped, order[:, 1], order[:, 0])
        return jax.nn.one_hot(cols, M, dtype=jnp.float32)

    return jax.vmap(build)(pick), -neg


def regret_of(proto, action):
    """Distance of one-hot ``action`` above the nearest assignment."""
    return 2.0 * (proto.max(1) - (proto * action).sum(1)).sum()


def score_choice(actor, critic, s, chosen, k: int, N: int, M: int,
                 pools: dict, proto=None):
    """How far the chosen assignment lies from the reference's choice:
    (Q gap below the best of the k candidates, as a share of the spread
    of Q over them; distance above the k-th candidate, as a share of its
    regret; 1 if the choice is not a one-hot assignment)."""
    if proto is None:
        proto = actor_proto(actor, s, N, M)
    cands, regrets = knn_candidates(proto, k, **pools)
    q = critic_q(critic, s, cands.reshape(k, -1))
    q_p = critic_q(critic, s, chosen.reshape(-1))
    spread = jnp.maximum(q.max() - q.min(), 1e-12)
    q_gap = jnp.maximum(q.max() - q_p, 0.0) / spread
    kth = jnp.maximum(regrets.max(), 1e-12)
    d_gap = jnp.maximum(regret_of(proto, chosen) - regrets.max(), 0.0) / kth
    bad = (jnp.any((chosen != 0.0) & (chosen != 1.0))
           | jnp.any(chosen.sum(1) != 1.0)).astype(jnp.float32)
    return q_gap, d_gap, bad


def _adam(state, params, grads, lr, b1=0.9, b2=0.999, eps=1e-8):
    step, mu, nu = state
    step = step + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    c1 = 1.0 - b1 ** step.astype(jnp.float32)
    c2 = 1.0 - b2 ** step.astype(jnp.float32)
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
        params, mu, nu)
    return (step, mu, nu), params


def update_ddpg(key, st: dict, agent: dict, N: int, M: int):
    """One learning update (Algorithm 1, lines 13-18); returns the new
    state and the norm of each online leaf's gradient."""
    idx = jax.random.randint(key, (agent["batch"],), 0,
                             jnp.maximum(st["size"], 1))
    s, a, r, s2 = st["S"][idx], st["A"][idx], st["Rw"][idx], st["S2"][idx]
    k = agent["k_nn"]

    def target(sv):
        cands, _ = knn_candidates(actor_proto(st["t_actor"], sv, N, M), k,
                                  **agent["knn_pools"])
        return critic_q(st["t_critic"], sv, cands.reshape(k, -1)).max()
    y = r + agent["gamma"] * jax.vmap(target)(s2)
    c_grad = jax.grad(lambda c: jnp.mean(
        jnp.square(y - critic_q(c, s, a))))(st["critic"])
    adam_c, critic = _adam(st["adam_c"], st["critic"], c_grad,
                           agent["lr_critic"])
    a_grad = jax.grad(lambda p: -jnp.mean(critic_q(
        critic, s, jax.nn.sigmoid(mlp(p, s)))))(st["actor"])
    adam_a, actor = _adam(st["adam_a"], st["actor"], a_grad,
                          agent["lr_actor"])
    tau = agent["tau"]
    soft = lambda t, o: jax.tree.map(                   # noqa: E731
        lambda x, y_: (1.0 - tau) * x + tau * y_, t, o)
    st = dict(st, actor=actor, critic=critic, adam_a=adam_a, adam_c=adam_c,
              t_actor=soft(st["t_actor"], actor),
              t_critic=soft(st["t_critic"], critic))
    norms = jnp.stack([jnp.linalg.norm(g) for g in
                       net_leaves(a_grad) + net_leaves(c_grad)])
    return st, norms


def store(st: dict, s, a_flat, s2, lat, reward_scale: float) -> dict:
    """Standardize the reward (minus the latency, scaled) by running
    statistics and append the transition to the ring buffer."""
    r = -lat * reward_scale
    cnt = st["r_count"] + 1
    alpha = jnp.maximum(0.02, 1.0 / cnt.astype(jnp.float32))
    mean = st["r_mean"] + alpha * (r - st["r_mean"])
    var = (1 - alpha) * st["r_var"] + alpha * jnp.square(r - mean)
    r_std = jnp.clip((r - mean) / jnp.maximum(jnp.sqrt(var), 1e-4), -10, 10)
    i, cap = st["ptr"], st["S"].shape[0]
    return dict(st, r_mean=mean, r_var=var, r_count=cnt,
                S=st["S"].at[i].set(s), A=st["A"].at[i].set(a_flat),
                Rw=st["Rw"].at[i].set(r_std), S2=st["S2"].at[i].set(s2),
                ptr=(i + 1) % cap, size=jnp.minimum(st["size"] + 1, cap))


def epsilon(agent: dict, epoch):
    frac = jnp.clip(epoch.astype(jnp.float32) / agent["eps_decay_epochs"],
                    0.0, 1.0)
    return agent["eps_start"] + frac * (agent["eps_end"] - agent["eps_start"])


def env_step(dep: Deployment, lane: dict, key, X, X_new, w):
    """Deploy X_new: executors moved, the mean of the configured number
    of noisy latency readings, and the next spout rates (a mean-reverting
    lognormal walk around the lane's base rates)."""
    base = lane["base_rates"]
    moved = (jnp.abs(X_new - X).sum(-1) > 0).sum()
    k_noise, k_w = jax.random.split(key)
    z = jax.random.normal(k_noise, (dep.readings,)) * lane["noise_sigma"]
    lat = (latency_ms(dep, X_new, w, lane["service"], lane["speed"])
           * jnp.exp(z)).mean()
    target = base * jnp.exp(jax.random.normal(k_w, w.shape) * dep.jitter)
    return lat, moved, w + dep.revert * (target - w)


def _ddpg_choose(st, agent, dep, s, X, chosen, key):
    """Score the program's assignment ``chosen`` [N, M] as the reference
    would have chosen (exploration noise from the same key).  Returns
    (q_gap, d_gap, infeasible, the assignment deployed)."""
    N, M = dep.N, dep.M
    proto = actor_proto(st["actor"], s, N, M)
    k_b, k_n = jax.random.split(key)
    proto = jnp.where(jax.random.bernoulli(k_b, epsilon(agent, st["epoch"])),
                      proto + jax.random.uniform(k_n, proto.shape), proto)
    q_gap, d_gap, bad = score_choice(st["actor"], st["critic"], s, chosen,
                                        agent["k_nn"], N, M,
                                        agent["knn_pools"], proto=proto)
    return q_gap, d_gap, bad, chosen, chosen.reshape(-1)


def _dqn_choose(st, agent, dep, s, X, move, key):
    """Score the program's move (executor move // M to machine move % M):
    on an exploring epoch (same key) it must be the reference's random
    move (d_gap 1 if not, q_gap NaN); otherwise its Q gap below the best
    move, as a share of the spread of Q over all moves."""
    M = dep.M
    q = mlp(st["qnet"], s)
    k_b, k_r = jax.random.split(key)
    explore = jax.random.bernoulli(k_b, epsilon(agent, st["epoch"]))
    rand = jax.random.randint(k_r, (), 0, q.shape[-1])
    spread = jnp.maximum(q.max() - q.min(), 1e-12)
    q_gap = jnp.where(explore, jnp.nan, (q.max() - q[move]) / spread)
    d_gap = jnp.where(explore, (move != rand).astype(jnp.float32), 0.0)
    bad = ((move < 0) | (move >= q.shape[-1])).astype(jnp.float32)
    X_new = X.at[move // M].set(jax.nn.one_hot(move % M, M))
    return q_gap, d_gap, bad, X_new, move.astype(jnp.float32)[None]


def init_dqn(key, dep: Deployment, agent: dict) -> dict:
    q = init_mlp(key, (dep.state_dim, *agent["hidden"], dep.N * dep.M))
    zeros = lambda t: jax.tree.map(jnp.zeros_like, t)   # noqa: E731
    cap = agent["buffer"]
    return {"qnet": q, "target": q,
            "adam": (jnp.zeros((), jnp.int32), zeros(q), zeros(q)),
            "S": jnp.zeros((cap, dep.state_dim)), "A": jnp.zeros((cap, 1)),
            "Rw": jnp.zeros((cap,)), "S2": jnp.zeros((cap, dep.state_dim)),
            "ptr": jnp.zeros((), jnp.int32), "size": jnp.zeros((), jnp.int32),
            "epoch": jnp.zeros((), jnp.int32),
            "r_mean": jnp.zeros(()), "r_var": jnp.ones(()),
            "r_count": jnp.zeros((), jnp.int32)}


def update_dqn(key, st: dict, agent: dict, N: int, M: int):
    """One Q-learning update with a soft-updated target network; returns
    the new state and the norm of each leaf's gradient."""
    idx = jax.random.randint(key, (agent["batch"],), 0,
                             jnp.maximum(st["size"], 1))
    s, a, r, s2 = st["S"][idx], st["A"][idx, 0].astype(jnp.int32), \
        st["Rw"][idx], st["S2"][idx]
    y = r + agent["gamma"] * mlp(st["target"], s2).max(-1)
    grad = jax.grad(lambda p: jnp.mean(jnp.square(
        y - jnp.take_along_axis(mlp(p, s), a[:, None], -1)[:, 0])))(st["qnet"])
    adam, qnet = _adam(st["adam"], st["qnet"], grad, agent["lr"])
    tau = agent["tau"]
    target = jax.tree.map(lambda t, o: (1.0 - tau) * t + tau * o,
                          st["target"], qnet)
    norms = jnp.stack([jnp.linalg.norm(g) for g in net_leaves(grad)])
    return dict(st, qnet=qnet, target=target, adam=adam), norms


AGENTS = {"ddpg": (init_ddpg, _ddpg_choose, update_ddpg),
          "dqn": (init_dqn, _dqn_choose, update_dqn)}


def rollout(dep: Deployment, agent: dict, lane: dict, init_key, run_key,
            chosen):
    """One lane's first job of T exploring epochs, teacher-forced on the
    program's choices ``chosen`` (ddpg: assignments [T, N, M]; dqn: moves
    [T]).  ``lane`` holds this lane's service [N], base_rates [S], speed
    [M] and noise_sigma.  Returns per-epoch (latency_ms, moved, q_gap,
    d_gap, infeasible), the final agent state and the first update's
    gradient norm per online leaf."""
    init, choose, upd = AGENTS[agent["name"]]
    base = lane["base_rates"]
    _, key = jax.random.split(run_key)          # the env reset's key unused
    st0 = init(init_key, dep, agent)
    n_leaves = 2 * (len(agent["hidden"]) + 1) * (
        2 if agent["name"] == "ddpg" else 1)

    def epoch(carry, c_p):
        st, X, w, key = carry
        key, k_act, k_step, k_upd = jax.random.split(key, 4)
        s = jnp.concatenate([X.reshape(-1), w / (base + 1e-9)])
        q_gap, d_gap, bad, X_new, a_flat = choose(st, agent, dep, s, X, c_p,
                                                  k_act)
        lat, moved, w2 = env_step(dep, lane, k_step, X, X_new, w)
        s2 = jnp.concatenate([X_new.reshape(-1), w2 / (base + 1e-9)])
        st = store(st, s, a_flat, s2, lat, agent["reward_scale"])
        norms = jnp.zeros((n_leaves,))
        for ku in jax.random.split(k_upd, agent["updates_per_epoch"]):
            st, norms = upd(ku, st, agent, dep.N, dep.M)
        st = dict(st, epoch=st["epoch"] + 1)
        return (st, X_new, w2, key), (lat, moved, q_gap, d_gap, bad, norms)

    X0 = jnp.asarray(dep.round_robin())
    (st, _, _, _), (lat, moved, q_gap, d_gap, bad, norms) = jax.lax.scan(
        epoch, (st0, X0, base, key), chosen)
    return (lat, moved, q_gap, d_gap, bad), st, norms[0]


def net_leaves(layers) -> list:
    """A net's arrays: every layer's weights, then every layer's biases."""
    return [w for w, _ in layers] + [b for _, b in layers]


def online_leaves(st: dict) -> list:
    """The online nets' arrays (ddpg: actor then critic; dqn: the Q net),
    in ``net_leaves`` order — the order of the updates' gradient norms."""
    if "qnet" in st:
        return net_leaves(st["qnet"])
    return net_leaves(st["actor"]) + net_leaves(st["critic"])
