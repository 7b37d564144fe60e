"""MAHPPO (paper §5, Algorithm 1): multi-actor hybrid-action PPO with one
global critic. Fully-jitted iteration: vectorized rollout (lax.scan over the
horizon, vmap over parallel envs) + K-epoch minibatch updates.

Generic over the env's HybridActionSpace: actions are a dict pytree
({head: (..., N) array}) sampled/scored by ``env.action_space`` — no head
is named here, so the single-server (split, channel, power) env and the
multi-server (split, channel, route, power) env train through the same
code path.

Three actor modes, selected by ``MAHPPOConfig.shared_policy`` /
``entity_policy`` (init / sampling / loss / update are generic over all):

* per-UE actors (default): N distinct parameter sets over the flat global
  observation — the paper's setup, bit-for-bit unchanged.
* shared policy: ONE parameter set applied to every UE's featurized
  observation row (``env.observe_per_ue``) via vmap, per-actor feasibility
  masks flowing through unchanged. Parameters are O(1) in the fleet size
  and the feature dimension is independent of N/E, so the trained policy
  transfers zero-shot across fleet sizes, device mixes, and pool layouts
  (benchmarks/bench_generalization.py). The critic pools the feature rows
  (mean over the fleet — permutation-invariant), so the whole agent is
  fleet-size-agnostic.
* entity policy: the structured entity-set observation
  (``env.observe_entities``) through a shared per-server route scorer
  (``nets.entity_actor_forward``) — route logits are computed per (UE,
  server) pair, so the SAME parameters run on pools of any size E
  (train on 2 servers, evaluate zero-shot on 3-4). Pair it with
  ``randomize_pool=True`` (an env built with ``pool_ranges``) so each
  episode draws a fresh pool geometry and the route head actually
  receives pool-feature gradients — single-pool training leaves pool
  features constant, which is why the mean-field shared policy cannot
  transfer across layouts.

Paper defaults: ||M||=1024, B=256, K reuse, gamma=0.95, lambda=0.95,
eps=0.2, zeta=0.001, lr=1e-4.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.env.mecenv import MECEnv
from repro.optim import adamw_init, adamw_update
from repro.rl import nets
from repro.rl.gae import gae


@dataclasses.dataclass(frozen=True)
class MAHPPOConfig:
    horizon: int = 1024          # ||M|| (split across n_envs)
    batch: int = 256
    reuse: int = 10              # K
    gamma: float = 0.95
    lam: float = 0.95
    clip: float = 0.2
    ent_coef: float = 0.001      # zeta
    lr: float = 1e-4
    n_envs: int = 8
    iterations: int = 50
    norm_adv: bool = True
    shared_policy: bool = False  # one weight-shared actor over per-UE rows
    entity_policy: bool = False  # entity-set obs + per-server route scorer
    randomize_pool: bool = False  # resample EdgePool geometry per episode
    n_shards: int = 1            # devices to shard the env axis across
    fused_scorer: bool = False   # fused pair-scorer kernel (entity mode)

    def __post_init__(self):
        if self.shared_policy and self.entity_policy:
            raise ValueError("pick one of shared_policy / entity_policy")
        if self.horizon % self.n_envs != 0:
            # collect() runs T = horizon // n_envs scan steps per env; a
            # non-divisible horizon would silently DROP the remainder
            # frames (horizon=1000, n_envs=8 trains on 1000 - 1000 % 8 =
            # 1000 frames, but horizon=1026 would train on 1024) — make
            # the truncation an error instead of a quiet budget cut
            raise ValueError(
                f"horizon={self.horizon} is not divisible by "
                f"n_envs={self.n_envs}: collect() would silently drop "
                f"the {self.horizon % self.n_envs} remainder frames — "
                f"pick horizon as a multiple of n_envs")
        if self.n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {self.n_shards}")
        if self.n_envs % self.n_shards != 0:
            raise ValueError(
                f"n_envs={self.n_envs} must be divisible by "
                f"n_shards={self.n_shards}: rollouts shard whole envs "
                f"across devices")
        if self.fused_scorer and not self.entity_policy:
            raise ValueError("fused_scorer fuses the entity route "
                             "scorer — set entity_policy=True")
        if self.randomize_pool and not self.entity_policy:
            # flat observations (observe / observe_per_ue) describe the
            # CONSTRUCTION-time pool only; training them on resampled
            # geometry would silently learn from state that contradicts
            # the physics. Only observe_entities follows EnvState.geom.
            raise ValueError("randomize_pool trains on resampled pool "
                             "geometry that only the entity observation "
                             "exposes — set entity_policy=True")


def _env_mesh(n_shards):
    """A 1-D device mesh over the env axis (named "env"). Raises early —
    at trace-fn build time, not inside jit — when the host doesn't expose
    enough devices (on CPU hosts set
    XLA_FLAGS=--xla_force_host_platform_device_count=N before importing
    jax to split the host into N virtual devices)."""
    devs = jax.devices()
    if len(devs) < n_shards:
        raise ValueError(
            f"n_shards={n_shards} but only {len(devs)} device(s) "
            f"visible; on CPU export XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_shards}")
    return Mesh(np.array(devs[:n_shards]), ("env",))


def init_agent(key, env: MECEnv, *, shared_policy=False,
               entity_policy=False):
    """Per-UE actors ({"actors": stacked params}); with ``shared_policy``,
    ONE actor over `env.observe_per_ue` feature rows ({"actor": params})
    with a mean-pooled critic; with ``entity_policy``, the entity-set
    actor + set critic ({"entity_actor": params}) over
    `env.observe_entities` pytrees. The default path's key stream is
    untouched — bit-for-bit the pre-shared-policy init."""
    if shared_policy and entity_policy:
        raise ValueError("pick one of shared_policy / entity_policy")
    ka, kc = jax.random.split(key)
    if entity_policy:
        actor = nets.init_entity_actor(ka, env.entity_dims,
                                       env.action_space)
        critic = nets.init_entity_critic(kc)
        return {"entity_actor": actor, "critic": critic}
    if shared_policy:
        actor = nets.init_actor(ka, env.ue_feat_dim, env.action_space)
        critic = nets.init_critic(kc, env.ue_feat_dim)
        return {"actor": actor, "critic": critic}
    n = env.params.n_ue
    actor_keys = jax.random.split(ka, n)
    actors = jax.vmap(lambda k: nets.init_actor(
        k, env.obs_dim, env.action_space))(actor_keys)
    critic = nets.init_critic(kc, env.obs_dim)
    return {"actors": actors, "critic": critic}


def _policy_all(actors, space, obs, masks):
    """obs: (obs_dim,); masks: {head: (N, n)} per-actor feasibility ->
    per-head distribution stacks with a leading actor axis (N, ...)."""
    return jax.vmap(lambda a, m: nets.actor_forward(a, space, obs, m),
                    in_axes=(0, 0))(actors, masks)


def _sample_all(space, keys, dist, masks, mask_axis=None):
    """keys/dist: (E, N, ...); masks: {head: (N, n)} shared across envs, or
    (E, N, n) leaves when mask_axis=0 (dynamic fleets)."""
    per_env = jax.vmap(space.sample)                # over UEs, masks (N, n)
    return jax.vmap(per_env, in_axes=(0, 0, mask_axis))(keys, dist, masks)


def make_train_fns(env: MECEnv, cfg: MAHPPOConfig):
    space = env.action_space
    masks0 = env.action_masks()                     # {head: (N, n)} per-UE
    n_ue = env.params.n_ue
    shared = cfg.shared_policy
    entity = cfg.entity_policy
    # shared/entity actors are vmapped over actor rows with in_axes=(0, 0),
    # so their mask pytree must be complete (every discrete head, (N, n))
    masks0_full = space.broadcast_masks(masks0, n_ue) \
        if (shared or entity) else None

    def _dist(agent, obs, masks):
        """Per-head distribution stacks (N, ...) for ONE env's observation
        — (obs_dim,) through N per-UE actors, (N, F) feature rows through
        the weight-shared actor, or the entity-set pytree through the
        per-server route scorer."""
        if entity:
            return nets.entity_actor_forward(agent["entity_actor"], space,
                                             obs, masks)
        if shared:
            return nets.shared_actor_forward(agent["actor"], space, obs,
                                             masks)
        return _policy_all(agent["actors"], space, obs, masks)

    def _value(agent, obs):
        """Critic input: the flat global observation, (shared mode) the
        mean-pooled feature rows, or (entity mode) the mean-pooled shared-
        trunk embeddings — permutation-invariant and O(1) in N either
        way."""
        if entity:
            return nets.entity_value_forward(agent["entity_actor"],
                                             agent["critic"], obs)
        return nets.critic_forward(agent["critic"],
                                   obs.mean(axis=0) if shared else obs)

    def _policy_value(agent, obs, masks):
        """Entity-mode (dist, value) in ONE trunk pass — the value head
        reads the same embeddings the scorer routes with, and the jitted
        step pays for one encoder evaluation, not two."""
        return nets.entity_policy_value(agent["entity_actor"],
                                        agent["critic"], space, obs, masks)

    def _observe(states):
        fn = (env.observe_entities_raw if cfg.fused_scorer
              else env.observe_entities) if entity \
            else env.observe_per_ue if shared else env.observe
        return jax.vmap(fn)(states)

    def sample_step(agent, key, states):
        """states: batched EnvState over E envs."""
        obs = _observe(states)      # (E, D) / rows (E, N, F) / entity tree
        n_envs_b = states.k.shape[0]
        active = states.active.astype(jnp.float32)                # (E, N)
        value = None
        if env.dynamic:
            # state-dependent masks: inactive actors pinned to full-local
            masks = jax.vmap(env.action_masks)(states)            # (E,N,n)
            if shared or entity:
                masks = jax.vmap(
                    lambda m: space.broadcast_masks(m, n_ue))(masks)
            if entity:
                dist, value = jax.vmap(
                    lambda o, m: _policy_value(agent, o, m))(obs, masks)
            else:
                dist = jax.vmap(lambda o, m: _dist(agent, o, m))(obs,
                                                                 masks)
        else:
            masks = masks0_full if (shared or entity) else masks0
            if entity:
                dist, value = jax.vmap(
                    lambda o: _policy_value(agent, o, masks))(obs)
            else:
                dist = jax.vmap(lambda o: _dist(agent, o, masks))(obs)
        keys = jax.random.split(key, n_envs_b * n_ue).reshape(
            n_envs_b, n_ue, 2)
        actions = _sample_all(space, keys, dist, masks,
                              mask_axis=0 if env.dynamic else None)
        logp = jax.vmap(jax.vmap(space.log_prob))(dist, actions, active)
        if value is None:
            value = jax.vmap(lambda o: _value(agent, o))(obs)
        phys = space.execute(actions)
        nstates, reward, done, info = jax.vmap(env.step)(states, phys)
        tr = {"obs": obs, "actions": actions, "logp": logp,
              "reward": reward, "done": done, "value": value,
              "active": active,
              "completed": info["completed"], "energy": info["energy"]}
        return nstates, tr

    def collect(agent, key, states):
        T = cfg.horizon // cfg.n_envs

        def body(carry, _):
            states, key = carry
            key, sub = jax.random.split(key)
            states, tr = sample_step(agent, sub, states)
            return (states, key), tr

        (states, key), traj = jax.lax.scan(body, (states, key), None, length=T)
        last_obs = _observe(states)
        last_v = jax.vmap(lambda o: _value(agent, o))(last_obs)
        return states, key, traj, last_v

    # ---- sharded rollouts: the SAME collect body, shard_mapped over the
    # env axis. Each shard folds its mesh index into the rollout key
    # (decorrelated streams without any cross-device key plumbing) and
    # steps only its local n_envs / n_shards envs; auto-reset is already
    # batched inside env.step (a jnp.where over the done mask), so a
    # sharded step never syncs per-env or cross-shard. The update step
    # gathers the env-sharded trajectory for the fleet-global minibatch
    # draws (see the shard_map around ``update`` below). Built only when
    # cfg.n_shards > 1: the single-device iteration below traces exactly
    # the pre-sharding graph (key stream included).
    if cfg.n_shards > 1:
        mesh = _env_mesh(cfg.n_shards)

        def _collect_local(agent, key, states):
            key = jax.random.fold_in(key, jax.lax.axis_index("env"))
            states, _, traj, last_v = collect(agent, key, states)
            return states, traj, last_v

        collect_sharded = jax.shard_map(
            _collect_local, mesh=mesh,
            in_specs=(P(), P(), P("env")),
            out_specs=(P("env"), P(None, "env"), P("env")),
            check_vma=False)

    def loss_fn(agent, batch):
        obs, actions = batch["obs"], batch["actions"]
        adv, ret, logp_old = batch["adv"], batch["ret"], batch["logp"]
        act = batch["active"]                                     # (B, N)
        if entity:
            dist, v = jax.vmap(
                lambda o: _policy_value(agent, o, masks0_full))(obs)
        else:
            dist = jax.vmap(lambda o: _dist(
                agent, o, masks0_full if shared else masks0))(obs)
        logp = jax.vmap(jax.vmap(space.log_prob))(dist, actions, act)
        ratio = jnp.exp(logp - logp_old)                          # (B, N)
        a = adv[:, None]
        surr = jnp.minimum(ratio * a,
                           jnp.clip(ratio, 1 - cfg.clip, 1 + cfg.clip) * a)
        ent = jax.vmap(jax.vmap(space.entropy))(dist, act)
        # per-actor mean over the samples where that actor was ACTIVE: dead
        # agents contribute neither surrogate nor entropy, and a mostly-
        # inactive actor's few live samples aren't diluted by its dead ones
        n_act = jnp.maximum(act.sum(axis=0), 1.0)                 # (N,)
        actor_loss = -(((surr * act).sum(axis=0) / n_act).sum()
                       + cfg.ent_coef * ((ent * act).sum(axis=0) / n_act).sum())
        if not entity:
            v = jax.vmap(lambda o: _value(agent, o))(obs)
        critic_loss = jnp.mean((v - ret) ** 2)
        total = actor_loss + critic_loss
        return total, {"actor_loss": actor_loss, "value_loss": critic_loss,
                       "entropy": ent.mean(), "ratio": ratio.mean()}

    def update(agent, opt, key, traj, last_v):
        adv, ret = gae(traj["reward"], traj["value"], traj["done"], last_v,
                       gamma=cfg.gamma, lam=cfg.lam)
        T, E = adv.shape
        M = T * E
        flat = {
            # flatten (T, E) -> M on every obs leaf: the flat (M, D)
            # observation, the shared mode's (M, N, F) rows, and the
            # entity mode's {"ue"/"server"/"edge"} pytree alike
            "obs": jax.tree_util.tree_map(
                lambda x: x.reshape((M,) + x.shape[2:]), traj["obs"]),
            "actions": jax.tree_util.tree_map(
                lambda x: x.reshape(M, n_ue), traj["actions"]),
            "logp": traj["logp"].reshape(M, n_ue),
            "active": traj["active"].reshape(M, n_ue),
            "adv": adv.reshape(M), "ret": ret.reshape(M)}
        if cfg.norm_adv:
            a = flat["adv"]
            flat["adv"] = (a - a.mean()) / (a.std() + 1e-8)
        # replace=False draws can't exceed the population: tiny horizons
        # (M < cfg.batch) clamp the minibatch instead of crashing
        bsz = min(cfg.batch, M)
        n_updates = cfg.reuse * max(M // bsz, 1)

        def epoch_body(carry, sub):
            agent, opt = carry
            idx = jax.random.choice(sub, M, (bsz,), replace=False)
            mb = jax.tree_util.tree_map(lambda x: x[idx], flat)
            (_, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(agent, mb)
            agent, opt = adamw_update(grads, opt, agent, cfg.lr,
                                      weight_decay=0.0)
            return (agent, opt), metrics

        keys = jax.random.split(key, n_updates)
        (agent, opt), metrics = jax.lax.scan(epoch_body, (agent, opt), keys)
        metrics = jax.tree_util.tree_map(lambda x: x[-1], metrics)
        return agent, opt, metrics

    if cfg.n_shards > 1:
        # a Mosaic kernel (the fused scorer on TPU) cannot be partitioned
        # automatically, so the update runs under shard_map as well: each
        # device applies the same update to the whole gathered trajectory
        update = jax.shard_map(update, mesh=mesh, in_specs=P(),
                               out_specs=P(), check_vma=False)

    @jax.jit
    def iteration(agent, opt, key, states):
        key, k1, k2 = jax.random.split(key, 3)
        if cfg.n_shards > 1:
            states, traj, last_v = collect_sharded(agent, k1, states)
        else:
            states, key, traj, last_v = collect(agent, k1, states)
        agent, opt, metrics = update(agent, opt, k2, traj, last_v)
        metrics = dict(metrics,
                       reward_mean=traj["reward"].mean(),
                       completed=traj["completed"].mean(),
                       energy=traj["energy"].mean())
        return agent, opt, key, states, metrics

    return iteration


def init_states(env: MECEnv, cfg: MAHPPOConfig, key):
    """Batched initial states for training: with ``cfg.randomize_pool``
    every parallel env draws its own pool geometry (and redraws it on
    each auto-reset), so one training run sees n_envs layouts at a time
    instead of one forever."""
    keys = jax.random.split(key, cfg.n_envs)
    if cfg.randomize_pool:
        return jax.vmap(lambda k: env.reset(k, randomize=True))(keys)
    return jax.vmap(env.reset)(keys)


def train_mahppo(env: MECEnv, cfg: MAHPPOConfig, seed=0,
                 log_cb: Callable = None):
    key = jax.random.PRNGKey(seed)
    key, ki, kr = jax.random.split(key, 3)
    agent = init_agent(ki, env, shared_policy=cfg.shared_policy,
                       entity_policy=cfg.entity_policy)
    opt = adamw_init(agent)
    states = init_states(env, cfg, kr)
    iteration = make_train_fns(env, cfg)
    history = []
    for it in range(cfg.iterations):
        agent, opt, key, states, metrics = iteration(agent, opt, key, states)
        rec = {k: float(v) for k, v in metrics.items()}
        rec["iteration"] = it
        rec["env_steps"] = (it + 1) * cfg.horizon
        history.append(rec)
        if log_cb:
            log_cb(rec)
    return agent, history


# ----------------------------------------------------------------- eval
def evaluate_policy(env: MECEnv, agent, *, frames=64, seed=0,
                    deterministic=True, fused_scorer=False, n_envs=1,
                    n_shards=1):
    """Run eval-mode episodes; report per-task latency/energy (Eq. 7/8
    realized under the learned policy) plus cumulative reward. On dynamic
    fleets the per-task overhead is aggregated over ACTIVE UEs only —
    standby slots neither transmit nor weigh into t_task/e_task.

    Dispatches on the agent pytree: a weight-shared agent ({"actor": ...},
    from shared_policy training) is applied to `env.observe_per_ue` rows —
    including envs of a DIFFERENT fleet size or pool layout than it was
    trained on (zero-shot transfer), since the feature dimension is
    N/E-independent. An entity agent ({"entity_actor": ...}) runs on
    `env.observe_entities` pytrees — transferring across pool SIZE too,
    since its route logits are scored per server rather than emitted by a
    fixed-width branch.

    ``n_envs`` > 1 averages over that many independent eval episodes
    (vmapped rollouts, each with its own key); ``n_shards`` > 1
    additionally shard_maps the batch over devices (see `_env_mesh`).
    The default ``n_envs=1`` path traces exactly the single-rollout
    graph. ``fused_scorer`` routes an entity agent through the fused
    pair-scorer kernel (``env.observe_entities_raw``)."""
    space = env.action_space
    n_ue = env.params.n_ue
    shared = "actor" in agent
    entity = "entity_actor" in agent
    # a distilled deployment trunk ({"flat_trunk": ...}, f32 or int8 —
    # see rl/distill.py) evaluates on the same observe_per_ue rows as the
    # shared policy, through one fused MLP pass
    trunk = "flat_trunk" in agent
    if fused_scorer and not entity:
        raise ValueError("fused_scorer needs an entity agent")
    obs_entities = env.observe_entities_raw if fused_scorer \
        else env.observe_entities

    def rollout(key):
        s = env.reset(key, eval_mode=True)

        def body(carry, sub):
            s = carry
            masks = env.action_masks(s)      # state-dependent when dynamic
            if entity:
                masks = space.broadcast_masks(masks, n_ue)
                dist = nets.entity_actor_forward(
                    agent["entity_actor"], space, obs_entities(s), masks)
            elif trunk:
                masks = space.broadcast_masks(masks, n_ue)
                dist = nets.flat_trunk_forward(
                    agent["flat_trunk"], space, env.observe_per_ue(s),
                    masks)
            elif shared:
                masks = space.broadcast_masks(masks, n_ue)
                dist = nets.shared_actor_forward(
                    agent["actor"], space, env.observe_per_ue(s), masks)
            else:
                dist = _policy_all(agent["actors"], space, env.observe(s),
                                   masks)
            if deterministic:
                actions = jax.vmap(space.mode)(dist, masks)
            else:
                actions = jax.vmap(space.sample)(
                    jax.random.split(sub, n_ue), dist, masks)
            phys = space.execute(actions)
            s2, reward, done, info = env.step(s, phys)
            # realized per-task overhead under this frame's interference
            t_task, e_task = env.task_overhead(s, phys)
            # completion-weighted per-task overhead: a UE finishing 18 fast
            # offloaded tasks counts 18x, one slow local task counts once.
            # Inactive UEs carry zero weight.
            w = jnp.where(t_task > 0, env.params.t0 / t_task, 0.0) \
                * (s.k > 0) * s.active
            return s2, {"reward": reward,
                        "t_sum": (t_task * w).sum(), "e_sum": (e_task * w).sum(),
                        "w_sum": w.sum(), "completed": info["completed"],
                        "n_active": info["n_active"], "done": done}

        _, out = jax.lax.scan(body, s, jax.random.split(key, frames))
        return out

    if n_envs == 1 and n_shards == 1:
        out = jax.jit(rollout)(jax.random.PRNGKey(seed))
    else:
        # batched eval: independent episodes under vmapped rollouts,
        # optionally shard_mapped over the env axis. Each episode's
        # computation depends only on its own key, so the sharded and
        # unsharded batched paths produce identical per-env outputs (the
        # aggregation below is numpy, outside any reduction-order change)
        if n_envs % n_shards != 0:
            raise ValueError(f"n_envs={n_envs} must be divisible by "
                             f"n_shards={n_shards}")
        fn = jax.vmap(rollout)
        if n_shards > 1:
            fn = jax.shard_map(fn, mesh=_env_mesh(n_shards),
                               in_specs=(P("env"),), out_specs=P("env"),
                               check_vma=False)
        out = jax.jit(fn)(jax.random.split(jax.random.PRNGKey(seed),
                                           n_envs))
    res = {k: float(np.asarray(v).mean()) for k, v in out.items()}
    res["t_task"] = res.pop("t_sum") / max(res["w_sum"], 1e-9)
    res["e_task"] = res.pop("e_sum") / max(res.pop("w_sum"), 1e-9)
    return res
