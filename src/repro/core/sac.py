"""Soft Actor-Critic for combinatorial MLaaS provider selection (Algo. 1).

Faithful to the paper's setup: twin soft-Q networks + squashed-Gaussian
actor, fixed entropy weight alpha=0.2, gamma=0.9, lr=1e-4, Polyak-averaged
target Q networks, no separate value function (Sec. IV-B).  The critic takes
the *binary* executed action from the replay buffer (Eq. 8); the actor
update back-propagates through the continuous proto action (Eq. 9).
Everything is jitted; the agent object just holds state.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import networks as nets
from repro.core.action_space import threshold_map
from repro.core.blocks import scan_update_block
from repro.optim.adamw import AdamWState, adamw_init, adamw_update


@dataclass(frozen=True)
class SACConfig:
    state_dim: int
    n_providers: int
    hidden: tuple = (256, 256)
    lr: float = 1e-4
    gamma: float = 0.9
    alpha: float = 0.2
    polyak: float = 0.995
    seed: int = 0
    # beyond-paper: Wolpertinger-style critic re-ranking over the k nearest
    # codebook actions instead of plain tau (0 = paper-faithful threshold)
    wolpertinger_k: int = 0


class SACState(NamedTuple):
    actor: Any
    q1: Any
    q2: Any
    q1_targ: Any
    q2_targ: Any
    opt_actor: AdamWState
    opt_q1: AdamWState
    opt_q2: AdamWState
    key: jnp.ndarray


def _init_state(cfg: SACConfig) -> SACState:
    k = jax.random.PRNGKey(cfg.seed)
    ka, k1, k2, kr = jax.random.split(k, 4)
    actor = nets.init_actor(ka, cfg.state_dim, cfg.n_providers, cfg.hidden)
    q1 = nets.init_q(k1, cfg.state_dim, cfg.n_providers, cfg.hidden)
    q2 = nets.init_q(k2, cfg.state_dim, cfg.n_providers, cfg.hidden)
    return SACState(actor, q1, q2,
                    jax.tree.map(jnp.copy, q1), jax.tree.map(jnp.copy, q2),
                    adamw_init(actor), adamw_init(q1), adamw_init(q2), kr)


@partial(jax.jit, static_argnums=0)
def _update(cfg: SACConfig, state: SACState, batch) -> tuple:
    key, k1, k2 = jax.random.split(state.key, 3)
    s, a, r, s2, d = batch["s"], batch["a"], batch["r"], batch["s2"], \
        batch["d"]

    # --- target (Eq. 6): a'~pi(.|s'), min of target Qs, entropy bonus
    a2, logp2 = nets.sample_action(state.actor, s2, k1)
    q1t = nets.q_value(state.q1_targ, s2, a2)
    q2t = nets.q_value(state.q2_targ, s2, a2)
    y = r + cfg.gamma * (1.0 - d) * (jnp.minimum(q1t, q2t)
                                     - cfg.alpha * logp2)
    y = jax.lax.stop_gradient(y)

    # --- critic updates (Eq. 8)
    def q_loss(qp):
        q = nets.q_value(qp, s, a)
        return jnp.mean((q - y) ** 2)
    l1, grads1 = jax.value_and_grad(q_loss)(state.q1)
    l2, grads2 = jax.value_and_grad(q_loss)(state.q2)
    q1, opt_q1 = adamw_update(state.q1, grads1, state.opt_q1, lr=cfg.lr)
    q2, opt_q2 = adamw_update(state.q2, grads2, state.opt_q2, lr=cfg.lr)

    # --- actor update (Eq. 9)
    def pi_loss(ap):
        at, logp = nets.sample_action(ap, s, k2)
        q = jnp.minimum(nets.q_value(q1, s, at), nets.q_value(q2, s, at))
        return jnp.mean(cfg.alpha * logp - q)
    gl, pl = jax.value_and_grad(pi_loss)(state.actor)
    actor, opt_actor = adamw_update(state.actor, pl, state.opt_actor,
                                    lr=cfg.lr)

    # --- Polyak target update (Eq. 10)
    rho = cfg.polyak
    q1_targ = jax.tree.map(lambda t, n: rho * t + (1 - rho) * n,
                           state.q1_targ, q1)
    q2_targ = jax.tree.map(lambda t, n: rho * t + (1 - rho) * n,
                           state.q2_targ, q2)
    new = SACState(actor, q1, q2, q1_targ, q2_targ, opt_actor, opt_q1,
                   opt_q2, key)
    metrics = {"q1_loss": l1, "q2_loss": l2, "pi_loss": gl,
               "q_mean": jnp.mean(nets.q_value(q1, s, a))}
    return new, metrics


# fused block of K gradient steps; see repro.core.blocks
_update_block = scan_update_block(_update)


@partial(jax.jit, static_argnums=(0, 4))
def _act(cfg: SACConfig, actor, key, s, deterministic: bool, critics=None):
    """Actions for ``s``: ``(a, proto, next_key)``.  Takes only what the
    action reads (the actor, the key, and the critics ``(q1, q2)`` when
    ``cfg.wolpertinger_k`` re-ranks) and returns no state: the targets
    and optimiser moments never enter the served program.  The key is
    split once a call in both modes; the deterministic program draws no
    sample."""
    key, sub = jax.random.split(key)
    if deterministic:
        proto = nets.mean_action(actor, s)
    else:
        proto, _ = nets.sample_action(actor, s, sub)
    if cfg.wolpertinger_k:
        q1, q2 = critics

        def q_fn(st, actions):
            sr = jnp.broadcast_to(st, (actions.shape[0], st.shape[-1]))
            return jnp.minimum(nets.q_value(q1, sr, actions),
                               nets.q_value(q2, sr, actions))
        from repro.core.action_space import wolpertinger_select
        a = wolpertinger_select(proto, s, q_fn, k=cfg.wolpertinger_k)
        return a, proto, key
    return threshold_map(proto), proto, key


class SAC:
    """Stateful wrapper: select_action / update / checkpointable state."""

    def __init__(self, cfg: SACConfig):
        self.cfg = cfg
        self.state = _init_state(cfg)

    def _forward(self, s, deterministic):
        st = self.state
        critics = (st.q1, st.q2) if self.cfg.wolpertinger_k else None
        a, proto, key = _act(self.cfg, st.actor, st.key, s,
                             bool(deterministic), critics)
        self.state = st._replace(key=key)
        return a, proto

    def select_action(self, s: np.ndarray, *, deterministic=False):
        a, proto = self._forward(s, deterministic)
        return np.asarray(a), np.asarray(proto)

    def select_action_batch(self, states: np.ndarray, *,
                            deterministic=False):
        """Actions only, for serving and evaluation: ``(actions, None)``,
        (T, N) for (T, D) states, (N,) for one (D,) state.  One ``_act``
        call and one readback; the proto action stays on the device.  The
        critic re-rank scores one state a call, so with
        ``wolpertinger_k`` a batch is taken row by row."""
        if self.cfg.wolpertinger_k and np.ndim(states) > 1:
            return np.stack([self.select_action_batch(
                s, deterministic=deterministic)[0] for s in states]), None
        return np.asarray(self._forward(states, deterministic)[0]), None

    def update(self, batch: Dict[str, np.ndarray]) -> Dict[str, float]:
        jb = {k: jnp.asarray(v) for k, v in batch.items()}
        self.state, metrics = _update(self.cfg, self.state, jb)
        return {k: float(v) for k, v in metrics.items()}

    def update_block(self, batches: Dict[str, np.ndarray], *,
                     sync: bool = True) -> Dict[str, Any]:
        """K fused gradient steps from pre-sampled (K, B, ...) batches
        (``ReplayBuffer.sample_block``); returns the last step's metrics,
        matching what an eager K-iteration loop would report.

        ``jnp.asarray`` is a no-op on device arrays, so batches from a
        ``DeviceReplayBuffer`` feed the scan zero-copy; ``sync=False``
        returns the raw (K,) per-step metric traces as device arrays —
        no host sync and no extra op dispatches (the device-resident
        driver discards them; index ``[-1]`` lazily if you need the
        last step)."""
        jb = {k: jnp.asarray(v) for k, v in batches.items()}
        self.state, metrics = _update_block(self.cfg, self.state, jb)
        if not sync:
            return dict(metrics)
        return {k: float(np.asarray(v)[-1]) for k, v in metrics.items()}
