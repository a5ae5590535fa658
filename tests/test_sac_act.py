"""SAC's action program takes and returns only what the action needs.

``_act`` takes the actor, the key (and the critics when Wolpertinger
re-ranks) and returns ``(a, proto, next_key)``; ``deterministic`` is
static.  Its results must be the whole-state formulation's bit for bit,
key sequence included, so seeded training that evaluates mid-run stays
reproducible.  The whole-state formulation is kept here as the reference.
"""
from functools import partial

import numpy as np
import pytest

pytest.importorskip("jax")
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import networks as nets  # noqa: E402
from repro.core.action_space import threshold_map  # noqa: E402
from repro.core.sac import SAC, SACConfig, _act  # noqa: E402
from repro.federation.env import ArmolEnv  # noqa: E402
from repro.federation.providers import default_providers  # noqa: E402
from repro.federation.traces import generate_traces  # noqa: E402
from repro.serving.async_service import AsyncFederationService  # noqa: E402
from repro.serving.federation_service import FederationService  # noqa: E402

STATE_DIM, N = 12, 3


@partial(jax.jit, static_argnums=0)
def _act_whole_state(cfg, state, s, deterministic):
    """The action program as it was: the whole SACState in and out, the
    mode a traced flag."""
    key, sub = jax.random.split(state.key)
    proto_s, _ = nets.sample_action(state.actor, s, sub)
    proto_d = nets.mean_action(state.actor, s)
    proto = jnp.where(deterministic, proto_d, proto_s)
    if cfg.wolpertinger_k:
        def q_fn(st, actions):
            sr = jnp.broadcast_to(st, (actions.shape[0], st.shape[-1]))
            return jnp.minimum(nets.q_value(state.q1, sr, actions),
                               nets.q_value(state.q2, sr, actions))
        from repro.core.action_space import wolpertinger_select
        a = wolpertinger_select(proto, s, q_fn, k=cfg.wolpertinger_k)
        return a, proto, state._replace(key=key)
    return threshold_map(proto), proto, state._replace(key=key)


def _cfg(wolpertinger_k=0):
    return SACConfig(state_dim=STATE_DIM, n_providers=N, hidden=(32, 32),
                     seed=7, wolpertinger_k=wolpertinger_k)


def _states(rows, seed=0):
    x = np.random.default_rng(seed).standard_normal(
        (max(rows, 1), STATE_DIM)).astype(np.float32)
    return x[0] if rows == 1 else x


# Wolpertinger's critic re-rank scores one state a call
CASES = [(det, rows, k) for det in (True, False) for rows in (1, 16)
         for k in (0, 4) if not (k and rows > 1)]


@pytest.mark.parametrize("deterministic,rows,wolpertinger_k", CASES)
def test_act_matches_whole_state_reference(deterministic, rows,
                                           wolpertinger_k):
    agent = SAC(_cfg(wolpertinger_k))
    ref_state = agent.state
    for call in range(4):
        s = _states(rows, seed=call)
        a, proto = agent.select_action(s, deterministic=deterministic)
        ra, rproto, ref_state = _act_whole_state(
            agent.cfg, ref_state, jnp.asarray(s), deterministic)
        np.testing.assert_array_equal(a, np.asarray(ra))
        np.testing.assert_array_equal(proto, np.asarray(rproto))
        np.testing.assert_array_equal(np.asarray(agent.state.key),
                                      np.asarray(ref_state.key))
    # nothing but the key moved
    for got, ref in zip(jax.tree.leaves(agent.state._replace(key=0)),
                        jax.tree.leaves(ref_state._replace(key=0))):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("wolpertinger_k", [0, 4])
@pytest.mark.parametrize("deterministic", [True, False])
def test_act_returns_three_arrays_and_no_state(deterministic,
                                               wolpertinger_k):
    cfg = _cfg(wolpertinger_k)
    st = SAC(cfg).state
    critics = (st.q1, st.q2) if wolpertinger_k else None
    out = jax.eval_shape(
        lambda actor, key, s, c: _act(cfg, actor, key, s, deterministic, c),
        st.actor, st.key, jnp.zeros(STATE_DIM, jnp.float32), critics)
    leaves = jax.tree.leaves(out)
    assert isinstance(out, tuple) and len(out) == 3 and len(leaves) == 3
    assert out[0].shape == out[1].shape == (N,)
    assert out[2].shape == st.key.shape and out[2].dtype == st.key.dtype


@pytest.mark.parametrize("rows", [1, 16])
@pytest.mark.parametrize("deterministic", [True, False])
@pytest.mark.parametrize("wolpertinger_k", [0, 4])
def test_select_action_batch_equals_select_action(deterministic, rows,
                                                  wolpertinger_k):
    batched, single = SAC(_cfg(wolpertinger_k)), SAC(_cfg(wolpertinger_k))
    for call in range(3):
        s = _states(rows, seed=10 + call)
        got, aux = batched.select_action_batch(s,
                                               deterministic=deterministic)
        if wolpertinger_k and rows > 1:     # row by row, a key each
            want = np.stack([single.select_action(
                r, deterministic=deterministic)[0] for r in s])
        else:
            want = single.select_action(s, deterministic=deterministic)[0]
        assert aux is None and isinstance(got, np.ndarray)
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(np.asarray(batched.state.key),
                                      np.asarray(single.state.key))


TR = generate_traces(default_providers(), 40, seed=9)
ENV = ArmolEnv(TR, mode="gt", beta=0.0, seed=0)


@pytest.mark.parametrize("flush", [1, 16])
def test_async_service_actions_equal_handle(flush):
    """A real SAC behind the thread plane (max_batch 16): single-request
    flushes and full padded flushes take the actions ``handle`` takes."""
    agent = SAC(SACConfig(state_dim=ENV.state_dim,
                          n_providers=ENV.n_providers, hidden=(16, 16),
                          seed=3))
    imgs = [int(i) for i in
            np.random.default_rng(4).integers(0, len(TR), 32)]
    with AsyncFederationService(ENV, agent, max_batch=16, workers=2,
                                max_wait_ms=1e3 if flush > 1 else 1.0
                                ) as asvc:
        if flush > 1:
            got = asvc.handle_many(imgs)
        else:
            got = [asvc.handle(i) for i in imgs]
        assert asvc.stats["max_flush"] == flush
    ref = FederationService(ENV, agent)
    for img, res in zip(imgs, got):
        want = ref.handle(img)
        np.testing.assert_array_equal(res.action, want.action)
        assert res.cost_milli_usd == want.cost_milli_usd
