"""Property tests: the socket (multi-HOST) shard pool must agree with
the unsharded reference core under random op streams — evaluations
interleaved with per-image invalidations AND host churn (kills that
condemn hosts mid-stream and re-home their images onto survivors).

Mirrors ``tests/test_serving_mp_fuzz.py`` with worker processes replaced
by shard HOSTS and a ``kill`` op added to the stream.  The host pool is
spawned once per module and shared across hypothesis examples: condemned
hosts stay condemned (the condemn-never-reuse discipline), which only
makes later interleavings harsher — parity never depends on which hosts
survive, because every host holds a full core over identical traces and
invalidations are mirrored on both sides.  The kill op is a no-op once
one host remains, so the pool always keeps serving.
"""
import os
import signal

import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
pytest.importorskip("jax")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.federation.evaluation import SubsetEvaluationCore  # noqa: E402
from repro.federation.providers import default_providers  # noqa: E402
from repro.federation.traces import generate_traces  # noqa: E402
from repro.serving.socket_shards import \
    SocketShardedSubsetEvaluationCore  # noqa: E402

pytestmark = pytest.mark.slow

TR = generate_traces(default_providers(), 20, seed=9)
N = TR.n_providers
ALL_MASKS = list(range(1, 1 << N))
H = 3
# hosts this module has killed, whether or not a call has surfaced it
KILLED = set()


@pytest.fixture(scope="module")
def pair():
    ref = SubsetEvaluationCore(TR)
    cut = SocketShardedSubsetEvaluationCore(TR, n_shards=H)
    yield ref, cut
    cut.close()


# op stream: evaluations, invalidations, and host churn
_op = st.one_of(
    st.tuples(st.just("ap"), st.integers(0, len(TR) - 1),
              st.sampled_from(ALL_MASKS)),
    st.tuples(st.just("ens"), st.integers(0, len(TR) - 1),
              st.sampled_from(ALL_MASKS)),
    st.tuples(st.just("inv"),
              st.lists(st.integers(0, len(TR) - 1), min_size=1,
                       max_size=6)),
    st.tuples(st.just("kill"), st.integers(0, H - 1)),
)


@settings(max_examples=10, deadline=None)
@given(ops=st.lists(_op, min_size=1, max_size=25))
def test_socket_shards_match_unsharded_under_churn(pair, ops):
    ref, cut = pair
    for op in ops:
        if op[0] == "kill":
            # churn: condemn a host mid-stream (kept no-op at one
            # survivor so the pool keeps serving for later examples).
            # A killed host stays healthy until a call touches it, so
            # the survivors are the healthy hosts not killed here.
            alive = [h for h in cut.healthy_hosts() if h not in KILLED]
            if len(alive) > 1:
                victim = alive[op[1] % len(alive)]
                KILLED.add(victim)
                os.kill(cut.host_pids()[victim], signal.SIGKILL)
                # first touch surfaces the death; eval_on requeues, so
                # correctness below never depends on when it lands
        elif op[0] == "inv":
            # mirror the drop on both sides; counts may differ only by
            # entries surviving from earlier examples on one side
            ref.invalidate_images(op[1])
            cut.invalidate_images(op[1])
        elif op[0] == "ap":
            assert cut.ap50(op[1], op[2]) == ref.ap50(op[1], op[2])
        else:
            a, b = cut.ensemble(op[1], op[2]), ref.ensemble(op[1], op[2])
            np.testing.assert_array_equal(a.boxes, b.boxes)
            np.testing.assert_array_equal(a.scores, b.scores)
            np.testing.assert_array_equal(a.labels, b.labels)
    # at least one host always survives, and routing stays total over
    # the healthy set
    assert len(cut.healthy_hosts()) >= 1
    groups = cut.partition(range(len(TR)))
    assert sorted(i for g in groups.values() for i in g) == \
        list(range(len(TR)))
    assert set(groups) <= set(cut.healthy_hosts())
