"""Socket (multi-HOST) serving plane: three-way transport parity,
host-kill condemn + requeue, health-check flap tolerance, reply
correlation over TCP, the HTTP front door, and the transport registry.

Slow-marked: every test spawns shard-host processes (seconds each on the
spawn context).  The nightly --full lane runs them; tier-1 stays fast.
"""
import os
import signal
import threading
import time

import numpy as np
import pytest

pytest.importorskip("jax")

from repro.federation.env import ArmolEnv
from repro.federation.evaluation import SubsetEvaluationCore
from repro.federation.providers import default_providers
from repro.federation.traces import generate_traces
from repro.serving import (AsyncFederationService, FederationClient,
                           FederationService, HttpFrontDoor,
                           HttpServingClient, ShardTransport,
                           ShardWorkerError,
                           SocketShardedSubsetEvaluationCore,
                           ThreadTransport, available_transports,
                           get_transport, register_transport)
from repro.serving.socket_shards import send_msg

pytestmark = pytest.mark.slow

TR = generate_traces(default_providers(), 30, seed=7)
ENV = ArmolEnv(TR, mode="gt", beta=0.0, seed=0)
N = TR.n_providers


class FixedAgent:
    """Always selects the same subset (batched-aware, like the real ones)."""

    def __init__(self, action):
        self.action = np.asarray(action, np.float32)

    def select_action(self, s, *, deterministic=False):
        s = np.asarray(s)
        if s.ndim == 2:
            return np.tile(self.action, (len(s), 1)), None
        return self.action.copy(), None


def _assert_results_equal(got, ref):
    np.testing.assert_array_equal(got.action, ref.action)
    assert got.cost_milli_usd == ref.cost_milli_usd
    assert got.latency_ms == ref.latency_ms
    np.testing.assert_array_equal(got.detections.boxes, ref.detections.boxes)
    np.testing.assert_array_equal(got.detections.scores,
                                  ref.detections.scores)
    np.testing.assert_array_equal(got.detections.labels,
                                  ref.detections.labels)


# -- direct core: parity, requeue, correlation, health ---------------------

@pytest.fixture(scope="module")
def sock_core():
    """One spawned 2-host pool shared by the read-only direct-core tests
    (tests that condemn hosts spawn their own)."""
    core = SocketShardedSubsetEvaluationCore(TR, n_shards=2)
    yield core
    core.close()


def test_socket_core_matches_unsharded_bit_for_bit(sock_core):
    ref = SubsetEvaluationCore(TR)
    rng = np.random.default_rng(0)
    for _ in range(25):
        img = int(rng.integers(0, len(TR)))
        mask = int(rng.integers(1, 1 << N))
        a = sock_core.ensemble(img, mask)
        b = ref.ensemble(img, mask)
        np.testing.assert_array_equal(a.boxes, b.boxes)
        np.testing.assert_array_equal(a.scores, b.scores)
        np.testing.assert_array_equal(a.labels, b.labels)
    # the one-round-trip lattice too
    la = sock_core.evaluate_lattice(4)
    lb = ref.evaluate_lattice(4)
    np.testing.assert_array_equal(la.masks, lb.masks)
    np.testing.assert_allclose(la.ap, lb.ap)


def test_hosts_hold_no_jax_backend(sock_core):
    """Shard hosts are host-only, like the process plane's workers."""
    assert sock_core._cfg["use_kernel"] is False
    for img in range(6):
        sock_core.ensemble(img, (1 << N) - 1)
    gauges = sock_core.metrics_snapshot()["gauges"]
    assert gauges["worker.jax_backends_initialized"] == 0


def test_hand_started_host_passes_hello_and_serves_twin_rows():
    """A host started by hand (``python -m repro.launch.shard_host``) with
    the front's roster arguments passes the hello handshake and answers
    the numpy twin's rows."""
    import re
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ,
               PYTHONPATH=os.path.join(root, "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.shard_host", "--port", "0",
         "--images", str(len(TR)), "--seed", "7"],
        stdout=subprocess.PIPE, text=True, env=env)
    core = None
    try:
        line = proc.stdout.readline()
        port = int(re.search(r" on [^ :]+:(\d+) ", line).group(1))
        core = SocketShardedSubsetEvaluationCore(
            TR, hosts=[("127.0.0.1", port)])
        ref = SubsetEvaluationCore(TR, use_kernel=False)
        for img in range(5):
            a, b = core.ensemble(img, 5), ref.ensemble(img, 5)
            np.testing.assert_array_equal(a.boxes, b.boxes)
            np.testing.assert_array_equal(a.scores, b.scores)
        gauges = core.metrics_snapshot()["gauges"]
        assert gauges["worker.jax_backends_initialized"] == 0
    finally:
        if core is not None:
            core.close()
        proc.kill()
        proc.wait(timeout=10)
        proc.stdout.close()
    assert proc.poll() is not None


def test_ring_routing_is_total_and_consistent(sock_core):
    groups = sock_core.partition(range(len(TR)))
    assert sorted(i for g in groups.values() for i in g) == \
        list(range(len(TR)))
    for hid, imgs in groups.items():
        assert all(sock_core.shard_id(i) == hid for i in imgs)


def test_host_kill_requeues_to_survivor_bit_identically():
    ref = SubsetEvaluationCore(TR)
    with SocketShardedSubsetEvaluationCore(TR, n_shards=2) as core:
        rng = np.random.default_rng(3)
        imgs = [int(i) for i in rng.integers(0, len(TR), 12)]
        masks = [int(m) for m in rng.integers(1, 1 << N, 12)]
        victim = core.shard_id(imgs[0])
        os.kill(core.host_pids()[victim], signal.SIGKILL)
        # rows homed to the dead host are requeued to the survivor —
        # the caller sees correct rows, not an error
        rows = core.eval_on(victim, imgs, masks)
        for img, mask, det in zip(imgs, masks, rows):
            np.testing.assert_array_equal(det.boxes,
                                          ref.ensemble(img, mask).boxes)
        assert core.condemned() == [victim]
        # the ring re-homed every image onto the survivor
        survivor = core.healthy_hosts()[0]
        assert {core.shard_id(i) for i in range(len(TR))} == {survivor}
        # condemned host is never reused
        with pytest.raises(ShardWorkerError, match="condemned"):
            core._rpc(victim, ("ping",))


def test_all_hosts_condemned_is_clean_error_not_hang():
    with SocketShardedSubsetEvaluationCore(TR, n_shards=2) as core:
        for pid in core.host_pids():
            os.kill(pid, signal.SIGKILL)
        with pytest.raises(ShardWorkerError):
            core.eval_on(0, [0, 1], [1, 2])
        assert core.healthy_hosts() == []


def test_stale_tcp_reply_condemns_host_never_misattributes():
    """A reply whose id does not match the in-flight request means the
    stream is desynchronized (e.g. a late answer from a previous wedge):
    the host must be condemned, never the row mis-attributed."""
    ref = SubsetEvaluationCore(TR)
    with SocketShardedSubsetEvaluationCore(TR, n_shards=2) as core:
        hid = core.shard_id(5)
        # inject an unsolicited request on the host's main connection:
        # its reply queues ahead of the client's next one
        send_msg(core._socks[hid], (999_999, "ping"))
        # the client detects the id mismatch, condemns the desynced
        # host, and transparently re-routes to the survivor — the
        # caller gets the CORRECT answer, never the stale one
        assert core.ap50(5, 3) == ref.ap50(5, 3)
        assert core.condemned() == [hid]
        # the survivor still answers bit-identically
        other = core.healthy_hosts()[0]
        img = next(i for i in range(len(TR))
                   if core.shard_id(i) == other)
        np.testing.assert_array_equal(core.ensemble(img, 5).boxes,
                                      ref.ensemble(img, 5).boxes)


def test_health_flap_marks_suspect_but_needs_consecutive_failures():
    with SocketShardedSubsetEvaluationCore(
            TR, n_shards=2, health_timeout_s=1.0,
            health_failures_to_condemn=2) as core:
        assert core.health_tick() == []
        # flap: point host 1's address at a dead port for one tick
        good_addr = core._addrs[1]
        core._health_socks[1] = None
        core._addrs[1] = ("127.0.0.1", 1)   # nothing listens there
        assert core.health_tick() == []     # 1 failure -> suspect only
        assert core._suspect[1] == 1 and core.condemned() == []
        core._addrs[1] = good_addr          # flap clears
        assert core.health_tick() == []
        assert core._suspect[1] == 0        # success resets the count
        # a real death: two consecutive failed ticks condemn
        os.kill(core.host_pids()[1], signal.SIGKILL)
        first, second = core.health_tick(), core.health_tick()
        assert first == [] and second == [1]
        assert core.condemned() == [1]


# -- service-level: three-way transport parity + mid-stream host kill ------

def test_three_transports_bit_identical_under_concurrency():
    agent = FixedAgent([0, 1, 1])
    rng = np.random.default_rng(11)
    streams = [[int(i) for i in rng.integers(0, len(TR), 30)]
               for _ in range(3)]
    results = {}
    for transport in ("thread", "process", "socket"):
        collected = [None] * len(streams)
        with AsyncFederationService(ENV, agent, max_batch=8, workers=2,
                                    max_wait_ms=1.0,
                                    transport=transport) as asvc:
            def client(k):
                futs = [asvc.submit(i) for i in streams[k]]
                collected[k] = [f.result() for f in futs]

            threads = [threading.Thread(target=client, args=(k,))
                       for k in range(len(streams))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert asvc.stats["requests"] == sum(map(len, streams))
        results[transport] = collected
    for k in range(len(streams)):
        for a, b, c in zip(results["thread"][k], results["process"][k],
                           results["socket"][k]):
            _assert_results_equal(a, b)
            _assert_results_equal(a, c)


def test_service_host_kill_mid_stream_keeps_serving_no_duplicates():
    agent = FixedAgent([1, 0, 1])
    svc_ref = FederationService(ENV, agent)
    imgs = [int(i) for i in
            np.random.default_rng(5).integers(0, len(TR), 40)]
    refs = [svc_ref.handle(i) for i in imgs]
    with AsyncFederationService(ENV, agent, max_batch=4, workers=2,
                                transport="socket") as asvc:
        first = asvc.handle_many(imgs[:10])
        victim = asvc.core.shard_id(imgs[10])
        os.kill(asvc.core.host_pids()[victim], signal.SIGKILL)
        rest = asvc.handle_many(imgs[10:])
        got = first + rest
        stats = dict(asvc.stats)
        assert asvc.transport.condemned == [victim]
        assert len(asvc.core.healthy_hosts()) == 1
    # every request answered exactly once, bit-identical to the sync
    # reference — the kill surfaced as a requeue, not an error or a dup
    assert len(got) == len(imgs)
    for g, r in zip(got, refs):
        _assert_results_equal(g, r)
    assert stats["requests"] == len(imgs)


# -- HTTP front door -------------------------------------------------------

def test_http_door_matches_in_process_and_degrades_on_kill():
    from repro.obs.prom import parse_prometheus
    agent = FixedAgent([1, 1, 0])
    imgs = [int(i) for i in
            np.random.default_rng(8).integers(0, len(TR), 16)]
    with AsyncFederationService(ENV, agent, max_batch=4, workers=2,
                                transport="socket") as asvc:
        local = FederationClient(asvc)
        with HttpFrontDoor(asvc) as door:
            cli = HttpServingClient(door.url)
            assert cli.healthz()["status"] == "ok"
            got = cli.handle_many(imgs)
            for g, r in zip(got, local.handle_many(imgs)):
                _assert_results_equal(g, r)
            # stats and invalidation flow through the same facade
            assert cli.stats["requests"] == asvc.stats["requests"]
            assert cli.invalidate_images(imgs[:3]) >= 1
            # /metrics is Prometheus text obs tooling can parse
            snap = parse_prometheus(cli.metrics_text())
            assert snap["counters"]["serving.requests"] == \
                asvc.stats["requests"]
            assert any(k.startswith("serving.host_rpc_ms")
                       for k in snap["histograms"])
            # kill one host: /healthz flips to degraded, serving goes on
            victim = asvc.core.healthy_hosts()[0]
            os.kill(asvc.core.host_pids()[victim], signal.SIGKILL)
            assert cli.handle(imgs[0]) is not None
            h = cli.healthz()
            assert h["status"] == "degraded" and h["condemned"] == [victim]
            cli.close()


def test_http_door_rejects_malformed_submit():
    import json
    import urllib.error
    import urllib.request
    with AsyncFederationService(ENV, FixedAgent([1, 0, 0]), max_batch=2,
                                workers=1) as asvc:
        with HttpFrontDoor(asvc) as door:
            req = urllib.request.Request(door.url + "/submit",
                                         data=b"not json", method="POST")
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req)
            assert ei.value.code == 400
            req = urllib.request.Request(door.url + "/nope")
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req)
            assert ei.value.code == 404
            body = json.dumps({"img": 3}).encode()
            req = urllib.request.Request(
                door.url + "/submit", data=body, method="POST",
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req) as resp:
                doc = json.loads(resp.read())
            assert doc["cost_milli_usd"] == float(ENV.costs[0])


# -- transport registry -----------------------------------------------------

def test_transport_registry_lists_and_resolves():
    names = available_transports()
    assert {"thread", "process", "socket"} <= set(names)
    assert get_transport("socket").name == "socket"
    with pytest.raises(ValueError, match="unknown shard transport"):
        get_transport("carrier-pigeon")


def test_service_accepts_prebuilt_transport_instance():
    tr = ThreadTransport.build(env=ENV, workers=3)
    with AsyncFederationService(ENV, FixedAgent([1, 0, 0]),
                                max_batch=2, transport=tr) as asvc:
        assert asvc.transport is tr
        assert asvc.workers == 3 and asvc.shard_backend == "thread"
        assert asvc.handle(2).cost_milli_usd == float(ENV.costs[0])


def test_custom_transport_registers_and_serves():
    @register_transport("loopback-test")
    class LoopbackTransport(ThreadTransport):
        pass

    try:
        with AsyncFederationService(ENV, FixedAgent([0, 1, 0]),
                                    max_batch=2, workers=2,
                                    transport="loopback-test") as asvc:
            assert asvc.shard_backend == "loopback-test"
            assert asvc.handle(1).cost_milli_usd == float(ENV.costs[1])
    finally:
        from repro.serving import transports as _t
        _t._REGISTRY.pop("loopback-test", None)
