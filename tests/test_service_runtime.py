"""Resident service runtime tests (ARCHITECTURE §16).

Covers the ISSUE-13 surface: the bounded atomic PublishQueue (put/drain
race, tenant round-robin fairness), admission control (HTTP 429 +
Retry-After, sim-time deadline shedding, draining 503), the supervised
dispatcher (injected-failure retry, poison-request quarantine), warm
restart from the service checkpoint sidecar, graceful SIGTERM shutdown,
the dst_service_* scrape (parsed with the PR-8 exposition parser), and
the two acceptance pins — overload stays bounded and sheds with 429s;
kill-and-restart replays bit-identically."""

import json
import math
import os
import signal
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from dst_libp2p_test_node_tpu.config.env import NodeConfig
from dst_libp2p_test_node_tpu.config.topology import TopoParams
from dst_libp2p_test_node_tpu.runtime.node_service import (
    NodeService,
    PublishQueue,
    PublishRequest,
    ServiceConfig,
    serve_forever,
)
from dst_libp2p_test_node_tpu.runtime.simulator import (
    ExperimentConfig,
    Simulator,
)

# the PR-8 exposition parser: the scrape tests must go through a real
# parse of the rendered text, not substring checks
from test_observability import _parse_exposition

INF = float("inf")


def _post(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=10) as r:
        return r.status, json.loads(r.read())


@pytest.fixture(scope="module")
def sim():
    cfg = ExperimentConfig(
        topo=TopoParams(network_size=16, msg_size_bytes=500, messages=1),
        connect_to=4, warmup_s=5.0, seed=3,
    )
    s = Simulator(cfg)
    s.warmup()
    return s


def _service(sim, **svc_kw) -> NodeService:
    node = NodeConfig(my_id=2, network_size=16, connect_to=4)
    return NodeService(sim, node, control_port=0, metrics_port=0,
                       service=ServiceConfig(**svc_kw))


class TestPublishQueue:
    def test_put_drain_atomic_under_race(self):
        # concurrent producers against a concurrent drainer: every request
        # comes out exactly once (the old queue.Queue get_nowait drain loop
        # could interleave with puts across two drains)
        q = PublishQueue(max_depth=10_000)
        n_threads, per_thread = 8, 200
        out, out_lock = [], threading.Lock()
        stop = threading.Event()

        def produce(t):
            for i in range(per_thread):
                assert q.offer(PublishRequest("test", 100, tenant=f"t{t}"))

        def drain_loop():
            while not stop.is_set():
                got = q.drain()
                with out_lock:
                    out.extend(got)

        dt = threading.Thread(target=drain_loop)
        dt.start()
        ts = [threading.Thread(target=produce, args=(t,))
              for t in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        stop.set()
        dt.join()
        out.extend(q.drain())
        assert len(out) == n_threads * per_thread
        assert q.depth() == 0

    def test_bounded_overflow_rejected(self):
        q = PublishQueue(max_depth=3)
        assert all(q.offer(PublishRequest("test", 1)) for _ in range(3))
        assert not q.offer(PublishRequest("test", 1))
        assert q.dropped == 1
        assert q.depth() == 3

    def test_device_budget_rejects_below_depth_cap(self):
        q = PublishQueue(max_depth=100, device_ms_budget=50.0)
        # est 30ms/dispatch: one queued request fits, a second would put
        # 2*30 = 60ms of estimated device time behind the budget
        assert q.offer(PublishRequest("test", 1), est_ms=30.0)
        assert not q.offer(PublishRequest("test", 1), est_ms=30.0)
        # with no estimate yet (cold start) only the depth cap applies
        assert q.offer(PublishRequest("test", 1), est_ms=0.0)

    def test_tenant_round_robin_fairness(self):
        q = PublishQueue(max_depth=100)
        for r in ("a1", "a2", "a3"):
            q.offer(PublishRequest("test", 1, tenant="a"))
        q.offer(PublishRequest("test", 1, tenant="b"))
        q.offer(PublishRequest("test", 1, tenant="c"))
        batch, shed = q.take_batch(3, now_ms=0.0)
        # one per tenant per lap — tenant a cannot monopolize the batch
        assert [r.tenant for r in batch] == ["a", "b", "c"]
        assert shed == []
        batch, _ = q.take_batch(10, now_ms=0.0)
        assert [r.tenant for r in batch] == ["a", "a"]

    def test_deadline_shed_at_pop(self):
        q = PublishQueue(max_depth=10)
        q.offer(PublishRequest("test", 1, deadline_ms=100.0))
        q.offer(PublishRequest("test", 1, deadline_ms=INF))
        batch, shed = q.take_batch(10, now_ms=500.0)
        assert len(batch) == 1 and math.isinf(batch[0].deadline_ms)
        assert len(shed) == 1 and shed[0].deadline_ms == 100.0

    def test_snapshot_restore_roundtrip(self):
        q = PublishQueue(max_depth=10)
        for t in ("a", "b", "a"):
            q.offer(PublishRequest("blocks", 7, tenant=t, deadline_ms=INF))
        q.take_batch(1, now_ms=0.0)  # advance the fairness cursor
        snap = q.snapshot()
        q2 = PublishQueue(max_depth=10)
        q2.restore(json.loads(json.dumps(snap)))  # through JSON, like a ckpt
        assert q2.snapshot() == snap
        assert q2.depth() == q.depth()


class TestAdmission:
    def test_http_429_backpressure_with_retry_after(self, sim):
        svc = _service(sim, max_queue_depth=2, max_batch=1)
        svc.start()
        try:
            url = f"http://127.0.0.1:{svc.control_port}/publish"
            codes = []
            for _ in range(5):
                try:
                    status, _ = _post(url, {"topic": "test", "msgSize": 100})
                    codes.append(status)
                except urllib.error.HTTPError as e:
                    codes.append(e.code)
                    assert e.code == 429
                    # explicit backpressure contract: Retry-After + strict
                    # JSON body naming the reason
                    assert int(e.headers["Retry-After"]) >= 1
                    body = json.loads(e.read())
                    assert body["reason"] == "backpressure"
            assert codes.count(200) == 2
            assert codes.count(429) == 3
            assert svc.counters["rejected"] == 3
            # the dropped-requests counter is on the scrape, by reason
            svc.pump()
            fams = _parse_exposition(svc.metrics_text())
            drops = fams["dst_service_dropped_requests_total"]
            assert drops[frozenset({"reason": "backpressure"}.items())] == 3.0
        finally:
            svc.stop()

    def test_deadline_expired_requests_shed_before_device(self, sim):
        svc = _service(sim, default_deadline_ms=50.0)
        n_before = len(sim.records)
        for _ in range(3):
            code, _, _ = svc.submit(PublishRequest("test", 100))
            assert code == 200
        # 500 sim-ms pass before the pump round reaches the queue: every
        # deadline (now+50ms at admission) has expired — shed, not published
        assert svc.pump(advance_ms=500.0) == 0
        assert svc.counters["shed_deadline"] == 3
        assert len(sim.records) == n_before
        fams = _parse_exposition(svc.metrics_text())
        drops = fams["dst_service_dropped_requests_total"]
        assert drops[frozenset({"reason": "deadline"}.items())] == 3.0

    def test_draining_rejects_with_503(self, sim):
        svc = _service(sim)
        svc.begin_drain()
        code, body, headers = svc.submit(PublishRequest("test", 100))
        assert code == 503
        assert body["status"] == "draining"
        assert "Retry-After" in headers

    def test_service_status_endpoint(self, sim):
        svc = _service(sim)
        svc.start()
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{svc.control_port}/service",
                    timeout=10) as r:
                st = json.loads(r.read())
            assert st["status"] == "serving"
            assert st["degraded"] is False
            assert st["max_queue_depth"] == 1024
            assert set(st["counters"]) >= {"admitted", "rejected",
                                           "quarantined", "restarts"}
        finally:
            svc.stop()


class TestSupervisor:
    def test_injected_failure_retried_and_degraded(self, sim):
        svc = _service(sim, inject_failures=1, max_retries=1,
                       retry_backoff_s=0.0)
        code, _, _ = svc.submit(PublishRequest("test", 100))
        assert code == 200
        assert svc.pump() == 1  # retry succeeded — the publish landed
        assert svc.counters["retries"] == 1
        assert svc.counters["dispatch_failures"] == 1
        assert svc.counters["quarantined"] == 0
        assert svc.degraded is True
        assert svc.service_status()["degraded"] is True
        fams = _parse_exposition(svc.metrics_text())
        assert fams["dst_service_dispatch_retries_total"][frozenset()] == 1.0
        assert fams["dst_service_degraded"][frozenset()] == 1.0

    def test_poison_request_quarantined_service_survives(self, sim,
                                                         monkeypatch):
        svc = _service(sim, max_retries=1, retry_backoff_s=0.0)
        svc.submit(PublishRequest("test", 100))

        def boom(*a, **kw):
            raise RuntimeError("device fell over")

        monkeypatch.setattr(svc.sim, "publish", boom)
        assert svc.pump() == 0
        assert svc.counters["quarantined"] == 1
        assert svc.counters["dispatch_failures"] == 2  # attempt + retry
        assert "device fell over" in svc.last_error
        monkeypatch.undo()
        # the service is still alive: the next request dispatches normally
        svc.submit(PublishRequest("test", 100))
        assert svc.pump() == 1

    def test_request_errors_not_retried(self, sim, monkeypatch):
        # a deterministic request error (ValueError from the engine) must
        # fail once and never burn the retry budget (retrying is waste)
        svc = _service(sim, max_retries=3, retry_backoff_s=0.0)
        calls = {"n": 0}

        def bad_publish(*a, **kw):
            calls["n"] += 1
            raise ValueError("malformed request")

        monkeypatch.setattr(svc.sim, "publish", bad_publish)
        svc.submit(PublishRequest("test", 100))
        assert svc.pump() == 0
        assert calls["n"] == 1  # exactly one attempt, no retries
        assert svc.counters["retries"] == 0
        assert svc.counters["quarantined"] == 0
        assert svc.metrics.publish_failures.get(svc.metrics.labels) >= 1


class TestWarmRestart:
    def test_checkpoint_sidecar_roundtrip(self, sim, tmp_path):
        path = str(tmp_path / "svc.npz")
        svc = _service(sim, max_batch=1, checkpoint_path=path)
        for t in ("a", "b", "a"):
            svc.submit(PublishRequest("test", 100, tenant=t))
        svc.pump()  # dispatches 1, leaves 2 pending
        assert svc.flush_checkpoint() == path
        restored = NodeService.restore(
            path, NodeConfig(my_id=2, network_size=16, connect_to=4),
            control_port=0, metrics_port=0,
            service=ServiceConfig(max_batch=1, checkpoint_path=path))
        assert restored.pump_rounds == svc.pump_rounds
        assert restored.publishes.depth() == 2
        assert restored.publishes.snapshot() == svc.publishes.snapshot()
        assert restored.counters["dispatched"] == svc.counters["dispatched"]
        assert restored.counters["restarts"] == 1
        # restored counters are re-based onto the fresh registry scrape
        fams = _parse_exposition(restored.metrics_text())
        assert fams["dst_service_restarts_total"][frozenset()] == 1.0

    def test_plain_checkpoint_has_empty_sidecar(self, sim, tmp_path):
        from dst_libp2p_test_node_tpu.runtime.checkpoint import (
            load_service_meta, save_checkpoint)

        path = str(tmp_path / "plain.npz")
        save_checkpoint(sim, path)
        assert load_service_meta(path) == {}

    def test_v9_checkpoint_loads_tolerantly(self, sim, tmp_path):
        # pre-service snapshots (v9, no "kind", no sidecar) must keep
        # loading after the v10 bump
        from dst_libp2p_test_node_tpu.runtime.checkpoint import (
            load_checkpoint, save_checkpoint)

        path = tmp_path / "v9.npz"
        save_checkpoint(sim, str(path))
        z = dict(np.load(str(path)))
        meta = json.loads(bytes(z["meta_json"]).decode())
        meta["version"] = 9
        meta.pop("kind", None)
        z["meta_json"] = np.frombuffer(
            json.dumps(meta).encode(), dtype=np.uint8)
        np.savez_compressed(str(path), **z)
        restored = load_checkpoint(str(path))
        assert float(restored.state.t_ms) == float(sim.state.t_ms)

    def test_multitopic_checkpoint_roundtrip_bit_identical(self, tmp_path):
        from dst_libp2p_test_node_tpu.runtime.checkpoint import (
            load_checkpoint, save_checkpoint)
        from dst_libp2p_test_node_tpu.runtime.multitopic import (
            MultiTopicConfig, MultiTopicSimulator)

        cfg = MultiTopicConfig(
            topo=TopoParams(network_size=16, msg_size_bytes=400),
            topics=("blocks", "att"), connect_to=4, warmup_s=5.0, seed=2)
        a = MultiTopicSimulator(cfg)
        a.warmup()
        a.publish("blocks", 1)
        path = str(tmp_path / "mt.npz")
        save_checkpoint(a, path)
        b = load_checkpoint(path)
        assert [t for t, _ in b.records] == ["blocks"]
        assert np.array_equal(b.records[0][1].delays_ms,
                              a.records[0][1].delays_ms)
        # continuing both lineages stays bit-identical: same msg ids, same
        # delay arrays (the warm-restart pin at sim granularity)
        for s in (a, b):
            s.advance(400.0)
        ra = a.publish("att", 3)
        rb = b.publish("att", 3)
        assert ra.msg_id == rb.msg_id
        assert np.array_equal(ra.delays_ms, rb.delays_ms)
        assert np.array_equal(ra.received, rb.received)


class TestGracefulShutdown:
    def test_sigterm_drains_flushes_and_returns(self, tmp_path):
        # serve_forever on the MAIN thread (pytest runs tests there), a
        # timer thread delivers a real SIGTERM: the loop must stop
        # admitting, drain, flush the final checkpoint, and RETURN (the
        # process-level exit 0), not die in a handler traceback
        cfg = ExperimentConfig(
            topo=TopoParams(network_size=16, msg_size_bytes=500),
            connect_to=4, warmup_s=3.0, seed=5)
        sim = Simulator(cfg)
        sim.warmup()
        path = str(tmp_path / "final.npz")
        node = NodeConfig(my_id=1, network_size=16, connect_to=4)
        timer = threading.Timer(
            0.4, lambda: os.kill(os.getpid(), signal.SIGTERM))
        old = signal.getsignal(signal.SIGTERM)
        timer.start()
        try:
            svc = serve_forever(
                sim, node, control_port=0, metrics_port=0,
                tick_s=0.05, time_scale=1.0,
                duration_s=30.0,  # fallback bound >> the 0.4s SIGTERM
                service=ServiceConfig(checkpoint_path=path,
                                      drain_deadline_s=2.0))
        finally:
            timer.cancel()
        assert svc.draining is True
        assert svc._servers == []  # HTTP torn down
        assert os.path.exists(path), "final checkpoint not flushed"
        assert svc.counters["checkpoint_flushes"] >= 1
        # handler restored — a later SIGTERM must not hit the drain hook
        assert signal.getsignal(signal.SIGTERM) == old


class TestAcceptancePins:
    def test_overload_sheds_and_stays_bounded(self):
        # ISSUE-13 acceptance: offered load 2x per-round capacity against a
        # depth-3 queue — the excess sheds with 429s, the queue bound holds,
        # and p99 of ADMITTED requests stays finite. No crash, no growth.
        from dst_libp2p_test_node_tpu.runtime.traffic import run_service_load

        out = run_service_load(
            n_peers=32, subnets=2, connect_to=5, warmup_s=5.0, seed=1,
            ticks=8, per_tick=4, tick_ms=200.0,
            max_queue_depth=3, max_batch=2, via_http=True)
        assert out["config"]["overload_factor"] == 2.0
        assert out["offered"] == 32
        assert out["rejected"] > 0, "overload must shed with 429s"
        assert out["queue_bound_held"], out["max_depth_seen"]
        assert out["dispatched"] > 0
        assert math.isfinite(out["p99_ms"]) and out["p99_ms"] >= 0.0
        assert 0.0 < out["shed_rate"] < 1.0
        assert out["offered"] == out["admitted"] + out["rejected"]
        assert out["scrape"]["dropped_backpressure"] == out["rejected"]
        assert out["scrape_serves_service_family"] is True

    def test_kill_and_restart_bit_identical(self, tmp_path):
        # ISSUE-13 acceptance: kill the service cold mid-traffic (no flush),
        # warm-restart from the last periodic checkpoint, replay — the
        # surviving lineage's record stream must equal the uninterrupted
        # reference bit-for-bit, with the injected dispatch failure's
        # recovery counter carried across the restart. The injected failure
        # surfaces as a retry in sequential mode and as a batch split when
        # it lands on a multi-request batched group, so the carried-across
        # signal is their sum.
        from dst_libp2p_test_node_tpu.runtime.traffic import run_service_load

        out = run_service_load(
            n_peers=32, subnets=2, connect_to=5, warmup_s=5.0, seed=7,
            ticks=8, per_tick=3, tick_ms=200.0,
            max_queue_depth=8, max_batch=2,
            inject_failures=1, max_retries=1, retry_backoff_s=0.0,
            kill_at_tick=4, checkpoint_path=str(tmp_path / "svc.npz"),
            checkpoint_every=2, via_http=False)
        k = out["kill"]
        assert k is not None
        assert k["resume_tick"] == 4  # flush every 2 rounds, killed at 4
        assert k["replayed_ticks"] == 4
        assert k["messages"] == k["ref_messages"] > 0
        assert k["bit_identical"] is True
        assert k["ref_codes_match"] is True
        recovered = (out["scrape"]["retries_total"]
                     + out["scrape"]["batch_splits_total"])
        assert recovered >= 1.0  # survived the restart
        assert out["scrape"]["restarts_total"] == 1.0
        assert out["degraded"] is True


class TestBatchedDispatch:
    """ISSUE-14 pins: the batched engine at service granularity — mixed
    static-shape groups stay bit-identical to sequential, the bisect
    fallback quarantines exactly the poison request, the admission EWMA
    times device work (not backoff sleeps), and /telemetry streams the
    flight-recorder curves as strict JSON."""

    def _fresh_service(self, dispatch_mode, **svc_kw):
        cfg = ExperimentConfig(
            topo=TopoParams(network_size=16, msg_size_bytes=500,
                            messages=1),
            connect_to=4, warmup_s=5.0, seed=3,
        )
        s = Simulator(cfg)
        s.warmup()
        return _service(s, dispatch_mode=dispatch_mode, max_batch=8,
                        **svc_kw)

    def test_mixed_tenant_round_bit_identical_to_sequential(self):
        # one pump round with TWO static-shape groups (msg_size 100 and
        # 300) interleaved across tenants: the batched engine must produce
        # the sequential engine's record stream bit-for-bit, in order
        reqs = [("a", 100), ("b", 100), ("a", 300), ("c", 100), ("b", 300)]
        svcs = {m: self._fresh_service(m) for m in ("sequential",
                                                    "batched")}
        for mode, svc in svcs.items():
            for tenant, size in reqs:
                code, _, _ = svc.submit(
                    PublishRequest("test", size, tenant=tenant))
                assert code == 200
            assert svc.pump() == len(reqs)
        seq, bat = svcs["sequential"].sim, svcs["batched"].sim
        assert len(bat.records) == len(reqs)
        for ra, rb in zip(seq.records, bat.records):
            assert ra.msg_id == rb.msg_id
            assert np.array_equal(ra.delays_ms, rb.delays_ms)
            assert np.array_equal(ra.received, rb.received)
            assert np.array_equal(ra.sends, rb.sends)
        # same stdout latency-line contract, same order
        assert svcs["batched"].lines_out == svcs["sequential"].lines_out
        # and the dispatch accounting proves batching actually happened:
        # 2 stacked dispatches (one per group) vs one per request
        assert svcs["batched"].counters["device_dispatches"] == 2
        assert svcs["sequential"].counters["device_dispatches"] == len(reqs)

    def test_poison_batch_bisected_only_poison_quarantined(self,
                                                           monkeypatch):
        # a 4-request group whose batch dispatch fails: the supervisor
        # bisects (4 -> 2+2 -> singles around the poison), re-dispatches
        # the healthy requests, and quarantines ONLY the poison — never
        # the batch (the PR-6 per-seed split lifted to batch granularity)
        from dst_libp2p_test_node_tpu.runtime.multitopic import (
            MultiTopicConfig, MultiTopicSimulator)

        cfg = MultiTopicConfig(
            topo=TopoParams(network_size=16, msg_size_bytes=400),
            topics=("blocks", "att_0", "att_1"), connect_to=4,
            warmup_s=5.0, seed=2)
        sim = MultiTopicSimulator(cfg)
        sim.warmup()
        svc = _service(sim, dispatch_mode="batched", max_batch=4,
                       max_retries=1, retry_backoff_s=0.0)
        real_batch = sim.publish_batch
        real_pub = sim.publish
        POISON = "att_1"

        def batch_boom(items, **kw):
            if any(t == POISON for t, _ in items):
                raise RuntimeError("poison in batch")
            return real_batch(items, **kw)

        def pub_boom(topic, *a, **kw):
            if topic == POISON:
                raise RuntimeError("poison request")
            return real_pub(topic, *a, **kw)

        monkeypatch.setattr(sim, "publish_batch", batch_boom)
        monkeypatch.setattr(sim, "publish", pub_boom)
        for t in ("blocks", POISON, "att_0", "blocks"):
            code, _, _ = svc.submit(PublishRequest(t, 400))
            assert code == 200
        # one group (same msg_size, all subscribed): [blocks, POISON,
        # att_0, blocks] -> split -> [blocks, POISON] + [att_0, blocks];
        # the left half splits again to singles, POISON exhausts its
        # retry budget, the right half lands as one stacked dispatch
        assert svc.pump() == 3
        assert svc.counters["quarantined"] == 1
        assert svc.counters["batch_splits"] == 2
        assert svc.degraded is True
        assert "poison" in svc.last_error
        # service still serves: the next clean group dispatches batched
        monkeypatch.undo()
        for t in ("att_0", "blocks"):
            svc.submit(PublishRequest(t, 400))
        assert svc.pump() == 2

    def test_ewma_times_device_work_not_backoff_sleep(self, sim):
        # satellite pin: a retried dispatch sleeps 200ms of backoff, but
        # the admission estimator must only see the device wall — the old
        # estimator folded the sleep in and over-shed healthy tenants.
        # The device wall of a first dispatch is its compile: warm this
        # message size here, whatever ran before on this worker
        warm = _service(sim)
        warm.submit(PublishRequest("test", 100))
        assert warm.pump() == 1
        svc = _service(sim, inject_failures=1, max_retries=1,
                       retry_backoff_s=0.2)
        svc.submit(PublishRequest("test", 100))
        assert svc.pump() == 1
        assert svc.counters["retries"] == 1
        assert svc._ewma_ms > 0.0
        assert svc._ewma_ms < 150.0, (
            f"EWMA {svc._ewma_ms:.1f}ms swallowed the 200ms retry backoff")

    def test_telemetry_endpoint_streams_curves(self, sim):
        from dst_libp2p_test_node_tpu.ops.telemetry import TelemetryParams

        svc = _service(sim, dispatch_mode="batched")
        svc.start()
        try:
            url = f"http://127.0.0.1:{svc.control_port}/telemetry"
            with urllib.request.urlopen(url, timeout=10) as r:
                cold = json.loads(r.read())  # strict JSON or die
            assert cold["curves"] == {}
            assert cold["heartbeats"] == 0
            sim.record_telemetry(TelemetryParams(record=True))
            svc.pump(advance_ms=2500.0)  # >= a few heartbeat intervals
            with urllib.request.urlopen(url, timeout=10) as r:
                hot = json.loads(r.read())
            assert hot["armed"] is True
            assert hot["pump_rounds"] >= 1
            assert hot["heartbeats"] > 0
            assert hot["curves"], "armed advance exported no tel_* curves"
            for k, v in hot["curves"].items():
                assert k.startswith("tel_")
                assert len(v) == hot["heartbeats"]
            json.dumps(hot, allow_nan=False)  # strict-JSON contract
        finally:
            sim.record_telemetry(None)
            svc.stop()
