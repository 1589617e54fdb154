"""Cross-process serve transport (ISSUE 12, docs/serving.md §Cross-process
transport).

Anchors: the wire protocol round-trips (msgpack and the JSON fallback); the
worker RPC surface (generate with absolute deadline + idempotent request id,
probe, drain, adapter registry-sync) behaves like the in-process batcher —
proven against a loopback server without paying a process spawn; a REAL
worker process spawns, beats, serves bit-identically to `cached_generate`,
and drains to exit 0; a SIGKILLed worker (via `FTC_FAULT_SERVE_*` forwarded
across the process boundary) loses no request and duplicates none — greedy
outputs bit-identical to the unkilled run — and is respawned with backoff;
adapter load/unload propagates to every worker over the registry-sync RPC,
with a re-register racing an in-flight generate as the regression pin; a
wedged worker (stale heartbeat, unresponsive socket) fails the probe the
LeaseChecker way; and the k8s backend renders one pod per replica.
"""

from __future__ import annotations

import asyncio
import json
import os
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_async
from finetune_controller_tpu.models.generate import cached_generate
from finetune_controller_tpu.resilience.faults import ServeFault
from finetune_controller_tpu.resilience.policy import RetryPolicy
from finetune_controller_tpu.serve.adapters import (
    AdapterRegistry,
    entry_from_wire,
    entry_to_wire,
)
from finetune_controller_tpu.serve.batcher import (
    Batcher,
    DeadlineExceeded,
    QueueFull,
)
from finetune_controller_tpu.serve.engine import (
    BatchEngine,
    EngineConfig,
    GenRequest,
    PromptTooLong,
    warm_engine,
)
from finetune_controller_tpu.serve.fleet import ReplicaFleet
from finetune_controller_tpu.serve.router import ReplicaRouter
from finetune_controller_tpu.transport import TransportError
from finetune_controller_tpu.transport import wire
from finetune_controller_tpu.transport.builders import (
    resolve_builder,
    tiny_test,
)
from finetune_controller_tpu.transport.client import (
    RemoteReplica,
    _Connection,
)
from finetune_controller_tpu.transport.process import ProcessTransport
from finetune_controller_tpu.transport.worker import WorkerServer, WorkerSpec

# same shapes as tests/test_serve.py / test_serve_fleet.py so the warm XLA
# cache is shared by this suite AND by the spawned worker processes
ENGINE_CFG = dict(slots=2, prompt_buckets=(8, 16), max_new_tokens=24)

PROMPTS = [
    [5, 9, 2, 7],
    [1, 3, 3, 8, 2, 2],
    [7, 7, 7],
    [2, 13],
    [11, 4, 9, 1],
    [3, 3, 1],
    [6, 2, 8, 8, 1],
    [9, 9],
]


def _reqs(max_new=8, tag="r"):
    return [
        GenRequest(request_id=f"{tag}{i}", tokens=p, max_new_tokens=max_new)
        for i, p in enumerate(PROMPTS)
    ]


@pytest.fixture(scope="module")
def payload():
    # the SAME deterministic builder worker processes use — cross-process
    # bit-identity needs identical weights in every process
    return tiny_test()


def _baseline(payload, prompt, n):
    model, variables = payload
    out = cached_generate(
        model, variables, jnp.asarray([prompt], jnp.int32), max_new_tokens=n
    )
    return [int(t) for t in np.asarray(out[0, len(prompt):])]


# ---------------------------------------------------------------------------
# Wire framing + codec
# ---------------------------------------------------------------------------


def test_wire_roundtrip_with_bytes():
    doc = {"op": "x", "id": 3,
           "payload": {"blob": b"\x00\xffbinary", "n": [1, 2, 3],
                       "f": 1.5, "s": "text", "none": None}}
    assert wire.loads(wire.dumps(doc)) == doc


def test_wire_json_fallback_roundtrip(monkeypatch):
    monkeypatch.setattr(wire, "msgpack", None)
    doc = {"payload": {"blob": b"\x01\x02", "nested": {"b": b"zz"}}}
    data = wire.dumps(doc)
    json.loads(data.decode())  # really JSON
    assert wire.loads(data) == doc


def test_wire_frame_io_and_oversize_refusal():
    async def main():
        server_got = []

        async def handle(reader, writer):
            server_got.append(await wire.read_msg(reader))
            await wire.write_msg(writer, {"ok": True})
            writer.close()

        server = await asyncio.start_server(handle, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        await wire.write_msg(writer, {"op": "ping", "id": 1, "payload": {}})
        reply = await wire.read_msg(reader)
        assert reply == {"ok": True}
        assert server_got[0]["op"] == "ping"
        # an oversized length prefix tears down instead of allocating
        writer2 = (await asyncio.open_connection("127.0.0.1", port))[1]
        writer.close()
        writer2.close()
        server.close()
        await server.wait_closed()

        class FakeReader:
            def __init__(self, data):
                self.data = data

            async def readexactly(self, n):
                out, self.data = self.data[:n], self.data[n:]
                return out

        big = (wire.MAX_FRAME + 1).to_bytes(4, "big")
        with pytest.raises(wire.FrameError, match="exceeds"):
            await wire.read_msg(FakeReader(big))

    run_async(main())


def test_builder_resolution():
    assert resolve_builder("tiny_test") is tiny_test
    fn = resolve_builder(
        "finetune_controller_tpu.transport.builders:tiny_test"
    )
    assert fn is tiny_test
    with pytest.raises(ValueError, match="unknown payload builder"):
        resolve_builder("nope")
    with pytest.raises(ValueError, match="not callable"):
        resolve_builder("finetune_controller_tpu.transport.builders:_BUILTINS")


def test_adapter_entry_wire_roundtrip():
    reg = AdapterRegistry(capacity=3, max_rank=8)
    tree = {"layer": {"q": {"lora_a": np.ones((4, 2), np.float32),
                            "lora_b": np.full((2, 4), 0.5, np.float32)}}}
    entry = reg.register("tenant-a", tree, 16.0, 2, meta={"step": 7})
    doc = entry_to_wire(entry)
    assert isinstance(doc["tree"], bytes)
    aid, tree2, alpha, rank, meta = entry_from_wire(doc)
    assert (aid, alpha, rank, meta) == ("tenant-a", 16.0, 2, {"step": 7})
    np.testing.assert_array_equal(
        tree2["layer"]["q"]["lora_b"], tree["layer"]["q"]["lora_b"]
    )


# ---------------------------------------------------------------------------
# Worker RPC protocol (loopback server — no process spawn)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def shared_engine(payload, tmp_path_factory):
    """One warm engine for the loopback protocol tests (per-test batcher +
    server are cheap; the engine's compiles are not)."""
    model, variables = payload
    registry = AdapterRegistry(capacity=4, max_rank=8)
    engine = BatchEngine(
        model, variables, EngineConfig(**ENGINE_CFG), adapters=registry
    )
    warm_engine(engine)
    return engine


class _Loopback:
    """Per-test loopback worker: fresh batcher + server over the shared
    engine, plus a connected RemoteReplica."""

    def __init__(self, engine, sandbox, **batcher_kw):
        self.engine = engine
        self.spec = WorkerSpec(
            job_id="loop-job", replica_id="r0", sandbox=str(sandbox),
            builder="tiny_test", builder_kwargs={},
            engine=dict(ENGINE_CFG, prompt_buckets=[8, 16]),
            batcher={},
        )
        self.server = WorkerServer(
            self.spec, engine, Batcher(engine, **batcher_kw),
            engine.adapters, exit_on_drain=False,
        )
        self.replica: RemoteReplica | None = None

    async def __aenter__(self):
        port = await self.server.start()
        conn = await _Connection.open("127.0.0.1", port)
        hello = await conn.call("hello", {}, timeout_s=10)
        self.replica = RemoteReplica(
            "r0", conn, hello, sandbox=self.spec.sandbox,
            heartbeat_interval_s=0.2,
        )
        return self

    async def __aexit__(self, *exc):
        await self.replica.close()
        await self.server.stop()


def test_generate_over_wire_bit_identical_and_dedupes(shared_engine, payload,
                                                      tmp_path):
    async def main():
        async with _Loopback(shared_engine, tmp_path) as loop:
            replica = loop.replica
            finished_before = shared_engine.requests_finished_total
            req = GenRequest(request_id="g1", tokens=[5, 9, 2, 7],
                            max_new_tokens=8)
            first, dup = await asyncio.gather(
                replica.submit(req), replica.submit(req)
            )
            # concurrent duplicate ATTACHED to the in-flight attempt
            assert first.generated == dup.generated
            assert shared_engine.requests_finished_total == finished_before + 1
            # completed duplicate REPLAYS from the worker's LRU
            replay = await replica.submit(req)
            assert replay.generated == first.generated
            assert shared_engine.requests_finished_total == finished_before + 1
            assert first.replica_id == "r0"
            assert [int(t) for t in first.generated] == \
                _baseline(payload, [5, 9, 2, 7], 8)

    run_async(main())


def test_typed_errors_cross_the_wire(shared_engine, tmp_path):
    async def main():
        async with _Loopback(shared_engine, tmp_path, max_queue=64) as loop:
            replica = loop.replica
            with pytest.raises(PromptTooLong):
                await replica.submit(GenRequest(
                    request_id="too-long", tokens=[1] * 99, max_new_tokens=4,
                ))
            # an already-spent deadline surfaces as DeadlineExceeded without
            # ever reaching the worker
            with pytest.raises(DeadlineExceeded):
                await replica.submit(
                    GenRequest(request_id="late", tokens=[1, 2],
                               max_new_tokens=4),
                    deadline=time.monotonic() - 1.0,
                )
            # a queued deadline expiring on the worker crosses back typed
            with pytest.raises(DeadlineExceeded):
                await replica.submit(
                    GenRequest(request_id="tight", tokens=[1, 2, 3],
                               max_new_tokens=24),
                    deadline=time.monotonic() + 0.0005,
                )

    run_async(main())


def test_probe_stats_and_tenant_busy(shared_engine, tmp_path):
    async def main():
        async with _Loopback(shared_engine, tmp_path) as loop:
            replica = loop.replica
            await replica.submit(GenRequest(
                request_id="p1", tokens=[7, 7, 7], max_new_tokens=4,
            ))
            probe = await replica.health_probe()
            assert probe["steps_total"] >= 1
            assert probe["slots_busy"] == 0
            assert probe["stats"]["requests_completed_total"] == 1
            assert probe["pid"] == os.getpid()
            # snapshot-backed sync surface the router reads between awaits
            assert replica.queue_depth == 0
            assert replica.engine.steps_total == probe["steps_total"]
            assert replica.stats()["transport"] == "process"
            assert await replica.tenant_busy("") == 0

    run_async(main())


def test_drain_bounces_queued_finishes_inflight(shared_engine, tmp_path):
    async def main():
        async with _Loopback(shared_engine, tmp_path) as loop:
            replica = loop.replica
            inflight = [
                asyncio.ensure_future(replica.submit(GenRequest(
                    request_id=f"d{i}", tokens=PROMPTS[i], max_new_tokens=6,
                ))) for i in range(len(PROMPTS))
            ]
            await asyncio.sleep(0.05)  # let some admit; the rest queue
            clean = await replica.drain(10.0)
            assert clean is True
            done = await asyncio.gather(*inflight, return_exceptions=True)
            finished = [r for r in done if not isinstance(r, Exception)]
            bounced = [r for r in done if isinstance(r, Exception)]
            # in-flight lanes finished; queued requests bounced retryably
            assert finished, "drain should let admitted lanes finish"
            from finetune_controller_tpu.serve.batcher import (
                ReplicaUnavailable,
            )

            assert all(isinstance(b, ReplicaUnavailable) for b in bounced)
            # post-drain submits refuse
            with pytest.raises(ReplicaUnavailable):
                await replica.submit(GenRequest(
                    request_id="late", tokens=[1], max_new_tokens=2,
                ))

    run_async(main())


def test_adapter_sync_rpcs_and_reregister_race(shared_engine, payload,
                                               tmp_path):
    """Registry-sync RPCs install/refresh/remove on the worker; the
    regression pin: a re-register racing an in-flight generate completes
    both — no crash, no torn stacks — and the refresh drops the tenant's
    prefix namespace (stale-KV poison fence)."""
    from test_serve_adapters import _make_adapter  # reuse the harness

    async def main():
        async with _Loopback(shared_engine, tmp_path) as loop:
            replica = loop.replica
            registry = AdapterRegistry(capacity=4, max_rank=8)
            tree_v1 = _make_adapter(seed=1, rank=4)
            entry = registry.register("ten-a", tree_v1, 16.0, 4)
            slot = await replica.adapter_register(entry_to_wire(entry))
            assert slot == entry.slot
            base = await replica.submit(GenRequest(
                request_id="a-base", tokens=[5, 9, 2, 7], max_new_tokens=6,
            ))
            tenant = await replica.submit(GenRequest(
                request_id="a-t1", tokens=[5, 9, 2, 7], max_new_tokens=6,
                adapter_id="ten-a",
            ))
            assert tenant.generated != base.generated, \
                "adapter must change decode"
            # --- re-register racing an in-flight generate ----------------
            racing = asyncio.ensure_future(replica.submit(GenRequest(
                request_id="a-race", tokens=PROMPTS[1], max_new_tokens=12,
                adapter_id="ten-a",
            )))
            await asyncio.sleep(0.02)
            tree_v2 = _make_adapter(seed=2, rank=4)
            entry2 = registry.register("ten-a", tree_v2, 16.0, 4)
            await replica.adapter_register(entry_to_wire(entry2),
                                           refresh=True)
            raced = await racing
            assert raced.finish_reason in ("length", "eos")
            # post-refresh decodes use the NEW deltas: bit-identical to a
            # fresh single-tenant run of tree_v2
            post = await replica.submit(GenRequest(
                request_id="a-t2", tokens=[5, 9, 2, 7], max_new_tokens=6,
                adapter_id="ten-a",
            ))
            from test_serve_adapters import _dedicated

            model, _vars = payload
            base_vars = {"params": tiny_test()[1]["params"]}
            expected = _dedicated(
                model, base_vars, "ten-a", tree_v2, 16.0, 4,
                GenRequest(request_id="ded", tokens=[5, 9, 2, 7],
                           max_new_tokens=6, adapter_id="ten-a"),
                page_tokens=0,
            )
            assert list(post.generated) == list(expected)
            # unregister clears the slot on the worker
            await replica.adapter_unregister("ten-a")
            from finetune_controller_tpu.serve.adapters import UnknownAdapter

            with pytest.raises(UnknownAdapter):
                await replica.submit(GenRequest(
                    request_id="a-gone", tokens=[5, 9], max_new_tokens=4,
                    adapter_id="ten-a",
                ))

    run_async(main())


def test_wedged_worker_fails_probe_lease_style(tmp_path):
    """A worker that accepts connections but never answers, with a stale
    heartbeat, must fail the probe (the fleet then kills it) — the
    LeaseChecker pattern applied to serve workers."""

    async def main():
        handlers = []

        async def black_hole(reader, writer):
            handlers.append(asyncio.current_task())
            try:
                await asyncio.sleep(3600)
            finally:
                writer.close()

        server = await asyncio.start_server(black_hole, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        # a heartbeat from the distant past
        with open(tmp_path / "heartbeat.json", "w") as f:
            json.dump({"step": 3, "ts": time.time() - 120.0}, f)
        conn = await _Connection.open("127.0.0.1", port)
        replica = RemoteReplica(
            "rX", conn, {"pid": 1, "engine": {}}, sandbox=str(tmp_path),
            heartbeat_interval_s=0.5, probe_timeout_s=0.5,
        )
        with pytest.raises(TransportError, match="stale"):
            await replica.health_probe()
        # a fresh beat moves the failure to the probe-timeout layer
        with open(tmp_path / "heartbeat.json", "w") as f:
            json.dump({"step": 3, "ts": time.time()}, f)
        with pytest.raises(TransportError, match="timed out"):
            await replica.health_probe()
        await replica.close()
        server.close()
        # Server.wait_closed() waits for every connection handler (Python
        # 3.12+): the black hole never returns by itself
        for task in handlers:
            task.cancel()
        await server.wait_closed()

    run_async(main())


def test_k8s_renders_one_pod_per_replica():
    from finetune_controller_tpu.controller.backends.k8s import (
        render_serve_worker_pod,
    )

    pod = render_serve_worker_pod(
        "job-1", "r0", namespace="ftc", image="img:tag",
        worker_spec={"job_id": "job-1", "replica_id": "r0",
                     "builder": "deploy_dir",
                     "builder_kwargs": {"dir": "/stage"}},
        extra_env={"FTC_FAULT_SERVE_REPLICA": "r0"},
    )
    assert pod["kind"] == "Pod"
    assert pod["metadata"]["name"] == "job-1-serve-r0"
    assert pod["spec"]["restartPolicy"] == "Never"  # the FLEET respawns
    container = pod["spec"]["containers"][0]
    env = {e["name"]: e.get("value") for e in container["env"]}
    spec_doc = json.loads(env["FTC_SERVE_WORKER_SPEC"])
    assert spec_doc["replica_id"] == "r0"
    assert spec_doc["port"] == container["ports"][0]["containerPort"]
    # the chaos hand crosses the pod boundary like the process boundary
    assert env["FTC_FAULT_SERVE_REPLICA"] == "r0"
    assert "transport.worker" in container["command"][-1]


# ---------------------------------------------------------------------------
# Real worker processes
# ---------------------------------------------------------------------------


def _transport(tmp_path, **kw):
    defaults = dict(
        job_id="proc-job", root=tmp_path / "workers",
        payload={"builder": "tiny_test", "kwargs": {}},
        spawn_timeout_s=240.0, heartbeat_interval_s=0.5,
        probe_timeout_s=30.0,
    )
    defaults.update(kw)
    return ProcessTransport(**defaults)


def _process_fleet(tmp_path, replicas=2, transport=None, **kw):
    defaults = dict(
        replicas=replicas,
        stall_timeout_s=30.0,
        drain_timeout_s=15.0,
        restart_policy=RetryPolicy(
            max_attempts=3, base_delay_s=0.1, max_delay_s=0.3, seed=0
        ),
    )
    defaults.update(kw)
    return ReplicaFleet(
        "proc-job", None, None, EngineConfig(**ENGINE_CFG),
        transport=transport or _transport(tmp_path), **defaults,
    )


def test_process_worker_spawn_generate_heartbeat_drain(tmp_path, payload):
    """One real worker process: spawn handshake, bit-identical generate,
    live heartbeat, probe, graceful drain to exit 0."""

    async def main():
        transport = _transport(tmp_path)
        replica = await transport.spawn(
            "r0", 0, engine_config=EngineConfig(**ENGINE_CFG),
            batcher_kwargs={}, adapters=None,
        )
        try:
            assert replica.pid != os.getpid()  # its own process
            res = await replica.submit(GenRequest(
                request_id="p0", tokens=[5, 9, 2, 7], max_new_tokens=8,
            ))
            assert [int(t) for t in res.generated] == \
                _baseline(payload, [5, 9, 2, 7], 8)
            probe = await replica.health_probe()
            assert probe["steps_total"] >= 1
            # the worker beats into its sandbox (resilience/heartbeat.py)
            hb_path = os.path.join(replica.sandbox, "heartbeat.json")
            with open(hb_path) as f:
                hb = json.load(f)
            assert hb["pid"] == replica.pid
            clean = await replica.drain(10.0)
            assert clean is True
            # the drained worker EXITS (code 0)
            for _ in range(100):
                code = replica._proc.poll()
                if code is not None:
                    break
                await asyncio.sleep(0.1)
            assert code == 0
        finally:
            await replica.close()

    run_async(main())


def test_sigkilled_worker_exactly_once_bit_identical(tmp_path, payload):
    """THE cross-process chaos anchor: `FTC_FAULT_SERVE_*` forwarded into
    the worker spawn env makes worker r0 REALLY SIGKILL itself mid-decode;
    every accepted request completes exactly once, greedy outputs are
    bit-identical to the baseline, and the fleet respawns a fresh sandbox
    with backoff."""

    async def main():
        once = tmp_path / "fault-spent"
        fault_env = ServeFault(
            replica_id="r0", at_step=2, mode="kill", once_file=str(once),
        ).to_env()
        transport = _transport(tmp_path, extra_env=fault_env)
        fleet = _process_fleet(tmp_path, transport=transport)
        await fleet.start()
        router = ReplicaRouter(fleet, default_timeout_s=120,
                               failover_retries=2)

        async def health_loop():
            while True:
                await fleet.health_tick()
                await asyncio.sleep(0.1)

        hl = asyncio.ensure_future(health_loop())
        try:
            results = await asyncio.gather(
                *(router.submit(r) for r in _reqs(max_new=8, tag="k"))
            )
            seen = {}
            for r in results:
                assert r.request_id not in seen, "request completed twice"
                seen[r.request_id] = r.generated
            assert len(seen) == len(PROMPTS), "accepted requests were lost"
            # the fault actually fired as a REAL SIGKILL in the worker
            assert once.exists(), "serve fault never fired"
            for rid, toks in seen.items():
                i = int(rid[1:])
                assert [int(t) for t in toks] == \
                    _baseline(payload, PROMPTS[i], 8), rid
            # the dead worker was detected and a fresh sandbox respawned
            for _ in range(150):
                if fleet.replica_restarts_total >= 1 \
                        and len(fleet.healthy_replicas()) >= 2:
                    break
                await asyncio.sleep(0.2)
            assert fleet.replica_restarts_total >= 1
            assert len(fleet.healthy_replicas()) >= 2
            assert fleet.replicas_failed_total >= 1
        finally:
            hl.cancel()
            await fleet.close()

    run_async(main())


@pytest.mark.slow
def test_adapter_sync_propagates_to_all_workers(tmp_path, payload):
    """Adapter register/unregister reach EVERY worker process through the
    stack-sync RPC; a worker spawned after registration syncs at spawn."""
    from test_serve_adapters import _make_adapter

    async def main():
        transport = _transport(
            tmp_path,
            payload={"builder": "tiny_test",
                     "kwargs": {"lora_rank": 0}},
        )
        registry = AdapterRegistry(capacity=3, max_rank=8)
        fleet = _process_fleet(tmp_path, replicas=2, transport=transport,
                               adapters=registry)
        await fleet.start()
        try:
            tree = _make_adapter(seed=3, rank=4)
            await fleet.register_adapter("ten-p", tree, 16.0, 4)
            # route one request to EACH worker directly: propagation proof,
            # not routing luck
            outs = []
            for replica in fleet.healthy_replicas():
                res = await replica.batcher.submit(GenRequest(
                    request_id=f"ad-{replica.replica_id}",
                    tokens=[5, 9, 2, 7], max_new_tokens=6,
                    adapter_id="ten-p",
                ))
                outs.append(list(res.generated))
            assert outs[0] == outs[1], "workers disagree on the adapter"
            # ... and matches a dedicated in-process unmerged engine
            from test_serve_adapters import _dedicated

            model, _ = payload
            base_vars = {"params": tiny_test(lora_rank=0)[1]["params"]}
            expected = _dedicated(
                model, base_vars, "ten-p", tree, 16.0, 4,
                GenRequest(request_id="ded", tokens=[5, 9, 2, 7],
                           max_new_tokens=6, adapter_id="ten-p"),
                page_tokens=0,
            )
            assert outs[0] == list(expected)
            # a worker spawned AFTER registration syncs at spawn
            fleet.target_replicas = 3
            late = await fleet.spawn_replica()
            res = await late.batcher.submit(GenRequest(
                request_id="ad-late", tokens=[5, 9, 2, 7], max_new_tokens=6,
                adapter_id="ten-p",
            ))
            assert list(res.generated) == outs[0]
            # unload drops the tenant everywhere
            await fleet.unregister_adapter("ten-p")
            from finetune_controller_tpu.serve.adapters import UnknownAdapter

            with pytest.raises(UnknownAdapter):
                await late.batcher.submit(GenRequest(
                    request_id="ad-gone", tokens=[9, 9], max_new_tokens=4,
                    adapter_id="ten-p",
                ))
        finally:
            await fleet.close()

    run_async(main())
