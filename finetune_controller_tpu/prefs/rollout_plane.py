"""Disaggregated RLHF data plane (docs/preference.md §Disaggregated rollouts).

The Podracer split realised on our substrate: the rollout actor moves out of
the learner's process into a serve-fleet worker (``transport/worker.py`` with
a ``rollout`` spec section), and scored preference pairs stream back over
four idempotent RPCs instead of a shared Python deque::

    learner (DPOTrainer.fit)                 rollout worker (own process)
    ────────────────────────                 ───────────────────────────
    RolloutPlane.push_policy  ──policy_version──▶  RolloutService (actor)
    puller thread             ──rollout_pull────▶    outbox of ROUND docs
       dedup → RolloutBuffer  ◀─rounds+spans────
                              ──rollout_ack─────▶    trim outbox

Exactly-once without a transaction log:

* the worker's outbox is a monotonically-sequenced list of ROUND documents;
  ``pull(after_seq)`` is a pure cursor read and ``ack(up_to_seq)`` a
  monotonic trim — a re-delivered pull replays identical documents;
* every pair carries an id ``v{version}:r{round}:p{i}``.  Generation is
  deterministic per (actor seed, version, round), so a SIGKILLed worker that
  respawns and regenerates the same round at the same policy version emits
  byte-identical pairs under the SAME ids — the learner's bounded seen-set
  then drops them as duplicates.  No pair enters the buffer twice (chaos
  test: ``tests/test_rollout_plane.py``).

Policy rollover is a PUSH of the adapter delta (``transport/wire.py::
tree_to_blob`` — megabytes of LoRA, the PR-11 wire format, never base
weights): the learner's checkpoint commits ship the trainable tree over
``rollout_policy_version``; the worker installs it BETWEEN rounds with the
zero-recompile in-place swap (:meth:`~.actor.RolloutActor.install_policy`),
so reload never stalls generation.  The frozen base crosses once, at spawn,
through the ``rollout_base`` artifact on disk (``transport/builders.py``).

The second half of the plane is the learned reward model: a ``task: reward``
job (:mod:`.reward_trainer`) trains a scalar head on the DPO data path; its
export is served by a standard worker with a ``reward`` spec section, and
:class:`RewardScorer` answers the batched ``reward_score`` RPC the actor's
``batch_reward_fn`` points at — one RPC scores a whole round's candidates.

Each round document ships a host-clock span (start/end ``time.time_ns``);
the learner re-records them into the job trace (service="rollout") so the
PR-9 timeline PROVES actor generation overlapped learner steps.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import logging
import os
import threading
import time
from pathlib import Path
from typing import Any, Callable, Iterator

from ..resilience.policy import RetryPolicy
from .actor import RolloutActor, increment_prompts, increment_reward
from .learner import RolloutConfig
from .rollout_buffer import PreferencePair, RolloutBuffer

logger = logging.getLogger(__name__)


def pair_id(version: int, round_no: int, index: int) -> str:
    """The idempotency key: deterministic generation makes a regenerated
    (version, round, index) byte-identical, so the id doubles as a content
    address for the learner's dedup."""
    return f"v{int(version)}:r{int(round_no)}:p{int(index)}"


def _pair_doc(pair: PreferencePair, pid: str) -> dict[str, Any]:
    return {
        "id": pid,
        "prompt": [int(t) for t in pair.prompt],
        "chosen": [int(t) for t in pair.chosen],
        "rejected": [int(t) for t in pair.rejected],
        "version": int(pair.version),
        "reward_chosen": float(pair.reward_chosen),
        "reward_rejected": float(pair.reward_rejected),
    }


def _pair_from_doc(doc: dict[str, Any]) -> PreferencePair:
    return PreferencePair(
        prompt=tuple(int(t) for t in doc["prompt"]),
        chosen=tuple(int(t) for t in doc["chosen"]),
        rejected=tuple(int(t) for t in doc["rejected"]),
        version=int(doc.get("version", 0)),
        reward_chosen=float(doc.get("reward_chosen", 0.0)),
        reward_rejected=float(doc.get("reward_rejected", 0.0)),
    )


# ---------------------------------------------------------------------------
# worker side: the streaming pair service
# ---------------------------------------------------------------------------


class RolloutService:
    """Producer loop + outbox behind the ``rollout_*`` RPCs.

    One daemon thread runs the actor round-robin: install any pending policy
    push, generate a round, append its document to the bounded outbox.  RPC
    handlers only touch the outbox/pending slots under the lock — a policy
    push never blocks on an in-flight generate round (it installs between
    rounds), which is what keeps rollover from stalling generation.
    """

    def __init__(self, actor: RolloutActor, *, reward_client=None,
                 max_outbox_rounds: int = 64):
        self.actor = actor
        self._reward_client = reward_client
        #: backpressure bound: a learner that stops acking stops the actor
        #: from burning device time on pairs nobody will train on
        self._max_outbox = max(1, max_outbox_rounds)
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._outbox: collections.deque[dict] = collections.deque()
        self._seq = 0
        self._pairs_per_round = 0
        self._pending_policy: tuple[int, dict | None] | None = None
        self._thread: threading.Thread | None = None
        self._stop = threading.Event()
        self._error: str | None = None
        self.rounds_total = 0
        self.policy_installs_total = 0

    # ---- RPC surface (sync; the worker wraps these in to_thread) ----------

    def start(self, pairs_per_round: int) -> dict[str, Any]:
        """Idempotent: a re-delivered start (or one after a plane respawn)
        re-confirms the running producer instead of double-starting it."""
        with self._lock:
            self._pairs_per_round = max(1, int(pairs_per_round))
            running = self._thread is not None and self._thread.is_alive()
            if not running and not self._stop.is_set():
                self._thread = threading.Thread(
                    target=self._produce, name="ftc-rollout-producer",
                    daemon=True,
                )
                self._thread.start()
                running = True
            seq = self._seq
        return {
            "started": running, "seq": seq,
            "version": self.actor.version,
        }

    def pull(self, after_seq: int, max_rounds: int = 8) -> dict[str, Any]:
        if self._error is not None:
            raise RuntimeError(f"rollout producer died: {self._error}")
        with self._lock:
            rounds = [
                d for d in self._outbox if d["seq"] > int(after_seq)
            ][: max(1, int(max_rounds))]
            seq = self._seq
        return {
            "rounds": rounds, "seq": seq,
            "version": self.actor.version, "stats": self.stats(),
        }

    def ack(self, up_to_seq: int) -> dict[str, Any]:
        dropped = 0
        with self._lock:
            while self._outbox and self._outbox[0]["seq"] <= int(up_to_seq):
                self._outbox.popleft()
                dropped += 1
            depth = len(self._outbox)
        self._wake.set()  # backpressured producer may resume
        return {"acked": dropped, "outbox_depth": depth}

    def push_policy(self, version: int, tree_blob: bytes | None
                    ) -> dict[str, Any]:
        """Stage a learner-shipped adapter delta; the producer installs it
        between rounds.  Idempotent + monotonic (stale versions no-op), so
        the plane may re-push its cached policy after every respawn."""
        from ..transport.wire import tree_from_blob

        version = int(version)
        tree = tree_from_blob(tree_blob) if tree_blob else None
        with self._lock:
            pending_v = self._pending_policy[0] if self._pending_policy else 0
            accepted = version > max(self.actor.version, pending_v)
            if accepted:
                self._pending_policy = (version, tree)
            running = self._thread is not None and self._thread.is_alive()
        if accepted and not running:
            # pushed before start(): install inline so the first round
            # already decodes with the shipped policy
            self._install_pending()
        with self._lock:
            pending = self._pending_policy is not None
        return {"accepted": accepted, "version": self.actor.version,
                "pending": pending}

    # ---- producer ---------------------------------------------------------

    def _install_pending(self) -> None:
        with self._lock:
            pending = self._pending_policy
            self._pending_policy = None
        if pending is not None and self.actor.install_policy(*pending):
            with self._lock:
                self.policy_installs_total += 1

    def _produce(self) -> None:
        try:
            while not self._stop.is_set():
                self._install_pending()
                with self._lock:
                    n = self._pairs_per_round
                    backpressure = len(self._outbox) >= self._max_outbox
                if backpressure:
                    self._wake.wait(0.05)
                    # ftc: ignore[lock-discipline,shared-mutable-without-lock] -- threading.Event is internally synchronized; a clear racing an ack's set() only costs one extra 50ms poll
                    self._wake.clear()
                    continue
                t0 = time.time_ns()
                pairs = self.actor.generate_pairs(n)
                t1 = time.time_ns()
                round_no = self.actor.rounds
                version = self.actor.version
                docs = [
                    _pair_doc(p, pair_id(version, round_no, i))
                    for i, p in enumerate(pairs)
                ]
                with self._lock:
                    self._seq += 1
                    self._outbox.append({
                        "seq": self._seq,
                        "round": round_no,
                        "version": version,
                        "pairs": docs,
                        # host-clock span, shipped to the learner's trace so
                        # the PR-9 timeline can prove generate/train overlap
                        "span": {
                            "start_ns": t0, "end_ns": t1,
                            "pairs": len(docs),
                            "tokens": self.actor.tokens_generated,
                        },
                    })
                    self.rounds_total += 1
        # ftc: ignore[silent-except] -- not swallowed: re-raised to the learner on its next pull
        except BaseException as exc:
            self._error = f"{type(exc).__name__}: {exc}"
            logger.exception("rollout producer died")

    def stop(self) -> None:
        self._stop.set()
        self._wake.set()
        with self._lock:
            thread = self._thread
        if thread is not None:
            thread.join(timeout=10.0)
        if self._reward_client is not None:
            self._reward_client.close()

    def stats(self) -> dict[str, Any]:
        with self._lock:
            counters = {
                "rollout_rounds_total": self.rounds_total,
                "rollout_outbox_depth": len(self._outbox),
                "rollout_seq": self._seq,
                "rollout_policy_installs_total": self.policy_installs_total,
            }
        return {
            **counters,
            "actor_tokens_per_sec": round(self.actor.tokens_per_sec, 1),
            "actor_version": self.actor.version,
            "actor_pairs_generated": self.actor.pairs_generated,
            "actor_rounds": self.actor.rounds,
            # cumulative decode counters: windowed deltas give the decode
            # throughput over any interval
            "actor_tokens_generated": self.actor.tokens_generated,
            "actor_generate_seconds": round(self.actor.generate_seconds, 6),
        }


class _RolloutBatcherShim:
    """The batcher-shaped surface :class:`~..transport.worker.WorkerServer`'s
    probe/heartbeat/drain paths expect, over a :class:`RolloutService` — NOT
    a real ``Batcher`` (one would double-step the actor's engine)."""

    def __init__(self, service: RolloutService):
        self.service = service
        self.engine = service.actor._engine

    async def health_probe(self) -> dict[str, Any]:
        return {
            "steps_total": self.engine.steps_total,
            "slots_busy": 0,
            "queue_depth": len(self.service._outbox),
            "step_errors_total": 1 if self.service._error else 0,
            "last_step_error": self.service._error,
        }

    def retry_after_s(self, extra_requests: int = 1) -> float:
        return 1.0

    def stats(self) -> dict[str, Any]:
        return self.service.stats()

    async def tenant_busy(self, adapter_id: str) -> int:
        return 0

    async def drain(self, timeout_s: float = 30.0) -> bool:
        import asyncio

        await asyncio.to_thread(self.service.stop)
        return True

    async def close(self, exc: BaseException | None = None) -> None:
        import asyncio

        await asyncio.to_thread(self.service.stop)


def build_rollout_worker(spec, *, exit_on_drain: bool = True):
    """Assemble a rollout-tenant worker from its spec (the ``spec.rollout``
    branch of ``transport/worker.py::build_worker``)."""
    from ..serve.engine import warm_engine
    from ..transport.builders import resolve_builder
    from ..transport.worker import WorkerServer

    rcfg = dict(spec.rollout or {})
    builder = resolve_builder(spec.builder)
    model, variables = builder(**(spec.builder_kwargs or {}))
    vocab = int(model.cfg.vocab_size)
    seq_len = int(rcfg.get("seq_len") or model.cfg.max_seq_len)
    prompt_fraction = float(rcfg.get("prompt_fraction", 0.5))
    prompt_len = max(2, int(seq_len * prompt_fraction))
    seed = int(rcfg.get("seed", 0))
    reward_client = None
    batch_reward_fn = None
    if int(rcfg.get("reward_port") or 0):
        from ..transport.client import RewardClient

        reward_client = RewardClient(
            str(rcfg.get("reward_host") or "127.0.0.1"),
            int(rcfg["reward_port"]),
        )
        batch_reward_fn = reward_client.batch_reward_fn()
    oracle_fn = None
    if batch_reward_fn is None and bool(rcfg.get("oracle_bootstrap", True)):
        # programmatic-reward mode keeps the cold-start bootstrap; with a
        # LEARNED reward model scores are continuous (ties are measure-zero)
        # and the oracle is retired to tests
        oracle_fn = lambda p, n: [(p[-1] + 1 + i) % vocab for i in range(n)]
    actor = RolloutActor(
        model, dict(variables)["params"], None,  # push mode: no ckpt_dir
        reward_fn=lambda p, c: increment_reward(p, c, vocab),
        batch_reward_fn=batch_reward_fn,
        prompts=increment_prompts(
            seq_len, vocab, seed + 7919, prompt_fraction
        ),
        oracle_fn=oracle_fn,
        prompt_bucket=prompt_len,
        max_new_tokens=min(
            int(rcfg.get("max_new_tokens", 16)), seq_len - prompt_len
        ),
        temperature=float(rcfg.get("temperature", 0.8)),
        top_k=int(rcfg.get("top_k", 0)),
        slots=int(rcfg.get("slots", 4)),
        seed=seed,
    )
    if spec.warm_start:
        warm_engine(actor._engine)
    service = RolloutService(
        actor, reward_client=reward_client,
        max_outbox_rounds=int(rcfg.get("max_outbox_rounds", 64)),
    )
    server = WorkerServer(spec, actor._engine, _RolloutBatcherShim(service),
                          None, exit_on_drain=exit_on_drain)
    server.rollout = service
    return server


# ---------------------------------------------------------------------------
# reward serving: the batched pair scorer behind ``reward_score``
# ---------------------------------------------------------------------------

REWARD_HEAD_FILENAME = "reward_head.msgpack"


class RewardScorer:
    """Scalar scores for (prompt, completion) items over a served policy
    trunk + the reward job's exported head (``prefs/losses.py::
    reward_scores``).  Batches are padded to pow2 (rows and length) so the
    jit cache stays bounded the same way the serve engine's buckets do."""

    def __init__(self, model, variables: dict, head: dict):
        import jax
        import jax.numpy as jnp

        self._model = model
        self._variables = variables
        self._head = jax.tree.map(jnp.asarray, head)
        self._fns: dict[tuple[int, int], Any] = {}
        self.scored_total = 0

    @classmethod
    def from_artifacts(cls, artifacts_dir: str, model,
                       variables: dict) -> "RewardScorer":
        """Load the head from a reward job's artifacts: the exported
        ``reward_head.msgpack`` when present, else the latest checkpoint's
        trainable tree — a staged serve prefix carries only
        spec+checkpoints (``serve/loader.py::fetch_promoted``), and the head
        rides every checkpoint by construction."""
        from flax import serialization

        path = os.path.join(artifacts_dir, REWARD_HEAD_FILENAME)
        if os.path.exists(path):
            with open(path, "rb") as f:
                head = serialization.msgpack_restore(f.read())
            return cls(model, variables, head)
        from ..train.checkpoint import CheckpointManager

        ckpt = CheckpointManager(os.path.join(artifacts_dir, "checkpoints"))
        latest = ckpt.latest_step()
        if latest is None:
            raise FileNotFoundError(
                f"no {REWARD_HEAD_FILENAME} and no committed checkpoint "
                f"under {artifacts_dir} — is this a task: reward job's "
                "artifact/deploy prefix?"
            )
        host = ckpt.restore(latest)  # raw: template-free, head only
        head = (host.get("trainable") or {}).get("head")
        if not isinstance(head, dict):
            raise ValueError(
                f"checkpoint step {latest} under {artifacts_dir} carries no "
                "reward head — was this job trained with task: reward?"
            )
        return cls(model, variables, head)

    def _fn(self, b: int, s: int):
        key = (b, s)
        fn = self._fns.get(key)
        if fn is None:
            import jax

            from .losses import reward_scores

            def score(variables, tokens, mask, head):
                logits = self._model.apply(
                    variables, tokens, deterministic=True
                )
                return reward_scores(logits, tokens, mask, head)

            fn = jax.jit(score)
            self._fns[key] = fn
        return fn

    def score(self, items: list[dict[str, Any]]) -> list[float]:
        import numpy as np

        from ..data.preference import _pad_pair

        if not items:
            return []
        n = len(items)
        longest = max(
            len(it["prompt"]) + len(it["completion"]) for it in items
        )
        s = 8
        while s < longest:
            s <<= 1
        s = min(s, int(self._model.cfg.max_seq_len))
        b = 1
        while b < n:
            b <<= 1
        tokens = np.zeros((b, s), np.int32)
        mask = np.zeros((b, s), np.float32)
        for i, it in enumerate(items):
            t, m = _pad_pair(
                [int(x) for x in it["prompt"]],
                [int(x) for x in it["completion"]], s,
            )
            tokens[i], mask[i] = t, m
        out = self._fn(b, s)(self._variables, tokens, mask, self._head)
        self.scored_total += n
        return [float(x) for x in np.asarray(out)[:n]]


# ---------------------------------------------------------------------------
# learner side: the plane
# ---------------------------------------------------------------------------


def write_rollout_base(artifacts_dir: str, model_spec: dict,
                       base_params: dict) -> str:
    """Stage the frozen base for remote actors (``transport/builders.py::
    rollout_base`` reads it back): model spec JSON + flax-msgpack params,
    written atomically.  Base weights cross the boundary HERE, on disk,
    exactly once — the wire only ever carries adapter deltas."""
    import jax
    import numpy as np
    from flax import serialization

    base = os.path.join(artifacts_dir, "rollout_base")
    os.makedirs(base, exist_ok=True)
    host = jax.tree.map(
        lambda x: np.asarray(jax.device_get(x)), base_params
    )
    blob = serialization.msgpack_serialize(host)
    for name, data in (
        ("model.json", json.dumps(model_spec, indent=2).encode()),
        ("params.msgpack", blob),
    ):
        tmp = os.path.join(base, f"{name}.tmp")
        with open(tmp, "wb") as f:
            f.write(data)
        os.replace(tmp, os.path.join(base, name))
    return base


@dataclasses.dataclass
class _WorkerState:
    worker_id: str
    handle: Any = None
    generation: int = 0
    #: highest seq ingested from the CURRENT incarnation (resets on respawn
    #: — the worker's outbox restarts at seq 1, and the pair-id dedup is
    #: what keeps the reset from double-ingesting)
    cursor: int = 0
    alive: bool = False
    last_stats: dict = dataclasses.field(default_factory=dict)


class RolloutPlane:
    """Learner-side home of the remote actors: spawns workers, pulls rounds
    into the :class:`~.rollout_buffer.RolloutBuffer` (one puller thread per
    worker), dedups by pair id, pushes policy rollovers, and respawns dead
    workers with seeded decorrelated backoff.

    ``spawn_fn`` is an async ``(worker_id, generation) -> handle`` where the
    handle speaks the :class:`~..transport.client.RemoteReplica` rollout
    surface — production uses :class:`~..transport.process.ProcessTransport`
    spawns; tests inject in-memory fakes to pin dedup/respawn semantics
    without process spawns.
    """

    def __init__(
        self,
        buffer: RolloutBuffer,
        *,
        num_workers: int,
        spawn_fn: Callable[..., Any],
        pairs_per_round: int,
        span_recorder=None,
        retry: RetryPolicy | None = None,
        dedup_capacity: int = 8192,
        pull_max_rounds: int = 8,
        idle_sleep_s: float = 0.02,
        rpc_timeout_s: float = 300.0,
    ):
        import asyncio

        self.buffer = buffer
        self._spawn_fn = spawn_fn
        self.pairs_per_round = int(pairs_per_round)
        self._spans = span_recorder
        # effectively-unbounded attempts: a rollout worker is cattle; the
        # learner keeps stepping on buffered pairs while it comes back
        self._retry = retry or RetryPolicy(
            max_attempts=10**9, base_delay_s=0.2, max_delay_s=10.0, seed=0
        )
        self._pull_max_rounds = int(pull_max_rounds)
        self._idle_sleep_s = idle_sleep_s
        self._rpc_timeout_s = rpc_timeout_s
        #: guards buffer + seen-set + ingest counters (pullers push from
        #: their own threads; the learner samples from the fit thread)
        self._lock = threading.Lock()
        self._seen: collections.OrderedDict[str, None] = (
            collections.OrderedDict()
        )
        self._dedup_capacity = int(dedup_capacity)
        self._workers = [
            _WorkerState(f"rollout-{i}") for i in range(max(1, num_workers))
        ]
        self._policy: tuple[int, bytes] | None = None
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []
        self.respawns_total = 0
        self.dup_pairs_total = 0
        self.policy_pushes_total = 0
        self.rounds_received_total = 0
        self._loop = asyncio.new_event_loop()
        self._loop_thread = threading.Thread(
            target=self._loop.run_forever, name="ftc-rollout-plane",
            daemon=True,
        )
        self._loop_thread.start()

    # ---- plumbing ---------------------------------------------------------

    def _run(self, coro, timeout: float | None = None):
        import asyncio

        return asyncio.run_coroutine_threadsafe(coro, self._loop).result(
            timeout or self._rpc_timeout_s
        )

    def start(self) -> "RolloutPlane":
        for ws in self._workers:
            self._bring_up(ws)
            t = threading.Thread(
                target=self._pull_loop, args=(ws,),
                name=f"ftc-pull-{ws.worker_id}", daemon=True,
            )
            with self._lock:
                self._threads.append(t)
            t.start()
        return self

    def _bring_up(self, ws: _WorkerState) -> None:
        ws.generation += 1
        ws.handle = self._run(self._spawn_fn(ws.worker_id, ws.generation))
        ws.cursor = 0  # fresh incarnation = fresh outbox sequence
        if self._policy is not None:
            version, blob = self._policy
            self._run(ws.handle.rollout_policy_version(version, blob))
        self._run(ws.handle.rollout_start(self.pairs_per_round))
        ws.alive = True

    # ---- the pull loop (one thread per worker) ----------------------------

    def _pull_loop(self, ws: _WorkerState) -> None:
        delay: float | None = None
        while not self._stop.is_set():
            try:
                out = self._run(
                    ws.handle.rollout_pull(ws.cursor, self._pull_max_rounds)
                )
            # ftc: ignore[silent-except] -- not swallowed: every failure funnels into the respawn path below
            except Exception as exc:
                if self._stop.is_set():
                    return
                ws.alive = False
                delay = self._retry.next_delay(delay)
                logger.warning(
                    "rollout worker %s lost (%s: %s); respawning in %.2fs",
                    ws.worker_id, type(exc).__name__, exc, delay,
                )
                if self._stop.wait(delay):
                    return
                try:
                    old = ws.handle
                    if old is not None:
                        # reap the corpse (kills a half-dead process)
                        self._run(old.close(), timeout=30.0)
                # ftc: ignore[silent-except] -- best-effort reap of an already-dead worker
                except Exception:
                    pass
                try:
                    self._bring_up(ws)
                    with self._lock:
                        self.respawns_total += 1
                # ftc: ignore[silent-except] -- respawn failure loops back into the backoff above
                except Exception as exc2:
                    logger.warning("respawn of %s failed: %s",
                                   ws.worker_id, exc2)
                continue
            delay = None
            ws.last_stats = out.get("stats") or ws.last_stats
            rounds = out.get("rounds") or []
            if not rounds:
                self._stop.wait(self._idle_sleep_s)
                continue
            acked = ws.cursor
            for doc in rounds:
                self._ingest(ws, doc)
                acked = max(acked, int(doc["seq"]))
            ws.cursor = acked
            try:
                self._run(ws.handle.rollout_ack(acked))
            # ftc: ignore[silent-except] -- a lost ack only re-delivers rounds the dedup already holds
            except Exception:
                pass

    def _ingest(self, ws: _WorkerState, doc: dict) -> None:
        fresh = 0
        with self._lock:
            for pd in doc.get("pairs") or []:
                pid = str(pd["id"])
                if pid in self._seen:
                    self.dup_pairs_total += 1
                    continue
                self._seen[pid] = None
                while len(self._seen) > self._dedup_capacity:
                    self._seen.popitem(last=False)
                self.buffer.push(_pair_from_doc(pd))
                fresh += 1
            self.rounds_received_total += 1
        span = doc.get("span") or {}
        if self._spans is not None and span.get("start_ns"):
            # worker-stamped interval, learner-recorded: both processes
            # share the host clock, so the trace timeline is comparable
            self._spans.record(
                "rollout.round",
                start_ns=span["start_ns"], end_ns=span["end_ns"],
                worker=ws.worker_id, seq=int(doc.get("seq", 0)),
                policy_version=int(doc.get("version", 0)),
                pairs=fresh,
            )

    # ---- learner-facing surface ------------------------------------------

    def push_policy(self, version: int, lora_tree: dict) -> None:
        """Ship the committed trainable tree to every live worker; cached so
        respawns re-push the newest policy before streaming resumes."""
        from ..transport.wire import tree_to_blob

        blob = tree_to_blob(lora_tree)
        self._policy = (int(version), blob)
        for ws in self._workers:
            if not ws.alive:
                continue
            try:
                self._run(ws.handle.rollout_policy_version(int(version), blob))
                with self._lock:
                    self.policy_pushes_total += 1
            # ftc: ignore[silent-except] -- the puller detects the death and the respawn re-pushes the cached policy
            except Exception as exc:
                logger.warning("policy push v%d to %s failed: %s",
                               version, ws.worker_id, exc)

    def depth(self) -> int:
        with self._lock:
            return self.buffer.depth

    def evict_below(self, min_version: int, *, watermark: int) -> int:
        with self._lock:
            return self.buffer.evict_below(min_version, watermark=watermark)

    def sample_batch(self, batch_size: int, seq_len: int) -> dict:
        with self._lock:
            return self.buffer.sample_batch(batch_size, seq_len)

    def workers_alive(self) -> int:
        return sum(1 for ws in self._workers if ws.alive)

    def stats(self) -> dict[str, Any]:
        with self._lock:
            out: dict[str, Any] = dict(self.buffer.stats())
            counters = {
                "rollout_respawns_total": self.respawns_total,
                "rollout_dup_pairs_total": self.dup_pairs_total,
                "rollout_policy_pushes_total": self.policy_pushes_total,
                "rollout_rounds_received_total": self.rounds_received_total,
            }
        out.update({
            "actor_tokens_per_sec": max(
                (float(ws.last_stats.get("actor_tokens_per_sec", 0.0))
                 for ws in self._workers), default=0.0,
            ),
            "actor_version": max(
                (int(ws.last_stats.get("actor_version", 0))
                 for ws in self._workers), default=0,
            ),
            "rollout_workers_alive": self.workers_alive(),
            "rollout_actor_tokens_generated": sum(
                int(ws.last_stats.get("actor_tokens_generated", 0))
                for ws in self._workers
            ),
            "rollout_actor_generate_seconds": sum(
                float(ws.last_stats.get("actor_generate_seconds", 0.0))
                for ws in self._workers
            ),
            **counters,
        })
        return out

    def close(self) -> None:
        self._stop.set()
        with self._lock:
            threads = list(self._threads)
        for t in threads:
            t.join(timeout=15.0)
        for ws in self._workers:
            ws.alive = False
            if ws.handle is None:
                continue
            try:
                self._run(ws.handle.close(), timeout=30.0)
            # ftc: ignore[silent-except] -- teardown of workers that may already be dead
            except Exception:
                logger.debug("close of %s raced its exit", ws.worker_id,
                             exc_info=True)
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._loop_thread.join(timeout=5.0)


def remote_rollout_batch_stream(
    plane: RolloutPlane,
    ckpt_reader,
    state_template: dict,
    *,
    batch_size: int,
    seq_len: int,
    checkpoint_every: int,
    rollout: RolloutConfig,
    fill_timeout_s: float = 180.0,
) -> Iterator[dict]:
    """The learner's batch source in remote mode.  Unlike the in-process
    stream, ``next()`` never RUNS the actor — generation happens in the
    worker processes continuously — so learner steps and rollout rounds
    genuinely overlap in wall-clock.  Each ``next()``:

    1. polls the learner's checkpoint dir; a new COMMITTED step ships its
       trainable tree to the fleet (``push_policy`` — works with async
       commits: ``latest_step`` only ever reports durable checkpoints);
    2. enforces the staleness watermark on the buffer;
    3. waits (bounded) for ``min_fill``, then samples a batch.
    """
    pushed = 0
    while True:
        latest = ckpt_reader.latest_step()
        if latest is not None and latest > pushed:
            host = ckpt_reader.restore(latest, like=state_template)
            plane.push_policy(latest, host["trainable"])
            pushed = latest
        min_version = pushed - (
            rollout.staleness_checkpoints * checkpoint_every
        )
        plane.evict_below(min_version, watermark=pushed)
        deadline = time.monotonic() + fill_timeout_s
        while plane.depth() < rollout.min_fill:
            if time.monotonic() > deadline:
                if plane.depth() > 0:
                    break  # train on what we have; workers are behind
                raise RuntimeError(
                    f"no rollout pairs arrived within {fill_timeout_s:.0f}s "
                    f"({plane.workers_alive()} workers alive) — remote "
                    "actors wedged or respawn-looping"
                )
            time.sleep(0.01)
        yield plane.sample_batch(batch_size, seq_len)


def build_remote_rlhf_loop(
    trainer,
    artifacts_dir: str,
    *,
    rollout: RolloutConfig | None = None,
    pretrained_dir: str | None = None,
    prompt_fraction: float = 0.5,
    model_spec: dict | None = None,
    spawn_fn=None,
) -> tuple[Iterator[dict], RolloutPlane, RolloutBuffer]:
    """Wire remote actors + plane + buffer onto a DPO learner — the
    disaggregated twin of :func:`~.learner.build_rlhf_loop`.

    ``model_spec`` is the job spec's ``model`` section (preset/overrides/
    lora); workers rebuild the exact policy architecture from it, so it is
    required unless a custom ``spawn_fn`` is injected.
    """
    import jax

    from ..obs.trace import SpanRecorder
    from ..train.checkpoint import CheckpointManager

    rollout = (rollout or RolloutConfig()).apply_env_overrides()
    cfg = trainer.cfg
    num_workers = max(1, int(getattr(cfg, "rollout_workers", 1)))
    if jax.process_count() > 1:
        raise ValueError(
            "remote rollout workers require a single-controller learner "
            "(multi-host gangs use the in-process rlhf loop)"
        )
    state = trainer.init_state()
    if pretrained_dir:
        state = trainer.load_pretrained(state, pretrained_dir)
    if spawn_fn is None and model_spec is None:
        raise ValueError(
            "build_remote_rlhf_loop needs the job's model spec (preset/"
            "overrides/lora) so workers can rebuild the policy architecture"
        )
    write_rollout_base(
        artifacts_dir, model_spec or {}, dict(state.frozen)["params"]
    )
    # the reader MUST exist before fit's first save: CheckpointManager's
    # init sweeps leftover staging dirs, and constructing it concurrently
    # with an in-flight async save would sweep the save's own staging dir
    reader = CheckpointManager(f"{artifacts_dir}/checkpoints", keep=10**9)
    state_template = trainer.state_to_host(state)
    buffer = RolloutBuffer(
        rollout.buffer_capacity, seed=cfg.seed,
        version_granularity=max(1, cfg.checkpoint_every),
    )
    prompt_len = max(2, int(cfg.seq_len * prompt_fraction))
    if spawn_fn is None:
        from ..serve.engine import EngineConfig
        from ..transport.process import ProcessTransport

        transport = ProcessTransport(
            job_id=os.path.basename(os.path.normpath(artifacts_dir))
            or "rlhf",
            root=Path(artifacts_dir) / "rollout_workers",
            payload={
                "builder": "rollout_base", "kwargs": {"dir": artifacts_dir}
            },
        )
        bucket = 8
        while bucket < prompt_len:
            bucket <<= 1
        engine_cfg = EngineConfig(
            slots=rollout.slots, prompt_buckets=(bucket,),
            max_new_tokens=min(
                rollout.max_new_tokens, cfg.seq_len - prompt_len
            ),
            prefix_cache_bytes=0,
        )

        async def spawn_fn(worker_id: str, generation: int):
            index = int(worker_id.rsplit("-", 1)[-1])
            rdoc: dict[str, Any] = {
                "seq_len": cfg.seq_len,
                "prompt_fraction": prompt_fraction,
                "max_new_tokens": rollout.max_new_tokens,
                "temperature": rollout.temperature,
                "top_k": rollout.top_k,
                "slots": rollout.slots,
                # STABLE across respawns (never generation-dependent):
                # deterministic regeneration is what makes replayed pair
                # ids collide with their originals and dedup cleanly
                "seed": cfg.seed + index,
            }
            if rollout.reward_port:
                rdoc["reward_host"] = rollout.reward_host or "127.0.0.1"
                rdoc["reward_port"] = rollout.reward_port
            return await transport.spawn(
                worker_id, generation,
                engine_config=engine_cfg, batcher_kwargs={},
                warm_start=True, rollout=rdoc,
            )

    trace_id = os.environ.get("FTC_TRACE_ID", "")
    spans = SpanRecorder(
        artifacts_dir, trace_id, service="rollout",
        attempt=int(os.environ.get("FTC_ATTEMPT", "1") or 1),
    )
    plane = RolloutPlane(
        buffer,
        num_workers=num_workers,
        spawn_fn=spawn_fn,
        pairs_per_round=rollout.pairs_per_round,
        span_recorder=spans,
        retry=RetryPolicy(
            max_attempts=10**9, base_delay_s=0.2, max_delay_s=10.0,
            seed=cfg.seed,
        ),
    )
    plane.start()
    stream = remote_rollout_batch_stream(
        plane, reader, state_template,
        batch_size=trainer.local_batch_size,
        seq_len=cfg.seq_len,
        checkpoint_every=cfg.checkpoint_every,
        rollout=rollout,
    )
    trainer.rollout_stats_fn = plane.stats
    return stream, plane, buffer
