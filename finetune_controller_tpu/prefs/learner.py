"""RLHF-lite learner: the actor/learner gang wired through the SFT machinery.

The learner IS :class:`~.dpo_trainer.DPOTrainer` — same jitted step,
checkpoints, elastic resume, preemption handling.  What makes it an
actor/learner loop is the BATCH STREAM: :func:`rollout_batch_stream` is an
iterator whose ``next()`` runs the actor's control loop before yielding a
batch —

1. reload the policy if the learner committed a new checkpoint
   (:meth:`~.actor.RolloutActor.maybe_reload` — so the actor picks up step
   N+1 on the first batch after the commit, i.e. within one round);
2. enforce the staleness watermark on the rollout buffer (the learner never
   trains on pairs more than ``staleness_checkpoints`` checkpoints old);
3. top the buffer up with fresh on-policy pairs until it holds at least
   ``min_fill``;
4. yield a seed-deterministic DPO batch sampled from the buffer.

Because ``Trainer.fit`` pulls batches synchronously (the rlhf path forces
``prefetch=0`` — the actor's engine must not decode on a background thread
interleaved with the learner's jitted steps), the actor and learner execute
as a round-robin gang on the job's chips: generate, then train, then
generate — the Podracer architecture collapsed onto one substrate, with the
``sched/`` gang admission holding the chips for both halves atomically
(``atomic_gang`` in the job spec).
"""

from __future__ import annotations

import dataclasses
import logging
import os
from typing import Iterator

from .actor import RolloutActor, increment_prompts, increment_reward
from .dpo_trainer import DPOTrainer
from .rollout_buffer import RolloutBuffer

logger = logging.getLogger(__name__)


@dataclasses.dataclass
class RolloutConfig:
    """Knobs of the actor/learner loop (job-spec arguments; the ``FTC_RLHF_*``
    env vars in ``examples/ftc.env.example`` are per-pod operator
    overrides)."""

    pairs_per_round: int = 16
    buffer_capacity: int = 256
    #: min pairs in the buffer before the learner takes a batch
    min_fill: int = 16
    #: drop pairs older than this many CHECKPOINTS behind the newest commit
    staleness_checkpoints: int = 2
    temperature: float = 0.8
    top_k: int = 0
    max_new_tokens: int = 16
    #: decode lanes of the actor's serve engine
    slots: int = 4
    #: consecutive pair-less rollout rounds tolerated before the learner
    #: proceeds on a partially-filled buffer (or fails loudly on an empty
    #: one) — the liveness backstop for converged/wedged policies
    max_empty_rounds: int = 25
    #: learned reward model endpoint (remote rollout workers only): when
    #: ``reward_port`` is set, actors score candidates through the served
    #: reward model's batched ``reward_score`` RPC instead of the
    #: programmatic increment reward (docs/preference.md §Disaggregated
    #: rollouts)
    reward_host: str = ""
    reward_port: int = 0

    _ENV_FIELDS = {
        "pairs_per_round": "FTC_RLHF_PAIRS_PER_ROUND",
        "buffer_capacity": "FTC_RLHF_BUFFER_CAPACITY",
        "min_fill": "FTC_RLHF_MIN_FILL",
        "staleness_checkpoints": "FTC_RLHF_STALENESS_CHECKPOINTS",
        "temperature": "FTC_RLHF_TEMPERATURE",
        "top_k": "FTC_RLHF_TOP_K",
        "max_new_tokens": "FTC_RLHF_MAX_NEW_TOKENS",
        "slots": "FTC_RLHF_SLOTS",
        "reward_host": "FTC_RLHF_REWARD_HOST",
        "reward_port": "FTC_RLHF_REWARD_PORT",
    }

    def apply_env_overrides(self) -> "RolloutConfig":
        """Operator env overrides (read in the job pod, not the controller)."""
        out = self
        for field, env in self._ENV_FIELDS.items():
            raw = os.environ.get(env)
            if raw is None:
                continue
            kind = type(getattr(self, field))
            out = dataclasses.replace(out, **{field: kind(raw)})
        return out


def rollout_batch_stream(
    actor: RolloutActor,
    buffer: RolloutBuffer,
    *,
    batch_size: int,
    seq_len: int,
    checkpoint_every: int,
    rollout: RolloutConfig,
) -> Iterator[dict]:
    """The learner's infinite batch source — see the module docstring."""
    while True:
        reloaded = actor.maybe_reload()
        min_version = actor.version - (
            rollout.staleness_checkpoints * checkpoint_every
        )
        buffer.evict_below(min_version, watermark=actor.version)
        if reloaded:
            # fresh policy ⇒ fresh on-policy data: one generation round per
            # reload keeps the buffer tracking the newest checkpoint even
            # when nothing was evicted yet
            for pair in actor.generate_pairs(rollout.pairs_per_round):
                buffer.push(pair)
        empty_rounds = 0
        while buffer.depth < rollout.min_fill:
            fresh = actor.generate_pairs(rollout.pairs_per_round)
            for pair in fresh:
                buffer.push(pair)
            if fresh:
                empty_rounds = 0
                continue
            # an all-ties round: common early (a fresh policy decodes
            # near-uniform noise — the oracle bootstrap usually breaks it)
            # and again at CONVERGENCE (every candidate scores 1.0, so
            # neither ranking nor bootstrap yields signal).  Bounded: past
            # the cap, train on whatever the buffer holds rather than
            # busy-looping the decoder forever; a buffer with NOTHING to
            # train on is a wedged reward function — fail loudly.
            empty_rounds += 1
            logger.info(
                "rollout round %d produced no ranked pairs (%d empty in a "
                "row)", actor.rounds, empty_rounds,
            )
            if empty_rounds >= rollout.max_empty_rounds:
                if buffer.depth > 0:
                    logger.info(
                        "proceeding below min_fill (%d/%d pairs) after %d "
                        "pair-less rounds — policy likely converged",
                        buffer.depth, rollout.min_fill, empty_rounds,
                    )
                    break
                raise RuntimeError(
                    f"{empty_rounds} consecutive rollout rounds produced no "
                    "preference pairs and the buffer is empty — the reward "
                    "function cannot rank this policy's samples"
                )
        yield buffer.sample_batch(batch_size, seq_len)


def build_rlhf_loop(
    trainer: DPOTrainer,
    artifacts_dir: str,
    *,
    rollout: RolloutConfig | None = None,
    pretrained_dir: str | None = None,
    prompt_fraction: float = 0.5,
) -> tuple[Iterator[dict], RolloutActor, RolloutBuffer]:
    """Wire an actor + buffer + batch stream onto a DPO learner.

    The actor shares the FROZEN base with the learner (same init seed — or
    the same pretrained weights — so the step-0 policy is identical), but
    its trainable adapter always comes from committed checkpoints: weights
    cross the actor/learner boundary only through the checkpoint channel.

    Known cost at scale: ``Trainer.fit`` re-inits (and re-loads pretrained
    weights) on entry, so the init here is paid twice and the actor pins
    its own base copy on device — fine for the current gang-on-one-substrate
    shape, and it disappears when the actor becomes a separate process
    (ROADMAP item 5 follow-on (a)).
    """
    import jax

    rollout = (rollout or RolloutConfig()).apply_env_overrides()
    cfg = trainer.cfg
    model_cfg = trainer.model_cfg
    state = trainer.init_state()
    if pretrained_dir:
        state = trainer.load_pretrained(state, pretrained_dir)
    vocab = model_cfg.vocab_size
    prompt_len = max(2, int(cfg.seq_len * prompt_fraction))
    # per-process seed offset: on a multi-host gang every host builds its
    # own loop, and identical seeds would make all hosts generate (and
    # sample) the SAME rollouts — a global batch of duplicated rows.  The
    # same shard-offset discipline every other data path uses.
    shard = jax.process_index()
    actor = RolloutActor(
        trainer.model,
        dict(state.frozen)["params"],
        f"{artifacts_dir}/checkpoints",
        reward_fn=lambda p, c: increment_reward(p, c, vocab),
        prompts=increment_prompts(
            cfg.seq_len, vocab, cfg.seed + 7919 + shard, prompt_fraction
        ),
        # the reward-optimal continuation — the cold-start bootstrap side
        oracle_fn=lambda p, n: [(p[-1] + 1 + i) % vocab for i in range(n)],
        # shape-validated restores (collective on multi-host — all hosts
        # build the loop, so all participate in the gather)
        state_template=trainer.state_to_host(state),
        prompt_bucket=prompt_len,
        max_new_tokens=min(rollout.max_new_tokens, cfg.seq_len - prompt_len),
        temperature=rollout.temperature,
        top_k=rollout.top_k,
        slots=rollout.slots,
        seed=cfg.seed + shard,
    )
    buffer = RolloutBuffer(
        rollout.buffer_capacity, seed=cfg.seed + shard,
        # versions are checkpoint STEPS; report staleness in checkpoints —
        # the unit the staleness_checkpoints knob (and the operator) uses
        version_granularity=max(1, cfg.checkpoint_every),
    )
    stream = rollout_batch_stream(
        actor, buffer,
        batch_size=trainer.local_batch_size,
        seq_len=cfg.seq_len,
        checkpoint_every=cfg.checkpoint_every,
        rollout=rollout,
    )

    def stats() -> dict:
        return {
            **buffer.stats(),
            "actor_tokens_per_sec": round(actor.tokens_per_sec, 1),
            "actor_version": actor.version,
        }

    trainer.rollout_stats_fn = stats
    return stream, actor, buffer
