"""Rollout actor: the PR-4 serve engine driven by the learner's checkpoints.

The Podracer shape (PAPERS.md) on our substrate: the actor is a decoupled
rollout generator that only ever sees the learner's COMMITTED checkpoints —
weights flow exclusively through the checkpoint channel, never through shared
Python state — so the same class serves the in-process gang today and a
separate actor process later.

Per round the actor:

1. :meth:`maybe_reload` — if ``checkpoints/`` has a newer committed step,
   restore its trainable tree, fold the LoRA deltas into the base
   (``serve.loader.merge_lora_variables`` — the serving path's merge), and
   swap the engine's weight dict IN PLACE.  The engine's compiled functions
   take ``variables`` as an argument, so a reload costs zero recompiles —
   the whole loop stays inside the engine's existing compile budget (the
   armed :class:`~..analysis.recompile_guard.RecompileGuard` raises
   otherwise; ``tests/test_prefs.py::test_actor_reloads_committed_checkpoint``
   asserts it);
2. :meth:`generate_pairs` — batch-decode TWO sampled candidates per prompt
   through :class:`~..serve.engine.BatchEngine` (continuous batching: both
   candidates of all prompts share the decode lanes), score them with the
   reward function, and emit the better/worse completions as a
   :class:`~.rollout_buffer.PreferencePair` tagged with the checkpoint step.

Sampling seeds derive deterministically from (actor seed, round, prompt,
candidate), so a given checkpoint + seed always produces the same pairs.
"""

from __future__ import annotations

import logging
import time
from typing import Callable, Iterator

from ..models.lora import LoRAConfig
from ..serve.engine import BatchEngine, EngineConfig, GenRequest
from ..train.checkpoint import CheckpointManager
from .rollout_buffer import PreferencePair

logger = logging.getLogger(__name__)


def increment_reward(prompt: list[int], completion: list[int],
                     vocab_size: int) -> float:
    """Reward for the synthetic increment task: the fraction of completion
    tokens that continue the +1 (mod vocab) sequence — the programmatic
    stand-in for a reward model that makes the loop seed-deterministic and
    egress-free (RLHF-*lite*)."""
    if not completion:
        return 0.0
    prev = prompt[-1]
    good = 0
    for tok in completion:
        if tok == (prev + 1) % vocab_size:
            good += 1
        prev = tok
    return good / len(completion)


def increment_prompts(seq_len: int, vocab_size: int, seed: int,
                      prompt_fraction: float = 0.5) -> Iterator[list[int]]:
    """Deterministic stream of increment prompts (matches the prompt half of
    ``data/preference.make_increment_pair``)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    prompt_len = max(2, int(seq_len * prompt_fraction))
    while True:
        start = int(rng.integers(0, vocab_size))
        yield [(start + i) % vocab_size for i in range(prompt_len)]


class RolloutActor:
    """Generates scored preference pairs from the latest committed checkpoint.

    ``base_variables`` is the frozen base (the ``params`` collection the
    trainer initialised/loaded); the trainable adapter ALWAYS comes from the
    checkpoint directory.  Before the first commit the actor decodes with the
    plain base — exactly the policy at step 0, since LoRA's B matrices are
    zero-initialised.
    """

    def __init__(
        self,
        model: object,                      # the policy model (LoRA config)
        base_params: dict,                  # frozen base "params" tree
        ckpt_dir: str | None,
        *,
        reward_fn: Callable[[list[int], list[int]], float],
        prompts: Iterator[list[int]],
        batch_reward_fn: Callable[
            [list[tuple[list[int], list[int]]]], list[float]
        ] | None = None,
        oracle_fn: Callable[[list[int], int], list[int]] | None = None,
        state_template: dict | None = None,
        prompt_bucket: int = 0,
        max_new_tokens: int = 16,
        temperature: float = 0.8,
        top_k: int = 0,
        slots: int = 4,
        seed: int = 0,
    ):
        self._model = model
        self._model_cfg = model.cfg
        self._base_params = base_params
        #: None = push mode (the remote rollout worker): the learner SHIPS
        #: adapter deltas through :meth:`install_policy` instead of the actor
        #: polling a shared checkpoint directory it cannot see
        self._ckpt = (
            CheckpointManager(ckpt_dir, keep=10**9)  # reader: no gc
            if ckpt_dir else None
        )
        self._reward_fn = reward_fn
        #: one-RPC-per-round scoring (the remote reward model): all 2n
        #: candidates of a round score in a single batched call; falls back
        #: to per-pair ``reward_fn`` when unset
        self._batch_reward_fn = batch_reward_fn
        self._prompts = prompts
        #: cold-start escape hatch: a freshly-initialised policy samples
        #: near-uniform noise, so both candidates often score 0.0 and tie —
        #: rounds could pass without a single ranked pair.  When a WHOLE
        #: round ties, ``oracle_fn(prompt, n)`` (the reward-optimal
        #: continuation) stands in as the chosen side against the sampled
        #: rollout — the best-of-n-with-oracle-fallback bootstrap
        #: (docs/preference.md).  None disables the fallback.
        self._oracle_fn = oracle_fn
        #: host-side template of the checkpoint tree (``state_to_host``
        #: layout) — restore validates shapes against it instead of
        #: restoring blind
        self._state_template = state_template
        self.bootstrap_pairs = 0
        self._max_new_tokens = max_new_tokens
        self._temperature = temperature
        self._top_k = top_k
        self._seed = seed
        #: checkpoint step the engine currently decodes with (0 = base)
        self.version = 0
        self.reloads = 0
        self.rounds = 0
        self.pairs_generated = 0
        self.tokens_generated = 0
        self.generate_seconds = 0.0
        # rank-0 twin for the merged serving weights (serve-loader semantics)
        self._merged_cfg = self._model_cfg.replace(
            lora=LoRAConfig(rank=0, alpha=self._model_cfg.lora.alpha,
                            targets=self._model_cfg.lora.targets)
        )
        self._merged_model = type(model)(cfg=self._merged_cfg)
        # one prefill bucket sized to the prompt distribution (the caller
        # knows it); default: the model's max — correct but compiles a
        # bigger-than-needed prefill
        bucket = 8
        prompt_cap = prompt_bucket or max(2, int(self._model_cfg.max_seq_len))
        while bucket < prompt_cap:
            bucket <<= 1
        self._engine = BatchEngine(
            self._merged_model,
            self._merge({}),  # adapterless start = the step-0 policy
            EngineConfig(
                slots=slots,
                prompt_buckets=(bucket,),
                max_new_tokens=max_new_tokens,
                # stale KV from a pre-reload policy must never splice into a
                # post-reload admission, so the prefix cache stays off here
                prefix_cache_bytes=0,
            ),
        )

    # ---- weights ---------------------------------------------------------

    def _merge(self, lora_tree: dict) -> dict:
        """Fold adapter deltas into the base kernels (dense serve weights)."""
        if not lora_tree:
            return {"params": self._base_params}
        from ..serve.loader import merge_lora_variables

        _, merged = merge_lora_variables(
            self._model_cfg,
            {"params": self._base_params, "lora": lora_tree},
        )
        return merged

    def maybe_reload(self) -> bool:
        """Swap in the newest committed checkpoint's policy; True on reload.

        Variables are an ARGUMENT of the engine's compiled fns, so this
        never recompiles — shapes are identical across checkpoints.
        """
        if self._ckpt is None:
            return False  # push mode: install_policy is the only reload path
        latest = self._ckpt.latest_step()
        if latest is None or latest == self.version:
            return False
        host = self._ckpt.restore(latest, like=self._state_template)
        self._engine.variables = self._merge(host["trainable"])
        self.version = latest
        self.reloads += 1
        logger.info("actor reloaded policy from checkpoint step %d", latest)
        return True

    def install_policy(self, version: int, lora_tree: dict | None) -> bool:
        """Push-mode rollover: install a learner-shipped adapter delta.

        Idempotent and monotonic — a re-delivered or stale push (version ≤
        the installed one) is a no-op, so the learner may re-push after a
        respawn without version checks of its own.  Same zero-recompile
        in-place swap as :meth:`maybe_reload`.
        """
        version = int(version)
        if version <= self.version:
            return False
        self._engine.variables = self._merge(dict(lora_tree or {}))
        self.version = version
        self.reloads += 1
        logger.info("actor installed pushed policy version %d", version)
        return True

    @property
    def compilations(self) -> int:
        return self._engine.compilations

    @property
    def compile_budget(self) -> int:
        return self._engine.guard.budget

    @property
    def tokens_per_sec(self) -> float:
        return self.tokens_generated / max(self.generate_seconds, 1e-9)

    # ---- rollouts --------------------------------------------------------

    def generate_pairs(self, n_pairs: int) -> list[PreferencePair]:
        """Decode 2 sampled candidates for each of ``n_pairs`` prompts and
        rank them by reward.  Ties are kept out of the buffer (a tied pair
        carries no preference signal and would only flatten the margin)."""
        self.rounds += 1
        prompts = [next(self._prompts) for _ in range(n_pairs)]
        requests = []
        for i, prompt in enumerate(prompts):
            for cand in range(2):
                requests.append(GenRequest(
                    request_id=f"r{self.rounds}p{i}c{cand}",
                    tokens=list(prompt),
                    max_new_tokens=self._max_new_tokens,
                    temperature=self._temperature,
                    top_k=self._top_k,
                    # deterministic per (actor seed, round, prompt, candidate)
                    seed=(((self._seed * 1_000_003 + self.rounds) * 4093
                           + i) * 2 + cand),
                ))
        t0 = time.perf_counter()
        results = self._engine.run(requests)
        self.generate_seconds += time.perf_counter() - t0
        pairs: list[PreferencePair] = []
        scored: list[tuple[list[int], list[list[int]], list[float]]] = []
        all_outs: list[list[list[int]]] = []
        for i, prompt in enumerate(prompts):
            outs = [
                results[f"r{self.rounds}p{i}c{c}"].generated for c in (0, 1)
            ]
            self.tokens_generated += sum(len(o) for o in outs)
            all_outs.append(outs)
        if self._batch_reward_fn is not None:
            # one batched scoring call for the whole round's 2n candidates
            # (one RPC when the reward model serves remotely)
            flat = self._batch_reward_fn([
                (prompt, out)
                for prompt, outs in zip(prompts, all_outs) for out in outs
            ])
            all_rewards = [
                [float(flat[2 * i]), float(flat[2 * i + 1])]
                for i in range(len(prompts))
            ]
        else:
            all_rewards = [
                [self._reward_fn(p, o) for o in outs]
                for p, outs in zip(prompts, all_outs)
            ]
        for i, prompt in enumerate(prompts):
            outs, rewards = all_outs[i], all_rewards[i]
            scored.append((prompt, outs, rewards))
            if rewards[0] == rewards[1]:
                continue
            hi, lo = (0, 1) if rewards[0] > rewards[1] else (1, 0)
            pairs.append(PreferencePair(
                prompt=tuple(prompt),
                chosen=tuple(outs[hi]),
                rejected=tuple(outs[lo]),
                version=self.version,
                reward_chosen=rewards[hi],
                reward_rejected=rewards[lo],
            ))
        if not pairs and self._oracle_fn is not None:
            # whole round tied (cold-start noise): oracle-bootstrap — the
            # reward-optimal continuation beats any imperfect rollout
            for prompt, outs, rewards in scored:
                if rewards[0] >= 1.0:
                    continue  # the rollout is already optimal; no signal
                oracle = self._oracle_fn(prompt, len(outs[0]) or 1)
                pairs.append(PreferencePair(
                    prompt=tuple(prompt),
                    chosen=tuple(oracle),
                    rejected=tuple(outs[0]),
                    version=self.version,
                    reward_chosen=self._reward_fn(prompt, oracle),
                    reward_rejected=rewards[0],
                ))
                self.bootstrap_pairs += 1
        self.pairs_generated += len(pairs)
        return pairs
