"""The prefix-reuse KV cache (ISSUE 6, docs/serving.md).

Three layers of proof:

* **trie semantics** (no model, no device): longest-common-prefix
  resolution including mid-edge divergence, and the bucket-granular reuse
  arithmetic (``resolve_reuse_length``);
* **byte-budget LRU**: eviction under pressure, recency refresh on hit,
  oversized-snapshot refusal;
* **the correctness anchor**: engine outputs with the cache ON are
  bit-identical to cache OFF (greedy and sampled, hit and miss), the
  compile budget stays ``2*len(buckets) + 1``, and evicting a snapshot
  while a request decodes from its splice changes nothing (lanes hold
  device-side copies).
"""

from __future__ import annotations

import jax
import pytest

from test_serve import _baseline, tiny_model  # noqa: F401 — shared fixture

from finetune_controller_tpu.serve.engine import (
    BatchEngine,
    EngineConfig,
    GenRequest,
)
from finetune_controller_tpu.serve.prefix_cache import (
    PrefixCache,
    resolve_reuse_length,
)


# ---------------------------------------------------------------------------
# resolve_reuse_length: bucket-granular reuse arithmetic
# ---------------------------------------------------------------------------


def test_resolve_reuse_length_bucket_granularity():
    buckets, cache_len = (32, 128, 512), 640  # max_new = 128
    # plain case: the suffix pads to the smallest bucket and fits
    assert resolve_reuse_length(100, 110, buckets, cache_len) == 100
    # full-prompt match still leaves one real token for the logits forward
    assert resolve_reuse_length(110, 110, buckets, cache_len) == 109
    # a match longer than the prompt is clamped the same way
    assert resolve_reuse_length(500, 110, buckets, cache_len) == 109
    # no match, or a single-token prompt, cannot reuse anything
    assert resolve_reuse_length(0, 110, buckets, cache_len) == 0
    assert resolve_reuse_length(1, 1, buckets, cache_len) == 0


def test_resolve_reuse_length_shrinks_on_bucket_overshoot():
    buckets, cache_len = (32, 128, 512), 640
    # match 381 of 512: suffix 131 pads to bucket 512 and 381+512 > 640 —
    # reuse shrinks to cache_len - 512 = 128 so the padded suffix fits
    reuse = resolve_reuse_length(381, 512, buckets, cache_len)
    assert reuse == 128
    suffix_bucket = next(b for b in buckets if 512 - reuse <= b)
    assert reuse + suffix_bucket <= cache_len
    assert reuse <= 381  # never reuses more than actually matched
    # tight cache: exactly the bucket itself -> miss, never an OOB splice
    assert resolve_reuse_length(381, 512, (512,), 512) == 0
    # one slack slot past the bucket: a (barely useful) 1-token reuse
    assert resolve_reuse_length(381, 512, (512,), 513) == 1


# ---------------------------------------------------------------------------
# Radix trie: longest-common-prefix lookup (no device arrays needed)
# ---------------------------------------------------------------------------


def _cache_with(pc: PrefixCache, key, tag, nbytes=10):
    assert pc.insert(key, tag, nbytes=nbytes)
    return tag


def test_trie_longest_prefix_resolution():
    pc = PrefixCache(budget_bytes=1000)
    _cache_with(pc, (1, 2, 3, 4, 5), "A")
    _cache_with(pc, (1, 2, 9, 9), "B")
    _cache_with(pc, (7, 7), "C")

    # exact key
    assert pc.lookup((1, 2, 3, 4, 5)) == (5, "A")
    # query extends a stored key: match = whole key
    assert pc.lookup((7, 7, 1, 2)) == (2, "C")
    # query diverges MID-EDGE: [1,2,3,9] shares 3 tokens with A's path
    n, cache = pc.lookup((1, 2, 3, 9))
    assert (n, cache) == (3, "A")
    # divergence at the [1,2] branch point: either snapshot proves 2 tokens
    n, cache = pc.lookup((1, 2, 5))
    assert n == 2 and cache in ("A", "B")
    # query is a strict prefix of a stored key
    n, cache = pc.lookup((1, 2, 9))
    assert (n, cache) == (3, "B")
    # complete miss
    assert pc.lookup((4, 4, 4)) == (0, None)
    assert len(pc) == 3


def test_trie_lru_byte_budget_eviction():
    pc = PrefixCache(budget_bytes=25)  # fits two 10-byte snapshots
    _cache_with(pc, (1, 1, 1), "A")
    _cache_with(pc, (2, 2, 2), "B")
    assert pc.total_bytes == 20
    _cache_with(pc, (3, 3, 3), "C")  # evicts A (least recently used)
    assert pc.lookup((1, 1, 1)) == (0, None)
    assert pc.lookup((2, 2, 2))[1] == "B"
    assert pc.evictions_total == 1 and pc.total_bytes == 20

    # a HIT refreshes recency: touch B, insert D -> C (not B) evicts
    pc.lookup((2, 2, 2))
    _cache_with(pc, (4, 4, 4), "D")
    assert pc.lookup((3, 3, 3)) == (0, None)
    assert pc.lookup((2, 2, 2))[1] == "B"

    # a snapshot larger than the whole budget is refused outright
    assert not pc.insert((5, 5, 5), "huge", nbytes=100)
    assert pc.lookup((5, 5, 5)) == (0, None)
    # re-inserting an existing key refreshes instead of double-counting
    assert pc.insert((2, 2, 2), "B2", nbytes=10)
    assert pc.total_bytes == 20
    assert pc.lookup((2, 2, 2))[1] == "B"


def test_trie_eviction_prunes_dead_branches():
    pc = PrefixCache(budget_bytes=100)
    _cache_with(pc, (1, 2, 3), "A")
    _cache_with(pc, (1, 2, 3, 4, 5), "B")
    # evict B by pressure: fill with unrelated keys sized to push it out
    pc.lookup((1, 2, 3))  # A is now most recent
    _cache_with(pc, (9,), "C", nbytes=85)  # 10+10+85 > 100 -> B evicts
    assert pc.evictions_total == 1
    # the pruned branch no longer resolves past A's key
    assert pc.lookup((1, 2, 3, 4, 5)) == (3, "A")
    assert pc.lookup((1, 2, 3)) == (3, "A")


# ---------------------------------------------------------------------------
# Engine integration: bit-identity, budget, mid-flight eviction
# ---------------------------------------------------------------------------


def _engine(model, variables, **kw):
    """test_serve's engine shape, with the prefix cache ON by default."""
    defaults = dict(slots=4, prompt_buckets=(8, 16), max_new_tokens=24,
                    prefix_cache_bytes=1 << 20)
    defaults.update(kw)
    return BatchEngine(model, variables, EngineConfig(**defaults))


SHARED = [5, 9, 2, 7, 1, 3, 3, 8, 2, 2]  # 10-token "system prompt"
PROMPTS = [SHARED + [11, 4], SHARED + [7, 7, 7], SHARED + [2], [6, 1, 4]]


def test_greedy_bit_identity_cache_on_vs_off(tiny_model):
    """The acceptance anchor: greedy tokens with the prefix cache enabled —
    misses, shared-prefix hits, and exact-key hits alike — are bit-identical
    to the cache-off engine and to single-request cached_generate."""
    model, variables = tiny_model
    eng = _engine(model, variables)

    def reqs(tag):
        return [
            GenRequest(request_id=f"{tag}{i}", tokens=p, max_new_tokens=8)
            for i, p in enumerate(PROMPTS)
        ]

    first = eng.run(reqs("a"))   # pass 1: misses seed the cache (+ 3 hits)
    second = eng.run(reqs("b"))  # pass 2: every prompt resolves a prefix
    assert eng.prefix_hits_total >= len(PROMPTS)  # pass 2 is all hits
    assert eng.prefill_tokens_saved_total > 0
    for i, p in enumerate(PROMPTS):
        want = _baseline(model, variables, p, 8)
        assert first[f"a{i}"].generated == want, f"pass-1 r{i} diverged"
        assert second[f"b{i}"].generated == want, f"hit-path r{i} diverged"
    # the budget holds with the cache on: fill+fill_from per bucket + decode
    assert eng.guard.on_excess == "raise"
    assert eng.compilations <= 2 * len(eng.config.prompt_buckets) + 1


@pytest.mark.slow  # runs on every ci_check gate via the serve-fast stage
def test_sampled_bit_identity_cache_on_vs_off(tiny_model):
    """Sampled decode reproduces the per-request PRNGKey(seed) stream on
    both the miss path and the prefix-hit path."""
    model, variables = tiny_model
    eng = _engine(model, variables)
    prompts = PROMPTS[:3]

    def reqs(tag):
        return [
            GenRequest(request_id=f"{tag}{i}", tokens=p, max_new_tokens=8,
                       temperature=0.7, top_k=5, seed=100 + i)
            for i, p in enumerate(prompts)
        ]

    first = eng.run(reqs("a"))
    second = eng.run(reqs("b"))  # all prefix hits
    assert eng.prefix_hits_total >= len(prompts)
    for i, p in enumerate(prompts):
        want = _baseline(model, variables, p, 8, temperature=0.7, top_k=5,
                         rng=jax.random.PRNGKey(100 + i))
        assert first[f"a{i}"].generated == want
        assert second[f"b{i}"].generated == want


@pytest.mark.slow  # runs on every ci_check gate via the serve-fast stage
def test_snapshot_eviction_mid_flight_is_invisible(tiny_model):
    """Evicting a snapshot while a request decodes from its splice changes
    nothing: lanes receive device-side copies, eviction only drops refs."""
    model, variables = tiny_model
    # budget sized to ONE snapshot: every insert evicts the previous one
    probe = _engine(model, variables)
    probe.admit(GenRequest(request_id="p", tokens=PROMPTS[0],
                           max_new_tokens=2))
    one_snapshot = probe.prefix_cache_bytes
    assert one_snapshot > 0
    eng = _engine(model, variables, prefix_cache_bytes=one_snapshot)

    r1 = GenRequest(request_id="r1", tokens=PROMPTS[0], max_new_tokens=8)
    eng.admit(r1)          # miss; snapshot for PROMPTS[0] stored
    eng.step()
    eng.step()             # r1 is mid-flight, decoding from the splice
    evictions_before = eng._prefix_cache.evictions_total
    r2 = GenRequest(request_id="r2", tokens=PROMPTS[3], max_new_tokens=4)
    eng.admit(r2)          # insert evicts r1's snapshot under the budget
    assert eng._prefix_cache.evictions_total > evictions_before
    results = {}
    while eng.active_requests:
        for r in eng.step():
            results[r.request_id] = r
    assert results["r1"].generated == _baseline(model, variables, PROMPTS[0], 8)
    assert results["r2"].generated == _baseline(model, variables, PROMPTS[3], 4)


@pytest.mark.slow  # runs on every ci_check gate via the serve-fast stage
def test_prefix_stats_and_disabled_engine(tiny_model):
    """Counter bookkeeping: hits/misses/saved line up with the workload, and
    a cache-off engine reports inert zeros."""
    model, variables = tiny_model
    eng = _engine(model, variables)
    req = GenRequest(request_id="x", tokens=PROMPTS[0], max_new_tokens=2)
    eng.run([req])
    assert (eng.prefix_hits_total, eng.prefix_misses_total) == (0, 1)
    eng.run([GenRequest(request_id="y", tokens=PROMPTS[0], max_new_tokens=2)])
    # exact-key hit reuses all but the final (logits-producing) token
    assert (eng.prefix_hits_total, eng.prefix_misses_total) == (1, 1)
    assert eng.prefill_tokens_saved_total == len(PROMPTS[0]) - 1
    assert eng.prefix_cache_entries == 1

    off = _engine(model, variables, prefix_cache_bytes=0)
    off.run([GenRequest(request_id="z", tokens=PROMPTS[0], max_new_tokens=2)])
    assert off.prefix_hits_total == 0 and off.prefix_misses_total == 0
    assert off.prefix_cache_bytes == 0 and off.prefix_cache_entries == 0


def test_shared_system_prompt_saves_most_prefill_tokens(tiny_model):
    """The workload the cache exists for: requests that share a long system
    prompt and differ in a short tail.  Once the prefix is cached, more than
    half of all prompt tokens are never prefilled again, with greedy output
    unchanged and the compile budget held."""
    model, variables = tiny_model
    system = [(7 * i + 3) % 200 + 1 for i in range(24)]
    prompts = [system + [210 + i, 220 + i] for i in range(6)]
    eng = _engine(model, variables, prompt_buckets=(8, 32))

    def reqs(tag):
        return [
            GenRequest(request_id=f"{tag}{i}", tokens=p, max_new_tokens=4)
            for i, p in enumerate(prompts)
        ]

    eng.run(reqs("w"))  # seeds the cache
    saved0 = eng.prefill_tokens_saved_total
    out = eng.run(reqs("m"))
    saved = eng.prefill_tokens_saved_total - saved0
    assert saved > 0.5 * sum(len(p) for p in prompts), saved
    for i, p in enumerate(prompts):
        assert out[f"m{i}"].generated == _baseline(model, variables, p, 4)
    assert eng.compilations <= 2 * len(eng.config.prompt_buckets) + 1
