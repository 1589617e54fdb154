"""Paged KV cache: allocator invariants + paged-engine numerics (ISSUE 11).

The acceptance anchors: greedy AND sampled decode through the page pool are
BIT-IDENTICAL to the unpaged path and to single-request ``cached_generate``
across staggered mixed-length batches, page-boundary-straddling prefills
(copy-on-write suffix splices), evict-refill page reuse (no stale reads),
and mid-flight prefix-entry eviction — while pool exhaustion surfaces as
queueing backpressure (and 429s with Retry-After past the queue), never as
an OOM or a corrupted lane.
"""

from __future__ import annotations

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_async
from finetune_controller_tpu.models.generate import cached_generate
from finetune_controller_tpu.models.llama import PRESETS, LlamaForCausalLM
from finetune_controller_tpu.models.lora import LoRAConfig
from finetune_controller_tpu.serve.batcher import Batcher, QueueFull
from finetune_controller_tpu.serve.engine import (
    BatchEngine,
    EngineConfig,
    GenRequest,
)
from finetune_controller_tpu.serve.kv_pages import (
    HostPagePool,
    HostRun,
    KVPagePool,
    PageRun,
    PoolExhausted,
)
from finetune_controller_tpu.serve.prefix_cache import PrefixCache


@pytest.fixture(scope="module")
def tiny_model():
    cfg = PRESETS["tiny-test"].replace(lora=LoRAConfig(rank=4))
    model = LlamaForCausalLM(cfg)
    variables = model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 4), jnp.int32)
    )
    return model, variables


def _paged_engine(model, variables, **kw):
    defaults = dict(slots=4, prompt_buckets=(8, 16), max_new_tokens=24,
                    page_tokens=8)
    defaults.update(kw)
    return BatchEngine(model, variables, EngineConfig(**defaults))


def _baseline(model, variables, prompt, n, **kw):
    out = cached_generate(
        model, variables, jnp.asarray([prompt], jnp.int32),
        max_new_tokens=n, **kw,
    )
    return list(np.asarray(out[0, len(prompt):]))


# ---------------------------------------------------------------------------
# KVPagePool allocator invariants (pure host logic, no jax)
# ---------------------------------------------------------------------------


def test_pool_alloc_release_roundtrip():
    pool = KVPagePool(num_pages=8, page_tokens=4, page_bytes=100)
    assert pool.usable_pages == 7 and pool.free_count == 7
    pool.reserve(3)
    pages = [pool.alloc_reserved() for _ in range(3)]
    assert 0 not in pages  # scratch is never handed out
    assert pool.free_count == 4 and pool.used_count == 3
    assert pool.reserved_outstanding == 0
    pool.lane_release(pages)
    assert pool.free_count == 7 and pool.used_count == 0


def test_pool_reserve_respects_slack_and_raises():
    pool = KVPagePool(num_pages=6, page_tokens=4)
    pool.reserve(5)
    assert pool.slack() == 0
    with pytest.raises(PoolExhausted):
        pool.reserve(1)
    assert pool.exhaustions_total == 1
    pool.unreserve(5)
    assert pool.slack() == 5


def test_pool_cache_only_pages_count_toward_slack_and_evict_on_demand():
    """Pages held ONLY by prefix-cache entries are evictable capacity: they
    count in the admission slack and free when the entry releases them."""
    pool = KVPagePool(num_pages=6, page_tokens=4, page_bytes=10)
    pool.reserve(3)
    pages = [pool.alloc_reserved() for _ in range(3)]
    charged = pool.cache_ref(pages)
    assert charged == 3  # first cache reference charges each page once
    pool.lane_release(pages)          # lane done; entry keeps them resident
    assert pool.free_count == 2
    assert pool.slack() == 5          # 2 free + 3 evictable
    # a second entry sharing two of the pages charges nothing new
    assert pool.cache_ref(pages[:2]) == 0
    assert pool.cache_release(pages[:2]) == 0  # still held by entry 1
    evicted = {"n": 0}

    def evict_one():
        if evicted["n"] >= 1:
            return False
        evicted["n"] += 1
        pool.cache_release(pages)
        return True

    pool.reserve(4)
    got = [pool.alloc_reserved(evict_one) for _ in range(4)]
    assert len(set(got)) == 4 and evicted["n"] == 1


def test_pool_shared_count_tracks_multi_holder_pages():
    pool = KVPagePool(num_pages=6, page_tokens=4)
    pool.reserve(2)
    pages = [pool.alloc_reserved() for _ in range(2)]
    assert pool.shared_count == 0
    pool.lane_ref(pages[0])  # a second lane splices it
    assert pool.shared_count == 1
    pool.cache_ref(pages)
    assert pool.shared_count == 2


# ---------------------------------------------------------------------------
# Paged engine: the bit-identity anchors
# ---------------------------------------------------------------------------


def test_paged_batching_invariance_mixed_staggered(tiny_model):
    """Greedy tokens through the page pool — mixed prompt lengths, requests
    joining mid-flight — are bit-identical to single-request
    cached_generate AND to the unpaged engine, for every request."""
    model, variables = tiny_model
    prompts = [
        [5, 9, 2, 7],
        [1, 3, 3, 8, 2, 2],
        [7, 7, 7],
        [11, 4, 9, 1, 2, 3, 4, 5, 6, 0, 2, 1],  # second bucket
        [2, 13],
    ]
    reqs = [
        GenRequest(request_id=f"r{i}", tokens=p, max_new_tokens=6 + 2 * i)
        for i, p in enumerate(prompts)
    ]
    paged = _paged_engine(model, variables, slots=2, pool_pages=12)
    unpaged = BatchEngine(model, variables, EngineConfig(
        slots=2, prompt_buckets=(8, 16), max_new_tokens=24))
    res_p = paged.run(list(reqs))
    res_u = unpaged.run(list(reqs))
    for i, p in enumerate(prompts):
        want = _baseline(model, variables, p, 6 + 2 * i)
        assert res_p[f"r{i}"].generated == want, f"paged diverged on r{i}"
        assert res_u[f"r{i}"].generated == want
    # the run drained: every page returned to the free list
    stats = paged.kv_page_stats()
    assert stats["pages_used"] == 0
    assert stats["pages_free"] == stats["pages_total"]


def test_paged_sampled_decode_reproducible(tiny_model):
    """Sampled decode through the pool reproduces the per-request
    PRNGKey(seed) stream bit-for-bit, independent of batch-mates."""
    model, variables = tiny_model
    reqs = [
        GenRequest(request_id=f"s{i}", tokens=[3 + i, 1, 4, 1], seed=40 + i,
                   temperature=0.8, top_k=7, max_new_tokens=8)
        for i in range(4)
    ]
    eng = _paged_engine(model, variables, slots=4, pool_pages=20)
    res = eng.run(reqs)
    for i in range(4):
        want = _baseline(
            model, variables, [3 + i, 1, 4, 1], 8,
            temperature=0.8, top_k=7, rng=jax.random.PRNGKey(40 + i),
        )
        assert res[f"s{i}"].generated == want


def test_page_boundary_straddling_prefill_and_cow_splice(tiny_model):
    """A page size that divides NEITHER the buckets NOR the reuse length:
    suffix prefills straddle page boundaries and the prefix splice must
    copy-on-write the boundary page.  Outputs stay bit-identical and the
    CoW copy actually happens."""
    model, variables = tiny_model
    eng = _paged_engine(
        model, variables, slots=2, page_tokens=7, pool_pages=16,
        prefix_cache_bytes=1 << 20,
    )
    shared = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]   # 10 tokens: 1.43 pages of 7
    reqs = [
        GenRequest(request_id=f"b{i}", tokens=shared + [20 + i],
                   max_new_tokens=7)
        for i in range(4)
    ]
    res = eng.run(reqs)
    for i in range(4):
        want = _baseline(model, variables, shared + [20 + i], 7)
        assert res[f"b{i}"].generated == want, f"b{i} diverged"
    assert eng.prefix_hits_total >= 3
    assert eng.prefill_tokens_saved_total > 0
    # reuse length (bucket-rounded) is not page-aligned here, so the hit
    # path must have copied the boundary page instead of sharing it
    assert eng.kv_page_stats()["cow_copies_total"] >= 1


def test_paged_evict_refill_no_stale_reads(tiny_model):
    """Freed pages get reallocated to new lanes; the recycled pages must
    never leak the previous occupant's KV into a fresh request."""
    model, variables = tiny_model
    # pool sized so the second wave MUST reuse the first wave's pages
    eng = _paged_engine(model, variables, slots=2, pool_pages=11)
    first = [
        GenRequest(request_id=f"a{i}", tokens=[9 - i, 2, 7, 1, 8],
                   max_new_tokens=10)
        for i in range(2)
    ]
    for r in first:
        eng.admit(r)
    for _ in range(3):
        eng.step()
    assert eng.evict("a0") is not None  # mid-flight eviction frees pages NOW
    freed_stats = eng.kv_page_stats()
    assert freed_stats["pages_free"] > 0
    second = GenRequest(request_id="fresh", tokens=[4, 4, 2, 6, 1, 3],
                        max_new_tokens=9)
    eng.admit(second)
    done = {}
    while eng.active_requests:
        for r in eng.step():
            done[r.request_id] = r
    assert done["fresh"].generated == _baseline(
        model, variables, [4, 4, 2, 6, 1, 3], 9)
    # the survivor of the eviction is also unperturbed
    assert done["a1"].generated == _baseline(
        model, variables, [8, 2, 7, 1, 8], 10)


def test_paged_prefix_entry_eviction_mid_flight_is_invisible(tiny_model):
    """Evicting a prefix-cache entry while a lane decodes from its spliced
    pages must not perturb the lane: lane refs keep shared pages alive."""
    model, variables = tiny_model
    eng = _paged_engine(model, variables, slots=2, pool_pages=20,
                        prefix_cache_bytes=1 << 20)
    shared = [3, 1, 4, 1, 5, 9, 2, 6]
    eng.run([GenRequest(request_id="seed", tokens=shared + [1],
                        max_new_tokens=2)])
    hit = GenRequest(request_id="hit", tokens=shared + [2], max_new_tokens=10)
    eng.admit(hit)
    assert eng.prefix_hits_total >= 1
    # drop EVERY cache entry while the lane is mid-flight
    while eng._prefix_cache.evict_oldest():
        pass
    assert len(eng._prefix_cache) == 0
    done = {}
    while eng.active_requests:
        for r in eng.step():
            done[r.request_id] = r
    assert done["hit"].generated == _baseline(
        model, variables, shared + [2], 10)


def test_paged_prefix_cache_charges_physical_bytes_shared_once(tiny_model):
    """Byte accounting is physical: two entries sharing prefix pages charge
    the shared pages once, and eviction only credits pages dropping their
    last cache reference."""
    model, variables = tiny_model
    eng = _paged_engine(model, variables, slots=2, page_tokens=8,
                        prompt_buckets=(8, 32), pool_pages=24,
                        prefix_cache_bytes=1 << 24)
    cache = eng._prefix_cache
    page_bytes = eng.kv_page_stats()["page_bytes"]
    shared = list(range(1, 17))                   # exactly 2 pages
    eng.run([GenRequest(request_id="p1", tokens=shared + [30],
                        max_new_tokens=2)])
    bytes_one = cache.total_bytes
    assert bytes_one == 3 * page_bytes            # 17 tokens -> 3 pages
    eng.run([GenRequest(request_id="p2", tokens=shared + [31],
                        max_new_tokens=2)])
    # the second entry shares the two whole prefix pages: only its private
    # boundary page is a new physical charge
    assert cache.total_bytes == bytes_one + page_bytes
    assert eng.kv_page_stats()["pages_shared"] >= 2
    # evicting the first entry credits ONLY its exclusively-held page
    cache.evict_oldest()
    assert cache.total_bytes == bytes_one


def test_paged_compile_budget_single_fill_program(tiny_model):
    """Paged mode serves fresh prompts and suffix continuations with ONE
    prefill program per bucket: budget = len(buckets) + 1 even with the
    prefix cache on (the unpaged engine needs 2 per bucket)."""
    model, variables = tiny_model
    eng = _paged_engine(model, variables, slots=2, pool_pages=20,
                        prefix_cache_bytes=1 << 20)
    assert eng.guard.budget == 3
    shared = [3, 1, 4, 1, 5, 9, 2, 6]
    prompts = [[5, 9, 2, 7], shared + [1], shared + [2],
               [11, 4, 9, 1, 2, 3, 4, 5, 6, 0, 2, 1]]
    eng.run([
        GenRequest(request_id=f"c{i}", tokens=p, max_new_tokens=4)
        for i, p in enumerate(prompts)
    ])
    assert eng.prefix_hits_total >= 1     # the hit path ran
    assert eng.compilations <= 3


# ---------------------------------------------------------------------------
# Pool exhaustion: backpressure, never OOM
# ---------------------------------------------------------------------------


def test_paged_admission_backpressure_and_recovery(tiny_model):
    """A pool sized for ~one full request at a time: can_admit gates the
    second admission until the first frees its pages; everything still
    completes bit-identically (run() waits instead of failing)."""
    model, variables = tiny_model
    # pages_per_lane = 5; pool holds 6 usable pages: two 3-page requests
    # cannot both reserve (3+3 > 6 - only with both lanes' worst case 4..)
    eng = _paged_engine(model, variables, slots=4, pool_pages=7)
    big = GenRequest(request_id="big", tokens=list(range(1, 13)),
                     max_new_tokens=24)        # span 35 -> 5 pages
    eng.admit(big)
    small = GenRequest(request_id="small", tokens=[5, 2], max_new_tokens=8)
    assert eng.free_slots > 0
    assert not eng.can_admit(small)            # 2 pages > 1 page of slack
    with pytest.raises(PoolExhausted):
        eng.admit(small)
    # requests drain -> pages free -> the small request admits and matches
    done = {}
    while eng.active_requests:
        for r in eng.step():
            done[r.request_id] = r
    assert eng.can_admit(small)
    res = eng.run([small])
    assert res["small"].generated == _baseline(model, variables, [5, 2], 8)


def test_paged_lanes_per_byte_at_a_fixed_budget(tiny_model):
    """The capacity argument for paging: with exactly the KV bytes a
    2-lane unpaged cache reserves, the paged engine runs at least twice as
    many short requests at once — they stop paying full-length
    reservations."""
    model, variables = tiny_model
    unpaged_lanes = 2
    probe = _paged_engine(model, variables)
    pages_per_lane = -(-probe.config.cache_len // probe.config.page_tokens)
    budget_pages = unpaged_lanes * pages_per_lane
    eng = _paged_engine(model, variables, slots=4 * unpaged_lanes,
                        pool_pages=budget_pages + 1)   # + the scratch page
    prompts = [[3 + i, 5, 8 + i][: 2 + i % 2] for i in range(4 * unpaged_lanes)]
    pending = [GenRequest(request_id=f"s{i}", tokens=p, max_new_tokens=4)
               for i, p in enumerate(prompts)]
    done, max_active = {}, 0
    while pending or eng.active_requests:
        while pending and eng.free_slots and eng.can_admit(pending[0]):
            eng.admit(pending.pop(0))
        max_active = max(max_active, eng.active_requests)
        for r in eng.step():
            done[r.request_id] = r
    assert max_active >= 2 * unpaged_lanes, max_active
    for i, p in enumerate(prompts):
        assert done[f"s{i}"].generated == _baseline(model, variables, p, 4)


def test_paged_pool_too_small_refused(tiny_model):
    model, variables = tiny_model
    with pytest.raises(ValueError, match="pool too small"):
        _paged_engine(model, variables, slots=2, page_tokens=8, pool_pages=4)


def test_pool_exhaustion_backpressures_through_batcher(tiny_model):
    """End of the backpressure chain: pool pressure keeps requests QUEUED
    (they all complete bit-identically once pages free), and a full queue
    sheds with QueueFull carrying the derived Retry-After — the HTTP
    layer's 429 — never an OOM, never a lost request."""
    model, variables = tiny_model

    async def main():
        # 10 usable pages; each big request reserves 5 -> two decode at a
        # time, the rest wait in the queue on pool pressure alone
        eng = _paged_engine(model, variables, slots=4, pool_pages=11)
        b = Batcher(eng, max_queue=8)
        big = [
            GenRequest(request_id=f"big{i}", tokens=list(range(1, 13)),
                       max_new_tokens=24)
            for i in range(6)
        ]
        tasks = [asyncio.ensure_future(b.submit(r, timeout_s=120))
                 for r in big]
        # pool fits 2 reservations (2 x 5 of 10 pages): the other 4 requests
        # sit QUEUED on pool pressure while slots stay free
        depth = 0
        for _ in range(2000):
            await asyncio.sleep(0.002)
            depth = b.queue_depth
            if depth >= 4:
                break
        assert depth >= 4, "pool pressure never queued the overflow"
        assert eng.free_slots >= 2  # lanes were NOT the bottleneck
        # cap the queue at its current depth: the next submit is the 429
        b.max_queue = depth
        with pytest.raises(QueueFull) as exc:
            await b.submit(GenRequest(
                request_id="shed", tokens=[1, 2], max_new_tokens=4,
            ), timeout_s=30)
        shed = exc.value
        assert shed.retry_after_s is None or shed.retry_after_s >= 1.0
        b.max_queue = 8
        results = await asyncio.gather(*tasks)
        want = _baseline(model, variables, list(range(1, 13)), 24)
        for r in results:
            assert r.generated == want
        await b.close()

    run_async(main())


# ---------------------------------------------------------------------------
# Host KV tier (docs/serving.md §KV tiering)
# ---------------------------------------------------------------------------


def test_host_pool_slot_lifecycle_and_bytes_roundtrip():
    host = HostPagePool(budget_bytes=100, page_bytes=25)
    assert host.capacity == 4 and host.free_count == 4
    slots = host.alloc(3)
    assert host.used_count == 3 and host.can_hold(1) and not host.can_hold(2)
    with pytest.raises(PoolExhausted):
        host.alloc(2)
    page = [np.arange(6, dtype=np.float32).reshape(2, 3),
            np.full((2, 3), 7.0, np.float32)]
    host.write(slots[0], page)
    got = host.read(slots[0])
    assert all(np.array_equal(a, b) for a, b in zip(got, page))
    host.free(slots)
    assert host.free_count == 4
    s = host.stats()
    assert s["tier_host_pages_total"] == 4
    assert s["tier_host_pages_used"] == 0 and s["tier_host_bytes"] == 0


def _tiered_trio(num_pages=7, budget_pages=6, host_pages=6, page_bytes=10):
    """KVPagePool + HostPagePool + PrefixCache wired with transfer fns that
    move accounting only (no device arrays) — the allocator-level seam the
    engine's _demote_run/_restore_run drive."""
    pool = KVPagePool(num_pages=num_pages, page_tokens=4,
                      page_bytes=page_bytes)
    host = HostPagePool(budget_bytes=host_pages * page_bytes,
                        page_bytes=page_bytes)
    cache = PrefixCache(budget_pages * page_bytes, pool=pool)

    def demote(run):
        if not host.can_hold(len(run.pages)):
            return None
        return HostRun(slots=tuple(host.alloc(len(run.pages))),
                       n_tokens=run.n_tokens)

    def restore(host_run):
        n = len(host_run.slots)
        try:
            pool.reserve(n)
        except PoolExhausted:
            return None
        pages = []
        try:
            for _ in range(n):
                pages.append(pool.alloc_reserved(cache.demote_or_evict))
        except BaseException:
            pool.lane_release(pages, n - len(pages))
            raise
        return PageRun(pages=tuple(pages), n_tokens=host_run.n_tokens)

    cache.enable_tier(host, demote, restore)
    return pool, host, cache


def _admit_entry(pool, cache, key, n_pages):
    """Admission-style insert: reserve, materialize, insert, lane done."""
    pool.reserve(n_pages)
    run = PageRun(
        pages=tuple(pool.alloc_reserved() for _ in range(n_pages)),
        n_tokens=n_pages * pool.page_tokens,
    )
    assert cache.insert(key, run)
    pool.lane_release(run.pages)
    return run


def test_tier_slack_invariant_across_demote_restore_inflight():
    """slack = free + cache-only - reserved must hold through every tier
    transition: demotion converts cache-only pages to free (slack
    UNCHANGED — demoted KV was already evictable capacity), restore
    converts them back, and a failed restore leaks no reservation."""
    pool, host, cache = _tiered_trio()
    _admit_entry(pool, cache, (1, 2, 3), 3)
    _admit_entry(pool, cache, (9, 8, 7), 3)
    assert (pool.free_count, pool._cache_only, pool.reserved_outstanding) \
        == (0, 6, 0)
    assert pool.slack() == 6

    # demote the LRU entry: its 3 pages move cache-only -> free
    assert cache.demote_or_evict()
    assert cache.stats()["entries_host"] == 1
    assert (pool.free_count, pool._cache_only, pool.reserved_outstanding) \
        == (3, 3, 0)
    assert pool.slack() == 6          # unchanged: evictable either way
    assert host.demotions_total == 3 and host.used_count == 3
    assert cache.total_bytes == 3 * pool.page_bytes  # host entry credited

    # a lane occupies the freed pages: restore must evict/demote to fit
    pool.reserve(3)
    lane = [pool.alloc_reserved() for _ in range(3)]
    assert pool.slack() == 3

    # restore-on-touch: entry A pages back in; the device budget then
    # forces entry B out (demoted, not evicted), via the nested
    # demote_or_evict hook — with A pinned "in-flight" throughout
    match, got = cache.lookup((1, 2, 3))
    assert match == 3 and isinstance(got, PageRun)
    assert host.restores_total == 3
    assert cache._lru[("", (1, 2, 3))].tier == "device"
    assert cache._lru[("", (9, 8, 7))].tier == "host"
    assert (pool.free_count, pool._cache_only, pool.reserved_outstanding) \
        == (0, 3, 0)
    assert pool.slack() == 3

    # failed restore is a miss and leaks nothing: consume the whole slack,
    # then touch the host entry
    pool.reserve(pool.slack())
    before = pool.reserved_outstanding
    match, got = cache.lookup((9, 8, 7))
    assert (match, got) == (0, None)
    assert cache._lru[("", (9, 8, 7))].tier == "host"
    assert pool.reserved_outstanding == before
    pool.unreserve(before - 3)
    pool.lane_release(lane, 3)


def test_tier_inflight_entry_pinned_against_eviction():
    pool, host, cache = _tiered_trio()
    _admit_entry(pool, cache, (1, 2, 3), 2)
    entry = cache._lru[("", (1, 2, 3))]
    entry.tier = "in-flight"
    assert not cache.evict_oldest()       # the only entry is pinned
    assert not cache._shed_one()          # and not demotable either
    entry.tier = "device"
    assert cache.evict_oldest()


def test_tier_demote_falls_back_to_eviction_when_host_full():
    pool, host, cache = _tiered_trio(host_pages=2)
    _admit_entry(pool, cache, (1, 2, 3), 3)   # 3 pages > host capacity 2
    assert cache.demote_or_evict()
    assert len(cache) == 0                    # evicted, not demoted
    assert host.demotions_total == 0 and cache.evictions_total == 1
    assert pool.free_count == 6


def test_tier_evicting_host_entry_frees_slots_not_device_pages():
    pool, host, cache = _tiered_trio()
    _admit_entry(pool, cache, (1, 2, 3), 3)
    assert cache.demote_or_evict()            # -> host
    free_before = pool.free_count
    assert cache.evict_oldest()               # drop the host entry
    assert host.used_count == 0
    assert pool.free_count == free_before     # no device pages involved
    assert cache.total_bytes == 0


def _tiered_engine(model, variables, device_budget_pages, **kw):
    """Paged engine with the host tier armed and a device prefix budget of
    exactly ``device_budget_pages`` pages."""
    probe = _paged_engine(model, variables, prefix_cache_bytes=1 << 20)
    page_bytes = probe.kv_page_stats()["page_bytes"]
    defaults = dict(
        slots=2, pool_pages=24,
        prefix_cache_bytes=device_budget_pages * page_bytes,
        host_pool_bytes=1 << 16,
    )
    defaults.update(kw)
    return _paged_engine(model, variables, **defaults)


def test_tier_capacity_beyond_device_budget(tiny_model):
    """The headline: a device prefix budget of ONE entry serves a working
    set of three distinct prefixes from the cache — entries past the
    budget demote to host instead of evicting, and the second round of
    touches hits via restore-on-touch, every output bit-identical."""
    model, variables = tiny_model
    eng = _tiered_engine(model, variables, device_budget_pages=2)
    prefixes = [list(range(1, 13)), list(range(40, 52)),
                list(range(70, 82))]
    for rnd, tail in enumerate((30, 33)):
        for j, shared in enumerate(prefixes):
            prompt = shared + [tail]
            rid = f"t{rnd}_{j}"
            res = eng.run([GenRequest(request_id=rid, tokens=prompt,
                                      max_new_tokens=4)])
            want = _baseline(model, variables, prompt, 4)
            assert res[rid].generated == want, f"{rid} diverged"
    hp = eng._host_pool
    assert hp.demotions_total > 0 and hp.restores_total > 0
    # every second-round touch was a prefix hit — the device budget alone
    # (1 entry) could have served at most one of the three
    assert eng.prefix_hits_total >= 3
    assert eng._prefix_cache.stats()["entries_host"] >= 1
    st = eng.kv_page_stats()
    for key in ("tier_host_pages_total", "tier_host_pages_used",
                "tier_host_bytes", "demotions_total", "restores_total"):
        assert key in st, key
    assert st["tier_host_pages_used"] == hp.used_count


def test_tier_admits_more_lanes_at_a_fixed_pool(tiny_model, monkeypatch):
    """Three shared prefixes, four lanes each, admitted until the pool is
    exhausted, with a device prefix budget of half an entry.  Without the
    host tier the cache refuses every insert and each lane reserves its
    full span; with it the entries live on the host, a group's first lane
    restores the prefix and its followers share those pages — at least 1.5x
    the lanes from the same pool, the same tokens, and no transfer inside
    the guarded decode window."""
    monkeypatch.setenv("FTC_TRANSFER_GUARD", "raise")
    model, variables = tiny_model
    buckets, page_tokens, new_tokens = (8, 32), 8, 8
    prefixes = [[(11 * j + 5 * i) % 190 + 1 for i in range(max(buckets) - 1)]
                for j in range(3)]
    lane_pages = -(-(max(buckets) + new_tokens - 1) // page_tokens)

    def reqs(tag, tails):
        return [GenRequest(request_id=f"{tag}-p{j}t{t}", tokens=pre + [t],
                           max_new_tokens=new_tokens)
                for j, pre in enumerate(prefixes) for t in tails]

    lanes, outs = {}, {}
    for which, host_bytes in (("tiered", 1 << 16), ("untiered", 0)):
        eng = _tiered_engine(model, variables, device_budget_pages=1,
                             slots=12, pool_pages=8 * lane_pages,
                             prompt_buckets=buckets, host_pool_bytes=host_bytes)
        eng.run(reqs("seed", [200]))   # entries born on the host, or refused
        admitted = 0
        for req in reqs("wave", [201, 202, 203, 204]):
            try:
                eng.admit(req)
            except PoolExhausted:
                break
            admitted += 1
        results = {}
        while eng.active_requests:
            for r in eng.step():
                results[r.request_id] = r
        lanes[which], outs[which] = admitted, results
        assert eng._transfer_guard.trips == 0
    assert lanes["tiered"] >= 1.5 * lanes["untiered"], lanes
    shared = set(outs["tiered"]) & set(outs["untiered"])
    assert shared
    for rid in shared:
        assert outs["tiered"][rid].generated == outs["untiered"][rid].generated


def test_tier_mid_flight_demotion_is_invisible(tiny_model):
    """Demoting a prefix entry while a lane decodes from its spliced pages
    must not perturb the lane (lane refs pin shared pages; the snapshot
    only reads), and the demoted entry still restores and serves later
    hits bit-identically."""
    model, variables = tiny_model
    eng = _tiered_engine(model, variables, device_budget_pages=16)
    shared = list(range(1, 13))
    eng.run([GenRequest(request_id="seed", tokens=shared + [1],
                        max_new_tokens=2)])
    hit = GenRequest(request_id="hit", tokens=shared + [2],
                     max_new_tokens=10)
    eng.admit(hit)
    assert eng.prefix_hits_total >= 1
    # demote EVERY entry to host while the lane is mid-flight
    while eng._prefix_cache.stats()["entries_host"] < len(eng._prefix_cache):
        assert eng._prefix_cache.demote_or_evict()
    assert eng._host_pool.demotions_total > 0
    done = {}
    while eng.active_requests:
        for r in eng.step():
            done[r.request_id] = r
    assert done["hit"].generated == _baseline(
        model, variables, shared + [2], 10)
    # the host-resident entry restores on the next touch and still hits
    res = eng.run([GenRequest(request_id="hit2", tokens=shared + [3],
                              max_new_tokens=6)])
    assert eng._host_pool.restores_total > 0
    assert res["hit2"].generated == _baseline(
        model, variables, shared + [3], 6)


def test_tier_oversized_entry_born_demoted():
    """An entry bigger than the whole DEVICE budget is not refused when the
    tier is armed: it inserts straight to host (zero device charge) and
    restores on touch — long-context KV stops competing for device pages."""
    pool, host, cache = _tiered_trio(budget_pages=2)   # budget < 3 pages
    pool.reserve(3)
    run = PageRun(pages=tuple(pool.alloc_reserved() for _ in range(3)),
                  n_tokens=12)
    assert cache.insert((1, 2, 3), run)                # would be refused
    entry = cache._lru[("", (1, 2, 3))]                # without the tier
    assert entry.tier == "host" and cache.total_bytes == 0
    assert host.demotions_total == 3
    pool.lane_release(run.pages)                       # writer lane drains
    assert pool.free_count == 6                        # no device residue
    # touch: restores (transient overshoot of the device budget), and the
    # next shed re-demotes it as the LRU victim
    match, got = cache.lookup((1, 2, 3))
    assert match == 3 and isinstance(got, PageRun)
    assert cache.total_bytes == 3 * pool.page_bytes    # over budget, pinned
    pool.reserve(2)
    run2 = PageRun(pages=tuple(pool.alloc_reserved() for _ in range(2)),
                   n_tokens=8)
    assert cache.insert((7, 7), run2)
    pool.lane_release(run2.pages)
    assert cache._lru[("", (1, 2, 3))].tier == "host"  # re-demoted
    assert cache.total_bytes == 2 * pool.page_bytes


def test_tier_oversized_entry_refused_when_host_full():
    pool, host, cache = _tiered_trio(budget_pages=2, host_pages=2)
    pool.reserve(3)
    run = PageRun(pages=tuple(pool.alloc_reserved() for _ in range(3)),
                  n_tokens=12)
    assert not cache.insert((1, 2, 3), run)            # host can't hold it
    assert len(cache) == 0
    pool.lane_release(run.pages)
