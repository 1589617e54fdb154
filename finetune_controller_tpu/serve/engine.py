"""Slot-based continuous-batching decode engine.

The serving core: a fixed batch of ``slots`` decode lanes runs ONE jitted
single-token step, and requests are admitted into free lanes between steps —
a new request joins mid-flight instead of waiting for the batch to drain
(the VirtualFlow idea: request slots decoupled from physical batch shape, so
traffic shape never changes the compiled program).

Two memory regimes for the KV cache (docs/serving.md §Paged KV):

* **unpaged** (the PR-4/6 layout): every lane owns a contiguous
  ``cache_len`` stripe of the batch cache, reserved at admit time whatever
  the request's actual length;
* **paged** (``EngineConfig.page_tokens > 0``): the cache is a shared pool
  of fixed-size pages (``serve/kv_pages.py``) addressed through per-lane
  page tables that ride into every jitted call — a lane materializes pages
  as its tokens actually arrive (prompt pages at admit, one page per
  ``page_tokens`` decode steps after), eviction frees them immediately, and
  the prefix cache stores page RUNS shared copy-on-write instead of
  full-shape snapshots.  Admission reserves a request's worst-case page
  count up front, so growth can never OOM mid-flight: a pool too full to
  host a request is backpressure (:class:`~finetune_controller_tpu.serve.
  kv_pages.PoolExhausted` → the batcher keeps it queued → a full queue is a
  429 with ``Retry-After``), never a crash.

Multi-tenant unmerged-LoRA multiplexing (docs/serving.md §Multi-tenant
adapters, ``EngineConfig.tenant_slots > 0``): the model's ``"tenants"``
collection stacks per-tenant adapters and each lane's adapter is selected by
the per-row ``adapter_ids`` vector the engine passes alongside the batch —
N fine-tuned tenants share one base-model engine, and the prefix cache keys
namespaces by adapter id so one tenant's KV never splices into another's.

Compile-count contract (armed with ``analysis.recompile_guard``):

* unpaged: prefill compiles once per **prompt bucket** (+ once more per
  bucket for the prefix-reuse suffix prefill when the cache is on); the
  decode step compiles **once** at ``(slots, 1)``;
* paged: ONE prefill program serves fresh prompts and suffix continuations
  alike (the page table makes them the same shape), so the budget is
  ``len(prompt_buckets) + 1`` with or without the prefix cache.

Two host↔device traffic rules keep the hot path hot (docs/performance.md):
prefix reuse (``serve/prefix_cache.py``) and on-device token selection (the
decode step returns a ``(slots,)`` int32 token vector, never the logits).

Correctness anchor (proved in ``tests/test_serve.py`` /
``tests/test_kv_pages.py``): greedy output for any request is bit-identical
to single-request :func:`~finetune_controller_tpu.models.generate.
cached_generate`, no matter what else shares the batch, whether the cache is
paged or not.  Per-row ops are independent of other rows; masked cache slots
(including anything gathered through an unmaterialized page-table entry's
scratch page) contribute exactly 0.0 to the softmax; and the per-row cache
index lets each lane write and attend at its own position.

MoE configs are refused: expert-capacity routing couples rows through the
shared capacity budget, so batching invariance cannot hold there.
Multimodal configs are refused until the image prefix learns per-slot fill.
"""

from __future__ import annotations

import dataclasses
import itertools
import logging
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.recompile_guard import RecompileGuard
from ..models.generate import _sample
from .adapters import (
    AdapterRegistry,
    UnknownAdapter,
    _leaf_name,
    install_into,
)
from .kv_pages import HostPagePool, HostRun, KVPagePool, PageRun, PoolExhausted
from .prefix_cache import PrefixCache, resolve_reuse_length

logger = logging.getLogger(__name__)


class PromptTooLong(ValueError):
    """Prompt exceeds the largest configured prefill bucket."""


class EngineBusy(RuntimeError):
    """No free slot (the batcher queues instead of surfacing this)."""


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Shape of the serving batch — these knobs bound the compile count."""

    #: fixed decode lanes (the physical batch); the compiled decode step
    #: always runs all of them, occupied or not
    slots: int = 8
    #: prefill pad targets, ascending; one prefill compile per bucket used
    prompt_buckets: tuple[int, ...] = (32, 128, 512)
    #: per-request cap on generated tokens; also sizes the KV cache
    max_new_tokens: int = 128
    #: byte budget for the prefix-reuse KV cache (0 = disabled): admissions
    #: whose prompt shares a cached prefix prefill only the suffix
    #: (``serve/prefix_cache.py``; ``serve_prefix_cache_mb`` in Settings)
    prefix_cache_bytes: int = 0
    #: compile budget: defaults to len(prompt_buckets) + 1 (the decode step),
    #: or 2*len(prompt_buckets) + 1 with the prefix cache on AND paging off
    #: (fill AND fill_from per bucket); the guard RAISES past it — an
    #: unexpected compile on the serve path is a latency bug, not a warning
    recompile_budget: int = 0
    #: paged KV (docs/serving.md §Paged KV): sequence positions per page;
    #: 0 keeps the unpaged contiguous-lane layout
    page_tokens: int = 0
    #: total pool pages including the scratch page; 0 = auto-size to the
    #: unpaged capacity (``slots * pages_per_lane + 1``) — set it lower to
    #: actually oversubscribe memory, which is the point
    pool_pages: int = 0
    #: multi-tenant adapter stack slots INCLUDING base slot 0; 0 = off
    tenant_slots: int = 0
    #: stacked adapter rank ceiling (tenants pad up to it, bit-neutrally)
    tenant_rank: int = 0
    #: host-RAM KV tier byte budget (docs/serving.md §KV tiering;
    #: ``serve_kv_host_pool_mb`` in Settings): 0 = off.  Paged + prefix
    #: cache only — past the DEVICE prefix budget, LRU entries demote to
    #: pinned host pages and restore on touch, so idle-session and
    #: long-context KV stops competing with hot decode for device pages
    host_pool_bytes: int = 0

    @property
    def cache_len(self) -> int:
        return max(self.prompt_buckets) + self.max_new_tokens

    @property
    def paged(self) -> bool:
        return self.page_tokens > 0

    @property
    def pages_per_lane(self) -> int:
        """Page-table width: pages covering one full-length lane."""
        if not self.page_tokens:
            return 0
        return -(-self.cache_len // self.page_tokens)

    @property
    def effective_pool_pages(self) -> int:
        if not self.paged:
            return 0
        return self.pool_pages or (self.slots * self.pages_per_lane + 1)

    def bucket_for(self, prompt_len: int) -> int:
        for b in self.prompt_buckets:
            if prompt_len <= b:
                return b
        raise PromptTooLong(
            f"prompt length {prompt_len} exceeds the largest prefill bucket "
            f"{max(self.prompt_buckets)}"
        )


@dataclasses.dataclass
class GenRequest:
    request_id: str
    tokens: list[int]                  # prompt token ids
    max_new_tokens: int = 32
    temperature: float = 0.0           # 0 = greedy (the bit-reproducible path)
    top_k: int = 0
    eos_id: int | None = None
    seed: int = 0                      # sampling stream (temperature > 0)
    #: multi-tenant serving: which loaded adapter decodes this request
    #: ("" = the base model, stack slot 0)
    adapter_id: str = ""


@dataclasses.dataclass
class GenResult:
    request_id: str
    prompt_tokens: list[int]
    generated: list[int]               # includes the eos token when hit
    finish_reason: str                 # "length" | "eos" | "evicted"
    steps: int                         # decode steps this request rode
    admitted_at: float = 0.0
    finished_at: float = 0.0
    #: which fleet replica decoded this request (router-annotated; "" when
    #: the engine is driven directly) — the router → replica trace hop
    replica_id: str = ""


@dataclasses.dataclass
class _Slot:
    lane: int = 0                      # this slot's row in the batch cache
    req: GenRequest | None = None
    next_pos: int = 0                  # sequence position of the token to feed
    last_token: int = 0                # token to feed at next_pos
    generated: list[int] = dataclasses.field(default_factory=list)
    rng: Any = None                    # per-request sampling stream
    admitted_at: float = 0.0
    # paged-mode bookkeeping (``serve/kv_pages.py``)
    pages: list[int] = dataclasses.field(default_factory=list)
    reserved: int = 0                  # booked-but-unmaterialized pages
    adapter_id: str = ""               # tenant serving this lane

    @property
    def active(self) -> bool:
        return self.req is not None


def _batch_axis(big_shape: tuple, small_shape: tuple) -> int:
    """The axis where a B=1 prefill cache leaf maps into the slots-wide batch
    cache leaf (scanned models carry a leading layer axis, so it is not a
    fixed position)."""
    for ax, (b, s) in enumerate(zip(big_shape, small_shape)):
        if s == 1 and b > 1:
            return ax
    return 0  # shapes identical (slots == 1): write-in-place anywhere


class BatchEngine:
    """Continuous-batching decode over shared serving weights.

    Host-driven: :meth:`admit` fills a free lane, :meth:`step` advances every
    active lane one token and returns whatever finished.  The asyncio layer
    (``serve/batcher.py``) owns queuing/deadlines; this class owns device
    state and numerics.
    """

    def __init__(
        self,
        model: Any,
        variables: dict,
        config: EngineConfig | None = None,
        adapters: AdapterRegistry | None = None,
    ):
        cfg = model.cfg
        if getattr(cfg, "n_experts", 0):
            raise ValueError(
                "BatchEngine does not serve MoE configs: expert-capacity "
                "routing couples batch rows, breaking batching invariance"
            )
        if getattr(cfg, "vision", None) is not None:
            raise ValueError("BatchEngine serves text-only models (no pixels)")
        self.config = config or EngineConfig()
        self.variables = variables
        # --- multi-tenant adapters -----------------------------------------
        if adapters is None and self.config.tenant_slots > 0:
            adapters = AdapterRegistry(
                self.config.tenant_slots, max(1, self.config.tenant_rank)
            )
        self.adapters = adapters
        tenant_slots = adapters.capacity if adapters is not None else 0
        tenant_rank = adapters.max_rank if adapters is not None else 0
        # --- paged KV pool --------------------------------------------------
        self._pool: KVPagePool | None = None
        pool_pages = self.config.effective_pool_pages
        if self.config.paged:
            if pool_pages - 1 < self.config.pages_per_lane:
                raise ValueError(
                    f"kv page pool too small: {pool_pages} pages cannot hold "
                    f"one full lane ({self.config.pages_per_lane} pages of "
                    f"{self.config.page_tokens} tokens)"
                )
        self._dcfg = cfg.replace(
            remat=False, attention_impl="xla",
            max_seq_len=self.config.cache_len,
            kv_page_tokens=self.config.page_tokens,
            kv_pool_pages=pool_pages,
            lora_tenant_slots=tenant_slots,
            lora_tenant_rank=tenant_rank,
        )
        self._dmodel = type(model)(cfg=self._dcfg)
        per_bucket = 1
        if self.config.prefix_cache_bytes > 0 and not self.config.paged:
            per_bucket = 2  # fill + fill_from; paged mode has ONE fill
        budget = self.config.recompile_budget or (
            per_bucket * len(self.config.prompt_buckets) + 1
        )
        self.guard = RecompileGuard(budget, on_excess="raise",
                                    name="serve-engine")
        self._slots = [_Slot(lane=i) for i in range(self.config.slots)]
        self._tenants: Any = {}
        self._cache = self._init_cache()
        if self.config.paged:
            page_bytes = sum(
                leaf.nbytes // pool_pages
                for path, leaf in
                jax.tree_util.tree_leaves_with_path(self._cache)
                if _leaf_name(path) in ("k", "v")
            )
            self._pool = KVPagePool(
                pool_pages, self.config.page_tokens, page_bytes
            )
        self._prefix_cache = (
            PrefixCache(self.config.prefix_cache_bytes, pool=self._pool)
            if self.config.prefix_cache_bytes > 0 else None
        )
        # host-RAM KV tier (docs/serving.md §KV tiering): meaningful only
        # with BOTH paging (the page is the transfer unit) and the prefix
        # cache (entries are the demotable population)
        self._host_pool: HostPagePool | None = None
        if (self.config.host_pool_bytes > 0 and self._pool is not None
                and self._prefix_cache is not None):
            self._host_pool = HostPagePool(
                self.config.host_pool_bytes, self._pool.page_bytes
            )
            self._prefix_cache.enable_tier(
                self._host_pool, self._demote_run, self._restore_run
            )
        # host masters for the per-call arguments: lane page tables (paged)
        # and per-lane adapter slots (tenants) — tiny int32 arrays shipped
        # into every jitted call, so admission/eviction never touches device
        # state beyond the index park
        self._tables = np.zeros(
            (self.config.slots, max(1, self.config.pages_per_lane)), np.int32
        )
        self._adapter_slots = np.zeros((self.config.slots,), np.int32)
        # per-lane sampling streams, mirrored to the decode step as a
        # (slots, 2) uint32 leaf — rows for greedy lanes are inert
        self._rng_keys = np.zeros((self.config.slots, 2), np.uint32)
        (self._fill, self._fill_from, self._fill_paged, self._decode,
         self._insert, self._set_lane_index, self._copy_page,
         self._read_page, self._write_page) = self._build_fns()
        if self.adapters is not None:
            self.sync_adapters()
        # counters the /metrics gauges read
        self.steps_total = 0
        self.tokens_generated_total = 0
        self.requests_finished_total = 0
        self.prefix_hits_total = 0
        self.prefix_misses_total = 0
        self.prefill_tokens_saved_total = 0
        #: per-tenant token counters ("" = base model)
        self.tokens_by_tenant: dict[str, int] = {}
        self._prefix_warned = False
        # runtime transfer guard on the decode hot window
        # (FTC_TRANSFER_GUARD=raise|warn; armed in tests/test_transfer_guard.py):
        # every per-step host->device argument is device_put EXPLICITLY
        # before the guarded dispatch, so a steady-state decode step that
        # moves anything else across the boundary aborts loudly
        from ..analysis.transfer_guard import TransferGuard

        self._transfer_guard = TransferGuard.from_env(name="serve-decode")
        #: seconds :func:`warm_engine` spent compiling (and once running)
        #: every program this engine uses; None until it has run
        self.warm_start_s: float | None = None
        self._runtime = self._runtime_static()

    def runtime_info(self) -> dict[str, Any]:
        """Where this engine runs, for the stats every transport already
        ships (``Batcher.stats`` → probe → ``GET /admin/serve``): the device
        as JAX reports it, the warm-start seconds, and which paged-attention
        implementation each compiled program resolves to.  A control plane
        that stays off JAX learns the device from this."""
        return {**self._runtime, "warm_start_s": self.warm_start_s}

    def _runtime_static(self) -> dict[str, Any]:
        from ..platform import device_report

        info: dict[str, Any] = device_report()
        if self.paged:
            from ..ops.attention import paged_attention_impl

            cfg, c = self._dcfg, self.config
            pool = jax.ShapeDtypeStruct(
                (c.effective_pool_pages, c.page_tokens, cfg.n_kv_heads,
                 cfg.head_dim), cfg.dtype)

            def impl(b: int, s: int) -> str:
                return paged_attention_impl(
                    jax.ShapeDtypeStruct(
                        (b, s, cfg.n_heads, cfg.head_dim), cfg.dtype),
                    pool, pool,
                    jax.ShapeDtypeStruct((b, c.pages_per_lane), jnp.int32),
                )

            info["paged_attention"] = {
                "decode": impl(c.slots, 1),
                **{f"prefill_{s}": impl(1, s) for s in c.prompt_buckets},
            }
        return info

    # ---- mode helpers -----------------------------------------------------

    @property
    def paged(self) -> bool:
        return self._pool is not None

    @property
    def tenant_mode(self) -> bool:
        return self.adapters is not None

    def _tenants_arg(self):
        return self._tenants

    def _page_table_arg(self):
        return jnp.asarray(self._tables) if self.paged else None

    def _adapter_ids_arg(self):
        return (jnp.asarray(self._adapter_slots)
                if self.tenant_mode else None)

    # ---- adapters ---------------------------------------------------------

    def install_adapter(self, adapter_id: str) -> None:
        """Write one registered tenant's (rank-padded) stacks into this
        engine's device tenants tree — an atomic reference swap, safe to run
        while a decode step is in flight on the previous tree."""
        entry = self.adapters.get(adapter_id)
        if entry is None:
            raise UnknownAdapter(f"adapter {adapter_id!r} is not registered")
        self._tenants = install_into(
            self._tenants, entry.slot, entry.tree, entry.alpha, entry.rank
        )

    def remove_adapter(self, adapter_id: str, slot: int) -> None:
        """Zero a departed tenant's slot and drop its prefix-cache namespace
        (the slot id may be reused by a different tenant)."""
        self._tenants = install_into(self._tenants, slot, None, 0.0, 1)
        self.drop_prefix_namespace(adapter_id)

    def drop_prefix_namespace(self, adapter_id: str) -> None:
        """Evict every prefix-cache entry computed under ``adapter_id`` —
        required whenever the tenant's WEIGHTS change (unload, and the
        in-place refresh of a tenant rollover): KV produced by the old
        deltas must never splice into lanes decoding with the new ones."""
        if self._prefix_cache is not None:
            self._prefix_cache.drop_namespace(adapter_id)

    def sync_adapters(self) -> None:
        """Install every registered tenant — fresh replicas and rollover
        generations call this before taking traffic."""
        for entry in self.adapters.entries():
            self.install_adapter(entry.adapter_id)

    def active_by_tenant(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for slot in self._slots:
            if slot.active:
                out[slot.adapter_id] = out.get(slot.adapter_id, 0) + 1
        return out

    # ---- jitted pieces ----------------------------------------------------

    def _init_cache(self):
        """Zero batch cache shaped by a throwaway (slots, 1) decode trace
        (paged mode: the page pools + per-lane index; tenant mode also
        creates the zero adapter stacks)."""
        tokens = jnp.zeros((self.config.slots, 1), jnp.int32)
        mutable = ("cache", "tenants") if self._dcfg.lora_tenant_slots \
            else ("cache",)
        kwargs: dict[str, Any] = {}
        if self.config.paged:
            kwargs["page_table"] = jnp.zeros(
                (self.config.slots, self.config.pages_per_lane), jnp.int32
            )
        if self._dcfg.lora_tenant_slots:
            kwargs["adapter_ids"] = jnp.zeros((self.config.slots,), jnp.int32)
        _, variables = self._dmodel.apply(
            self.variables, tokens,
            positions=jnp.zeros((self.config.slots, 1), jnp.int32),
            deterministic=True, decode=True, mutable=mutable, **kwargs,
        )
        if "tenants" in variables:
            self._tenants = variables["tenants"]  # zeros: slot 0 = base
        return jax.tree.map(jnp.zeros_like, variables["cache"])

    def _build_fns(self) -> tuple[Callable, ...]:
        dmodel = self._dmodel

        def _assemble(variables, tenants, cache=None):
            out = dict(variables)
            if tenants:
                out["tenants"] = tenants
            if cache is not None:
                out["cache"] = cache
            return out

        def _index_setter(value):
            def fix(path, leaf):
                return (jnp.full_like(leaf, value)
                        if _leaf_name(path) == "index" else leaf)

            return fix

        @jax.jit
        def fill(variables, tenants, tokens, adapter_ids, last_idx, true_len):
            """Prefill one request (B=1, right-padded to a bucket): logits at
            the TRUE last prompt position + a cache whose index rows read
            ``true_len`` (the model wrote the padded length)."""
            logits, updated = dmodel.apply(
                _assemble(variables, tenants), tokens, deterministic=True,
                decode=True, mutable=("cache",), adapter_ids=adapter_ids,
            )
            cache = jax.tree_util.tree_map_with_path(
                _index_setter(true_len), updated["cache"]
            )
            return jnp.take(logits, last_idx, axis=1).astype(jnp.float32), cache

        @jax.jit
        def fill_from(variables, tenants, cache, tokens, adapter_ids, start,
                      last_idx, true_len):
            """Suffix prefill over a B=1 prefix snapshot: the first ``start``
            cache positions are reused as-is, the (bucket-padded) suffix
            ``tokens`` runs a chunked forward at absolute positions
            ``[start, start + bucket)``.  Returns logits at the TRUE last
            prompt position + a lane-ready cache whose index rows read
            ``true_len`` — the same contract as ``fill``, which is what makes
            a prefix hit invisible to everything downstream."""
            cache = jax.tree_util.tree_map_with_path(
                _index_setter(start), cache
            )
            positions = (
                start + jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :]
            )
            logits, updated = dmodel.apply(
                _assemble(variables, tenants, cache), tokens,
                positions=positions, deterministic=True, decode=True,
                mutable=("cache",), adapter_ids=adapter_ids,
            )
            cache = jax.tree_util.tree_map_with_path(
                _index_setter(true_len), updated["cache"]
            )
            return jnp.take(logits, last_idx, axis=1).astype(jnp.float32), cache

        @jax.jit
        def fill_paged(variables, tenants, cache, tokens, page_table,
                       adapter_ids, start, last_idx):
            """Paged prefill/suffix-prefill, ONE program for both: a B=1
            forward whose writes scatter through ``page_table`` into the
            shared pools and whose attention gathers back through it
            (``models/llama.py`` paged branch).  ``start`` is 0 for a fresh
            prompt or the reuse length over spliced prefix pages; the lane's
            true index is set host-side after the call, so no index fixup
            pass is needed here."""
            positions = (
                start + jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :]
            )
            logits, updated = dmodel.apply(
                _assemble(variables, tenants, cache), tokens,
                positions=positions, deterministic=True, decode=True,
                mutable=("cache",), page_table=page_table,
                adapter_ids=adapter_ids,
            )
            return (jnp.take(logits, last_idx, axis=1).astype(jnp.float32),
                    updated["cache"])

        @jax.jit
        def decode(variables, tenants, cache, tokens, positions, temps,
                   top_ks, rngs, page_table, adapter_ids):
            """One batched decode step with ON-DEVICE token selection: returns
            ``(slots,)`` int32 next tokens + advanced per-lane PRNG keys +
            the updated cache — the per-step device→host transfer no longer
            scales with vocab size.  Greedy lanes take the in-graph argmax;
            sampled lanes walk the SAME ``_sample`` stream a single-request
            ``cached_generate(rng=PRNGKey(seed))`` walks (scale → per-lane
            top-k mask → split → categorical), so per-request sampled decodes
            stay reproducible independent of batch-mates."""
            logits, updated = dmodel.apply(
                _assemble(variables, tenants, cache), tokens,
                positions=positions, deterministic=True, decode=True,
                mutable=("cache",), page_table=page_table,
                adapter_ids=adapter_ids,
            )
            logits = logits[:, -1].astype(jnp.float32)   # (slots, V)
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            vocab = logits.shape[-1]

            def lane_sample(lane_logits, temp, top_k, key, greedy_tok):
                # mirrors models.generate._sample with traced temp/top_k;
                # the greedy fallback keeps inactive/greedy lanes inert
                scaled = lane_logits / jnp.where(temp > 0.0, temp, 1.0)
                kth = jnp.sort(scaled)[jnp.clip(vocab - top_k, 0, vocab - 1)]
                dist = jnp.where(
                    (top_k > 0) & (scaled < kth), -jnp.inf, scaled
                )
                split = jax.random.split(key)
                tok = jax.random.categorical(split[1], dist).astype(jnp.int32)
                sampled = temp > 0.0
                return (
                    jnp.where(sampled, tok, greedy_tok),
                    jnp.where(sampled, split[0], key),
                )

            tokens_out, rngs_out = jax.lax.cond(
                jnp.any(temps > 0.0),
                lambda: jax.vmap(lane_sample)(logits, temps, top_ks, rngs,
                                              greedy),
                # all-greedy traffic skips the per-lane vocab sort entirely
                lambda: (greedy, rngs),
            )
            return tokens_out, rngs_out, updated["cache"]

        @jax.jit
        def insert(cache, one, slot):
            """Write a B=1 prefill cache into batch lane ``slot``."""

            def put(big, small):
                ax = _batch_axis(big.shape, small.shape)
                starts = [jnp.asarray(0, jnp.int32)] * big.ndim
                starts[ax] = jnp.asarray(slot, jnp.int32)
                return jax.lax.dynamic_update_slice(big, small, tuple(starts))

            return jax.tree.map(put, cache, one)

        @jax.jit
        def set_lane_index(cache, slot, value):
            """Point one lane's cache-index rows at ``value``: 0 parks a
            freed lane (its throwaway decode writes stay benign and
            in-bounds — scratch page 0 in paged mode, position 0 unpaged),
            a prompt length arms a just-admitted paged lane (index leaves
            are batch-last: ``(B,)``, or ``(L, B)`` scanned)."""

            def fix(path, leaf):
                return (leaf.at[..., slot].set(value)
                        if _leaf_name(path) == "index" else leaf)

            return jax.tree_util.tree_map_with_path(fix, cache)

        @jax.jit
        def copy_page(cache, dst, src):
            """Copy-on-write: duplicate pool page ``src`` into ``dst`` across
            every layer's K and V pools (the page axis sits at ``ndim - 4``;
            scanned models carry a leading layer axis)."""

            def fix(path, leaf):
                if _leaf_name(path) not in ("k", "v"):
                    return leaf
                ax = leaf.ndim - 4
                page = jax.lax.dynamic_slice_in_dim(leaf, src, 1, axis=ax)
                return jax.lax.dynamic_update_slice_in_dim(
                    leaf, page, dst, axis=ax
                )

            return jax.tree_util.tree_map_with_path(fix, cache)

        @jax.jit
        def read_page(cache, src):
            """Slice pool page ``src`` out of every K/V leaf (KV tiering's
            demote path) — fixed shapes, so all page ids share ONE compile;
            leaf order is the tree traversal order ``write_page`` replays."""
            return [
                jax.lax.dynamic_slice_in_dim(leaf, src, 1, axis=leaf.ndim - 4)
                for path, leaf in jax.tree_util.tree_leaves_with_path(cache)
                if _leaf_name(path) in ("k", "v")
            ]

        @jax.jit
        def write_page(cache, dst, pages):
            """Write per-leaf page slices (a ``read_page`` result, possibly
            round-tripped through the host tier) into pool page ``dst``."""
            it = iter(pages)

            def fix(path, leaf):
                if _leaf_name(path) not in ("k", "v"):
                    return leaf
                return jax.lax.dynamic_update_slice_in_dim(
                    leaf, next(it), dst, axis=leaf.ndim - 4
                )

            return jax.tree_util.tree_map_with_path(fix, cache)

        # insert/set_lane_index/copy_page/read_page/write_page have exactly
        # one signature each (the cache trees are fixed-shape), so they stay
        # outside the guard: the budget counts the shapes that can vary with
        # traffic — prefill buckets and the decode step
        return (
            self.guard.wrap(fill, "fill"),
            self.guard.wrap(fill_from, "fill_from"),
            self.guard.wrap(fill_paged, "fill_paged"),
            self.guard.wrap(decode, "decode_step"),
            insert,
            set_lane_index,
            copy_page,
            read_page,
            write_page,
        )

    # ---- slot management --------------------------------------------------

    @property
    def free_slots(self) -> int:
        return sum(1 for s in self._slots if not s.active)

    @property
    def active_requests(self) -> int:
        return self.config.slots - self.free_slots

    @property
    def compilations(self) -> int:
        return self.guard.compilations

    @property
    def prefix_cache_bytes(self) -> int:
        return self._prefix_cache.total_bytes if self._prefix_cache else 0

    @property
    def prefix_cache_entries(self) -> int:
        return len(self._prefix_cache) if self._prefix_cache else 0

    def kv_page_stats(self) -> dict[str, int]:
        """Pool gauges for /metrics (empty when unpaged); with the host
        tier armed, its gauges and transfer counters ride along."""
        if self._pool is None:
            return {}
        stats = self._pool.stats()
        if self._host_pool is not None:
            stats.update(self._host_pool.stats())
        return stats

    def kv_slack_pages(self) -> int | None:
        """Pages still promisable to new admissions (None when unpaged) —
        the router's page-aware routing signal."""
        return self._pool.slack() if self._pool is not None else None

    def _request_span(self, req: GenRequest) -> int:
        """Last written sequence position + 1 for ``req``: the prompt plus
        every decode step's write (the final token is recorded, not
        written)."""
        return len(req.tokens) + max(0, req.max_new_tokens - 1)

    def admission_pages(self, req: GenRequest) -> int:
        """Worst-case pages admitting ``req`` reserves (0 when unpaged) —
        the batcher sums this over a multi-request admission batch so the
        batch as a WHOLE fits the pool, not just each request alone."""
        if self._pool is None:
            return 0
        return self._pool.pages_for(self._request_span(req))

    def can_admit(self, req: GenRequest, pending_pages: int = 0) -> bool:
        """Whether :meth:`admit` would succeed NOW — the batcher's gate, so
        pool pressure keeps requests queued instead of failing them.
        ``pending_pages`` adds pages already promised to requests picked for
        the same admission batch but not yet admitted.  Conservative in
        paged mode: ignores prefix sharing, so a True can never turn into a
        mid-admission exhaustion.  Permanently-impossible requests return
        True so ``admit`` raises their real error."""
        if self.free_slots == 0:
            return False
        if self._pool is None:
            return True
        need = self._pool.pages_for(self._request_span(req))
        if need > self._pool.usable_pages:
            return True  # impossible forever: let admit() fail it loudly
        return self._pool.can_reserve(need + pending_pages)

    def _resolve_adapter(self, req: GenRequest) -> tuple[int, str]:
        """(stack slot, prefix-cache namespace) for the request's tenant."""
        if not req.adapter_id:
            return 0, ""
        if self.adapters is None:
            raise UnknownAdapter(
                f"request {req.request_id} names adapter "
                f"{req.adapter_id!r} but this engine has no adapter "
                "registry (serve_max_adapters=0)"
            )
        return self.adapters.resolve(req.adapter_id), req.adapter_id

    def _resolve_prefix(self, tokens: list[int], plen: int, ns: str):
        """Longest reusable cached prefix for ``tokens`` under the adapter
        namespace ``ns``, at bucket granularity; returns ``(reuse_len,
        snapshot)`` or ``(0, None)``."""
        match_len, snapshot = self._prefix_cache.lookup(tokens, namespace=ns)
        if snapshot is None:
            return 0, None
        reuse = resolve_reuse_length(
            match_len, plen, self.config.prompt_buckets, self.config.cache_len
        )
        if reuse <= 0:
            return 0, None
        return reuse, snapshot

    def admit(self, req: GenRequest) -> GenResult | None:
        """Prefill ``req`` into a free lane (raises :class:`EngineBusy` when
        the batch is full, :class:`PromptTooLong` past the largest bucket,
        :class:`~finetune_controller_tpu.serve.kv_pages.PoolExhausted` when
        the paged pool cannot host it yet — use :meth:`can_admit` to gate).

        With the prefix cache on, the longest cached prefix of the prompt
        UNDER THE REQUEST'S ADAPTER is spliced in and only the
        (bucket-padded) suffix runs a forward — greedy/sampled outputs stay
        bit-identical to the cache-off path because causal KV depends only
        on the tokens before it (and on the adapter, which the namespace
        pins).

        Returns a :class:`GenResult` when the request finishes ON admission
        (its first sampled token hits eos, or ``max_new_tokens == 1``) —
        such a request never occupies a lane across a step."""
        slot_id = next(
            (i for i, s in enumerate(self._slots) if not s.active), None
        )
        if slot_id is None:
            raise EngineBusy("all decode slots are busy")
        plen = len(req.tokens)
        if plen < 1:
            raise ValueError("empty prompt")
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        cap = self.config.max_new_tokens
        if req.max_new_tokens > cap:
            raise ValueError(f"max_new_tokens {req.max_new_tokens} > engine cap {cap}")
        a_slot, ns = self._resolve_adapter(req)
        self.config.bucket_for(plen)  # PromptTooLong before any allocation
        if self.paged:
            logits = self._prefill_paged(req, slot_id, plen, a_slot, ns)
        else:
            logits = self._prefill_unpaged(req, slot_id, plen, a_slot, ns)
        self._adapter_slots[slot_id] = a_slot
        slot = self._slots[slot_id]
        slot.req = req
        slot.generated = []
        slot.next_pos = plen
        slot.adapter_id = req.adapter_id
        slot.rng = jax.random.PRNGKey(req.seed)
        slot.admitted_at = time.monotonic()
        result = self._emit(slot, logits)
        if result is None and req.temperature > 0.0:
            # hand the post-first-token stream to the device-side sampler
            self._rng_keys[slot_id] = np.asarray(slot.rng, np.uint32)
        return result

    # ---- unpaged prefill --------------------------------------------------

    def _prefill_unpaged(self, req, slot_id, plen, a_slot, ns):
        bucket = self.config.bucket_for(plen)
        ids1 = (jnp.asarray([a_slot], jnp.int32)
                if self.tenant_mode else None)
        reuse, snapshot = (
            self._resolve_prefix(req.tokens, plen, ns)
            if self._prefix_cache is not None else (0, None)
        )
        if snapshot is not None:
            suffix = req.tokens[reuse:]
            sbucket = self.config.bucket_for(len(suffix))
            padded = np.zeros((1, sbucket), np.int32)
            padded[0, :len(suffix)] = suffix
            logits, one = self._fill_from(
                self.variables, self._tenants_arg(), snapshot,
                jnp.asarray(padded), ids1,
                jnp.asarray(reuse, jnp.int32),
                jnp.asarray(len(suffix) - 1, jnp.int32),
                jnp.asarray(plen, jnp.int32),
            )
            self.prefix_hits_total += 1
            self.prefill_tokens_saved_total += reuse
        else:
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :plen] = req.tokens
            logits, one = self._fill(
                self.variables, self._tenants_arg(), jnp.asarray(padded),
                ids1, jnp.asarray(plen - 1, jnp.int32),
                jnp.asarray(plen, jnp.int32),
            )
            if self._prefix_cache is not None:
                self.prefix_misses_total += 1
        if self._prefix_cache is not None:
            # the hit path's `one` is a full-prompt cache too, so every
            # admission leaves its prompt resolvable for the next request
            if (not self._prefix_cache.insert(tuple(req.tokens), one,
                                              namespace=ns)
                    and not self._prefix_warned):
                self._prefix_warned = True
                logger.warning(
                    "prefix cache cannot hold a single KV snapshot (%d B > "
                    "budget %d B) — every admission will miss; raise "
                    "serve_prefix_cache_mb or disable the cache",
                    sum(x.nbytes for x in jax.tree.leaves(one)),
                    self._prefix_cache.budget_bytes,
                )
        self._cache = self._insert(self._cache, one, slot_id)
        return logits

    # ---- paged prefill ----------------------------------------------------

    def _evict_hook(self):
        if self._prefix_cache is None:
            return None
        if self._host_pool is not None:
            # tier armed: page pressure demotes LRU entries to host RAM
            # instead of destroying them (falls back to eviction when the
            # host tier is full)
            return self._prefix_cache.demote_or_evict
        return self._prefix_cache.evict_oldest

    # ---- host KV tier transfers (docs/serving.md §KV tiering) -------------
    #
    # Both directions run in ADMISSION paths (prefix lookup/insert, page
    # growth) — never inside the transfer-guarded decode dispatch, which is
    # what keeps the guard's "decode moves only its per-step feeds" contract
    # intact with the tier on.

    def _demote_run(self, run: PageRun) -> HostRun | None:
        """Copy every page of ``run`` into host slots (device state is NOT
        touched — the prefix cache releases the device refs after the swap).
        None when the host tier cannot hold the run."""
        hp = self._host_pool
        if hp is None or not hp.can_hold(len(run.pages)):
            return None
        slots = hp.alloc(len(run.pages))
        for slot_id, page in zip(slots, run.pages):
            slices = self._read_page(self._cache, jnp.asarray(page, jnp.int32))
            hp.write(slot_id, [np.asarray(x) for x in jax.device_get(slices)])
        return HostRun(slots=tuple(slots), n_tokens=run.n_tokens)

    def _restore_run(self, host_run: HostRun) -> PageRun | None:
        """Upload a demoted run back into freshly allocated device pages.
        Admission-style allocation — reserve first (None on exhaustion: the
        caller treats the hit as a miss), then materialize page by page,
        shedding OTHER cache entries under pressure.  The returned pages
        hold synthetic lane refs the prefix cache converts to cache refs."""
        pool = self._pool
        n = len(host_run.slots)
        try:
            pool.reserve(n)
        except PoolExhausted:
            return None
        pages: list[int] = []
        try:
            for slot_id in host_run.slots:
                phys = pool.alloc_reserved(self._evict_hook())
                pages.append(phys)
                self._cache = self._write_page(
                    self._cache, jnp.asarray(phys, jnp.int32),
                    [jnp.asarray(x) for x in self._host_pool.read(slot_id)],
                )
        except BaseException:
            pool.lane_release(pages, n - len(pages))
            raise
        return PageRun(pages=tuple(pages), n_tokens=host_run.n_tokens)

    def _b1_cache(self, start: int):
        """Per-admission B=1 view over the live cache: the shared pools ride
        along by reference, the per-lane index leaves shrink to one row
        holding the prefill's start position."""

        def fix(path, leaf):
            if _leaf_name(path) == "index":
                return jnp.full(leaf.shape[:-1] + (1,), start, jnp.int32)
            return leaf

        return jax.tree_util.tree_map_with_path(fix, self._cache)

    def _merge_pools(self, updated_cache):
        """Take the (B=1 apply's) updated pool leaves back into the batch
        cache, keeping the batch-shaped index leaves."""

        def pick(path, batch_leaf, b1_leaf):
            return b1_leaf if _leaf_name(path) in ("k", "v") else batch_leaf

        self._cache = jax.tree_util.tree_map_with_path(
            pick, self._cache, updated_cache
        )

    def _prefill_paged(self, req, slot_id, plen, a_slot, ns):
        pool, t = self._pool, self._pool.page_tokens
        need_total = pool.pages_for(self._request_span(req))
        if need_total > pool.usable_pages:
            raise ValueError(
                f"request {req.request_id} needs {need_total} kv pages but "
                f"the pool holds {pool.usable_pages} — raise "
                "serve_kv_pool_pages or shrink the request"
            )
        reuse, run = (
            self._resolve_prefix(req.tokens, plen, ns)
            if self._prefix_cache is not None else (0, None)
        )
        shared = list(run.pages[: reuse // t]) if run is not None else []
        pool.reserve(need_total - len(shared))  # PoolExhausted backpressure
        for page in shared:
            pool.lane_ref(page)
        slot = self._slots[slot_id]
        slot.pages = list(shared)
        slot.reserved = need_total - len(shared)
        row = np.zeros((self._tables.shape[1],), np.int32)
        row[: len(shared)] = shared
        try:
            # materialize the pages the prompt writes NOW; decode growth
            # spends the rest of the reservation page-by-page
            prompt_pages = pool.pages_for(plen)
            for i in range(len(shared), prompt_pages):
                phys = pool.alloc_reserved(self._evict_hook())
                row[i] = phys
                slot.pages.append(phys)
                slot.reserved -= 1
            if run is not None and reuse % t:
                # copy-on-write boundary: the page holding position `reuse`
                # keeps the entry's prefix KV but will be written by this
                # lane's suffix — it must be a private copy
                self._cache = self._copy_page(
                    self._cache,
                    jnp.asarray(int(row[reuse // t]), jnp.int32),
                    jnp.asarray(int(run.pages[reuse // t]), jnp.int32),
                )
                pool.cow_copies_total += 1
            start = reuse if run is not None else 0
            suffix = req.tokens[start:]
            sbucket = self.config.bucket_for(len(suffix))
            padded = np.zeros((1, sbucket), np.int32)
            padded[0, :len(suffix)] = suffix
            ids1 = (jnp.asarray([a_slot], jnp.int32)
                    if self.tenant_mode else None)
            logits, updated = self._fill_paged(
                self.variables, self._tenants_arg(), self._b1_cache(start),
                jnp.asarray(padded), jnp.asarray(row[None, :]), ids1,
                jnp.asarray(start, jnp.int32),
                jnp.asarray(len(suffix) - 1, jnp.int32),
            )
        except BaseException:
            # roll the lane's pool state back so a failed prefill (bad
            # request shape, injected fault) never leaks pages
            pool.lane_release(slot.pages, slot.reserved)
            slot.pages, slot.reserved = [], 0
            raise
        self._merge_pools(updated)
        self._cache = self._set_lane_index(
            self._cache, jnp.asarray(slot_id, jnp.int32),
            jnp.asarray(plen, jnp.int32),
        )
        self._tables[slot_id, :] = row
        if self._prefix_cache is not None:
            if run is not None:
                self.prefix_hits_total += 1
                self.prefill_tokens_saved_total += start
            else:
                self.prefix_misses_total += 1
            run_new = PageRun(
                pages=tuple(int(x) for x in row[:pool.pages_for(plen)]),
                n_tokens=plen,
            )
            if (not self._prefix_cache.insert(tuple(req.tokens), run_new,
                                              namespace=ns)
                    and not self._prefix_warned):
                self._prefix_warned = True
                logger.warning(
                    "prefix cache cannot hold a single page run (%d pages x "
                    "%d B > budget %d B) — every admission will miss; raise "
                    "serve_prefix_cache_mb or disable the cache",
                    len(run_new.pages), pool.page_bytes,
                    self._prefix_cache.budget_bytes,
                )
        return logits

    def evict(self, request_id: str) -> GenResult | None:
        """Drop an in-flight request (deadline blown / client gone); frees
        the lane — and, in paged mode, its pool pages — immediately and
        parks its cache index at 0 (see :meth:`_finish`): the freed lane
        still rides every step, decoding throwaway tokens at benign
        in-bounds positions that other rows never see, until re-admission
        overwrites it."""
        for slot in self._slots:
            if slot.active and slot.req.request_id == request_id:
                return self._finish(slot, "evicted")
        return None

    def _emit(self, slot: _Slot, logits) -> GenResult | None:
        """Select the FIRST token for a just-admitted lane from its prefill
        logits row (host-side — a B=1 admission transfer, not the per-step
        hot path, which selects on device)."""
        req = slot.req
        if req.temperature <= 0.0:
            tok = int(np.argmax(np.asarray(logits[0], np.float32)))
        else:
            # the same _sample stream a single-request cached_generate(B=1,
            # rng=PRNGKey(seed)) walks, so sampled decodes are reproducible
            # per request, independent of batch-mates
            nxt, slot.rng = _sample(
                logits[:1], temperature=req.temperature, top_k=req.top_k,
                rng=slot.rng,
            )
            tok = int(nxt[0])
        return self._record(slot, tok)

    def _record(self, slot: _Slot, tok: int) -> GenResult | None:
        """Host bookkeeping for one selected token: eos/length latching."""
        req = slot.req
        slot.generated.append(tok)
        slot.last_token = tok
        self.tokens_generated_total += 1
        self.tokens_by_tenant[slot.adapter_id] = (
            self.tokens_by_tenant.get(slot.adapter_id, 0) + 1
        )
        if req.eos_id is not None and tok == req.eos_id:
            return self._finish(slot, "eos")
        if len(slot.generated) >= req.max_new_tokens:
            return self._finish(slot, "length")
        return None

    def _finish(self, slot: _Slot, reason: str) -> GenResult:
        req = slot.req
        result = GenResult(
            request_id=req.request_id,
            prompt_tokens=list(req.tokens),
            generated=list(slot.generated),
            finish_reason=reason,
            steps=len(slot.generated),
            admitted_at=slot.admitted_at,
            finished_at=time.monotonic(),
        )
        slot.req = None
        slot.generated = []
        slot.rng = None
        slot.last_token = 0
        slot.next_pos = 0
        slot.adapter_id = ""
        self._adapter_slots[slot.lane] = 0
        if self.paged:
            # free the lane's pages (shared refs drop; exclusive pages still
            # referenced by prefix-cache entries stay resident for reuse)
            # and return the unspent reservation — eviction reclaims memory
            # IMMEDIATELY, the paged contract
            self._pool.lane_release(slot.pages, slot.reserved)
            slot.pages = []
            slot.reserved = 0
            self._tables[slot.lane, :] = 0
        # park the lane's device cache index at 0: a freed lane still rides
        # every decode step, and left at its stale position it would creep
        # toward (and past) the cache end — reset keeps its throwaway writes
        # benign and in-bounds (scratch page 0 in paged mode) until
        # re-admission overwrites the lane
        self._cache = self._set_lane_index(
            self._cache, jnp.asarray(slot.lane, jnp.int32),
            jnp.asarray(0, jnp.int32),
        )
        self.requests_finished_total += 1
        return result

    # ---- the decode loop --------------------------------------------------

    def _grow_pages(self) -> None:
        """Materialize the page each active lane's NEXT write lands in, when
        it has not been allocated yet — reservation-backed, so the free list
        (after evicting cache-only pages) can never come up short."""
        t = self._pool.page_tokens
        width = self._tables.shape[1]
        for slot in self._slots:
            if not slot.active:
                continue
            page_idx = slot.next_pos // t
            if page_idx < width and self._tables[slot.lane, page_idx] == 0:
                phys = self._pool.alloc_reserved(self._evict_hook())
                self._tables[slot.lane, page_idx] = phys
                slot.pages.append(phys)
                slot.reserved -= 1

    def step(self) -> list[GenResult]:
        """One batched decode step; returns requests that finished on it.

        Token selection happens IN the compiled step: the host receives a
        ``(slots,)`` int32 vector (plus the advanced per-lane PRNG keys),
        never the ``(slots, vocab)`` logits array."""
        if self.active_requests == 0:
            return []
        if self.paged:
            self._grow_pages()
        tokens = np.zeros((self.config.slots, 1), np.int32)
        positions = np.zeros((self.config.slots, 1), np.int32)
        temps = np.zeros((self.config.slots,), np.float32)
        top_ks = np.zeros((self.config.slots,), np.int32)
        for i, slot in enumerate(self._slots):
            if slot.active:
                tokens[i, 0] = slot.last_token
                positions[i, 0] = slot.next_pos
                temps[i] = max(slot.req.temperature, 0.0)
                top_ks[i] = slot.req.top_k
        # the tiny per-step host->device feeds (last tokens, positions,
        # sampling params — slots×a-few int32/float32) are converted BEFORE
        # the guarded window: they are the decode step's entire intended
        # transfer budget, and anything else crossing the boundary inside
        # the dispatch trips the transfer guard
        args = (
            self.variables, self._tenants_arg(), self._cache,
            jnp.asarray(tokens), jnp.asarray(positions),
            jnp.asarray(temps), jnp.asarray(top_ks),
            jnp.asarray(self._rng_keys),
            self._page_table_arg(), self._adapter_ids_arg(),
        )
        if self._transfer_guard is not None:
            next_tokens, rng_keys, self._cache = self._transfer_guard.run(
                "decode", self._decode, *args
            )
        else:
            next_tokens, rng_keys, self._cache = self._decode(*args)
        self.steps_total += 1
        next_tokens = np.asarray(next_tokens)
        # np.array (not asarray): admit() writes per-lane rows into this
        # buffer, and a zero-copy view of a jax array is read-only
        self._rng_keys = np.array(rng_keys, np.uint32)
        finished: list[GenResult] = []
        for i, slot in enumerate(self._slots):
            if not slot.active:
                continue
            slot.next_pos += 1
            done = self._record(slot, int(next_tokens[i]))
            if done is not None:
                finished.append(done)
        return finished

    def run(self, requests: list[GenRequest]) -> dict[str, GenResult]:
        """Synchronous convenience driver (tests): admit everything —
        overflow waits for a lane or for pool pages — and step until the
        batch drains."""
        results: dict[str, GenResult] = {}
        pending = list(requests)
        guard_steps = itertools.count()
        limit = sum(r.max_new_tokens for r in requests) + len(requests) + 8
        while pending or self.active_requests:
            while pending and self.free_slots and self.can_admit(pending[0]):
                done = self.admit(pending.pop(0))
                if done is not None:  # finished on admission (eos / max_new=1)
                    results[done.request_id] = done
            if pending and not self.active_requests \
                    and not self.can_admit(pending[0]):
                raise PoolExhausted(
                    f"request {pending[0].request_id} can never admit: the "
                    "kv page pool is exhausted with no work in flight"
                )
            for done in self.step():
                results[done.request_id] = done
            if next(guard_steps) > limit:  # pragma: no cover - safety valve
                raise RuntimeError("engine.run failed to converge")
        missing = [r.request_id for r in requests if r.request_id not in results]
        if missing:  # pragma: no cover - engine invariant
            raise RuntimeError(f"requests did not finish: {missing}")
        return results


def warm_engine(engine: "BatchEngine", *, warm_new: int | None = None) -> None:
    """Pay every compile an engine will ever need BEFORE it takes traffic:
    one dummy request per prompt bucket plus a decode step.  The zero-downtime
    rollover contract depends on a fresh replica not compiling under load —
    the in-process fleet and the transport worker share this exact warmup so
    process-mode replicas are warm-started too (docs/serving.md §Fleet).
    Warmup counter noise is zeroed; the shapes are exactly the budgeted ones,
    so the recompile guard stays armed and accurate."""
    new_tokens = warm_new if warm_new is not None \
        else min(2, engine.config.max_new_tokens)
    t0 = time.perf_counter()
    for bucket in engine.config.prompt_buckets:
        engine.run([GenRequest(
            request_id=f"_warm-{bucket}", tokens=[1] * bucket,
            max_new_tokens=new_tokens,
        )])
    engine.steps_total = 0
    engine.tokens_generated_total = 0
    engine.requests_finished_total = 0
    engine.prefix_hits_total = 0
    engine.prefix_misses_total = 0
    engine.prefill_tokens_saved_total = 0
    engine.tokens_by_tenant = {}
    engine.warm_start_s = round(time.perf_counter() - t0, 3)
