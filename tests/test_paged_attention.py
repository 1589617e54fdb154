"""Pallas paged-attention kernel: agreement with the gather path + dispatch.

The acceptance anchors: the block-sparse kernel (``ops/pallas/
paged_attention.py``) walks each lane's page list through the BlockSpec
index map instead of materialising a gathered logical cache.  Its matmuls
accumulate in f32 (what the chip's compiler takes), so it agrees with the
gather oracle to storage-dtype rounding, not bit for bit: CI pins that
tolerance in interpret mode — across dtypes, page-table shapes with
scratch-page slots, per-row and scalar positions, and the chip's own serve
shapes — and the serving engine under ``FTC_PAGED_ATTN=kernel`` reproduces
``cached_generate`` token for token on an f32 model (greedy AND sampled,
staggered mixed batches, page-boundary-straddling CoW splices) within the
same compile budget as the gather path.  That the kernel compiles for the
chip is ``tests/test_chip_compile.py``; that it runs there is
``chip_smoke.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from finetune_controller_tpu.models.generate import cached_generate
from finetune_controller_tpu.models.llama import PRESETS, LlamaForCausalLM
from finetune_controller_tpu.models.lora import LoRAConfig
from finetune_controller_tpu.ops.attention import (
    chunked_cache_attention,
    paged_attention_impl,
    paged_cache_attention,
    paged_gather,
    paged_kernel_eligible,
)
from finetune_controller_tpu.ops.pallas.paged_attention import (
    paged_attention,
    paged_attention_vmem_bytes,
)
from finetune_controller_tpu.serve.engine import (
    BatchEngine,
    EngineConfig,
    GenRequest,
)


@jax.jit
def _gather_oracle(q, k_pool, v_pool, table, idx):
    """The reference path, jitted: gather + chunked_cache_attention —
    exactly what the gather impl of ``paged_cache_attention`` runs."""
    return chunked_cache_attention(
        q, paged_gather(k_pool, table), paged_gather(v_pool, table), idx
    )


#: max |kernel - gather| the contract allows: two roundings of the storage
#: dtype at the outputs' magnitude (unit-normal V rows average to |out| < 4,
#: where one bf16 ulp is 2**-6) — the same numbers chip_smoke.py holds the
#: compiled kernel to on the chip
TOLERANCE = {jnp.float32: 4e-6, jnp.bfloat16: 2 ** -5}


def _max_err(got, want):
    return float(jnp.max(jnp.abs(
        got.astype(jnp.float32) - want.astype(jnp.float32))))


def _case(key, *, b, s, mp, t, h, hkv, pool_pages, dtype, d=16):
    """Random pools (scratch page 0 holds garbage like the real pool),
    a random page table with some slots pointing at scratch, per-row
    positions that straddle page boundaries."""
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (b, s, h, d), dtype)
    k_pool = jax.random.normal(ks[1], (pool_pages, t, hkv, d), dtype)
    v_pool = jax.random.normal(ks[2], (pool_pages, t, hkv, d), dtype)
    table = jax.random.randint(ks[3], (b, mp), 0, pool_pages, jnp.int32)
    # unmaterialised tail slots -> scratch page, like the engine's tables
    table = table.at[:, -1].set(0)
    idx = jax.random.randint(ks[4], (b,), 0, mp * t - s + 1, jnp.int32)
    return q, k_pool, v_pool, table, idx


CASES = [
    dict(b=1, s=1, mp=2, t=4, h=4, hkv=2, pool_pages=5),    # decode step
    dict(b=3, s=1, mp=4, t=8, h=4, hkv=2, pool_pages=9),    # batched decode
    dict(b=2, s=8, mp=3, t=8, h=4, hkv=4, pool_pages=7),    # suffix prefill
    dict(b=2, s=4, mp=5, t=4, h=8, hkv=2, pool_pages=11),   # g=4 grouping
    dict(b=4, s=2, mp=2, t=16, h=2, hkv=1, pool_pages=3),   # MQA
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", range(len(CASES)))
def test_kernel_matches_gather_oracle(case, dtype):
    """The contract: equal to the oracle up to one rounding of the storage
    dtype — every shape, both dtypes."""
    spec = CASES[case]
    q, k, v, table, idx = _case(jax.random.PRNGKey(case), dtype=dtype, **spec)
    want = _gather_oracle(q, k, v, table, idx)
    got = paged_attention(q, k, v, table, idx, interpret=True)
    assert got.dtype == want.dtype and got.shape == want.shape
    err = _max_err(got, want)
    assert err <= TOLERANCE[dtype], (
        f"kernel off the gather oracle by {err} on case {spec} {dtype}")


#: the chip's serve shapes (tinyllama-1.1b and a head-dim-128 GQA model at the
#: default buckets: 16-token pages, 40 pages per lane) — several query-row
#: blocks, row padding (h=28: G=7), lane-sliced KV heads
CHIP_CASES = [
    dict(b=2, s=1, mp=40, t=16, h=32, hkv=4, pool_pages=90, d=64),
    dict(b=1, s=200, mp=40, t=16, h=32, hkv=4, pool_pages=50, d=64),
    dict(b=2, s=3, mp=40, t=16, h=28, hkv=4, pool_pages=90, d=128),
]


@pytest.mark.parametrize("case", range(len(CHIP_CASES)))
def test_kernel_matches_gather_oracle_at_chip_shapes(case):
    spec = CHIP_CASES[case]
    q, k, v, table, idx = _case(
        jax.random.PRNGKey(20 + case), dtype=jnp.bfloat16, **spec)
    assert paged_kernel_eligible(q, k, v, table)
    want = _gather_oracle(q, k, v, table, idx)
    got = paged_attention(q, k, v, table, idx, interpret=True)
    assert _max_err(got, want) <= TOLERANCE[jnp.bfloat16]


def test_kernel_scalar_idx_matches_per_row():
    """A scalar position (cached_generate's lockstep decode) must hit the
    same program as the equivalent per-row vector."""
    q, k, v, table, _ = _case(
        jax.random.PRNGKey(7), b=3, s=1, mp=3, t=4, h=4, hkv=2,
        pool_pages=6, dtype=jnp.float32,
    )
    got_scalar = paged_attention(q, k, v, table, 5, interpret=True)
    got_vec = paged_attention(
        q, k, v, table, jnp.full((3,), 5, jnp.int32), interpret=True
    )
    assert jnp.array_equal(got_scalar, got_vec)


def test_kernel_batch_independence():
    """A lane's result must not depend on which other lanes ride the batch
    — the serving engine's batching invariance (a request decodes the same
    alone or alongside others) rests on it.  Bit for bit, per lane."""
    q, k, v, table, idx = _case(
        jax.random.PRNGKey(11), b=4, s=2, mp=3, t=8, h=4, hkv=2,
        pool_pages=8, dtype=jnp.bfloat16,
    )
    full = paged_attention(q, k, v, table, idx, interpret=True)
    for lane in range(4):
        solo = paged_attention(
            q[lane:lane + 1], k, v, table[lane:lane + 1], idx[lane:lane + 1],
            interpret=True,
        )
        assert jnp.array_equal(
            solo.view(jnp.uint16), full[lane:lane + 1].view(jnp.uint16)
        ), f"kernel is batch-dependent at lane {lane}"


def test_kernel_dtype_mismatch_raises():
    q, k, v, table, idx = _case(
        jax.random.PRNGKey(0), b=1, s=1, mp=2, t=4, h=4, hkv=2,
        pool_pages=4, dtype=jnp.float32,
    )
    with pytest.raises(ValueError, match="dtypes must match"):
        paged_attention(q.astype(jnp.bfloat16), k, v, table, idx)


def test_vmem_budget_scales_with_pages():
    small = paged_attention_vmem_bytes((1, 1, 4, 16), 2, 8, 2, 2)
    big = paged_attention_vmem_bytes((1, 1, 4, 16), 64, 8, 2, 2)
    assert 0 < small < big


def test_kernel_eligibility_is_shape_and_budget(monkeypatch):
    """``auto`` only ever picks the kernel for calls the chip's compiler
    takes: tile-aligned pages/heads, matching dtypes, and a VMEM need within
    the budget the kernel is compiled with (FTC_PAGED_VMEM_MB)."""
    monkeypatch.delenv("FTC_PAGED_VMEM_MB", raising=False)
    S, bf = jax.ShapeDtypeStruct, jnp.bfloat16

    def call(q, pool, mp, dtype=bf, pool_dtype=bf):
        return paged_kernel_eligible(
            S(q, dtype), S(pool, pool_dtype), S(pool, pool_dtype),
            S((q[0], mp), jnp.int32))

    assert call((8, 1, 32, 64), (512, 16, 4, 64), 40)       # tinyllama decode
    assert call((1, 512, 32, 128), (512, 16, 8, 128), 40)   # d128 prefill
    assert not call((8, 1, 32, 64), (512, 8, 4, 64), 40)    # half a bf16 tile
    assert call((8, 1, 32, 64), (512, 8, 4, 64), 40, jnp.float32, jnp.float32)
    assert not call((8, 1, 4, 16), (512, 16, 2, 16), 4)     # Hkv*D = 32 lanes
    assert not call((8, 1, 32, 64), (512, 16, 4, 64), 40, bf, jnp.float32)
    # a 16k-token lane at Hkv*D = 1024 needs > 64 MiB of scratch
    assert not call((8, 1, 32, 128), (9000, 16, 8, 128), 1024)
    monkeypatch.setenv("FTC_PAGED_VMEM_MB", "100")
    assert call((8, 1, 32, 128), (9000, 16, 8, 128), 1024)


# ---------------------------------------------------------------------------
# Dispatch: FTC_PAGED_ATTN / FTC_PAGED_VMEM_MB
# ---------------------------------------------------------------------------


def _dispatch_args(dtype=jnp.float32):
    q, k, v, table, _ = _case(
        jax.random.PRNGKey(1), b=1, s=1, mp=2, t=4, h=4, hkv=2,
        pool_pages=4, dtype=dtype,
    )
    return q, k, v, table


def test_dispatch_auto_is_gather_off_tpu(monkeypatch):
    monkeypatch.delenv("FTC_PAGED_ATTN", raising=False)
    if jax.default_backend() == "tpu":
        pytest.skip("auto resolves to kernel on TPU")
    assert paged_attention_impl(*_dispatch_args()) == "gather"


def test_dispatch_forced_kernel_everywhere(monkeypatch):
    monkeypatch.setenv("FTC_PAGED_ATTN", "kernel")
    assert paged_attention_impl(*_dispatch_args()) == "kernel"
    # auto declines mixed dtypes, but the explicit override is the
    # operator's call
    q, k, v, table = _dispatch_args()
    assert paged_attention_impl(
        q.astype(jnp.bfloat16), k, v, table) == "kernel"


def test_dispatch_rejects_unknown_impl(monkeypatch):
    monkeypatch.setenv("FTC_PAGED_ATTN", "turbo")
    with pytest.raises(ValueError, match="FTC_PAGED_ATTN"):
        paged_attention_impl(*_dispatch_args())


def test_dispatch_rejects_bad_vmem_budget(monkeypatch):
    monkeypatch.setenv("FTC_PAGED_VMEM_MB", "-3")
    q, k, v, table, _ = _case(
        jax.random.PRNGKey(1), b=1, s=1, mp=2, t=16, h=4, hkv=2,
        pool_pages=4, dtype=jnp.bfloat16, d=64,
    )
    with pytest.raises(ValueError, match="FTC_PAGED_VMEM_MB"):
        paged_kernel_eligible(q, k, v, table)


def test_paged_cache_attention_kernel_equals_gather(monkeypatch):
    """The public seam: flipping FTC_PAGED_ATTN moves the result by no more
    than the storage dtype's rounding."""
    q, k, v, table, idx = _case(
        jax.random.PRNGKey(3), b=2, s=4, mp=3, t=8, h=4, hkv=2,
        pool_pages=7, dtype=jnp.bfloat16,
    )
    monkeypatch.setenv("FTC_PAGED_ATTN", "gather")
    want = jax.jit(paged_cache_attention)(q, k, v, table, idx)
    monkeypatch.setenv("FTC_PAGED_ATTN", "kernel")
    got = jax.jit(paged_cache_attention)(q, k, v, table, idx)
    assert _max_err(got, want) <= TOLERANCE[jnp.bfloat16]


# ---------------------------------------------------------------------------
# Engine anchors under FTC_PAGED_ATTN=kernel
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_model():
    # f32 compute: kernel and gather then differ in the last f32 bit, far
    # below any logit gap, so token-for-token equality with cached_generate
    # (which runs the gather numerics) tests the engine's page plumbing and
    # not the luck of a bf16 near-tie
    cfg = PRESETS["tiny-test"].replace(
        lora=LoRAConfig(rank=4), dtype=jnp.float32)
    model = LlamaForCausalLM(cfg)
    variables = model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 4), jnp.int32)
    )
    return model, variables


def _baseline(model, variables, prompt, n, **kw):
    out = cached_generate(
        model, variables, jnp.asarray([prompt], jnp.int32),
        max_new_tokens=n, **kw,
    )
    return list(np.asarray(out[0, len(prompt):]))


def _kernel_engine(model, variables, **kw):
    defaults = dict(slots=2, prompt_buckets=(8, 16), max_new_tokens=24,
                    page_tokens=8)
    defaults.update(kw)
    return BatchEngine(model, variables, EngineConfig(**defaults))


def test_engine_greedy_kernel_staggered_identity(tiny_model, monkeypatch):
    """Greedy decode through the kernel — mixed prompt lengths joining
    mid-flight — token-identical to single-request cached_generate."""
    monkeypatch.setenv("FTC_PAGED_ATTN", "kernel")
    model, variables = tiny_model
    prompts = [
        [5, 9, 2, 7],
        [1, 3, 3, 8, 2, 2],
        [11, 4, 9, 1, 2, 3, 4, 5, 6, 0, 2, 1],  # second bucket
    ]
    reqs = [
        GenRequest(request_id=f"r{i}", tokens=p, max_new_tokens=5 + 2 * i)
        for i, p in enumerate(prompts)
    ]
    eng = _kernel_engine(model, variables, pool_pages=12)
    res = eng.run(list(reqs))
    for i, p in enumerate(prompts):
        want = _baseline(model, variables, p, 5 + 2 * i)
        assert res[f"r{i}"].generated == want, f"kernel diverged on r{i}"


def test_engine_sampled_kernel_reproducible(tiny_model, monkeypatch):
    """Sampled decode through the kernel reproduces the per-request
    PRNGKey(seed) stream token for token."""
    monkeypatch.setenv("FTC_PAGED_ATTN", "kernel")
    model, variables = tiny_model
    reqs = [
        GenRequest(request_id=f"s{i}", tokens=[3 + i, 1, 4, 1], seed=40 + i,
                   temperature=0.8, top_k=7, max_new_tokens=6)
        for i in range(2)
    ]
    eng = _kernel_engine(model, variables, pool_pages=12)
    res = eng.run(reqs)
    for i in range(2):
        want = _baseline(
            model, variables, [3 + i, 1, 4, 1], 6,
            temperature=0.8, top_k=7, rng=jax.random.PRNGKey(40 + i),
        )
        assert res[f"s{i}"].generated == want


def test_engine_kernel_page_boundary_cow_splice(tiny_model, monkeypatch):
    """Page size dividing neither bucket nor reuse length: the kernel
    serves CoW boundary splices token-identically, within the paged
    compile budget (len(buckets) + 1 — unchanged by the kernel)."""
    monkeypatch.setenv("FTC_PAGED_ATTN", "kernel")
    model, variables = tiny_model
    eng = _kernel_engine(
        model, variables, page_tokens=7, pool_pages=16,
        prefix_cache_bytes=1 << 20,
    )
    assert eng.guard.budget == 3
    shared = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]   # 10 tokens: 1.43 pages of 7
    reqs = [
        GenRequest(request_id=f"b{i}", tokens=shared + [20 + i],
                   max_new_tokens=5)
        for i in range(3)
    ]
    res = eng.run(reqs)
    for i in range(3):
        want = _baseline(model, variables, shared + [20 + i], 5)
        assert res[f"b{i}"].generated == want, f"b{i} diverged"
    assert eng.prefix_hits_total >= 2
    assert eng.kv_page_stats()["cow_copies_total"] >= 1
    assert eng.compilations <= 3
