"""Compile the main path's kernels for a described TPU v5e — no chip attached.

The TPU compiler is installed next to the CPU backend and compiles for a
topology that is described, not attached (``v5e:2x2``).  Interpret-mode tests
cannot see what it refuses: a matmul accumulator it does not take, a slice off
the tiling, more VMEM than a kernel may use, a Mosaic kernel left for the
partitioner to split.  Each case here is a compile of a second or two at the
real serve/train widths; nothing runs, so nothing here says anything about
results or times (``chip_smoke.py`` does).

Code that asks ``jax.default_backend()`` still sees the CPU here, so the
kernels are compiled directly with ``interpret=False`` rather than through
their dispatch.  The persistent compilation cache is off around these
compiles: an entry written for a described device cannot be read back without
one, and the next run would only warn about it.
"""

from __future__ import annotations

import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from finetune_controller_tpu.ops.attention import (
    _flash_attention_on_mesh,
    paged_kernel_eligible,
)
from finetune_controller_tpu.ops.pallas.flash_attention import flash_attention
from finetune_controller_tpu.ops.pallas.paged_attention import (
    DEFAULT_VMEM_MB,
    _paged_attention,
)
from finetune_controller_tpu.parallel.mesh import MeshSpec
from finetune_controller_tpu.parallel.ring import ring_mesh

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {exc}")
    return topo.devices


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _custom_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


# ---------------------------------------------------------------------------
# flash attention: the training kernels
# ---------------------------------------------------------------------------

#: (B, S, H, Hkv, D[, Dv]): the TinyLlama training shape chip_smoke.py runs,
#: a head-dim-128 GQA shape (Llama-3 / Mistral heads at a batch that fits),
#: the two Mistral cells' calls (8 x 2048 and 2 x 8192), and latent
#: attention's uneven pair (q/k 128 + 64, v 128; 192 is no multiple of the
#: 128 lanes) at the expert cell's batch, and the hybrid cell's call (one row
#: of 8,192, 20 query heads over 4 key/value heads: a group of 5, where every
#: other shape's is 1, 4 or 8).  Every one runs 1024-wide blocks whose
#: diagonal ones are computed in sub-tiles.
FLASH_SHAPES = {
    "tinyllama-b8-s2048": (8, 2048, 32, 4, 64),
    "d128-b2-s2048": (2, 2048, 32, 8, 128),
    "d128-b8-s2048": (8, 2048, 32, 8, 128),
    "d128-b2-s8192": (2, 8192, 32, 8, 128),
    "qk192-v128-b2-s4096": (2, 4096, 32, 32, 192, 128),
    "d128-g5-b1-s8192": (1, 8192, 20, 4, 128),
    # the pattern cell's one attention layer: 32 query heads over 2 key/value
    # heads, a group of 16 (the dK/dV kernel's inner sweep is 16 x the q blocks)
    "d128-g16-b1-s8192": (1, 8192, 32, 2, 128),
}


@pytest.mark.parametrize("shape,segments", [
    ("tinyllama-b8-s2048", False), ("tinyllama-b8-s2048", True),
    ("d128-b2-s2048", False), ("qk192-v128-b2-s4096", False),
    ("d128-b8-s2048", False), ("d128-b2-s8192", False), ("d128-b2-s8192", True),
    ("d128-g5-b1-s8192", False), ("d128-g5-b1-s8192", True),
    ("d128-g16-b1-s8192", False), ("d128-g16-b1-s8192", True),
], ids=["tinyllama-plain", "tinyllama-segments", "d128-plain", "qk192-v128",
        "mistral-2k-plain", "mistral-8k-plain", "mistral-8k-segments",
        "hybrid-8k-plain", "hybrid-8k-segments",
        "pattern-8k-plain", "pattern-8k-segments"])
def test_flash_forward_and_grad_compile_for_v5e(v5e, shape, segments):
    b, s, h, hkv, d, *rest = FLASH_SHAPES[shape]
    one = SingleDeviceSharding(v5e[0])
    q = jax.ShapeDtypeStruct((b, s, h, d), BF16, sharding=one)
    k = jax.ShapeDtypeStruct((b, s, hkv, d), BF16, sharding=one)
    v = jax.ShapeDtypeStruct((b, s, hkv, rest[0] if rest else d), BF16,
                             sharding=one)
    seg = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=one)

    def loss(q, k, v, seg):
        out = flash_attention(
            q, k, v, segment_ids=seg if segments else None, interpret=False)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        q, k, v, seg).compile()
    assert _custom_calls(compiled) == 3  # forward + dQ + dK/dV


#: the window/full configuration's two attention calls as published (one row
#: of 16,384, 64 query heads of 192 beside v heads of 128): over 8 key/value
#: heads under a window of 128 keys beside a sink, over 4 with every earlier key
WINDOW_SHAPES = {
    "swa-w128-sink": (1, 16384, 64, 8, 192, 128, 128, True),
    "gqa192-full": (1, 16384, 64, 4, 192, 128, None, False),
    # a window past a block: three inner steps, whole blocks between the edges
    "swa-w2500": (1, 8192, 32, 8, 128, 128, 2500, False),
}


@pytest.mark.parametrize("shape, segments", [
    ("swa-w128-sink", False), ("swa-w128-sink", True), ("gqa192-full", False),
    ("swa-w2500", True)], ids=["window-plain", "window-segments", "full-plain",
                               "window-2500-segments"])
def test_window_kernels_compile_for_v5e_at_published_shapes_forward_and_grad(
        v5e, shape, segments):
    """The three kernel bodies with a static window and a sink operand are
    three Mosaic calls under their own names (``flash_swa_*``); the full
    layers' call at the same widths keeps the names it always had."""
    b, s, h, hkv, d, dv, window, with_sink = WINDOW_SHAPES[shape]
    one = SingleDeviceSharding(v5e[0])
    q = jax.ShapeDtypeStruct((b, s, h, d), BF16, sharding=one)
    k = jax.ShapeDtypeStruct((b, s, hkv, d), BF16, sharding=one)
    v = jax.ShapeDtypeStruct((b, s, hkv, dv), BF16, sharding=one)
    seg = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=one)
    sink = jax.ShapeDtypeStruct((h,), jnp.float32, sharding=one)

    def loss(q, k, v, sink, seg):
        out = flash_attention(
            q, k, v, segment_ids=seg if segments else None, window=window,
            sink=sink if with_sink else None, interpret=False)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    wrt = (0, 1, 2, 3) if with_sink else (0, 1, 2)
    compiled = jax.jit(jax.value_and_grad(loss, argnums=wrt)).lower(
        q, k, v, sink, seg).compile()
    assert _custom_calls(compiled) == 3
    text = compiled.as_text()
    names = ("flash_swa_fwd", "flash_swa_bwd_dq", "flash_swa_bwd_dkv")
    if window is None:
        assert not any(name in text for name in names)
        assert all(n in text for n in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"))
    else:
        assert all(name in text for name in names)


def test_a_call_with_no_window_and_no_sink_traces_the_kernels_it_always_did():
    """``window=None`` and no sink: the jaxpr of the call and its gradient —
    kernel bodies, grids, index maps — holds no trace of the second frontier
    (no ``flash_swa`` name, the causal grid of every key block), and a sink
    alone adds ONE operand to the forward kernel and none to the backward's."""
    q = jnp.zeros((1, 2048, 4, 64), BF16)
    kv = jnp.zeros((1, 2048, 2, 64), BF16)

    def grad_text(**kw):
        def loss(q, k, v, sink):
            out = flash_attention(q, k, v, interpret=False, **(
                {"sink": sink} if kw.get("sink") else {}), window=kw.get("window"))
            return jnp.sum(out.astype(jnp.float32) ** 2)
        return str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(
            q, kv, kv, jnp.zeros((4,), jnp.float32)))

    plain = grad_text()
    assert "flash_swa" not in plain and plain.count("pallas_call") == 3
    assert "grid=(1, 4, 2, 2)" in plain            # every key block of two
    sunk = grad_text(sink=True)
    assert "flash_swa" not in sunk and "name=flash_fwd" in sunk
    windowed = grad_text(window=128)
    assert windowed.count("flash_swa_") >= 3 and "name=flash_fwd" not in windowed


# ---------------------------------------------------------------------------
# the state-space mixer (models/ssm.py): its recurrence is two Mosaic kernels
# (ops/pallas/ssd_scan.py), the rest plain jnp the compiler must take
# ---------------------------------------------------------------------------


#: a mixer at its configuration's published widths: the hybrid one (32 heads
#: of 128 over 128 x 256 states, B and C in 2 groups, a muP multiplier on
#: every segment) and the pattern one (128 heads of 64 over 64 x 128 states,
#: B and C in 8 groups, no multiplier: four times the heads at the same state
#: bytes, two heads a 128-lane tile)
MIXER_WIDTHS = {
    "hybrid": dict(
        d_model=5120, ssm_n_heads=32, ssm_head_dim=128, ssm_d_state=256,
        ssm_n_groups=2, ssm_in_multiplier=0.25,
        ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                         0.3535533905932738)),
    "pattern": dict(
        d_model=4096, ssm_n_heads=128, ssm_head_dim=64, ssm_d_state=128,
        ssm_n_groups=8),
}


@pytest.mark.parametrize("family", list(MIXER_WIDTHS))
@pytest.mark.parametrize("segments", [False, True], ids=["plain", "segments"])
def test_scan_kernels_compile_for_v5e_at_published_shapes_forward_and_grad(
        v5e, segments, family):
    """The chunked scan's kernels at the two published head shapes, one row
    of 8,192 in chunks of 128, the block the chooser's rule gives (a group's
    16 heads): the plain call is ONE kernel and writes no entering state;
    value and the gradients of all six inputs are TWO (the forward that keeps
    the bf16 entering states, the reverse walk), with and without document
    boundaries."""
    from finetune_controller_tpu.ops.pallas import ssd_scan

    w = MIXER_WIDTHS[family]
    h, p = w["ssm_n_heads"], w["ssm_head_dim"]
    g, n = w["ssm_n_groups"], w["ssm_d_state"]
    heads = ssd_scan.heads_per_block(h, p, g, n, 128)
    assert heads == 16
    one = SingleDeviceSharding(v5e[0])

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    args = (on_chip((1, 8192, h, p), BF16), on_chip((1, 8192, h), jnp.float32),
            on_chip((h,), jnp.float32), on_chip((1, 8192, g, n), BF16),
            on_chip((1, 8192, g, n), BF16), on_chip((h,), jnp.float32),
            on_chip((1, 8192), jnp.int32))

    def scan(x, dt, a, b, c, d, runs):
        return ssd_scan.ssd_scan_pallas(
            x, dt, a, b, c, d, runs if segments else None, chunk=128,
            heads_per_block=heads, interpret=False)

    forward = jax.jit(scan).lower(*args).compile()
    assert _custom_calls(forward) == 1
    assert not _written(forward.as_text(), [(1, 64, n, h * p)])
    compiled = jax.jit(jax.grad(
        lambda *t: jnp.sum(scan(*t) ** 2), argnums=(0, 1, 2, 3, 4, 5))).lower(
            *args).compile()
    assert _custom_calls(compiled) == 2
    # no float32 decay, score or chunk state of the jnp form is left: what the
    # backward holds is y's cotangent, the entering states and the outputs
    assert compiled.memory_analysis().temp_size_in_bytes < 1.3e9


@pytest.mark.parametrize("segments", [False, True], ids=["plain", "segments"])
def test_sparse_and_lightning_calls_compile_for_v5e_at_published_shapes(v5e, segments):
    """The sparse / lightning configuration's two calls at their published
    shapes, one row of 32,768: the flash kernels over a selection for each of
    the 2 key/value heads (32 query heads of 128, ``(1, 2, S, 1024)`` words
    read in place) and the scan where every one of 32 heads of 128 has its
    own keys and queries (blocks of 16 heads that span 16 groups, a chain of
    256 chunk states) — value and gradients, three kernels and two."""
    from finetune_controller_tpu.ops.pallas import ssd_scan

    one = SingleDeviceSharding(v5e[0])

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    s = 32768
    seg = on_chip((1, s), jnp.int32)

    def attend(q, k, v, words, seg):
        out = flash_attention(q, k, v, selection=words, interpret=False,
                              segment_ids=seg if segments else None)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.value_and_grad(attend, argnums=(0, 1, 2))).lower(
        on_chip((1, s, 32, 128), BF16), on_chip((1, s, 2, 128), BF16),
        on_chip((1, s, 2, 128), BF16), on_chip((1, 2, s, 1024), jnp.int32),
        seg).compile()
    assert _custom_calls(compiled) == 3
    # the words are read where they lie: no second copy of their 268 MB
    assert not [line for line in compiled.as_text().splitlines()
                if re.search(r" = s32\[(1,)?2,32768,1024\]", line)
                and not re.search(r" (parameter|get-tuple-element|bitcast)\(", line)]

    heads = ssd_scan.heads_per_block(32, 128, 32, 128, 128)
    assert heads == 16

    def scan(v, k, q, runs):
        y = ssd_scan.ssd_scan_pallas(
            v, jnp.ones((1, s, 32), jnp.float32),
            -jnp.exp2(-jnp.arange(1.0, 33.0) / 4), k, q,
            jnp.zeros((32,), jnp.float32), runs if segments else None,
            chunk=128, heads_per_block=heads, interpret=False)
        return jnp.sum(y ** 2)

    heads_of = on_chip((1, s, 32, 128), BF16)
    compiled = jax.jit(jax.grad(scan, argnums=(0, 1, 2))).lower(
        heads_of, heads_of, heads_of, seg).compile()
    assert _custom_calls(compiled) == 2
    assert compiled.memory_analysis().temp_size_in_bytes < 2.6e9


@pytest.mark.parametrize("family", list(MIXER_WIDTHS))
@pytest.mark.parametrize("segments", [False, True], ids=["plain", "segments"])
def test_mixer_compiles_for_v5e_at_published_widths_forward_and_grad(
        v5e, segments, family, monkeypatch):
    """One layer's mixer at its configuration's published widths, chunks of
    128, one row of 8,192, with adapters on both projections, as the chip
    traces it (``jax.default_backend`` answers ``tpu`` here, so the chooser
    takes the kernels): value and gradients compile for the chip, the
    recurrence is the scan's two Mosaic kernels — the carry across the 64
    chunks is no loop of the program in either pass — and what the layer's
    backward pass holds stays under the 6 GB the step has for a block."""
    from finetune_controller_tpu.models.llama import LlamaConfig
    from finetune_controller_tpu.models.lora import HYBRID_TARGETS, LoRAConfig
    from finetune_controller_tpu.models.ssm import Mamba2Mixer

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = LlamaConfig(
        dtype=BF16, ssm_d_conv=4, ssm_chunk=128, **MIXER_WIDTHS[family],
        lora=LoRAConfig(rank=16, targets=HYBRID_TARGETS))
    width = cfg.d_model
    mixer = Mamba2Mixer(cfg)
    one = SingleDeviceSharding(v5e[0])

    def on_chip(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(
                s.shape, BF16 if s.dtype == jnp.float32 else s.dtype,
                sharding=one), tree)

    variables = jax.eval_shape(lambda: mixer.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8, width), BF16)))
    assert sorted(variables["params"]) == [
        "A_log", "D", "conv1d", "dt_bias", "in_proj", "norm", "out_proj"]
    params = on_chip(variables["params"])        # the frozen base, as stored
    lora = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one), variables["lora"])
    u = jax.ShapeDtypeStruct((1, 8192, width), BF16, sharding=one)
    seg = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=one)

    def loss(u, lora, params, seg):
        y = mixer.apply({"params": params, "lora": lora}, u,
                        seg if segments else None)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        u, lora, params, seg).compile()
    text = compiled.as_text()
    assert _custom_calls(compiled) == 2
    assert "ssd_scan_fwd" in text and "ssd_scan_bwd" in text
    assert not re.findall(r"\bwhile\(", text), "the carry is the kernels' grid"
    assert compiled.memory_analysis().temp_size_in_bytes < 6e9


# ---------------------------------------------------------------------------
# the expert layer's grouped product (models/moe.py dropless dispatch)
# ---------------------------------------------------------------------------


def _written(text: str, shapes) -> list[str]:
    """The instructions that WRITE a bf16 array of one of ``shapes``: every
    one whose result has it but a parameter, a tuple's element or a bitcast."""
    results = tuple(f" = bf16[{','.join(map(str, s))}]" for s in shapes)
    return [line.strip()[:160] for line in text.splitlines()
            if any(r in line for r in results)
            and not re.search(r" (parameter|get-tuple-element|bitcast)\(", line)]


#: the two expert configurations' grouped products: rows a call, the groups
#: they are spread over, the experts' widths (d_model, d_ff), the layers of
#: the scanned stack
EXPERT_SHAPES = {
    # 8,192 tokens x 8 over all 256 experts: 256 rows a group
    "joyai_4k": (65536, 256, 2048, 768, 4),
    # a pass of ``held_row_bound`` rows over the 16 experts held: 1,024 a group
    "glm_16k_share": (16384, 16, 6144, 2048, 4),
    # a pass of ``held_row_bound`` rows (180,224 pairs, a quarter held) over
    # the 128 experts held, in the 1024-wide latent: 352 rows a group under an
    # even router; contractions of 1024 and 2688 for the first time
    "nemotron_8k_share": (90112, 128, 1024, 2688, 5),
}
#: (rows, contraction, width) the rule gives ``(up, down)`` and, the same two
#: swapped, their activation gradients
EXPERT_TILES = {
    "joyai_4k": ((256, 2048, 768), (256, 768, 2048)),
    "glm_16k_share": ((512, 1024, 1024), (512, 1024, 1024)),
    "nemotron_8k_share": ((512, 1024, 384), (512, 384, 1024)),
}


@pytest.mark.parametrize("where", ["plain", "in_place"])
@pytest.mark.parametrize("product", ["up", "down"])
@pytest.mark.parametrize("shape", list(EXPERT_SHAPES))
def test_grouped_expert_product_compiles_for_v5e_forward_and_activation_gradient(
        v5e, monkeypatch, shape, product, where):
    """Both expert configurations' products at the published widths, with the
    tiles the rule picks from each product's own shapes and the groups its
    rows are spread over (``_GmmTiling``): the Pallas kernel forward and,
    transposed, for the activation gradient (the frozen experts' weight
    gradient is never asked for, so its kernel goes) — handed a layer's
    experts, and reading them in place in the scanned stack's whole leaf,
    where the kernel sees ``L·G`` groups (all but ``G`` empty; at 4 x 256 the
    group and tile ids are 1,279 entries in SMEM) and the rule still ``G``:
    there the kernel takes a bitcast of the leaf, so nothing shaped like the
    leaf or like one layer of it is written."""
    from finetune_controller_tpu.models import moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    m, g, d, f, layers = EXPERT_SHAPES[shape]
    k, n = (d, f) if product == "up" else (f, d)
    forward, swapped = EXPERT_TILES[shape][::1 if product == "up" else -1]
    assert moe._GmmTiling(g)(m, k, n) == forward
    assert moe._GmmTiling(g)(m, n, k) == swapped
    one = SingleDeviceSharding(v5e[0])
    rows = jax.ShapeDtypeStruct((m, k), BF16, sharding=one)
    sizes = jax.ShapeDtypeStruct((g,), jnp.int32, sharding=one)
    if where == "plain":
        args = (rows, jax.ShapeDtypeStruct((g, k, n), BF16, sharding=one), sizes)
    else:
        args = (rows, jax.ShapeDtypeStruct((layers, g, k, n), BF16, sharding=one),
                sizes, jax.ShapeDtypeStruct((), jnp.int32, sharding=one))

    def loss(rows, kernels, sizes, layer=None):
        return jnp.sum(
            moe._grouped_dot(rows, kernels, sizes, layer).astype(jnp.float32))

    text = jax.jit(jax.value_and_grad(loss)).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "ragged-dot" not in text
    if where == "in_place":
        assert not _written(
            text, [(layers, g, k, n), (layers * g, k, n), (g, k, n)])


@pytest.mark.parametrize("path", ["in_place", "sliced"])
def test_scanned_expert_step_copies_no_layer_of_expert_kernels(
        v5e, monkeypatch, path):
    """A scanned stack of two expert layers at small width, loss and LoRA
    gradients under full remat, compiled for one v5e: with the experts read
    in place no instruction writes an array shaped like a layer's expert
    kernel — in neither loop body, nor anywhere.  ``sliced`` is why the path
    exists, and that this test sees it: handed the loop's slice, the kernel
    has its operand copied (three kernels in each of the two loops)."""
    from finetune_controller_tpu.models import moe
    from finetune_controller_tpu.models.llama import PRESETS, LlamaForCausalLM
    from finetune_controller_tpu.models.lora import MLA_TARGETS, LoRAConfig

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if path == "sliced":
        monkeypatch.setattr(LlamaForCausalLM, "_stacked_experts", lambda self: None)
    cfg = PRESETS["tiny-mla-moe-test"].replace(
        d_model=256, moe_d_ff=128, dtype=BF16, moe_select_bias=False,
        lora=LoRAConfig(rank=4, targets=MLA_TARGETS))
    model = LlamaForCausalLM(cfg)
    one = SingleDeviceSharding(v5e[0])
    shapes = jax.eval_shape(lambda: model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32)))
    # the frozen base stored in the compute type, adapters in float32
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, BF16, sharding=one),
        shapes["params"])
    lora = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
        shapes["lora"])
    tokens = jax.ShapeDtypeStruct((2, 64), jnp.int32, sharding=one)

    def loss(lora, params, tokens):
        logits, sown = model.apply({"params": params, "lora": lora}, tokens,
                                   mutable=("moe_stats",))
        return jnp.mean(logits ** 2), moe.moe_counters(sown)

    text = jax.jit(jax.value_and_grad(loss, has_aux=True)).lower(
        lora, params, tokens).compile().as_text()
    # three products forward, three recomputed, three activation gradients
    assert text.count('custom_call_target="tpu_custom_call"') == 9
    e, d, f = cfg.n_experts, cfg.d_model, cfg.moe_d_ff
    copies = _written(text, [(e, d, f), (e, f, d)])
    assert bool(copies) == (path == "sliced"), copies


def test_scanned_step_of_a_held_share_copies_no_expert_kernel(v5e, monkeypatch):
    """The same step where a layer holds a share of the experts (three of
    eight; 2,048 pairs in passes of 1,536 rows, so the later passes' two loops
    are in the program): the first pass and the loops read the share in place
    in the stacked leaf — no instruction writes an array shaped like a
    layer's share, or like the stack, anywhere."""
    from finetune_controller_tpu.models import moe
    from finetune_controller_tpu.models.llama import PRESETS, LlamaForCausalLM
    from finetune_controller_tpu.models.lora import MLA_TARGETS, LoRAConfig

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = PRESETS["tiny-mla-moe-test"].replace(
        d_model=256, moe_d_ff=128, dtype=BF16, moe_select_bias=False,
        experts_held=(0, 3), lora=LoRAConfig(rank=4, targets=MLA_TARGETS))
    model = LlamaForCausalLM(cfg)
    one = SingleDeviceSharding(v5e[0])
    shapes = jax.eval_shape(lambda: model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32)))
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, BF16, sharding=one),
        shapes["params"])
    lora = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
        shapes["lora"])
    tokens = jax.ShapeDtypeStruct((2, 512), jnp.int32, sharding=one)
    pairs = 2 * 512 * cfg.moe_top_k
    assert moe.held_row_bound(pairs, 3, cfg.n_experts) < pairs

    def loss(lora, params, tokens):
        logits, sown = model.apply({"params": params, "lora": lora}, tokens,
                                   mutable=("moe_stats",))
        return jnp.mean(logits ** 2), moe.moe_counters(sown)

    text = jax.jit(jax.value_and_grad(loss, has_aux=True)).lower(
        lora, params, tokens).compile().as_text()
    # the first pass's nine products, and the loops' own
    assert text.count('custom_call_target="tpu_custom_call"') > 9
    assert "ragged-dot" not in text
    layers, d, f = cfg.n_layers - cfg.first_k_dense, cfg.d_model, cfg.moe_d_ff
    copies = _written(text, [(3, d, f), (3, f, d), (layers, 3, d, f),
                             (layers, 3, f, d), (layers * 3, d, f),
                             (layers * 3, f, d)])
    # The kernels read the share in place: no slice or copy of those shapes.
    # What there is since ISSUE 37 (the adapted projections around the expert
    # layer take the joined form at these 1,024 rows): the compiler parks this
    # tiny stack WHOLE in its fast memory around the loops — asynchronous
    # copies of the whole 393,216-byte leaf between HBM and memory space 1
    # (``S(1)`` on exactly one side of the pair a ``copy-start`` returns), in
    # or back out.  A cell's stack of 1.6 GB cannot be parked: the next test
    for line in copies:
        start = re.search(r" copy-done\((%copy-start[\w.\-]*)\)", line)
        assert start, line
        pair = re.search(
            re.escape(start.group(1)) + rf" = \((bf16\[{layers},3,[\d,]+\]"
            r"\{[^}]*\}), (bf16\[[\d,]+\]\{[^}]*\}),", text)
        assert pair and sum("S(1)" in side for side in pair.groups()) == 1, line
    assert 2 * layers * 3 * d * f == 393216


def test_step_at_the_16k_cells_widths_copies_no_expert_kernel(v5e, monkeypatch):
    """The held share at the widths and rows it has in ``glm-5.2-lora.
    train-sft-16k`` (16 experts of 6144 x 2048 a layer, 1 x 16,384 tokens, a
    dense layer and a scanned stack of two expert layers; 13 of the 16 adapted
    projections joined, as in the cell): no instruction writes an array shaped
    like a layer's share, or like the stack — no slice for the kernels, and
    no move into fast memory either (806 MB of stack do not fit there)."""
    from benchmarks.harness.manifest import Manifest
    from finetune_controller_tpu.models import moe
    from finetune_controller_tpu.models.llama import LlamaForCausalLM

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    conf = Manifest().config("glm-5.2-lora")
    cfg = Manifest().program(conf).model_config(conf, max_seq_len=16384).replace(
        n_layers=3, indexer_types=("full", "shared", "full"))
    model = LlamaForCausalLM(cfg)
    one = SingleDeviceSharding(v5e[0])
    shapes = jax.eval_shape(lambda: model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32)))
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, BF16, sharding=one),
        shapes["params"])
    lora = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
        shapes["lora"])
    tokens = jax.ShapeDtypeStruct((1, 16384), jnp.int32, sharding=one)

    def loss(lora, params, tokens):
        logits, sown = model.apply({"params": params, "lora": lora}, tokens,
                                   mutable=("moe_stats",))
        return jnp.mean(logits ** 2), moe.moe_counters(sown)

    assert str(jax.make_jaxpr(loss)(lora, params, tokens)).count(
        "joined_product") == 13
    text = jax.jit(jax.value_and_grad(loss, has_aux=True)).lower(
        lora, params, tokens).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') > 9
    held, d, f = cfg.experts_held[1], cfg.d_model, cfg.moe_d_ff
    assert (held, d, f) == (16, 6144, 2048)
    assert not _written(text, [(held, d, f), (held, f, d), (2, held, d, f),
                               (2, held, f, d), (2 * held, d, f),
                               (2 * held, f, d)])


def test_step_at_the_pattern_cells_widths_copies_no_expert_kernel(v5e, monkeypatch):
    """The pattern configuration at its published widths and the cell's rows
    (``nemotron-3-super-lora.train-sft-8k``: 128 experts of 1024 x 2688 held
    of 512, top-22, 1 x 8,192 tokens), cut to ``EMEM*`` — a scanned pair of an
    expert layer in a latent and a mixer, twice, then attention without
    positions at a head group of 16: the step compiles for the chip with the
    grouped products (two an expert layer and pass), the three flash kernels,
    and no array shaped like a layer's share of the experts, or like the
    stack, written anywhere — the expert layer inside the scanned UNIT reads
    its kernels in place in its own stacked leaf."""
    from benchmarks.harness.manifest import Manifest
    from finetune_controller_tpu.models import moe
    from finetune_controller_tpu.models.llama import LlamaForCausalLM

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    conf = Manifest().config("nemotron-3-super-lora")
    cfg = Manifest().program(conf).model_config(conf, max_seq_len=8192).replace(
        n_layers=5, layer_pattern="EMEM*")
    assert cfg.pattern_runs() == (("EM", 2), ("*", 1))
    model = LlamaForCausalLM(cfg)
    one = SingleDeviceSharding(v5e[0])
    shapes = jax.eval_shape(lambda: model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32)))
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, BF16, sharding=one),
        shapes["params"])
    lora = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
        shapes["lora"])
    assert sorted(shapes["lora"]["blocks"]["layer_0"]["moe"]) == [
        "fc1_latent_proj", "fc2_latent_proj", "shared"]
    tokens = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=one)

    def loss(lora, params, tokens):
        logits, sown = model.apply({"params": params, "lora": lora}, tokens,
                                   mutable=("moe_stats",))
        return jnp.mean(logits ** 2), moe.moe_counters(sown)

    compiled = jax.jit(jax.value_and_grad(loss, has_aux=True)).lower(
        lora, params, tokens).compile()
    text = compiled.as_text()
    # two grouped products a pass (forward, recomputed, activation gradients)
    # and flash forward (twice: the unrolled layer is replayed too), dQ, dK/dV
    assert text.count('custom_call_target="tpu_custom_call"') >= 6 + 3
    assert "ragged-dot" not in text
    held, latent, f = cfg.experts_held[1], cfg.moe_latent, cfg.moe_d_ff
    assert (held, latent, f) == (128, 1024, 2688)
    assert moe.dropless_row_tile(8192 * 22, held, cfg.n_experts) == 512
    assert not _written(text, [(held, latent, f), (held, f, latent),
                               (2, held, latent, f), (2, held, f, latent),
                               (2 * held, latent, f), (2 * held, f, latent)])


# ---------------------------------------------------------------------------
# paged attention: the serve kernel
# ---------------------------------------------------------------------------

#: (B, S, H, Hkv, D, T, MP) at the default serve config (16-token pages, 40
#: pages per lane): tinyllama-1.1b decode and bucketed prefill, and a
#: head-dim-128 model (Hkv=8) at decode and at the widest prefill bucket
PAGED_SHAPES = {
    "tinyllama-decode": (8, 1, 32, 4, 64, 16, 40),
    "tinyllama-prefill-128": (1, 128, 32, 4, 64, 16, 40),
    "tinyllama-prefill-512": (1, 512, 32, 4, 64, 16, 40),
    "d128-decode": (8, 1, 32, 8, 128, 16, 40),
    "d128-prefill-512": (1, 512, 32, 8, 128, 16, 40),
    # a 8k-token lane: the scratch cache alone is 32 MiB of the 64 MiB budget
    "d128-decode-8k": (8, 1, 32, 8, 128, 16, 512),
}


def _paged_args(device, b, s, h, hkv, d, t, mp):
    one = SingleDeviceSharding(device)
    pages = b * mp + 1
    return (
        jax.ShapeDtypeStruct((b, s, h, d), BF16, sharding=one),
        jax.ShapeDtypeStruct((pages, t, hkv, d), BF16, sharding=one),
        jax.ShapeDtypeStruct((pages, t, hkv, d), BF16, sharding=one),
        jax.ShapeDtypeStruct((b, mp), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one),
    )


@pytest.mark.parametrize("shape", list(PAGED_SHAPES), ids=list(PAGED_SHAPES))
def test_paged_kernel_compiles_for_v5e_where_auto_picks_it(
        v5e, shape, monkeypatch):
    """Whatever ``auto`` would hand the kernel on a TPU, the compiler takes —
    under exactly the VMEM limit the dispatch budgets against."""
    monkeypatch.delenv("FTC_PAGED_VMEM_MB", raising=False)
    args = _paged_args(v5e[0], *PAGED_SHAPES[shape])
    assert paged_kernel_eligible(*args[:4])
    compiled = _paged_attention.lower(
        *args, interpret=False, vmem_limit_bytes=DEFAULT_VMEM_MB << 20,
    ).compile()
    assert _custom_calls(compiled) == 1


def test_paged_kernel_is_refused_past_its_vmem_limit(v5e, monkeypatch):
    """The limit handed to the compiler is a real one: the widest prefill
    does not compile under 8 MiB — and at that budget ``auto`` declines it,
    so the dispatch never asks for what the compiler would refuse."""
    args = _paged_args(v5e[0], *PAGED_SHAPES["d128-prefill-512"])
    monkeypatch.setenv("FTC_PAGED_VMEM_MB", "8")
    assert not paged_kernel_eligible(*args[:4])
    with pytest.raises(Exception, match="(?i)vmem"):
        _paged_attention.lower(
            *args, interpret=False, vmem_limit_bytes=8 << 20).compile()


# ---------------------------------------------------------------------------
# four chips: the flash kernel under the trainer's mesh
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "mesh_axes", [{"fsdp": 4}, {"fsdp": 2, "tp": 2}], ids=["fsdp4", "fsdp2-tp2"])
def test_sharded_flash_compiles_for_four_chips_without_gathering(
        v5e, mesh_axes, monkeypatch):
    """The Mosaic kernel cannot be partitioned by the compiler; under a mesh
    of several devices ``_flash_attention_on_mesh`` wraps it in shard_map —
    batch over dp/fsdp, heads over tp — so each chip runs it on its own
    shard: the kernels are in the program and no q/k/v all-gather is."""
    # the dispatch passes the kernel no argument: it compiles for the chip
    # (not the interpreter) where the backend says it is on one
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    b, s, h, hkv, d = FLASH_SHAPES["tinyllama-b8-s2048"]
    mesh = MeshSpec(**mesh_axes).build(v5e)
    heads = "tp" if mesh_axes.get("tp", 1) > 1 else None
    sharding = NamedSharding(mesh, P(("dp", "fsdp"), None, heads, None))
    q = jax.ShapeDtypeStruct((b, s, h, d), BF16, sharding=sharding)
    kv = jax.ShapeDtypeStruct((b, s, hkv, d), BF16, sharding=sharding)

    def loss(q, k, v):
        out = _flash_attention_on_mesh(q, k, v, None)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    with mesh, ring_mesh(mesh):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, kv, kv).compile()
    text = compiled.as_text()
    assert _custom_calls(compiled) == 3
    assert not re.search(r"\ball-gather(-start)?\(", text), (
        "q/k/v are gathered in front of the kernel")


def test_bare_flash_under_a_mesh_is_what_the_compiler_refuses(v5e):
    """Why the wrap exists: the same call left to the partitioner fails."""
    b, s, h, hkv, d = FLASH_SHAPES["tinyllama-b8-s2048"]
    mesh = MeshSpec(fsdp=4).build(v5e)
    sharding = NamedSharding(mesh, P(("dp", "fsdp"), None, None, None))
    q = jax.ShapeDtypeStruct((b, s, h, d), BF16, sharding=sharding)
    kv = jax.ShapeDtypeStruct((b, s, hkv, d), BF16, sharding=sharding)
    with pytest.raises(Exception, match="shard_map"):
        jax.jit(lambda q, k, v: flash_attention(q, k, v, interpret=False)
                ).lower(q, kv, kv).compile()


# ---------------------------------------------------------------------------
# int4 dequantisation: what exists in HBM around a quantised projection
# ---------------------------------------------------------------------------

#: (in, out) of the Mistral-7B MLP projections at the train cell's 8 x 2048
#: rows; until PR 25 each held 470 MB of float32 temporaries a product
QLORA_SHAPES = {"up-4096x14336": (4096, 14336), "down-14336x4096": (14336, 4096)}
_ARRAY = re.compile(r"\b(f32|bf16|s8|u8|s32|u32)\[([\d,]+)\]")


def _arrays(text: str):
    """(dtype, elements) of every instruction's result outside the fused
    computations: what a fusion computes inside itself (the v5e's VPU
    multiplies in f32) is never a buffer, what it returns is."""
    fused = set(re.findall(r"\bfusion\(.*?calls=(%[\w.\-]+)", text))
    inside = False
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.\-]+) \(.*\{$", line)
        if head:
            inside = head.group(1) in fused
        elif not inside and " = " in line:
            result = re.split(r"\s[\w\-]+\(", line.split(" = ", 1)[1], 1)[0]
            for m in _ARRAY.finditer(result):
                yield m.group(1), math.prod(map(int, m.group(2).split(",")))


@pytest.mark.parametrize("which", ["forward", "grad"])
@pytest.mark.parametrize("shape", list(QLORA_SHAPES), ids=list(QLORA_SHAPES))
def test_quantised_projection_holds_no_float32_kernel(v5e, shape, which):
    """The dequantised kernel reaches the base matmul as bf16 and never
    exists wider: no f32 array of even half a kernel's elements (values,
    interleave or broadcast scales), temporaries under a quarter of the 470
    MB the float32 round trip took, and the matmul's fusion reads a bf16
    ``[in, out]`` operand."""
    from finetune_controller_tpu.models.lora import LoRADense

    in_f, out_f = QLORA_SHAPES[shape]
    one = SingleDeviceSharding(v5e[0])
    layer = LoRADense(features=out_f, lora_rank=16, quantize_base=True,
                      dtype=BF16)
    x = jax.ShapeDtypeStruct((8, 2048, in_f), BF16, sharding=one)
    variables = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
        jax.eval_shape(lambda: layer.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8, in_f), BF16))))

    def loss(x, lora, params):
        y = layer.apply({"params": params, "lora": lora}, x)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    if which == "forward":
        compiled = jax.jit(layer.apply).lower(variables, x).compile()
    else:
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            x, variables["lora"], variables["params"]).compile()
    text = compiled.as_text()
    wide = [(t, n) for t, n in _arrays(text)
            if t in ("f32", "s32", "u32") and n >= in_f * out_f // 2]
    assert not wide, f"kernel-sized 4-byte arrays in the program: {set(wide)}"
    # the kernel is written once, as bf16, in the blocks' shape (a bitcast
    # of [in, out]), by an operation of the dequant_int4 scope
    assert re.search(
        rf"bf16\[{in_f // 64},64,{out_f}\]\S* fusion\(.*dequant_int4", text)
    # activations and results are out of this: x 134/470 MB, y 470/134 MB
    temp = compiled.memory_analysis().temp_size_in_bytes
    acts = 2 * 8 * 2048 * (in_f + out_f) if which == "grad" else 0
    if which == "forward":
        # the joined form (ISSUE 37: ``up`` at these rows, not ``down``) reads
        # x in the layout the 16-wide ``h`` takes, and this program's x is
        # its own parameter: the compiler writes it ONCE more in that layout.
        # Counted by its shape and operand, not granted as bytes: where no
        # such copy is written the bound below is the whole of it
        x_copies = re.findall(
            rf" = bf16\[8,2048,{in_f}\]\S* copy\(%args_0_", text)
        assert len(x_copies) == (shape == "up-4096x14336"), x_copies
        acts = len(x_copies) * 2 * 8 * 2048 * in_f
    assert temp - acts < 470e6 / 4, temp


# ---------------------------------------------------------------------------
# the proof a refactor rests on: a cell's step, lowered and hashed
# ---------------------------------------------------------------------------


def test_step_hash_follows_the_program_and_nothing_else(v5e):
    """``scripts/step_hash.py``: the tiny fixture's training cell lowered
    twice for the described v5e gives ONE hash, and a model with a layer more
    another — so an equal pair of hashes at two commits says the step's
    program did not change, and a changed program cannot hide."""
    import importlib.util
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(root))
    from benchmarks.harness.manifest import Manifest

    spec = importlib.util.spec_from_file_location(
        "step_hash", root / "scripts/step_hash.py")
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)

    manifest = Manifest(root / "tests/benchmarks/fixtures/BENCHMARK.tiny.json")
    cell = "tiny-qlora.train-tiny"
    first = tool.step_hash(manifest, cell)
    assert first == tool.step_hash(manifest, cell)
    assert jax.default_backend() == "cpu"      # the tool put it back
    config = manifest.config
    manifest.config = lambda name: dict(
        config(name), num_hidden_layers=config(name)["num_hidden_layers"] + 1)
    assert tool.step_hash(manifest, cell) != first
