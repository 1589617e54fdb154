"""The plain reference against ``models/llama.py`` on the tiny presets'
shapes, int4 and bf16 — and a lower-precision case that must fail."""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.harness import program, weights  # noqa: E402
from benchmarks.harness.programs import llama as llama_program  # noqa: E402
from benchmarks.reference import model as ref  # noqa: E402
from benchmarks.reference import train as rtrain  # noqa: E402
from finetune_controller_tpu.models.llama import PRESETS, LlamaForCausalLM  # noqa: E402
from finetune_controller_tpu.models.quant import dequantize_int4  # noqa: E402

FIX = ROOT / "tests/benchmarks/fixtures/configs"
SEED = 2**31 + 4242          # the driver's seeds pass 32 signed bits
CASES = {"tiny-qlora": "tiny-test", "tiny-qwen-lora": "tiny-qwen-test"}


def load(name):
    return json.loads((FIX / f"{name}.json").read_text())


@pytest.fixture(scope="module", params=sorted(CASES))
def case(request):
    conf = load(request.param)
    cfg = llama_program.model_config(conf)
    model = LlamaForCausalLM(cfg)
    variables = program.seeded_serving_variables(model, SEED)
    arch = ref.Arch.from_config(conf)
    key = weights.root_key(SEED)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, 48), 0, conf["vocab_size"])
    return dict(name=request.param, conf=conf, cfg=cfg, model=model,
                variables=variables, arch=arch, key=key, tokens=tokens,
                lora=ref.init_lora(arch, key))


def test_fixture_shapes_are_the_presets(case):
    preset, cfg = PRESETS[CASES[case["name"]]], case["cfg"]
    for f in ("vocab_size", "d_model", "n_layers", "n_heads", "n_kv_heads",
              "d_ff", "rms_eps", "rope_theta", "attention_qkv_bias"):
        assert getattr(cfg, f) == getattr(preset, f), f


def test_float32_program_equals_reference(case):
    """In float32 at highest precision the two are the same function."""
    m32 = LlamaForCausalLM(case["cfg"].replace(dtype=jnp.float32))
    with jax.default_matmul_precision("highest"):
        got = m32.apply(case["variables"], case["tokens"])
    want = ref.make_forward(case["arch"])(
        case["key"], case["lora"], case["tokens"], jnp.arange(48))
    # tolerance: float32 rounding through two layers; a wrong rope
    # convention, mask, bias or scale is off by 1e-1 and more
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5


def test_bf16_program_is_near_and_fp8_is_far(case):
    got = case["model"].apply(case["variables"], case["tokens"])
    fwd = lambda q: ref.make_forward(case["arch"], q, "highest")(
        case["key"], case["lora"], case["tokens"], jnp.arange(48))
    want = fwd(ref.identity)
    near = float(jnp.max(jnp.abs(got - want)))
    far = float(jnp.max(jnp.abs(fwd(ref.to_fp8) - want)))
    # logits have unit variance; bf16 through two layers reads ~0.02 here
    # and fp8 ~0.17 (measured, PR 23): the limit sits between with room
    assert near < 0.06 < far, (near, far)


def test_seeded_leaves_regenerate_layer_by_layer(case):
    """What the program is handed is what the reference regenerates."""
    flat = {program.canonical(p): v for p, v in
            jax.tree_util.tree_flatten_with_path(case["variables"])[0]}
    arch, key = case["arch"], case["key"]
    w1 = ref.layer_weights(arch, key, 1)
    if arch.quantized:
        stored = dequantize_int4(
            flat["blocks/mlp/down_proj/kernel_packed"][1],
            flat["blocks/mlp/down_proj/kernel_scales"][1], dtype=jnp.float32)
    else:
        stored = flat["blocks/mlp/down_proj/kernel"][1].astype(jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(flat["blocks/attn/k_proj/bias"][1], np.float32),
            np.asarray(w1["attn/k_proj/bias"]))
    np.testing.assert_array_equal(np.asarray(stored), np.asarray(w1["mlp/down_proj"]))
    # float32 leaves may differ in the last bit (a fused multiply); stored
    # bf16 and int4 leaves are equal bit for bit
    np.testing.assert_allclose(
        np.asarray(flat["blocks/attn/q_proj/lora_b"]),
        np.asarray(case["lora"]["blocks/attn/q_proj/lora_b"]), rtol=1e-6)
    assert float(jnp.abs(case["lora"]["blocks/attn/q_proj/lora_b"]).max()) > 0


def test_one_fill_program_serves_every_seed(case):
    """The seed reaches the fill program as an argument: closed over, it is
    a constant of the program, which then compiles anew for every seed —
    inside every run's set-up."""
    shapes = jax.eval_shape(lambda: case["variables"])
    qb = case["cfg"].quant_block

    def lowered(seed):
        return jax.jit(lambda key: program.fill(shapes, key, qb)).lower(
            weights.root_key(seed)).as_text()

    assert lowered(1) == lowered(SEED)


def test_dequant_int4_by_hand():
    packed = jnp.asarray([[0x2F], [0x80]], jnp.uint8)   # rows: -1, 2, 0, -8
    scales = jnp.asarray([[0.5]], jnp.bfloat16)
    got = ref.dequant_int4(packed, scales, 4)
    np.testing.assert_array_equal(np.asarray(got[:, 0]), [-0.5, 1.0, 0.0, -4.0])


def test_reference_gradients_equal_autodiff_of_the_program_in_float32(case):
    """The hand-written layer-by-layer reverse pass gives jax.grad of the
    program's own float32 loss."""
    from finetune_controller_tpu.train.losses import next_token_loss

    tokens = jax.random.randint(jax.random.PRNGKey(5), (4, 24), 0, 256)
    m32 = LlamaForCausalLM(case["cfg"].replace(dtype=jnp.float32))
    variables = case["variables"]

    def loss(lora):
        logits = m32.apply({"params": variables["params"], "lora": lora}, tokens)
        return next_token_loss(logits, tokens)[0]

    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(loss)(variables["lora"])
    want = {program.canonical(p): v for p, v in
            jax.tree_util.tree_flatten_with_path(want)[0]}
    got_loss, got = rtrain.make_loss_and_grads(case["arch"], rows_per_block=2)(
        case["key"], case["lora"], tokens)
    assert abs(float(got_loss) - float(want_loss)) < 1e-5
    for name, g in got.items():
        scale = float(jnp.max(jnp.abs(want[name]))) + 1e-12
        assert float(jnp.max(jnp.abs(g - want[name]))) / scale < 1e-3, name


def test_reference_adamw_equals_optax():
    import optax

    params = {"a": jnp.linspace(-1, 1, 12).reshape(3, 4), "b": jnp.ones((5,))}
    grads = [{"a": jnp.sin(jnp.arange(12.0)).reshape(3, 4) * s,
              "b": jnp.cos(jnp.arange(5.0)) * s} for s in (3.0, 0.1, 1.0)]
    tx = optax.chain(optax.clip_by_global_norm(1.0),
                     optax.adamw(0.002, weight_decay=0.0))
    state, want = tx.init(params), params
    opt, got = rtrain.AdamW(0.002, clip_norm=1.0), params
    for g in grads:
        upd, state = tx.update(g, state, want)
        want = optax.apply_updates(want, upd)
        got, _ = opt.update(got, g)
    for k in params:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=1e-6, atol=1e-7)


# ---- attention in blocks of heads (ISSUE 26: the 8k cell's reference) ----------

@pytest.mark.parametrize("rows,heads,seq,want", [
    (2, 32, 2048, 32),      # the 2k cell: exactly the budget, unblocked
    (1, 32, 8192, 4),       # the 8k cell: 8.6 GB whole, four heads at a time
    (1, 32, 2304, 32),      # the serve check's longest padded length
    (2, 32, 8192, 2),
    (1, 28, 8192, 4),       # a divisor of the heads, never a ragged block
    (1, 7, 8192, 1),
    (1, 4, 32768, 1),       # never less than one head
    (4, 4, 32, 4),
])
def test_heads_per_block_from_shapes(rows, heads, seq, want):
    assert ref.heads_per_block(rows, heads, seq) == want


def test_attention_in_blocks_of_heads_is_the_same_function(monkeypatch):
    """Forward, and the gradients to the adapters and to the input, with the
    scores made two heads at a time against all four at once."""
    conf = load("tiny-qlora")
    arch, key = ref.Arch.from_config(conf), weights.root_key(SEED)
    lora_l = ref._layer_lora(ref.init_lora(arch, key), 1)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, 64), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(32), (2, 32))

    def passes():
        def f(ll, xx):
            with jax.default_matmul_precision("highest"):
                return ref.layer_forward(arch, ref.layer_weights(arch, key, 1),
                                         ll, xx, pos)
        y, vjp = jax.vjp(f, lora_l, x)
        return y, vjp(jnp.cos(y))

    whole = passes()
    monkeypatch.setattr(ref, "SCORE_BYTES", 2 * 2 * 32 * 32 * 4)
    assert ref.heads_per_block(2, 4, 32) == 2
    blocked = passes()
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(blocked)):
        scale = float(jnp.max(jnp.abs(a)))
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-6 * scale


def test_a_layer_outside_the_stack_is_drawn_under_its_own_name():
    conf = load("tiny-qlora")
    arch, key = ref.Arch.from_config(conf), weights.root_key(SEED)
    stacked = ref.layer_weights(arch, key, 0)
    again = ref.layer_weights(arch, key, 0, "blocks")
    outside = ref.layer_weights(arch, key, 0, "dense_0")
    assert set(stacked) == set(outside)
    for name in stacked:
        assert np.array_equal(stacked[name], again[name])
        assert not np.array_equal(stacked[name], outside[name]), name
    want = weights.leaf(key, "dense_0/attn_norm/scale", (64,), jnp.bfloat16,
                        stacked=False).astype(jnp.float32)
    assert np.array_equal(outside["attn_norm"], want)
