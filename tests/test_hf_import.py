"""Pretrained-weight import tests: HF Llama-family checkpoints → our tree.

Verified the strong way — numerically, against ``transformers``' own PyTorch
forward pass on the same (random) weights. The reference never loads weights
(user containers bring their own — SURVEY.md §2.2), so this surface is pure
greenfield and the conversion is exactly where silent corruption would hide.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from finetune_controller_tpu.models.hf_import import load_llama_params
from finetune_controller_tpu.models.llama import PRESETS, LlamaForCausalLM
from finetune_controller_tpu.models.lora import LoRAConfig
from finetune_controller_tpu.train.trainer import TrainConfig, Trainer

TINY = PRESETS["tiny-test"].replace(dtype=jnp.float32)


def _save_hf_llama(tmp_path, *, tie=False):
    import torch
    from transformers import LlamaConfig as HFConfig, LlamaForCausalLM as HFModel

    torch.manual_seed(0)
    hf_cfg = HFConfig(
        vocab_size=TINY.vocab_size, hidden_size=TINY.d_model,
        num_hidden_layers=TINY.n_layers, num_attention_heads=TINY.n_heads,
        num_key_value_heads=TINY.n_kv_heads,
        intermediate_size=TINY.d_ff, rms_norm_eps=TINY.rms_eps,
        rope_theta=TINY.rope_theta, max_position_embeddings=TINY.max_seq_len,
        tie_word_embeddings=tie, attention_bias=False, mlp_bias=False,
    )
    model = HFModel(hf_cfg).eval()
    ckpt = tmp_path / "hf"
    model.save_pretrained(str(ckpt), safe_serialization=True)
    return model, ckpt


def test_import_matches_transformers_forward(tmp_path):
    torch = pytest.importorskip("torch")
    hf_model, ckpt = _save_hf_llama(tmp_path)

    params = load_llama_params(ckpt, TINY, dtype=jnp.float32)
    ours = LlamaForCausalLM(TINY)

    tokens = np.random.default_rng(0).integers(0, TINY.vocab_size, (2, 16))
    with torch.no_grad():
        ref = hf_model(torch.tensor(tokens)).logits.float().numpy()
    out = ours.apply({"params": params}, jnp.asarray(tokens, jnp.int32))
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-4, rtol=1e-3)


def test_import_shape_mismatch_fails_loudly(tmp_path):
    pytest.importorskip("torch")
    _, ckpt = _save_hf_llama(tmp_path)
    wrong = TINY.replace(d_ff=64)
    with pytest.raises(ValueError):
        # conversion itself reads fine; the trainer-side adaptation catches
        # the shape mismatch. load_llama_params catches layer-count drift.
        trainer = Trainer(
            wrong.replace(lora=LoRAConfig(rank=2)),
            TrainConfig(mode="lora", total_steps=1, batch_size=2, seq_len=16),
        )
        state = trainer.init_state()
        trainer.load_pretrained(state, str(ckpt))


def test_trainer_loads_pretrained_and_trains(tmp_path):
    torch = pytest.importorskip("torch")
    hf_model, ckpt = _save_hf_llama(tmp_path)
    cfg = TINY.replace(lora=LoRAConfig(rank=4))
    trainer = Trainer(
        cfg, TrainConfig(mode="lora", total_steps=2, batch_size=2, seq_len=16,
                         learning_rate=1e-3),
    )
    state = trainer.init_state()
    state = trainer.load_pretrained(state, str(ckpt))

    # the loaded frozen base reproduces the HF forward through the trainer's
    # assembled model (LoRA deltas start at zero)
    tokens = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 16))
    variables = trainer._assemble(state.frozen, state.trainable)
    out = trainer.model.apply(variables, jnp.asarray(tokens, jnp.int32))
    with torch.no_grad():
        ref = hf_model(torch.tensor(tokens)).logits.float().numpy()
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-4, rtol=1e-3)

    # and it trains
    batch = {"tokens": tokens.astype(np.int32),
             "loss_mask": np.ones_like(tokens, np.float32)}
    state2, metrics = trainer.step(state, batch)
    assert np.isfinite(float(metrics["loss"]))


def test_qlora_pretrained_quantizes_on_load(tmp_path):
    pytest.importorskip("torch")
    _, ckpt = _save_hf_llama(tmp_path)
    cfg = TINY.replace(lora=LoRAConfig(rank=4), quantize_base=True, quant_block=32)
    trainer = Trainer(
        cfg, TrainConfig(mode="lora", total_steps=1, batch_size=2, seq_len=16),
    )
    state = trainer.init_state()
    state = trainer.load_pretrained(state, str(ckpt))
    blocks = state.frozen["params"]["blocks"]["block"]
    q = blocks["attn"]["q_proj"]
    assert q["kernel_packed"].dtype == jnp.uint8
    assert q["kernel_scales"].dtype == jnp.bfloat16
    # int4 round-trip stays close to the f32 original
    from finetune_controller_tpu.models.quant import dequantize_int4

    deq = dequantize_int4(q["kernel_packed"][0], q["kernel_scales"][0],
                          dtype=jnp.float32)
    orig = load_llama_params(ckpt, TINY, dtype=jnp.float32)
    ref = orig["blocks"]["block"]["attn"]["q_proj"]["kernel"][0]
    err = np.max(np.abs(np.asarray(deq) - np.asarray(ref)))
    assert err < np.max(np.abs(np.asarray(ref))) * 0.1

    batch = {"tokens": np.zeros((2, 16), np.int32),
             "loss_mask": np.ones((2, 16), np.float32)}
    _, metrics = trainer.step(state, batch)
    assert np.isfinite(float(metrics["loss"]))


def test_mixtral_moe_import_matches_transformers(tmp_path):
    torch = pytest.importorskip("torch")
    from transformers import MixtralConfig, MixtralForCausalLM

    moe = PRESETS["tiny-moe-test"].replace(
        dtype=jnp.float32, capacity_factor=100.0,  # no token dropping
    )
    torch.manual_seed(0)
    hf_cfg = MixtralConfig(
        vocab_size=moe.vocab_size, hidden_size=moe.d_model,
        num_hidden_layers=moe.n_layers, num_attention_heads=moe.n_heads,
        num_key_value_heads=moe.n_kv_heads, intermediate_size=moe.d_ff,
        num_local_experts=moe.n_experts, num_experts_per_tok=moe.moe_top_k,
        rms_norm_eps=moe.rms_eps, rope_theta=moe.rope_theta,
        max_position_embeddings=moe.max_seq_len, tie_word_embeddings=False,
        attention_bias=False,
    )
    hf_model = MixtralForCausalLM(hf_cfg).eval()
    ckpt = tmp_path / "hf-moe"
    hf_model.save_pretrained(str(ckpt), safe_serialization=True)

    params = load_llama_params(ckpt, moe, dtype=jnp.float32)
    ours = LlamaForCausalLM(moe)
    tokens = np.random.default_rng(0).integers(0, moe.vocab_size, (2, 16))
    with torch.no_grad():
        ref = hf_model(torch.tensor(tokens)).logits.float().numpy()
    out, _ = ours.apply(
        {"params": params}, jnp.asarray(tokens, jnp.int32), mutable=("moe_aux",)
    )
    np.testing.assert_allclose(np.asarray(out), ref, atol=5e-4, rtol=1e-2)


def test_gemma_import_matches_transformers(tmp_path):
    """Gemma family: head_dim decoupled from d_model/n_heads, GeGLU MLP,
    (1+w) RMSNorm, sqrt(d) embed scaling, tied head — all verified
    numerically against transformers' GemmaForCausalLM on shared weights."""
    torch = pytest.importorskip("torch")
    from transformers import GemmaConfig, GemmaForCausalLM

    cfg = PRESETS["tiny-gemma-test"].replace(dtype=jnp.float32)
    torch.manual_seed(0)
    hf_cfg = GemmaConfig(
        vocab_size=cfg.vocab_size, hidden_size=cfg.d_model,
        num_hidden_layers=cfg.n_layers, num_attention_heads=cfg.n_heads,
        num_key_value_heads=cfg.n_kv_heads, intermediate_size=cfg.d_ff,
        head_dim=cfg.head_dim, rms_norm_eps=cfg.rms_eps,
        rope_theta=cfg.rope_theta, max_position_embeddings=cfg.max_seq_len,
        hidden_activation="gelu_pytorch_tanh", tie_word_embeddings=True,
        attention_bias=False,
    )
    hf_model = GemmaForCausalLM(hf_cfg).eval()
    ckpt = tmp_path / "hf-gemma"
    hf_model.save_pretrained(str(ckpt), safe_serialization=True)

    params = load_llama_params(ckpt, cfg, dtype=jnp.float32)
    ours = LlamaForCausalLM(cfg)
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 16))
    with torch.no_grad():
        ref = hf_model(torch.tensor(tokens)).logits.float().numpy()
    out = ours.apply({"params": params}, jnp.asarray(tokens, jnp.int32))
    np.testing.assert_allclose(np.asarray(out), ref, atol=5e-4, rtol=1e-3)


def test_qwen2_import_matches_transformers(tmp_path):
    """Qwen-2 family: Llama-shaped decoder with q/k/v projection biases —
    verified numerically against transformers' Qwen2ForCausalLM."""
    torch = pytest.importorskip("torch")
    from transformers import Qwen2Config, Qwen2ForCausalLM

    cfg = PRESETS["tiny-qwen-test"].replace(dtype=jnp.float32)
    torch.manual_seed(0)
    hf_cfg = Qwen2Config(
        vocab_size=cfg.vocab_size, hidden_size=cfg.d_model,
        num_hidden_layers=cfg.n_layers, num_attention_heads=cfg.n_heads,
        num_key_value_heads=cfg.n_kv_heads, intermediate_size=cfg.d_ff,
        rms_norm_eps=cfg.rms_eps, rope_theta=cfg.rope_theta,
        max_position_embeddings=cfg.max_seq_len, tie_word_embeddings=False,
    )
    hf_model = Qwen2ForCausalLM(hf_cfg).eval()
    ckpt = tmp_path / "hf-qwen"
    hf_model.save_pretrained(str(ckpt), safe_serialization=True)

    params = load_llama_params(ckpt, cfg, dtype=jnp.float32)
    # biases actually landed (all-zero biases would hide a dropped mapping)
    assert "bias" in params["blocks"]["block"]["attn"]["q_proj"]
    ours = LlamaForCausalLM(cfg)
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 16))
    with torch.no_grad():
        ref = hf_model(torch.tensor(tokens)).logits.float().numpy()
    out = ours.apply({"params": params}, jnp.asarray(tokens, jnp.int32))
    np.testing.assert_allclose(np.asarray(out), ref, atol=5e-4, rtol=1e-3)


def test_llama32_rope_scaling_matches_transformers(tmp_path):
    """llama3-style RoPE scaling (Llama-3.1/3.2): our rope_inv_freqs and the
    scaled forward must match transformers' _compute_llama3_parameters path
    numerically. original_max_len is set BELOW the test seq len so the
    scaled long-wavelength band is actually exercised."""
    torch = pytest.importorskip("torch")
    from transformers import LlamaConfig as HFConfig, LlamaForCausalLM as HFModel

    cfg = TINY.replace(
        tie_embeddings=True,
        rope_scaling_factor=8.0,
        rope_scaling_original_max_len=16,
        max_seq_len=128,
    )
    torch.manual_seed(0)
    hf_cfg = HFConfig(
        vocab_size=cfg.vocab_size, hidden_size=cfg.d_model,
        num_hidden_layers=cfg.n_layers, num_attention_heads=cfg.n_heads,
        num_key_value_heads=cfg.n_kv_heads,
        intermediate_size=cfg.d_ff, rms_norm_eps=cfg.rms_eps,
        rope_theta=cfg.rope_theta, max_position_embeddings=cfg.max_seq_len,
        tie_word_embeddings=True, attention_bias=False, mlp_bias=False,
        rope_scaling={
            "rope_type": "llama3", "factor": 8.0,
            "low_freq_factor": 1.0, "high_freq_factor": 4.0,
            "original_max_position_embeddings": 16,
        },
    )
    hf_model = HFModel(hf_cfg).eval()
    ckpt = tmp_path / "hf-32"
    hf_model.save_pretrained(str(ckpt), safe_serialization=True)

    # frequency-level parity first (isolates the formula from the rest)
    from finetune_controller_tpu.models.llama import rope_inv_freqs

    ours_freqs = np.asarray(rope_inv_freqs(cfg))
    theirs = hf_model.model.rotary_emb.inv_freq.numpy()
    np.testing.assert_allclose(ours_freqs, theirs, rtol=1e-6)

    params = load_llama_params(ckpt, cfg, dtype=jnp.float32)
    ours = LlamaForCausalLM(cfg)
    # positions past original_max_len, so scaling wrongness would show
    tokens = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 48))
    with torch.no_grad():
        ref = hf_model(torch.tensor(tokens)).logits.float().numpy()
    out = ours.apply({"params": params}, jnp.asarray(tokens, jnp.int32))
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-4, rtol=1e-3)


def test_qlora_moe_experts_quantize_on_load(tmp_path):
    """Quantized MoE: a Mixtral checkpoint loads into a quantize_base
    config — the stacked (L, E, in, out) expert kernels quantize on the way
    in (the generic *_packed path in _adapt_loaded_params), dense
    projections too, and the quantized forward stays close to the f32
    oracle."""
    torch = pytest.importorskip("torch")
    from transformers import MixtralConfig, MixtralForCausalLM

    moe = PRESETS["tiny-moe-test"].replace(
        dtype=jnp.float32, capacity_factor=100.0,
        quantize_base=True, quant_block=32, lora=LoRAConfig(rank=4),
    )
    torch.manual_seed(0)
    hf_cfg = MixtralConfig(
        vocab_size=moe.vocab_size, hidden_size=moe.d_model,
        num_hidden_layers=moe.n_layers, num_attention_heads=moe.n_heads,
        num_key_value_heads=moe.n_kv_heads, intermediate_size=moe.d_ff,
        num_local_experts=moe.n_experts, num_experts_per_tok=moe.moe_top_k,
        rms_norm_eps=moe.rms_eps, rope_theta=moe.rope_theta,
        max_position_embeddings=moe.max_seq_len, tie_word_embeddings=False,
        attention_bias=False,
    )
    hf_model = MixtralForCausalLM(hf_cfg).eval()
    ckpt = tmp_path / "hf-moe-q"
    hf_model.save_pretrained(str(ckpt), safe_serialization=True)

    trainer = Trainer(
        moe, TrainConfig(mode="lora", total_steps=1, batch_size=2, seq_len=16),
    )
    state = trainer.init_state()
    state = trainer.load_pretrained(state, str(ckpt))

    blocks = state.frozen["params"]["blocks"]["block"]
    gate = blocks["moe"]["experts"]["gate_proj"]["kernel_packed"]
    assert gate.dtype == jnp.uint8
    # (L, E, in/2, out): expert axis preserved through the vmapped quantize
    assert gate.shape == (
        moe.n_layers, moe.n_experts, moe.d_model // 2, moe.d_ff,
    )

    # quantized forward ~= the f32 import (int4 on top of f32 weights);
    # compare through LoRA-free configs — the adapters start at identity and
    # the frozen params tree is what we're checking
    tokens = np.random.default_rng(0).integers(0, moe.vocab_size, (2, 16))
    nolora = moe.replace(lora=LoRAConfig())
    f32_params = load_llama_params(ckpt, nolora.replace(quantize_base=False),
                                   dtype=jnp.float32)
    oracle = LlamaForCausalLM(nolora.replace(quantize_base=False))
    ref, _ = oracle.apply(
        {"params": f32_params}, jnp.asarray(tokens, jnp.int32),
        mutable=("moe_aux",),
    )
    q_model = LlamaForCausalLM(nolora)
    out, _ = q_model.apply(
        {"params": state.frozen["params"]}, jnp.asarray(tokens, jnp.int32),
        mutable=("moe_aux",),
    )
    # the tight guarantee lives at the weight level: per-expert int4
    # round-trip within 10% of the per-block absmax bound
    from finetune_controller_tpu.models.quant import dequantize_int4

    deq = dequantize_int4(
        np.asarray(gate[0, 0]),
        np.asarray(blocks["moe"]["experts"]["gate_proj"]["kernel_scales"][0, 0]),
        dtype=jnp.float32,
    )
    orig = f32_params["blocks"]["block"]["moe"]["experts"]["gate_proj"]["kernel"][0, 0]
    werr = np.max(np.abs(np.asarray(deq) - np.asarray(orig)))
    assert werr < 0.1 * np.max(np.abs(np.asarray(orig))), werr
    # logits: int4 error compounds through layers — sanity bound only
    err = np.max(np.abs(np.asarray(out) - np.asarray(ref)))
    scale = np.max(np.abs(np.asarray(ref)))
    assert err < 0.25 * scale, (err, scale)

    batch = {"tokens": np.zeros((2, 16), np.int32),
             "loss_mask": np.ones((2, 16), np.float32)}
    _, metrics = trainer.step(state, batch)
    assert np.isfinite(float(metrics["loss"]))


def test_llava_import_matches_transformers(tmp_path):
    """Round-5 (VERDICT #3): a real LLaVA checkpoint — CLIP vision tower
    (class token, pre-norm, quick-gelu, penultimate-layer features),
    projector, and Llama language model — imports with exact logits parity
    against transformers' LlavaForConditionalGeneration."""
    torch = pytest.importorskip("torch")
    from transformers import (
        CLIPVisionConfig,
        LlamaConfig as HFLlamaConfig,
        LlavaConfig as HFLlavaConfig,
        LlavaForConditionalGeneration,
    )

    from finetune_controller_tpu.models.hf_import import load_llava_params
    from finetune_controller_tpu.models.llama import LlamaConfig
    from finetune_controller_tpu.models.multimodal import (
        LlavaConfig,
        LlavaForCausalLM,
        ViTConfig,
    )

    torch.manual_seed(0)
    vcfg = CLIPVisionConfig(
        hidden_size=32, intermediate_size=64, num_hidden_layers=3,
        num_attention_heads=2, image_size=16, patch_size=8,
        hidden_act="quick_gelu",
    )
    tcfg = HFLlamaConfig(
        vocab_size=256, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=128,
        max_position_embeddings=128, tie_word_embeddings=False,
    )
    hf_cfg = HFLlavaConfig(
        vision_config=vcfg, text_config=tcfg, image_token_index=255,
        projector_hidden_act="gelu", vision_feature_layer=-2,
        vision_feature_select_strategy="default",
    )
    hf_model = LlavaForConditionalGeneration(hf_cfg).eval()
    ckpt = tmp_path / "llava-tiny"
    hf_model.save_pretrained(str(ckpt), safe_serialization=True)

    n_patches = (16 // 8) ** 2
    text = [5, 6, 7, 8, 9, 10]
    input_ids = torch.tensor([[255] * n_patches + text])
    pixels = torch.tensor(
        np.random.default_rng(0).normal(0, 1, (1, 3, 16, 16)).astype(np.float32)
    )
    with torch.no_grad():
        ref = hf_model(
            input_ids=input_ids, pixel_values=pixels,
            attention_mask=torch.ones_like(input_ids),
        ).logits[:, n_patches:].float().numpy()

    cfg = LlavaConfig(
        vision=ViTConfig(
            image_size=16, patch_size=8, d_model=32, n_layers=3, n_heads=2,
            d_ff=64, cls_token=True, pre_norm=True, patch_bias=False,
            act="quick_gelu", feature_layer=-2, dtype=jnp.float32,
        ),
        text=LlamaConfig(
            vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=128, max_seq_len=128, rms_eps=1e-6, dtype=jnp.float32,
        ),
        projector_hidden=64,
    )
    params = load_llava_params(ckpt, cfg)
    ours = LlavaForCausalLM(cfg)
    out = ours.apply(
        {"params": params},
        jnp.asarray([text], jnp.int32),
        jnp.asarray(np.transpose(pixels.numpy(), (0, 2, 3, 1))),  # NCHW→NHWC
    )
    np.testing.assert_allclose(np.asarray(out), ref, atol=3e-5, rtol=1e-4)
