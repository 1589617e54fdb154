"""HF export round-trip tests: PEFT adapters and merged checkpoints are
verified by loading them back with ``peft``/``transformers`` and comparing
logits against our own forward — the strongest possible deployability check.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from finetune_controller_tpu.models.hf_export import (
    export_lora_adapter,
    export_merged_checkpoint,
)
from finetune_controller_tpu.models.hf_import import load_llama_params
from finetune_controller_tpu.models.llama import PRESETS, LlamaForCausalLM
from finetune_controller_tpu.models.lora import LoRAConfig

TINY = PRESETS["tiny-test"].replace(dtype=jnp.float32, lora=LoRAConfig(rank=4))


def _hf_base(tmp_path):
    import torch
    from transformers import LlamaConfig as HFConfig, LlamaForCausalLM as HFModel

    torch.manual_seed(0)
    hf_cfg = HFConfig(
        vocab_size=TINY.vocab_size, hidden_size=TINY.d_model,
        num_hidden_layers=TINY.n_layers, num_attention_heads=TINY.n_heads,
        num_key_value_heads=TINY.n_kv_heads, intermediate_size=TINY.d_ff,
        rms_norm_eps=TINY.rms_eps, rope_theta=TINY.rope_theta,
        max_position_embeddings=TINY.max_seq_len, tie_word_embeddings=False,
        attention_bias=False, mlp_bias=False,
    )
    model = HFModel(hf_cfg).eval()
    ckpt = tmp_path / "base"
    model.save_pretrained(str(ckpt), safe_serialization=True)
    return model, ckpt


def _random_lora(variables, seed=7):
    """Non-zero adapters (lora_b inits to zero → the delta would be trivial)."""
    leaves, treedef = jax.tree.flatten(variables["lora"])
    rng = np.random.default_rng(seed)
    new = [np.asarray(rng.normal(0, 0.05, l.shape), np.float32) for l in leaves]
    return jax.tree.unflatten(treedef, new)


def test_adapter_roundtrip_through_peft(tmp_path):
    torch = pytest.importorskip("torch")
    peft = pytest.importorskip("peft")
    hf_model, ckpt = _hf_base(tmp_path)

    params = load_llama_params(ckpt, TINY, dtype=jnp.float32)
    ours = LlamaForCausalLM(TINY)
    init_vars = ours.init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32)
    )
    lora = _random_lora(init_vars)

    adapter_dir = export_lora_adapter(
        TINY, lora, tmp_path / "adapter", base_model_name=str(ckpt)
    )

    peft_model = peft.PeftModel.from_pretrained(hf_model, str(adapter_dir)).eval()
    tokens = np.random.default_rng(0).integers(0, TINY.vocab_size, (2, 16))
    with torch.no_grad():
        ref = peft_model(torch.tensor(tokens)).logits.float().numpy()
    out = ours.apply(
        {"params": params, "lora": lora}, jnp.asarray(tokens, jnp.int32)
    )
    np.testing.assert_allclose(np.asarray(out), ref, atol=3e-4, rtol=1e-3)


def test_merged_checkpoint_roundtrip_through_transformers(tmp_path):
    torch = pytest.importorskip("torch")
    from transformers import LlamaForCausalLM as HFModel

    _, ckpt = _hf_base(tmp_path)
    params = load_llama_params(ckpt, TINY, dtype=jnp.float32)
    ours = LlamaForCausalLM(TINY)
    init_vars = ours.init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32)
    )
    lora = _random_lora(init_vars)

    merged_dir = export_merged_checkpoint(
        TINY, {"params": params, "lora": lora}, tmp_path / "merged"
    )
    reloaded = HFModel.from_pretrained(str(merged_dir)).eval()

    tokens = np.random.default_rng(1).integers(0, TINY.vocab_size, (2, 16))
    out = ours.apply(
        {"params": params, "lora": lora}, jnp.asarray(tokens, jnp.int32)
    )
    with torch.no_grad():
        ref = reloaded(torch.tensor(tokens)).logits.float().numpy()
    np.testing.assert_allclose(np.asarray(out), ref, atol=3e-4, rtol=1e-3)


def test_cli_run_ships_adapter(tmp_path):
    from finetune_controller_tpu.train import cli

    spec = {
        "job_id": "export-e2e",
        "model": {"preset": "tiny-test", "lora": {"rank": 2}},
        "training": {"mode": "lora", "total_steps": 3, "batch_size": 2,
                     "seq_len": 16, "log_every": 10, "checkpoint_every": 100,
                     "export_merged": True},
        "mesh": {"dp": 1, "fsdp": 1},
        "dataset": {"synthetic": {"task": "increment"}},
        "artifacts_dir": str(tmp_path / "artifacts"),
    }
    cli.run_job(spec)
    art = tmp_path / "artifacts"
    assert (art / "adapter" / "adapter_model.safetensors").exists()
    assert (art / "adapter" / "adapter_config.json").exists()
    assert (art / "merged" / "model.safetensors").exists()
    assert (art / "merged" / "config.json").exists()


def test_gemma_adapter_roundtrip_through_peft(tmp_path):
    """The PEFT adapter export is model-family-agnostic: a Gemma base
    (tied head, decoupled head_dim, GeGLU) round-trips through peft with
    matching logits."""
    torch = pytest.importorskip("torch")
    peft = pytest.importorskip("peft")
    from transformers import GemmaConfig, GemmaForCausalLM

    cfg = PRESETS["tiny-gemma-test"].replace(
        dtype=jnp.float32, lora=LoRAConfig(rank=4)
    )
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
    ckpt = tmp_path / "gemma-base"
    hf_model.save_pretrained(str(ckpt), safe_serialization=True)

    params = load_llama_params(ckpt, cfg, dtype=jnp.float32)
    ours = LlamaForCausalLM(cfg)
    init_vars = ours.init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32)
    )
    lora = _random_lora(init_vars)

    adapter_dir = export_lora_adapter(
        cfg, lora, tmp_path / "gemma-adapter", base_model_name=str(ckpt)
    )
    peft_model = peft.PeftModel.from_pretrained(hf_model, str(adapter_dir)).eval()
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 16))
    with torch.no_grad():
        ref = peft_model(torch.tensor(tokens)).logits.float().numpy()
    out = ours.apply(
        {"params": params, "lora": lora}, jnp.asarray(tokens, jnp.int32)
    )
    np.testing.assert_allclose(np.asarray(out), ref, atol=5e-4, rtol=1e-3)


def test_qwen2_merged_checkpoint_keeps_biases(tmp_path):
    """Merged export for a Qwen-2-family model must carry the q/k/v biases
    and declare the qwen2 architecture — silent bias loss would corrupt the
    deployed model's logits."""
    torch = pytest.importorskip("torch")
    from transformers import AutoModelForCausalLM, Qwen2Config, Qwen2ForCausalLM

    cfg = PRESETS["tiny-qwen-test"].replace(
        dtype=jnp.float32, lora=LoRAConfig(rank=4)
    )
    torch.manual_seed(0)
    hf_cfg = Qwen2Config(
        vocab_size=cfg.vocab_size, hidden_size=cfg.d_model,
        num_hidden_layers=cfg.n_layers, num_attention_heads=cfg.n_heads,
        num_key_value_heads=cfg.n_kv_heads, intermediate_size=cfg.d_ff,
        rms_norm_eps=cfg.rms_eps, rope_theta=cfg.rope_theta,
        max_position_embeddings=cfg.max_seq_len, tie_word_embeddings=False,
    )
    base = Qwen2ForCausalLM(hf_cfg).eval()
    ckpt = tmp_path / "qwen-base"
    base.save_pretrained(str(ckpt), safe_serialization=True)

    params = load_llama_params(ckpt, cfg, dtype=jnp.float32)
    ours = LlamaForCausalLM(cfg)
    init_vars = ours.init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32)
    )
    lora = _random_lora(init_vars)

    merged_dir = export_merged_checkpoint(
        cfg, {"params": params, "lora": lora}, tmp_path / "qwen-merged"
    )
    reloaded = AutoModelForCausalLM.from_pretrained(str(merged_dir)).eval()
    assert reloaded.config.model_type == "qwen2"

    tokens = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 16))
    out = ours.apply(
        {"params": params, "lora": lora}, jnp.asarray(tokens, jnp.int32)
    )
    with torch.no_grad():
        ref = reloaded(torch.tensor(tokens)).logits.float().numpy()
    np.testing.assert_allclose(np.asarray(out), ref, atol=5e-4, rtol=1e-3)


def test_gemma_merged_checkpoint_roundtrip(tmp_path):
    """Round-5 (VERDICT #4): Gemma merged export — the offset-form norms,
    GeGLU, embed scaling and tied head ride the exported config; transformers'
    GemmaForCausalLM reproduces our merged forward."""
    torch = pytest.importorskip("torch")
    from transformers import AutoModelForCausalLM

    cfg = PRESETS["tiny-gemma-test"].replace(
        dtype=jnp.float32, lora=LoRAConfig(rank=4)
    )
    ours = LlamaForCausalLM(cfg)
    variables = ours.init(
        {"params": jax.random.PRNGKey(4)}, jnp.zeros((1, 8), jnp.int32)
    )
    lora = _random_lora(variables)

    merged_dir = export_merged_checkpoint(
        cfg, {"params": variables["params"], "lora": lora},
        tmp_path / "gemma-merged",
    )
    reloaded = AutoModelForCausalLM.from_pretrained(str(merged_dir)).eval()
    assert reloaded.config.model_type == "gemma"

    tokens = np.random.default_rng(5).integers(0, cfg.vocab_size, (2, 16))
    out = ours.apply(
        {"params": variables["params"], "lora": lora},
        jnp.asarray(tokens, jnp.int32),
    )
    with torch.no_grad():
        ref = reloaded(torch.tensor(tokens)).logits.float().numpy()
    np.testing.assert_allclose(np.asarray(out), ref, atol=5e-4, rtol=1e-3)


def test_partial_gemma_semantics_still_refuse(tmp_path):
    """A hybrid config (embed scaling without the rest) matches no HF
    architecture — the exporter must refuse before writing any file."""
    cfg = TINY.replace(embed_scale=True)
    with pytest.raises(NotImplementedError, match="adapter"):
        export_merged_checkpoint(cfg, {"params": {}}, tmp_path / "nope")
    assert not (tmp_path / "nope").exists()


def test_mixtral_merged_checkpoint_roundtrip(tmp_path):
    """Round-5 (VERDICT #4): MoE merged export — stacked experts unstack to
    per-expert w1/w2/w3, the router exports as gate, attention LoRA merges;
    transformers' MixtralForCausalLM reproduces our forward (dropless
    capacity so our static-capacity routing matches HF's per-token top-k)."""
    torch = pytest.importorskip("torch")
    from transformers import AutoModelForCausalLM

    cfg = PRESETS["tiny-moe-test"].replace(
        dtype=jnp.float32, lora=LoRAConfig(rank=4),
        capacity_factor=float(PRESETS["tiny-moe-test"].n_experts),
    )
    ours = LlamaForCausalLM(cfg)
    variables = ours.init(
        {"params": jax.random.PRNGKey(6)}, jnp.zeros((1, 8), jnp.int32)
    )
    lora = _random_lora(variables)

    merged_dir = export_merged_checkpoint(
        cfg, {"params": variables["params"], "lora": lora},
        tmp_path / "moe-merged",
    )
    reloaded = AutoModelForCausalLM.from_pretrained(str(merged_dir)).eval()
    assert reloaded.config.model_type == "mixtral"
    assert reloaded.config.num_local_experts == cfg.n_experts
    assert reloaded.config.num_experts_per_tok == cfg.moe_top_k

    tokens = np.random.default_rng(7).integers(0, cfg.vocab_size, (2, 16))
    out, _ = ours.apply(
        {"params": variables["params"], "lora": lora},
        jnp.asarray(tokens, jnp.int32), mutable=("moe_aux",),
    )
    with torch.no_grad():
        ref = reloaded(torch.tensor(tokens)).logits.float().numpy()
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-3, rtol=1e-2)


def test_mixtral_int4_experts_merged_export(tmp_path):
    """MoE-QLoRA: int4-packed expert stacks dequantize on export; the written
    tensors equal the dequantized stacks our forward computes with."""
    from safetensors.numpy import load_file

    from finetune_controller_tpu.models.quant import dequantize_int4

    cfg = PRESETS["tiny-moe-test"].replace(
        dtype=jnp.float32, lora=LoRAConfig(rank=2), quantize_base=True,
    )
    ours = LlamaForCausalLM(cfg)
    variables = ours.init(
        {"params": jax.random.PRNGKey(8)}, jnp.zeros((1, 8), jnp.int32)
    )
    merged_dir = export_merged_checkpoint(
        cfg, {"params": variables["params"], "lora": variables["lora"]},
        tmp_path / "moe-int4-merged",
    )
    tensors = load_file(str(merged_dir / "model.safetensors"))
    moe = variables["params"]["blocks"]["block"]["moe"]
    want = np.asarray(dequantize_int4(
        moe["experts"]["gate_proj"]["kernel_packed"][0][1],
        moe["experts"]["gate_proj"]["kernel_scales"][0][1],
        dtype=jnp.float32,
    )).T
    got = tensors["model.layers.0.block_sparse_moe.experts.1.w1.weight"]
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_multihost_merged_export_reloads_base(tmp_path, monkeypatch):
    """Round-5 (VERDICT #4): on a multi-host mesh the frozen base is never
    gathered cross-host — rank 0 reloads it from the job's pretrained dir
    and merges the (already-gathered) adapter into it."""
    from finetune_controller_tpu.train.trainer import TrainConfig, Trainer

    ours = LlamaForCausalLM(TINY)
    base_vars = ours.init(
        {"params": jax.random.PRNGKey(9)}, jnp.zeros((1, 8), jnp.int32)
    )
    base_dir = export_merged_checkpoint(
        TINY, {"params": base_vars["params"]}, tmp_path / "base"
    )

    tcfg = TrainConfig(mode="lora", batch_size=2, seq_len=16, total_steps=1,
                       export_merged=True)
    tr = Trainer(TINY, tcfg)
    state = tr.init_state()
    state = tr.load_pretrained(state, str(base_dir))
    state = state.replace(trainable=_random_lora({"lora": state.trainable}))

    # simulate the 2-host view: process_count lies; the collective gather is
    # replaced by the single-host equivalent (the adapter IS addressable
    # here — what the fake must preserve is the code path that skips
    # gathering the frozen base and reloads it from disk instead)
    monkeypatch.setattr(
        Trainer, "state_to_host",
        lambda self, st, fields=("step", "trainable", "opt_state"): {
            f: jax.tree.map(lambda x: np.asarray(x), getattr(st, f))
            for f in fields
        },
    )
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    try:
        tr.export_artifacts(
            state, str(tmp_path / "art"), pretrained_dir=str(base_dir)
        )
    finally:
        monkeypatch.undo()

    from safetensors.numpy import load_file

    merged = load_file(str(tmp_path / "art" / "merged" / "model.safetensors"))
    base = load_file(str(base_dir / "model.safetensors"))
    lora = state.trainable["blocks"]["block"]["attn"]["q_proj"]
    scale = TINY.lora.alpha / TINY.lora.rank
    want = base["model.layers.0.self_attn.q_proj.weight"].T + scale * (
        np.asarray(lora["lora_a"][0]) @ np.asarray(lora["lora_b"][0])
    )
    got = merged["model.layers.0.self_attn.q_proj.weight"].T
    np.testing.assert_allclose(got, want, atol=1e-5)
    # the adapter shipped too (every LoRA run exports one)
    assert (tmp_path / "art" / "adapter" / "adapter_model.safetensors").exists()


def test_rope_scaled_merged_export_roundtrip(tmp_path):
    """A llama3-rope-scaled config exports its rope_scaling block, and the
    reloaded transformers model reproduces our scaled forward — proving the
    exported config.json reconstructs the same frequency schedule."""
    torch = pytest.importorskip("torch")
    import json as _json

    from transformers import LlamaForCausalLM as HFModel

    cfg = TINY.replace(
        tie_embeddings=True, rope_scaling_factor=8.0,
        rope_scaling_original_max_len=16, max_seq_len=128,
    )
    ours = LlamaForCausalLM(cfg)
    variables = ours.init(
        {"params": jax.random.PRNGKey(2)}, jnp.zeros((1, 8), jnp.int32)
    )
    lora = _random_lora(variables)

    merged_dir = export_merged_checkpoint(
        cfg, {"params": variables["params"], "lora": lora}, tmp_path / "m32"
    )
    written = _json.loads((merged_dir / "config.json").read_text())
    assert written["rope_scaling"] == {
        "rope_type": "llama3", "factor": 8.0, "low_freq_factor": 1.0,
        "high_freq_factor": 4.0, "original_max_position_embeddings": 16,
    }

    reloaded = HFModel.from_pretrained(str(merged_dir)).eval()
    tokens = np.random.default_rng(3).integers(0, cfg.vocab_size, (2, 48))
    out = ours.apply(
        {"params": variables["params"], "lora": lora},
        jnp.asarray(tokens, jnp.int32),
    )
    with torch.no_grad():
        ref = reloaded(torch.tensor(tokens)).logits.float().numpy()
    np.testing.assert_allclose(np.asarray(out), ref, atol=3e-4, rtol=1e-3)
