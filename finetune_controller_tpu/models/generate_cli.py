"""Post-finetune sanity generation from a job's artifacts directory.

``python -m finetune_controller_tpu.models.generate_cli --artifacts DIR
--prompt "..."`` reconstructs the trained model exactly the way a resume
does — the job's ``resolved_config.json`` rebuilds the model/train configs,
``init_state`` (seeded) or ``model.weights_dir`` recreates the frozen base,
and the latest checkpoint restores the trained collection — then runs the
KV-cached decode path (``models/generate.py``).

The reference has no generation surface at all (inference happens wherever
promoted artifacts are deployed — SURVEY.md §2.2); this is the operator
command that makes the framework's post-finetune quality check reachable
without writing Python. Token IO uses the same tokenizer contract as the
data pipeline (``data/loader.py``): a HuggingFace ``tokenizers`` JSON file
when given, byte-level fallback otherwise.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _parse_token_list(raw: str) -> list[int]:
    try:
        return [int(t) for t in raw.replace(" ", "").split(",") if t]
    except ValueError:
        raise SystemExit(f"--prompt-tokens must be comma-separated ints, got {raw!r}")


def main(argv: list[str] | None = None) -> int:
    from ..platform import enable_compile_cache

    enable_compile_cache()

    p = argparse.ArgumentParser(
        prog="ftc-generate",
        description="Generate from a fine-tuned job's artifacts (sanity check)",
    )
    p.add_argument("--artifacts", required=True,
                   help="job artifacts dir (resolved_config.json + checkpoints/)")
    p.add_argument("--prompt", help="text prompt (tokenized per --tokenizer)")
    p.add_argument("--prompt-tokens",
                   help="comma-separated token ids (skips tokenization)")
    p.add_argument("--tokenizer",
                   help="HF tokenizers JSON file; default: byte-level fallback "
                        "(the data pipeline's convention)")
    p.add_argument("--max-new-tokens", type=int, default=64)
    p.add_argument("--temperature", type=float, default=0.0,
                   help="0 = greedy (default)")
    p.add_argument("--top-k", type=int, default=0, help="0 = full distribution")
    p.add_argument("--eos-id", type=int, default=None)
    p.add_argument("--seed", type=int, default=0, help="sampling seed")
    p.add_argument("--oracle", action="store_true",
                   help="uncached O(n^2) forward per token — the numerics "
                        "oracle; impractically slow past ~1B params")
    p.add_argument("--image",
                   help="image for multimodal jobs (path / data URI / "
                        "base64) — required for LLaVA-family artifacts")
    args = p.parse_args(argv)

    if (args.prompt is None) == (args.prompt_tokens is None):
        raise SystemExit("pass exactly one of --prompt or --prompt-tokens")

    spec_path = os.path.join(args.artifacts, "resolved_config.json")
    if not os.path.exists(spec_path):
        raise SystemExit(f"{spec_path} not found — is this a job artifacts dir?")
    with open(spec_path) as f:
        spec = json.load(f)

    from ..train.cli import build_model_config, build_train_config

    cfg = build_model_config(spec)
    multimodal = getattr(cfg, "vision", None) is not None
    if multimodal and not args.image:
        raise SystemExit(
            "this is a multimodal job's artifacts dir — pass --image "
            "(path / data URI / base64) for the image prefix"
        )
    if args.image and not multimodal:
        raise SystemExit("--image given but the job's model is text-only")

    # ---- tokenize ---------------------------------------------------------
    # tokenizer resolution: an explicit --tokenizer always loads (and, in
    # token-id mode, turns decode on); otherwise --prompt mode uses the
    # tokenizer the JOB trained with (dataset.tokenizer_file in
    # resolved_config.json) so the prompt lands in the vocabulary the model
    # actually saw, with the byte fallback only when the job itself trained
    # on the byte fallback. Plain token-id mode never touches the spec's
    # tokenizer (it may be a pod-local path): ids in, ids out.
    tok_file = args.tokenizer
    if tok_file is None and args.prompt is not None:
        tok_file = spec.get("dataset", {}).get("tokenizer_file")
    tokenizer = None
    if tok_file:
        from tokenizers import Tokenizer

        try:
            tokenizer = Tokenizer.from_file(tok_file)
        except Exception as e:
            raise SystemExit(
                f"could not load tokenizer {tok_file!r} ({e}) — pass "
                "--tokenizer with a local path, or --prompt-tokens to skip "
                "tokenization"
            )
    if args.prompt_tokens is not None:
        ids = _parse_token_list(args.prompt_tokens)
    elif tokenizer is not None:
        ids = tokenizer.encode(args.prompt).ids
    else:
        from ..data.loader import _byte_tokenize

        ids = _byte_tokenize(args.prompt)
    if not ids:
        raise SystemExit("empty prompt")
    bad = [i for i in ids if not 0 <= i < cfg.vocab_size]
    if bad:
        raise SystemExit(
            f"prompt ids {bad[:5]} out of range for vocab {cfg.vocab_size}"
        )

    # ---- rebuild the trained model (the resume recipe) --------------------
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..train.checkpoint import CheckpointManager
    from ..train.trainer import Trainer

    # prefer the job's own mesh (a model trained sharded over N chips may
    # only fit sharded); fall back to the single-device default when this
    # host can't form it (e.g. generating on a CPU box from a slice job)
    mesh = None
    try:
        from ..train.cli import build_mesh

        mesh = build_mesh(spec)
    except ValueError as e:
        # ValueError = this host cannot form the job's mesh (device-count
        # mismatch) — the expected case when generating on a CPU box from a
        # slice job. A typo'd mesh key (TypeError from MeshSpec(**...)) is a
        # genuine spec error and propagates.
        print(
            f"note: job mesh {spec.get('mesh', {})} unavailable here ({e}); "
            "using default single-device mesh — a model that only fits "
            "sharded will OOM",
            file=sys.stderr,
        )
    tcfg = build_train_config(spec)
    trainer = Trainer(cfg, tcfg, mesh=mesh)  # mesh=None -> trainer default
    state = trainer.init_state()
    weights_dir = spec.get("model", {}).get("weights_dir")
    if weights_dir and tcfg.mode != "full":
        # in full fine-tune the checkpoint holds every weight (and this CLI
        # requires a checkpoint) — reloading the safetensors base just to
        # overwrite it would waste minutes at 7B; same guard as the
        # trainer's own resume recipe
        state = trainer.load_pretrained(state, weights_dir)
    ckpt = CheckpointManager(os.path.join(args.artifacts, "checkpoints"))
    restored = ckpt.restore_latest(like=trainer.state_to_host(state))
    if restored is None:
        raise SystemExit(f"no checkpoint under {args.artifacts}/checkpoints")
    step, host = restored
    state = state.replace(
        trainable=jax.tree.map(jnp.asarray, host["trainable"])
    )

    from .generate import cached_generate, generate

    prefix = cfg.vision.n_patches if multimodal else 0
    if prefix + len(ids) + args.max_new_tokens > cfg.max_seq_len:
        print(
            f"warning: image prefix ({prefix}) + prompt ({len(ids)}) + "
            f"max_new_tokens ({args.max_new_tokens}) exceeds the model's "
            f"trained max_seq_len ({cfg.max_seq_len}) — RoPE positions past "
            "the trained range degrade quality",
            file=sys.stderr,
        )

    variables = trainer._assemble(state.frozen, state.trainable)
    prompt = jnp.asarray([ids], jnp.int32)
    gen_kw: dict = {}
    if multimodal:
        from ..data.images import preprocess_image

        gen_kw["pixels"] = jnp.asarray(preprocess_image(
            args.image, cfg.image_size,
            normalize=spec.get("dataset", {}).get("image_normalize", "clip"),
        ))[None]
    gen_fn = generate if args.oracle else cached_generate
    out = gen_fn(
        trainer.model, variables, prompt,
        max_new_tokens=args.max_new_tokens,
        temperature=args.temperature, top_k=args.top_k, eos_id=args.eos_id,
        rng=jax.random.PRNGKey(args.seed),
        **gen_kw,
    )
    new_ids = np.asarray(out)[0, len(ids):].tolist()
    if args.eos_id is not None and args.eos_id in new_ids:
        new_ids = new_ids[: new_ids.index(args.eos_id)]

    if tokenizer is not None:
        text = tokenizer.decode(new_ids)
    elif args.prompt is not None:
        text = bytes(i for i in new_ids if 0 <= i < 256).decode(
            "utf-8", errors="replace"
        )
    else:
        text = None  # token-id mode: ids in, ids out
    print(json.dumps({
        "checkpoint_step": step,
        "prompt_tokens": len(ids),
        "new_tokens": new_ids,
        "text": text,
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
