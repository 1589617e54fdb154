"""Sampling utility: sanity-check a fine-tuned model by generating from it.

Two paths:

* :func:`generate` — the numerics ORACLE: each step re-runs the full forward
  over the sequence so far (no KV cache), O(n²) in generated length but
  exactly matching training numerics.
* :func:`cached_generate` — the practical path for 7B-class models: a
  static-length KV cache (fill the prompt once, then one-token decode
  steps), jitted fill + decode functions.  Verified token-for-token against
  the oracle in ``tests/test_generate.py``.

The reference has no equivalent surface at all (inference happens wherever
the promoted artifacts are deployed); PEFT/merged exports (``hf_export.py``)
remain the deployment path.

Works with any of the text families (Llama/Gemma/Qwen/Mixtral) and the
trainer's assembled variables::

    toks = greedy_generate(model, variables, prompt, max_new_tokens=32)
    toks = cached_generate(model, variables, prompt, max_new_tokens=256)
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Any

import jax
import jax.numpy as jnp


def _logits_fn(
    model: Any, variables: dict, tokens: jax.Array,
    pixels: jax.Array | None = None,
) -> jax.Array:
    """Last-position logits (B, V); MoE models sow aux state we discard;
    multimodal models take the image prefix via ``pixels``."""
    kw: dict = {}
    if pixels is not None:
        kw["pixels"] = pixels
    n_experts = getattr(getattr(model, "cfg", None), "n_experts", 0)
    if n_experts:
        logits, _ = model.apply(variables, tokens, mutable=("moe_aux",), **kw)
    else:
        logits = model.apply(variables, tokens, **kw)
    return logits[:, -1].astype(jnp.float32)


def generate(
    model: Any,
    variables: dict,
    prompt_tokens: jax.Array,      # (B, S) int32
    *,
    max_new_tokens: int = 32,
    temperature: float = 0.0,      # 0 = greedy
    top_k: int = 0,                # 0 = full distribution
    eos_id: int | None = None,
    rng: jax.Array | None = None,
    pixels: jax.Array | None = None,  # (B, H, W, 3) for multimodal models
) -> jax.Array:
    """Autoregressive sampling; returns (B, S + max_new_tokens) tokens.

    Rows that emit ``eos_id`` keep emitting it (a poor man's stop mask), so
    callers can trim on the first EOS per row. ``pixels`` feeds a multimodal
    model's image prefix (re-encoded every step — this is the oracle path;
    fine for sanity checks, not serving).
    """
    if getattr(model.cfg, "vision", None) is not None and pixels is None:
        # a multimodal model quietly falls back to text-only embeddings —
        # the sanity check would "work" without ever seeing the image
        raise ValueError("multimodal generation needs pixels=")
    tokens = jnp.asarray(prompt_tokens, jnp.int32)
    if tokens.ndim != 2:
        raise ValueError(f"prompt_tokens must be (B, S), got {tokens.shape}")
    if rng is None:
        rng = jax.random.PRNGKey(0)
    done = jnp.zeros((tokens.shape[0],), bool)

    for _ in range(max_new_tokens):
        logits = _logits_fn(model, variables, tokens, pixels)  # (B, V)
        nxt, rng = _sample(logits, temperature=temperature, top_k=top_k, rng=rng)
        if eos_id is not None:
            nxt = jnp.where(done, eos_id, nxt)
            done = done | (nxt == eos_id)
        tokens = jnp.concatenate([tokens, nxt[:, None].astype(jnp.int32)], axis=1)
    return tokens


def greedy_generate(model, variables, prompt_tokens, *, max_new_tokens=32,
                    eos_id=None):
    return generate(
        model, variables, prompt_tokens,
        max_new_tokens=max_new_tokens, temperature=0.0, eos_id=eos_id,
    )


#: jitted (fill, decode_step) pairs keyed by (model class, decode config) —
#: defined at module level so REPEATED cached_generate calls (the whole point
#: of a usable 7B sanity loop) reuse compilations instead of re-tracing.
#: Configs are frozen dataclasses, hence hashable.  A true bounded LRU (the
#: ``PixelCache`` shape from ``data/mm_loader.py``): evicting only the
#: least-recently-used entry means N+1 alternating configs thrash exactly one
#: slot, where the old clear-everything-at-capacity behavior re-traced ALL of
#: them forever.
_DECODE_FNS_MAX = 8
_DECODE_FNS_CACHE: OrderedDict = OrderedDict()


def _decode_fns(model_type, dcfg):
    key = (model_type, dcfg)
    cached = _DECODE_FNS_CACHE.get(key)
    if cached is not None:
        _DECODE_FNS_CACHE.move_to_end(key)
        return cached
    dmodel = model_type(cfg=dcfg)
    mutable = ("cache", *dcfg.sown_in_eval)

    # one pair serves both families: fill takes pixels variadically (the
    # multimodal [image; text] prefix — cached_generate passes it only for
    # LLaVA models), and both model classes accept positions by keyword
    # (decode steps use ABSOLUTE positions; the mm wrapper offsets nothing)
    @jax.jit
    def fill(variables, tokens, *pixels):
        logits, updated = dmodel.apply(
            variables, tokens, *pixels, deterministic=True, decode=True,
            mutable=mutable,
        )
        return logits[:, -1].astype(jnp.float32), updated["cache"]

    @jax.jit
    def decode_step(variables, token, pos):
        positions = jnp.broadcast_to(pos[None, None], (token.shape[0], 1))
        logits, updated = dmodel.apply(
            variables, token, positions=positions, deterministic=True,
            decode=True, mutable=mutable,
        )
        return logits[:, -1].astype(jnp.float32), updated["cache"]

    if len(_DECODE_FNS_CACHE) >= _DECODE_FNS_MAX:
        _DECODE_FNS_CACHE.popitem(last=False)
    _DECODE_FNS_CACHE[key] = (fill, decode_step)
    return fill, decode_step


def _sample(logits, *, temperature, top_k, rng):
    """Shared sampling rule — cached and uncached paths must pick the same
    token from the same logits."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1), rng
    scaled = logits / temperature
    if top_k:
        kth = jnp.sort(scaled, axis=-1)[:, -top_k][:, None]
        scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
    rng, sub = jax.random.split(rng)
    return jax.random.categorical(sub, scaled, axis=-1), rng


def cached_generate(
    model: Any,
    variables: dict,
    prompt_tokens: jax.Array,      # (B, S) int32
    *,
    max_new_tokens: int = 32,
    temperature: float = 0.0,
    top_k: int = 0,
    eos_id: int | None = None,
    rng: jax.Array | None = None,
    pixels: jax.Array | None = None,  # (B, H, W, 3) for multimodal models
) -> jax.Array:
    """KV-cached fill-then-decode sampling; same contract as :func:`generate`.

    The cache is a static ``prompt_len + max_new_tokens`` slots per layer
    (plus the ``n_patches`` image-prefix slots for multimodal models)
    (flax ``cache`` collection — ``models/llama.py`` ``_decode_attention``),
    so each new token costs one single-position forward instead of a full
    re-run: at 7B this is the difference between a usable post-finetune
    sanity generation and an hours-long one.  Remat is disabled (no gradients
    here) and attention runs the XLA path (flash kernels don't apply to
    single-token queries).

    MoE note: expert capacity scales with the live token count, so a
    one-token decode step is effectively dropless while a long-sequence
    recompute may drop tokens — cached and uncached logits can differ
    (cached is the *less* lossy of the two).  ``tests/test_generate.py``
    verifies equivalence under a dropless capacity.
    """
    multimodal = getattr(model.cfg, "vision", None) is not None
    if multimodal and pixels is None:
        raise ValueError("multimodal cached decode needs pixels=")
    if pixels is not None and not multimodal:
        # fail fast like generate() does — a silently dropped image would
        # return plausible text that never saw it
        raise ValueError("pixels= given but the model is text-only")
    tokens = jnp.asarray(prompt_tokens, jnp.int32)
    if tokens.ndim != 2:
        raise ValueError(f"prompt_tokens must be (B, S), got {tokens.shape}")
    if rng is None:
        rng = jax.random.PRNGKey(0)
    b, prompt_len = tokens.shape
    # the image prefix occupies cache slots before the text (multimodal)
    prefix = model.cfg.vision.n_patches if multimodal else 0
    cache_len = prefix + prompt_len + max_new_tokens
    dcfg = model.cfg.replace(
        remat=False, attention_impl="xla", max_seq_len=cache_len
    )
    fill, decode_step = _decode_fns(type(model), dcfg)
    if multimodal:
        logits, cache = fill(variables, tokens, jnp.asarray(pixels))
    else:
        logits, cache = fill(variables, tokens)
    done = jnp.zeros((b,), bool)
    for t in range(max_new_tokens):
        nxt, rng = _sample(logits, temperature=temperature, top_k=top_k, rng=rng)
        if eos_id is not None:
            nxt = jnp.where(done, eos_id, nxt)
            done = done | (nxt == eos_id)
        tokens = jnp.concatenate(
            [tokens, nxt[:, None].astype(jnp.int32)], axis=1)
        if t == max_new_tokens - 1:
            break
        logits, cache = decode_step(
            {**variables, "cache": cache},
            nxt[:, None].astype(jnp.int32),
            jnp.asarray(prefix + prompt_len + t, jnp.int32),
        )
    return tokens
