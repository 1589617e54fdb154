"""Loss functions. Next-token cross-entropy with a loss mask, f32 throughout."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def next_token_loss(
    logits: jax.Array, tokens: jax.Array, loss_mask: jax.Array | None = None
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Shifted cross-entropy.

    logits: (B, S, V) f32; tokens: (B, S) int; loss_mask: (B, S) — 1 where the
    *target* token counts (e.g. completion tokens in SFT).
    """
    with jax.named_scope("loss"):
        targets = tokens[:, 1:]
        logits = logits[:, :-1].astype(jnp.float32)
        if loss_mask is None:
            mask = jnp.ones_like(targets, jnp.float32)
        else:
            mask = loss_mask[:, 1:].astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        denom = jnp.maximum(mask.sum(), 1.0)
        loss = (nll * mask).sum() / denom
        acc = ((logits.argmax(-1) == targets) * mask).sum() / denom
        return loss, {"loss": loss, "accuracy": acc, "target_tokens": mask.sum()}
