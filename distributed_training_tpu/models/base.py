"""Model protocol + shared initializers."""

from __future__ import annotations

from typing import Any, Mapping, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np


@runtime_checkable
class Model(Protocol):
    """Functional model contract consumed by the Trainer.

    - ``init(rng)`` builds the param pytree (host-side shapes; sharding is
      applied by the trainer via the strategy's specs).
    - ``loss(params, batch, rng, train)`` returns ``(scalar_loss, metrics)``
      — models own their loss so the trainer stays model-agnostic (the
      reference hard-codes F.cross_entropy in the trainer,
      src/distributed_trainer.py:163; see SURVEY.md §8 B5 for why that
      pairing is degenerate).
    - ``logical_axes()`` mirrors the param pytree with per-dim logical
      names (``"embed"``, ``"mlp"``, ``"heads"``, ``"vocab"``, ...) that
      strategies map to mesh axes; ``None`` → shape heuristics.
    - ``flops_per_sample(seq_len?)`` powers MFU accounting.
    """

    def init(self, rng: jax.Array) -> Any: ...

    def loss(self, params: Any, batch: Mapping[str, jax.Array],
             rng: jax.Array, train: bool = True
             ) -> tuple[jax.Array, dict[str, jax.Array]]: ...

    def logical_axes(self) -> Any: ...

    def flops_per_sample(self) -> float: ...


def uniform_fan_in(rng: jax.Array, shape: tuple[int, ...], fan_in: int,
                   dtype=jnp.float32) -> jax.Array:
    """torch.nn.Linear default init: U(-1/sqrt(fan_in), 1/sqrt(fan_in)).

    Loss-curve parity with the reference requires matching this family
    (SURVEY.md §7 hard parts), not the distribution draw itself (different
    RNG streams) — curves are compared statistically, not bitwise.
    """
    bound = 1.0 / np.sqrt(max(fan_in, 1))
    return jax.random.uniform(rng, shape, dtype, -bound, bound)


def normal_init(rng: jax.Array, shape: tuple[int, ...], stddev: float,
                dtype=jnp.float32) -> jax.Array:
    return stddev * jax.random.normal(rng, shape, dtype)


def count_params(params: Any) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))


class ApplyLM:
    """Next-token ``loss`` and ``generate`` for a language model that
    has only a full forward ``apply(params, tokens (B, S)) -> logits
    (B, S, V)`` and a ``cfg.max_seq_len``: the plain path of the models
    that are served through the engine and not yet trained here
    (``models/latent_moe.py``, ``models/window_moe.py``)."""

    batch_keys: tuple[str, ...] = ("tokens",)

    def loss(self, params, batch, rng: jax.Array, train: bool = True):
        """Mean next-token cross-entropy of ``batch["tokens"]``
        (B, S + 1)."""
        tokens = batch["tokens"]
        logp = jax.nn.log_softmax(self.apply(params, tokens[:, :-1]))
        nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], -1)
        loss = jnp.mean(nll)
        return loss, {"loss": loss}

    def generate(self, params, prompt, max_new_tokens: int,
                 temperature: float = 0.0, top_k: int = 0,
                 rng: jax.Array | None = None):
        """``prompt`` (1, S) -> the new tokens (1, max_new_tokens), by
        the full forward over one padded row a token: the plain path
        (``generate.py`` serves greedy requests through the engine)."""
        total = prompt.shape[1] + max_new_tokens
        if total > self.cfg.max_seq_len:
            raise ValueError(f"{total} positions exceed max_seq_len "
                             f"{self.cfg.max_seq_len}")
        row = jnp.zeros((1, total), jnp.int32).at[:, :prompt.shape[1]
                                                  ].set(prompt)
        forward = jax.jit(self.apply)
        for n in range(prompt.shape[1], total):
            lg = forward(params, row)[0, n - 1]
            if temperature <= 0:
                tok = jnp.argmax(lg)
            else:
                lg = lg / temperature
                if top_k:
                    lg = jnp.where(lg < jax.lax.top_k(lg, top_k)[0][-1],
                                   -jnp.inf, lg)
                rng, key = jax.random.split(rng)
                tok = jax.random.categorical(key, lg)
            row = row.at[0, n].set(tok.astype(jnp.int32))
        return row[:, prompt.shape[1]:]
