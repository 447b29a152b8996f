"""Model registry keyed by config ``model.name``."""

from __future__ import annotations

from typing import Any


def build_model(name: str, loss: str = "auto", dtype: str = "float32",
                **kwargs: Any):
    """Construct a model family from config.

    ``loss="auto"`` keeps each family's natural default (MLP → mse like
    the playground; transformer → next-token xent). The reference's
    degenerate trainer pairing is available as ``loss=prob_xent``
    (SURVEY.md §8 B5).
    """
    name = name.lower()
    if name == "mlp":
        from distributed_training_tpu.models.mlp import MLP
        loss_name = "mse" if loss == "auto" else loss
        return MLP(loss_name=loss_name, dtype=dtype, **kwargs)
    if name in ("transformer", "gpt2", "gpt2_125m", "gpt2_350m",
                "transformer_1b", "transformer_7b", "moe_transformer"):
        from distributed_training_tpu.models.transformer import (
            build_transformer,
        )
        return build_transformer(name, loss=loss, dtype=dtype, **kwargs)
    if name == "latent_moe":
        from distributed_training_tpu.models.latent_moe import (
            build_latent_moe,
        )
        return build_latent_moe(loss=loss, dtype=dtype, **kwargs)
    if name == "window_moe":
        from distributed_training_tpu.models.window_moe import (
            build_window_moe,
        )
        return build_window_moe(loss=loss, dtype=dtype, **kwargs)
    if name == "parallel_moe":
        from distributed_training_tpu.models.parallel_moe import (
            build_parallel_moe,
        )
        return build_parallel_moe(loss=loss, dtype=dtype, **kwargs)
    if name == "sparse_latent_moe":
        from distributed_training_tpu.models.sparse_latent_moe import (
            build_sparse_latent_moe,
        )
        return build_sparse_latent_moe(loss=loss, dtype=dtype, **kwargs)
    if name in ("resnet", "resnet18"):
        from distributed_training_tpu.models.resnet import ResNet
        return ResNet(dtype=dtype, **kwargs)
    raise ValueError(f"unknown model '{name}'")
