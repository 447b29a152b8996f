"""Model zoo: plain-pytree functional models with logical sharding axes.

The reference's "model zoo" is a single ``torch.nn.Linear`` built inline
(src/distributed_trainer.py:199; playground: ddp_script.py:16-23). The
framework generalizes to the BASELINE.json families — MLP, ResNet-18,
GPT-2-class transformers (125M → 7B) — as *functional* models: explicit
``init(rng) -> params`` pytrees and pure ``apply``/``loss`` functions.
No module framework in the hot path: params are transparent pytrees that
strategies annotate with logical axes and jit shards — the idiomatic
SPMD shape for XLA.

Served through ``serving/blocks.py``'s interface and not yet trained
here (``base.ApplyLM``): ``latent_moe`` (latent attention, experts held
in part), ``window_moe`` (window and global layers over two pools),
``sparse_latent_moe`` (a learned selection, two latent kinds) and
``parallel_moe`` (attention and experts parallel under one LayerNorm,
shared experts averaged, a tied head); the expert layer of all four is
``experts.py``.
"""

from distributed_training_tpu.models.base import Model  # noqa: F401
from distributed_training_tpu.models.registry import build_model  # noqa: F401
