"""Decoder-only transformer family (GPT-2 125M → 7B, optional MoE).

The BASELINE.json model targets (configs 3-5). Designed TPU-first:

- **stacked layers + ``lax.scan``**: per-layer params are stacked along a
  leading depth axis and the decoder runs as a scan — compile time is
  O(1) in depth, the standard XLA-friendly shape for deep stacks.
- **remat**: ``cfg.remat`` wraps the scanned block in ``jax.checkpoint``
  (recompute activations in backward), the HBM-for-FLOPs trade the 7B
  config requires.
- **mixed precision**: compute dtype bf16 with fp32 params/optimizer and
  fp32 softmax/logits — MXU-native.
- **logical sharding axes** on every param (``vocab``, ``embed``,
  ``mlp``, ``heads``, ``kv``, ``expert``) so DP/FSDP/TP/EP layouts are
  pure strategy decisions; the batch's sequence dim can additionally be
  sharded over ``sp`` (ring attention) without touching this file.
- **attention dispatch** via ops.attention (naive reference / Pallas
  flash / ring).

No counterpart exists in the reference repo (its models are Linear
stubs, src/distributed_trainer.py:199); interface parity is with the
framework's own Model protocol.
"""

from __future__ import annotations

import functools
import math
import os
import warnings
from dataclasses import dataclass

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from distributed_training_tpu.models.base import normal_init
from distributed_training_tpu.ops.attention import dot_product_attention


@dataclass
class TransformerConfig:
    vocab_size: int = 50257
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    n_kv_heads: int = 0          # 0 → = n_heads (MHA); < n_heads → GQA
    d_ff: int = 0                # 0 → 4 * d_model
    max_seq_len: int = 1024
    pos_encoding: str = "learned"  # "learned" (GPT-2) | "rope"
    dropout: float = 0.0
    tie_embeddings: bool = True
    dtype: str = "bfloat16"      # compute dtype
    param_dtype: str = "float32"
    remat: bool = False
    # "full": jax.checkpoint over the whole block — minimal memory,
    # recomputes everything incl. attention in the backward pass.
    # "selective": save attention outputs (small, B*S*D) and recompute
    # only the LN/MLP intermediates (the big B*S*4D buffers) — avoids
    # re-running the flash-attention kernel under remat, which costs
    # extra Pallas launches and compiles far more slowly.
    # "mlp": save every D-wide block tensor (MLP_POLICY_SAVED) so the
    # only recompute is the two (B, S, 4D) MLP hiddens — the single
    # largest residual class (measured on a v5e: six 1.12 GiB stacked
    # buffers at B=16, the whole OOM). Backward recompute = wi-matmul
    # + gelu (~+11% of fwd FLOPs) — the cheapest policy that unlocks
    # large batches.
    remat_policy: str = "selective"  # "full"|"selective"|"mlp"|"mlp_pre"
    attention_impl: str = "auto"
    # Sliding-window (Mistral-style) attention: query i attends keys
    # in [i − window + 1, i]. 0 = full causal. Flash kernels skip
    # out-of-band blocks (O(S·window) FLOPs); composes with every
    # impl: single-device and Ulysses apply the band over the full
    # local sequence; the ring maps it onto its per-block geometry in
    # GLOBAL positions (out-of-window blocks skipped, the boundary
    # block band-masked — the sequence-parallel option for windowed
    # GQA models whose head counts rule out Ulysses).
    attention_window: int = 0
    # Flash-kernel tile overrides (0 → ops/flash_attention defaults);
    # exposed so the bench sweep can tune them on real hardware.
    flash_block_q: int = 0
    flash_block_k: int = 0
    # lax.scan unroll over layers (1 = no unroll). Unrolling lets XLA
    # schedule/fuse across layer boundaries and shrink scan-stack
    # copies at the cost of compile time; a bench-sweep knob, numerics
    # are unchanged. Must divide n_layers (lax.scan requirement is
    # looser, but a ragged tail recompiles the remainder block).
    scan_unroll: int = 1
    pp_microbatches: int = 4      # microbatches when mesh pp > 1
    pp_schedule: str = "gpipe"    # "gpipe" | "interleaved"
    pp_virtual_stages: int = 2    # chunks/device when interleaved
    # MoE (expert-parallel): > 0 turns every MLP into a top-k routed
    # expert layer with a load-balancing aux loss.
    moe_num_experts: int = 0
    moe_top_k: int = 2
    moe_aux_weight: float = 0.01
    # "routed": capacity-bounded top-k dispatch (FLOPs ~independent of
    # the expert count at fixed top_k). "dense": every expert computes
    # every token, then masks — exact, O(E) FLOPs; kept as the
    # numerics reference and for tiny expert counts.
    moe_impl: str = "routed"
    moe_capacity_factor: float = 1.25
    moe_group_size: int = 1024    # tokens per dispatch group (cap)
    loss_name: str = "xent"
    # "fused": chunked custom-VJP xent head (ops/xent.py) — never
    # materializes (B, S, V) logits, the HBM hog that caps batch size.
    # "dense": materialize fp32 logits + log_softmax (reference-style).
    loss_impl: str = "fused"
    # Row budget per xent scan chunk (ops/xent.py DEFAULT_CHUNK_ROWS);
    # the live (rows, V) fp32 logits buffer holds ~this many rows.
    # A bench-sweep knob: bigger chunks = fewer scan steps / bigger
    # matmuls vs a larger live buffer.
    xent_chunk_rows: int = 2048

    def __post_init__(self):
        if self.n_kv_heads == 0:
            self.n_kv_heads = self.n_heads
        if self.d_ff == 0:
            self.d_ff = 4 * self.d_model
        if self.d_model % self.n_heads:
            raise ValueError("d_model must divide into n_heads")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must divide into n_kv_heads")
        if not 0.0 <= self.dropout < 1.0:
            raise ValueError(
                f"dropout must be in [0, 1), got {self.dropout}")
        if self.moe_num_experts > 0 and self.moe_capacity_factor <= 0:
            raise ValueError(
                f"moe_capacity_factor must be > 0, got "
                f"{self.moe_capacity_factor} (capacity 0 would silently "
                "drop every token)")
        if self.pp_schedule not in ("gpipe", "interleaved"):
            raise ValueError(
                f"unknown pp_schedule '{self.pp_schedule}' "
                "(expected 'gpipe' or 'interleaved')")
        if self.moe_impl not in ("routed", "dense"):
            raise ValueError(
                f"unknown moe_impl '{self.moe_impl}' "
                "(expected 'routed' or 'dense')")
        if self.loss_impl not in ("fused", "dense"):
            raise ValueError(
                f"unknown loss_impl '{self.loss_impl}' "
                "(expected 'fused' or 'dense')")
        if self.attention_window < 0:
            raise ValueError(
                f"attention_window must be >= 0, got "
                f"{self.attention_window}")
        if self.scan_unroll < 1 or self.n_layers % self.scan_unroll:
            raise ValueError(
                f"scan_unroll ({self.scan_unroll}) must be >= 1 and "
                f"divide n_layers ({self.n_layers})")
        if self.remat_policy not in ("full", "selective", "mlp",
                                     "mlp_pre"):
            # Validate here (not only in the remat branch of apply) so
            # a typo surfaces at construction even with remat=False or
            # on pp>1 meshes that bypass the single-stack remat path.
            raise ValueError(
                f"unknown remat_policy '{self.remat_policy}' "
                "(expected 'full', 'selective', 'mlp' or 'mlp_pre')")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


# Allow-list for remat_policy="mlp": every D-wide tag _block emits,
# PLUS the flash kernel's custom-VJP residuals (flash_out/flash_lse,
# named in ops/flash_attention._flash_bhsd_fwd) — without them the
# backward re-runs the forward attention kernel even though attn_out
# itself is saved (measured r4: 31.8 ms/step of rematted pallas_call
# at batch 32). The F-wide MLP hiddens are the only block
# intermediates NOT here — they are the recompute this policy trades
# for HBM.
FLASH_RESIDUAL_NAMES = ("flash_out", "flash_lse")
MLP_POLICY_SAVED = ("ln1_out", "q_rope", "k_rope", "v_proj",
                    "attn_out", "resid_attn", "ln2_out",
                    *FLASH_RESIDUAL_NAMES)
# remat_policy="mlp_pre" additionally saves the ONE F-wide pre-gelu
# tensor, eliminating the wi-matmul recompute that "mlp" pays every
# backward (2*B*S*D*F FLOPs/layer ~ 8% of the step at gpt2_125m
# shapes); the only remaining recompute is the elementwise gelu, whose
# VJP input the saved pre-activation provides directly. HBM cost:
# B*S*F*2 bytes/layer (192 MiB at batch 32, gpt2_125m) — the
# compile-level memory ladder (10.76 GiB @32 with "mlp" on a 16 GiB
# v5e) says it fits; "mlp" remains the default for tighter configs.
MLP_PRE_POLICY_SAVED = (*MLP_POLICY_SAVED, "mlp_pre")

# DTT_NO_BHSD=1 keeps attention in the BSHD einsum layout (disables
# the _bhsd_fast path) — the chip session A/Bs the layout fast path on
# real hardware. Read once at import so the knob can't flip between
# already-compiled shapes mid-process (jit cache keys don't include
# env vars); process-start-only, like DTT_FLASH_SPLIT_BWD.
_NO_BHSD = os.environ.get("DTT_NO_BHSD", "0") not in ("", "0")

# Reference hyperparameters for the BASELINE.json ladder. Vocab is
# GPT-2's 50257 padded to 50304 (next multiple of 128): lane-aligned
# for the MXU and divisible by any power-of-two tp axis — the standard
# padding trick; the tokenizer never emits the padding ids.
PRESETS: dict[str, dict] = {
    "gpt2_125m": dict(vocab_size=50304, d_model=768, n_layers=12,
                      n_heads=12, max_seq_len=1024),
    "gpt2_350m": dict(vocab_size=50304, d_model=1024, n_layers=24,
                      n_heads=16, max_seq_len=1024),
    "transformer_1b": dict(vocab_size=50304, d_model=2048, n_layers=24,
                           n_heads=16, max_seq_len=2048,
                           pos_encoding="rope", tie_embeddings=False),
    "transformer_7b": dict(vocab_size=50304, d_model=4096, n_layers=32,
                           n_heads=32, n_kv_heads=8, max_seq_len=2048,
                           pos_encoding="rope", tie_embeddings=False,
                           remat=True),
}


def _dropout(x: jax.Array, rng: jax.Array, rate: float) -> jax.Array:
    """Inverted dropout: zero with prob ``rate``, scale kept values by
    1/(1-rate) so the expectation is unchanged."""
    keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate),
                     jnp.zeros((), x.dtype)).astype(x.dtype)


def _rope(q: jax.Array, k: jax.Array, positions: jax.Array,
          layout: str = "bshd") -> tuple:
    """Rotary position embedding on (B, S, H, D) or (B, H, S, D) q/k
    (``layout``: the sequence axis is 1 or 2 respectively)."""
    D = q.shape[-1]
    half = D // 2
    freqs = 1.0 / (10000 ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions[:, None].astype(jnp.float32) * freqs[None, :]
    if layout == "bhsd":
        cos = jnp.cos(angles)[None, None, :, :]  # (1, 1, S, half)
        sin = jnp.sin(angles)[None, None, :, :]
    else:
        cos = jnp.cos(angles)[None, :, None, :]  # (1, S, 1, half)
        sin = jnp.sin(angles)[None, :, None, :]

    def rot(x):
        x1, x2 = x[..., :half], x[..., half:]
        xr = jnp.concatenate([x1 * cos - x2 * sin,
                              x1 * sin + x2 * cos], axis=-1)
        return xr.astype(x.dtype)

    return rot(q), rot(k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _gather_for_compute(w, fwd_sharding, bwd_sharding):
    """Asymmetric sharding constraint for FSDP weights: ``w`` is
    constrained ``fwd_sharding`` (replicated — the all-gather) in the
    forward, while the backward pins the cotangent to ``bwd_sharding``
    (the param's own layout) so gradient sync can lower to
    reduce-scatter. A plain with_sharding_constraint cannot express
    this: its VJP applies the SAME sharding to the cotangent."""
    return jax.lax.with_sharding_constraint(w, fwd_sharding)


def _gfc_fwd(w, fwd_sharding, bwd_sharding):
    return jax.lax.with_sharding_constraint(w, fwd_sharding), None


def _gfc_bwd(fwd_sharding, bwd_sharding, _res, g):
    return (jax.lax.with_sharding_constraint(g, bwd_sharding),)


_gather_for_compute.defvjp(_gfc_fwd, _gfc_bwd)


def _layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array
                ) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + 1e-5)
    return (y * scale + bias).astype(dtype)


class Transformer:
    """Functional decoder-only transformer (Model protocol)."""

    batch_keys: tuple[str, ...] = ("tokens",)

    def __init__(self, cfg: TransformerConfig):
        self.cfg = cfg
        self.mesh = None  # bound by the trainer for ring/ulysses
        # True while tracing the pipeline stage body (every mesh axis
        # is already manual there — _attention must not open a nested
        # shard_map).
        self._inside_pp = False
        self._compute_replicate = None  # bind_gather_for_compute
        self._compute_bwd_specs = {}

    def bind_mesh(self, mesh) -> None:
        """Give the model the device mesh (needed only for the
        sequence-parallel attention impls, ``'ring'`` and
        ``'ulysses'``: their shard_maps over the ``sp`` axis are
        constructed against a concrete mesh)."""
        self.mesh = mesh

    def bind_gather_for_compute(self, sharding,
                                bwd_specs: dict | None = None) -> None:
        """FSDP compute contract: constrain weights to ``sharding``
        (replicated) at their cast-to-compute-dtype sites, so XLA
        ALL-GATHERS each weight for its matmuls instead of running
        partial matmuls on weight shards and ALL-REDUCING the
        activations. Found by benchmarks/audit_collectives.py: with
        fsdp-sharded params and no constraint, the partitioner's cost
        model chose activation-shaped all-reduces — (B, S, V) logits,
        (B, S, H, D) qkv — which dwarf the parameter traffic FSDP is
        supposed to pay. The constraint sits INSIDE the layer scan on
        the per-layer slice (gathers are layer-by-layer, bf16, and
        transient) and on the embedding table / unembedding head at
        their single use sites.

        ``bwd_specs`` (path → NamedSharding of the PER-SLICE param
        layout, e.g. "attn/wq" → the stored spec minus the stacked
        layer dim) upgrades the constraint to an asymmetric custom
        VJP: replicated on forward (the gather), pinned to the param
        spec on backward — so each weight COTANGENT is born sharded
        and gradient sync can compile to reduce-scatter instead of
        all-reduce + slice. Without it, with_sharding_constraint's
        self-transposing VJP pins cotangents replicated and forces
        the 2x all-reduce (measured via audit_collectives)."""
        self._compute_replicate = sharding
        self._compute_bwd_specs = bwd_specs or {}

    def _w(self, p: jax.Array, dt, path: str | None = None
           ) -> jax.Array:
        """Cast a weight to compute dtype; under an FSDP gather-for-
        compute binding, also constrain it replicated (cast FIRST so
        the gather moves bf16, not fp32 masters). When the binding
        carries a per-leaf backward spec for ``path``, the asymmetric
        custom VJP is used so the weight's cotangent is born in the
        param layout (reduce-scatter-able) instead of replicated.
        Inside the pipeline's shard_map every mesh axis is manual — a
        named sharding constraint would be rejected at trace time —
        so the constraint is skipped there (stage params arrive
        already gathered per-stage by the pipeline's own specs)."""
        w = p.astype(dt)
        if self._compute_replicate is None or self._inside_pp:
            return w
        bwd = self._compute_bwd_specs.get(path) if path else None
        if bwd is None:
            return jax.lax.with_sharding_constraint(
                w, self._compute_replicate)
        return _gather_for_compute(w, self._compute_replicate, bwd)

    def _mesh_axis_sizes(self) -> dict:
        if self.mesh is None:
            return {}
        return dict(zip(self.mesh.axis_names, self.mesh.devices.shape))

    def _flash_active(self, seq_len: int) -> bool:
        """Will attention at ``seq_len`` run through the Pallas flash
        custom-VJP? Trace-time mirror of the dispatch in
        ops/attention.py, used to pick which attention-output name the
        remat allow-lists save.

        Mirrors ``flash_attention.supported()`` on the EFFECTIVE
        local-attention shapes rather than just the backend: a True
        here while dispatch demotes to naive per-shape
        saves residual names that never exist in the trace, and the
        backward silently recomputes all attention from the q/k/v tags
        — for ulysses that recompute includes the all-to-alls (always
        the case on CPU test meshes, where supported() is False).
        impl='flash' forces the kernel unconditionally at dispatch,
        and the ring names flash_out/flash_lse inside its own custom
        VJP for every inner path, so both resolve by impl alone."""
        from distributed_training_tpu.ops import flash_attention as fa
        c = self.cfg
        impl = c.attention_impl
        if impl == "naive":
            return False
        if impl == "ring":
            return True
        if impl in ("auto", "flash") and not self._tp_head_shardable():
            # Heads don't divide tp: the per-shard kernel cannot run
            # on a fractional head, so _attention demotes to naive —
            # the allow-lists must save attn_out accordingly.
            return False
        if impl == "flash":
            return True
        # 'auto' (single-device) and 'ulysses' (local attention over
        # the full sequence after the a2a; head counts shrink by
        # tp*sp, which preserves the H % Hkv ratio supported()
        # checks, so global counts predict the same answer).
        Dh = c.d_model // c.n_heads
        dt = jnp.dtype(c.dtype)
        q_s = jax.ShapeDtypeStruct((1, seq_len, c.n_heads, Dh), dt)
        kv = jax.ShapeDtypeStruct(
            (1, seq_len, c.n_kv_heads or c.n_heads, Dh), dt)
        return fa.supported(q_s, kv, kv, block_q=c.flash_block_q,
                            block_k=c.flash_block_k, layout="bshd")

    def _bhsd_fast(self, seq_len: int) -> bool:
        """Run the block's attention segment natively in (B, H, S, D)?

        The flash kernels work in BHSD; with the model's default BSHD
        einsum layout the wrapper transposes q/k/v in and the output
        back out every layer — and the backward recomputes those
        transposes from the saved BSHD residuals (measured r4:
        11.25 ms/step of standalone transposes at batch 32). When the
        single-device flash path is active, the qkv projections emit
        BHSD directly instead (XLA folds the output permutation into
        the matmul), rope and the residual tags follow, and no layout
        churn remains. Ring/Ulysses keep the BSHD contract — they
        shard the sequence axis and manage their own layouts.
        DTT_NO_BHSD=1 disables the fast path (chip A/B; read once at
        import — process-start-only, like DTT_FLASH_SPLIT_BWD)."""
        return (not _NO_BHSD
                and self.cfg.attention_impl in ("auto", "flash")
                and self._flash_active(seq_len))

    def _active_batch_axes(self) -> tuple:
        """Mesh batch axes with size > 1 (the data axes activations
        are actually sharded over) — single source for the pin
        constraint and the flash shard_map in_specs, which MUST agree
        (a mismatch is only caught by a topology compile)."""
        if self.mesh is None:
            return ()
        from distributed_training_tpu.runtime import BATCH_AXES
        sizes = self._mesh_axis_sizes()
        return tuple(a for a in BATCH_AXES if sizes.get(a, 1) > 1)

    def _tp_head_shardable(self) -> bool:
        """Can the flash kernel take a tp head shard? False when a
        bound mesh has tp > 1 that does not divide the (kv) head
        counts — the per-shard kernel cannot run on a fractional head,
        so dispatch demotes to naive and the remat allow-lists must
        save attn_out, not the flash residual names (the two MUST stay
        in sync: saving names that never exist makes the backward
        silently recompute all attention, the r4 31.8 ms/step bug
        class). Inside the pipeline's shard_map stage params are
        replicated over tp, so heads arrive whole."""
        if self.mesh is None or self._inside_pp:
            return True
        from distributed_training_tpu.runtime import AXIS_TP
        tp = self._mesh_axis_sizes().get(AXIS_TP, 1)
        if tp <= 1:
            return True
        c = self.cfg
        return not (c.n_heads % tp or (c.n_kv_heads or c.n_heads) % tp)

    def _pin_batch(self, x: jax.Array) -> jax.Array:
        """Constrain x's leading (batch) dim to the data axes; other
        dims unconstrained (sp layouts keep their sequence sharding).
        Applied OUTSIDE the jax.checkpoint boundary in the layer scan:
        the residual jax.checkpoint saves is its INPUT, and without
        the pin, sharding propagation through scan + the attention
        shard_map left the stacked per-layer residuals REPLICATED —
        at 7B/fsdp=16 an 8 GB bf16[L, B_global, S, D] buffer per
        device (caught by the device-less topology compile)."""
        if self.mesh is None or self._inside_pp:
            return x
        b_axes = self._active_batch_axes()
        if not b_axes:
            return x
        from jax.sharding import NamedSharding, PartitionSpec as P
        U = P.UNCONSTRAINED
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh,
                             P(b_axes, *([U] * (x.ndim - 1)))))

    def _gathered_table(self, tbl: jax.Array) -> jax.Array:
        """Constrain an embedding TABLE replicated at its lookup site.

        The token-embedding gather is the tp+sp+fsdp reshard cliff
        MULTICHIP_r05.json recorded: with the table model-sharded
        (vocab over tp, embed over fsdp) and the lookup's consumers
        demanding batch/seq-sharded activations, GSPMD cannot bridge
        the two shardings and falls back to "Involuntary full
        rematerialization" — replicating the ACTIVATION-scale gather
        result on every device (the SPMD001 finding analysis/ gates
        on; pinning the OUTPUT sharding does not help, the partitioner
        still computes the gather in the table's layout first).
        Replicating the TABLE instead makes the gather shard-local
        over batch/seq: one param-scale all-gather in compute dtype —
        the same gather-for-compute discipline the FSDP binding
        applies through ``_w`` (which already covers this table when
        bound, hence the ``_compute_replicate`` guard). Inside the
        pipeline's shard_map every axis is manual and stage params
        arrive gathered, so the constraint is skipped there."""
        if (self.mesh is None or self._inside_pp
                or self._compute_replicate is not None):
            return tbl
        from jax.sharding import NamedSharding, PartitionSpec
        return jax.lax.with_sharding_constraint(
            tbl, NamedSharding(self.mesh, PartitionSpec()))

    def _attention(self, q, k, v, layout: str = "bshd"):
        c = self.cfg
        # A window covering the whole (or more of the) sequence is
        # mathematically plain causal; normalize to 0 so the dispatch
        # keeps the fused/flash paths (windowed ring blocks run the
        # einsum reference) and skips no-op band masks. The comparison
        # is against the GLOBAL sequence length: inside the pipeline's
        # shard_map with sequence parallelism, q.shape[1] is the local
        # S/sp shard — comparing the window against THAT would turn a
        # valid window silently into full causal.
        S_total = q.shape[2] if layout == "bhsd" else q.shape[1]
        if self._inside_pp and c.attention_impl in ("ring", "ulysses"):
            from distributed_training_tpu.runtime import AXIS_SP
            S_total *= self._mesh_axis_sizes().get(AXIS_SP, 1)
        window = (c.attention_window
                  if 0 < c.attention_window < S_total else 0)
        if c.attention_impl in ("ring", "ulysses"):
            if layout != "bshd":
                raise ValueError(
                    "sequence-parallel attention takes BSHD inputs; "
                    "the BHSD fast path is single-device-flash only")
            if self.mesh is None:
                raise ValueError(
                    f"attention_impl='{c.attention_impl}' requires "
                    "bind_mesh(mesh) before tracing (the Trainer does "
                    "this)")
            if c.attention_impl == "ulysses":
                from distributed_training_tpu.parallel.ulysses import (
                    make_ulysses_attention, ulysses_attention,
                )
                from distributed_training_tpu.runtime import (
                    AXIS_SP, AXIS_TP)
                sizes = self._mesh_axis_sizes()
                tp = sizes.get(AXIS_TP, 1)
                sp = sizes.get(AXIS_SP, 1)
                if self._inside_pp:
                    # Already inside the pipeline's shard_map (every
                    # mesh axis is manual there): call the collective-
                    # level fn directly — a nested shard_map would
                    # throw. Stage params are replicated over tp
                    # (pipeline_spec), so heads arrive whole and only
                    # sp divides them.
                    if c.n_kv_heads % sp or c.n_heads % sp:
                        raise ValueError(
                            f"attention_impl='ulysses' under pp with "
                            f"sp={sp} needs n_heads ({c.n_heads}) and "
                            f"n_kv_heads ({c.n_kv_heads}) divisible "
                            "by sp")
                    return ulysses_attention(
                        q, k, v, axis_name=AXIS_SP, causal=True,
                        block_q=c.flash_block_q,
                        block_k=c.flash_block_k,
                        window=window)
                if c.n_kv_heads % (tp * sp) or c.n_heads % (tp * sp):
                    # Heads are the shard currency for BOTH tp and the
                    # Ulysses a2a — refuse up front with global counts
                    # (the in-shard_map check would report per-shard
                    # numbers).
                    raise ValueError(
                        f"attention_impl='ulysses' on tp={tp}, "
                        f"sp={sp} needs n_heads ({c.n_heads}) and "
                        f"n_kv_heads ({c.n_kv_heads}) divisible by "
                        "tp*sp; use attention_impl='ring' (no head "
                        "constraint)")
                head_ax = AXIS_TP if tp > 1 else None
                fn = make_ulysses_attention(self.mesh, causal=True,
                                            block_q=c.flash_block_q,
                                            block_k=c.flash_block_k,
                                            head_axis=head_ax,
                                            window=window)
                return fn(q, k, v)
            from distributed_training_tpu.parallel.ring_attention import (
                make_ring_attention, ring_attention,
            )
            # (only the ring reaches here — ulysses returned above).
            # attention_window composes: the ring skips blocks behind
            # the window and band-masks the boundary block in GLOBAL
            # positions (parallel/ring_attention.py) — this is the
            # sequence-parallel option for windowed GQA models whose
            # head counts rule out Ulysses (H % (tp·sp) != 0).
            from distributed_training_tpu.runtime import (
                AXIS_SP, AXIS_TP)
            if self._inside_pp:
                # Same pattern as the Ulysses branch: inside the
                # pipeline's shard_map the sp axis is already manual,
                # so call the collective-level ring directly (stage
                # params are replicated over tp there, so no head
                # axis applies).
                return ring_attention(q, k, v, axis_name=AXIS_SP,
                                      causal=True,
                                      block_q=c.flash_block_q,
                                      block_k=c.flash_block_k,
                                      window=window)
            sizes = self._mesh_axis_sizes()
            head_ax = AXIS_TP if sizes.get(AXIS_TP, 1) > 1 else None
            fn = make_ring_attention(self.mesh, causal=True,
                                     head_axis=head_ax,
                                     block_q=c.flash_block_q,
                                     block_k=c.flash_block_k,
                                     window=window)
            return fn(q, k, v)
        # Per-shard flash under a bound multi-device mesh must run
        # inside shard_map: the SPMD partitioner cannot partition a
        # Mosaic custom call ("Mosaic kernels cannot be automatically
        # partitioned"), so the plain-jit path that works single-chip
        # FAILS TO COMPILE on a real pod with dp/fsdp/tp > 1 — caught
        # by the device-less 7B fsdp=16 topology compile (the CPU
        # dryrun masked it: off-TPU the dispatch demotes to naive,
        # which the partitioner handles). Inside the pipeline's
        # shard_map every axis is already manual, so the direct call
        # is correct there.
        if (self.mesh is not None and not self._inside_pp
                and c.attention_impl in ("auto", "flash")
                and self._flash_active(S_total)):
            # _flash_active already returned False for the
            # tp-indivisible case (see _tp_head_shardable) — here the
            # kernel is definitely running, so wrap it in shard_map.
            from distributed_training_tpu.runtime import AXIS_TP
            sizes = self._mesh_axis_sizes()
            b_axes = self._active_batch_axes()
            head_ax = AXIS_TP if sizes.get(AXIS_TP, 1) > 1 else None
            if b_axes or head_ax:
                from jax import shard_map
                from jax.sharding import PartitionSpec as P
                if layout == "bhsd":
                    spec = P(b_axes or None, head_ax, None, None)
                else:
                    spec = P(b_axes or None, None, head_ax, None)
                fn = shard_map(
                    functools.partial(
                        dot_product_attention, causal=True,
                        impl=c.attention_impl,
                        block_q=c.flash_block_q,
                        block_k=c.flash_block_k,
                        window=window, layout=layout),
                    mesh=self.mesh, in_specs=(spec, spec, spec),
                    out_specs=spec, check_vma=False)
                return fn(q, k, v)
        impl = c.attention_impl
        if impl in ("auto", "flash") and not self._tp_head_shardable():
            # The kernel can't take a fractional tp head shard — run
            # the naive path, which the partitioner handles with
            # collectives (correct, slower; ring attention is the
            # fast option for such head counts). Matches
            # _flash_active, so the remat allow-lists save attn_out.
            from distributed_training_tpu.ops import (
                flash_attention as fa)
            fa.log_naive_choice(
                f"tp={self._mesh_axis_sizes().get('tp')} does not "
                f"divide n_heads={c.n_heads} / n_kv_heads="
                f"{c.n_kv_heads or c.n_heads}")
            impl = "naive"
        return dot_product_attention(q, k, v, causal=True,
                                     impl=impl,
                                     block_q=c.flash_block_q,
                                     block_k=c.flash_block_k,
                                     window=window, layout=layout)

    def serving_block(self):
        """This model's side of the serving engine's programs
        (serving/blocks.py)."""
        from distributed_training_tpu.serving.blocks import DenseBlock
        return DenseBlock(self.cfg)

    # -- init --------------------------------------------------------------

    def init(self, rng: jax.Array):
        c = self.cfg
        pdt = jnp.dtype(c.param_dtype)
        keys = iter(jax.random.split(rng, 16))
        std = 0.02
        L, D, F = c.n_layers, c.d_model, c.d_ff
        H, Hkv, hd = c.n_heads, c.n_kv_heads, c.head_dim

        def norm_pair():
            return {"scale": jnp.ones((L, D), pdt),
                    "bias": jnp.zeros((L, D), pdt)}

        params = {
            "tok_embed": normal_init(next(keys), (c.vocab_size, D), std,
                                     pdt),
            "ln1": norm_pair(),
            "ln2": norm_pair(),
            "attn": {
                "wq": normal_init(next(keys), (L, D, H, hd), std, pdt),
                "wk": normal_init(next(keys), (L, D, Hkv, hd), std, pdt),
                "wv": normal_init(next(keys), (L, D, Hkv, hd), std, pdt),
                # GPT-2-style depth-scaled residual-out init.
                "wo": normal_init(next(keys), (L, H, hd, D),
                                  std / (2 * L) ** 0.5, pdt),
            },
            "final_norm": {"scale": jnp.ones((D,), pdt),
                           "bias": jnp.zeros((D,), pdt)},
        }
        if c.moe_num_experts > 0:
            E = c.moe_num_experts
            params["mlp"] = {
                "router": normal_init(next(keys), (L, D, E), std, pdt),
                "wi": normal_init(next(keys), (L, E, D, F), std, pdt),
                "wo": normal_init(next(keys), (L, E, F, D),
                                  std / (2 * L) ** 0.5, pdt),
            }
        else:
            params["mlp"] = {
                "wi": normal_init(next(keys), (L, D, F), std, pdt),
                "bi": jnp.zeros((L, F), pdt),
                "wo": normal_init(next(keys), (L, F, D),
                                  std / (2 * L) ** 0.5, pdt),
                "bo": jnp.zeros((L, D), pdt),
            }
        if c.pos_encoding == "learned":
            params["pos_embed"] = normal_init(
                next(keys), (c.max_seq_len, D), std, pdt)
        if not c.tie_embeddings:
            params["lm_head"] = normal_init(
                next(keys), (D, c.vocab_size), std, pdt)
        return params

    # -- logical sharding axes --------------------------------------------

    def logical_axes(self):
        c = self.cfg
        axes = {
            "tok_embed": ("vocab", "embed"),
            "ln1": {"scale": (None, "embed"), "bias": (None, "embed")},
            "ln2": {"scale": (None, "embed"), "bias": (None, "embed")},
            "attn": {
                "wq": (None, "embed", "heads", None),
                "wk": (None, "embed", "kv", None),
                "wv": (None, "embed", "kv", None),
                "wo": (None, "heads", None, "embed"),
            },
            "final_norm": {"scale": ("embed",), "bias": ("embed",)},
        }
        if c.moe_num_experts > 0:
            axes["mlp"] = {
                "router": (None, "embed", None),
                "wi": (None, "expert", "embed", "mlp"),
                "wo": (None, "expert", "mlp", "embed"),
            }
        else:
            axes["mlp"] = {
                "wi": (None, "embed", "mlp"),
                "bi": (None, "mlp"),
                "wo": (None, "mlp", "embed"),
                "bo": (None, "embed"),
            }
        if c.pos_encoding == "learned":
            axes["pos_embed"] = (None, "embed")
        if not c.tie_embeddings:
            axes["lm_head"] = ("embed", "vocab")
        return axes

    # -- forward -----------------------------------------------------------

    def _block(self, x: jax.Array, layer: dict, positions: jax.Array,
               dropout_rng: jax.Array | None = None,
               return_kv: bool = False):
        """One decoder block. x: (B, S, D) in compute dtype.
        Returns (x, aux_loss) — plus the post-rope (k, v) when
        ``return_kv`` (generation prefill fills its cache from them).
        ``dropout_rng`` non-None enables residual-branch dropout at
        ``cfg.dropout`` (GPT-2's resid_pdrop)."""
        c = self.cfg
        dt = x.dtype
        drop = (functools.partial(_dropout, rate=c.dropout)
                if dropout_rng is not None else None)


        # checkpoint_name tags drive the remat policies (allow-list
        # semantics — save_only_these_names; the "anything except"
        # combinator is defeated by aliasing: it happily saves the
        # producing einsum's output, leaving the name a no-op).
        # "selective" saves only attn_out; "mlp" saves every D-wide
        # tag below and recomputes just the F-wide MLP hiddens.
        name = jax.ad_checkpoint.checkpoint_name

        h = _layer_norm(x, layer["ln1"]["scale"], layer["ln1"]["bias"])
        h = name(h, "ln1_out")
        # BHSD fast path (single-device flash): the qkv projections
        # emit the kernels' (B, H, S, D) layout directly — XLA folds
        # the output permutation into the matmul — so the flash
        # wrapper's per-layer q/k/v/out transposes (and their remat
        # recompute in backward) vanish. Everything else (ring,
        # ulysses, naive) keeps the BSHD contract.
        bhsd = (not return_kv) and self._bhsd_fast(x.shape[1])
        lay = "bhsk" if bhsd else "bshk"
        q = jnp.einsum(f"bsd,dhk->{lay}", h,
                       self._w(layer["attn"]["wq"], dt, "attn/wq"))
        k = jnp.einsum(f"bsd,dhk->{lay}", h,
                       self._w(layer["attn"]["wk"], dt, "attn/wk"))
        v = jnp.einsum(f"bsd,dhk->{lay}", h,
                       self._w(layer["attn"]["wv"], dt, "attn/wv"))
        if c.pos_encoding == "rope":
            q, k = _rope(q, k, positions,
                         layout="bhsd" if bhsd else "bshd")
        # Post-rope: saving these skips both the qkv einsums and the
        # rope rotation in backward (rope's VJP needs only cos/sin).
        q, k, v = name(q, "q_rope"), name(k, "k_rope"), name(v, "v_proj")
        attn = self._attention(q, k, v,
                               layout="bhsd" if bhsd else "bshd")
        attn = name(attn, "attn_out")
        attn_proj = jnp.einsum(f"{lay},hkd->bsd", attn,
                               self._w(layer["attn"]["wo"], dt,
                                       "attn/wo"))
        if drop is not None:
            attn_proj = drop(attn_proj,
                             rng=jax.random.fold_in(dropout_rng, 0))
        x = name(x + attn_proj, "resid_attn")

        h = _layer_norm(x, layer["ln2"]["scale"], layer["ln2"]["bias"])
        h = name(h, "ln2_out")
        if c.moe_num_experts > 0:
            mlp_out, aux = _moe_mlp(h, layer["mlp"], c, w=self._w)
        else:
            m = layer["mlp"]
            # Under the "mlp" policy's allow-list the two (B, S, 4D)
            # tensors here are the only recompute (wi-matmul + gelu in
            # backward); "mlp_pre" saves the tagged pre-gelu one and
            # recomputes just the elementwise gelu.
            u = jnp.einsum(
                "bsd,df->bsf", h, self._w(m["wi"], dt, "mlp/wi")
            ) + m["bi"].astype(dt)
            # Tag is a no-op unless the active policy allow-lists it
            # ("mlp_pre"); under "mlp" both (B, S, 4D) tensors stay
            # un-named and are the policy's deliberate recompute.
            u = name(u, "mlp_pre")
            u = jax.nn.gelu(u)
            mlp_out = jnp.einsum(
                "bsf,fd->bsd", u, self._w(m["wo"], dt, "mlp/wo")
            ) + m["bo"].astype(dt)
            aux = jnp.zeros((), jnp.float32)
        if drop is not None:
            mlp_out = drop(mlp_out,
                           rng=jax.random.fold_in(dropout_rng, 1))
        if return_kv:
            return x + mlp_out, aux, (k, v)
        return x + mlp_out, aux

    def _trunk(self, params, tokens: jax.Array,
               rng: jax.Array | None = None, train: bool = False
               ) -> tuple[jax.Array, jax.Array]:
        """tokens (B, S) → final-norm hidden states (B, S, D) in compute
        dtype, plus the MoE aux-loss scalar. Everything except the
        unembedding projection (the loss path feeds these straight into
        the fused xent head, ops/xent.py)."""
        c = self.cfg
        dt = jnp.dtype(c.dtype)
        B, S = tokens.shape
        dropping = bool(train and c.dropout > 0.0 and rng is not None)
        # Gather-for-compute (when bound): constrain the TABLE before
        # indexing, so a vocab-sharded embedding is all-gathered once
        # (param-scale, bf16) instead of the lookup emitting an
        # activation-scale (B, S, D) all-reduce of one-hot partials.
        # _gathered_table extends the same discipline to EVERY sharded
        # strategy: this lookup is the MULTICHIP_r05 reshard cliff
        # (SPMD001), fixed by constraining the table, not the output.
        x = self._gathered_table(
            self._w(params["tok_embed"], dt, "tok_embed"))[tokens]
        positions = jnp.arange(S)
        if c.pos_encoding == "learned":
            x = x + self._w(params["pos_embed"], dt,
                            "pos_embed")[:S]
        if dropping:  # GPT-2's embd_pdrop (fold_in needs non-negative)
            x = _dropout(x, rng=jax.random.fold_in(rng, 1_000_003),
                         rate=c.dropout)

        # Stack per-layer params for the scan: they already carry a
        # leading L dim.
        stacked = {k: params[k] for k in ("ln1", "ln2", "attn", "mlp")}

        pp = self._mesh_axis_sizes().get("pp", 1)

        # Per-layer dropout rngs derive from (global layer id,
        # microbatch index, data-shard index) so the draws are identical
        # on every schedule: plain scan uses mb=0/shard=0; the pipeline
        # threads the tick's microbatch through and folds the batch
        # shard (inside shard_map each device sees only its batch rows,
        # so without the shard term every dp/fsdp shard would draw the
        # SAME mask — correlated dropout across data shards). pp=N with
        # one microbatch and one data shard draws exactly the masks
        # pp=1 draws (tested in tests/test_pipeline.py). Carve-out:
        # under pp>1 WITH sp>1 the sp index is folded in too (each sp
        # member holds a sequence slice and draws its own local mask),
        # so masks are decorrelated along S but do NOT bit-match the
        # pp=1 global draw — same objective in distribution, different
        # realization; cross-layout trajectory parity with dropout>0
        # holds only at sp=1.
        rng7 = jax.random.fold_in(rng, 7) if dropping else None

        def body_with(mb_idx, shard_idx, pos=None):
            pos = positions if pos is None else pos

            def body(carry, inp):
                layer, lid = inp
                x, aux = carry
                lrng = None
                if dropping:
                    lrng = jax.random.fold_in(
                        jax.random.fold_in(
                            jax.random.fold_in(rng7, lid), mb_idx),
                        shard_idx)
                x, layer_aux = self._block(x, layer, pos,
                                           dropout_rng=lrng)
                return (x, aux + layer_aux), None
            return body

        layer_ids_all = jnp.arange(c.n_layers, dtype=jnp.int32)

        if pp > 1:
            # Pipeline wavefront over pp stages (parallel/pipeline.py):
            # each stage scans its local layer chunk per microbatch.
            # Both sequence-parallel impls compose: the stage body
            # calls the collective-level attention directly — see
            # _attention (inside the pipeline shard_map every mesh
            # axis is manual; a nested shard_map would throw).
            from distributed_training_tpu.parallel.pipeline import (
                pipeline_apply,
            )
            from distributed_training_tpu.runtime import (
                AXIS_SP, BATCH_AXES)

            sp = self._mesh_axis_sizes().get(AXIS_SP, 1)
            seq_parallel = (c.attention_impl in ("ring", "ulysses")
                            and sp > 1)
            batch_ax = tuple(
                a for a in BATCH_AXES
                if self._mesh_axis_sizes().get(a, 1) > 1)

            def stage_body(stage_params, layer_ids, xb, mb_idx):
                shard_idx = (jax.lax.axis_index(batch_ax) if batch_ax
                             else jnp.zeros((), jnp.int32))
                pos = None
                if seq_parallel:
                    # Fold the sp position in too: each sp member
                    # holds a different sequence slice, and without
                    # this term they would all draw the SAME local
                    # dropout mask (correlated dropout along S).
                    shard_idx = (shard_idx * sp
                                 + jax.lax.axis_index(AXIS_SP))
                    # And offset positions to the shard's slice of the
                    # global sequence (rope must see global indices).
                    s_loc = xb.shape[1]
                    pos = (jax.lax.axis_index(AXIS_SP) * s_loc
                           + jnp.arange(s_loc))
                # The sweep's scan_unroll knob applies here too; the
                # stage's local layer count (L/pp, or L/(v*pp) per
                # interleaved chunk) must divide it, else fall back
                # loudly rather than silently ignoring the knob.
                l_local = jax.tree.leaves(stage_params)[0].shape[0]
                unroll = c.scan_unroll
                if unroll > 1 and l_local % unroll:
                    warnings.warn(
                        f"scan_unroll={unroll} does not divide the "
                        f"pipeline stage's {l_local} local layers; "
                        "using unroll=1", stacklevel=2)
                    unroll = 1
                (xb, aux), _ = jax.lax.scan(
                    body_with(mb_idx, shard_idx, pos=pos),
                    (xb, jnp.zeros((), jnp.float32)),
                    (stage_params, layer_ids), unroll=unroll)
                return xb, aux

            # Largest microbatch count <= pp_microbatches such that the
            # per-microbatch batch B/M still splits evenly over the
            # data-sharded mesh axes (shard_map requires it).
            shards = math.prod(
                self._mesh_axis_sizes().get(a, 1) for a in BATCH_AXES)
            M = max(m for m in range(1, min(c.pp_microbatches, B) + 1)
                    if B % m == 0 and (B // m) % shards == 0)
            self._inside_pp = True
            try:
                x, aux = pipeline_apply(
                    stage_body, stacked, x, self.mesh,
                    num_microbatches=M, batch_axes=BATCH_AXES,
                    schedule=c.pp_schedule,
                    virtual_stages=c.pp_virtual_stages,
                    seq_axis=AXIS_SP if seq_parallel else None)
            finally:
                self._inside_pp = False
            # aux is an intensive (batch-mean) statistic summed over M
            # microbatches — renormalize so pp meshes optimize the same
            # objective as non-pp meshes.
            aux = aux / M
        else:
            block = body_with(jnp.zeros((), jnp.int32),
                              jnp.zeros((), jnp.int32))
            if c.remat:
                # Values validated in __post_init__; "full" → default
                # save-nothing policy. Allow-lists only: see the
                # checkpoint_name comment in _block. The attention
                # output exists under two names — attn_out (BSHD, the
                # model-side tag) and flash_out (BHSD, the kernel's
                # custom-VJP residual) — and saving both would store
                # the same values twice (~B*S*D*2 bytes/layer). Save
                # whichever layout the active backward actually
                # consumes: flash's VJP needs its own residuals (the
                # BSHD twin is then one cheap transpose away), the
                # naive path has no flash residuals at all.
                if self._flash_active(x.shape[1]):
                    attn_names = FLASH_RESIDUAL_NAMES
                else:
                    attn_names = ("attn_out",)
                if c.remat_policy == "selective":
                    policy = (jax.checkpoint_policies
                              .save_only_these_names(*attn_names))
                elif c.remat_policy in ("mlp", "mlp_pre"):
                    # The "mlp_pre" tag exists only in the dense MLP
                    # branch; with MoE active the policy degrades to
                    # "mlp" (an unmatched allow-list name is a silent
                    # no-op — keep the estimator in utils/memory.py in
                    # agreement).
                    base = (MLP_PRE_POLICY_SAVED
                            if (c.remat_policy == "mlp_pre"
                                and c.moe_num_experts == 0)
                            else MLP_POLICY_SAVED)
                    saved = tuple(
                        n for n in base
                        if n not in ("attn_out", *FLASH_RESIDUAL_NAMES)
                    ) + attn_names
                    policy = (jax.checkpoint_policies
                              .save_only_these_names(*saved))
                else:
                    policy = None
                block = jax.checkpoint(block, prevent_cse=False,
                                       policy=policy)

            def pinned_block(carry, inp, _block=block):
                # Batch-pin OUTSIDE the checkpoint boundary so the
                # residual jax.checkpoint saves (its input) is the
                # batch-sharded value — see _pin_batch.
                xc, acc = carry
                return _block((self._pin_batch(xc), acc), inp)

            (x, aux), _ = jax.lax.scan(
                pinned_block, (x, jnp.zeros((), jnp.float32)),
                (stacked, layer_ids_all), unroll=c.scan_unroll)
        aux = aux / c.n_layers  # mean load-balancing loss over layers

        x = _layer_norm(x, params["final_norm"]["scale"],
                        params["final_norm"]["bias"])
        return x, aux

    def _head(self, params) -> jax.Array:
        """Unembedding matrix (D, V) in param dtype."""
        return (params["tok_embed"].T if self.cfg.tie_embeddings
                else params["lm_head"])

    def apply(self, params, tokens: jax.Array,
              rng: jax.Array | None = None, train: bool = False
              ) -> tuple[jax.Array, jax.Array]:
        """tokens (B, S) int32 → logits (B, S, V) fp32, aux loss scalar.

        Dropout (``cfg.dropout > 0``) is active only when ``train`` and
        an ``rng`` is given; eval/inference is deterministic."""
        x, aux = self._trunk(params, tokens, rng=rng, train=train)
        logits = jnp.einsum("bsd,dv->bsv", x,
                            self._w(self._head(params), x.dtype,
                                    "head"))
        return logits.astype(jnp.float32), aux

    # -- loss --------------------------------------------------------------

    def loss(self, params, batch, rng: jax.Array, train: bool = True):
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        if self.cfg.loss_impl == "fused":
            from distributed_training_tpu.ops.xent import lm_cross_entropy
            x, aux = self._trunk(params, inputs, rng=rng, train=train)
            nll = lm_cross_entropy(
                x, self._w(self._head(params), x.dtype, "head"),
                targets, chunk_rows=self.cfg.xent_chunk_rows)
            # Negative target ids are masked pad positions (zero nll &
            # gradient inside the op) — average over real tokens only.
            valid = jnp.sum(targets >= 0)
            loss = jnp.sum(nll) / jnp.maximum(valid, 1)
        else:
            logits, aux = self.apply(params, inputs, rng=rng, train=train)
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(
                logp, jnp.maximum(targets, 0)[..., None],
                axis=-1)[..., 0]
            # Same masking contract as the fused path: negative target
            # ids are pad positions with zero loss contribution.
            nll = jnp.where(targets >= 0, nll, 0.0)
            valid = jnp.sum(targets >= 0)
            loss = jnp.sum(nll) / jnp.maximum(valid, 1)
        metrics = {"loss": loss, "perplexity": jnp.exp(loss)}
        if self.cfg.moe_num_experts > 0:
            loss = loss + self.cfg.moe_aux_weight * aux
            metrics["moe_aux"] = aux
        return loss, metrics

    # -- accounting --------------------------------------------------------

    def num_params(self) -> int:
        shapes = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        import numpy as np
        return int(sum(np.prod(s.shape) for s in jax.tree.leaves(shapes)))

    def flops_per_token(self, seq_len: int | None = None) -> float:
        """Fwd+bwd FLOPs/token: 6 * N_dense + attention quadratic term
        (causal → half; sliding window → the band's average width), the
        standard PaLM-appendix accounting."""
        c = self.cfg
        S = seq_len or c.max_seq_len
        N = self.num_params()
        if c.moe_num_experts > 0:
            # only top_k experts execute per token
            expert_p = (c.moe_num_experts * 2 * c.d_model * c.d_ff
                        * c.n_layers)
            N = N - expert_p + expert_p * c.moe_top_k // c.moe_num_experts
        # Average live keys per query: causal = (S+1)/2 ~ S/2; with a
        # window W, query i sees min(i+1, W) keys.
        if c.attention_window:
            W = min(c.attention_window, S)
            avg_keys = W - W * (W - 1) / (2 * S)
        else:
            avg_keys = S * 0.5
        attn = 12 * c.n_layers * c.d_model * avg_keys
        return 6.0 * N + attn

    def flops_per_sample(self) -> float:
        # Trainer feeds (seq_len + 1) token rows; model consumes seq_len.
        S = self.cfg.max_seq_len
        return self.flops_per_token(S) * S

    # -- generation --------------------------------------------------------

    def _decode_cache_len(self, max_len: int) -> int:
        """KV-cache sequence capacity for decode: a sliding window
        needs only the last ``window`` positions (the rolling buffer —
        O(window) decode memory instead of O(max_len)); full causal
        keeps every position."""
        c = self.cfg
        if c.attention_window:
            return min(max_len, c.attention_window)
        return max_len

    def _attend_cache(self, q, k_cache, v_cache, pos):
        """Single-position attention: q (B, 1, H, hd) against the cache
        (B, Sm, Hkv, hd). GQA-grouped like ops.attention (hkv-major
        head order).

        The cache is a MODULAR ring over absolute positions: position
        p lives in slot ``p % Sm``, so slot s currently holds absolute
        position ``pos − ((pos − s) mod Sm)`` — for a full-length
        cache (Sm > pos) that reduces to s itself for s ≤ pos and a
        negative (masked) value beyond it, and for a window-sized
        rolling buffer it is the newest ≤ pos occupant of the slot.
        One mask therefore covers both layouts: visible iff the slot's
        absolute position is ≥ 0 (ever written) and inside the
        attention window when one is set."""
        c = self.cfg
        group = c.n_heads // c.n_kv_heads
        B, Sm = k_cache.shape[0], k_cache.shape[1]
        qg = q[:, 0].reshape(B, c.n_kv_heads, group, c.head_dim)
        logits = jnp.einsum(
            "bhgd,bshd->bhgs", qg, k_cache,
            preferred_element_type=jnp.float32) * c.head_dim ** -0.5
        idx = jnp.arange(Sm)[None, None, None, :]
        abs_pos = pos - ((pos - idx) % Sm)
        mask = abs_pos >= 0
        if c.attention_window:
            mask = jnp.logical_and(
                mask, abs_pos >= pos - (c.attention_window - 1))
        logits = jnp.where(mask, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhgs,bshd->bhgd",
                         probs.astype(v_cache.dtype), v_cache,
                         preferred_element_type=jnp.float32)
        return out.reshape(B, 1, c.n_heads, c.head_dim).astype(q.dtype)

    def _block_decode(self, x, layer, k_cache, v_cache, pos):
        """One block for one new token at position ``pos`` (B, 1, D),
        reading/extending the layer's KV cache."""
        c = self.cfg
        dt = x.dtype
        h = _layer_norm(x, layer["ln1"]["scale"], layer["ln1"]["bias"])
        q = jnp.einsum("bsd,dhk->bshk", h, layer["attn"]["wq"].astype(dt))
        k = jnp.einsum("bsd,dhk->bshk", h, layer["attn"]["wk"].astype(dt))
        v = jnp.einsum("bsd,dhk->bshk", h, layer["attn"]["wv"].astype(dt))
        if c.pos_encoding == "rope":
            q, k = _rope(q, k, jnp.full((1,), pos, jnp.int32))
        # Modular slot: identity for a full-length cache, ring-wrap
        # for the window-sized rolling buffer (see _attend_cache).
        slot = pos % k_cache.shape[1]
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k.astype(k_cache.dtype), (0, slot, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v.astype(v_cache.dtype), (0, slot, 0, 0))
        attn = self._attend_cache(q, k_cache, v_cache, pos)
        x = x + jnp.einsum("bshk,hkd->bsd", attn,
                           layer["attn"]["wo"].astype(dt))
        h = _layer_norm(x, layer["ln2"]["scale"], layer["ln2"]["bias"])
        if c.moe_num_experts > 0:
            mlp_out, _ = _moe_mlp(h, layer["mlp"], c)
        else:
            m = layer["mlp"]
            u = jax.nn.gelu(jnp.einsum("bsd,df->bsf", h,
                                       m["wi"].astype(dt))
                            + m["bi"].astype(dt))
            mlp_out = jnp.einsum(
                "bsf,fd->bsd", u, m["wo"].astype(dt)
            ) + m["bo"].astype(dt)
        return x + mlp_out, k_cache, v_cache

    def _lm_head(self, params, x_last):
        """(B, D) hidden → (B, V) fp32 logits (final LN + head)."""
        x = _layer_norm(x_last, params["final_norm"]["scale"],
                        params["final_norm"]["bias"])
        head = (params["tok_embed"].T if self.cfg.tie_embeddings
                else params["lm_head"])
        return jnp.einsum("bd,dv->bv", x,
                          head.astype(x.dtype)).astype(jnp.float32)

    def prefill(self, params, tokens, max_len: int):
        """Run the prompt (B, P) through the stack, returning per-layer
        KV caches plus fp32 logits for the next position:
        (k_cache (L,B,Sm,Hkv,hd), v_cache, logits), where
        ``Sm = _decode_cache_len(max_len)`` — ``max_len`` for full
        causal, the window size for windowed models (the rolling
        ring-slot layout _attend_cache reads; position p lives in slot
        ``p % Sm``)."""
        c = self.cfg
        dt = jnp.dtype(c.dtype)
        B, P = tokens.shape
        x = params["tok_embed"][tokens].astype(dt)
        positions = jnp.arange(P)
        if c.pos_encoding == "learned":
            x = x + params["pos_embed"][:P].astype(dt)
        stacked = {k: params[k] for k in ("ln1", "ln2", "attn", "mlp")}

        def body(carry, layer):
            x, = carry
            x, _aux, kv = self._block(x, layer, positions,
                                      return_kv=True)
            return (x,), kv

        (x,), (ks, vs) = jax.lax.scan(body, (x,), stacked)
        # ks: (L, B, P, Hkv, hd) → caches of capacity Sm. Windowed
        # decode keeps only the last min(P, Sm) prompt positions, each
        # in its modular slot p % Sm (slots hit at most once — the kept
        # positions are consecutive), matching _attend_cache's ring
        # layout; a full-length cache gets the identity layout (slot p
        # == p) plus zero padding.
        Sm = self._decode_cache_len(max_len)
        keep = min(P, Sm)
        zshape = (c.n_layers, B, Sm) + ks.shape[3:]
        slots = (jnp.arange(P - keep, P) % Sm).astype(jnp.int32)
        k_cache = jnp.zeros(zshape, dt).at[:, :, slots].set(
            ks[:, :, P - keep:].astype(dt))
        v_cache = jnp.zeros(zshape, dt).at[:, :, slots].set(
            vs[:, :, P - keep:].astype(dt))
        return k_cache, v_cache, self._lm_head(params, x[:, -1])

    def generate(self, params, prompt, max_new_tokens: int,
                 temperature: float = 0.0, top_k: int = 0,
                 rng: jax.Array | None = None,
                 max_len: int | None = None) -> jax.Array:
        """Autoregressive sampling: (B, P) int32 prompt → (B,
        max_new_tokens) continuations. ``temperature == 0`` is greedy;
        otherwise categorical sampling, optionally truncated to the
        ``top_k`` most likely tokens. The whole loop (prefill + cached
        decode scan) is jitted; no data-dependent Python control flow.
        """
        c = self.cfg
        B, P = prompt.shape
        max_len = max_len or c.max_seq_len
        if P + max_new_tokens > max_len:
            raise ValueError(
                f"prompt ({P}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_len ({max_len})")
        if temperature > 0 and rng is None:
            raise ValueError("sampling (temperature > 0) needs an rng")
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        # The compiled loop is cached per trace signature — a bare
        # jax.jit(run) here would retrace and recompile on EVERY call.
        cache_key = (P, max_new_tokens, temperature, top_k, max_len)
        if not hasattr(self, "_generate_cache"):
            self._generate_cache: dict = {}
        cached = self._generate_cache.get(cache_key)
        if cached is not None:
            return cached(params, prompt, rng)
        stacked_keys = ("ln1", "ln2", "attn", "mlp")

        def sample(logits, key):
            if temperature <= 0:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            logits = logits / temperature
            if top_k:
                kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
                logits = jnp.where(logits < kth, -jnp.inf, logits)
            return jax.random.categorical(key, logits).astype(jnp.int32)

        def run(params, prompt, rng):
            k_cache, v_cache, logits = self.prefill(params, prompt,
                                                    max_len)
            stacked = {k: params[k] for k in stacked_keys}
            rng0, rng_loop = jax.random.split(rng)
            tok0 = sample(logits, rng0)

            def step(carry, i):
                k_cache, v_cache, tok, key = carry
                pos = P + i
                x = params["tok_embed"][tok][:, None, :].astype(
                    jnp.dtype(c.dtype))
                if c.pos_encoding == "learned":
                    x = x + params["pos_embed"][pos][
                        None, None, :
                    ].astype(x.dtype)

                def layer_body(xc, inp):
                    layer, kc, vc = inp
                    x, = xc
                    x, kc, vc = self._block_decode(x, layer, kc, vc,
                                                   pos)
                    return (x,), (kc, vc)

                (x,), (k_cache, v_cache) = jax.lax.scan(
                    layer_body, (x,), (stacked, k_cache, v_cache))
                logits = self._lm_head(params, x[:, 0])
                key, sub = jax.random.split(key)
                nxt = sample(logits, sub)
                return (k_cache, v_cache, nxt, key), nxt

            n_scan = max_new_tokens - 1
            if n_scan > 0:
                (_, _, _, _), rest = jax.lax.scan(
                    step, (k_cache, v_cache, tok0, rng_loop),
                    jnp.arange(n_scan))
                return jnp.concatenate(
                    [tok0[:, None], rest.T.astype(jnp.int32)], axis=1)
            return tok0[:, None]

        fn = jax.jit(run)
        self._generate_cache[cache_key] = fn
        return fn(params, prompt, rng)


def _cast_w(p, dt, path=None):
    """Default weight consumer for the MoE helpers: plain cast. The
    train path passes ``Transformer._w`` instead so expert/router
    weights get the FSDP gather-for-compute constraint (without it,
    fsdp-sharded expert weights re-trigger the activation-all-reduce
    pathology benchmarks/audit_collectives.py exposed)."""
    return p.astype(dt)


def _topk_by_argmax(p: jax.Array, k: int):
    """Top-k along the last axis via k iterations of argmax + mask.

    Identical selection, ordering AND gradient to ``jax.lax.top_k``
    (descending values, first-index tie-break; cotangent scattered
    only to the selected indices), but it lowers to plain reduces and
    gathers over the UNSHARDED expert axis — lax.top_k becomes a TopK
    custom-call the SPMD partitioner cannot partition, so it
    all-gathered the full (B, G, gs, E) routing probs across data-
    parallel shards before routing (the one activation-scale
    collective in the otherwise-clean MoE communication contract;
    now pinned to zero by
    tests/test_benchmarks.py::test_fsdp_step_has_no_activation_scale_collectives).
    k is the tiny moe_top_k (1-2 in practice), so the unrolled loop
    costs k cheap (…, E) passes. Values are re-gathered from the
    ORIGINAL tensor via take_along_axis — jnp.max's VJP would split
    the cotangent across tied maxima (e.g. a freshly-initialized
    router where every expert ties), leaking gradient onto unselected
    experts."""
    orig = p
    vals, idxs = [], []
    for _ in range(k):
        i = jnp.argmax(p, axis=-1)
        vals.append(jnp.take_along_axis(orig, i[..., None],
                                        axis=-1)[..., 0])
        idxs.append(i)
        p = jnp.where(jax.nn.one_hot(i, p.shape[-1], dtype=jnp.bool_),
                      -jnp.inf, p)
    return jnp.stack(vals, -1), jnp.stack(idxs, -1)


def _moe_router(h: jax.Array, mlp: dict, c: TransformerConfig,
                valid: jax.Array | None = None, w=_cast_w):
    """Shared routing head: normalized top-k weights/indices + the
    Switch/GShard load-balancing aux (E · Σ_e mean_prob_e · mean_frac_e),
    computed pre-capacity so the balance signal sees dropped tokens.

    ``valid`` (same shape as h minus the feature dim) masks padding
    rows: they are removed from the assignment one-hots (so they claim
    no capacity slots) and from the aux statistics."""
    dt = h.dtype
    E, k = c.moe_num_experts, c.moe_top_k
    gates = jnp.einsum("...d,de->...e", h,
                       w(mlp["router"], dt, "mlp/router"))
    probs = jax.nn.softmax(gates.astype(jnp.float32), axis=-1)
    topv, topi = _topk_by_argmax(probs, k)            # (..., k)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(topi, E, dtype=jnp.float32)  # (..., k, E)
    red = tuple(range(probs.ndim - 1))
    if valid is None:
        frac = jnp.mean(jnp.sum(onehot, axis=-2), axis=red)  # (E,)
        mean_prob = jnp.mean(probs, axis=red)                # (E,)
    else:
        v = valid.astype(jnp.float32)
        onehot = onehot * v[..., None, None]
        n = jnp.maximum(jnp.sum(v), 1.0)
        frac = jnp.sum(onehot, axis=red + (onehot.ndim - 2,)) / n
        mean_prob = jnp.sum(probs * v[..., None], axis=red) / n
    aux = E * jnp.sum(frac * mean_prob)
    return topv, onehot, aux


def _moe_mlp_dense(h, mlp, c: TransformerConfig, w=_cast_w):
    """Reference dispatch: every expert computes every token, masked
    combine. Exact but O(E) FLOPs — numerics baseline for the routed
    path and the sane choice for very small E."""
    dt = h.dtype
    topv, onehot, aux = _moe_router(h, mlp, c, w=w)
    combine = jnp.einsum("bsk,bske->bse", topv, onehot)  # (B,S,E)
    up = jnp.einsum("bsd,edf->besf", h, w(mlp["wi"], dt, "mlp/wi"))
    # Deliberately un-named: under remat_policy="mlp"'s allow-list the
    # (B, E, S, F) expert hiddens (E× the dense class) are recomputed.
    up = jax.nn.gelu(up)
    down = jnp.einsum("besf,efd->besd", up,
                      w(mlp["wo"], dt, "mlp/wo"))
    out = jnp.einsum("besd,bse->bsd", down, combine.astype(dt))
    return out, aux


def _moe_group_size(S: int, cap: int) -> tuple[int, int]:
    """Routing-group length along the SEQUENCE axis and the padded
    sequence length: S pads UP to a multiple of ``min(S, cap)`` rather
    than shrinking the group to a divisor — a divisor search would
    collapse to tiny groups for poorly-composite lengths (e.g. 1031),
    exploding the per-group capacity overhead. Pad positions are
    masked out of routing entirely."""
    g = min(S, max(1, cap))
    return g, -(-S // g) * g


def _moe_mlp_routed(h, mlp, c: TransformerConfig, w=_cast_w):
    """Capacity-bounded top-k dispatch (GShard-style, TPU-first).

    Groups are SEQUENCE chunks within each batch row — the batch axis
    is never flattened into the group axis, so a dp/fsdp-sharded
    batch stays shard-local through routing and dispatch (the same
    sharding contract as ops/xent.py; an earlier version grouped
    flat (B*S) tokens, which made the SPMD partitioner gather
    routing tensors across data-parallel ranks —
    benchmarks/audit_collectives.py). Each (row, group) routes its
    ``gs`` tokens into per-expert capacity buffers
    ``C = ceil(cf * k * gs / E)``: position-in-expert comes from a
    slot-major cumsum (slot 0 beats slot 1 on overflow — earlier/
    higher top-k choices win buffer slots), overflowing tokens are
    dropped (their combine weight never lands in a buffer slot,
    standard GShard semantics). Dispatch/combine are one-hot einsums
    — pure MXU work that shards over the ``expert`` axis under EP —
    and expert FLOPs are ``4*D*F*cf*k*T``: independent of E at fixed
    top_k, vs the dense path's O(E). Grouping bounds the (gs, E, C)
    dispatch tensor and the dispatch-einsum FLOPs, which would
    otherwise rival the expert compute itself at large T.
    """
    dt = h.dtype
    E, k = c.moe_num_experts, c.moe_top_k
    B, S, D = h.shape
    gs, S_pad = _moe_group_size(S, c.moe_group_size)
    G = S_pad // gs
    C = int(-(-c.moe_capacity_factor * k * gs // E))  # ceil
    C = min(C, gs * k)  # can't hold more than every (token, slot)

    x = h
    valid = None
    if S_pad != S:
        x = jnp.concatenate(
            [x, jnp.zeros((B, S_pad - S, D), x.dtype)], axis=1)
        valid = jnp.broadcast_to(
            jnp.arange(S_pad) < S, (B, S_pad)).reshape(B, G, gs)
    x = x.reshape(B, G, gs, D)
    topv, onehot, aux = _moe_router(x, mlp, c, valid=valid, w=w)
    # (B, G, gs, k, E) -> slot-major (B, G, k*gs, E): all slot-0 rows
    # first, so the running count gives slot 0 strictly higher buffer
    # priority.
    oh = onehot.transpose(0, 1, 3, 2, 4).reshape(B, G, k * gs, E)
    pos = (jnp.cumsum(oh, axis=2) * oh - 1.0).astype(
        jnp.int32
    )                                                 # (B, G, k*gs, E)
    # one_hot maps out-of-range indices to the zero vector, which IS
    # the drop: unselected entries (pos == -1) and capacity overflow
    # (pos >= C) land in no buffer slot.
    slot = jax.nn.one_hot(pos, C, dtype=jnp.float32)  # (B,G,k*gs,E,C)
    wts = topv.transpose(0, 1, 3, 2).reshape(B, G, k * gs)
    combine = (jnp.einsum("bgt,bgtec->bgtec", wts, slot)
               .reshape(B, G, k, gs, E, C)
               .sum(axis=2))                          # (B, G, gs, E, C)
    dispatch = combine > 0.0

    expert_in = jnp.einsum("bgsec,bgsd->bgecd", dispatch.astype(dt), x)
    up = jnp.einsum("bgecd,edf->bgecf", expert_in,
                    w(mlp["wi"], dt, "mlp/wi"))
    # Deliberately un-named: under remat_policy="mlp"'s allow-list the
    # (B, G, E, C, F) expert hiddens — the routed path's biggest
    # residuals — are recomputed in backward.
    up = jax.nn.gelu(up)
    down = jnp.einsum("bgecf,efd->bgecd", up,
                      w(mlp["wo"], dt, "mlp/wo"))
    out = jnp.einsum("bgsec,bgecd->bgsd", combine.astype(dt), down)
    return out.reshape(B, S_pad, D)[:, :S], aux


def _moe_mlp(h: jax.Array, mlp: dict, c: TransformerConfig,
             w=_cast_w) -> tuple[jax.Array, jax.Array]:
    """Top-k routed expert MLP; dispatch per ``cfg.moe_impl``."""
    if c.moe_impl == "routed":
        return _moe_mlp_routed(h, mlp, c, w=w)
    return _moe_mlp_dense(h, mlp, c, w=w)


def build_transformer(name: str, loss: str = "auto",
                      dtype: str = "bfloat16", **kwargs) -> Transformer:
    """Build from a preset name or raw kwargs (registry entrypoint)."""
    preset: dict = {}
    if name in PRESETS:
        preset = dict(PRESETS[name])
    elif name == "moe_transformer":
        preset = dict(d_model=512, n_layers=8, n_heads=8,
                      max_seq_len=512, moe_num_experts=8)
    preset.update(kwargs)
    preset.setdefault("dtype", dtype)
    if loss != "auto":
        preset["loss_name"] = loss
    return Transformer(TransformerConfig(**preset))
