"""The block interface: what the engine's programs take from a model.

``serving/engine.py`` owns the programs (one token a slot, a lane
table of chunks, the resident loop), the page
coordinates, the scatter into the pool and the sampling. What happens
to a token between the embedding and the logits is the model's, behind
the calls below. A model hands its block over with
``model.serving_block()``; the engine asks for nothing else of it.

A block has

- ``cache``: the fields of ``PagedCacheConfig`` that the model decides
  (``n_layers``, ``n_kv_heads``, ``head_dim``, ``v_head_dim``,
  ``kind``; a model that mixes window and global layers adds
  ``window``, ``window_layers`` and its own name as ``block``, and
  ``window_head_dim`` / ``window_v_head_dim`` where a window layer's
  rows are of another width; one whose global layers keep an index key
  a token adds ``index_dim`` and ``index_topk``). There
  are always two pools ``k_pages`` / ``v_pages``; how they are stored
  is the cache's business (``serving/kv_cache.py::PoolLayout``; with
  window layers each is a pool a kind of layer), what a row of each
  holds the block's (keys and values a head for ``DenseBlock`` and
  ``models/window_moe.py``; the latent row and the rotary key for
  ``models/latent_moe.py``);
- ``counters``: names of the int32 sums ``finish`` returns a layer
  (``()`` for a block that counts nothing). The programs add them up
  over layers and iterations and return them beside the tokens, so they
  ride the fetch the tokens ride (step-record fields of these names);
- ``embed(params, tokens, positions)`` -> ``x (..., D)``;
- ``segments(params)`` -> the stacked layer parameters in layer order,
  one pytree a run of like layers (leading axis = layers). The engine
  scans each run with the pool's matching layers;
- ``at(layer)`` -> the block as the run that starts at layer number
  ``layer`` sees it: the object whose ``project`` / ``attend_chunk`` /
  ``finish`` the engine calls in that run. A block whose layers are
  all alike returns itself; one whose runs differ (positions on some,
  a window on some) returns the run's view;
- ``project(layer, x, positions)`` -> ``(q, k_new, v_new)`` or ``(q,
  k_new, v_new, i_new)``: the layer's normed input projected; ``k_new``
  / ``v_new`` ``(..., n_kv_heads, width)`` are the rows this token adds
  to the two pools (each kind of layer at its own widths, where the
  block's ``cache`` gives a window layer others: ``window_head_dim``,
  ``window_v_head_dim``), ``q`` whatever the block's ``attend_chunk``
  wants (an array or a tuple of arrays, leading shapes as ``x``'s). A
  run of GLOBAL layers of a block whose ``cache`` has ``index_dim``
  (and ``index_topk``) returns the fourth: ``i_new (..., 1,
  index_dim)``, the token's index key, which the engine writes into
  the index pool at the same page and slot (``models/
  sparse_latent_moe.py``);
- ``attend_chunk(layer, q, kp, vp, page_rows, q_pos[, ip])``: ``S`` lanes of
  ``C`` queries, each against its sequence's pages at positions up to
  its own (``q_pos`` ``(S, C)``, negative = a dead query, zero output).
  ``kp`` / ``vp`` are the layer of the two carried pools, unread
  (``kv_cache.PoolLayer``: read through its ``slots()`` /
  ``pages(tables)``), and already hold the queries' own rows. The one
  attention entry: the one-token decode program calls it at ``C = 1``.
  In a run of window layers ``page_rows`` are the sequences' RINGS in
  the window pool and ``kp`` / ``vp`` that pool's layer
  (``ops/paged_attention.py``: ``window=..., ring=True``). ``ip``, given
  where the run's ``project`` returned an index key, is the layer of
  the index pool, unread like ``kp`` / ``vp`` and addressed by the same
  ``page_rows``;
- ``finish(layer, x, attn, valid)`` -> ``(x, counts)``: the output
  projection, the residuals and the feed-forward; ``valid`` marks the
  rows that are real tokens (for counters only);
- ``logits(params, x)`` -> float32 logits of the final hidden states.

Leading shapes are free: ``(B,)`` rows in the decode program,
``(S, C)`` in the lane table.
"""

from __future__ import annotations


def rope_bhd(x, positions, theta: float = 10000.0):
    """RoPE on (..., H, hd) with per-row absolute positions (...),
    halves paired (``x[i]`` with ``x[i + hd/2]``), base ``theta`` —
    at the default the same freqs/rotation as models.transformer._rope
    (parity with the training stack is load-bearing: drift here is
    silent output corruption, caught by the paged⇄dense test). The
    leading shape is free: (B,) rows for the one-token decode, (S, C)
    lanes×positions for the batched chunk program."""
    import jax.numpy as jnp

    D = x.shape[-1]
    half = D // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32)
                             / half))
    angles = positions[..., None].astype(jnp.float32) * freqs
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def layer_norm(x, scale, bias=None, eps: float = 1e-5):
    """LayerNorm over the last axis, float32 inside; ``bias`` None: a
    norm with a scale only (``models/parallel_moe.py``)."""
    import jax
    import jax.numpy as jnp

    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps) * scale
    return (y if bias is None else y + bias).astype(dtype)


def weight(leaf, dt):
    """A weight leaf in compute dtype. An int8 weight-only leaf is a
    dict ``{"qw": int8, "scale": fp32}`` with per-output-channel
    scales (serving/disagg.py ``quantize_params_int8``) and is
    DEQUANTIZED AT COMPUTE — the stored layout (and its tp/fsdp
    partition specs) stays int8; plain arrays cast exactly as
    before. Every weight einsum in the serving programs reads its
    operand through this one helper so the fp32 and int8 paths
    cannot drift."""
    if isinstance(leaf, dict):
        return leaf["qw"].astype(dt) * leaf["scale"].astype(dt)
    return leaf.astype(dt)


class DenseBlock:
    """GPT-2's block (``models/transformer.py::Transformer``):
    LayerNorm with bias, one key and one value a kv head in the pool,
    learned or rotary positions, a GELU MLP or, with
    ``moe_impl="dense"``, experts that every token passes through.
    ``attention_window`` is honoured: every layer masks to it over the
    full table (the pages behind the window stay held; a ring is a
    cache with GLOBAL layers beside the window ones,
    ``models/window_moe.py``)."""

    counters: tuple = ()

    def __init__(self, cfg):
        if cfg.moe_num_experts > 0 and cfg.moe_impl != "dense":
            raise ValueError(
                "the serving engine cannot serve moe_impl='routed': "
                "its experts drop the tokens over moe_capacity_factor "
                f"({cfg.moe_capacity_factor}) of an even share, and a "
                "served token may not be dropped (moe_impl='dense' "
                "computes every expert and is served)")
        self.cfg = cfg
        self.cache = dict(n_layers=cfg.n_layers,
                          n_kv_heads=cfg.n_kv_heads,
                          head_dim=cfg.head_dim, kind="kv")

    def embed(self, params, tokens, positions):
        import jax.numpy as jnp

        c = self.cfg
        x = params["tok_embed"][tokens].astype(jnp.dtype(c.dtype))
        if c.pos_encoding == "learned":
            # Clamp padding positions into range; their rows are dead.
            safe = jnp.minimum(positions, c.max_seq_len - 1)
            x = x + params["pos_embed"][safe].astype(x.dtype)
        return x

    def segments(self, params):
        return ({k: params[k] for k in ("ln1", "ln2", "attn", "mlp")},)

    def at(self, layer):
        del layer
        return self

    def project(self, layer, x, positions):
        import jax.numpy as jnp

        dt = x.dtype
        a = layer["attn"]
        h = layer_norm(x, layer["ln1"]["scale"], layer["ln1"]["bias"])
        q = jnp.einsum("...d,dhk->...hk", h, weight(a["wq"], dt))
        k = jnp.einsum("...d,dhk->...hk", h, weight(a["wk"], dt))
        v = jnp.einsum("...d,dhk->...hk", h, weight(a["wv"], dt))
        if self.cfg.pos_encoding == "rope":
            q = rope_bhd(q, positions)
            k = rope_bhd(k, positions)
        return q, k, v

    def attend_chunk(self, layer, q, kp, vp, page_rows, q_pos):
        from distributed_training_tpu.ops.paged_attention import (
            paged_attention_chunk)

        return paged_attention_chunk(
            q, kp, vp, page_rows, q_pos,
            window=self.cfg.attention_window or None)

    def finish(self, layer, x, attn, valid):
        import jax
        import jax.numpy as jnp

        del valid
        c = self.cfg
        dt = x.dtype
        with jax.named_scope("dtt.attn.out"):
            x = x + jnp.einsum("...hk,hkd->...d", attn,
                               weight(layer["attn"]["wo"], dt))
        m = layer["mlp"]
        if c.moe_num_experts > 0:
            from distributed_training_tpu.models.transformer import (
                _moe_mlp_dense)

            with jax.named_scope("dtt.moe.experts"):
                h = layer_norm(x, layer["ln2"]["scale"],
                               layer["ln2"]["bias"])
                rows = h.reshape(1, -1, h.shape[-1])
                out, _aux = _moe_mlp_dense(
                    rows, m, c, w=lambda p, d, path=None: weight(p, d))
                x = x + out.reshape(h.shape)
        else:
            with jax.named_scope("dtt.mlp"):
                h = layer_norm(x, layer["ln2"]["scale"],
                               layer["ln2"]["bias"])
                u = jax.nn.gelu(jnp.einsum("...d,df->...f", h,
                                           weight(m["wi"], dt))
                                + m["bi"].astype(dt))
                x = x + (jnp.einsum("...f,fd->...d", u,
                                    weight(m["wo"], dt))
                         + m["bo"].astype(dt))
        return x, jnp.zeros((0,), jnp.int32)

    def logits(self, params, x):
        import jax.numpy as jnp

        c = self.cfg
        x = layer_norm(x, params["final_norm"]["scale"],
                       params["final_norm"]["bias"])
        head = (params["tok_embed"].T if c.tie_embeddings
                else params["lm_head"])
        return jnp.einsum("...d,dv->...v", x,
                          weight(head, x.dtype)).astype(jnp.float32)
