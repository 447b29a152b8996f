"""Prefill/decode disaggregation: two plans, one weight store.

Prefill is compute-bound (a prompt's worth of matmuls, batch-friendly)
and decode is latency-bound (one token per step, KV-residency-hungry)
— they want DIFFERENT layouts of the same weights. The planner
resolves both from one model (``parallel/planner.py`` objectives
"prefill"/"decode", committed as ``conf/plans/serving_4dev_cpu_*``),
and this module is everything that makes the pair runnable:

- ``WeightStore`` — the consolidated export artifact
  (checkpoint/export.py) loaded ONCE to host memory and laid out
  per-plan onto any mesh slice on demand. Plan provenance embedded in
  the artifact (the export CLI stamps the source run's plan name +
  fingerprint) is verified against the committed plan file: serving a
  checkpoint under a silently-regenerated plan is refused; legacy
  artifacts (no provenance) load with a warning.
- ``plan_shardings``/``place_params`` — a plan's sharding-map-by-name
  resolved to ``NamedSharding``s on a concrete mesh and applied with
  one ``device_put`` per leaf.
- ``DisaggPipeline`` — the end-to-end demo the parity test pins: the
  8-device mesh split into a prefill slice and a decode slice, each
  laid out under its own plan from the one store; prompts prefill on
  slice A, the paged KV hands off to slice B (dense per-sequence
  export → page-granular import, resharding kv-head layout in the
  copy), and continuous-batching decode finishes there. Greedy tokens
  are equal to the co-located engine's token-for-token.
- ``compile_verify_serving`` — the planner's stage-2 verifier for
  serving objectives: abstract-compile the engine's ACTUAL decode (or
  prefill) program under the candidate plan on a fake mesh and
  disqualify on any SPMD involuntary-reshard warning, exactly as
  ``compile_verify`` does for the train step.
"""

from __future__ import annotations

import logging

import numpy as np

from distributed_training_tpu.serving.engine import (
    Engine,
    EngineConfig,
    build_decode_fn,
)

logger = logging.getLogger(__name__)


# ---------------------------------------------------------------------------
# Plan-directed placement
# ---------------------------------------------------------------------------


def _is_quant_leaf(x) -> bool:
    """An int8 weight-only leaf: ``{"qw": int8, "scale": f32}`` —
    the dict IS the leaf for placement purposes (one sharding entry
    in the plan covers both members)."""
    return isinstance(x, dict) and "qw" in x and "scale" in x


def plan_shardings(plan, mesh, params_tree):
    """Resolve ``plan.sharding_map`` (path → per-dim axis entries)
    into a pytree of NamedShardings matching ``params_tree``. Raises
    on a param path the plan does not name (same contract as
    PlannedStrategy: a model/plan mismatch fails at placement, not as
    a silently replicated layout).

    Int8 weight-only leaves (``{"qw", "scale"}`` dicts) resolve under
    the SAME committed entries as their fp32 original: ``qw`` keeps
    the weight's shape so it takes the plan's spec verbatim; the
    keepdims ``scale`` replicates every REDUCED (size-1) dim and
    inherits the spec on its kept output-channel dims — the quantized
    layout is the committed layout, not a new one."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    def leaf(path, lf):
        key = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                       for k in path)
        try:
            entries = plan.sharding_map[key]
        except KeyError:
            raise ValueError(
                f"plan '{plan.name}' names no sharding for param "
                f"'{key}' — it was resolved against a different "
                "model") from None

        def ns(ent):
            return NamedSharding(mesh, P(*[
                tuple(e) if isinstance(e, list) else e for e in ent]))

        if _is_quant_leaf(lf):
            scale_ent = [None if lf["scale"].shape[d] == 1 else e
                         for d, e in enumerate(entries)]
            return {"qw": ns(entries), "scale": ns(scale_ent)}
        return ns(entries)

    return jax.tree_util.tree_map_with_path(
        leaf, params_tree, is_leaf=_is_quant_leaf)


def place_params(params, mesh, plan):
    """One ``device_put`` per leaf onto the plan's layout."""
    import jax

    shardings = plan_shardings(plan, mesh, params)
    return jax.tree.map(jax.device_put, params, shardings)


# ---------------------------------------------------------------------------
# Int8 weight-only quantization
# ---------------------------------------------------------------------------

# The quantizable weight sites (the serving transformer's matmul
# operands) and the dims their per-OUTPUT-CHANNEL scale reduces over
# — dim 0 is the stacked layer axis, always kept. Embeddings, the LM
# head, norms and biases stay fp32: they are a rounding-error share
# of the bytes and the head's logits precision is the parity gate.
_QUANT_AXES: dict[tuple[str, str], tuple[int, ...]] = {
    ("attn", "wq"): (1,),        # (L, D, H, hd)  — reduce D
    ("attn", "wk"): (1,),        # (L, D, Hkv, hd)
    ("attn", "wv"): (1,),        # (L, D, Hkv, hd)
    ("attn", "wo"): (1, 2),      # (L, H, hd, D)  — reduce H, hd
    ("mlp", "wi"): (1,),         # (L, D, F)      — reduce D
    ("mlp", "wo"): (1,),         # (L, F, D)      — reduce F
}


def _quantize_leaf(w, axes: tuple[int, ...]) -> dict:
    """Symmetric per-channel int8: ``qw * scale ≈ w`` with one f32
    scale per output channel (keepdims — broadcast at dequant). An
    all-zero channel keeps scale 1.0 (qw is 0 there anyway)."""
    w = np.array(w, np.float32)
    amax = np.max(np.abs(w), axis=axes, keepdims=True)
    scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    qw = np.clip(np.round(w / scale), -127, 127).astype(np.int8)
    return {"qw": qw, "scale": scale}


def quantize_params_int8(params):
    """The int8 weight-only layout of a serving params tree: every
    ``_QUANT_AXES`` site becomes a ``{"qw": int8, "scale": f32}``
    leaf (4× the bytes of the dominant weights back); everything
    else passes through untouched. The engine's programs dequantize
    AT COMPUTE through one helper (serving/engine.py ``_w``), so
    fp32 and int8 stores run the same program bodies."""
    out = dict(params)
    for (grp, name), axes in _QUANT_AXES.items():
        if grp not in out or name not in out[grp]:
            continue
        sub = dict(out[grp])
        sub[name] = _quantize_leaf(sub[name], axes)
        out[grp] = sub
    return out


def quantized_weight_bytes(params) -> dict:
    """``{"fp32": bytes, "int8": bytes}`` for a (possibly already
    quantized) params tree — the planner's HBM credit and the bench's
    ``weight_bytes`` evidence share this arithmetic."""
    import jax

    fp32 = int8 = 0
    for leaf in jax.tree.leaves(
            params, is_leaf=_is_quant_leaf):
        if _is_quant_leaf(leaf):
            fp32 += 4 * int(np.prod(leaf["qw"].shape))
            int8 += (leaf["qw"].size * leaf["qw"].dtype.itemsize
                     + leaf["scale"].size
                     * leaf["scale"].dtype.itemsize)
        else:
            n = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
            fp32 += n
            int8 += n
    return {"fp32": fp32, "int8": int8}


# ---------------------------------------------------------------------------
# The weight store
# ---------------------------------------------------------------------------


class ProvenanceError(ValueError):
    """Artifact plan provenance contradicts the committed plan."""


class WeightStore:
    """One consolidated artifact, many per-plan layouts.

    Loads the msgpack export (host NumPy — no mesh needed) exactly
    once; ``params_for(mesh, plan)`` lays the SAME host copy out under
    any plan on any mesh slice, which is what lets prefill and decode
    slices share a checkpoint without double-loading or re-export.

    Provenance contract (checkpoint/export.py stamps it): an artifact
    carrying ``meta["sharding_plan"] = {"name", "fingerprint"}``
    refuses to load when the committed plan of that name now has a
    DIFFERENT fingerprint — weights exported under one resolved
    layout must not silently serve under a regenerated one (re-export
    or re-plan deliberately instead). Artifacts without the stamp
    (legacy / foreign) load with a warning.
    """

    def __init__(self, artifact_path: str, check_provenance: bool = True):
        from distributed_training_tpu.checkpoint.consolidate import (
            load_consolidated)

        state, meta = load_consolidated(artifact_path)
        self.path = artifact_path
        self.meta = meta
        self.state = state
        self.params = state["params"] if "params" in state else state
        # Quantization provenance: the export CLI stamps the layout
        # it wrote (checkpoint/export.py --quantize); an unknown
        # stamp is refused rather than served as garbage weights.
        self.quantization = str(
            (meta or {}).get("quantization", "none"))
        if self.quantization not in ("none", "int8"):
            raise ValueError(
                f"artifact {artifact_path} stamps unknown "
                f"quantization '{self.quantization}' (supported: "
                "none, int8)")
        if check_provenance:
            self._check_provenance()

    def _check_provenance(self) -> None:
        from distributed_training_tpu.parallel.planner import (
            PlanError, load_plan)

        prov = self.meta.get("sharding_plan")
        if not prov:
            logger.warning(
                "artifact %s carries no sharding-plan provenance "
                "(legacy or foreign export) — serving layout cannot "
                "be cross-checked against the training plan",
                self.path)
            return
        name = prov.get("name")
        try:
            committed = load_plan(name)
        except (PlanError, FileNotFoundError) as e:
            raise ProvenanceError(
                f"artifact {self.path} was exported from plan "
                f"'{name}', which no longer loads ({e}) — re-export "
                "from a run on a committed plan") from e
        if committed.fingerprint() != prov.get("fingerprint"):
            raise ProvenanceError(
                f"artifact {self.path} was exported from plan "
                f"'{name}'@{prov.get('fingerprint')}, but the "
                f"committed plan is now @{committed.fingerprint()} — "
                "the plan was regenerated since export; re-export "
                "the checkpoint (or restore the plan) rather than "
                "serving weights under a layout that does not match "
                "their provenance")

    @property
    def provenance(self) -> dict | None:
        """The artifact's plan provenance stamp ``{"name",
        "fingerprint"}`` (None on legacy artifacts) — the baseline
        ``Engine.swap_weights`` gates every live publish against."""
        prov = (self.meta or {}).get("sharding_plan")
        return dict(prov) if prov else None

    def params_for(self, mesh, plan):
        """The host weights laid out under ``plan`` on ``mesh``."""
        import jax.numpy as jnp
        import jax

        params = jax.tree.map(jnp.asarray, self.params)
        return place_params(params, mesh, plan)


# ---------------------------------------------------------------------------
# KV handoff between slices
# ---------------------------------------------------------------------------


def export_kv(cache, seq_id):
    """A sequence's KV as dense host arrays (L, Hkv, len, hd) —
    page-table indirection resolved, ready to cross a mesh boundary
    (the handoff wire format; at pod scale this is the DCN payload)."""
    k, v = export_kv_batch(cache, [seq_id])
    return k[0], v[0]


def export_kv_batch(cache, seq_ids):
    """Dense KV for MANY in-flight sequences in ONE device→host
    transfer — the continuous-handoff rate path: the page gather for
    every sequence in the batch is a single device slice instead of
    one transfer per request (per-request ``export_kv`` is this with
    a batch of one, so the two can never produce different bytes).
    Returns ``(ks, vs)`` — parallel lists of (L, Hkv, len_i, hd)
    arrays. Refuses a cache with window layers: a ring holds a
    window's rows, not a sequence's."""
    cache.full_tables_only("a dense KV hand-off (export)")
    pages_of, lens = [], []
    for sid in seq_ids:
        n = cache.length(sid)
        n_pages = -(-n // cache.cfg.page_size) if n else 0
        pages_of.append((cache.group_of(sid),
                         cache.page_row(sid)[:n_pages]))
        lens.append(n)
    if not seq_ids:
        return [], []
    # One gather over the union of (group, page) coordinates, sliced
    # ON DEVICE before pulling to host: np.asarray(pool) would
    # materialize the ENTIRE pool; this transfers only the batch's
    # own pages, once.
    groups = np.concatenate([np.full(len(p), g, np.int32)
                             for g, p in pages_of]) \
        if any(len(p) for _g, p in pages_of) else np.zeros(0, np.int32)
    pages = np.concatenate([p for _g, p in pages_of]) \
        if groups.size else np.zeros(0, np.int32)
    k_all, v_all = map(np.asarray, cache.read_pages(groups, pages))
    ks, vs = [], []
    off = 0
    ps = cache.cfg.page_size
    for (_g, p), n in zip(pages_of, lens):
        kseq = k_all[off:off + len(p)]        # (p, L, Hkv, ps, hd)
        vseq = v_all[off:off + len(p)]
        off += len(p)
        L = cache.cfg.n_layers
        Hkv = cache.cfg.n_kv_heads
        k = kseq.transpose(1, 2, 0, 3, 4).reshape(
            L, Hkv, len(p) * ps, cache.cfg.head_dim)[:, :, :n]
        v = vseq.transpose(1, 2, 0, 3, 4).reshape(
            L, Hkv, len(p) * ps, cache.cfg.v_head_dim)[:, :, :n]
        ks.append(k)
        vs.append(v)
    return ks, vs


def import_kv(cache, seq_id, k, v) -> None:
    """Write dense (L, Hkv, len, hd) KV into a (different) cache's
    pages for ``seq_id`` (already joined; pages are ensured here —
    in the sequence's own dp group's shard). The destination pool's
    sharding resharding happens in the ``.at[].set`` device_puts —
    kv-head/group layout follows the destination mesh."""
    import_kv_batch(cache, [(seq_id, k, v)])


def import_kv_batch(cache, items) -> None:
    """Batched page-granular import: ``items`` is a list of
    ``(seq_id, k, v)`` dense KV triples (every seq already joined).
    All pages across all sequences land in ONE scatter per pool —
    the per-engine-step transfer the continuous handoff batches,
    instead of one device round-trip per request. Raises when a
    destination group's shard cannot hold a sequence, and the raise
    aborts the WHOLE batch before the scatter: nothing is written
    and no cursor advances, but earlier items' pages are left
    allocated-and-empty (ensure() is atomic per sequence). Callers
    must free every item and retry — ``Engine.adopt_batch`` does.
    Refuses a cache with window layers, as ``export_kv_batch``."""
    cache.full_tables_only("a dense KV hand-off (import)")
    todo = []
    ps = cache.cfg.page_size
    for seq_id, k, v in items:
        n = k.shape[2]
        if n == 0:
            continue
        if not cache.ensure(seq_id, n):
            raise RuntimeError(
                f"KV import for {seq_id!r}: destination pool cannot "
                f"hold {n} positions")
        todo.append((seq_id, k, v, n))
    if not todo:
        return
    groups, pages, k_chunks, v_chunks = [], [], [], []
    for seq_id, k, v, n in todo:
        g = cache.group_of(seq_id)
        table = cache._tables[seq_id]
        for j, pid in enumerate(table[: -(-n // ps)]):
            lo, hi = j * ps, min((j + 1) * ps, n)
            kc = np.zeros((k.shape[0], k.shape[1], ps, k.shape[3]),
                          k.dtype)
            vc = np.zeros((v.shape[0], v.shape[1], ps, v.shape[3]),
                          v.dtype)
            kc[:, :, :hi - lo] = k[:, :, lo:hi]
            vc[:, :, :hi - lo] = v[:, :, lo:hi]
            groups.append(g)
            pages.append(pid)
            k_chunks.append(kc)
            v_chunks.append(vc)
    cache.write_pages(np.asarray(groups, np.int32),
                      np.asarray(pages, np.int32),
                      np.stack(k_chunks), np.stack(v_chunks))
    for seq_id, _k, _v, n in todo:
        cache.advance(seq_id, n)


# ---------------------------------------------------------------------------
# The disaggregated pipeline
# ---------------------------------------------------------------------------


def engine_config_for_plan(plan, page_size: int = 16,
                           prefill_chunk: int = 16,
                           spec_k: int = 1,
                           resident_k: int = 1) -> EngineConfig:
    """The ONE engine geometry a plan implies — shared by the bench,
    the disagg pipeline, and the analysis audit targets so they all
    compile the same program shapes. ``batch_per_shard`` is the
    AGGREGATE slot count, dealt over the plan's ``dp`` groups
    (serving/engine.py) — decode slots for decode plans, prefill
    lanes for prefill plans (``prefill_slots`` defaults to the same
    table); ``num_pages`` is each group's pool shard, sized so its
    own slots fit at full length — the whole-pool total is the same
    HBM the replicated-table engine reserved, now batch-sharded.
    ``spec_k``/``resident_k`` select the decode program
    (speculative, device-resident); the plan's layout is
    program-agnostic — dp deals lanes, tp shards heads, either way.

    Pool sizing (SERVING_r05): when the plan's provenance carries
    ``kv_pool_tokens`` (the planner's residual-HBM-credit sizing —
    int8 plans vacate weight bytes that become KV pages), each
    group's shard is grown to hold its share of that token budget;
    plans without the field keep the minimal slots-fit-at-full-length
    pool, so pre-r05 plan files stay valid."""
    slots = plan.batch_per_shard
    dp = plan.mesh.get("dp", 1)
    if slots % dp:
        raise ValueError(
            f"plan '{plan.name}': batch_per_shard ({slots}) does not "
            f"deal over dp={dp} — the planner must not emit this "
            "(slots%dp feasibility)")
    pages_per_seq = -(-plan.seq_len // page_size)
    num_pages = (slots // dp) * pages_per_seq + 1
    pool_tokens = ((plan.provenance or {}).get("score") or {}).get(
        "kv_pool_tokens")
    if isinstance(pool_tokens, int) and pool_tokens > 0:
        num_pages = max(num_pages,
                        -(-(pool_tokens // dp) // page_size) + 1)
    return EngineConfig(
        max_batch=slots,
        page_size=page_size,
        num_pages=num_pages,
        max_seq_len=plan.seq_len,
        prefill_chunk=prefill_chunk,
        spec_k=spec_k,
        resident_k=resident_k,
        kv_axis="tp",
        dp_axis="dp")


class DisaggPipeline:
    """Prefill on one mesh slice, decode on another, one WeightStore.

    ``prefill_devices``/``decode_devices``: disjoint device lists
    (the 4+4 split of the 8-device CPU mesh in tests). Each slice
    builds its own mesh from its plan's axes and lays the shared
    weights out under that plan. ``generate`` runs the full path:
    chunked prefill on slice A, dense-KV handoff, continuous-batching
    decode on slice B.
    """

    def __init__(self, store: WeightStore, prefill_plan, decode_plan,
                 prefill_devices, decode_devices,
                 page_size: int = 16, prefill_chunk: int = 16):
        from distributed_training_tpu.parallel.planner import (
            model_for_plan, model_kwargs_for)
        from distributed_training_tpu.runtime import MeshSpec, build_mesh

        mk_p = model_kwargs_for(prefill_plan)
        mk_d = model_kwargs_for(decode_plan)
        if {k: v for k, v in mk_p.items() if k != "remat"} != \
                {k: v for k, v in mk_d.items() if k != "remat"}:
            raise ValueError(
                "prefill and decode plans describe different models "
                "— disaggregation requires one model, two layouts")
        self.model = model_for_plan(decode_plan)

        def slice_mesh(plan, devices):
            spec = MeshSpec(**{a: plan.mesh.get(a, 1)
                               for a in ("pp", "dp", "fsdp", "sp",
                                         "tp")})
            if spec.total != len(devices):
                raise ValueError(
                    f"plan '{plan.name}' needs {spec.total} devices, "
                    f"slice has {len(devices)}")
            return build_mesh(spec, list(devices))

        self.prefill_mesh = slice_mesh(prefill_plan, prefill_devices)
        self.decode_mesh = slice_mesh(decode_plan, decode_devices)
        self.prefill_params = store.params_for(self.prefill_mesh,
                                               prefill_plan)
        # Prefill slice: an Engine used only for its prefill programs
        # + pool (its decode program never runs).
        self.prefill_engine = Engine(
            self.model, self.prefill_params,
            engine_config_for_plan(prefill_plan, page_size,
                                   prefill_chunk),
            mesh=self.prefill_mesh)
        self.decode_engine = Engine(
            self.model, store.params_for(self.decode_mesh,
                                         decode_plan),
            engine_config_for_plan(decode_plan, page_size,
                                   prefill_chunk),
            mesh=self.decode_mesh)

    def generate(self, prompt: np.ndarray, max_new_tokens: int,
                 req_id: str = "disagg",
                 tenant: str = "default") -> list[int]:
        from distributed_training_tpu.serving.engine import Request

        prompt = np.array(prompt, np.int32)
        pe = self.prefill_engine
        req = Request(id=req_id, prompt=prompt,
                      max_new_tokens=max_new_tokens, tenant=tenant)
        pe.submit(req)
        # Drive ONLY prefill steps on the prefill slice: the request
        # completes its prompt and samples the first token there.
        while not any(s is not None and s.prefill_done
                      for s in pe.slots):
            rec = pe.step()
            if rec["op"] == "idle":
                raise RuntimeError("prefill slice made no progress")
        seq = next(s for s in pe.slots
                   if s is not None and s.prefill_done)
        first_token = seq.generated[0]
        k, v = export_kv(pe.cache, req.id)
        # Release the prefill slice (continuous batching: the slot is
        # immediately reusable for the next prompt).
        pe.cache.free(req.id)
        pe.slots[seq.slot] = None
        de = self.decode_engine
        # The adopted Request keeps the ORIGINAL arrival and tenant:
        # the decode-side trace must account the whole journey
        # (prefill slice included) to the submitting tenant.
        de.adopt(Request(id=req_id, prompt=prompt,
                         max_new_tokens=max_new_tokens,
                         arrival=req.arrival, tenant=tenant),
                 first_token, k, v)
        de.run_until_drained()
        rec = next(r for r in reversed(de.completed)
                   if r["id"] == req_id)
        return rec["tokens"]

    def generate_many(self, requests, max_steps: int = 100_000
                      ) -> dict:
        """CONTINUOUS KV handoff at rate: drive many requests through
        the pair with page transfers batched per engine step and
        overlapped with ongoing decode, instead of one synchronous
        transfer per request (``generate``'s shape).

        Per loop iteration: the prefill slice takes one step (its own
        continuous batch of prompts); every sequence that finished
        its prompt THIS step is exported in ONE batched device→host
        gather, adopted into the decode slice in ONE batched scatter
        (``export_kv_batch``/``import_kv_batch``), and the decode
        slice takes one step for everything already adopted — so
        handoffs for late prompts ride alongside decode for early
        ones. A handoff the decode slice cannot absorb yet
        (slots/pages) is held and retried next iteration —
        backpressure, not failure.

        ``requests`` is a list of Requests; returns
        ``{req_id: tokens}``, token-identical to the per-request path
        (pinned by test)."""
        pe, de = self.prefill_engine, self.decode_engine
        for r in requests:
            pe.submit(r)
        want = {r.id for r in requests}
        held: list = []       # handoffs awaiting decode capacity
        for _ in range(max_steps):
            done = {r["id"]: r["tokens"] for r in de.completed}
            # Finished-on-prefill requests (<= chunk prompts whose
            # first token IS the last token) complete on pe.
            done.update({r["id"]: r["tokens"] for r in pe.completed
                         if r["id"] in want})
            if want <= set(done):
                return {rid: done[rid] for rid in want}
            if not pe.idle:
                pe.step()
            # Collect every sequence that completed its prompt —
            # batch their exports into one transfer.
            ready = [s for s in pe.slots
                     if s is not None and s.prefill_done]
            if ready:
                ids = [s.req.id for s in ready]
                ks, vs = export_kv_batch(pe.cache, ids)
                for s, k, v in zip(ready, ks, vs):
                    held.append((s.req, s.generated[0], k, v))
                    pe.cache.free(s.req.id)
                    pe.slots[s.slot] = None
            if held:
                try:
                    de.adopt_batch(held)
                    held = []
                except RuntimeError:
                    # Decode slice cannot take the WHOLE batch
                    # (adopt_batch is all-or-nothing): adopt whatever
                    # fits one-by-one, hold the rest for the next
                    # iteration — backpressure must make partial
                    # progress or a burst larger than the decode
                    # table would livelock.
                    still = []
                    for item in held:
                        try:
                            de.adopt_batch([item])
                        except RuntimeError:
                            still.append(item)
                    held = still
            if not de.idle:
                de.step()
        raise RuntimeError(
            f"disagg pipeline not drained after {max_steps} steps "
            f"({len(held)} handoff(s) held, prefill idle={pe.idle}, "
            f"decode idle={de.idle})")


# ---------------------------------------------------------------------------
# Stage-2 verifier for serving-objective plans
# ---------------------------------------------------------------------------


def _quantize_struct(params_shapes):
    """The int8 layout's ShapeDtypeStruct tree — the abstract twin of
    ``quantize_params_int8`` (same sites, same keepdims scale shapes)
    so plan verification compiles the program quantized stores
    actually run."""
    import jax
    import jax.numpy as jnp

    out = dict(params_shapes)
    for (grp, name), axes in _QUANT_AXES.items():
        if grp not in out or name not in out[grp]:
            continue
        sub = dict(out[grp])
        s = sub[name]
        sshape = tuple(1 if d in axes else n
                       for d, n in enumerate(s.shape))
        sub[name] = {
            "qw": jax.ShapeDtypeStruct(s.shape, jnp.int8),
            "scale": jax.ShapeDtypeStruct(sshape, jnp.float32)}
        out[grp] = sub
    return out


def lower_serving_program(plan, objective: str):
    """Abstractly lower the engine's compiled program for ``plan``
    (objective "decode" → the dp-sharded group-batched decode
    program; "prefill" → the BATCHED multi-sequence prefill program,
    the served path since SERVING_r03; "resident" → the
    DEVICE-RESIDENT K-step decode loop, SERVING_r04's served decode
    path) on a fake CPU mesh with params laid out per the plan.
    Returns ``(lowered, mesh)`` — no state materialized
    (ShapeDtypeStruct inputs carrying the plan's NamedShardings,
    analysis/compile.py's discipline). The program itself comes from
    the SAME builders the engine compiles (serving/engine.py
    ``build_decode_fn``/``build_prefill_batch_fn``/
    ``build_resident_decode_fn``), so the verified program and the
    served program can never drift — shard_map over dp included. A
    plan carrying ``inputs["quant"] == "int8"`` lowers against the
    quantized param structs, so the dequant-at-compute einsums are
    in the verified HLO."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_training_tpu.parallel.planner import (
        model_for_plan)
    from distributed_training_tpu.runtime import fake_cpu_runtime
    from distributed_training_tpu.serving.engine import (
        build_prefill_batch_fn, build_resident_decode_fn)
    from distributed_training_tpu.serving.kv_cache import (
        PagedCacheConfig, PagedKVCache, kv_shards, pool_sharding)

    jax.config.update("jax_platforms", "cpu")
    model = model_for_plan(plan)
    rt = fake_cpu_runtime(plan.devices,
                          **{a: s for a, s in plan.mesh.items()
                             if s > 1})
    mesh = rt.mesh
    resident = objective == "resident"
    ecfg = engine_config_for_plan(
        plan, spec_k=4 if resident else 1,
        resident_k=4 if resident else 1)
    c = model.serving_block()
    params_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if plan.inputs.get("quant", "none") == "int8":
        params_shapes = _quantize_struct(params_shapes)
    shardings = plan_shardings(plan, mesh, params_shapes)
    params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=sh),
        params_shapes, shardings)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    kv_ax = "tp" if sizes.get("tp", 1) > 1 else None
    dp_ax = "dp" if sizes.get("dp", 1) > 1 else None
    G = sizes.get("dp", 1)
    B = ecfg.max_batch // G
    # GPT-2's two pools are one shape: its keys and values are as wide.
    pool = jax.ShapeDtypeStruct(
        PagedKVCache.pool_shapes(
            PagedCacheConfig(**c.cache, page_size=ecfg.page_size,
                             num_pages=ecfg.num_pages,
                             max_seq_len=ecfg.max_seq_len, dp_groups=G),
            kv_shards(mesh, kv_ax))[0],
        jnp.dtype(model.cfg.dtype),
        sharding=pool_sharding(mesh, model.cfg.n_kv_heads, G, kv_ax,
                               dp_ax))
    rep = NamedSharding(mesh, P())
    grp = NamedSharding(mesh, P(dp_ax))
    Ppages = -(-ecfg.max_seq_len // ecfg.page_size)

    def arr(shape, dtype, sh=rep):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    if objective == "decode":
        fn = build_decode_fn(c, ecfg, mesh=mesh)
        args = (params, pool, pool, arr((G, B), jnp.int32, grp),
                arr((G, B), jnp.int32, grp),
                arr((G, B, Ppages), jnp.int32, grp),
                arr((G, B), jnp.bool_, grp),
                arr((G, 2), jnp.uint32, grp))
    elif objective == "resident":
        # The device-resident burst program at the r04 bench shape
        # (resident_k=4, spec_k=4) — the carried slot table (history,
        # cursors, tokens left), page rows, budgets and live flags all
        # group-batched; no rng (greedy by contract).
        fn = build_resident_decode_fn(c, ecfg, mesh=mesh)
        args = (params, pool, pool,
                arr((G, B, ecfg.max_seq_len), jnp.int32, grp),
                arr((G, B), jnp.int32, grp),
                arr((G, B), jnp.int32, grp),
                arr((G, B, Ppages), jnp.int32, grp),
                arr((G, B), jnp.int32, grp),
                arr((G, B), jnp.bool_, grp))
    else:
        # The batched prefill lane table: the plan's slot count dealt
        # over dp, prefill_chunk tokens per lane — exactly the
        # program Engine._run_prefill_batch launches.
        fn = build_prefill_batch_fn(c, ecfg, mesh=mesh)
        Sp = (ecfg.prefill_slots or ecfg.max_batch) // G
        C = ecfg.prefill_chunk
        args = (params, pool, pool,
                arr((G, Sp, Ppages), jnp.int32, grp),
                arr((G, Sp, C), jnp.int32, grp),
                arr((G, Sp), jnp.int32, grp),
                arr((G, Sp), jnp.int32, grp),
                arr((G, Sp), jnp.bool_, grp),
                arr((G, 2), jnp.uint32, grp))
    return fn.lower(*args), mesh


def compile_serving_hlo(plan, objective: str):
    """Compile the lowered serving program, capturing the SPMD
    partitioner's stderr. Returns ``(hlo_text, reshard_warnings,
    mesh)`` — the raw material for both the planner's disqualify
    decision and the audit target's findings."""
    from distributed_training_tpu.telemetry import collectives

    lowered, mesh = lower_serving_program(plan, objective)
    with collectives.capture_stderr_fd() as cap:
        text = lowered.compile().as_text()
    return text, collectives.parse_reshard_warnings(cap.text), mesh


def compile_verify_serving(target, plan) -> dict:
    """The planner's stage-2 verifier for serving-objective targets:
    same evidence dict shape as planner.compile_verify — any reshard
    warning disqualifies the candidate either way."""
    from distributed_training_tpu.telemetry import collectives

    text, warnings, mesh = compile_serving_hlo(plan,
                                               target.objective)
    coll = collectives.audit_hlo_text(text, mesh=mesh)
    return {
        "spmd_reshard_warnings": len(warnings),
        "reshard_ops": sorted({w["op"] for w in warnings}),
        "collective_bytes_per_step": coll["bytes_per_step"],
        "total_collectives": coll["total_collectives"],
        "program": {"decode": "decode",
                    "resident": "resident"}.get(target.objective,
                                                "prefill_batch"),
    }
