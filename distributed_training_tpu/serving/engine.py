"""Continuous-batching engine: admission queue → two jitted programs.

The serving hot loop. Requests join and leave the running batch at
every step (continuous batching — no head-of-line blocking behind the
longest sequence in a static batch), against TWO compiled programs
whose shapes never change, both BATCH-SHARDED over the mesh's ``dp``
axis (a ``shard_map`` manual over ``dp``; every other mesh axis —
``tp``'s head shard in particular — stays under the SPMD partitioner
via the ``auto`` axes):

- **batched prefill** — up to ``prefill_slots`` sequences' CURRENT
  prompt chunks in ONE launch: each dp group packs its own admitted
  prompts into ``prefill_slots/dp`` lanes of ``prefill_chunk`` tokens
  (per-lane page rows, start positions, valid counts, live masks —
  the SERVING_r02 per-group ``q_pos=-1`` masking generalized to a
  whole lane table) and writes their KV through one batched page-row
  scatter; the next token of every prompt-completing lane is sampled
  IN-PROGRAM, so completion reads a ``(G, slots)`` int32 block
  instead of a vocab-sized logits block per prompt.
- **decode** — the ``max_batch`` slot table dealt into ``dp`` groups
  of ``max_batch/dp``, each group decoding only its own slots against
  its own KV pool shard; dp adds ZERO new collectives (rows are
  independent). With ``spec_k > 1`` the decode step is
  MULTI-TOKEN SELF-SPECULATIVE: each slot drafts ``spec_k - 1``
  tokens by prompt-lookup (the most recent earlier occurrence of the
  sequence's own trailing n-gram — no second model), verifies the
  whole chain in one batched forward (the same chunk program as
  batched prefill, emitting the argmax at EVERY position), and emits
  the accepted prefix. Greedy output is token-identical BY
  CONSTRUCTION: every emitted token is the verified argmax given the
  true prefix (a draft is accepted only when it equals the previous
  position's argmax), so speculation changes launch count, never
  tokens. Launch overhead amortizes by the acceptance length
  (telemetry: ``spec_accepted_mean``, ``slots_stepped`` and
  ``slot_iters`` on step records).

Join/evict never change a traced shape: admission fills a slot in ONE
group and allocates pages from that group's shard; completion frees
them; the programs compile once at warmup and never again
(``compile_counts`` exposes the jit cache sizes so the bench can
ASSERT zero recompiles mid-storm).

Admission is dp-aware: the queue load-balances across groups —
fewest-active-slots-first, pages permitting — so a burst cannot pile
onto one shard while the others idle (pinned by test under a skewed
arrival burst). Under batched prefill a prefill step admits as many
queued requests as slots+pages allow before launching (one admission
per step would starve the lane table it just paid for).

Scheduling: pending prompt work runs before decode (lowest TTFT;
decode tokens wait behind a prompt storm), and a prefill step that
makes no progress falls through to decode so pages free up. Prompts
that wait for a prefill lane get it in the order they were admitted.

``prefill_chunk`` is the per-lane prefill token budget of a step;
decode emits up to ``max_batch`` tokens per step (all groups fire in
one program launch).

Sampling is greedy at ``temperature == 0`` (the parity-tested path —
token-for-token equal to full-context argmax); ``temperature > 0``
samples per-slot from a per-(step, group) folded key. Batch-
composition independence (a sequence's tokens don't depend on who
shares the batch OR which group it was dealt into) is exact for
greedy decoding and pinned by test.

Token streaming: ``add_token_listener(req_id, fn)`` registers a
callback fired as ``fn(token, done)`` the moment each token is
sampled — the HTTP server's chunked ``"stream": true`` path rides
this (serving/server.py); listener failures are isolated from the
step loop.

What a token passes through between its embedding and its logits is
the MODEL's: every program calls the block that ``model.serving_block()``
hands over (``serving/blocks.py``: project, then the engine writes the
cache, attend, finish), and the pool's row widths come from it. A
model whose experts drop tokens has no block and is refused there.
"""

from __future__ import annotations

import collections
import logging
import time
from dataclasses import dataclass, field

import numpy as np

from distributed_training_tpu.serving.kv_cache import (
    GLOBAL,
    WINDOW,
    PagedCacheConfig,
    PagedKVCache,
    copy_pages,
    kv_shards,
    pool_of,
    with_pool,
)
from distributed_training_tpu.telemetry import current, event, phase
from distributed_training_tpu.telemetry.op_scopes import (
    UNSCOPED, own_metadata, scope_map)

logger = logging.getLogger(__name__)

# The parts of a step, in the order a launch passes them: the keys of
# a step record's ``phase_s`` and the ``serving.<part>`` annotations.
_PHASES = ("admit", "pack", "launch", "fetch", "emit")


@dataclass(frozen=True)
class EngineConfig:
    """Engine knobs (mirrored by ``conf/serving/default.yaml``).

    ``max_batch`` is the AGGREGATE decode slot count across all dp
    groups; on a mesh whose ``dp_axis`` has extent G it must divide
    into G equal group-local tables. ``num_pages`` is the per-group
    pool shard size (serving/kv_cache.py). ``prefill_slots`` is the
    AGGREGATE lane count of the batched prefill program (0 = same as
    ``max_batch``), dealt over dp exactly like the decode table.
    ``spec_k`` is the tokens-per-decode-launch of the speculative
    program (1 = the plain one-token decode; > 1 requires greedy
    ``temperature == 0`` — acceptance verification is exact only for
    the argmax chain)."""

    max_batch: int = 8            # decode slots, aggregate over dp
    page_size: int = 16
    num_pages: int = 128          # per dp group
    max_seq_len: int = 256        # per-sequence cap (prompt + new)
    prefill_chunk: int = 32       # tokens per prefill lane per step
    prefill_slots: int = 0        # batched-prefill lanes (0 = max_batch)
    spec_k: int = 1               # decode tokens per launch (1 = off)
    resident_k: int = 1           # device-resident decode steps (1 = off)
    prefix_sharing: bool = True   # refcounted prefix reuse + sessions
    eos_id: int = -1              # stop token (< 0 = disabled)
    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0
    kv_axis: str = "tp"           # pool kv-head shard axis
    dp_axis: str = "dp"           # slot-table / pool batch shard axis
    swap_staleness_tokens: int = -1  # hot-swap bound (-1 = unbounded)

    def __post_init__(self):
        if self.swap_staleness_tokens < -1:
            raise ValueError(
                "swap_staleness_tokens must be >= -1 (-1 disables "
                "the bound; 0 resubmits every in-flight request with "
                "emitted tokens at swap time)")
        if self.prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        if self.max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        if self.prefill_slots < 0:
            raise ValueError("prefill_slots must be >= 0")
        if self.spec_k < 1:
            raise ValueError("spec_k must be >= 1")
        if self.spec_k > 1 and self.temperature > 0:
            raise ValueError(
                "speculative decode (spec_k > 1) requires greedy "
                "temperature == 0 — the verification accepts exactly "
                "the argmax chain, which has no sampled analogue "
                "without rejection sampling")
        if self.resident_k < 1:
            raise ValueError("resident_k must be >= 1")
        if self.resident_k > 1 and self.temperature > 0:
            raise ValueError(
                "device-resident decode (resident_k > 1) requires "
                "greedy temperature == 0 — the in-program accept/"
                "stop logic is exact only for the argmax chain")


@dataclass
class Request:
    """One generation request. ``arrival`` defaults to submit time.
    ``session``: chat-session key — on completion the sequence's KV
    pages are RETAINED under this key instead of freed, and a later
    request with the same key whose prompt extends the retained
    history re-attaches them (zero prefill for the shared part;
    an exact-history prompt needs zero prefill launches at all).
    ``tenant``: multi-tenant accounting label — threaded from the HTTP
    JSON body into the per-request ``serving_trace`` record and the
    tenant-labeled latency histograms; never affects scheduling.
    ``submitted``: stamped by ``Engine.submit`` — arrival to here is
    the server's mailbox, here to admission the engine's queue (the
    ``submitted`` span of the request's trace)."""

    id: str
    prompt: np.ndarray
    max_new_tokens: int
    arrival: float | None = None
    session: str | None = None
    tenant: str = "default"
    submitted: float | None = None


@dataclass
class _Seq:
    req: Request
    slot: int                     # global slot id (group * B_local + i)
    prefilled: int = 0            # prompt tokens consumed so far
    generated: list = field(default_factory=list)
    first_token_t: float | None = None
    token_times: list = field(default_factory=list)
    eos: bool = False             # emitted the configured stop token
    ngram: "NgramIndex | None" = None  # lazy prompt-lookup index
    trace: list = field(default_factory=list)  # lifecycle spans
    queue_wait_s: float | None = None  # arrival -> admission
    admitted_n: int = 0           # how many were admitted before it
    prefix_hit: int = 0           # prompt tokens served from cache
    # Per-token weight-version tags, run-length encoded as
    # ``[version, count]`` pairs in emission order — a sequence that
    # straddles a hot-swap shows both versions; most show one.
    versions: list = field(default_factory=list)
    # What the launches dispatched and not yet retired may still emit
    # of it: exact at ``spec_k`` 1 with no stop token, else an upper
    # bound (the device's slot table holds the truth). ``prefilled``
    # counts a chunk from its dispatch, ``generated`` a token from its
    # retire; ``pending`` is what lies between.
    pending: int = 0
    # Its row of the device's slot table holds its history (run-ahead
    # engines): written by its own prefill chunks, or uploaded once
    # where part of it was never prefilled here (``Engine._seed``).
    on_device: bool = False

    def span(self, ev: str, t: float, **fields) -> None:
        """Append a lifecycle span. ``t`` is an absolute monotonic
        host timestamp taken at a point the host already occupies
        (admission bookkeeping, the post-``_fetch_host`` reads every
        launch path takes) — stored RELATIVE to arrival so the trace
        is meaningful offline. Pure host-side list append: no device
        touch, no sync, no recompile."""
        rel = t - self.req.arrival if self.req.arrival is not None \
            else t
        self.trace.append({"ev": ev, "t": round(rel, 6), **fields})

    @property
    def prompt_len(self) -> int:
        return int(self.req.prompt.shape[0])

    @property
    def last_token(self) -> int:
        """The token the next decode launch feeds. A zero-prefill
        admission (full prefix hit / exact session resume) starts
        decoding with NOTHING generated yet — it replays the last
        PROMPT token at its already-resident position (the COW'd
        boundary page takes the rewrite), which samples exactly the
        first token a prefill launch would have."""
        return int(self.generated[-1]) if self.generated \
            else int(self.req.prompt[-1])

    @property
    def prefill_done(self) -> bool:
        return self.prefilled >= self.prompt_len

    @property
    def done(self) -> bool:
        return self.eos or \
            len(self.generated) >= self.req.max_new_tokens

    @property
    def left(self) -> int:
        """Tokens it may still be given a budget for: what the request
        asked for less what was emitted and what launches in flight
        may emit (a lower bound where ``pending`` is an upper one)."""
        return (self.req.max_new_tokens - len(self.generated)
                - self.pending)

    @property
    def kv_ahead(self) -> int:
        """Positions written once every launch in flight has landed,
        for a sequence past its prompt: the newest token's KV is
        written by the launch that feeds it, so one less than its
        tokens. An upper bound where ``pending`` is."""
        return (self.prompt_len + len(self.generated) + self.pending
                - 1)


@dataclass
class _Launch:
    """One dispatched launch until ``Engine._retire`` lands it: what
    to fetch (None: nothing, a prefill launch that ends no prompt),
    the cadence's own back half ``emit(now, *fetched) -> tokens``, and
    whether another launch was un-retired when it was dispatched."""

    op: str
    outs: tuple | None
    emit: object
    ran_ahead: int
    lanes: list | None = None     # prefill lanes a group (dp records)


# The longest trailing n-gram the prompt-lookup draft tries first, on
# the host (``NgramIndex``) and in the resident loop alike.
SPEC_NGRAM = 3


def draft_tokens(history: np.ndarray, m: int,
                 ngram_max: int = SPEC_NGRAM) -> np.ndarray:
    """Prompt-lookup drafting: ``m`` speculative tokens from the
    sequence's OWN history (prompt + generated) — no second model.

    Finds the most recent EARLIER occurrence of the history's
    trailing n-gram (longest n <= ngram_max first) and drafts the
    tokens that followed it; short continuations pad with the last
    token, and a history with no repeated n-gram drafts the last
    token repeated. Draft quality only moves the ACCEPTANCE LENGTH —
    never the output: verification emits exactly the argmax chain
    regardless (serving/engine.py spec decode)."""
    hist = np.array(history, np.int32)
    L = hist.shape[0]
    if m <= 0 or L == 0:
        return np.zeros((max(0, m),), np.int32)
    fill = int(hist[-1])
    for n in range(min(ngram_max, L - 1), 0, -1):
        pat = hist[L - n:]
        # All windows starting strictly before the trailing n-gram
        # itself (an occurrence needs at least one continuation
        # token).
        win = np.lib.stride_tricks.sliding_window_view(
            hist, n)[:L - n]
        matches = np.nonzero((win == pat).all(axis=1))[0]
        if matches.size:
            p = int(matches[-1])
            cont = hist[p + n:p + n + m]
            if cont.shape[0] < m:
                cont = np.concatenate([
                    cont, np.full((m - cont.shape[0],), fill,
                                  np.int32)])
            return cont.astype(np.int32)
    return np.full((m,), fill, np.int32)


class NgramIndex:
    """Incremental trailing-n-gram index behind ``Engine._draft``.

    ``draft_tokens`` re-scans the sequence's FULL history with a
    sliding-window numpy pass per launch — O(L · ngram) per slot per
    launch, the dominant host cost of a long sequence's speculative
    step. This keeps, per n <= ngram_max, a dict from n-gram tuple to
    its MOST RECENT start plus a per-start link to the previous start
    of the same gram, updated in O(ngram) per appended token — so a
    draft is a dict probe, not a rescan. Drafts are pinned IDENTICAL
    to ``draft_tokens`` by a randomized test (draft quality only
    moves acceptance length, but the pin keeps the ledgers
    comparable across revisions)."""

    def __init__(self, ngram_max: int = SPEC_NGRAM):
        self.ngram_max = ngram_max
        self.hist: list[int] = []
        # maps[n-1]: gram tuple -> most recent start index;
        # prev[n-1]: start index -> previous start of the same gram.
        self._maps: list[dict] = [{} for _ in range(ngram_max)]
        self._prev: list[dict] = [{} for _ in range(ngram_max)]

    def __len__(self) -> int:
        return len(self.hist)

    def extend(self, tokens) -> None:
        for t in tokens:
            self.append(int(t))

    def append(self, t: int) -> None:
        self.hist.append(int(t))
        L = len(self.hist)
        for n in range(1, self.ngram_max + 1):
            if L < n:
                break
            start = L - n
            gram = tuple(self.hist[start:])
            m = self._maps[n - 1]
            if gram in m:
                self._prev[n - 1][start] = m[gram]
            m[gram] = start

    def draft(self, m: int) -> np.ndarray:
        """``m`` drafted tokens — same contract (and pinned same
        output) as ``draft_tokens(hist, m, ngram_max)``."""
        L = len(self.hist)
        if m <= 0 or L == 0:
            return np.zeros((max(0, m),), np.int32)
        fill = self.hist[-1]
        for n in range(min(self.ngram_max, L - 1), 0, -1):
            pat = tuple(self.hist[L - n:])
            p = self._maps[n - 1].get(pat)
            if p == L - n:
                # The trailing gram itself — an occurrence needs a
                # continuation token, so step to the previous start
                # (draft_tokens' windows stop at L - n).
                p = self._prev[n - 1].get(p)
            if p is None:
                continue
            cont = self.hist[p + n:p + n + m]
            return np.array(cont + [fill] * (m - len(cont)),
                            np.int32)
        return np.full((m,), fill, np.int32)


# ---------------------------------------------------------------------------
# Program builders (shared by the engine and the planner's stage-2
# serving verifier, serving/disagg.py — the verified program and the
# served program are constructed HERE, once, so they cannot drift)
# ---------------------------------------------------------------------------


def _dp_extent(mesh, dp_axis: str) -> int:
    if mesh is None:
        return 1
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes.get(dp_axis, 1)


def _out_shardings(block, ecfg: EngineConfig, mesh):
    """(per-group result sharding, pool sharding) for the jitted
    programs' ``out_shardings``. Pinning these is load-bearing:
    shard_map's out_specs only fix the MANUAL dp axis, so without an
    explicit jit-level constraint the pool's tp (auto-axis) layout
    could drift between warmup and the storm and force a mid-storm
    recompile. One resolution shared with the cache's device_put
    (kv_cache.pool_sharding)."""
    from distributed_training_tpu.serving.kv_cache import (
        pool_sharding)
    from jax.sharding import NamedSharding, PartitionSpec as P

    if mesh is None:
        return None, None
    G = _dp_extent(mesh, ecfg.dp_axis)
    pool = pool_sharding(mesh, block.cache["n_kv_heads"], G,
                         ecfg.kv_axis, ecfg.dp_axis)
    grp = NamedSharding(mesh, P(ecfg.dp_axis if G > 1 else None))
    return grp, pool


def _cache_config(block, ecfg: EngineConfig, mesh,
                  dtype: str = "float32") -> PagedCacheConfig:
    """The cache of (block, engine cfg, mesh): the block's widths and
    kinds of layer, the engine's geometry. The most rows a launch
    writes of a sequence before it reads is the prefill chunk or the
    speculative width, and the slots a group what a window pool holds a
    ring each of."""
    G = _dp_extent(mesh, ecfg.dp_axis)
    return PagedCacheConfig(
        **block.cache, page_size=ecfg.page_size,
        num_pages=ecfg.num_pages, max_seq_len=ecfg.max_seq_len,
        dtype=dtype, dp_groups=G,
        max_write=max(ecfg.prefill_chunk, ecfg.spec_k),
        slots=ecfg.max_batch // G)


def _plan(block, ecfg: EngineConfig, mesh):
    """What the programs of (block, engine cfg, mesh) know of the cache
    when they are traced (``kv_cache.PoolPlan``): how the pools are
    stored and which layers lie in which — the cache's own choice
    (``PagedKVCache.plan``), static in every program."""
    return PagedKVCache.plan(_cache_config(block, ecfg, mesh),
                             kv_shards(mesh, ecfg.kv_axis))


def _counters(block, plan) -> tuple:
    """Names of the int32 sums every program returns beside its
    tokens: the block's, then the engine's own —
    ``window_bound_iters``, the slot-iterations (lanes of a chunk
    launch, slots of a decode iteration) whose sequence was longer than
    the window, where the block has window layers, and
    ``sparse_bound_iters``, those whose sequence was longer than the
    ``index_topk`` positions a learned selection keeps, where its
    global layers have one."""
    return tuple(block.counters) + (
        ("window_bound_iters",) if plan.window_layers else ()) + (
        ("sparse_bound_iters",) if plan.index_topk else ())


def _named(name: str, body):
    """``body`` under the function name ``name``, for ``jax.jit``: a
    jitted ``functools.partial`` (and a ``shard_map`` of one) has no
    name, so jax calls every engine program ``jit__unknown`` — on the
    trace's ``XLA Modules`` line, in the compile cache's hit and miss
    lists and in compiler errors. The jitted program of ``name`` is
    ``jit_<name>``. ``program`` runs when jax traces it, which is when
    the paged attention inside takes its form from the shapes: that
    form is kept as ``program.paged_form`` (``Engine.paged_forms``)."""
    from distributed_training_tpu.ops.paged_attention import (
        observe_forms)

    def program(*args):
        with observe_forms() as seen:
            out = body(*args)
        program.paged_form = "+".join(sorted(set(seen))) or None
        return out
    program.__name__ = program.__qualname__ = name
    return program


# The slot table a run-ahead engine carries on the device between
# launches (``Engine._slot_state``): history rows (G, B, max_seq_len),
# committed lengths (G, B), tokens each request has left (G, B).
_CARRIED = 3


def carries_slots(ecfg: EngineConfig) -> bool:
    """Do the programs of ``ecfg`` carry the slots' decode state on the
    device? The resident burst does (it drafts from, appends to and
    stops by it in-program), and then the prefill program writes it;
    the one-token and speculative launches are fed from the host."""
    return ecfg.resident_k > 1


def _jit_program(name: str, body, block, ecfg: EngineConfig, mesh,
                 n_grouped: int, n_results: int, params: bool = True,
                 pools: bool = True, carried: bool = False):
    """``body`` jitted as ``jit_<name>``. ``body`` is a group-local
    program: ``params`` first (unless ``params=False``), then
    ``n_grouped`` group-batched arrays (leading dp-group dim): the two
    pools the first of them (unless ``pools=False``), then the carried
    slot table (``carried``: ``_CARRIED`` arrays), all of these donated
    (serving HBM's dominant term must not hold two copies, and the
    table is updated where it lies); it returns ``n_results``
    group-batched results, then the carried table, then the two pools.
    Where the mesh has a dp axis the body runs under a shard_map
    manual over it (specs ``P()`` for the params, ``P(dp)`` for the
    rest); every OTHER mesh axis is an ``auto`` axis — tp's head shard
    (params + pool kv-head dim) stays under the SPMD partitioner
    exactly as in the unsharded engine. The out shardings are pinned
    (``_out_shardings``). What the program takes and returns of the
    engine's state is kept on it (``Engine._call``)."""
    import jax

    first = int(params)    # where the donated state starts
    n_carried = _CARRIED * carried
    n_out = n_results + n_carried + 2 * pools
    kw = {}
    if mesh is not None:
        grp, pool = _out_shardings(block, ecfg, mesh)
        kw["out_shardings"] = ((grp,) * (n_results + n_carried)
                               + (pool, pool) * pools)
    if _dp_extent(mesh, ecfg.dp_axis) > 1:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        grouped = P(ecfg.dp_axis)
        body = shard_map(
            body, mesh=mesh,
            in_specs=(P(),) * first + (grouped,) * n_grouped,
            out_specs=(grouped,) * n_out,
            axis_names={ecfg.dp_axis}, check_vma=False)
    program = _named(name, body)
    program.takes = (params, pools, carried)
    return jax.jit(program, donate_argnums=tuple(
        range(first, first + 2 * pools + n_carried)), **kw)


def build_decode_fn(block, ecfg: EngineConfig, mesh=None):
    """The jitted dp-sharded decode program for (the model's block,
    engine cfg, mesh). Signature (all group-batched, G = dp extent,
    B = group-local slots): ``fn(params, k_pages, v_pages, tokens
    (G, B), positions (G, B), page_tables (G, B, P), active (G, B),
    rng_data (G, 2)) -> (next_tokens (G, B), counts (G, n), k_pages,
    v_pages)``; ``counts`` are the block's ``counters`` summed over the
    launch (n = 0 for a block that counts nothing), in every
    program."""
    import functools

    body = functools.partial(
        _decode_program, block=block,
        plan=_plan(block, ecfg, mesh),
        temperature=ecfg.temperature, top_k=ecfg.top_k)
    return _jit_program("serving_decode", body, block, ecfg, mesh,
                        n_grouped=7, n_results=2)


def _chunk_fn(block, ecfg: EngineConfig, emit: str, name: str,
              mesh=None):
    """Jit the multi-lane chunk program (``_chunk_program``) for
    (model, engine cfg, mesh) as ``jit_<name>``. Signature (all
    group-batched, G = dp extent, S = lanes per group, C = tokens per
    lane):
    ``fn(params, k_pages, v_pages, page_rows (G, S, P),
    tokens (G, S, C), start_pos (G, S), n_valid (G, S),
    active (G, S), rng_data (G, 2)) -> (next_tokens, counts (G, n),
    k_pages, v_pages)`` where next_tokens is (G, S) for
    ``emit="last"`` (the
    batched-prefill first-token sample) and (G, S, C) for
    ``emit="all"`` (the speculative verification chain)."""
    import functools

    body = functools.partial(
        _chunk_program, block=block,
        plan=_plan(block, ecfg, mesh),
        temperature=ecfg.temperature, top_k=ecfg.top_k, emit=emit)
    return _jit_program(name, body, block, ecfg, mesh, n_grouped=8,
                        n_results=2)


def build_prefill_batch_fn(block, ecfg: EngineConfig, mesh=None):
    """The jitted BATCHED multi-sequence prefill program: up to
    ``prefill_slots/dp`` prompt chunks per group in one launch, each
    lane writing its chunk's KV through the batched page-row scatter
    and sampling its next token in-program (the first token of every
    prompt-completing lane — read as one (G, S) int32 block, never a
    vocab-sized logits transfer).

    Where the engine's programs carry the slot table
    (``carries_slots``) this program writes it too, in the same
    launch: the lane's chunk into its decode slot's history row and,
    where the chunk ends the prompt, the sampled token after it, the
    slot's committed length and the tokens the request has left
    (``_prefill_slots_program``), so the burst dispatched next reads
    the first token where it was made. In the prefill program and not
    in one of its own beside it: the chunk and the sample are already
    here, so nothing more is dispatched and no sampled token is handed
    from one program to the next. Signature then:
    ``fn(params, k_pages, v_pages, history (G, B, Lmax), kv_len (G, B),
    left (G, B), <the chunk program's arguments>, slot (G, S),
    max_new (G, S)) -> (next_tokens, counts, history, kv_len, left,
    k_pages, v_pages)``."""
    if not carries_slots(ecfg):
        return _chunk_fn(block, ecfg, emit="last",
                         name="serving_prefill_batch", mesh=mesh)
    import functools

    body = functools.partial(
        _prefill_slots_program, block=block,
        plan=_plan(block, ecfg, mesh),
        temperature=ecfg.temperature, top_k=ecfg.top_k,
        eos_id=ecfg.eos_id)
    return _jit_program("serving_prefill_batch", body, block, ecfg,
                        mesh, n_grouped=13, n_results=2, carried=True)


def build_spec_decode_fn(block, ecfg: EngineConfig, mesh=None):
    """The jitted MULTI-TOKEN speculative decode program: ``spec_k``
    tokens per slot across the whole dealt slot table in one launch —
    lane c's argmax is the verified next token GIVEN the drafted
    prefix, so the host accepts exactly the prefix whose drafts match
    the chain (greedy-token-identical by construction)."""
    return _chunk_fn(block, ecfg, emit="all",
                     name="serving_spec_decode", mesh=mesh)


def build_resident_decode_fn(block, ecfg: EngineConfig,
                             mesh=None):
    """The jitted DEVICE-RESIDENT decode program: a
    ``lax.while_loop`` of up to ``resident_k`` chunk iterations
    (each one a ``spec_k``-wide speculative step — the same
    ``_chunk_hidden`` math as the host-driven paths), drafting,
    verifying, stop-detecting (EOS / budget) and advancing each
    slot's page cursor IN-PROGRAM, on the slot table it takes donated
    and hands back. The host syncs once per burst, and packs the next
    burst without that sync.

    Signature (all group-batched, G = dp extent, B = group-local
    slots, Lmax = max_seq_len, T = resident_k * spec_k):
    ``fn(params, k_pages, v_pages, history (G, B, Lmax), kv_len (G, B),
    left (G, B), page_rows (G, B, P), budget (G, B), active (G, B)) ->
    (out (G, B, T), n_emitted (G, B), steps (G,), counts (G, n),
    history, kv_len, left, k_pages, v_pages)``. An
    all-slots-complete burst returns early via the loop predicate."""
    import functools

    body = functools.partial(
        _resident_program, block=block,
        plan=_plan(block, ecfg, mesh), K=ecfg.resident_k,
        C=ecfg.spec_k, ngram=SPEC_NGRAM, eos_id=ecfg.eos_id)
    return _jit_program("serving_resident_decode", body, block, ecfg,
                        mesh, n_grouped=8, n_results=4, carried=True)


def _seed_program(history, kv_len, left, rows, kv, left_new, live):
    """Overwrite the ``live`` slots of the carried slot table with what
    the host uploads: ``rows`` (G, B, Lmax) their histories, ``kv`` /
    ``left_new`` (G, B) their committed lengths and tokens left. Every
    other slot passes through."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("dtt.engine"):
        return (jnp.where(live[..., None], rows, history),
                jnp.where(live, kv, kv_len),
                jnp.where(live, left_new, left))


def build_seed_fn(block, ecfg: EngineConfig, mesh=None):
    """The jitted upload into the carried slot table, for a slot that
    starts with tokens no prefill launch of this engine wrote.
    Signature: ``fn(history, kv_len, left, rows (G, B, Lmax),
    kv (G, B), left_new (G, B), live (G, B)) -> (history, kv_len,
    left)``."""
    return _jit_program("serving_seed", _seed_program, block, ecfg,
                        mesh, n_grouped=7, n_results=0, params=False,
                        pools=False, carried=True)


def _cow_program(k_pages, v_pages, src, dst):
    """Copy-on-write page copy for one dp group's pool shard:
    ``k/v_pages`` the group's pools (leading dim 1), ``src``/``dst``
    (1, W) int32 page ids. One batched gather + scatter per pool — W
    page copies in ONE launch, no per-token host sync, zero
    collectives (pages never cross a group shard). Unused lanes ride
    as (0 -> 0): a scratch-to-scratch identity copy, the same
    dead-write trick as the decode program's inactive slots."""
    import jax

    s, d = src[0], dst[0]
    with jax.named_scope("dtt.kv.write"):
        return (copy_pages(k_pages[0], s, d)[None],
                copy_pages(v_pages[0], s, d)[None])


def build_cow_fn(block, ecfg: EngineConfig, mesh=None):
    """The jitted COW page-copy program. Signature:
    ``fn(k_pages, v_pages, src (G, W), dst (G, W)) -> (k_pages,
    v_pages)``, fixed W so a storm's forks never change a traced
    shape."""
    return _jit_program("serving_cow", _cow_program, block, ecfg, mesh,
                        n_grouped=4, n_results=0, params=False)


class Engine:
    """The continuous-batching engine over one model + weight set.

    ``params`` should already be placed (serving/disagg.py
    ``place_params`` for a planned layout); ``mesh`` shards the KV
    pool's kv-head axis over ``cfg.kv_axis`` and the slot table +
    pool's group axis over ``cfg.dp_axis`` (each axis when its extent
    is > 1). ``telemetry`` rides the ambient sink
    (telemetry/events.py) — every step emits a ``serving`` record the
    metrics endpoint folds into the ``dtt_serving_*`` gauges,
    per-group stats included.
    """

    def __init__(self, model, params, cfg: EngineConfig,
                 mesh=None, weights_version: str = "v0",
                 weights_provenance: dict | None = None):
        import jax

        # The model's side of every program (serving/blocks.py). A
        # model that cannot be served (experts that drop tokens) is
        # refused by its own ``serving_block``.
        self.block = model.serving_block()
        if cfg.max_seq_len > model.cfg.max_seq_len:
            raise ValueError(
                f"engine max_seq_len ({cfg.max_seq_len}) exceeds the "
                f"model's ({model.cfg.max_seq_len})")
        self.model = model
        self.cfg = cfg
        self.mesh = mesh
        self.params = params
        # Live-swap state (``swap_weights`` is the ONLY other place
        # allowed to rebind ``self.params`` — pitfalls rule DTT011).
        self.weights_version = weights_version
        self.weights_provenance = (dict(weights_provenance)
                                   if weights_provenance else None)
        self.swap_stats = {"installed": 0, "refused": 0,
                           "stale_preempted": 0}
        self.dp_groups = _dp_extent(mesh, cfg.dp_axis)
        if cfg.max_batch % self.dp_groups:
            raise ValueError(
                f"max_batch ({cfg.max_batch}) must divide over the "
                f"{self.dp_groups} dp group(s) — the slot table is "
                "dealt into equal group-local tables")
        self.batch_local = cfg.max_batch // self.dp_groups
        prefill_slots = cfg.prefill_slots or cfg.max_batch
        if prefill_slots % self.dp_groups:
            raise ValueError(
                f"prefill_slots ({prefill_slots}) must divide over "
                f"the {self.dp_groups} dp group(s) — the prefill "
                "lane table deals exactly like the decode table")
        self.prefill_local = prefill_slots // self.dp_groups
        # What the engine's time since the last retire leaves for the
        # next step record (the record is the only ledger: totals are
        # sums over records): when that stretch began, the phases'
        # seconds and the syncs in it, and the fields that only the
        # retired launch's cadence has (speculative acceptance,
        # resident loop depth, slots and iterations, first tokens).
        self._tile_t0 = time.monotonic()
        self._tile_syncs0 = 0
        self._phase_s = dict.fromkeys(_PHASES, 0.0)
        self._step_counts: dict = {}
        # The launch dispatched and not yet retired (run-ahead engines;
        # None between the steps of every other), and the sequences
        # whose rows the slot table lacks, for ``_page_rows`` to upload
        # before the launch that claimed them.
        self._flying: _Launch | None = None
        self._unseeded: list[_Seq] = []
        # Prefix sharing + chat sessions (SERVING_r05). ``sessions``
        # maps session key -> retained state (cache id holding the
        # parked pages, the full token history they cover, the owning
        # dp group, last-use time for LRU eviction under pool
        # pressure). The stats totals feed the bench ledger; the
        # per-step pair feeds the step record the metrics endpoint
        # folds into the dtt_serving_prefix_* counters.
        self._sharing = cfg.prefix_sharing
        self.sessions: dict[str, dict] = {}
        self.prefix_stats = {"hit_tokens": 0, "saved_tokens": 0,
                             "cow_pages": 0, "session_resumes": 0}
        self._step_prefix = [0, 0]
        # Prefill-compute accounting for the sharing win: prompt
        # tokens actually pushed through a prefill program, and
        # prefill program launches (a zero-prefill session re-attach
        # must not move either).
        self.prefill_tokens_computed = 0
        self.prefill_launches = 0
        self._cow_width = max(self.batch_local, self.prefill_local)
        # EVERY device->host sync in the serving hot path goes
        # through ``_fetch_host`` (pitfalls rule DTT010), so this
        # counter is exact — the bench asserts syncs <= tokens /
        # resident_k + completions.
        self.host_syncs = 0
        self.weight_bytes = int(sum(
            getattr(x, "nbytes", 0)
            for x in jax.tree.leaves(params)))
        self.cache = PagedKVCache(
            _cache_config(self.block, cfg, mesh, model.cfg.dtype),
            mesh=mesh, kv_axis=cfg.kv_axis, dp_axis=cfg.dp_axis)
        if cfg.prefix_sharing:
            # Refused by name where the block has window layers.
            self.cache.full_tables_only(
                "EngineConfig.prefix_sharing (the prefix index, "
                "copy-on-write and retained sessions; pass "
                "prefix_sharing=False)")
        self._plan = _plan(self.block, cfg, mesh)
        self._counters = _counters(self.block, self._plan)
        self.queue: collections.deque[Request] = collections.deque()
        self._admitted = 0            # admissions so far (_Seq.admitted_n)
        self.slots: list[_Seq | None] = [None] * cfg.max_batch
        self.completed: list[dict] = []
        self._step_counter = 0
        self._base_rng = jax.random.PRNGKey(cfg.seed)
        self._token_listeners: dict[str, object] = {}
        # Exactly-once stream state: per-request emitted-token
        # high-water mark. SURVIVES preemption (unlike the listener
        # registry) so a resubmitted request's regenerated prefix —
        # greedy decode makes it token-identical — is never delivered
        # twice; popped only at completion. ``finished_total`` is the
        # monotone progress counter the serving supervisor's restart
        # budget refunds against.
        self._emit_hwm: dict[str, int] = {}
        self.finished_total = 0
        # Drain / fault-injection state: ``draining`` gates admission
        # only (in-flight work keeps stepping); ``launch_count`` is
        # the serving analogue of the global step — one per non-idle
        # step — that ``resilience/faults.py`` serving kinds key on
        # via the ``faults`` injector slot (None = no injection).
        self.draining = False
        self.launch_count = 0
        self.faults = None
        self._build_programs()
        self._slot_state = self._new_slot_state()
        # Greedy decode never reads the rng operand — fold_in/
        # key_data are ~5 device dispatches PER STEP, and on the CPU
        # mesh that was ~40% of the decode step's wall clock
        # (SERVING_r02's dispatch-bound profile). One cached zero key
        # per group replaces them when temperature == 0.
        import jax.numpy as jnp
        self._zero_rng = jnp.zeros((self.dp_groups, 2), jnp.uint32)

    # -- jitted programs ---------------------------------------------------

    def _build_programs(self) -> None:
        block = self.block
        # How far the engine runs ahead follows from the programs it
        # builds: one launch where they carry the slots' state on the
        # device (launch n+1 is dispatched before launch n is fetched,
        # ``_step``), none where the host must read a token to pack
        # the next launch.
        self._run_ahead = carries_slots(self.cfg)
        if self.cfg.resident_k > 1:
            # The device-resident K-step loop IS the decode program:
            # each loop iteration is one spec_k-wide chunk (spec_k=1
            # degenerates to plain one-token steps), so speculation
            # composes inside the burst. One jit entry, one sync per
            # burst.
            self._decode_fn = build_resident_decode_fn(
                block, self.cfg, self.mesh)
            self._run_decode = self._run_decode_resident
        elif self.cfg.spec_k > 1:
            # Multi-token decode IS the chunk program at C = spec_k
            # (even an effective one-token launch — pages tight, or
            # one token remaining — rides it with n_valid = 1: one
            # program, one jit entry, zero recompiles).
            self._decode_fn = build_spec_decode_fn(block, self.cfg,
                                                   self.mesh)
            self._run_decode = self._run_decode_spec
        else:
            # One token a slot a launch: the one cadence that samples
            # (``temperature > 0``).
            self._decode_fn = build_decode_fn(block, self.cfg,
                                              self.mesh)
            self._run_decode = self._run_decode_token
        self._prefill_batch_fn = build_prefill_batch_fn(
            block, self.cfg, mesh=self.mesh)
        if self._run_ahead:
            self._seed_fn = build_seed_fn(block, self.cfg, self.mesh)
        if self._sharing:
            self._cow_fn = build_cow_fn(block, self.cfg, mesh=self.mesh)

    def _programs(self) -> dict:
        """Every jitted program this engine built, by its role."""
        fns = {"decode": self._decode_fn,
               "prefill_batch": self._prefill_batch_fn}
        if self._run_ahead:
            fns["seed"] = self._seed_fn
        if self._sharing:
            fns["cow"] = self._cow_fn
        return fns

    def compile_counts(self) -> dict:
        """Jit-cache sizes per program — the bench's zero-recompile
        assertion compares this dict before/after the storm."""
        return {role: fn._cache_size()
                for role, fn in self._programs().items()}

    def paged_forms(self) -> dict:
        """``{program: paged_form}`` for every program traced so far,
        under the names the trace shows less ``jit_``
        (``serving_resident_decode``, ``serving_prefill_batch``, ...):
        ``"pool"`` or ``"gather"``, over a latent cache ``"absorbed"``
        or ``"expanded"`` (ops/paged_attention.py), fixed by the shapes
        when the program was traced; ``None`` for a program that reads
        no pool (``serving_cow``). Read-only."""
        return {fn.__wrapped__.__name__: fn.__wrapped__.paged_form
                for fn in self._programs().values()
                if hasattr(fn.__wrapped__, "paged_form")}

    def _new_slot_state(self) -> tuple:
        """The carried slot table, empty (``_CARRIED`` distinct
        buffers: each is donated on its own), laid out as the programs
        return it; nothing where no program carries it."""
        import jax
        import jax.numpy as jnp

        if not self._run_ahead:
            return ()
        G, B = self.dp_groups, self.batch_local
        grp, _pool = _out_shardings(self.block, self.cfg, self.mesh)
        return tuple(
            jax.device_put(z, grp) if grp is not None else z
            for z in (jnp.zeros((G, B, self.cfg.max_seq_len), jnp.int32),
                      jnp.zeros((G, B), jnp.int32),
                      jnp.zeros((G, B), jnp.int32)))

    def _state_of(self, fn) -> tuple:
        """What ``fn`` takes of the engine's state, in its order: the
        params, the two pools, the carried slot table (the last two
        donated: ``_call`` adopts what comes back)."""
        params, pools, carried = fn.__wrapped__.takes
        state = (self.params,) if params else ()
        if pools:
            state += (self.cache.k_pages, self.cache.v_pages)
        if carried:
            state += self._slot_state
        return state

    def _call(self, fn, *args) -> list:
        """One dispatch of ``fn`` on the state it takes and ``args``.
        The pools and the slot table it returns are adopted; the rest,
        its results, come back as they are: device arrays, not
        fetched."""
        outs = list(fn(*self._state_of(fn), *args))
        _params, pools, carried = fn.__wrapped__.takes
        if pools:
            self.cache.update_pools(*outs[-2:])
            del outs[-2:]
        if carried:
            self._slot_state = tuple(outs[-_CARRIED:])
            del outs[-_CARRIED:]
        return outs

    def _warmup_calls(self):
        """``(program, arguments)`` for every program this engine
        built, against scratch-only page rows and all-dead lanes (zero
        allocator side effects: every write lands in each group's
        scratch page, and no slot of the carried table is live). The
        arguments past the engine's own state (``_state_of``), which
        ``warmup`` threads from call to call."""
        import jax.numpy as jnp

        G, B = self.dp_groups, self.batch_local
        P = self.cache.cfg.row_width
        C = self.cfg.prefill_chunk
        rng = jnp.zeros((G, 2), jnp.uint32)

        def zeros(*shape, dtype=jnp.int32):
            return jnp.zeros(shape, dtype)

        if self.cfg.resident_k > 1:
            # All-dead burst: zero budgets fail the loop predicate at
            # iteration 0 (the all-slots-complete early exit), but
            # tracing still compiles the full resident body.
            yield self._decode_fn, (
                zeros(G, B, P), zeros(G, B),
                zeros(G, B, dtype=jnp.bool_))
        elif self.cfg.spec_k > 1:
            yield self._decode_fn, (
                zeros(G, B, P), zeros(G, B, self.cfg.spec_k),
                zeros(G, B), zeros(G, B),
                zeros(G, B, dtype=jnp.bool_), rng)
        else:
            yield self._decode_fn, (
                zeros(G, B), zeros(G, B), zeros(G, B, P),
                zeros(G, B, dtype=jnp.bool_), rng)
        Sp = self.prefill_local
        yield self._prefill_batch_fn, (
            zeros(G, Sp, P), zeros(G, Sp, C), zeros(G, Sp),
            zeros(G, Sp), zeros(G, Sp, dtype=jnp.bool_), rng,
            *((zeros(G, Sp), zeros(G, Sp)) if self._run_ahead else ()))
        if self._run_ahead:
            yield self._seed_fn, (
                zeros(G, B, self.cfg.max_seq_len), zeros(G, B),
                zeros(G, B), zeros(G, B, dtype=jnp.bool_))
        if self._sharing:
            # Scratch-to-scratch identity copies.
            W = self._cow_width
            yield self._cow_fn, (zeros(G, W), zeros(G, W))

    def warmup(self) -> dict:
        """Compile every program (``_warmup_calls``) and emit one
        ``serving_warmup`` record with each program's ``paged_form``,
        the cache's kind, its bytes a token and what the pools take as
        stored (``PagedKVCache.footprint``). Where a sink records it, each
        program is compiled once more ahead of time for its
        ``temp_bytes`` (``compiled.memory_analysis()``: a program whose
        temporaries reach the pool's bytes holds a copy of it) and for
        one ``program_scopes`` record, the map from its HLO instructions
        to the ``dtt.*`` scopes they lie in
        (``telemetry/op_scopes.py::scope_map`` of
        ``compiled.as_text()``; a text that names no scope, an
        executable that kept no metadata, gives no record), both
        compiles under ``own_metadata``; with no sink nothing is
        compiled twice and nothing is parsed. Returns
        compile_counts()."""
        import jax

        temp_bytes = {}
        for fn, args in self._warmup_calls():
            if not current().enabled:
                self._call(fn, *args)
                continue
            # Shapes, taken before the call donates the state.
            shapes = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=a.sharding)
                if isinstance(a, jax.Array) else a,
                (*self._state_of(fn), *args))
            name = fn.__wrapped__.__name__
            # The text and the executable that runs are one compile's,
            # this tree's, whatever a shared compile cache holds.
            with own_metadata():
                compiled = fn.lower(*shapes).compile()
                self._call(fn, *args)
            temp_bytes[name] = getattr(
                compiled.memory_analysis(), "temp_size_in_bytes", None)
            scopes = scope_map(compiled.as_text())
            if set(scopes["scopes"]) - {UNSCOPED}:
                event("program_scopes", program=name, **scopes)
        event("serving_warmup",
              programs=[{"program": name, "paged_form": form,
                         "temp_bytes": temp_bytes.get(name)}
                        for name, form in self.paged_forms().items()],
              cache_kind=self.cache.cfg.kind,
              cache_bytes_per_token=self.cache.cfg.kv_bytes_per_token(),
              pools=self.cache.pools(), **self.cache.footprint())
        return self.compile_counts()

    # -- admission ---------------------------------------------------------

    def _validate(self, req: Request) -> None:
        if req.prompt.shape[0] == 0:
            raise ValueError(f"request {req.id}: empty prompt")
        if req.max_new_tokens < 1:
            raise ValueError(
                f"request {req.id}: max_new_tokens must be >= 1")
        if req.session is not None:
            self.cache.full_tables_only(
                f"request {req.id}: a retained session")
        total = req.prompt.shape[0] + req.max_new_tokens
        if total > self.cfg.max_seq_len:
            raise ValueError(
                f"request {req.id}: prompt ({req.prompt.shape[0]}) + "
                f"max_new_tokens ({req.max_new_tokens}) exceeds "
                f"max_seq_len ({self.cfg.max_seq_len})")

    def submit(self, req: Request) -> None:
        req.submitted = time.monotonic()
        if req.arrival is None:
            req.arrival = req.submitted
        self._validate(req)
        self.queue.append(req)

    # -- request-lifecycle tracing ------------------------------------------
    #
    # Spans are host-side list appends at points the admission /
    # launch bookkeeping already occupies; timestamps reuse the
    # monotonic reads the engine already takes after ``_fetch_host``
    # where one exists. Zero device syncs (DTT010), zero new jit
    # entries, and the only write path is telemetry.event() — see
    # telemetry/serving_trace.py for the schema the analyzer pins.

    def _mark_admitted(self, seq: _Seq, ev: str, **fields) -> None:
        """Open a sequence's trace: queued at t=0 (arrival),
        ``submitted`` where ``submit`` stamped the request (an adopted
        or staleness-requeued one never passed it), then the
        admission span (``admitted`` / ``resumed`` / ``adopted``).
        ``queue_wait_s`` is fixed here — a resubmitted-after-preempt
        request keeps its ORIGINAL arrival, so its second trace shows
        the full wait including the lost first pass."""
        now = time.monotonic()
        seq.admitted_n = self._admitted
        self._admitted += 1
        seq.trace.append({"ev": "queued", "t": 0.0})
        if seq.req.submitted is not None:
            seq.span("submitted", seq.req.submitted)
        seq.span(ev, now, slot=seq.slot, **fields)
        if seq.req.arrival is not None:
            seq.queue_wait_s = now - seq.req.arrival
        seq.prefix_hit = int(fields.get("prefix_hit_tokens")
                             or fields.get("hit_tokens") or 0)

    def _emit_trace(self, seq: _Seq, outcome: str, now: float,
                    tokens_discarded: int = 0) -> None:
        """Close a sequence's trace and emit the ``serving_trace``
        record through the ambient sink. ``now`` is a timestamp the
        caller already took (post-fetch or preempt bookkeeping)."""
        seq.span(outcome, now,
                 **({"tokens_discarded": tokens_discarded}
                    if outcome == "preempted" else {}))
        arrival = seq.req.arrival
        ttft = None
        if seq.first_token_t is not None and arrival is not None:
            ttft = seq.first_token_t - arrival
        event("serving_trace",
              id=seq.req.id,
              tenant=seq.req.tenant,
              outcome=outcome,
              prompt_tokens=seq.prompt_len,
              new_tokens=len(seq.generated),
              queue_wait_s=seq.queue_wait_s,
              ttft_s=ttft,
              e2e_s=(now - arrival) if arrival is not None else None,
              prefix_hit_tokens=seq.prefix_hit,
              tokens_discarded=tokens_discarded,
              weights_versions=[list(p) for p in seq.versions],
              spans=list(seq.trace))

    def add_token_listener(self, req_id: str, fn) -> None:
        """Register ``fn(token: int, done: bool)`` to fire as each of
        ``req_id``'s tokens is sampled (the HTTP streaming path).
        Dropped automatically when the request completes; listener
        exceptions are logged, never raised into the step loop."""
        self._token_listeners[req_id] = fn

    def remove_token_listener(self, req_id: str) -> None:
        self._token_listeners.pop(req_id, None)

    def _emit_token(self, seq: _Seq, token: int) -> None:
        # Tag EVERY emitted token with the live weight version
        # (run-length on the sequence — the trace/debug surfaces
        # decode it), listener or not.
        if not seq.versions or \
                seq.versions[-1][0] != self.weights_version:
            seq.versions.append([self.weights_version, 0])
        seq.versions[-1][1] += 1
        # Exactly-once gate: this token's index vs the request's
        # high-water mark. A replayed prefix (preempt-resubmit or
        # crash re-adoption regenerates tokens already emitted —
        # greedy-identical values) advances the slot state but is NOT
        # re-delivered.
        idx = len(seq.generated) - 1
        hwm = self._emit_hwm.get(seq.req.id, 0)
        fresh = idx >= hwm
        if fresh:
            self._emit_hwm[seq.req.id] = idx + 1
        fn = self._token_listeners.get(seq.req.id)
        if fn is not None and fresh:
            try:
                fn(int(token), seq.done)
            except Exception:
                logger.exception("token listener for %r failed; "
                                 "dropping it", seq.req.id)
                self._token_listeners.pop(seq.req.id, None)
        if seq.done:
            self._token_listeners.pop(seq.req.id, None)

    @property
    def in_flight(self) -> int:
        return sum(1 for s in self.slots if s is not None)

    @property
    def idle(self) -> bool:
        """Nothing queued, no slot held and no launch in flight (a
        launch not yet retired still owes its tokens)."""
        return (not self.queue and self.in_flight == 0
                and self._flying is None)

    def group_of_slot(self, slot: int) -> int:
        return slot // self.batch_local

    def slots_active_by_group(self) -> list[int]:
        B = self.batch_local
        return [sum(1 for s in self.slots[g * B:(g + 1) * B]
                    if s is not None)
                for g in range(self.dp_groups)]

    def _free_slot(self, group: int | None = None) -> int | None:
        B = self.batch_local
        if group is None:
            for i, s in enumerate(self.slots):
                if s is None:
                    return i
            return None
        for i in range(group * B, (group + 1) * B):
            if self.slots[i] is None:
                return i
        return None

    def _pick_group(self, first_tokens: int) -> tuple[int, int] | None:
        """Admission load balancing: the fewest-active-slots group
        (ties to the lowest index) that has BOTH a free slot and pages
        for the first chunk. None = every group is full/backpressured
        (the request stays queued)."""
        active = self.slots_active_by_group()
        order = sorted(range(self.dp_groups),
                       key=lambda g: (active[g], g))
        for g in order:
            slot = self._free_slot(g)
            if slot is None:
                continue
            if not self.cache.can_admit(first_tokens, group=g):
                continue
            return g, slot
        return None

    def _admit(self) -> _Seq | None:
        """Move the head-of-queue request into a free slot. With
        prefix sharing the placement prefers the group holding the
        LONGEST resident page-aligned prefix of the prompt (the new
        sequence attaches those pages read-only and prefills only the
        unmatched tail — a full cover prefills nothing); with no hit
        anywhere it falls back to fewest-active-slots-first, exactly
        the pre-sharing balancing. A session request whose retained
        turn is resident resumes in ITS group (pages cannot cross a
        pool shard) or waits for a slot there. None = backpressure —
        the request stays queued."""
        if self.draining or not self.queue:
            return None
        req = self.queue[0]
        plen = int(req.prompt.shape[0])
        first = min(plen, self.cfg.prefill_chunk)
        if not self._sharing:
            picked = self._pick_group(first)
            if picked is None:
                return None
            group, slot = picked
            self.queue.popleft()
            self.cache.join(req.id, group=group)
            self.cache.ensure(req.id, first)
            seq = _Seq(req=req, slot=slot)
            self._mark_admitted(seq, "admitted", group=group,
                                prefix_hit_tokens=0)
            self.slots[slot] = seq
            return seq
        if req.session is not None and req.session in self.sessions:
            res = self._try_resume(req)
            if res is not None:
                return None if res == "wait" else res
            # retained turn diverged from this prompt — it was
            # dropped; fall through to the normal path (the prefix
            # index may still cover part of the prompt).
        ps = self.cfg.page_size
        active = self.slots_active_by_group()
        order = sorted(range(self.dp_groups),
                       key=lambda g: (active[g], g))
        best = None      # (m, pages, group, slot), longest match wins
        starved = None   # best candidate short on pages (sessions
        for g in order:  # may be evictable — deferred to the pick)
            slot = self._free_slot(g)
            if slot is None:
                continue
            pages, m = self.cache.match_prefix(g, req.prompt)
            if m * ps >= plen:
                need = 1  # COW headroom for the boundary replay
            elif m:
                tgt = min(plen, m * ps + self.cfg.prefill_chunk)
                need = -(-tgt // ps) - m
            else:
                need = -(-first // ps)
            if need > self.cache.free_pages_in(g):
                if starved is None or m > starved[0]:
                    starved = (m, pages, g, slot, need)
                continue
            if best is None or m > best[0]:
                best = (m, pages, g, slot)
            if best[0] == 0:
                break  # no hit and the balanced pick already found
        if best is None and starved is not None:
            # Every slot-holding group is short on pages; evict idle
            # sessions (LRU) in the best starved group's shard before
            # giving up — retained pages must never wedge admission.
            # Re-match afterwards: the eviction may have freed the
            # very pages the match pointed at.
            m, pages, g, slot, need = starved
            if self._evict_sessions(g, need):
                pages, m = self.cache.match_prefix(g, req.prompt)
                if m * ps >= plen or m or \
                        self.cache.can_admit(first, group=g):
                    best = (m, pages, g, slot)
        if best is None:
            return None
        m, pages, group, slot = best
        self.queue.popleft()
        self.cache.join(req.id, group=group)
        seq = _Seq(req=req, slot=slot)
        if m * ps >= plen:
            # Full page-aligned cover: ZERO prefill — attach all the
            # pages at length plen - 1 and let the first decode
            # replay the last prompt token (COW forks the boundary
            # page; the sampled token is the prefill's first token).
            self.cache.attach(req.id, pages, plen - 1)
            seq.prefilled = plen
            hit = plen
        elif m:
            self.cache.attach(req.id, pages, m * ps)
            self.cache.ensure(
                req.id, min(plen, m * ps + self.cfg.prefill_chunk))
            seq.prefilled = m * ps
            hit = m * ps
        else:
            self.cache.ensure(req.id, first)
            hit = 0
        if hit:
            self.prefix_stats["hit_tokens"] += hit
            self.prefix_stats["saved_tokens"] += hit
            self._step_prefix[0] += hit
            self._step_prefix[1] += hit
        self._mark_admitted(seq, "admitted", group=group,
                            prefix_hit_tokens=hit)
        self.slots[slot] = seq
        return seq

    # -- prefix sharing / sessions -----------------------------------------

    def _try_resume(self, req: Request):
        """Re-attach a retained session turn. Returns the installed
        ``_Seq``, ``"wait"`` (the session's group has no free slot —
        stay queued; its pages live in ONE pool shard), or None (the
        prompt diverged from the retained history, which was just
        dropped)."""
        key = req.session
        sess = self.sessions[key]
        hist = sess["history"]
        hl = int(hist.shape[0])
        prompt = np.array(req.prompt, np.int32)
        plen = int(prompt.shape[0])
        if hl > plen or not np.array_equal(prompt[:hl], hist):
            self._drop_session(key)
            return None
        slot = self._free_slot(sess["group"])
        if slot is None:
            return "wait"
        self.queue.popleft()
        del self.sessions[key]
        self.cache.rename(sess["cache_id"], req.id)
        # Retained length is hl - 1 (the last generated token was
        # sampled but its KV never written — decode's standard
        # frontier). Exact match: prefilled = plen, zero prefill
        # launches, decode replays prompt[-1]. Extended: the tail
        # from position hl - 1 prefills as a continuation chunk.
        exact = plen == hl
        seq = _Seq(req=req, slot=slot,
                   prefilled=plen if exact else hl - 1)
        self.slots[slot] = seq
        saved = plen if exact else hl - 1
        self._mark_admitted(seq, "resumed", group=sess["group"],
                            session=key, hit_tokens=saved)
        self.prefix_stats["session_resumes"] += 1
        self.prefix_stats["hit_tokens"] += saved
        self.prefix_stats["saved_tokens"] += saved
        self._step_prefix[0] += saved
        self._step_prefix[1] += saved
        return seq

    def _drop_session(self, key: str) -> None:
        sess = self.sessions.pop(key)
        self.cache.free(sess["cache_id"])

    def _evict_sessions(self, group: int, need: int) -> bool:
        """Free retained sessions in ``group`` (LRU first) until
        ``need`` pages are free. Returns True when satisfied.
        Sessions sharing pages with live sequences release only
        their unshared pages (refcounts protect the rest) — the loop
        keeps evicting until the target is met or no session in the
        group remains."""
        while self.cache.free_pages_in(group) < need:
            cands = sorted(
                (s["t"], k) for k, s in self.sessions.items()
                if s["group"] == group)
            if not cands:
                return False
            self._drop_session(cands[0][1])
        return True

    def _cow_guard(self, seq_id) -> list | None:
        """Privatize any shared page the next write into ``seq_id``
        would touch. Returns the (src, dst) page pairs for
        ``_apply_cow`` ([] = nothing shared), or None when the fork
        stalled on free pages even after evicting an idle session
        (the sequence skips this launch)."""
        pairs = self.cache.privatize(seq_id)
        if pairs is None:
            self._evict_sessions(self.cache.group_of(seq_id), 1)
            pairs = self.cache.privatize(seq_id)
        return pairs

    def _apply_cow(self, pairs: list) -> None:
        """ONE fixed-shape launch copying every forked page:
        ``pairs`` is [(group, src_page, dst_page)]. Unused lanes stay
        (0 -> 0) scratch identities, so fork count never changes a
        traced shape."""
        import jax.numpy as jnp

        G, W = self.dp_groups, self._cow_width
        with self._phase("pack"):
            src = np.zeros((G, W), np.int32)
            dst = np.zeros((G, W), np.int32)
            fill = [0] * G
            for g, a, b in pairs:
                src[g, fill[g]] = a
                dst[g, fill[g]] = b
                fill[g] += 1
        with self._phase("launch"):
            self._call(self._cow_fn, jnp.asarray(src), jnp.asarray(dst))
        self.prefix_stats["cow_pages"] += len(pairs)

    def _seed(self, seqs: list) -> None:
        """Upload into the carried slot table the rows of ``seqs``,
        sequences that begin with tokens no prefill launch of this
        engine wrote (a prefix hit, a resumed session, an adopted or
        re-adopted sequence): the whole history the host knows, the
        committed length and the tokens left. Once a sequence, before
        the first launch that packs it (``_claim``), so none of its
        own is in flight and the host's numbers are exact; from then
        on its prefill chunks and bursts keep the row on the device."""
        import jax.numpy as jnp

        with self._phase("pack"):
            G, B = self.dp_groups, self.batch_local
            rows = np.zeros((G, B, self.cfg.max_seq_len), np.int32)
            kv = np.zeros((G, B), np.int32)
            left = np.zeros((G, B), np.int32)
            live = np.zeros((G, B), bool)
            for s in seqs:
                at = divmod(s.slot, B)
                hist = np.concatenate([
                    np.array(s.req.prompt, np.int32),
                    np.array(s.generated, np.int32)])
                rows[at][:hist.shape[0]] = hist
                kv[at] = self.cache.length(s.req.id)
                left[at] = s.req.max_new_tokens - len(s.generated)
                live[at] = True
        with self._phase("launch"):
            self._call(self._seed_fn, *(jnp.asarray(a) for a in
                                        (rows, kv, left, live)))

    def _register(self, seq: _Seq) -> None:
        """Index the sequence's newly committed page-aligned
        prefixes so later prompts can attach them. Skipped when the
        pages are about to be freed anyway (finished, no session)."""
        if not self._sharing:
            return
        if seq.done and seq.req.session is None:
            return
        if not self.cache.needs_register(seq.req.id):
            return
        self.cache.register_prefix(
            seq.req.id,
            np.concatenate([np.array(seq.req.prompt, np.int32),
                            np.array(seq.generated, np.int32)]))

    # -- step --------------------------------------------------------------

    def _prefill_candidates(self) -> list[_Seq]:
        """Slots still in their prompt, in the order they were
        admitted: the prefill lanes go first come, first served, and
        not to whoever landed in the lowest slot (which slot a request
        finds free is chance, so the order of two waiting prompts, and
        with it every time after, would be too)."""
        return sorted((s for s in self.slots
                       if s is not None and not s.prefill_done),
                      key=lambda s: s.admitted_n)

    def _decode_candidates(self) -> list[_Seq]:
        """Slots past their prompt that may be given a budget: not
        ended, and with tokens left beyond what launches in flight may
        emit (the projection; nothing is in flight where the engine
        does not run ahead)."""
        return [s for s in self.slots
                if s is not None and s.prefill_done and not s.eos
                and s.left > 0]

    def _phase(self, key: str) -> phase:
        """One of the five parts of a step (``_PHASES``): the
        ``serving.<key>`` trace annotation, its seconds added to the
        step record's ``phase_s[key]``. The parts never nest, so they
        sum to at most ``dur_s``."""
        return phase("serving." + key, self._phase_s, key)

    def step(self) -> dict:
        """One scheduling decision, one compiled program launch
        dispatched and one retired. Returns the record of the launch
        it retired (``op``: prefill/decode; idle when there was
        none)."""
        with phase("serving.step"):
            return self._step()

    def _step(self) -> dict:
        """Admit, pack and dispatch the next launch, then retire the
        launch before it: fetch it (the one sync), emit its tokens.
        Where the programs carry the slots' state (``_run_ahead``) the
        launch just dispatched stays in flight while the one before is
        fetched and emitted, so the device has the next launch queued
        when a launch ends and the host's bookkeeping of a burst runs
        beside the next burst; a step that finds nothing in flight
        fills the pipeline first (dispatches twice), and one with
        nothing to dispatch retires what is in flight. Everywhere
        else the launch is retired at once: the host needs its tokens
        to pack the next."""
        if self._flying is None:
            self._tile_t0 = time.monotonic()
        launch = self._dispatch()
        if self._run_ahead:
            if self._flying is None and launch is not None:
                self._flying = launch
                launch = self._dispatch()
            launch, self._flying = self._flying, launch
        return self._retire(launch)

    def _dispatch(self) -> _Launch | None:
        """The front half of a step: admission, the scheduling
        decision, and the launch it leads to, dispatched and not
        fetched. None = nothing to launch (idle, or every candidate
        stalled on pages)."""
        with self._phase("admit"):
            pending = self._prefill_candidates()
            can_admit = (not self.draining and self.queue
                         and self._free_slot() is not None)
            decodable = self._decode_candidates()
            # Pending prompt work runs before decode.
            kind = "prefill" if pending or can_admit else (
                "decode" if decodable else "idle")
            if kind == "prefill":
                # Admit everything slots+pages allow BEFORE the
                # launch — one admission per step would starve the
                # lane table the batched program pays for.
                while not self.draining and self.queue \
                        and self._admit() is not None:
                    pass
                pending = self._prefill_candidates()
        launch = None
        if kind == "prefill":
            launch = self._run_prefill_batch(pending)
            if launch is None:
                # Backpressure fallback: when admission OR a
                # mid-prompt page allocation fails (pool exhausted,
                # every pending chunk stalled), decode instead —
                # decoding sequences finish and free the pages the
                # prefill is waiting for. Without it a
                # prefill-priority engine livelocks
                # (regression-pinned in tests/test_serving.py).
                # Zero-prefill admissions (full prefix hit / exact
                # session resume) land here too: nothing to prefill,
                # and the fresh slot decodes this very step — so
                # decodable is recomputed.
                decodable = self._decode_candidates()
                kind = "decode" if decodable else "idle"
        if kind == "decode":
            launch = self._run_decode(decodable)
        return launch

    def _retire(self, launch: _Launch | None) -> dict:
        """The back half of a step, for one launch: the ONE
        ``_fetch_host`` of what it returned (none for a prefill launch
        that ended no prompt), the block's counts (``_count``), the
        cadence's emit loop, and the step record. The clock is read
        AFTER the blocking fetch: under async dispatch an earlier read
        would leave the launch's own compute out of a request's
        latencies.

        The record describes ONE launch, this one (``op``, ``tokens``,
        what its cadence left in ``_step_counts``, ``ran_ahead``), and
        the records tile the engine's time: ``dur_s`` runs from the
        end of the retire before (from the start of the step, where
        nothing was in flight then) to the end of this one, and
        ``phase_s`` and ``host_syncs`` are what fell into that
        stretch, the dispatch of the launch after this one
        included."""
        tokens_out = 0
        if launch is not None:
            fetched = ()
            try:
                if launch.outs is not None:
                    *fetched, counts = self._fetch_host(*launch.outs)
                    self._count(counts)
                now = time.monotonic()
                with self._phase("emit"):
                    tokens_out = launch.emit(now, *fetched)
            except BaseException:
                # The launch dispatched behind this one continues from
                # tokens the host now never had: it goes with it.
                self._flying = None
                raise
        kind = launch.op if launch is not None else "idle"
        end = time.monotonic()
        # "op", not "kind": telemetry's record envelope owns "kind"
        # (the event name), and a colliding field would silently
        # relabel the whole record past the metrics observer.
        # "tokens" counts NEW tokens for decode steps and PROMPT
        # tokens processed for (batched) prefill steps — the metrics
        # observer splits them into the decode/prefill tok/s gauges
        # by "op". ``phase_s`` splits ``dur_s`` into the five
        # ``serving.*`` parts; what is left of it is the scheduling
        # glue between them. A launching step also carries what its
        # ``_run_*`` path left in ``_step_counts``: ``slots_stepped``,
        # ``slot_iters`` and ``iters`` (decode), with ``spec_k`` and
        # ``spec_accepted_mean`` or ``resident_k`` and
        # ``resident_steps_per_launch`` by cadence; ``first_tokens``
        # (prefill); and ``ran_ahead``: 1 when the launch was
        # dispatched while another was un-retired.
        rec = {"op": kind, "dur_s": end - self._tile_t0,
               "tokens": tokens_out,
               "phase_s": {k: round(v, 6)
                           for k, v in self._phase_s.items()},
               "in_flight": self.in_flight,
               "queue_depth": len(self.queue),
               **self._step_counts,
               **self.cache.occupancy()}
        if launch is not None:
            rec["ran_ahead"] = launch.ran_ahead
        if self._sharing:
            # Additive sharing fields (schema pinned by test): the
            # metrics observer accumulates the per-step deltas into
            # the dtt_serving_prefix_* counters and folds the
            # per-group shared-page list into a labeled family.
            rec["prefix_hit_tokens"] = self._step_prefix[0]
            rec["prefill_tokens_saved"] = self._step_prefix[1]
            rec["sessions_resident"] = len(self.sessions)
            rec["kv_pages_shared"] = [
                self.cache.shared_pages_in(g)
                for g in range(self.dp_groups)]
        syncs = self.host_syncs - self._tile_syncs0
        rec["host_syncs"] = syncs
        if tokens_out:
            rec["host_syncs_per_token"] = round(
                syncs / tokens_out, 6)
        rec["weight_bytes"] = self.weight_bytes
        if self.dp_groups > 1:
            rec["group_slots_active"] = self.slots_active_by_group()
            if launch is not None and launch.lanes is not None:
                rec["group_prefill_slots_active"] = launch.lanes
        # The next record's stretch starts here.
        self._tile_t0 = end
        self._tile_syncs0 = self.host_syncs
        self._phase_s = dict.fromkeys(_PHASES, 0.0)
        self._step_counts = {}
        self._step_prefix = [0, 0]
        event("serving", **rec)
        self._step_counter += 1
        if kind != "idle":
            self.launch_count += 1
            if self.faults is not None:
                self._run_faults()
        return rec

    def _settle(self) -> None:
        """Retire the launch in flight, if any. Whatever reads or moves
        the sequences' host-side state from outside a step (``preempt``,
        ``drain``, ``swap_weights``, ``export_in_flight``,
        ``export_emission_state``, ``adopt_batch``, the fault hook)
        calls this first: until then ``generated`` and the cache's
        lengths lag what the device has done. Its step record is
        emitted like any other."""
        if self._flying is not None:
            launch, self._flying = self._flying, None
            self._retire(launch)

    def _run_faults(self) -> None:
        """Serving fault hook, fired AFTER the step record is emitted
        (the fault ledger write happens inside the injector BEFORE
        any action — crash/restart cannot re-fire a fault). The
        injector sleeps ``slow_decode`` itself; the engine performs
        the actions that need its state: ``client_disconnect`` drops
        one live stream listener (the high-water mark keeps
        advancing, so the severed stream never resumes mid-request
        with duplicates), and ``engine_crash`` raises out of
        ``step()`` exactly like a real engine-thread fault."""
        from distributed_training_tpu.resilience.faults import (
            InjectedCrash)

        fired = self.faults.on_launch(self.launch_count)
        if "client_disconnect" in fired or "engine_crash" in fired:
            # Both act on the streams' host-side state: the launch in
            # flight lands first (its own record may fire faults too).
            self._settle()
        if "client_disconnect" in fired and self._token_listeners:
            rid = next(iter(self._token_listeners))
            self._token_listeners.pop(rid, None)
            logger.warning("injected client_disconnect: dropped "
                           "stream listener %r", rid)
        if "engine_crash" in fired:
            raise InjectedCrash(
                f"injected engine_crash at launch "
                f"{self.launch_count}")

    def _fetch_host(self, *arrays) -> tuple:
        """THE designated device->host sync point of the serving hot
        path: every blocking fetch in the step loop funnels through
        here so the sync cadence is countable (``host_syncs``, the
        ``dtt_serving_host_syncs_per_token`` gauge) and so pitfalls
        rule DTT010 can flag any round-trip that creeps in anywhere
        else. One call = one sync, however many arrays ride it."""
        self.host_syncs += 1
        with self._phase("fetch"):
            return tuple(np.asarray(a) for a in arrays)

    def _count(self, counts) -> None:
        """Add a launch's fetched ``counts`` (G, n), the block's
        ``counters`` and the engine's own (``_counters``) summed in the
        program, to the step record."""
        for name, n in zip(self._counters, counts.sum(axis=0)):
            self._step_counts[name] = (
                self._step_counts.get(name, 0) + int(n))

    def _rng_grouped(self, salt: int):
        """(G, 2) uint32 per-group key data for the compiled
        programs' sampling tail. Greedy returns the cached zero key
        (the operand is dead — the r02 dispatch diet)."""
        import jax
        import jax.numpy as jnp

        if self.cfg.temperature <= 0:
            return self._zero_rng
        base = jax.random.fold_in(self._base_rng, salt)
        return jnp.asarray(np.stack([
            np.asarray(jax.random.key_data(  # noqa: DTT010 — sampled
                jax.random.fold_in(base, g)))  # path only; greedy
            for g in range(self.dp_groups)]))  # rides _zero_rng

    # -- the front and the back of every launch -----------------------------

    def _claim(self, s: _Seq, upto: int, table: list, cow: list,
               lane: int | None = None) -> tuple[int, int] | None:
        """Claim what the next launch writes of ``s``: pages for
        ``upto`` tokens, a private copy of any shared page the write
        would touch (the (group, src, dst) pairs go onto ``cow``, for
        ``_page_rows`` to copy), and its place ``(g, i)`` in the
        launch's ``table`` ((G, lanes) sequence ids, None = a dead
        lane): its slot of its group's table, or ``lane`` where the
        launch packs lanes of its own (batched prefill). None = the
        group's pool shard is short, also after evicting an idle
        session: the sequence skips this launch and resumes when pages
        free."""
        if not self.cache.ensure(s.req.id, upto):
            return None
        g, i = divmod(s.slot, self.batch_local)
        if self._sharing:
            pairs = self._cow_guard(s.req.id)
            if pairs is None:
                return None
            cow += [(g, a, b) for a, b in pairs]
        if self._run_ahead and not s.on_device:
            # Its first launch here. A prompt prefilled from its first
            # token writes its own row; anything else is uploaded.
            s.on_device = True
            if s.prefilled:
                self._unseeded.append(s)
        if lane is not None:
            i = lane
        table[g][i] = s.req.id
        return g, i

    def _page_rows(self, table: list, cow: list) -> np.ndarray:
        """The page rows of a launch's claimed ``table``, once the
        pages ``_claim`` forked are copied and the rows the slot table
        lacks are uploaded (one launch of their own each)."""
        if cow:
            self._apply_cow(cow)
        if self._unseeded:
            self._seed(self._unseeded)
            self._unseeded = []
        with self._phase("pack"):
            return self.cache.page_rows_grouped(table)

    def _launch(self, fn, op: str, emit, *args, fetch: bool = True,
                lanes: list | None = None) -> _Launch:
        """Dispatch one launch of ``fn`` on the engine's state (the
        params; the pools and, where ``fn`` carries it, the slot table,
        donated and adopted as returned: ``_call``) and ``args``, and
        record what is in flight: the results to fetch, the block's
        counts last (``fetch=False`` reads nothing: a prefill launch
        that ends no prompt), and the cadence's ``emit`` for
        ``_retire`` to run on them. Nothing is fetched here."""
        import jax.numpy as jnp

        with self._phase("launch"):
            outs = self._call(fn, *(jnp.asarray(a) for a in args))
        return _Launch(op, tuple(outs) if fetch else None, emit,
                       ran_ahead=int(self._flying is not None),
                       lanes=lanes)

    def _walked(self, runs: list, calls: int) -> dict:
        """``kv_pages_walked`` / ``kv_pages_tabled`` of a decode
        launch's step record, where its program reads a kind of layer
        in the ragged form (``paged_form`` of the traced program; else
        nothing): the pages the kernel walked, and the pages the
        gather form would have copied. ``runs``: ``(low, top)``, the
        lowest and highest query position of every live slot of every
        iteration; ``calls``: the slots of every iteration, dead ones
        too, whose whole table rows a gather copies. A kind's layers
        walk the pages that hold positions ``low - window + 1`` (0
        without a window) to ``top``: host arithmetic on positions the
        retire holds, no device sync."""
        forms = (self._decode_fn.__wrapped__.paged_form or "").split("+")
        plan, ps = self._plan, self.cfg.page_size
        windowed = len(plan.window_layers)
        kinds = []              # (layers, pages a table row, window)
        if "ragged" in forms:
            # A block may mask its full tables to a window of its own.
            kinds.append((plan.n_layers - windowed, plan.pages_per_seq,
                          getattr(self.block.cfg, "attention_window",
                                  0) or 0))
        if "ragged.window" in forms:
            kinds.append((windowed, plan.ring_pages, plan.window))
        if not kinds:
            return {}
        walked = sum(
            layers * (top // ps - (max(low - window + 1, 0)
                                   if window else 0) // ps + 1)
            for layers, _row, window in kinds for low, top in runs)
        return {"kv_pages_walked": walked,
                "kv_pages_tabled": calls * sum(
                    layers * row for layers, row, _w in kinds)}

    def _emit(self, s: _Seq, toks, now: float, ev: str, advance: int,
              **fields) -> None:
        """The back of every launch, for one sequence: the cache
        advances by what the launch wrote of it, the ``ev`` span,
        then each of ``toks`` (the tokens fetched for it, none for a
        prompt chunk that did not end its prompt) is appended, tested
        for the stop token, stamped and streamed; then the prefix
        index and completion."""
        self.cache.advance(s.req.id, advance)
        s.span(ev, now, **fields)
        eos = self.cfg.eos_id
        for tok in toks:
            s.generated.append(tok)
            if eos >= 0 and tok == eos:
                s.eos = True
            if s.first_token_t is None:
                s.first_token_t = now
            s.token_times.append(now)
            self._emit_token(s, tok)
        self._register(s)
        self._maybe_finish(s)

    def _run_prefill_batch(self, pending: list[_Seq]
                           ) -> _Launch | None:
        """One launch of the batched prefill program: pack up to
        ``prefill_local`` pending sequences PER GROUP (each lane is
        one sequence's current chunk, pages claimed first), write all
        their KV through one batched scatter, and at its retire read
        the in-program sample for every lane whose chunk completed its
        prompt. A chunk counts as prefilled from its dispatch (the
        next launch may pack the chunk after it, or the sequence's
        first burst, while this one is in flight); where the programs
        carry the slot table the launch writes the lane's row of it
        (``build_prefill_batch_fn``). None = every pending chunk
        stalled on pages — backpressure; the caller lets decode run so
        pages free up."""
        with self._phase("pack"):
            G, Sp, C = (self.dp_groups, self.prefill_local,
                        self.cfg.prefill_chunk)
            tokens = np.zeros((G, Sp, C), np.int32)
            start_pos = np.zeros((G, Sp), np.int32)
            n_valid = np.zeros((G, Sp), np.int32)
            active = np.zeros((G, Sp), bool)
            slot = np.zeros((G, Sp), np.int32)
            max_new = np.zeros((G, Sp), np.int32)
            seq_ids: list[list] = [[None] * Sp for _ in range(G)]
            lanes = [0] * G
            chosen: list[tuple[_Seq, int, int, int, bool]] = []
            cow: list = []
            for s in pending:
                g = self.group_of_slot(s.slot)
                if lanes[g] >= Sp:
                    continue
                start = s.prefilled
                n = min(C, s.prompt_len - start)
                # A lane that stalls on pages leaves the others to
                # launch.
                at = self._claim(s, start + n, seq_ids, cow,
                                 lane=lanes[g])
                if at is None:
                    continue
                lanes[g] += 1
                tokens[at][:n] = s.req.prompt[start:start + n]
                start_pos[at] = start
                n_valid[at] = n
                active[at] = True
                slot[at] = s.slot % self.batch_local
                s.prefilled += n
                ends = s.prefill_done
                if ends:
                    max_new[at] = s.req.max_new_tokens
                    s.pending += 1
                chosen.append((s, *at, n, ends))
        if not chosen:
            return None

        def emit(now, fetched=None):
            total = first_tokens = 0
            for s, g, i, n, ends in chosen:
                s.pending -= ends
                total += n
                self._emit(s, (int(fetched[g, i]),) if ends else (),
                           now, "prefill", n, tokens=n)
                first_tokens += ends
            self.prefill_launches += 1
            self.prefill_tokens_computed += total
            self._step_counts["first_tokens"] = first_tokens
            return total

        # ONE (G, Sp) int32 pull for the whole launch, and only when
        # some prompt completed — never a logits block.
        return self._launch(
            self._prefill_batch_fn, "prefill", emit,
            self._page_rows(seq_ids, cow), tokens, start_pos, n_valid,
            active, self._rng_grouped(1_000_000 + self._step_counter),
            *((slot, max_new) if self._run_ahead else ()),
            fetch=any(c[-1] for c in chosen), lanes=lanes)

    def _draft(self, seq: _Seq, m: int) -> np.ndarray:
        """``m`` drafted tokens for ``seq`` by prompt lookup over its
        own history (prompt + generated) — ``draft_tokens``
        semantics served from the sequence's INCREMENTAL
        ``NgramIndex`` (built lazily on first draft, extended by the
        tokens emitted since the last one — O(new tokens), not a
        full-history rescan per launch)."""
        if m <= 0:
            return np.zeros((0,), np.int32)
        idx = seq.ngram
        if idx is None:
            idx = seq.ngram = NgramIndex()
            idx.extend(seq.req.prompt.tolist())
            idx.extend(seq.generated)
        else:
            idx.extend(
                seq.generated[len(idx) - seq.prompt_len:])
        return idx.draft(m)

    def _run_decode_spec(self, decodable: list[_Seq]
                         ) -> _Launch | None:
        """One launch of the speculative multi-token decode program:
        every decodable slot carries [last sampled token, spec_k - 1
        drafted tokens], the program argmax-verifies all positions in
        one forward, and the host emits the accepted prefix — each
        emitted token IS the argmax given the true prefix, so greedy
        output is token-identical to one-token decode. The cache
        advances only by the accepted length; rejected positions'
        stale KV sits beyond ``length`` (masked out of attention) and
        is overwritten by the next launch's writes."""
        with self._phase("pack"):
            G, B = self.dp_groups, self.batch_local
            K = self.cfg.spec_k
            tokens = np.zeros((G, B, K), np.int32)
            start_pos = np.zeros((G, B), np.int32)
            n_valid = np.zeros((G, B), np.int32)
            active = np.zeros((G, B), bool)
            seq_ids: list[list] = [[None] * B for _ in range(G)]
            stepped: list[tuple[_Seq, tuple, int, np.ndarray]] = []
            cow: list = []
            for s in decodable:
                length = self.cache.length(s.req.id)
                remaining = s.req.max_new_tokens - len(s.generated)
                # Clamp the chain to what the sequence can still hold
                # — positions past max_seq_len or past the request's
                # budget ride as masked padding (n_valid), never as
                # writes.
                n = min(K, remaining, self.cfg.max_seq_len - length)
                if n > 1 and not self.cache.ensure(s.req.id,
                                                   length + n):
                    # Pages for the full chain are short: fall back
                    # to a one-token launch in the SAME program
                    # before stalling outright.
                    n = 1
                at = self._claim(s, length + n, seq_ids, cow)
                if at is None:
                    continue
                draft = self._draft(s, n - 1)
                tokens[at][0] = s.last_token
                if n > 1:
                    tokens[at][1:n] = draft
                start_pos[at] = length
                n_valid[at] = n
                active[at] = True
                stepped.append((s, at, n, draft))
        if not stepped:
            return None

        def emit(now, out):
            total = 0
            runs = [(int(start_pos[at]), int(start_pos[at]) + n - 1)
                    for _s, at, n, _d in stepped]
            for s, at, n, draft in stepped:
                # out[at][j] is the verified argmax AFTER position
                # j. Accept draft j while it equals the chain's
                # previous token; every accepted position's argmax is
                # then conditioned on true tokens only.
                chain = out[at].tolist()
                took = chain[:1]
                j = 1
                while j < n and int(draft[j - 1]) == took[-1]:
                    took.append(chain[j])
                    j += 1
                if self.cfg.eos_id >= 0 and self.cfg.eos_id in took:
                    # Stop at the stop token: later accepted
                    # positions are conditioned on a sequence that
                    # already ended.
                    took = took[:took.index(self.cfg.eos_id) + 1]
                self._emit(s, took, now, "decode", len(took),
                           emitted=len(took), budget=n)
                total += len(took)
            # One verification chunk a stepped slot: ``slot_iters``
            # is exact.
            self._step_counts.update(
                slots_stepped=len(stepped), slot_iters=len(stepped),
                iters=1, spec_k=K,
                spec_accepted_mean=round(total / len(stepped), 4),
                **self._walked(runs, G * B))
            return total

        return self._launch(
            self._decode_fn, "decode", emit,
            self._page_rows(seq_ids, cow), tokens, start_pos, n_valid,
            active, self._zero_rng)

    def _run_decode_resident(self, decodable: list[_Seq]
                             ) -> _Launch | None:
        """One BURST of the device-resident decode loop: every
        decodable slot ships its page rows and a token budget, the
        program runs up to ``resident_k`` chunk iterations (drafting,
        verifying, stop-detecting and advancing its own page cursor
        per slot ON DEVICE, on the slot table it carries from the
        launch before) and the host syncs ONCE for the whole burst —
        ``(out, n_emitted, steps)``, one ``_fetch_host`` call, at its
        retire. Greedy token identity is preserved by construction:
        each iteration emits exactly the argmax chain the host spec
        path would (the same ``_chunk_hidden`` math), so K only moves
        the sync cadence, never tokens.

        The burst is packed while the launch before it may be in
        flight, from a projection: a slot's budget is what its request
        has left beyond what that launch may emit (``_Seq.left``), and
        its pages are claimed for the length that launch may reach
        (``_Seq.kv_ahead``) plus the budget. The device holds the
        truth and clamps: a slot that stopped early in the launch
        before (the stop token, or ``spec_k > 1`` accepting less than
        its budget) runs on from where it really is, or not at all,
        and the pages claimed for tokens that never came go back at
        the retire. A burst is atomic host-side — the cache advances
        only at its retire, and everything that takes sequences away
        retires first (``_settle``) — so a preemption resubmits
        cleanly.

        The step record's ``slot_iters`` is the sum over the stepped
        slots of the loop iterations each was live in. The program
        returns tokens a slot and iterations a group, not iterations
        a slot, so it is ``min(tokens, group iterations)``: exact at
        ``spec_k == 1``, where a live iteration emits exactly one
        token, and an upper bound at ``spec_k > 1`` for a slot that
        stopped before its group did (tokens over ``slot_iters`` then
        reads low, never high)."""
        with self._phase("pack"):
            G, B = self.dp_groups, self.batch_local
            T = self.cfg.resident_k * self.cfg.spec_k
            budget = np.zeros((G, B), np.int32)
            active = np.zeros((G, B), bool)
            seq_ids: list[list] = [[None] * B for _ in range(G)]
            stepped: list[tuple[_Seq, tuple, int]] = []
            cow: list = []
            for s in decodable:
                length = s.kv_ahead
                # The burst budget is clamped to the pages the slot
                # could actually claim RIGHT NOW (its allocated pages
                # + its group's free list): a tight pool degrades the
                # burst toward one token — the all-slots-stall
                # fallback — instead of stalling the slot outright.
                cap = self.cache.token_capacity(s.req.id)
                want = min(s.left, T, cap - length)
                if want < 1:
                    continue  # zero headroom: wait for frees
                at = self._claim(s, length + want, seq_ids, cow)
                if at is None:
                    continue
                budget[at] = want
                active[at] = True
                s.pending += want
                stepped.append((s, at, want))
        if not stepped:
            return None

        def emit(now, out, n_emitted, steps):
            total = slot_iters = 0
            runs, K = [], self.cfg.spec_k
            for s, at, want in stepped:
                if self.slots[s.slot] is not s:
                    continue    # it ended in the launch before
                s.pending -= want
                e = int(n_emitted[at])
                start = self.cache.length(s.req.id)
                self._emit(s, out[at][:e].tolist(), now, "decode", e,
                           emitted=e, budget=want)
                total += e
                # A live iteration emits at least one token, and a
                # slot is live in at most its group's iterations.
                live = min(e, int(steps[at[0]]))
                slot_iters += live
                # Iteration i's queries: exact at ``spec_k == 1``, the
                # accepted tokens spread evenly otherwise.
                runs += [(start + i * e // live,
                          start + i * e // live + K - 1)
                         for i in range(live)]
                if e < want and self.slots[s.slot] is s:
                    # Pages claimed for tokens that did not come.
                    self.cache.trim(s.req.id, s.kv_ahead)
            g_steps = [int(steps[g]) for g in range(G)
                       if active[g].any()]
            mean_steps = sum(g_steps) / max(1, len(g_steps))
            # ``iters``: the iterations the launch ran, its longest
            # group's (the groups run side by side).
            self._step_counts.update(
                slots_stepped=len(stepped), slot_iters=slot_iters,
                iters=max(g_steps, default=0),
                resident_k=self.cfg.resident_k,
                resident_steps_per_launch=round(mean_steps, 4),
                **self._walked(runs, sum(g_steps) * B))
            return total

        return self._launch(
            self._decode_fn, "decode", emit,
            self._page_rows(seq_ids, cow), budget, active)

    def _run_decode_token(self, decodable: list[_Seq]
                          ) -> _Launch | None:
        """One launch of the one-token decode program: every decodable
        slot feeds its last token and reads the next, sampled in the
        program (the argmax at ``temperature == 0``, else a draw from
        the step's folded key)."""
        with self._phase("pack"):
            G, B = self.dp_groups, self.batch_local
            tokens = np.zeros((G, B), np.int32)
            positions = np.zeros((G, B), np.int32)
            active = np.zeros((G, B), bool)
            seq_ids: list[list] = [[None] * B for _ in range(G)]
            stepped: list[tuple[_Seq, tuple]] = []
            cow: list = []
            for s in decodable:
                # The new token's KV lands at position length(seq);
                # a page must cover it.
                length = self.cache.length(s.req.id)
                at = self._claim(s, length + 1, seq_ids, cow)
                if at is None:
                    continue
                tokens[at] = s.last_token
                positions[at] = length
                active[at] = True
                stepped.append((s, at))
        if not stepped:
            return None

        def emit(now, nxt):
            for s, at in stepped:
                self._emit(s, (int(nxt[at]),), now, "decode", 1,
                           emitted=1)
            self._step_counts.update(
                slots_stepped=len(stepped), slot_iters=len(stepped),
                iters=1, **self._walked(
                    [(int(positions[at]),) * 2 for _s, at in stepped],
                    G * B))
            return len(stepped)

        return self._launch(
            self._decode_fn, "decode", emit, tokens, positions,
            self._page_rows(seq_ids, cow), active,
            self._rng_grouped(self._step_counter))

    def _maybe_finish(self, seq: _Seq) -> None:
        if not seq.done:
            return
        if self._sharing and seq.req.session is not None:
            # Retain the turn's pages under the session key instead
            # of freeing them: a follow-up request with this key
            # re-attaches with zero prefill for the whole retained
            # history. A stale earlier turn of the same key is
            # superseded (its pages go back through the refcounted
            # free).
            key = seq.req.session
            if key in self.sessions:
                self._drop_session(key)
            cid = f"~session:{key}"
            self.cache.rename(seq.req.id, cid)
            retain_t = time.monotonic()
            self.sessions[key] = {
                "cache_id": cid,
                "history": np.concatenate([
                    np.array(seq.req.prompt, np.int32),
                    np.array(seq.generated, np.int32)]),
                "group": self.cache.group_of(cid),
                "t": retain_t}
            seq.span("session_retain", retain_t, session=key)
        else:
            self.cache.free(seq.req.id)
        self.slots[seq.slot] = None
        now = time.monotonic()
        arrival = seq.req.arrival if seq.req.arrival is not None \
            else now
        gaps = [b - a for a, b in zip(seq.token_times,
                                      seq.token_times[1:])]
        rec = {
            "id": seq.req.id,
            "tenant": seq.req.tenant,
            "prompt_tokens": seq.prompt_len,
            "new_tokens": len(seq.generated),
            "tokens": list(seq.generated),
            "ttft_s": (seq.first_token_t - arrival
                       if seq.first_token_t is not None else None),
            "queue_wait_s": seq.queue_wait_s,
            "latency_s": now - arrival,
            "token_gaps_s": gaps,
            "group": self.group_of_slot(seq.slot),
            "weights_versions": [list(p) for p in seq.versions],
        }
        self.completed.append(rec)
        self.finished_total += 1
        self._emit_hwm.pop(seq.req.id, None)
        event("serving_request",
              **{k: rec[k] for k in ("id", "tenant",
                                     "prompt_tokens", "new_tokens",
                                     "ttft_s", "queue_wait_s",
                                     "latency_s", "group")})
        self._emit_trace(seq, "finished", now)

    # -- convenience -------------------------------------------------------

    def run_until_drained(self, max_steps: int = 100_000) -> int:
        """Step until queue + slots are empty. Returns steps taken."""
        n = 0
        while not self.idle and n < max_steps:
            self.step()
            n += 1
        if not self.idle:
            raise RuntimeError(
                f"engine not drained after {max_steps} steps "
                f"(queue={len(self.queue)}, in_flight="
                f"{self.in_flight})")
        return n

    def generate(self, prompt: np.ndarray, max_new_tokens: int
                 ) -> list[int]:
        """One prompt through the full continuous-batching path
        (the generate-CLI route). Returns the generated token ids."""
        rid = f"gen-{self._step_counter}-{len(self.completed)}"
        self.submit(Request(id=rid,
                            prompt=np.array(prompt, np.int32),
                            max_new_tokens=max_new_tokens))
        self.run_until_drained()
        rec = next(r for r in reversed(self.completed)
                   if r["id"] == rid)
        return rec["tokens"]

    def adopt(self, req: Request, first_token: int,
              k_dense: np.ndarray, v_dense: np.ndarray) -> None:
        """Adopt an EXTERNALLY-PREFILLED sequence (the disaggregation
        handoff, serving/disagg.py): its prompt KV arrives as dense
        (L, Hkv, prompt_len, hd) arrays and is written into this
        engine's pages — into the least-loaded dp group's shard, the
        same balancing as queue admission; decode continues here as
        if the prefill had run locally. ``first_token`` is the token
        the prefill slice sampled from its final logits."""
        self.adopt_batch([(req, first_token, k_dense, v_dense)])

    def adopt_batch(self, items) -> None:
        """Adopt MANY externally-prefilled sequences in one batched
        page import (serving/disagg.py ``import_kv_batch`` — a single
        scatter per pool instead of one device round-trip per
        request; the continuous-handoff rate path). ``items`` is a
        list of ``(req, tokens, k_dense, v_dense)`` where ``tokens``
        is either the single first sampled token (the disaggregation
        handoff) or the FULL generated history so far (the crash-
        recovery re-adoption, ``export_in_flight``) — the dense KV
        must cover ``prompt_len + len(tokens) - 1`` positions, the
        decode invariant (the newest token's KV is written by its own
        decode launch). Raises before touching the pool when any
        request cannot get a slot+pages — the caller holds the batch
        and retries once decode frees capacity."""
        from distributed_training_tpu.serving.disagg import (
            import_kv_batch)

        self.cache.full_tables_only("Engine.adopt_batch (a dense "
                                    "KV hand-off)")
        self._settle()
        now = time.monotonic()
        staged = []
        try:
            for req, toks, k_dense, v_dense in items:
                tokens = ([int(toks)]
                          if isinstance(toks, (int, np.integer))
                          else [int(t) for t in toks])
                if not tokens:
                    raise ValueError(
                        f"adopt of {req.id!r} carries no tokens — a "
                        "never-decoded sequence resubmits as a fresh "
                        "request instead")
                if req.arrival is None:
                    req.arrival = now
                self._validate(req)
                need = req.prompt.shape[0] + len(tokens) - 1
                picked = self._pick_group(need)
                if picked is None:
                    raise RuntimeError(
                        f"no free slot/pages to adopt {req.id!r} "
                        "into")
                group, slot = picked
                self.cache.join(req.id, group=group)
                seq = _Seq(req=req, slot=slot,
                           prefilled=req.prompt.shape[0])
                self._mark_admitted(seq, "adopted", group=group)
                self.slots[slot] = seq
                staged.append((seq, tokens, k_dense, v_dense))
            import_kv_batch(self.cache,
                            [(s.req.id, k, v)
                             for s, _t, k, v in staged])
        except Exception:
            # A failed batch must not leak joined table entries or
            # slots (a retry of the same request id would hit
            # "already joined" forever). ensure() inside the batch
            # import is atomic per sequence, so freeing returns
            # exactly the pages taken.
            for s, _t, _k, _v in staged:
                self.cache.free(s.req.id)
                self.slots[s.slot] = None
            raise
        now = time.monotonic()
        for seq, tokens, _k, _v in staged:
            seq.first_token_t = now
            for tok in tokens:
                seq.token_times.append(now)
                seq.generated.append(tok)
                if self.cfg.eos_id >= 0 and tok == self.cfg.eos_id:
                    seq.eos = True
                self._emit_token(seq, tok)
                self._register(seq)
            self._maybe_finish(seq)

    def preempt(self) -> list[Request]:
        """Simulated engine preemption: drop all device-side progress,
        free every page, and hand back the unfinished work (queued +
        in-flight requests, fresh — generation restarts from the
        prompt, the standard continuous-batching recovery). The
        engine is reusable afterwards (a restarted incarnation calls
        ``submit`` with these). Token listeners for the lost work are
        dropped too — a resubmitted request restarts from the prompt,
        and a stale listener would stream its early tokens twice.
        RETAINED SESSIONS SURVIVE: their pages are refcount-held, so
        freeing the in-flight sequences (some sharing those pages)
        returns exactly the unshared pages — no leak, no double-free
        — and a post-preemption resume still re-attaches with zero
        prefill. Page content is untouched by the frees (a page is
        never reused while held), so the retained KV stays valid."""
        self._settle()
        lost: list[Request] = []
        now = time.monotonic()
        for i, s in enumerate(self.slots):
            if s is None:
                continue
            # Close the trace honestly BEFORE dropping the state:
            # the tokens this incarnation computed and is about to
            # throw away are recorded, so the offline retry-cost
            # number is derived from the stream, never inferred.
            self._emit_trace(s, "preempted", now,
                             tokens_discarded=len(s.generated))
            self.cache.free(s.req.id)
            self.slots[i] = None
            lost.append(Request(id=s.req.id, prompt=s.req.prompt,
                                max_new_tokens=s.req.max_new_tokens,
                                arrival=s.req.arrival,
                                tenant=s.req.tenant))
        lost.extend(self.queue)
        self.queue.clear()
        for req in lost:
            self._token_listeners.pop(req.id, None)
        event("serving_preempt", lost=len(lost))
        return lost

    # -- resilience: live weight swap, drain, crash recovery ----------------

    def _replay_request(self, seq: _Seq) -> Request:
        """A fresh Request preserving the ORIGINAL identity (id,
        arrival, session, tenant) — resubmission/re-adoption keeps
        the queue-wait accounting and the exactly-once stream keyed
        to the same request."""
        return Request(id=seq.req.id, prompt=seq.req.prompt,
                       max_new_tokens=seq.req.max_new_tokens,
                       arrival=seq.req.arrival,
                       session=seq.req.session,
                       tenant=seq.req.tenant)

    def _preempt_seq(self, seq: _Seq) -> None:
        """Preempt ONE in-flight sequence back to the head of the
        queue (the staleness-bound path): pages freed, slot vacated,
        trace closed honestly. Unlike ``preempt()`` the stream
        listener and the emitted-token high-water mark are KEPT —
        greedy decode regenerates a token-identical prefix, and the
        high-water mark suppresses its re-delivery, so the client
        stream continues exactly once."""
        now = time.monotonic()
        self._emit_trace(seq, "preempted", now,
                         tokens_discarded=len(seq.generated))
        self.cache.free(seq.req.id)
        self.slots[seq.slot] = None
        self.queue.appendleft(self._replay_request(seq))

    def swap_weights(self, params, version: str,
                     provenance: dict | None = None) -> int:
        """Install a new weight set into the RUNNING engine between
        launches — the live hot-swap (ROADMAP item 1's transfer
        primitive). All-or-nothing: every gate below runs BEFORE the
        first byte is installed, and a refusal leaves the engine
        serving the incumbent version untouched.

        Gates, in order: (1) injected ``swap_corrupt`` (a torn
        publish whose artifact no longer verifies), (2) plan
        provenance — the publish must carry the SAME plan name +
        fingerprint the engine's weights were laid out under (the
        WeightStore discipline: new weights under a silently-
        regenerated plan are refused), (3) pytree structure, (4)
        per-leaf shape/dtype, (5) placement — each leaf is
        ``device_put`` onto the incumbent leaf's sharding, so the
        installed tree is layout-identical and every existing jit
        entry is reused (ZERO recompiles; the programs take params as
        a call argument, never close over them).

        After install, in-flight sequences keep decoding — their
        remaining tokens come from the new version and every emitted
        token is version-tagged. With ``cfg.swap_staleness_tokens``
        >= 0, any sequence that has already emitted MORE than that
        many old-version tokens is preempted-and-resubmitted instead
        (regenerating token-identically under the new version, the
        high-water mark deduplicating its stream); the contract: a
        completed request carries at most ``swap_staleness_tokens``
        tokens from a superseded version. Returns the number of
        sequences preempted for staleness."""
        import jax

        from distributed_training_tpu.serving.disagg import (
            ProvenanceError)

        # The launch in flight ran on the incumbent weights: its tokens
        # are emitted, and tagged, before anything is installed.
        self._settle()

        def _refuse(exc: Exception):
            self.swap_stats["refused"] += 1
            event("serving_swap", outcome="refused",
                  version=version, engine_version=self.weights_version,
                  reason=str(exc))
            logger.warning("weight swap to %r REFUSED: %s", version,
                           exc)
            raise exc

        if self.faults is not None and \
                self.faults.on_swap(self.launch_count):
            _refuse(ProvenanceError(
                f"swap to {version!r}: injected swap_corrupt — "
                "published artifact failed verification"))
        if self.weights_provenance is not None:
            if provenance is None:
                _refuse(ProvenanceError(
                    f"swap to {version!r}: engine weights carry plan "
                    f"provenance ({self.weights_provenance.get('name')})"
                    " but the publish carries none"))
            for key in ("name", "fingerprint"):
                if provenance.get(key) != \
                        self.weights_provenance.get(key):
                    _refuse(ProvenanceError(
                        f"swap to {version!r}: plan {key} mismatch — "
                        f"engine {self.weights_provenance.get(key)!r}"
                        f" vs publish {provenance.get(key)!r}"))
        elif provenance is None:
            logger.warning(
                "weight swap to %r: no provenance on either side "
                "(legacy artifact) — accepting on shape/dtype/"
                "placement gates only", version)
        old_leaves, old_def = jax.tree.flatten(self.params)
        new_leaves, new_def = jax.tree.flatten(params)
        if old_def != new_def:
            _refuse(ValueError(
                f"swap to {version!r}: params tree structure "
                f"differs from the serving tree"))
        bad = [i for i, (o, n) in
               enumerate(zip(old_leaves, new_leaves))
               if getattr(o, "shape", None) != getattr(n, "shape",
                                                       None)
               or getattr(o, "dtype", None) != getattr(n, "dtype",
                                                       None)]
        if bad:
            _refuse(ValueError(
                f"swap to {version!r}: {len(bad)} leaf(s) differ in "
                f"shape/dtype (first at flat index {bad[0]})"))
        # Placement: each leaf lands on the incumbent leaf's sharding
        # so every existing jit entry is reused. Leaves already laid
        # out identically (same sharding AND same device-commitment —
        # commitment is part of the jit cache key, so a gratuitous
        # device_put on an uncommitted tree would retrace) pass
        # through untouched.
        def _place(o, n):
            if not hasattr(o, "sharding"):
                return n
            if getattr(n, "sharding", None) == o.sharding and \
                    getattr(n, "committed", None) == \
                    getattr(o, "committed", None):
                return n
            return jax.device_put(n, o.sharding)

        placed = [_place(o, n)
                  for o, n in zip(old_leaves, new_leaves)]
        # Every gate passed: install. The ONE sanctioned rebinding of
        # ``self.params`` outside __init__ (pitfalls rule DTT011).
        self.params = jax.tree.unflatten(old_def, placed)
        self.weights_version = version
        if provenance is not None:
            self.weights_provenance = dict(provenance)
        self.swap_stats["installed"] += 1
        bound = self.cfg.swap_staleness_tokens
        stale = []
        if bound >= 0:
            stale = [s for s in self.slots
                     if s is not None and len(s.generated) > bound]
            for s in stale:
                self._preempt_seq(s)
            self.swap_stats["stale_preempted"] += len(stale)
        event("serving_swap", outcome="installed", version=version,
              stale_preempted=len(stale), in_flight=self.in_flight,
              swaps_installed=self.swap_stats["installed"])
        return len(stale)

    def drain(self, deadline_s: float | None = None) -> dict:
        """Graceful drain: stop admission, run in-flight work to
        completion (or to ``deadline_s``), and report per-request
        outcomes. Queued-but-never-admitted requests stay queued and
        are listed as ``requeued`` (a successor engine submits them
        verbatim); at the deadline, still-in-flight sequences are
        persisted host-side via ``export_in_flight`` and returned
        under ``persisted`` for re-adoption. Retained sessions
        survive by construction — they live in the cache's refcounted
        session table, not in slots. The engine stays ``draining``
        afterwards (flip the flag to reopen admission)."""
        self.draining = True
        t0 = time.monotonic()
        n0 = len(self.completed)
        steps = 0
        while (self.in_flight or self._flying is not None) and \
                (deadline_s is None
                 or time.monotonic() - t0 < deadline_s):
            self.step()
            steps += 1
            if steps > 200_000:
                raise RuntimeError(
                    "drain not converging after 200k steps "
                    f"(in_flight={self.in_flight})")
        self._settle()
        persisted = self.export_in_flight() if self.in_flight \
            else {"adoptable": [], "requests": []}
        report = {
            "finished": [r["id"] for r in self.completed[n0:]],
            "persisted": ([it[0].id for it in persisted["adoptable"]]
                          + [r.id for r in persisted["requests"]]),
            "requeued": [r.id for r in self.queue],
            "steps": steps,
            "duration_s": time.monotonic() - t0,
            "export": persisted,
        }
        event("serving_drain", deadline_s=deadline_s,
              finished=len(report["finished"]),
              persisted=len(report["persisted"]),
              requeued=len(report["requeued"]),
              steps=steps, duration_s=report["duration_s"])
        return report

    def export_in_flight(self) -> dict:
        """Persist every in-flight sequence host-side and vacate its
        device state (the crash-salvage / drain-deadline path).
        Sequences that have decoded at least one token export their
        EXACT dense KV (one batched ``export_kv_batch`` fetch) plus
        generated history — ``adopt_batch`` items for a successor
        engine, nothing recomputed but the newest token's KV write
        (the decode invariant: ``prompt + generated - 1`` positions
        are resident). Never-decoded sequences (mid-prefill, or
        zero-prefill admissions awaiting their first launch) come
        back as fresh ``Request``s — nothing was emitted, so restart
        costs only their prefill. Traces close as ``preempted`` with
        ``tokens_discarded=0`` for the persisted group (their tokens
        survive). Listeners and high-water marks are NOT touched —
        ``export_emission_state`` carries those."""
        from distributed_training_tpu.serving.disagg import (
            export_kv_batch)

        self._settle()
        now = time.monotonic()
        seqs = [s for s in self.slots if s is not None]
        # A block with window layers has no dense export (its window
        # pages are a ring): everything restarts from its prompt.
        adoptable = [s for s in seqs
                     if s.prefill_done and s.generated
                     and not self.cache.cfg.window_layers]
        adopt_ids = {id(s) for s in adoptable}
        fresh = [s for s in seqs if id(s) not in adopt_ids]
        ks, vs = (export_kv_batch(self.cache,
                                  [s.req.id for s in adoptable])
                  if adoptable else ([], []))
        items = [(self._replay_request(s), list(s.generated), k, v)
                 for s, k, v in zip(adoptable, ks, vs)]
        requests = [self._replay_request(s) for s in fresh]
        for s in seqs:
            discarded = 0 if id(s) in adopt_ids \
                else len(s.generated)
            self._emit_trace(s, "preempted", now,
                             tokens_discarded=discarded)
            self.cache.free(s.req.id)
            self.slots[s.slot] = None
        return {"adoptable": items, "requests": requests}

    def export_emission_state(self) -> dict:
        """Host-side exactly-once stream state for an IN-PROCESS
        successor engine: the per-request emitted-token high-water
        marks plus the live token listeners (callables — same-process
        transfer only, the serving supervisor's restart path)."""
        self._settle()
        return {"hwm": dict(self._emit_hwm),
                "listeners": dict(self._token_listeners)}

    def import_emission_state(self, state: dict | None) -> None:
        if not state:
            return
        self._emit_hwm.update(state.get("hwm", {}))
        self._token_listeners.update(state.get("listeners", {}))


# ---------------------------------------------------------------------------
# The compiled programs (pure functions of arrays + static model cfg).
# Each body sees ONE dp group's block: pools (1, L, N, ps, lanes) as
# the cache stores them (``plan``, a ``kv_cache.PoolPlan``: its ``k`` /
# ``v`` layouts are the only thing that indexes them; where the model
# has window layers each of the two is a ``kv_cache.Pools``, a pool a
# kind of layer), batch arrays with a leading group dim of 1 — under
# shard_map that is the per-group shard; without a dp mesh it is the
# whole (only) group.
# ---------------------------------------------------------------------------


def _group0(pools):
    """One group's pool(s) out of the grouped argument."""
    import jax

    return jax.tree.map(lambda p: p[0], pools)


def _grouped(pools):
    """``_group0`` the other way, for the result."""
    import jax

    return jax.tree.map(lambda p: p[None], pools)


def _write_rows(layout, pool, layer, rows, page_ids, offsets):
    """Scatter per-token new rows into one layer of a group's pool,
    where the pool lies.

    pool the group's pool as stored (of the layer's kind), with its
    ``layout`` of the plan; layer () int32, its number in that pool;
    rows (..., heads, width) in the pool's own width; page_ids/offsets
    (...) int32, shaped like the rows' leading axes — rows whose write
    must be dead point at the scratch page (id 0). Live rows never
    share a (page, slot) pair (pages are owned by exactly one
    sequence), so scatter order is immaterial; scratch-page collisions
    write garbage over garbage."""
    return layout.write(
        pool, layer, page_ids.reshape(-1), offsets.reshape(-1),
        rows.reshape((-1,) + rows.shape[-2:]).astype(pool.dtype))


def _scan_layers(block, plan, params, x, k_pages_g, v_pages_g,
                 positions, coords, valid, attend):
    """Every layer of the model's block on ``x``, THE layer body of
    every program: the block projects the layer's input
    (``positions`` shaped like ``x`` less its width), the new rows go
    into the layer's pool at its kind's ``coords[kind] = (page_ids,
    offsets)`` (shaped like ``positions``: a whole lane table is one
    scatter, whose live coordinates never collide; a block that
    projects a third row, its index key, has it written at the same
    coordinates into the index pool, ``kv_cache.Pools.index`` of
    ``k_pages_g``), ``attend(kind, run, layer, q, k_new, v_new, kp,
    vp[, ip])`` is the program's own way to
    the block's attention, and the block finishes the layer; ``valid``
    marks real tokens for the block's counters. One ``lax.scan`` a run
    of like layers (``block.segments``; ``run = block.at(first
    layer)`` is the block as that run sees it) over the layers'
    parameters (the body takes its layer by ``models/experts.py::
    layer_of``) and their numbers in their kind's pool (``plan.run``:
    global layers in the one pool, window layers in the other, where
    the model has both); the pools (k_pages_g/v_pages_g, one group's)
    are carried whole through all of them, so runs of unlike layers
    cost no slice and no concatenation of them, and ``kp`` / ``vp`` are
    the carried pool with the layer's number (``PoolLayer``), so
    attention reads its layer where it lies. Returns ``(x, counts (n,)
    summed over layers, k_pages_g, v_pages_g)``."""
    import jax
    import jax.numpy as jnp

    from distributed_training_tpu.models import experts

    def layer_body(kind, run, layers):
        page_ids, offsets = coords[kind]
        k_layout, v_layout = plan.of(kind)

        def body(carry, inp):
            x, kg, vg = carry
            i, number = inp
            layer = experts.layer_of(layers, i)
            with jax.named_scope("dtt.attn.project"):
                q, k, v, *key = run.project(layer, x, positions)
            with jax.named_scope("dtt.kv.write"):
                kp = _write_rows(k_layout, pool_of(kg, kind), number, k,
                                 page_ids, offsets)
                vp = _write_rows(v_layout, pool_of(vg, kind), number, v,
                                 page_ids, offsets)
                kg, vg = with_pool(kg, kind, kp), with_pool(vg, kind, vp)
                index = ()
                if key:
                    # The third row: the index key, at the same page and
                    # slot of the index pool's layer of the same number.
                    ip = _write_rows(plan.index, kg.index, number,
                                     key[0], page_ids, offsets)
                    kg = kg._replace(index=ip)
                    index = (plan.index.layer(ip, number),)
            # What ``ops/paged_attention.py`` does not name more closely
            # (``dtt.kv.read``, ``dtt.attn.select``) is the attention
            # itself.
            with jax.named_scope("dtt.attn.core"):
                attn = attend(kind, run, layer, q, k, v,
                              k_layout.layer(kp, number),
                              v_layout.layer(vp, number), *index)
            # ``dtt.attn.out``, then ``dtt.mlp`` or ``dtt.moe.*``: the
            # block's.
            x, counts = run.finish(layer, x, attn, valid)
            return (x, kg, vg), counts
        return body

    counts = jnp.zeros((len(block.counters),), jnp.int32)
    carry, lo = (x, k_pages_g, v_pages_g), 0
    for layers in block.segments(params):
        hi = lo + jax.tree.leaves(layers)[0].shape[0]
        kind, first = plan.run(lo, hi)
        # The loop and what it carries are the engine's; the body names
        # its parts (``telemetry/op_scopes.py::SCOPES``).
        with jax.named_scope("dtt.engine"):
            carry, c = jax.lax.scan(
                layer_body(kind, block.at(lo), layers), carry,
                (jnp.arange(hi - lo, dtype=jnp.int32),
                 jnp.arange(first, first + hi - lo, dtype=jnp.int32)))
            counts = counts + c.sum(axis=0)
        lo = hi
    x, k_pages_g, v_pages_g = carry
    return x, counts, k_pages_g, v_pages_g


def _sample(logits, active, rng_data, temperature, top_k):
    """(n, V) float32 logits -> (n,) int32 tokens: the argmax at
    temperature 0, else a categorical draw a row from the group's
    folded key ``rng_data`` (1, 2); rows not ``active`` give 0."""
    import jax
    import jax.numpy as jnp

    if temperature <= 0:
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    else:
        lg = logits / temperature
        if top_k:
            kth = jax.lax.top_k(lg, top_k)[0][..., -1:]
            lg = jnp.where(lg < kth, -jnp.inf, lg)
        keys = jax.random.split(
            jax.random.wrap_key_data(rng_data[0]), logits.shape[0])
        nxt = jax.vmap(jax.random.categorical)(keys, lg).astype(
            jnp.int32)
    return jnp.where(active, nxt, 0)


def _decode_program(params, k_pages, v_pages, tokens, positions,
                    page_tables, active, rng_data, *, block, plan,
                    temperature, top_k):
    """One token for one dp group's slot table: a chunk of one a slot
    through ``_chunk_hidden``, then sampled.

    k_pages/v_pages (1, L, N, ps, lanes) — the group's pool
    shard; tokens (1, B) int32 — last sampled token per local slot;
    positions (1, B) — the ABSOLUTE position that token occupies
    (== kv entries already written); page_tables (1, B, P); active
    (1, B) bool; rng_data (1, 2) uint32 — the group's folded key.
    Returns (next_tokens (1, B), counts (1, n), k_pages, v_pages).
    Inactive slots compute garbage into the scratch page and their
    sampled token is 0.
    """
    import jax
    import jax.numpy as jnp

    active = active[0]
    x, _valid, counts, k_pages_g, v_pages_g = _chunk_hidden(
        params, _group0(k_pages), _group0(v_pages), page_tables[0],
        tokens[0][:, None], positions[0],
        jnp.ones_like(positions[0]), active, block=block, plan=plan)
    with jax.named_scope("dtt.head"):
        nxt = _sample(block.logits(params, x[:, 0]), active, rng_data,
                      temperature, top_k)
    return (nxt[None], counts[None], _grouped(k_pages_g),
            _grouped(v_pages_g))


def _chunk_hidden(params, k_pages_g, v_pages_g, page_rows, tokens,
                  start_pos, n_valid, active, *, block, plan):
    """The multi-lane chunk forward SHARED by ``_chunk_program``
    (batched prefill + speculative verification) and
    ``_resident_program`` (every resident loop iteration) — ONE
    implementation, so the device-resident path cannot drift from
    the host-verified chunk math. Operates on one group's UNPACKED
    block (no leading group dim): k_pages_g/v_pages_g
    (L, N, ps, lanes), or with window layers a ``Pools`` of the two
    kinds'; page_rows (S, P), the tables, with window layers followed
    by the rings (``plan.rows``); tokens (S, C);
    start_pos, n_valid (S,); active (S,) bool. Writes every lane's
    valid tokens' KV through one batched page-row scatter a layer, a
    global layer's at the table's coordinates and a window layer's at
    the ring's (logical page ``p`` in entry ``p % ring_pages``), and
    returns ``(x (S, C, D) final hidden states, valid (S, C), counts
    (n,): the block's counters and the engine's (``_counters``),
    k_pages_g, v_pages_g)``."""
    import jax.numpy as jnp

    import jax

    S, C = tokens.shape
    ps = plan.k.page_size(pool_of(k_pages_g, GLOBAL))
    with jax.named_scope("dtt.engine"):
        idx = jnp.arange(C, dtype=jnp.int32)
        abs_pos = start_pos[:, None] + idx[None, :]       # (S, C)
        valid = (idx[None, :] < n_valid[:, None]) & active[:, None]
    with jax.named_scope("dtt.embed"):
        x = block.embed(params, tokens, abs_pos)          # (S, C, D)
    with jax.named_scope("dtt.engine"):
        rows = plan.rows(page_rows)
    coords = {}
    for kind, table in rows.items():
        # Page coordinates per (lane, position); dead writes → each
        # group's scratch page 0 (page index clamped first: padding
        # positions of a lane near max_seq_len could index past its
        # row).
        P = table.shape[1]
        with jax.named_scope("dtt.kv.write"):
            logical = (abs_pos // ps % P if kind == WINDOW
                       else jnp.minimum(abs_pos // ps, P - 1))
            coords[kind] = (
                jnp.where(valid, jnp.take_along_axis(table, logical,
                                                     axis=1), 0),
                jnp.where(valid, abs_pos % ps, 0))
    with jax.named_scope("dtt.engine"):
        q_pos = jnp.where(valid, abs_pos, -1)             # (S, C)
    x, counts, k_pages_g, v_pages_g = _scan_layers(
        block, plan, params, x, k_pages_g, v_pages_g, abs_pos, coords,
        valid,
        lambda kind, run, layer, q, _k, _v, kp, vp, *ip:
        run.attend_chunk(layer, q, kp, vp, rows[kind], q_pos, *ip))
    # The engine's own counters, in ``_counters``' order: sequences
    # longer than the window, and than the selection's top-k.
    with jax.named_scope("dtt.engine"):
        for bound in ((plan.window,) if plan.window_layers else ()) + (
                (plan.index_topk,) if plan.index_topk else ()):
            over = active & (start_pos + n_valid > bound)
            counts = jnp.concatenate(
                [counts, jnp.sum(over, dtype=jnp.int32)[None]])
    return x, valid, counts, k_pages_g, v_pages_g


def _argmax_chain(block, params, x, valid):
    """The verification chain over chunk hidden states: the ARGMAX
    after EVERY position (position c's argmax is the verified next
    token given tokens[:c+1]) — greedy only, by the spec/resident
    config contract. Invalid positions emit 0."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("dtt.head"):
        nxt = jnp.argmax(block.logits(params, x), axis=-1).astype(
            jnp.int32)
        return jnp.where(valid, nxt, 0)


def _chunk_program(params, k_pages, v_pages, page_rows, tokens,
                   start_pos, n_valid, active, rng_data, *, block,
                   plan, temperature, top_k, emit):
    """Multi-token chunks for a whole lane table, one dp group.

    The ONE program body behind both batched prefill (``emit="last"``,
    S = prefill lanes, C = prefill_chunk) and speculative multi-token
    decode (``emit="all"``, S = decode slots, C = spec_k) — the math
    is identical: write every lane's C tokens' KV into its pages
    through one batched scatter, then attend each query to its own
    pages at positions <= its own (the paged chunk form — for a
    first chunk that reduces to causal self-attention, for decode it
    verifies the drafted chain exactly as sequential steps would).

    k_pages/v_pages (1, L, N, ps, lanes) — the group's pool
    shard; page_rows (1, S, P); tokens (1, S, C) int32 (positions >=
    n_valid[s] are padding); start_pos (1, S) — each lane's first
    ABSOLUTE position; n_valid (1, S) — valid tokens per lane;
    active (1, S) bool — dead lanes write to the scratch page and
    their queries mask out via q_pos = -1; rng_data (1, 2).

    Returns ``(next_tokens, counts (1, n), k_pages, v_pages)``:

    - ``emit="last"``: next_tokens (1, S) int32 — the SAMPLED token
      after each lane's last valid position (argmax at temperature 0,
      per-lane categorical otherwise) — meaningful when the lane's
      chunk completes its prompt;
    - ``emit="all"``: next_tokens (1, S, C) int32 — the ARGMAX after
      EVERY position (position c's argmax is the verified next token
      given tokens[:c+1]); the host accepts the longest prefix whose
      drafts match the chain. Always greedy (EngineConfig forbids
      spec_k > 1 with temperature > 0).

    Inactive lanes' outputs are 0.
    """
    import jax
    import jax.numpy as jnp

    k_pages_g, v_pages_g = _group0(k_pages), _group0(v_pages)
    page_rows, tokens = page_rows[0], tokens[0]
    start_pos, n_valid, active = start_pos[0], n_valid[0], active[0]
    S = tokens.shape[0]
    x, valid, counts, k_pages_g, v_pages_g = _chunk_hidden(
        params, k_pages_g, v_pages_g, page_rows, tokens,
        start_pos, n_valid, active, block=block, plan=plan)
    if emit == "all":
        # The verification chain: logits at EVERY position, argmax
        # only (spec decode is greedy by config contract).
        nxt = _argmax_chain(block, params, x, valid)
    else:
        # emit == "last": each lane's LAST VALID position only — the
        # vocab-sized logits never leave the program.
        with jax.named_scope("dtt.head"):
            last = jnp.maximum(n_valid - 1, 0)[:, None, None]  # (S, 1, 1)
            x_last = jnp.take_along_axis(
                x, jnp.broadcast_to(last, (S, 1, x.shape[-1])),
                axis=1)[:, 0]
            nxt = _sample(block.logits(params, x_last), active,
                          rng_data, temperature, top_k)
    return (nxt[None], counts[None], _grouped(k_pages_g),
            _grouped(v_pages_g))


def _prefill_slots_program(params, k_pages, v_pages, history, kv_len,
                           left, page_rows, tokens, start_pos, n_valid,
                           active, rng_data, slot, max_new, *, block,
                           plan, temperature, top_k, eos_id):
    """Batched prefill (``_chunk_program``, ``emit="last"``) that also
    writes the carried slot table, one dp group: lane s's chunk goes
    into row ``slot[s]`` of ``history`` at its positions, and where the
    chunk ends its prompt (``max_new[s] > 0``: the tokens the request
    asked for; 0 on every other lane) the sampled token goes after it,
    ``kv_len[slot]`` becomes the prompt's length and ``left[slot]``
    what the request may still emit: ``max_new - 1``, or 0 where the
    sample is the stop token. That is the decode invariant the
    resident burst starts from (``history[kv_len]`` the newest token,
    its KV unwritten), so the burst dispatched next needs nothing from
    the host. Dead lanes and padding write nothing (out-of-range rows
    are dropped); two lanes never share a slot.

    history (1, B, Lmax); kv_len, left (1, B); slot, max_new (1, S);
    the rest as ``_chunk_program``. Returns ``(next_tokens (1, S),
    counts (1, n), history, kv_len, left, k_pages, v_pages)``."""
    import jax
    import jax.numpy as jnp

    nxt, counts, k_pages, v_pages = _chunk_program(
        params, k_pages, v_pages, page_rows, tokens, start_pos, n_valid,
        active, rng_data, block=block, plan=plan,
        temperature=temperature, top_k=top_k, emit="last")
    with jax.named_scope("dtt.engine"):
        hist, kvl, lft = history[0], kv_len[0], left[0]
        B = hist.shape[0]
        C = tokens.shape[-1]
        idx = jnp.arange(C, dtype=jnp.int32)
        valid = (idx[None, :] < n_valid[0][:, None]) & active[0][:, None]
        hist = hist.at[jnp.where(valid, slot[0][:, None], B),
                       start_pos[0][:, None] + idx[None, :]].set(
                           tokens[0], mode="drop")
        ends = active[0] & (max_new[0] > 0)
        row = jnp.where(ends, slot[0], B)
        end = start_pos[0] + n_valid[0]
        hist = hist.at[row, end].set(nxt[0], mode="drop")
        kvl = kvl.at[row].set(end, mode="drop")
        stop = nxt[0] == eos_id if eos_id >= 0 else False
        lft = lft.at[row].set(jnp.where(stop, 0, max_new[0] - 1),
                              mode="drop")
    return (nxt, counts, hist[None], kvl[None], lft[None], k_pages,
            v_pages)


def _resident_program(params, k_pages, v_pages, history, kv_len, left,
                      page_rows, budget, active, *, block, plan, K,
                      C, ngram, eos_id):
    """Device-resident K-step decode for one dp group's slot table.

    A ``lax.while_loop`` of up to ``K`` iterations; each iteration
    is one ``C``-wide speculative chunk through ``_chunk_hidden`` —
    the SAME forward as the host-driven spec path, so greedy token
    identity holds by construction (drafts only ever change the
    ACCEPTED PREFIX LENGTH, never a token value, so the in-program
    prompt-lookup draft need not match the host-side index). Per
    iteration each running slot drafts from its own history,
    verifies the argmax chain, truncates at EOS, appends accepted
    tokens to its history row and advances its KV cursor — all
    in-program. The loop predicate exits early once every slot has
    stopped (EOS or budget), so an all-slots-complete burst costs
    the iterations it used, not ``K``.

    k_pages/v_pages (1, L, N, ps, lanes); page_rows (1, B, P).
    The slot table, carried from launch to launch on the device
    (donated in, returned): history (1, B, Lmax) int32 — prompt +
    generated so far, with ``history[kv_len]`` the last generated
    token (its KV not yet written, exactly the host decode
    invariant); kv_len (1, B) — each slot's committed KV length;
    left (1, B) — tokens the slot's request may still emit (0 once it
    met the stop token). The table is the truth: the host packs this
    burst before it has read the one before, from a projection.
    budget (1, B) — max tokens this burst may emit per slot, clamped
    here by ``left`` (the host sized it against page capacity at a
    length no shorter than ``kv_len``: positions written never exceed
    ``kv_len + budget - 1`` because ``kv_len + remaining_budget`` is
    loop-invariant); active (1, B) bool. A slot the host did not pack
    passes through unchanged.

    Returns ``(out (1, B, K*C) emitted tokens, n_emitted (1, B),
    steps (1,) loop iterations used, counts (1, n) the block's
    counters over all iterations, history, kv_len, left, k_pages,
    v_pages)``.
    """
    import jax
    import jax.numpy as jnp

    kp, vp = _group0(k_pages), _group0(v_pages)
    page_rows_g = page_rows[0]
    history_g, kv_len_g, left_g = history[0], kv_len[0], left[0]
    with jax.named_scope("dtt.engine"):
        budget_g = jnp.where(active[0],
                             jnp.minimum(budget[0], left_g), 0)
    B, Lmax = history_g.shape
    T = K * C
    pos = jnp.arange(Lmax, dtype=jnp.int32)

    def draft_cols(hist, hlen, last):
        """Prompt-lookup drafts (B, C-1): for each slot, the longest
        trailing n-gram (n <= ngram) with an EARLIER occurrence in
        ``hist[:hlen]`` proposes its continuation; slots with no
        match repeat ``last``. Vectorized over every window at once
        (ascending n — the longest match overwrites)."""
        draft = jnp.broadcast_to(last[:, None], (B, C - 1))
        for n in range(1, ngram + 1):
            off = jnp.arange(n, dtype=jnp.int32)
            pat_idx = jnp.clip(hlen[:, None] - n + off[None, :],
                               0, Lmax - 1)
            pat = jnp.take_along_axis(hist, pat_idx, axis=1)
            win_idx = jnp.clip(pos[:, None] + off[None, :],
                               0, Lmax - 1)             # (Lmax, n)
            win = hist[:, win_idx]                      # (B, Lmax, n)
            match = (win == pat[:, None, :]).all(-1)
            # earlier occurrences only: the window's continuation
            # position must land strictly inside history, and the
            # trailing gram itself (start hlen-n) is excluded.
            ok = match & ((pos[None, :] + n) < hlen[:, None])
            has = ok.any(axis=1) & (hlen > n)
            p = jnp.max(jnp.where(ok, pos[None, :], -1), axis=1)
            cont_idx = (p[:, None] + n
                        + jnp.arange(C - 1, dtype=jnp.int32)[None, :])
            cont = jnp.take_along_axis(
                hist, jnp.clip(cont_idx, 0, Lmax - 1), axis=1)
            cont = jnp.where(cont_idx < hlen[:, None], cont,
                             last[:, None])
            draft = jnp.where(has[:, None], cont, draft)
        return draft

    def cond(carry):
        j, running = carry[0], carry[7]
        return (j < K) & running.any()

    def body(carry):
        (j, out, n_em, kvl, bud, lft, hist, running, counts, kp,
         vp) = carry
        n = jnp.where(running, jnp.minimum(C, bud), 0).astype(
            jnp.int32)
        last = jnp.take_along_axis(hist, kvl[:, None], axis=1)[:, 0]
        if C > 1:
            tokens = jnp.concatenate(
                [last[:, None], draft_cols(hist, kvl + 1, last)],
                axis=1)
        else:
            tokens = last[:, None]
        x, valid, c, kp, vp = _chunk_hidden(
            params, kp, vp, page_rows_g, tokens, kvl, n, running,
            block=block, plan=plan)
        nxt = _argmax_chain(block, params, x, valid)    # (B, C)
        if C > 1:
            sl = jnp.arange(C - 1, dtype=jnp.int32)
            match = ((tokens[:, 1:] == nxt[:, :-1])
                     & (sl[None, :] < (n - 1)[:, None]))
            e = 1 + jnp.sum(
                jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
        else:
            e = jnp.ones((B,), jnp.int32)
        e = jnp.where(n > 0, e, 0).astype(jnp.int32)
        cl = jnp.arange(C, dtype=jnp.int32)
        if eos_id >= 0:
            is_eos = (nxt == eos_id) & (cl[None, :] < e[:, None])
            any_eos = is_eos.any(axis=1)
            e = jnp.where(
                any_eos,
                jnp.argmax(is_eos, axis=1).astype(jnp.int32) + 1, e)
        else:
            any_eos = jnp.zeros((B,), jnp.bool_)
        # scatter this iteration's accepted tokens into the output
        # block at each slot's emission cursor, and append them to
        # the history row right after its current last token: B x C
        # values, the unaccepted columns (and any position past the
        # row) sent out of range and dropped.
        acc = cl[None, :] < e[:, None]
        rows = jnp.arange(B, dtype=jnp.int32)[:, None]
        out = out.at[rows, jnp.where(acc, n_em[:, None] + cl, T)].set(
            nxt, mode="drop")
        hist = hist.at[rows, jnp.where(acc, (kvl + 1)[:, None] + cl,
                                       Lmax)].set(nxt, mode="drop")
        n_em = n_em + e
        kvl = kvl + e
        bud = bud - e
        lft = jnp.where(any_eos, 0, lft - e)
        running = running & (bud > 0) & ~any_eos
        return (j + 1, out, n_em, kvl, bud, lft, hist, running,
                counts + c, kp, vp)

    # The loop, its draft, its stop conditions and what it appends are
    # the engine's; ``_chunk_hidden`` inside names the model's parts.
    with jax.named_scope("dtt.engine"):
        init = (jnp.zeros((), jnp.int32),
                jnp.zeros((B, T), jnp.int32),
                jnp.zeros((B,), jnp.int32),
                kv_len_g, budget_g, left_g, history_g, budget_g > 0,
                jnp.zeros((len(_counters(block, plan)),), jnp.int32),
                kp, vp)
        j, out, n_em, kvl, _bud, lft, hist, _run, counts, kp, vp = \
            jax.lax.while_loop(cond, body, init)
    return (out[None], n_em[None], jnp.reshape(j, (1,)), counts[None],
            hist[None], kvl[None], lft[None], _grouped(kp),
            _grouped(vp))
