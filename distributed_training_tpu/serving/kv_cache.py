"""Paged KV cache: fixed-size pages in one preallocated, sharded pool.

Per-request max_len buffers waste HBM quadratically under continuous
batching (every slot reserves the worst case); the paged layout is
virtual memory for KV instead. One reservation of ``dp_groups``
independent pool shards of ``num_pages`` pages of ``page_size`` tokens
each, per layer, stored the way the chip tiles it (``PoolLayout``):

    k_pages, v_pages: (dp_groups, n_layers, num_pages, page_size,
                       lanes)

token-major, a token's row of a layer being its kv heads side by side
in a whole number of 128-lane tiles. ``PoolLayout`` and the views it
hands out (``PoolLayer``) are the ONLY code that knows this order:
every writer and reader of the pool (the engine's scatter and its
hand-over to attention, both forms of paged attention, copy-on-write,
the KV hand-off's export and import) goes through them.

What a row of each pool holds is the model's (``kind``, from its
serving block, ``serving/blocks.py``): ``"kv"`` a key and a value a kv
head, both ``head_dim`` wide; ``"latent"`` one row a token shared by
all heads (``n_kv_heads == 1``), the latent vector (``head_dim`` =
its rank) in ``k_pages`` and the rotary key (``v_head_dim`` wide) in
``v_pages``. Everything but the width of a row (tables, allocator,
prefix index, copy-on-write) is one code path.

A sequence owns an ordered list of physical page ids (its PAGE TABLE)
inside ONE dp group's shard; logical position ``p`` lives in slot
``p % page_size`` of its ``p // page_size``-th page. Join = allocate
pages from the group's free list, evict = return them — no copying, no
compaction, and the device arrays never change shape, so the decode
program never recompiles.

**Two kinds of layer (PR 32).** A model may mix GLOBAL layers, which
attend every earlier position, with WINDOW layers, which attend the
last ``window`` (``cfg.window_layers``, from the model's serving block).
The cache then holds a pool and an allocator a kind (``Pools``), and a
sequence a page row a kind: the global row is the table above and grows
with the sequence; the window row is a RING of ``cfg.ring_pages`` pages,
taken as the sequence grows and never more, logical page ``p`` living
in ring entry ``p % ring_pages`` (position ``p`` in ring slot ``p %
(ring_pages * page_size)``: a row overwrites the row one ring before
it, which no query can see any more because the ring holds the window
AND the most rows one launch writes before it reads, ``max_write``).
``page_row`` hands the programs both rows side by side, the global
entries first. The window pool holds ``slots * ring_pages`` pages a
group, its exact worst case, so it is never the pool a sequence waits
on. Whatever moves pages by ONE table a sequence (the prefix index,
``attach``, ``privatize``, ``rename``, ``read_pages`` / ``write_pages``)
refuses such a cache by name (``full_tables_only``); a model without
window layers gets exactly the single pool described above.

**Rows a kind, and a third row (PR 34).** The two kinds need not store
rows of one width: a window layer's may be wider or narrower than a
global layer's (``cfg.window_head_dim`` / ``window_v_head_dim``), so a
layout belongs to a kind (``PagedKVCache.layouts(cfg, shards, kind)``,
``PoolPlan.of(kind)``). And a global layer may keep a THIRD row a
token, the key a learned selection scores every iteration
(``cfg.index_dim`` wide, one a token): the INDEX pool, a member of
``k_pages``' ``Pools`` beside the table it belongs to. It has the
global pool's pages and is addressed by the global table, so one
allocator a kind still serves: a page taken or freed is taken or freed
in both, and the index pool is donated, carried and deleted with
``k_pages``. Such a cache, too, refuses whatever moves pages by one
table (``full_tables_only``): those movers copy two rows a token.

**Page 0 of every group is that group's scratch page**: never
allocated, the write target for inactive batch slots and padding
positions (the jitted decode/prefill programs write unconditionally;
pointing dead writes at scratch keeps them out of live pages without
dynamic shapes). Unused page-table entries also point at it — their
slots are masked out of attention by position, so the garbage is never
read into a softmax.

**Sharding**: on a multi-device mesh the pool is sharded along the
LEADING dp-group axis over the plan's ``dp`` mesh axis (the decode
engine's batch-parallel slot shard — each dp group decodes only its
own slots against its own pool shard, serving/engine.py) and along the
lanes, a shard's kv heads in whole tiles of their own, over the plan's
``tp`` axis (the decode plan's head currency), replicated elsewhere.
Page tables/lengths are tiny int32 rows and stay host-side.

**Accounting**: the allocator is host-side (plain Python — allocation
decisions are control flow, not math), PER GROUP, and every alloc/free
emits a ``serving_kv`` telemetry record with the pool occupancy AND
the owning group, which the metrics endpoint folds into
``dtt_serving_kv_pages_{used,total}`` plus the per-group labeled
gauges. Invariant (pinned by test, per shard): for every group,
``pages_used_in(g) + free == num_pages - 1`` always, and freeing every
sequence returns every group's occupancy to zero — no join/evict order
can leak a page or let one group's allocation bleed into another's
shard.

**Sharing (SERVING_r05)**: pages are REFCOUNTED per (group, page).
``attach`` lets a new sequence take read-only references on another
sequence's committed pages (its table becomes a view of the shared
prefix); ``free`` returns a page to the free list only when its LAST
owner releases it, so the leak invariant extends unchanged — a page is
"used" while any table holds it. A group-local PREFIX INDEX maps the
exact bytes of each page-aligned token prefix to the page ids holding
its KV (``register_prefix``/``match_prefix``); entries are registered
only for FULLY COMMITTED pages (every slot written, so the content is
immutable — later writes go through copy-on-write) and invalidated
when their last page's refcount hits zero. ``privatize`` is the COW
half: before a sequence writes into a page it shares (only the page at
``length // page_size`` can qualify — committed pages below it are
never written again), the shared page is swapped for a fresh private
one and the caller performs the one batched device copy. ``rename``
moves a table between owner keys without touching refcounts — the
engine's session retention (a finished chat turn parks its pages under
a session key for zero-prefill resume).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple

import jax
import numpy as np

from distributed_training_tpu.telemetry import event

LANES = 128     # the minor dimension of a TPU tile, whatever the dtype


def _padded(xp, x, axis: int, size: int):
    """``x`` with zeros appended along ``axis`` up to ``size``."""
    short = size - x.shape[axis]
    if not short:
        return x
    zeros = x.shape[:axis] + (short,) + x.shape[axis + 1:]
    return xp.concatenate([x, xp.zeros(zeros, x.dtype)], axis=axis)


@dataclass(frozen=True)
class PoolLayout:
    """How a pool stores a token's row of a layer, and every index
    into it: a function of the widths the cache can observe.

    One group's pool is ``(n_layers, num_pages, page_size, lanes)``.
    Token-major, because the scatter that writes it decides the order
    XLA carries it in whatever order it is declared in. A token's row
    is cut into TILES of whole 128-lane multiples, because a minor
    dimension under 128 costs 128 lanes of HBM in every read and
    write: ``per`` kv heads of ``width`` side by side a tile (heads of
    64 go two a tile, 25 of them as 13 tiles with the last half tile
    zero and never read into a result; a head of 128 or a 512-wide
    latent row is one lane-full tile as it is). Declared in that
    order the entry parameter, a loop's carry and the donated result
    have one layout and no program re-lays the pool out. With the
    heads sharded over ``shards`` devices each shard's heads fill
    tiles of their own, so a shard holds whole rows.

    The contractions of paged attention run on the tiles as they lie
    (``PoolLayer.slots`` / ``.pages``): ``spread`` puts each query
    head on the lanes its kv head has in its tile, zeros on the
    others', and ``collect`` takes each head's own lanes of the
    result. The zeros cost MXU rows, not bytes, and change no sum.
    """

    heads: int
    width: int
    shards: int = 1

    @property
    def per(self) -> int:
        """kv heads a tile."""
        return max(1, LANES // self.width)

    @property
    def tile(self) -> int:
        """Lanes a tile."""
        return -(-self.per * self.width // LANES) * LANES

    @property
    def tiles(self) -> int:
        """Tiles a token's row of a layer, over all shards."""
        return self.shards * -(-self.heads // self.shards // self.per)

    @property
    def lanes(self) -> int:
        """Lanes a token's row of a layer takes, padding included."""
        return self.tiles * self.tile

    def shape(self, n_layers: int, num_pages: int,
              page_size: int) -> tuple:
        """One group's pool."""
        return (n_layers, num_pages, page_size, self.lanes)

    def _to_tiles(self, x, axis: int):
        """``x`` with its kv-head axis ``(heads,)`` made ``(tiles,
        per)``: each shard's heads dealt to its own tiles, zeros where
        a shard's last tile is short of heads."""
        xp = np if isinstance(x, np.ndarray) else jax.numpy
        axis %= x.ndim
        lead, rest = x.shape[:axis], x.shape[axis + 1:]
        x = x.reshape(lead + (self.shards, -1) + rest)
        x = _padded(xp, x, axis + 1,
                    self.tiles // self.shards * self.per)
        return x.reshape(lead + (self.tiles, self.per) + rest)

    def _from_tiles(self, x, axis: int):
        """``_to_tiles`` the other way, the padding heads dropped."""
        axis %= x.ndim
        lead, rest = x.shape[:axis], x.shape[axis + 2:]
        x = x.reshape(lead + (self.shards, -1) + rest)
        x = x[(slice(None),) * (axis + 1)
              + (slice(self.heads // self.shards),)]
        return x.reshape(lead + (self.heads,) + rest)

    def pack(self, rows):
        """``(..., heads, width)`` rows cut into tiles, ``(..., tiles,
        tile)``; numpy in, numpy out."""
        xp = np if isinstance(rows, np.ndarray) else jax.numpy
        rows = self._to_tiles(rows, -2)
        rows = rows.reshape(rows.shape[:-2] + (self.per * self.width,))
        return _padded(xp, rows, rows.ndim - 1, self.tile)

    def stored(self, rows):
        """``(..., heads, width)`` rows as the pool's last axis holds
        them, ``(..., lanes)``."""
        tiles = self.pack(rows)
        return tiles.reshape(tiles.shape[:-2] + (self.lanes,))

    def unpack(self, tiles):
        """``pack`` the other way, the padding dropped: ``(...,
        tiles, tile)`` to ``(..., heads, width)``."""
        rows = tiles[..., :self.per * self.width].reshape(
            tiles.shape[:-1] + (self.per, self.width))
        return self._from_tiles(rows, -3)

    def spread(self, q):
        """Queries ``(B, S, H, width)``, ``H`` a multiple of the kv
        heads, onto the tiles: ``(B, S, tiles, per * group, tile)``,
        each query head on the lanes of its kv head and zero on its
        tile-mates', so that a contraction over a tile's lanes is the
        head's own."""
        jnp = jax.numpy
        B, S, H, _ = q.shape
        if H % self.heads:
            raise ValueError(f"n_heads {H} not divisible by "
                             f"n_kv_heads {self.heads}")
        q = self._to_tiles(
            q.reshape(B, S, self.heads, H // self.heads, self.width), 2)
        q = jnp.einsum("bstjgw,jk->bstjgkw", q,
                       jnp.eye(self.per, dtype=q.dtype))
        return _padded(jnp, q.reshape(q.shape[:3] + (
            -1, self.per * self.width)), 4, self.tile)

    def collect(self, out):
        """What ``spread`` queries gave, ``(B, S, tiles, per * group,
        tile)``, back to ``(B, S, H, width)``: of each query head's
        tile the lanes of its own kv head."""
        B, S = out.shape[:2]
        out = out[..., :self.per * self.width].reshape(
            B, S, self.tiles, self.per, -1, self.per, self.width)
        out = jax.numpy.einsum("bstjgjw->bstjgw", out)
        return self._from_tiles(out, 2).reshape(B, S, -1, self.width)

    def tiled(self, stored):
        """``(..., lanes)`` as the pool holds it, cut into its tiles:
        ``(..., tiles, tile)``."""
        return stored.reshape(stored.shape[:-1]
                              + (self.tiles, self.tile))

    def page_size(self, pool) -> int:
        """Slots a page of a group's pool."""
        return pool.shape[2]

    def write(self, pool, layer, page_ids, offsets, rows):
        """Scatter ``rows (B, heads, width)`` into ``layer`` of a
        group's pool at ``(page_ids, offsets)`` (each ``(B,)``), where
        the pool lies."""
        return pool.at[layer, page_ids, offsets].set(self.stored(rows))

    def layer(self, pool, number) -> "PoolLayer":
        """Layer ``number`` of a group's carried pool, to be read."""
        return PoolLayer(self, pool, number)

    def take_pages(self, pools, groups, pages):
        """Pages ``(groups[i], pages[i])`` of the whole (grouped)
        pool, every layer: ``(n, n_layers, heads, page_size, width)``
        on the device."""
        got = self.unpack(self.tiled(pools[groups, :, pages]))
        return got.transpose(0, 1, 3, 2, 4)          # from (n, L, ps, ..)

    def put_pages(self, pools, groups, pages, chunks):
        """``take_pages`` the other way: the whole pool with ``chunks
        (n, n_layers, heads, page_size, width)`` written over pages
        ``(groups[i], pages[i])``."""
        return pools.at[groups, :, pages].set(
            self.stored(chunks.transpose(0, 1, 3, 2, 4)))


def copy_pages(pool, src, dst):
    """A group's pool with pages ``src`` copied over pages ``dst``
    (each ``(W,)``), every layer."""
    return pool.at[:, dst].set(pool[:, src])


class Pools(NamedTuple):
    """The pools of a cache with more than one, in place of the one
    array a uniform cache has: ``full`` holds the global layers (tables
    that grow), ``ring`` the window layers (a ring a sequence; None
    without window layers), ``index`` the global layers' third row, the
    key a learned selection scores (``k_pages`` only, at the global
    table's pages; None where the model keeps none). A pytree, so it
    crosses ``jax.jit`` and is donated like the array it stands for;
    ``delete`` frees every member."""

    full: object
    ring: object = None
    index: object = None

    def delete(self) -> None:
        for pool in self:
            if pool is not None:
                pool.delete()


GLOBAL, WINDOW, INDEX = "global", "window", "index"


def pool_of(pools, kind: str):
    """The pool of ``kind`` layers: ``pools`` itself where the cache is
    uniform (one array)."""
    if not isinstance(pools, Pools):
        return pools
    return pools.ring if kind == WINDOW else pools.full


def with_pool(pools, kind: str, pool):
    """``pools`` with the pool of ``kind`` layers replaced."""
    if not isinstance(pools, Pools):
        return pool
    return pools._replace(**{"ring" if kind == WINDOW else "full": pool})


@dataclass(frozen=True)
class PoolPlan:
    """What every program knows of the cache when it is traced: how the
    two pools store a global layer's row (``k``, ``v``) and a window
    layer's (``ring_k``, ``ring_v``: ``of(kind)``), how wide a
    sequence's table is (``pages_per_seq``), where the model has window
    layers which they are, how wide the window and how many pages a
    ring, and where its global layers keep an index key a token, how
    the index pool stores it (``index``) and how many positions the
    selection keeps (``index_topk``) (``PagedKVCache.plan``). Static in
    every program."""

    k: PoolLayout
    v: PoolLayout
    n_layers: int
    pages_per_seq: int
    window: int = 0
    window_layers: tuple = ()
    ring_pages: int = 0
    ring_k: PoolLayout | None = None
    ring_v: PoolLayout | None = None
    index: PoolLayout | None = None
    index_topk: int = 0

    def of(self, kind: str) -> tuple:
        """``(k_layout, v_layout)`` of ``kind`` layers' rows."""
        if kind == WINDOW:
            return self.ring_k or self.k, self.ring_v or self.v
        return self.k, self.v

    def run(self, lo: int, hi: int) -> tuple:
        """``(kind, first)`` for the run of like layers ``[lo, hi)``:
        which pool holds them and the number of layer ``lo`` in it (a
        pool's layers are its kind's, in model order)."""
        inside = [n in self.window_layers for n in range(lo, hi)]
        if any(inside) != all(inside):
            raise ValueError(
                f"layers {lo}..{hi - 1} are one run of the block's "
                f"segments but mix window and global layers "
                f"(window layers: {self.window_layers})")
        if not inside[0]:
            return GLOBAL, lo - sum(n < lo for n in self.window_layers)
        return WINDOW, sum(n < lo for n in self.window_layers)

    def rows(self, page_rows) -> dict:
        """A launch's page rows ``(..., pages_per_seq + ring_pages)``
        cut into each kind's: ``{kind: (..., width)}``."""
        if not self.window_layers:
            return {GLOBAL: page_rows}
        return {GLOBAL: page_rows[..., :self.pages_per_seq],
                WINDOW: page_rows[..., self.pages_per_seq:]}


class PoolLayer:
    """One layer of a group's carried pool, handed to attention
    unread: the carried pool and the layer's number, so that a reader
    indexes what it needs straight out of the pool with no slice of
    the layer first. Both reads give tiles as they are stored
    (``PoolLayout``: ``layout.unpack`` makes heads of them, ``spread``
    and ``collect`` contract on them as they lie). A pytree (the pool
    and the number its leaves), so it crosses ``jax.jit`` like the
    arrays it stands for."""

    def __init__(self, layout: PoolLayout, pool, number):
        self.layout, self.pool, self.number = layout, pool, number

    num_pages = property(lambda self: self.pool.shape[1])
    page_size = property(lambda self: self.layout.page_size(self.pool))
    dtype = property(lambda self: self.pool.dtype)

    def slots(self):
        """Every slot of the layer in physical order, ``(num_pages *
        page_size, tiles, tile)``: the pool form's read."""
        with jax.named_scope("dtt.kv.read"):
            tiles = self.layout.tiled(self.pool[self.number])
            return tiles.reshape((-1,) + tiles.shape[2:])

    def pages(self, page_indices):
        """The pages of ``page_indices (B, P)`` dense in table order,
        ``(B, P * page_size, tiles, tile)``: the gather form's read,
        indexed ``(layer, page)`` out of the carried pool. Slot ``s``
        of row ``b`` is logical position ``s`` of the sequence."""
        with jax.named_scope("dtt.kv.read"):
            tiles = self.layout.tiled(
                self.pool[self.number, page_indices])
            return tiles.reshape(
                tiles.shape[:1] + (-1,) + tiles.shape[3:])

    def rows(self, page_ids, offsets):
        """The rows at ``(page_ids, offsets)`` (one shape, any), ``(...,
        tiles, tile)``: the read of a selection, which takes of a table
        the rows it chose and no page whole."""
        with jax.named_scope("dtt.kv.read"):
            return self.layout.tiled(
                self.pool[self.number, page_ids, offsets])


def as_layer(pages) -> PoolLayer:
    """Head-major keys or values ``(heads, num_pages, page_size,
    width)``, the way a test or a calibration table writes a layer
    down, as the one layer of a pool the cache would store."""
    heads, _num_pages, _page_size, width = pages.shape
    layout = PoolLayout(heads, width)
    # The number on the device like the pool: as a Python int it would
    # be sent along with every call of a jitted reader.
    return layout.layer(
        layout.stored(pages.transpose(1, 2, 0, 3))[None],
        jax.numpy.zeros((), jax.numpy.int32))


jax.tree_util.register_pytree_node(
    PoolLayer, lambda v: ((v.pool, v.number), v.layout),
    lambda layout, leaves: PoolLayer(layout, *leaves))


@dataclass(frozen=True)
class PagedCacheConfig:
    """Pool geometry. ``max_seq_len`` bounds pages per sequence;
    ``num_pages`` is PER GROUP (each dp group owns its own shard of
    ``num_pages`` pages, scratch included). ``head_dim`` is the width
    of a ``k_pages`` row, ``v_head_dim`` of a ``v_pages`` row (0 = the
    same); a window layer's rows have ``window_head_dim`` /
    ``window_v_head_dim`` where those are given (0 = as the global
    layers'). ``index_dim``: the width of the third row a token that
    every global layer keeps in the index pool (0 = none), and
    ``index_topk`` the positions its selection keeps, for the
    engine's counter."""

    n_layers: int
    n_kv_heads: int
    head_dim: int
    v_head_dim: int = 0
    kind: str = "kv"              # what the rows hold: "kv" | "latent"
    page_size: int = 16
    num_pages: int = 128          # per group, scratch page 0 included
    max_seq_len: int = 256
    dtype: str = "float32"
    dp_groups: int = 1            # leading pool dim / allocator shards
    # Window layers (from the block; none = one uniform pool): which
    # layers attend only the last ``window`` positions, the most rows
    # one launch writes of a sequence before it reads (the engine's
    # prefill chunk or speculative width), the slots a group (the
    # window pool holds ``slots`` rings), and the block's name, for
    # the refusals.
    window: int = 0
    window_layers: tuple = ()
    max_write: int = 1
    slots: int = 0
    block: str = ""
    window_head_dim: int = 0
    window_v_head_dim: int = 0
    index_dim: int = 0
    index_topk: int = 0

    def __post_init__(self):
        if self.v_head_dim == 0:
            object.__setattr__(self, "v_head_dim", self.head_dim)
        if (self.window_head_dim or self.window_v_head_dim) \
                and not self.window_layers:
            raise ValueError("window_head_dim / window_v_head_dim are "
                             "the widths of window layers' rows, and "
                             "there are no window_layers")
        if bool(self.index_dim) != bool(self.index_topk):
            raise ValueError(
                f"index_dim ({self.index_dim}) and index_topk "
                f"({self.index_topk}) come together: the key a "
                "selection scores and how many positions it keeps")
        object.__setattr__(self, "window_layers",
                           tuple(sorted(self.window_layers)))
        if self.window_layers:
            if self.window < 1:
                raise ValueError("window layers need window >= 1, got "
                                 f"{self.window}")
            if not set(self.window_layers) <= set(range(self.n_layers)):
                raise ValueError(
                    f"window_layers {self.window_layers} not all in "
                    f"[0, {self.n_layers})")
            if len(self.window_layers) == self.n_layers:
                raise ValueError(
                    "every layer a window layer: the cache keeps the "
                    "global pool for the sequence's table, so at least "
                    "one layer must be global")
            if self.slots < 1:
                raise ValueError("a cache with window layers needs "
                                 "slots (a group) >= 1: its window "
                                 "pool holds one ring a slot")
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got "
                             f"{self.page_size}")
        if self.num_pages < 2:
            raise ValueError(
                f"num_pages must be >= 2 (page 0 is scratch), got "
                f"{self.num_pages}")
        if self.max_seq_len % self.page_size:
            raise ValueError(
                f"max_seq_len ({self.max_seq_len}) must be a multiple "
                f"of page_size ({self.page_size})")
        if self.dp_groups < 1:
            raise ValueError(
                f"dp_groups must be >= 1, got {self.dp_groups}")

    @property
    def pages_per_seq(self) -> int:
        return self.max_seq_len // self.page_size

    @property
    def ring_pages(self) -> int:
        """Pages of a sequence's ring in the window pool: the window
        and the rows one launch may write ahead of a query, never more
        than a whole table; 0 without window layers."""
        if not self.window_layers:
            return 0
        return min(self.pages_per_seq,
                   -(-(self.window + self.max_write) // self.page_size))

    @property
    def row_width(self) -> int:
        """Entries of a sequence's page row as the programs take it:
        the table, then the ring."""
        return self.pages_per_seq + self.ring_pages

    @property
    def window_num_pages(self) -> int:
        """Pages a group of the window pool, scratch included: a ring
        a slot, the exact worst case."""
        return self.slots * self.ring_pages + 1 if self.window_layers \
            else 0

    @property
    def window_usable_pages_total(self) -> int:
        """Pages of the window pool a sequence can be given, all
        groups: a ring a slot (0 without window layers)."""
        return self.dp_groups * self.slots * self.ring_pages

    @property
    def global_layers(self) -> tuple:
        return tuple(n for n in range(self.n_layers)
                     if n not in self.window_layers)

    @property
    def usable_pages(self) -> int:
        return self.num_pages - 1  # per group, minus scratch

    @property
    def usable_pages_total(self) -> int:
        return self.dp_groups * self.usable_pages

    def widths(self, kind: str) -> tuple:
        """``(k_pages row, v_pages row)`` widths a kv head of ``kind``
        layers."""
        if kind == WINDOW:
            return (self.window_head_dim or self.head_dim,
                    self.window_v_head_dim or self.v_head_dim)
        return self.head_dim, self.v_head_dim

    def row_bytes(self, kind: str) -> int:
        """What one cached token costs in ONE layer of ``kind``
        (``"index"``: its key in the index pool), lane padding not
        counted."""
        # numpy alone has no bfloat16
        itemsize = jax.numpy.dtype(self.dtype).itemsize
        if kind == INDEX:
            return self.index_dim * itemsize
        return self.n_kv_heads * sum(self.widths(kind)) * itemsize

    def kv_bytes_per_token(self) -> int:
        """HBM cost of one cached token across all layers: each kind's
        layers at that kind's widths, and the index key of every global
        layer that keeps one (a row of each pool; lane padding not
        counted)."""
        return (len(self.global_layers)
                * (self.row_bytes(GLOBAL) + self.row_bytes(INDEX))
                + len(self.window_layers) * self.row_bytes(WINDOW))


def kv_shards(mesh, kv_axis: str | None) -> int:
    """Devices the pool's kv heads are sharded over: the extent of
    ``kv_axis`` on ``mesh``, 1 without either."""
    if mesh is None or not kv_axis:
        return 1
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(
        kv_axis, 1)


def pool_sharding(mesh, n_kv_heads: int, dp_groups: int,
                  kv_axis: str | None, dp_axis: str | None):
    """The pool's NamedSharding on ``mesh`` (None when no mesh):
    leading group dim over ``dp_axis``, the lanes (a shard's kv heads
    in whole rows, ``PoolLayout``) over ``kv_axis``, each when its
    extent > 1. ONE resolution shared by the cache's device_put and
    the engine's program ``out_shardings`` (serving/engine.py) — if
    they disagreed, every step's donated pool would come back in a
    different layout and the decode program would recompile
    mid-storm."""
    if mesh is None:
        return None
    from jax.sharding import NamedSharding, PartitionSpec as P
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    kv_ax = kv_axis if kv_shards(mesh, kv_axis) > 1 else None
    if kv_ax is not None and n_kv_heads % sizes[kv_ax]:
        raise ValueError(
            f"kv pool cannot shard {n_kv_heads} kv heads over "
            f"{kv_axis}={sizes[kv_ax]}")
    dp_ax = dp_axis if dp_axis and sizes.get(dp_axis, 1) > 1 else None
    if dp_ax is not None and dp_groups != sizes[dp_ax]:
        raise ValueError(
            f"pool has {dp_groups} dp group(s) but mesh axis "
            f"'{dp_axis}' has extent {sizes[dp_ax]} — the allocator "
            "groups must be the mesh's dp groups")
    return NamedSharding(mesh, P(dp_ax, None, None, None, kv_ax))


class PagedKVCache:
    """The pool + its per-group host-side allocators and page tables.

    ``mesh``/``kv_axis``/``dp_axis``: shard the pools' kv heads
    over ``kv_axis`` and the leading group dim over ``dp_axis``
    (either skipped when its axis has extent 1 or no mesh is given).
    ``cfg.dp_groups`` must equal the ``dp_axis`` extent when that axis
    is sharded — the allocator groups ARE the mesh's dp groups. The
    device pools are handed to the engine's jitted programs as donated
    inputs; the engine writes the updated arrays back via
    ``update_pools`` each step.
    """

    def __init__(self, cfg: PagedCacheConfig, mesh=None,
                 kv_axis: str | None = None,
                 dp_axis: str | None = "dp"):
        import jax.numpy as jnp

        self.cfg = cfg
        self.sharding = sharding = pool_sharding(
            mesh, cfg.n_kv_heads, cfg.dp_groups, kv_axis, dp_axis)
        shards = kv_shards(mesh, kv_axis)
        self.k_layout, self.v_layout = self.layouts(cfg, shards)

        def pool(shape):
            # Two DISTINCT buffers: k and v are donated separately to
            # the jitted programs, and donating one aliased array
            # twice is an XLA error.
            z = jnp.zeros(shape, jnp.dtype(cfg.dtype))
            return jax.device_put(z, sharding) \
                if sharding is not None else z

        self.k_pages, self.v_pages = (
            Pools(*(s and pool(s) for s in shapes))
            if isinstance(shapes, Pools) else pool(shapes)
            for shapes in self.pool_shapes(cfg, shards))
        # Host allocator state, PER GROUP. Free lists are LIFO:
        # recently-freed pages are re-handed first (warm in cache, and
        # deterministic for the tests' join/evict permutations).
        self._frees: list[list[int]] = [
            list(range(cfg.num_pages - 1, 0, -1))
            for _ in range(cfg.dp_groups)]
        # The window pool's allocator, a group (empty lists without
        # window layers): a sequence's ring, entry ``e`` holding its
        # logical pages ``e, e + ring_pages, ...`` in turn. Never
        # shared, so no refcounts.
        self._ring_frees: list[list[int]] = [
            list(range(cfg.window_num_pages - 1, 0, -1))
            for _ in range(cfg.dp_groups)]
        self._rings: dict[object, list[int]] = {}
        self._tables: dict[object, list[int]] = {}
        self._lengths: dict[object, int] = {}
        self._groups: dict[object, int] = {}
        # Sharing state, PER GROUP. ``_refs[g][page]`` counts the
        # tables holding ``page`` (absent == on the free list);
        # ``_index[g]`` maps the exact bytes of a page-aligned token
        # prefix to the page ids holding its KV; ``_page_keys[g]``
        # maps a page id to the index keys whose LAST page it is (a
        # key dies exactly when its last page is released — earlier
        # pages outlive it by the prefix-holding property, so one
        # reverse entry per key suffices). ``_registered`` tracks how
        # many of each sequence's pages are already in the index.
        self._refs: list[dict[int, int]] = [
            {} for _ in range(cfg.dp_groups)]
        self._index: list[dict[bytes, tuple]] = [
            {} for _ in range(cfg.dp_groups)]
        self._page_keys: list[dict[int, set]] = [
            {} for _ in range(cfg.dp_groups)]
        self._registered: dict[object, int] = {}

    # -- the stored layout -------------------------------------------------

    @staticmethod
    def layouts(cfg: PagedCacheConfig, shards: int = 1,
                kind: str = GLOBAL) -> tuple:
        """``(k_layout, v_layout)``: how the two pools of ``cfg``
        store a row of a ``kind`` layer, their heads over ``shards``
        devices. The one place a layout is chosen, from the widths
        alone; the engine's programs take theirs from here as the
        cache does."""
        k, v = cfg.widths(kind)
        return (PoolLayout(cfg.n_kv_heads, k, shards),
                PoolLayout(cfg.n_kv_heads, v, shards))

    @staticmethod
    def index_layout(cfg: PagedCacheConfig):
        """How the index pool stores a token's key (one a token, for
        every head of the selection); None where ``cfg`` keeps none."""
        return PoolLayout(1, cfg.index_dim) if cfg.index_dim else None

    @classmethod
    def plan(cls, cfg: PagedCacheConfig, shards: int = 1) -> PoolPlan:
        """What the engine's programs are traced with (``PoolPlan``)."""
        ring = cls.layouts(cfg, shards, WINDOW) if cfg.window_layers \
            else (None, None)
        return PoolPlan(*cls.layouts(cfg, shards), cfg.n_layers,
                        cfg.pages_per_seq, cfg.window,
                        cfg.window_layers, cfg.ring_pages, *ring,
                        cls.index_layout(cfg), cfg.index_topk)

    @classmethod
    def pool_shapes(cls, cfg: PagedCacheConfig,
                    shards: int = 1) -> tuple:
        """The shapes of ``k_pages`` and ``v_pages``, nothing
        allocated (what an abstract lowering needs): a shape each, or
        a ``Pools`` where there is more than one pool: with window
        layers the global layers in ``num_pages`` pages and the window
        layers in a ring a slot, each at its kind's widths, and in
        ``k_pages`` the index pool at the global pool's pages where
        ``cfg`` keeps an index key."""
        def shape(lay, layers, pages):
            return (cfg.dp_groups,) + lay.shape(len(layers), pages,
                                                cfg.page_size)

        def shapes(which):
            full = shape(cls.layouts(cfg, shards)[which],
                         cfg.global_layers, cfg.num_pages)
            ring = shape(cls.layouts(cfg, shards, WINDOW)[which],
                         cfg.window_layers, cfg.window_num_pages) \
                if cfg.window_layers else None
            index = shape(cls.index_layout(cfg), cfg.global_layers,
                          cfg.num_pages) \
                if cfg.index_dim and which == 0 else None
            if ring is None and index is None:
                return full
            return Pools(full, ring, index)
        return shapes(0), shapes(1)

    def pools(self) -> list:
        """One entry a pool: its kind, its layers, its pages (all
        groups, scratch included), the pages of a sequence's ring (0:
        a table that grows), the lanes a row takes in ``k_pages`` and
        ``v_pages`` as stored (``row_lanes``), what a token costs in it
        a layer before lane padding (``row_bytes``), and what the pool
        takes as stored in all and a token (``serving_warmup``'s
        ``pools``). The index pool, where there is one, is an entry of
        its own with the global pool's pages."""
        cfg = self.cfg
        itemsize = jax.numpy.dtype(cfg.dtype).itemsize
        kinds = [(GLOBAL, cfg.global_layers, cfg.num_pages, 0,
                  self.layouts(cfg, self.k_layout.shards))]
        if cfg.window_layers:
            kinds.append((WINDOW, cfg.window_layers,
                          cfg.window_num_pages, cfg.ring_pages,
                          self.layouts(cfg, self.k_layout.shards,
                                       WINDOW)))
        if cfg.index_dim:
            kinds.append((INDEX, cfg.global_layers, cfg.num_pages, 0,
                          (self.index_layout(cfg),)))
        out = []
        for kind, layers, pages, ring, lays in kinds:
            lanes = [lay.lanes for lay in lays]
            row = sum(lanes) * itemsize
            out.append({
                "kind": kind, "layers": list(layers),
                "pages": cfg.dp_groups * pages, "ring_pages": ring,
                "row_lanes": lanes, "row_bytes": cfg.row_bytes(kind),
                "bytes": cfg.dp_groups * pages * cfg.page_size
                * len(layers) * row,
                "bytes_per_token": len(layers) * row})
        return out

    def footprint(self) -> dict:
        """What the pools take: their stored shapes, the bytes of the
        rows alone (``pool_bytes``) and as stored, every row in whole
        128-lane tiles (``pool_bytes_tiled``). A program whose
        temporaries reach these holds a copy of the pool."""
        cfg = self.cfg
        pools = self.pools()
        return {
            "pool_shapes": [list(a.shape) for p in (self.k_pages,
                                                    self.v_pages)
                            for a in jax.tree.leaves(p)],
            "pool_bytes": sum(p["pages"] * cfg.page_size
                              * len(p["layers"]) * p["row_bytes"]
                              for p in pools),
            "pool_bytes_tiled": sum(p["bytes"] for p in pools)}

    def read_pages(self, groups, pages) -> tuple:
        """Pages ``(groups[i], pages[i])`` of both pools, each ``(n,
        n_layers, n_kv_heads, page_size, width)``, still on the
        device: ONE slice a pool, so that the caller's fetch moves only
        the named pages."""
        self.full_tables_only("reading pages out by table")
        return (self.k_layout.take_pages(self.k_pages, groups, pages),
                self.v_layout.take_pages(self.v_pages, groups, pages))

    def write_pages(self, groups, pages, k_chunks, v_chunks) -> None:
        """``read_pages`` the other way: one scatter a pool."""
        self.full_tables_only("writing pages in by table")
        self.update_pools(
            self.k_layout.put_pages(self.k_pages, groups, pages,
                                    k_chunks),
            self.v_layout.put_pages(self.v_pages, groups, pages,
                                    v_chunks))

    # -- allocator ---------------------------------------------------------

    @property
    def _free(self) -> list[int]:
        """Group 0's free list — the PR-13 single-pool surface, kept
        for the unsharded (dp_groups == 1) callers and tests."""
        if self.cfg.dp_groups != 1:
            raise AttributeError(
                "no single free list on a dp-sharded pool — use "
                "free_pages_in(group)")
        return self._frees[0]

    def full_tables_only(self, feature: str) -> None:
        """Refuse ``feature``, which moves pages by ONE table a
        sequence and two rows a token, on a cache with window layers
        (their pages are a ring that is overwritten as the sequence
        grows, so a page taken from it is not the prefix it once held)
        or with an index pool (a page's third row would stay
        behind)."""
        if self.cfg.window_layers:
            raise NotImplementedError(
                f"{feature}: {self.cfg.block or 'the block'} has "
                f"window layers ({len(self.cfg.window_layers)} of "
                f"{self.cfg.n_layers}, window {self.cfg.window}) whose "
                f"pages are a ring of {self.cfg.ring_pages} a "
                "sequence, and this moves pages by one full table a "
                "sequence (ROADMAP M3)")
        if self.cfg.index_dim:
            raise NotImplementedError(
                f"{feature}: {self.cfg.block or 'the block'} keeps an "
                f"index key a token ({self.cfg.index_dim} wide) in a "
                "third pool beside its global layers' rows, and this "
                "moves the two rows a token of k_pages and v_pages "
                "(ROADMAP M3)")

    def free_pages_in(self, group: int) -> int:
        return len(self._frees[group])

    @property
    def pages_total(self) -> int:
        """Pages a sequence can be given, all groups, both pools."""
        return (self.cfg.usable_pages_total
                + self.cfg.window_usable_pages_total)

    @property
    def pages_used(self) -> int:
        """Pages allocated across ALL groups, both pools."""
        return self.pages_total - sum(
            len(f) for f in self._frees + self._ring_frees)

    def pages_used_in(self, group: int) -> int:
        return self.cfg.usable_pages - len(self._frees[group])

    def pages_by_kind(self) -> dict:
        """``pages_used_<kind>`` / ``pages_total_<kind>`` of a cache
        with window layers (step-record fields); nothing otherwise."""
        if not self.cfg.window_layers:
            return {}
        out = {}
        for kind, total, frees in (
                (GLOBAL, self.cfg.usable_pages_total, self._frees),
                (WINDOW, self.cfg.window_usable_pages_total,
                 self._ring_frees)):
            out[f"pages_used_{kind}"] = total - sum(map(len, frees))
            out[f"pages_total_{kind}"] = total
        return out

    @property
    def seqs(self) -> int:
        return len(self._tables)

    def seqs_in(self, group: int) -> int:
        return sum(1 for g in self._groups.values() if g == group)

    def _emit(self, op: str, seq_id) -> None:
        event("serving_kv", op=op, seq=str(seq_id),
              group=self._groups.get(seq_id, 0),
              pages_used=self.pages_used,
              pages_total=self.pages_total,
              seqs=self.seqs)

    def can_admit(self, n_tokens: int, group: int = 0) -> bool:
        """Would ``ensure`` succeed for a NEW sequence of n_tokens in
        ``group``? Both pools are counted."""
        need = -(-max(1, n_tokens) // self.cfg.page_size)
        return (need <= len(self._frees[group])
                and min(need, self.cfg.ring_pages)
                <= len(self._ring_frees[group]))

    def join(self, seq_id, group: int = 0) -> None:
        if seq_id in self._tables:
            raise KeyError(f"sequence {seq_id!r} already joined")
        if not 0 <= group < self.cfg.dp_groups:
            raise ValueError(
                f"group {group} out of range (pool has "
                f"{self.cfg.dp_groups} dp group(s))")
        self._tables[seq_id] = []
        self._rings[seq_id] = []
        self._lengths[seq_id] = 0
        self._groups[seq_id] = group
        self._emit("join", seq_id)

    def group_of(self, seq_id) -> int:
        return self._groups[seq_id]

    def ensure(self, seq_id, n_tokens: int) -> bool:
        """Grow seq_id's table to cover ``n_tokens`` total positions,
        from its OWN group's free list. Returns False (allocating
        NOTHING — admission is atomic per call) when that free list
        cannot cover the growth; the engine treats that as
        backpressure and defers the work."""
        if n_tokens > self.cfg.max_seq_len:
            raise ValueError(
                f"sequence {seq_id!r} needs {n_tokens} positions, "
                f"pool max_seq_len is {self.cfg.max_seq_len}")
        table = self._tables[seq_id]
        ring = self._rings[seq_id]
        group = self._groups[seq_id]
        free, ring_free = self._frees[group], self._ring_frees[group]
        pages = -(-n_tokens // self.cfg.page_size)
        need = pages - len(table)
        # The ring takes pages as the sequence grows, and never more
        # than ``ring_pages`` (0 without window layers).
        ring_need = min(pages, self.cfg.ring_pages) - len(ring)
        if need <= 0 and ring_need <= 0:
            return True
        if need > len(free) or ring_need > len(ring_free):
            return False
        refs = self._refs[group]
        for _ in range(need):
            page = free.pop()
            refs[page] = 1
            table.append(page)
        for _ in range(ring_need):
            ring.append(ring_free.pop())
        self._emit("grow", seq_id)
        return True

    def advance(self, seq_id, n_tokens: int) -> None:
        """Record ``n_tokens`` more positions as written (pages must
        already be ensured)."""
        new_len = self._lengths[seq_id] + n_tokens
        table = self._tables[seq_id]
        if new_len > len(table) * self.cfg.page_size:
            raise RuntimeError(
                f"sequence {seq_id!r}: advancing to {new_len} "
                f"positions but only {len(table)} page(s) allocated "
                "— ensure() first")
        self._lengths[seq_id] = new_len

    def trim(self, seq_id, n_tokens: int) -> int:
        """Give back the pages at the end of ``seq_id``'s table that
        ``n_tokens`` positions do not need: pages claimed ahead for
        tokens that did not come (the engine claims a burst's pages
        from a projection). Only pages this sequence alone holds go,
        and none at or below its committed length. Returns the pages
        released."""
        table = self._tables[seq_id]
        group = self._groups[seq_id]
        refs = self._refs[group]
        keep = -(-max(n_tokens, self._lengths[seq_id])
                 // self.cfg.page_size)
        released = []
        while len(table) > keep and refs[table[-1]] == 1:
            page = table.pop()
            del refs[page]
            self._invalidate(group, page)
            released.append(page)
        self._frees[group].extend(released)
        # A ring not yet full holds its logical pages in order like a
        # table; a full one keeps all its pages.
        ring = self._rings[seq_id]
        while len(ring) > keep:
            page = ring.pop()
            self._ring_frees[group].append(page)
            released.append(page)
        if released:
            self._emit("trim", seq_id)
        return len(released)

    def free(self, seq_id) -> int:
        """Evict: drop one reference on each of the sequence's pages;
        pages whose LAST reference this was go back to the group's
        free list (and their prefix-index entries die with them).
        Returns the page count actually released."""
        table = self._tables.pop(seq_id)
        del self._lengths[seq_id]
        group = self._groups[seq_id]
        refs = self._refs[group]
        released = []
        for page in table:
            refs[page] -= 1
            if refs[page] == 0:
                del refs[page]
                self._invalidate(group, page)
                released.append(page)
        self._frees[group].extend(reversed(released))
        ring = self._rings.pop(seq_id)
        self._ring_frees[group].extend(reversed(ring))
        self._registered.pop(seq_id, None)
        self._emit("free", seq_id)
        del self._groups[seq_id]
        return len(released) + len(ring)

    def length(self, seq_id) -> int:
        return self._lengths[seq_id]

    def pages_of(self, seq_id) -> int:
        """Pages in ``seq_id``'s table (shared pages count — they are
        held, refcounted), its ring's included. The /debug/requests
        introspection read; raises KeyError for unknown ids like every
        per-seq accessor."""
        return len(self._tables[seq_id]) + len(self._rings[seq_id])

    def ring_pages_of(self, seq_id) -> int:
        """Pages of ``seq_id``'s ring in the window pool."""
        return len(self._rings[seq_id])

    # -- sharing: refcounted attach / COW / prefix index -------------------

    def attach(self, seq_id, pages, n_tokens: int) -> None:
        """Take read-only references on ``pages`` (an existing
        resident prefix, in table order) for a JOINED sequence with an
        EMPTY table, and mark ``n_tokens`` positions as already
        written. The pages must be live in the sequence's group —
        attaching a freed page is a hard error, not a silent
        corruption."""
        self.full_tables_only("attaching a resident prefix")
        table = self._tables[seq_id]
        if table or self._lengths[seq_id]:
            raise RuntimeError(
                f"sequence {seq_id!r} already has pages — attach is "
                "admission-time only")
        if n_tokens > len(pages) * self.cfg.page_size:
            raise ValueError(
                f"sequence {seq_id!r}: attaching {len(pages)} page(s) "
                f"cannot cover {n_tokens} positions")
        refs = self._refs[self._groups[seq_id]]
        for page in pages:
            refs[page] = refs[page] + 1  # KeyError if not live
        table.extend(pages)
        self._lengths[seq_id] = n_tokens
        # The attached prefix is already indexed (it came FROM the
        # index or a session table) — start registration past it.
        self._registered[seq_id] = len(pages)
        self._emit("attach", seq_id)

    def rename(self, old_id, new_id) -> None:
        """Move a table between owner keys (refcounts untouched) —
        session retention parks a finished sequence's pages under its
        session key; resume renames them back."""
        if new_id in self._tables:
            raise KeyError(f"sequence {new_id!r} already joined")
        self.full_tables_only("retaining a session's pages")
        self._tables[new_id] = self._tables.pop(old_id)
        self._rings[new_id] = self._rings.pop(old_id)
        self._lengths[new_id] = self._lengths.pop(old_id)
        self._groups[new_id] = self._groups.pop(old_id)
        if old_id in self._registered:
            self._registered[new_id] = self._registered.pop(old_id)

    def privatize(self, seq_id):
        """Copy-on-write bookkeeping: swap every SHARED page at or
        past the sequence's write frontier (``length // page_size``)
        for a fresh private page. Returns the ``(src, dst)`` page-id
        pairs for the caller's batched device copy ([] when nothing
        was shared), or None — allocating nothing — when the free list
        cannot cover the swap (backpressure, same contract as
        ``ensure``). Only the frontier page can be both shared and
        written (pages below it are fully committed and never written
        again), so this is at most one pair per call in practice; the
        loop keeps the invariant rather than assuming it."""
        self.full_tables_only("copy-on-write of a shared page")
        table = self._tables[seq_id]
        group = self._groups[seq_id]
        refs = self._refs[group]
        free = self._frees[group]
        start = self._lengths[seq_id] // self.cfg.page_size
        idxs = [i for i in range(start, len(table))
                if refs[table[i]] > 1]
        if len(idxs) > len(free):
            return None
        pairs = []
        for i in idxs:
            src = table[i]
            dst = free.pop()
            refs[src] -= 1
            refs[dst] = 1
            table[i] = dst
            pairs.append((src, dst))
        if pairs:
            # Our claim on any index entries ending at src moved with
            # the fork: keep registration honest by clamping what this
            # sequence counts as registered below the forked page.
            if self._registered.get(seq_id, 0) > idxs[0]:
                self._registered[seq_id] = idxs[0]
            self._emit("cow", seq_id)
        return pairs

    def register_prefix(self, seq_id, tokens) -> None:
        """Index every fully-committed page-aligned prefix of
        ``tokens`` (the sequence's token history) not yet registered.
        Keyed by the EXACT prefix bytes — matching is equality, not a
        lossy hash, so a hit can never alias two different prompts."""
        self.full_tables_only("the prefix index")
        table = self._tables[seq_id]
        group = self._groups[seq_id]
        ps = self.cfg.page_size
        full = self._lengths[seq_id] // ps
        done = self._registered.get(seq_id, 0)
        if full <= done:
            return
        toks = np.array(tokens, np.int32)
        for j in range(done + 1, full + 1):
            key = toks[:j * ps].tobytes()
            self._index[group][key] = tuple(table[:j])
            self._page_keys[group].setdefault(
                table[j - 1], set()).add(key)
        self._registered[seq_id] = full

    def needs_register(self, seq_id) -> bool:
        """Does the sequence have committed pages not yet indexed?"""
        return (self._lengths[seq_id] // self.cfg.page_size
                > self._registered.get(seq_id, 0))

    def match_prefix(self, group: int, tokens):
        """Longest indexed page-aligned prefix of ``tokens`` resident
        in ``group``: returns ``(pages, n_pages)`` or ``((), 0)``."""
        index = self._index[group]
        if not index:
            return (), 0
        toks = np.array(tokens, np.int32)
        ps = self.cfg.page_size
        for j in range(len(toks) // ps, 0, -1):
            pages = index.get(toks[:j * ps].tobytes())
            if pages is not None:
                return pages, j
        return (), 0

    def _invalidate(self, group: int, page: int) -> None:
        """Drop the index entries whose last page just died."""
        for key in self._page_keys[group].pop(page, ()):
            self._index[group].pop(key, None)

    def shared_pages_in(self, group: int) -> int:
        """Pages in ``group`` held by more than one table."""
        return sum(1 for n in self._refs[group].values() if n > 1)

    def token_capacity(self, seq_id) -> int:
        """Max TOTAL positions this sequence could hold right now:
        its allocated pages plus everything left on its group's free
        list, capped by max_seq_len. The resident decode path sizes
        burst budgets against this so an in-program loop can never
        out-write what ``ensure`` could cover."""
        g = self._groups[seq_id]
        pages = len(self._tables[seq_id]) + len(self._frees[g])
        ring = len(self._rings[seq_id]) + len(self._ring_frees[g])
        if ring < self.cfg.ring_pages:
            # Short of a whole ring (it never is while the window pool
            # holds a ring a slot): what there is, as a table.
            pages = min(pages, ring)
        return min(pages * self.cfg.page_size, self.cfg.max_seq_len)

    def occupancy(self) -> dict:
        rec = {"pages_used": self.pages_used,
               "pages_total": self.pages_total,
               "seqs": self.seqs, **self.pages_by_kind()}
        if self.cfg.dp_groups > 1:
            # Per-group occupancy rides the same record (additive —
            # the metrics observer folds these into the labeled
            # dtt_serving_* gauges; schema pinned by test).
            rec["group_pages_used"] = [
                self.pages_used_in(g)
                for g in range(self.cfg.dp_groups)]
            rec["group_seqs"] = [
                self.seqs_in(g) for g in range(self.cfg.dp_groups)]
        return rec

    # -- device-side views -------------------------------------------------

    def page_row(self, seq_id) -> np.ndarray:
        """(row_width,) int32 page row, scratch-padded: the table's
        ``pages_per_seq`` entries, then (window layers) the ring's."""
        row = np.zeros((self.cfg.row_width,), np.int32)
        table = self._tables[seq_id]
        row[:len(table)] = table
        ring = self._rings[seq_id]
        P = self.cfg.pages_per_seq
        row[P:P + len(ring)] = ring
        return row

    def page_rows(self, seq_ids: list) -> np.ndarray:
        """(len(seq_ids), row_width) int32 table; ``None`` entries
        (empty batch slots) become all-scratch rows."""
        rows = np.zeros((len(seq_ids), self.cfg.row_width), np.int32)
        for i, sid in enumerate(seq_ids):
            if sid is not None:
                rows[i] = self.page_row(sid)
        return rows

    def page_rows_grouped(self, seq_ids_by_group: list,
                          width: int | None = None) -> np.ndarray:
        """(dp_groups, width, row_width) int32 tables from a
        per-group nested id list — the batched programs' layout
        (group g's rows index ONLY group g's pool shard). Lists may
        be RAGGED (the batched prefill packs however many lanes each
        group has pending): short groups pad with all-scratch rows up
        to ``width`` (default: the longest group's length — the
        decode path passes equal full-width lists)."""
        b = width if width is not None else max(
            (len(ids) for ids in seq_ids_by_group), default=0)
        rows = np.zeros((self.cfg.dp_groups, b, self.cfg.row_width),
                        np.int32)
        for g, ids in enumerate(seq_ids_by_group):
            for i, sid in enumerate(ids):
                if sid is not None:
                    rows[g, i] = self.page_row(sid)
        return rows

    def update_pools(self, k_pages, v_pages) -> None:
        """Adopt the jitted program's updated (donated-in) pools."""
        self.k_pages = k_pages
        self.v_pages = v_pages
