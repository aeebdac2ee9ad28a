"""Paged KV pool: fixed-size int8 KV pages, per-slot page tables, and a
refcounted free-list allocator.

The contiguous per-slot cache (``models.llama.init_kv_cache``) makes
three things expensive on the serving hot path:

* **Padded windows** — every lane in a decode batch reads the pow2
  ``kv_bucket`` window of the LONGEST lane; a ragged batch pays for
  tokens it does not have.
* **Copy grafts** — sharing a cached prefix (PR 1's radix index) means
  a device gather/scatter of the whole prefix KV into the new slot.
* **Padded accounting** — a parked prefix holds its full ``max_len``
  row whatever its true length.

The pool fixes all three (vLLM's PagedAttention block pool; SGLang's
RadixAttention zero-copy prefix reuse): KV lives in FLAT pool leaves —
values ``(L, KH, P, HD)`` int8, scales ``(L, KH, P)`` bf16 with
``P = total_pages * page_tokens`` — and each scheduler slot maps logical
token positions to pool pages through a ``(max_batch, n_slot_pages)``
int32 page table.  Grafting a prefix is a HOST table copy plus refcount
increments (zero device dispatch — ``PAGE_EVENTS`` counts both sides so
bench/tests can assert it); divergent appends copy-on-write only the
boundary page; parking holds exactly ``ceil(len / page_tokens)`` pages.

Layout invariants the attention/flush paths rely on:

* **Page 0 is the garbage page** — permanently refcounted, never in the
  free list, and the target of every UNOWNED table entry (rows are
  zero-filled).  Masked-lane writes (parked lanes pinned to
  ``max_len - 1``, append-buffer flush garbage, padded prefill tails
  beyond the owned range) land there by construction, so they can never
  corrupt a live or shared page; masked reads of it zero out exactly in
  the attention core (`ops.decode_attention._window_buffer_attention_core`).
* **A shared page is read-only** — any write into a page whose refcount
  exceeds 1 must be preceded by :meth:`make_writable`, which installs a
  private copy (COW) for the writing slot.  The scheduler calls it with
  the exact token range each dispatch will write, so untouched prefix
  pages stay shared forever.
* **Deadlock-freedom** — ``total_pages`` is floored at
  ``max_batch * n_slot_pages + 1``.  With ``S`` = number of extra
  references held by sharing, ``free = (max_batch * n_slot_pages -
  sum(held)) + S >= S >= 0``; a plain allocation is only needed when the
  slot owns fewer than ``n_slot_pages`` pages (so the first term is
  >= 1) and a COW copy implies ``S >= 1`` — either way a free page
  exists, so admission can always proceed once parked segments are
  evictable.  :class:`PoolExhausted` is defensive, not expected.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# Host-side dispatch counters (the qmm BLOCK_EVENTS idiom): nothing on
# the paged graft path launches device work, and tests/bench assert it
# by watching ``device_graft_dispatch`` stay flat while ``host_grafts``
# advances.  ``cow_copies`` counts pages privatized by make_writable
# (each batched copy launch also bumps ``cow_dispatch`` once).
PAGE_EVENTS = {
    "device_graft_dispatch": 0,
    "host_grafts": 0,
    "cow_copies": 0,
    "cow_dispatch": 0,
}


class PoolExhausted(RuntimeError):
    """No free page for a required allocation.

    Unreachable at the floor pool sizing (see the module docstring's
    invariant); raised defensively so a sizing/accounting bug fails
    loudly instead of corrupting a shared page.
    """


def num_slot_pages(max_len: int, page_tokens: int) -> int:
    """Table width: pages needed to cover one slot's max_len tokens."""
    return -(-max_len // page_tokens)


@functools.partial(
    jax.jit, donate_argnums=(0,), static_argnames=("page_tokens",)
)
def _copy_pages(leaves, src, dst, *, page_tokens):
    """Batched page copy inside the donated pool leaves.

    ``src``/``dst`` are (n,) int32 page ids (padded pairs are (0, 0):
    page 0 onto itself, a harmless identity on the garbage page).  One
    fused gather/scatter over the flat token axis per leaf — the ONLY
    device work on the COW path.
    """
    offs = jnp.arange(page_tokens, dtype=jnp.int32)
    s_idx = (src[:, None] * page_tokens + offs[None, :]).reshape(-1)
    d_idx = (dst[:, None] * page_tokens + offs[None, :]).reshape(-1)
    return tuple(
        leaf.at[:, :, d_idx].set(leaf[:, :, s_idx]) for leaf in leaves
    )


class PagedKVPool:
    """Host-side allocator + device leaves for the paged KV cache.

    All bookkeeping (refcounts, free list, tables) is plain numpy on the
    host — the device only ever sees the flat leaves and the uploaded
    table.  Not thread-safe; owned and driven by the scheduler loop.
    """

    def __init__(
        self,
        cfg,
        max_batch: int,
        max_len: int,
        page_tokens: int,
        total_pages: int | None = None,
        mesh=None,
    ):
        if mesh is not None and getattr(mesh, "size", 1) > 1:
            raise ValueError(
                "paged KV cache is single-chip only (the page-table "
                "walk does not shard); use kv_layout='contiguous' on "
                "meshes"
            )
        if getattr(cfg, "kv_dtype", None) != "int8":
            raise ValueError(
                "paged KV cache requires kv_dtype='int8' (per-page "
                "scale leaves mirror the int8 cache layout)"
            )
        if page_tokens < 1:
            raise ValueError(f"page_tokens must be >= 1: {page_tokens}")
        self.page_tokens = int(page_tokens)
        self.max_batch = int(max_batch)
        self.max_len = int(max_len)
        self.n_slot_pages = num_slot_pages(max_len, page_tokens)
        floor = self.max_batch * self.n_slot_pages + 1
        self.total_pages = max(int(total_pages or 0), floor)

        kv_heads = cfg.n_kv_heads if cfg.n_kv_heads else cfg.n_heads
        # The token axis pads to whole 128-lane tiles: the paged kernel
        # copies scales a tile at a time, so the last page's tile must
        # exist.  Padding slots belong to no page and are never mapped.
        p = -(-self.total_pages * self.page_tokens // 128) * 128
        self.leaves = (
            jnp.zeros(
                (cfg.n_layers, kv_heads, p, cfg.head_dim), jnp.int8
            ),
            jnp.zeros(
                (cfg.n_layers, kv_heads, p, cfg.head_dim), jnp.int8
            ),
            jnp.zeros((cfg.n_layers, kv_heads, p), jnp.bfloat16),
            jnp.zeros((cfg.n_layers, kv_heads, p), jnp.bfloat16),
        )
        # refcount[0] stays >= 1 forever: the garbage page is never
        # allocated and never freed.
        self._refcount = np.zeros(self.total_pages, np.int32)
        self._refcount[0] = 1
        self._free = list(range(self.total_pages - 1, 0, -1))
        self.tables = np.zeros(
            (self.max_batch, self.n_slot_pages), np.int32
        )
        # Leading table entries currently owned (allocated or shared).
        self._held = np.zeros(self.max_batch, np.int32)
        self._dirty = True
        self._device_table = None
        # Monotonic counters: pages privatized by COW (the
        # ``engine_kv_cow_breaks_total`` counter) and pages returned to
        # the free list (the 429 Retry-After path projects page frees
        # from this counter's rate).
        self.cow_breaks = 0
        self.frees_total = 0

    # ---- gauges -----------------------------------------------------

    @property
    def pages_free(self) -> int:
        return len(self._free)

    @property
    def pages_shared(self) -> int:
        """Pages with refcount > 1 (held by several owners; COW-armed).
        Feeds the ``engine_kv_pages_shared`` gauge."""
        return int((self._refcount[1:] > 1).sum())

    def slot_pages(self, slot: int) -> int:
        return int(self._held[slot])

    # ---- device views ----------------------------------------------

    def device_table(self) -> jnp.ndarray:
        """The (max_batch, n_slot_pages) int32 table, uploaded only
        when host state changed since the last call."""
        if self._dirty or self._device_table is None:
            self._device_table = jnp.asarray(self.tables)
            self._dirty = False
        return self._device_table

    # ---- allocation -------------------------------------------------

    def _alloc(self) -> int:
        if not self._free:
            raise PoolExhausted(
                f"no free KV page (total={self.total_pages})"
            )
        pg = self._free.pop()
        self._refcount[pg] = 1
        return pg

    def _deref(self, pg: int) -> None:
        if pg == 0:
            return
        self._refcount[pg] -= 1
        if self._refcount[pg] == 0:
            self._free.append(pg)
            self.frees_total += 1

    def reset_slot(self, slot: int) -> None:
        """Release every page the slot holds; its table row goes back
        to all-garbage (page 0)."""
        h = int(self._held[slot])
        for j in range(h):
            self._deref(int(self.tables[slot, j]))
        if h:
            self.tables[slot, :h] = 0
            self._dirty = True
        self._held[slot] = 0

    def trim(self, slot: int, n_tokens: int) -> None:
        """Release pages beyond ``ceil(n_tokens / page_tokens)`` — the
        page-granular phantom-KV clip: rejected speculative drafts and
        parked histories keep exactly the pages their surviving tokens
        occupy, and a release can never touch a page some other slot
        still references (refcounts, not ownership, decide freeing)."""
        keep = num_slot_pages(max(int(n_tokens), 0), self.page_tokens)
        h = int(self._held[slot])
        for j in range(keep, h):
            self._deref(int(self.tables[slot, j]))
            self.tables[slot, j] = 0
        if h > keep:
            self._dirty = True
            self._held[slot] = keep

    def share(self, src: int, dst: int, n_tokens: int) -> None:
        """Zero-copy graft: ``dst`` references ``src``'s first
        ``ceil(n_tokens / page_tokens)`` pages (boundary page included —
        a later divergent append into it COWs via make_writable).

        Pure host work: table copy + refcount increments.  The caller
        must have reset ``dst`` (or be claiming a fresh slot).
        """
        n = num_slot_pages(max(int(n_tokens), 0), self.page_tokens)
        if self._held[dst]:
            raise ValueError(
                f"share target slot {dst} still holds pages; reset first"
            )
        for j in range(n):
            pg = int(self.tables[src, j])
            self.tables[dst, j] = pg
            if pg:
                self._refcount[pg] += 1
        self._held[dst] = n
        self._dirty = True
        PAGE_EVENTS["host_grafts"] += 1

    # ---- segment ownership ------------------------------------------
    #
    # The radix prefix index (engine.prefix_cache) owns parked prefixes
    # as PAGE LISTS, not slot copies: parking detaches the pages from
    # the finishing slot (which is then free for the next admission),
    # a prefix hit shares them back into whatever slot the admission
    # claims, and evicting the segment releases them.  Ownership is
    # purely refcount transfers — no device work on any of these paths.

    def detach(self, slot: int) -> list[int]:
        """Transfer the slot's held pages OUT: returns the page ids (the
        caller — a parked radix segment — now owns their references) and
        clears the table row without dereferencing.  The slot is free
        for reuse immediately; the pages keep their refcounts."""
        h = int(self._held[slot])
        pages = [int(self.tables[slot, j]) for j in range(h)]
        if h:
            self.tables[slot, :h] = 0
            self._dirty = True
        self._held[slot] = 0
        return pages

    def release(self, pages) -> None:
        """Drop one reference per page — the segment-eviction half of
        :meth:`detach`/:meth:`share_pages` (pages shared into live slots
        survive via those slots' references)."""
        for pg in pages:
            self._deref(int(pg))

    def share_pages(self, pages, dst: int, n_tokens: int) -> None:
        """Zero-copy graft from a parked segment's page list: ``dst``
        references the first ``ceil(n_tokens / page_tokens)`` of
        ``pages`` (boundary page included — the slot's first divergent
        append COWs it via make_writable).  Host table write + refcount
        increments only; the caller must hand in a reset slot."""
        n = num_slot_pages(max(int(n_tokens), 0), self.page_tokens)
        if n > len(pages):
            raise ValueError(
                f"segment holds {len(pages)} pages; {n} needed for "
                f"{n_tokens} tokens"
            )
        if self._held[dst]:
            raise ValueError(
                f"share target slot {dst} still holds pages; reset first"
            )
        for j in range(n):
            pg = int(pages[j])
            self.tables[dst, j] = pg
            if pg:
                self._refcount[pg] += 1
        self._held[dst] = n
        self._dirty = True
        PAGE_EVENTS["host_grafts"] += 1

    def make_writable(self, slot: int, start_tok: int, end_tok: int) -> None:
        """Guarantee the pages covering tokens [start_tok, end_tok) are
        PRIVATE to ``slot``: allocate missing pages, copy-on-write
        shared ones.  Pages wholly before ``start_tok`` are untouched —
        a grafted prefix stays shared no matter how long the slot
        decodes past it.
        """
        if end_tok <= start_tok:
            return
        pt = self.page_tokens
        first = max(int(start_tok), 0) // pt
        last = num_slot_pages(min(int(end_tok), self.max_len), pt)
        cow_src, cow_dst = [], []
        changed = False
        for j in range(first, last):
            if j >= self._held[slot]:
                self.tables[slot, j] = self._alloc()
                changed = True
            else:
                pg = int(self.tables[slot, j])
                if pg == 0:
                    self.tables[slot, j] = self._alloc()
                    changed = True
                elif self._refcount[pg] > 1:
                    fresh = self._alloc()
                    cow_src.append(pg)
                    cow_dst.append(fresh)
                    self._refcount[pg] -= 1
                    self.tables[slot, j] = fresh
                    changed = True
        self._held[slot] = max(int(self._held[slot]), last)
        if changed:
            self._dirty = True
        if cow_src:
            # Pad the pair list to a pow2 bucket so the jitted copy
            # compiles O(log n) variants; (0, 0) pads are identity
            # writes on the garbage page.
            n = len(cow_src)
            width = 1
            while width < n:
                width *= 2
            cow_src += [0] * (width - n)
            cow_dst += [0] * (width - n)
            self.leaves = _copy_pages(
                self.leaves,
                jnp.asarray(cow_src, jnp.int32),
                jnp.asarray(cow_dst, jnp.int32),
                page_tokens=pt,
            )
            PAGE_EVENTS["cow_copies"] += n
            PAGE_EVENTS["cow_dispatch"] += 1
            self.cow_breaks += n

    def reset_all(self) -> None:
        """Catastrophic-recovery reset: EVERY reference is dropped —
        slot tables, and any references parked radix segments still hold
        (the caller clears its index in the same recovery) — and the
        leaves are replaced with fresh zeros (the device buffers may
        have been donated away by a faulted dispatch)."""
        self._refcount[:] = 0
        self._refcount[0] = 1
        self._free = list(range(self.total_pages - 1, 0, -1))
        self.tables[:] = 0
        self._held[:] = 0
        self.leaves = tuple(jnp.zeros_like(leaf) for leaf in self.leaves)
        self._dirty = True
