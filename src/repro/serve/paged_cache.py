"""Paged KV storage for the continuous-batching engine.

Two cache backends behind one interface:

* :class:`PagedCache` — attention-KV families (``dense``/``moe``): a global
  pool of fixed-size pages ``[L, n_pages, Hkv, page_size, hd]`` with a host-
  side free-list allocator and per-slot page tables.  In ``kv_dtype="mxfp4"``
  mode pages hold the *real* 4.25-bit payload (packed E2M1 nibble codes +
  E8M0 scale-exponent bytes, via ``core.quantizers.kv_quantize``); the
  ``"dense"`` mode stores the model compute dtype for parity testing.
  Quantize happens once per token on write; gather dequantizes pages into the
  stacked dense cache layout the model's decode step already consumes.

* :class:`DenseSlotCache` — families whose decode state is not positional KV
  (SSM conv+ssm states, hybrid, enc-dec / VLM cross caches): one dense cache
  slot per sequence, preallocated at ``max_len``, with per-slot slice /
  write-back / reset helpers.  These schedule identically; they just don't
  page.

Page id 0 is reserved as a scratch page: masked (inactive) decode lanes
redirect their writes there, so one jitted decode step can cover every slot
without corrupting sequences that are still prefilling.  Stale page contents
are never zeroed — causal attention masks every position greater than the
querying token's, and a sequence writes position ``p`` before any of its
queries reach ``p``, so garbage is unreachable by construction.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import formats as F
from repro.core import quantizers as Q
from repro.kernels.paged_attention import (  # noqa: F401  (re-exports)
    PagedKV,
    prefill_chunk_layout,
    quant_fmt as _quant_fmt,
    scatter_token,
)
from repro.models.registry import Model

# ---------------------------------------------------------------------------
# pure (jit-traceable) pool ops
# ---------------------------------------------------------------------------


def quantize_kv(x: jnp.ndarray) -> Q.PackedQuant:
    """[..., hd] values → packed MXFP4 payload (codes [..., hd/2] u8,
    scale codes [..., hd/block] u8)."""
    return Q.kv_quantize(x, _quant_fmt(x.shape[-1]))


def dequantize_kv(codes: jnp.ndarray, scales: jnp.ndarray, dtype) -> jnp.ndarray:
    """Packed payload → [..., hd] values in the model compute dtype."""
    hd = codes.shape[-1] * 2
    return Q.kv_dequantize(Q.PackedQuant(codes, scales), _quant_fmt(hd), dtype)


def gather_pages(pool: dict, tables: jnp.ndarray, dtype) -> tuple[jnp.ndarray, jnp.ndarray]:
    """pool pages → dense stacked KV caches.

    tables [B, n_pages_per_slot] int32 → (k, v) [L, B, T, Hkv, hd] with
    T = n_pages_per_slot · page_size, dequantizing if the pool is packed.

    Used only by the ``decode_backend="gather"`` parity oracle (per-slot
    chunked prefill and gather decode/verify); the default paged backend —
    batched decode, verify AND batched prefill — attends directly over the
    packed pool (``kernels/paged_attention``) and never materializes this
    dense view.
    """

    def one(codes, scales=None):
        g = codes[:, tables]  # [L, B, np, H, ps, payload]
        vals = g if scales is None else dequantize_kv(g, scales[:, tables], dtype)
        vals = jnp.swapaxes(vals, 3, 4)  # [L, B, np, ps, H, hd]
        return vals.reshape(*vals.shape[:2], -1, *vals.shape[4:])

    if "k" in pool:  # dense mode
        return one(pool["k"]), one(pool["v"])
    return (one(pool["k_codes"], pool["k_scales"]),
            one(pool["v_codes"], pool["v_scales"]))


def scatter_tokens(pool: dict, page_ids: jnp.ndarray, offsets: jnp.ndarray,
                   k_new: jnp.ndarray, v_new: jnp.ndarray) -> dict:
    """Write one token per (page, offset) pair into the pool.

    page_ids/offsets [N]; k_new/v_new [L, N, Hkv, hd].  Quantize-on-write in
    packed mode.  Duplicate (page, offset) pairs (masked lanes redirected to
    the scratch page) resolve arbitrarily — scratch contents are never read.
    The index ``[:, page_ids, :, offsets]`` yields ``[N, L, Hkv, payload]``
    (split index arrays broadcast first), hence the swap of the values.
    """

    def put(leaf, vals):
        return leaf.at[:, page_ids, :, offsets].set(
            jnp.swapaxes(vals, 0, 1).astype(leaf.dtype))

    if "k" in pool:
        return {"k": put(pool["k"], k_new), "v": put(pool["v"], v_new)}
    kq, vq = quantize_kv(k_new), quantize_kv(v_new)
    return {
        "k_codes": put(pool["k_codes"], kq.codes),
        "k_scales": put(pool["k_scales"], kq.scales),
        "v_codes": put(pool["v_codes"], vq.codes),
        "v_scales": put(pool["v_scales"], vq.scales),
    }


@jax.jit
def copy_page(pool: dict, src: jnp.ndarray, dst: jnp.ndarray) -> dict:
    """Copy one physical page's full payload ``src`` → ``dst`` across every
    layer and stream (packed E2M1 codes + E8M0 scales, or dense k/v) — the
    copy-on-write primitive.  ``src``/``dst`` are runtime int32 operands, so
    one compile covers every COW the pool ever performs."""
    return {name: arr.at[:, dst].set(arr[:, src]) for name, arr in pool.items()}


def reservation_sizing(n_slots: int, max_len: int, page_size: int,
                       spec_k: int = 0) -> tuple[int, int]:
    """``(pages_per_slot, n_pages)`` under the admission-reservation contract
    — the ONE sizing rule shared by the engine's target cache and the draft
    proposer's mirror cache (they must not drift: the no-OOM contract rests
    on it).

    Page-table WIDTH carries ``+spec_k`` sentinel-capacity columns so a
    speculative burst's beyond-budget positions index the table in bounds
    (their entries are never mapped, redirecting writes to scratch page 0);
    the POOL holds exactly one full reservation of
    ``ceil(max_len / page_size)`` pages per slot plus the scratch page —
    mapped pages never exceed a request's admission reservation, so no +k
    pool headroom exists or is needed."""
    pages_per_slot = -(-(max_len + spec_k) // page_size)
    n_pages = 1 + n_slots * (-(-max_len // page_size))
    return pages_per_slot, n_pages


# ---------------------------------------------------------------------------
# PagedCache (attention-KV families)
# ---------------------------------------------------------------------------


class PagedCache:
    """Fixed-size KV pages + free-list allocator + per-slot page tables.

    Device state (``self.pool``) is a dict of jnp arrays and is only mutated
    through the pure functions above (the engine threads it through its jitted
    steps).  Allocator state (free list, page tables) is host-side numpy —
    tables are passed into jitted functions as ordinary int32 operands.
    """

    def __init__(self, model: Model | None, *, n_slots: int, pages_per_slot: int,
                 page_size: int, n_pages: int | None = None,
                 kv_dtype: str = "mxfp4", debug: bool = False,
                 geometry: tuple[int, int, int] | None = None,
                 dtype=None):
        """``geometry=(layers, kv_heads, head_dim)`` (with ``dtype``) sizes the
        pool explicitly instead of via ``model.cache_spec`` — how
        :class:`~repro.serve.state_pool.StatePool` carves attention-KV and
        cross-KV planes out of families whose cache tree is NOT a plain
        stacked (k, v) pair (enc-dec, VLM, hybrid).  The family gate applies
        only to the model-derived path: an explicit geometry is, by
        construction, a positional-KV plane."""
        if geometry is None:
            cfg = model.cfg
            if cfg.family not in ("dense", "moe"):
                raise ValueError(
                    f"PagedCache supports attention-KV families, got {cfg.family!r} "
                    f"(non-attention families carve planes via explicit geometry=)")
            spec_k, _ = model.cache_spec(1, page_size)  # [L, 1, T, Hkv, hd]
            L, _, _, H, hd = spec_k.shape
            dtype = cfg.dtype
        else:
            L, H, hd = geometry
            if dtype is None:
                raise ValueError("explicit geometry= needs an explicit dtype=")
        if kv_dtype not in ("mxfp4", "dense"):
            raise ValueError(f"kv_dtype must be 'mxfp4' or 'dense', got {kv_dtype!r}")
        if hd % 2 != 0:
            raise ValueError(f"head dim {hd} must be even for nibble packing")
        # page 0 is the reserved scratch page
        n_pages = n_pages if n_pages is not None else 1 + n_slots * pages_per_slot
        self.n_slots, self.page_size = n_slots, page_size
        self.pages_per_slot, self.n_pages = pages_per_slot, n_pages
        self.kv_dtype = kv_dtype
        self.layers, self.kv_heads, self.head_dim = L, H, hd
        self._dtype = jnp.dtype(dtype)
        nb = hd // _quant_fmt(hd).block
        if kv_dtype == "dense":
            shape = (L, n_pages, H, page_size, hd)
            self.pool = {"k": jnp.zeros(shape, self._dtype),
                         "v": jnp.zeros(shape, self._dtype)}
        else:
            cshape = (L, n_pages, H, page_size, hd // 2)
            sshape = (L, n_pages, H, page_size, nb)
            self.pool = {"k_codes": jnp.zeros(cshape, jnp.uint8),
                         "k_scales": jnp.zeros(sshape, jnp.uint8),
                         "v_codes": jnp.zeros(cshape, jnp.uint8),
                         "v_scales": jnp.zeros(sshape, jnp.uint8)}
        self._free = list(range(n_pages - 1, 0, -1))  # pop() hands out low ids first
        self.tables = np.zeros((n_slots, pages_per_slot), np.int32)
        # physical-page reference counts: a page may be mapped by MANY slot
        # tables (prefix sharing) and pinned by external holders (the radix
        # prefix index) — it returns to the free list only at refcount zero.
        self.refcounts = np.zeros((n_pages,), np.int32)
        self._external = np.zeros((n_pages,), np.int32)  # non-table pins
        self.debug = debug  # run check_invariants after every mutate

    # -- allocator ----------------------------------------------------------

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def pages_needed(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)

    def can_alloc(self, n_tokens: int) -> bool:
        n = self.pages_needed(n_tokens)
        return n <= min(len(self._free), self.pages_per_slot)

    def _take_fresh(self) -> int:
        """Pop a page off the free list with refcount 1 (sole owner)."""
        pid = self._free.pop()
        if self.refcounts[pid] != 0:
            raise RuntimeError(f"free-list page {pid} has refcount "
                               f"{self.refcounts[pid]} != 0")
        self.refcounts[pid] = 1
        return pid

    def _decref(self, pid: int) -> bool:
        """Drop one reference; True if the page returned to the free list.
        Callers re-sort the free list after a batch of decrefs."""
        rc = int(self.refcounts[pid]) - 1
        if rc < 0:
            raise RuntimeError(f"refcount underflow on page {pid}")
        self.refcounts[pid] = rc
        if rc == 0:
            self._free.append(pid)
            return True
        return False

    def alloc(self, slot: int, n_tokens: int, shared=()) -> None:
        """Map enough pages onto ``slot`` to hold ``n_tokens`` positions.

        ``shared`` is an optional sequence of LIVE page ids (a radix-index
        prefix match) aliased at the front of the table row instead of fresh
        pages — each gains a reference; only the remainder pops the free
        list.  A slot that still carries live mappings is freed first —
        zeroing the table row without dropping its references would silently
        leak pages if the engine's alloc/free ordering ever regresses,
        shrinking the pool until admission wedges.  Page conservation
        (live + free == n_pages - 1) therefore survives re-alloc."""
        n = self.pages_needed(n_tokens)
        if n > self.pages_per_slot:
            raise ValueError(f"{n_tokens} tokens need {n} pages > pages_per_slot={self.pages_per_slot}")
        shared = [int(p) for p in shared]
        if len(shared) > n:
            raise ValueError(f"{len(shared)} shared pages > {n} pages needed")
        if self.tables[slot].any():
            self.free(slot)
        if n - len(shared) > len(self._free):
            raise RuntimeError(
                f"out of pages: need {n - len(shared)}, free {len(self._free)}")
        for i, pid in enumerate(shared):
            if pid == 0 or self.refcounts[pid] <= 0:
                raise ValueError(f"cannot alias dead/scratch page {pid}")
            self.tables[slot, i] = pid
            self.refcounts[pid] += 1
        for i in range(len(shared), n):
            self.tables[slot, i] = self._take_fresh()
        self._check()

    def free(self, slot: int) -> None:
        for pid in self.tables[slot]:
            if pid != 0:
                self._decref(int(pid))
        # keep the free list sorted (descending) so the low-ids-first contract
        # of pop() survives out-of-order retirement — allocation stays
        # deterministic under any admission/finish interleaving
        self._free.sort(reverse=True)
        self.tables[slot] = 0
        self._check()

    def mapped_pages(self, slot: int) -> int:
        """Pages currently mapped onto ``slot`` (alloc/ensure fill from index
        0 and truncate frees from the tail, so nonzero entries are a prefix)."""
        return int(np.count_nonzero(self.tables[slot]))

    def mapped_total(self) -> int:
        """Pages mapped across ALL slots.  Page conservation means
        ``mapped_total() + free_pages == n_pages - 1`` (scratch excluded)."""
        return int(np.count_nonzero(self.tables))

    def occupancy(self) -> float:
        """Live fraction of the allocatable pool (scratch page excluded) —
        the telemetry ``pool_occupancy`` gauge.  Counts physical pages, so
        prefix-shared pages contribute once however many slots alias them."""
        allocatable = self.n_pages - 1
        return self.live_pages() / allocatable if allocatable else 0.0

    def live_pages(self) -> int:
        """Physical pages with at least one reference (slot table or external
        pin).  Conservation: ``live_pages() + free_pages == n_pages - 1``
        always — unlike ``mapped_total()``, which double-counts a page
        aliased by several slots."""
        return int((self.refcounts > 0).sum())

    def page_mask(self) -> np.ndarray:
        """[n_pages] bool — True where the page is live (referenced by a slot
        table or an external pin such as the prefix index).  The runtime
        operand of the telemetry pool-health reduction (scratch page 0 is
        never referenced, so it is never counted)."""
        return self.refcounts > 0

    def ensure(self, slot: int, n_tokens: int) -> int:
        """Extend ``slot``'s mapping to cover ``n_tokens`` positions (no-op if
        already covered); returns pages added.  Allocator primitive: the
        engine itself never maps beyond a request's admission reservation
        mid-flight (that is the "reserved up front so decode never OOMs"
        contract — speculative writes past the budget redirect to the
        scratch page instead of mapping headroom on demand)."""
        need = self.pages_needed(n_tokens)
        if need > self.pages_per_slot:
            raise ValueError(
                f"{n_tokens} tokens need {need} pages > pages_per_slot={self.pages_per_slot}")
        have = self.mapped_pages(slot)
        if need <= have:
            return 0
        if need - have > len(self._free):
            raise RuntimeError(
                f"out of pages: need {need - have} more, free {len(self._free)}")
        for i in range(have, need):
            self.tables[slot, i] = self._take_fresh()
        self._check()
        return need - have

    def truncate(self, slot: int, n_tokens: int) -> int:
        """Shrink ``slot``'s mapping to cover only ``n_tokens`` positions,
        unmapping the *trailing* pages (pages wholly past
        ``ceil(n_tokens / page_size)``).  Page contents are left as-is —
        causal masking makes positions ≥ the logical length unreachable, and
        a future ``ensure`` re-maps (possibly different) pages that are
        rewritten before they are read, exactly like any recycled page.
        Allocator primitive: speculative rollback in the engine is logical
        (lengths shrink, pages stay mapped within the reservation), so this
        is for cache-external policies that really do want to give pages
        back early.  Keeps the free list sorted descending (same contract
        as :meth:`free`); returns the number of pages released."""
        keep = self.pages_needed(n_tokens)
        released = 0
        for i in range(keep, self.pages_per_slot):
            pid = int(self.tables[slot, i])
            if pid != 0:
                self._decref(pid)
                self.tables[slot, i] = 0
                released += 1
        if released:
            self._free.sort(reverse=True)
        self._check()
        return released

    # -- prefix sharing: external pins + copy-on-write ----------------------

    def ref_page(self, pid: int) -> None:
        """Take an external (non-table) reference on a live page — how the
        radix prefix index pins a cached page so it survives the writing
        slot's retirement."""
        if pid == 0 or self.refcounts[pid] <= 0:
            raise ValueError(f"cannot pin dead/scratch page {pid}")
        self.refcounts[pid] += 1
        self._external[pid] += 1
        self._check()

    def unref_page(self, pid: int) -> bool:
        """Drop an external reference; True if the page returned to the free
        list (no slot maps it either) — the eviction path."""
        if self._external[pid] <= 0:
            raise ValueError(f"page {pid} has no external reference to drop")
        self._external[pid] -= 1
        if self._decref(pid):
            self._free.sort(reverse=True)
            self._check()
            return True
        self._check()
        return False

    def cow_range(self, slot: int, start_tok: int, n_tokens: int) -> int:
        """Copy-on-write guard: before ``slot`` writes positions
        ``[start_tok, start_tok + n_tokens)``, any page in that range that is
        SHARED (refcount > 1 — aliased by another slot or pinned by the
        prefix index) is copied payload-and-all into a fresh page mapped only
        by this slot; the other holders keep the original bits.  Pages the
        slot owns outright pass through untouched, so this is free on the
        non-sharing path.  Returns the number of pages copied."""
        if n_tokens <= 0:
            return 0
        first = start_tok // self.page_size
        last = (start_tok + n_tokens - 1) // self.page_size
        copied = 0
        for idx in range(first, min(last + 1, self.pages_per_slot)):
            pid = int(self.tables[slot, idx])
            if pid == 0 or self.refcounts[pid] <= 1:
                continue  # unmapped (scratch-redirected) or exclusively owned
            if not self._free:
                raise RuntimeError(f"out of pages for copy-on-write of page {pid}")
            new = self._take_fresh()
            self.pool = copy_page(self.pool, jnp.int32(pid), jnp.int32(new))
            self.tables[slot, idx] = new
            self._decref(pid)  # refcount was > 1: never frees here
            copied += 1
        self._check()
        return copied

    # -- invariants ---------------------------------------------------------

    def _check(self) -> None:
        if self.debug:
            self.check_invariants()

    def check_invariants(self) -> None:
        """Allocator-state invariants, asserted after every mutate when the
        ``debug`` flag is on (and directly by tests):

        * free-list hygiene — in-range ids, no duplicates, sorted descending
          (the low-ids-first pop contract), every free page at refcount 0;
        * refcount consistency — each page's refcount equals its table-cell
          mappings plus its external pins, the scratch page is never
          referenced, no negative counts;
        * page conservation — live pages + free pages == n_pages - 1, which
          also implies no slot maps a freed page.
        """
        free = self._free
        if len(set(free)) != len(free):
            raise AssertionError("free list contains duplicate pages")
        if any(p <= 0 or p >= self.n_pages for p in free):
            raise AssertionError("free list contains out-of-range/scratch ids")
        if free != sorted(free, reverse=True):
            raise AssertionError("free list not sorted descending")
        rc = self.refcounts
        if int(rc[0]) != 0 or int(self._external[0]) != 0:
            raise AssertionError("scratch page 0 acquired a reference")
        if (rc < 0).any() or (self._external < 0).any():
            raise AssertionError("negative refcount")
        counts = np.bincount(self.tables.reshape(-1), minlength=self.n_pages)
        counts[0] = 0  # table zeros mean unmapped, not scratch references
        expect = counts[:self.n_pages] + self._external
        if not (rc == expect).all():
            bad = np.nonzero(rc != expect)[0][:8].tolist()
            raise AssertionError(
                f"refcount mismatch on pages {bad}: rc={rc[bad].tolist()} "
                f"!= tables+external={expect[bad].tolist()}")
        for p in free:
            if int(rc[p]) != 0:
                raise AssertionError(f"page {p} is free but has refcount {rc[p]}")
        live = int((rc > 0).sum())
        if live + len(free) != self.n_pages - 1:
            raise AssertionError(
                f"page conservation violated: live {live} + free {len(free)} "
                f"!= {self.n_pages - 1}")

    # -- accounting ---------------------------------------------------------

    def cache_bytes(self) -> int:
        """Persistent KV bytes held by the pool (the number the FP4 mode
        shrinks; transient gather buffers are working memory, not state)."""
        return sum(int(a.nbytes) for a in self.pool.values())

    def bits_per_element(self) -> float:
        elems = self.layers * self.n_pages * self.page_size * self.kv_heads * self.head_dim * 2
        return self.cache_bytes() * 8 / elems


# ---------------------------------------------------------------------------
# DenseSlotCache (SSM / hybrid / cross-KV fallback)
# ---------------------------------------------------------------------------


def slice_slot(caches: Any, slot: jnp.ndarray) -> Any:
    """Select one slot's cache (batch axis 1 on every leaf) → batch-1 view."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=1), caches)


def write_slot(caches: Any, update: Any, slot: jnp.ndarray) -> Any:
    """Write a batch-1 cache back into ``slot``."""
    return jax.tree.map(
        lambda a, u: jax.lax.dynamic_update_slice_in_dim(a, u.astype(a.dtype), slot, axis=1),
        caches, update)


def merge_masked(old: Any, new: Any, mask: jnp.ndarray) -> Any:
    """Per-slot select: keep ``new`` where mask (batch axis 1), else ``old`` —
    the one batched decode step leaves non-decoding slots untouched."""

    def sel(o, n):
        shape = [1] * o.ndim
        shape[1] = mask.shape[0]
        return jnp.where(mask.reshape(shape), n.astype(o.dtype), o)

    return jax.tree.map(sel, old, new)


class DenseSlotCache:
    """Per-slot dense decode state for families without paged attention KV."""

    def __init__(self, model: Model, *, n_slots: int, max_len: int):
        self.n_slots, self.max_len = n_slots, max_len
        spec = model.cache_spec(n_slots, max_len)
        self.caches = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)
        self._reset = jax.jit(self._reset_impl)

    @staticmethod
    def _reset_impl(caches, slot):
        zero = jax.tree.map(
            lambda a: jnp.zeros((a.shape[0], 1, *a.shape[2:]), a.dtype), caches)
        return write_slot(caches, zero, slot)

    def reset_slot(self, slot: int) -> None:
        """Zero one slot's state before a new request prefills into it (SSM
        recurrences have no positional masking to hide a predecessor's state)."""
        self.caches = self._reset(self.caches, jnp.int32(slot))

    def cache_bytes(self) -> int:
        return sum(int(a.nbytes) for a in jax.tree.leaves(self.caches))
