"""Paged KV-cache whose pages are packed QTensor blocks.

The serving memory bill is the KV-cache: at f32 carriers a cached token
costs ``2 * KV * dh * 4`` bytes per layer, and a static per-sequence
``max_t`` allocation strands most of it when sequences have wildly
different lengths.  This module fixes both:

* **Packed pages** — K/V values are stored as int8 ``(1, e, m)`` codes
  (the ``repro.quant.qtensor`` layout, same codes the training pipeline
  carries) plus ONE power-of-two scale exponent per (layer, page).  The
  scale is an exponent offset: multiplying by 2^se only shifts the
  exponent, so dequantization is exact on representable values and the
  narrow format's exponent range is re-centered on the page's actual
  magnitude without spending per-element bits.  4x fewer KV bytes than the
  f32 carrier (2x vs bf16), and the decode kernel unpacks pages in VMEM —
  no dequantized copy of the cache ever exists in HBM.
* **Paging** — the arena is a fixed pool of ``page_size``-token pages
  shared by all sequences; ``PagePool`` (host-side) hands out pages as
  sequences grow and reclaims them on completion, so the HBM bill tracks
  the tokens actually cached, not ``batch * max_t``.

Layout (one arena per model; the layer axis leads so the per-layer scan
in ``models.lm.paged_decode`` can carry arena slices as scan xs)::

    k / v   : (L, P, KV, page_size, dh)  int8 codes
    k_se/v_se: (L, P)                     int32 scale exponents

Page 0 is the reserved **null page**: the pool never allocates it, padded
page-table entries point at it, and padded batch rows write their (masked,
never read) token there — so scatter writes need no predication.

Scale discipline: a page's scale exponent is fixed by the FIRST write that
touches it (``floor(log2(amax))`` of the written block) and later tokens in
the page quantize under it (the quantizer saturates/flushes as usual).
K/V magnitudes are post-norm and stable across a few dozen tokens, and the
format's own exponent field absorbs the drift.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.quant.formats import FPFormat
from repro.kernels.common import quantize_block
from repro.obs.trace import span
from repro.quant.qtensor import pack_block, unpack_block

__all__ = [
    "PagedKVConfig",
    "PagePool",
    "ShardedPagePool",
    "SwapStore",
    "init_arena",
    "append_token",
    "write_prompt",
    "gather_pages",
    "dequantize_pages",
    "swap_out_pages",
    "swap_in_pages",
    "truncate_pages",
    "kv_bytes_per_token",
]

# scale exponents clipped well inside f32's range so exp2() stays finite
_SE_LIM = 120


@dataclass(frozen=True)
class PagedKVConfig:
    """Shapes + format of one paged arena."""

    n_layers: int
    n_kv_heads: int
    head_dim: int
    n_pages: int
    page_size: int
    kv_fmt: FPFormat = FPFormat(e=5, m=2)

    def __post_init__(self):
        if self.kv_fmt.bits > 8:
            raise ValueError(f"kv_fmt {self.kv_fmt} does not fit int8 codes")
        if self.n_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is reserved)")

    @property
    def tokens_capacity(self) -> int:
        return (self.n_pages - 1) * self.page_size  # page 0 reserved

    @classmethod
    def for_model(cls, cfg, *, n_pages: int, page_size: int,
                  kv_fmt: FPFormat | None = None,
                  n_layers: int | None = None) -> "PagedKVConfig":
        return cls(
            n_layers=n_layers if n_layers is not None else cfg.n_layers,
            n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
            n_pages=n_pages, page_size=page_size,
            kv_fmt=kv_fmt or FPFormat(e=5, m=2))


def init_arena(pc: PagedKVConfig) -> dict[str, jnp.ndarray]:
    """Zero-initialized arena pytree (int8 code 0 decodes to +0.0)."""
    shape = (pc.n_layers, pc.n_pages, pc.n_kv_heads, pc.page_size,
             pc.head_dim)
    z = jnp.zeros(shape, jnp.int8)
    se = jnp.zeros((pc.n_layers, pc.n_pages), jnp.int32)
    return {"k": z, "v": z, "k_se": se, "v_se": se}


def _scale_exp(amax: jnp.ndarray) -> jnp.ndarray:
    """Per-page power-of-two scale exponent from a block's max magnitude."""
    safe = jnp.where(amax > 0.0, amax, 1.0)
    se = jnp.floor(jnp.log2(safe))
    return jnp.clip(se, -_SE_LIM, _SE_LIM).astype(jnp.int32)


def _encode(x: jnp.ndarray, se: jnp.ndarray, fmt: FPFormat) -> jnp.ndarray:
    """Quantize ``x`` under the 2^se scale and pack to int8 codes; ``se``
    broadcasts over the trailing axes of ``x``."""
    scaled = x * jnp.exp2(-se.astype(jnp.float32))
    return pack_block(quantize_block(scaled, fmt.e, fmt.m), fmt.e, fmt.m)


def _decode(codes: jnp.ndarray, se: jnp.ndarray, fmt: FPFormat) -> jnp.ndarray:
    return unpack_block(codes, fmt.e, fmt.m) * jnp.exp2(se.astype(jnp.float32))


def append_token(arena_l: jnp.ndarray, se_l: jnp.ndarray, x: jnp.ndarray,
                 page_id: jnp.ndarray, slot: jnp.ndarray,
                 fmt: FPFormat,
                 pmax_axis: str | None = None) -> tuple[jnp.ndarray,
                                                        jnp.ndarray]:
    """Write one decode token per sequence into a layer's arena slice.

    ``arena_l`` (P, KV, page_size, dh) int8, ``se_l`` (P,) int32,
    ``x`` (B, KV, dh) f32 values, ``page_id``/``slot`` (B,) int32.  A write
    at ``slot == 0`` is the page's first and fixes its scale exponent.
    Padded batch rows must carry ``page_id == 0`` (the null page).

    ``pmax_axis``: inside a tensor-parallel ``shard_map`` where each shard
    holds a KV-head slice, the per-page amax is pmax'd over the mesh axis
    BEFORE fixing the scale exponent — every shard then derives the same
    (global, all-heads) exponent the single-device write would, so the
    shard-local codes are a bitwise slice of the unsharded arena.
    """
    amax = jnp.max(jnp.abs(x), axis=(1, 2))  # (B,)
    if pmax_axis is not None:
        amax = jax.lax.pmax(amax, pmax_axis)
    se = jnp.where(slot == 0, _scale_exp(amax), se_l[page_id])
    se_l = se_l.at[page_id].set(se)
    codes = _encode(x, se[:, None, None], fmt)  # (B, KV, dh)
    arena_l = arena_l.at[page_id, :, slot].set(codes)
    return arena_l, se_l


def write_prompt(arena_l: jnp.ndarray, se_l: jnp.ndarray, x: jnp.ndarray,
                 page_ids: jnp.ndarray, fmt: FPFormat,
                 pmax_axis: str | None = None,
                 ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Write one sequence's prompt K (or V) into a layer's arena slice.

    ``x`` (S, KV, dh) f32; ``page_ids`` (n_pages,) int32 with
    ``n_pages * page_size >= S`` (the tail page is zero-padded; code 0
    decodes to 0.0 and padded tokens are masked out of attention anyway).
    Returns ``(arena_l, se_l, dequant)`` where ``dequant`` (S, KV, dh) is
    the exact values the cache now holds — prefill attends to THESE, so
    later paged decode sees the same history prefill saw.

    ``pmax_axis``: see ``append_token`` — the per-page amax is shared over
    the mesh axis so KV-head-sharded writes fix the same scale exponents
    as the single-device write.
    """
    s, kv, dh = x.shape
    npg = page_ids.shape[0]
    page_size = arena_l.shape[2]
    xp = jnp.pad(x.astype(jnp.float32),
                 ((0, npg * page_size - s), (0, 0), (0, 0)))
    blocks = xp.reshape(npg, page_size, kv, dh).transpose(0, 2, 1, 3)
    amax = jnp.max(jnp.abs(blocks), axis=(1, 2, 3))  # (npg,)
    if pmax_axis is not None:
        amax = jax.lax.pmax(amax, pmax_axis)
    se = _scale_exp(amax)
    codes = _encode(blocks, se[:, None, None, None], fmt)
    arena_l = arena_l.at[page_ids].set(codes)
    se_l = se_l.at[page_ids].set(se)
    deq = _decode(codes, se[:, None, None, None], fmt)
    deq = deq.transpose(0, 2, 1, 3).reshape(npg * page_size, kv, dh)[:s]
    return arena_l, se_l, deq


def gather_pages(arena_l: jnp.ndarray, se_l: jnp.ndarray,
                 page_ids: jnp.ndarray, fmt: FPFormat) -> jnp.ndarray:
    """Dequantized token-major view of one sequence's pages in a layer:
    (len(page_ids) * page_size, KV, dh) f32 — exactly the values
    ``write_prompt`` returned when the pages were written (same codes, same
    per-page scale exponents).  Chunked prefill attends its history through
    this view, so a resumed slab sees bit-identically what a one-shot
    prefill over the whole prompt would have seen."""
    codes = arena_l[page_ids]  # (n, KV, page_size, dh)
    deq = _decode(codes, se_l[page_ids][:, None, None, None], fmt)
    n, kv, page_size, dh = deq.shape
    return deq.transpose(0, 2, 1, 3).reshape(n * page_size, kv, dh)


def dequantize_pages(arena_l: jnp.ndarray, se_l: jnp.ndarray,
                     fmt: FPFormat) -> jnp.ndarray:
    """Full f32 view of a layer's pages — the oracle / parity-mode carrier.
    (P, KV, page_size, dh) f32; identical values to the kernel's in-VMEM
    unpack."""
    return _decode(arena_l, se_l[:, None, None, None], fmt)


# --------------------------------------------------------------------------
# preemption swap: packed pages round-trip host memory byte-identically
# --------------------------------------------------------------------------


def swap_out_pages(kv_state: dict[str, jnp.ndarray],
                   pages: list[int]) -> dict[str, np.ndarray]:
    """Copy one sequence's pages (all layers) to host memory.  The pages
    are already wire-format QTensor blocks — int8 codes + int32 scale
    exponents — so a swap is a COPY, not a requantization: the blob holds
    the exact bytes the arena held, keyed by the page's ordinal within the
    sequence (physical page ids are NOT recorded; swap-in may land the
    blob on different pages and only the page table changes)."""
    idx = np.asarray(pages, np.int32)
    return {
        "k": np.asarray(kv_state["k"][:, idx]),     # (L, n, KV, ps, dh) int8
        "v": np.asarray(kv_state["v"][:, idx]),
        "k_se": np.asarray(kv_state["k_se"][:, idx]),  # (L, n) int32
        "v_se": np.asarray(kv_state["v_se"][:, idx]),
    }


def swap_in_pages(kv_state: dict[str, jnp.ndarray], pages: list[int],
                  blob: dict[str, np.ndarray]) -> dict[str, jnp.ndarray]:
    """Restore a swapped-out blob into (possibly different) pages.  The
    inverse of ``swap_out_pages``: byte-identical codes and scale
    exponents, so a restored sequence decodes exactly as if it had never
    been preempted (recompute-free restore)."""
    if blob["k"].shape[1] != len(pages):
        raise ValueError(
            f"blob holds {blob['k'].shape[1]} pages, restore got {len(pages)}")
    idx = jnp.asarray(pages, jnp.int32)
    return {
        "k": kv_state["k"].at[:, idx].set(jnp.asarray(blob["k"])),
        "v": kv_state["v"].at[:, idx].set(jnp.asarray(blob["v"])),
        "k_se": kv_state["k_se"].at[:, idx].set(jnp.asarray(blob["k_se"])),
        "v_se": kv_state["v_se"].at[:, idx].set(jnp.asarray(blob["v_se"])),
    }


def truncate_pages(kv_state: dict[str, jnp.ndarray],
                   released: jnp.ndarray,
                   boundary_page: jnp.ndarray,
                   keep_slots: jnp.ndarray) -> dict[str, jnp.ndarray]:
    """Scrub a rolled-back (rejected-draft) tail out of the arena.

    ``released`` (R,) int32 — the page ids ``PagePool.rollback_seq_len``
    freed (pad with 0: re-zeroing the null page is harmless).  Their codes
    AND scale exponents return to the zero-initialized state, so on pages
    that were fresh before the speculative append the arena is bitwise
    identical to one that never appended.  ``boundary_page`` is the page
    containing the rollback point when it lands mid-page: slots
    ``>= keep_slots`` are zeroed there but its scale exponent is KEPT — it
    was fixed by the page's slot-0 write, which is part of the accepted
    prefix (pass ``boundary_page=0, keep_slots=0`` for a page-aligned
    rollback; that zeroes only the never-read null page).

    Rejection is page-exact and rounding-free: accepted tokens' codes are
    untouched (no requantization — the QTensor pages are immutable wire
    bytes, same property the swap path proves), and the next accepted
    token writes exactly the first zeroed slot under the same scale
    discipline a never-speculated decode would.
    """
    out = dict(kv_state)
    page_size = kv_state["k"].shape[3]
    rel = jnp.asarray(released, jnp.int32)
    slot_mask = (jnp.arange(page_size) >= keep_slots)[None, None, :, None]
    for name in ("k", "v"):
        codes = kv_state[name].at[:, rel].set(jnp.int8(0))
        codes = codes.at[:, boundary_page].set(
            jnp.where(slot_mask, jnp.int8(0), codes[:, boundary_page]))
        out[name] = codes
        out[name + "_se"] = kv_state[name + "_se"].at[:, rel].set(0)
    return out


class SwapStore:
    """Host-side store of preempted sequences' packed KV pages.

    One entry per swapped-out sequence: the ``swap_out_pages`` blob plus
    the cached-token count it covers.  Entries are exact byte copies —
    ``tests/test_serve.py`` pins the swap-out → swap-in round trip as
    byte-identical — so restoring is a page allocation + scatter, never a
    recompute or requantization.
    """

    def __init__(self):
        self._entries: dict[int, tuple[dict[str, np.ndarray], int]] = {}

    def __contains__(self, sid: int) -> bool:
        return sid in self._entries

    def __len__(self) -> int:
        return len(self._entries)

    def put(self, sid: int, blob: dict[str, np.ndarray],
            n_tokens: int) -> None:
        if sid in self._entries:
            raise ValueError(f"sequence {sid} already swapped out")
        self._entries[sid] = (blob, n_tokens)

    def n_tokens(self, sid: int) -> int:
        return self._entries[sid][1]

    def take(self, sid: int) -> tuple[dict[str, np.ndarray], int]:
        """Remove and return ``(blob, n_tokens)`` for a restore."""
        return self._entries.pop(sid)

    @property
    def bytes_used(self) -> int:
        return sum(sum(a.nbytes for a in blob.values())
                   for blob, _ in self._entries.values())


def kv_bytes_per_token(pc: PagedKVConfig, *, carrier_bytes: int = 1,
                       tp_shards: int = 1) -> float:
    """Cache bytes per cached token across all layers: K + V payloads plus
    the amortized per-page scale exponents.  ``carrier_bytes=4`` prices the
    f32-carrier baseline (2 for bf16) for the compression ratio.

    ``tp_shards > 1`` prices ONE shard of a tensor-parallel arena: the
    int8 payloads split with the KV-head axis, while the per-page scale
    exponents are replicated on every shard (they are pmax-shared at write
    time, see ``write_prompt``)."""
    per_layer = 2 * (pc.n_kv_heads // tp_shards) * pc.head_dim * carrier_bytes
    if carrier_bytes == 1:  # packed: two int32 scale exponents per page
        per_layer += 2 * 4 / pc.page_size
    return pc.n_layers * per_layer


# --------------------------------------------------------------------------
# host-side page accounting
# --------------------------------------------------------------------------


class PagePool:
    """Host-side allocator over the arena's page ids.

    Invariants (pinned by the scheduler property tests): page 0 is never
    handed out; a page is owned by at most one sequence; released pages
    return to the free list — ``free + in-use == n_pages - 1`` always.
    """

    def __init__(self, n_pages: int, page_size: int):
        if n_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is reserved)")
        self.n_pages = n_pages
        self.page_size = page_size
        self._free: list[int] = list(range(n_pages - 1, 0, -1))
        self._pages: dict[int, list[int]] = {}
        self._lens: dict[int, int] = {}

    # ------------------------------ queries --------------------------------
    @property
    def free_pages(self) -> int:
        return len(self._free)

    def pages_for(self, n_tokens: int) -> int:
        return -(-max(n_tokens, 1) // self.page_size)

    def can_admit(self, n_tokens: int) -> bool:
        return self.free_pages >= self.pages_for(n_tokens)

    def seq_len(self, sid: int) -> int:
        return self._lens[sid]

    def owns(self, sid: int) -> bool:
        return sid in self._pages

    def pages(self, sid: int) -> list[int]:
        return list(self._pages[sid])

    def can_extend(self, sid: int, n_new: int = 1) -> bool:
        need = self.pages_for(self._lens[sid] + n_new) - len(self._pages[sid])
        return need <= self.free_pages

    # ------------------------------ mutation -------------------------------
    def allocate(self, sid: int, n_tokens: int) -> list[int]:
        """Claim pages for a new sequence of ``n_tokens`` cached tokens."""
        if sid in self._pages:
            raise ValueError(f"sequence {sid} already allocated")
        need = self.pages_for(n_tokens)
        if need > self.free_pages:
            raise RuntimeError(
                f"KV pool exhausted: need {need} pages, {self.free_pages} free")
        got = [self._free.pop() for _ in range(need)]
        self._pages[sid] = got
        self._lens[sid] = n_tokens
        return list(got)

    def extend(self, sid: int, n_new: int = 1) -> list[int]:
        """Grow a sequence by ``n_new`` tokens, claiming pages as the length
        crosses page boundaries.  Returns the newly claimed page ids."""
        new_len = self._lens[sid] + n_new
        need = self.pages_for(new_len) - len(self._pages[sid])
        if need > self.free_pages:
            raise RuntimeError(
                f"KV pool exhausted extending seq {sid}: need {need} pages")
        got = [self._free.pop() for _ in range(need)]
        self._pages[sid].extend(got)
        self._lens[sid] = new_len
        return got

    def release(self, sid: int) -> None:
        """Completion eviction: all of the sequence's pages return to the
        free list."""
        self._free.extend(reversed(self._pages.pop(sid)))
        del self._lens[sid]

    def rollback_seq_len(self, sid: int, new_len: int) -> list[int]:
        """Speculative-decode rejection: shrink a sequence to ``new_len``
        cached tokens, freeing the tail pages the rejected suffix claimed.
        Returns the released page ids (in sequence order) so the caller can
        scrub them from the arena (``truncate_pages``).  Freed pages go
        back LIFO like ``release`` — a subsequent extend re-claims exactly
        the pages a never-speculated pool would have handed out, which is
        what keeps rolled-back arenas bitwise identical to never-appended
        ones."""
        if not 1 <= new_len <= self._lens[sid]:
            raise ValueError(
                f"rollback of seq {sid} to {new_len} tokens "
                f"(has {self._lens[sid]})")
        keep = self.pages_for(new_len)
        pages = self._pages[sid]
        tail = pages[keep:]
        self._pages[sid] = pages[:keep]
        self._lens[sid] = new_len
        self._free.extend(reversed(tail))
        return tail

    # ------------------------------ views ----------------------------------
    def page_table(self, sids: list[int], width: int) -> np.ndarray:
        """(len(sids), width) int32 page table, rows padded with the null
        page 0 (masked out by seq_lens in the kernel)."""
        out = np.zeros((len(sids), width), np.int32)
        for i, sid in enumerate(sids):
            pages = self._pages[sid]
            if len(pages) > width:
                raise ValueError(
                    f"seq {sid} has {len(pages)} pages > table width {width}")
            out[i, :len(pages)] = pages
        return out

    def check_invariants(self) -> None:
        used = [p for pages in self._pages.values() for p in pages]
        assert 0 not in used, "null page handed out"
        assert 0 not in self._free, "null page on the free list"
        assert len(set(used)) == len(used), "page owned twice"
        assert len(used) + len(self._free) == self.n_pages - 1, "page leak"
        for sid, pages in self._pages.items():
            assert len(pages) == self.pages_for(self._lens[sid]), \
                f"seq {sid}: {len(pages)} pages for {self._lens[sid]} tokens"


class ShardedPagePool(PagePool):
    """Page accounting for a tensor-parallel arena: ONE logical allocator
    (page ids are GLOBAL — shard ``i`` stores its KV-head slice of page
    ``p`` at local index ``p``, so every shard's page table is the same
    host-side array) plus one replica ``PagePool`` per shard kept in
    lockstep.

    The replicas are the mesh-mode analogue of the stamped sim arena: the
    engine only ever talks to the primary, every mutation is mirrored, and
    ``check_invariants`` additionally proves the per-shard pools never
    drifted — a scheduler path that mutated one shard's accounting without
    the others (the classic TP desync bug) fails the next invariant sweep
    rather than corrupting a remote arena.

    Each mirrored mutation and its lockstep check run inside a
    ``serve.pool_lockstep`` span (``repro.obs.trace.span``, recorded in
    ``tracer`` when one is given), so a profile puts their host time down
    to the engine.
    """

    def __init__(self, n_pages: int, page_size: int, *, n_shards: int,
                 tracer=None):
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        super().__init__(n_pages, page_size)
        self.n_shards = n_shards
        self.tracer = tracer
        self._replicas = [PagePool(n_pages, page_size)
                          for _ in range(n_shards)]

    def _mirror(self, op: str, sid: int, *args) -> None:
        want = self._pages.get(sid)
        with span(self.tracer, "serve.pool_lockstep"):
            for i, rep in enumerate(self._replicas):
                got = getattr(rep, op)(sid, *args)
                if op != "release" and rep._pages.get(sid) != want:
                    raise AssertionError(
                        f"shard {i} pool drifted on {op}(sid={sid}): "
                        f"{got} vs primary {want}")

    def allocate(self, sid: int, n_tokens: int) -> list[int]:
        got = super().allocate(sid, n_tokens)
        self._mirror("allocate", sid, n_tokens)
        return got

    def extend(self, sid: int, n_new: int = 1) -> list[int]:
        got = super().extend(sid, n_new)
        self._mirror("extend", sid, n_new)
        return got

    def release(self, sid: int) -> None:
        super().release(sid)
        self._mirror("release", sid)

    def rollback_seq_len(self, sid: int, new_len: int) -> list[int]:
        got = super().rollback_seq_len(sid, new_len)
        self._mirror("rollback_seq_len", sid, new_len)
        return got

    def check_invariants(self) -> None:
        super().check_invariants()
        for i, rep in enumerate(self._replicas):
            rep.check_invariants()
            assert rep._pages == self._pages, \
                f"shard {i} page ownership drifted from the primary"
            assert rep._lens == self._lens, \
                f"shard {i} sequence lengths drifted from the primary"
            assert rep._free == self._free, \
                f"shard {i} free list drifted from the primary"
