"""Decode-state containers: KV caches for attention layers, conv+SSD state
for SSM layers, plus the paged KV cache backing the serving path.

Dense specs (``attn_cache_spec`` / ``ssm_cache_spec``) are stored stacked
per scan position-group (leading n_super dim) so the layer scan can thread
them as xs/ys; they remain the prefill/training-eval format.

The paged cache replaces the per-request dense (B, max_seq, KV, hd) layout
for serving: one global pool of fixed-size pages per layer, a host-side
:class:`PageAllocator` (block table + free-list) that hands pages to
requests on admission and recycles them on completion, and pure gather /
scatter helpers the decode step uses on device. Heads shard over the mesh
axis (pages carry the KV-head dim), so the explicit tensor-parallel decode
path keeps each rank's page pool local.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List

import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.ssm import ssm_dims


def attn_cache_spec(cfg: ModelConfig, batch: int, max_seq: int, dtype) -> Dict:
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, max_seq, kv, hd), dtype),
        "v": jnp.zeros((batch, max_seq, kv, hd), dtype),
    }


def ssm_cache_spec(cfg: ModelConfig, batch: int, dtype) -> Dict:
    d_in, H, P, G, N = ssm_dims(cfg)
    return {
        "conv_x": jnp.zeros((batch, cfg.ssm_conv - 1, d_in), dtype),
        "conv_bc": jnp.zeros((batch, cfg.ssm_conv - 1, 2 * G * N), dtype),
        "state": jnp.zeros((batch, H, P, N), jnp.float32),
    }


# ---------------------------------------------------------------------------
# paged KV cache
# ---------------------------------------------------------------------------


class OutOfPagesError(RuntimeError):
    """The page pool cannot satisfy an allocation (pages or slots)."""


@dataclass(frozen=True)
class PagedCacheConfig:
    """Geometry of the page pool.

    ``page_size``   tokens per page.
    ``num_pages``   pool size, shared by all requests (also the block-table
                    sentinel value: an entry == ``num_pages`` means "no
                    page"; device scatters to it are dropped).
    ``max_slots``   decode batch width — concurrent requests.
    ``max_seq``     per-request token cap (prompt + generated); bounds the
                    block-table row width.
    """
    page_size: int
    num_pages: int
    max_slots: int
    max_seq: int

    def __post_init__(self):
        if min(self.page_size, self.num_pages,
               self.max_slots, self.max_seq) <= 0:
            raise ValueError(f"non-positive paged-cache geometry: {self}")

    @property
    def pages_per_slot(self) -> int:
        return -(-self.max_seq // self.page_size)


def paged_attn_cache_spec(cfg: ModelConfig, pcfg: PagedCacheConfig,
                          dtype) -> Dict:
    """One layer's page pool: k/v pages of (num_pages, page_size, KV, hd)."""
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    shape = (pcfg.num_pages, pcfg.page_size, kv, hd)
    return {"k_pages": jnp.zeros(shape, dtype),
            "v_pages": jnp.zeros(shape, dtype)}


class PageAllocator:
    """Host-side block table + free-list over one page pool.

    A request reserves its worst-case page count up front (``allocate`` with
    the prompt + max-new token total), so decode never runs out of pages
    mid-flight — admission control happens once, via ``can_allocate``.
    ``seq_len`` then tracks the filled prefix: ``append`` advances it one
    token per decode step, ``release`` recycles the slot and its pages.

    The numpy ``block_table`` / ``seq_lens`` views are the device inputs:
    unallocated entries hold the sentinel ``num_pages`` so device-side
    scatters into them drop and gathers clip (masked off by length).
    """

    def __init__(self, pcfg: PagedCacheConfig):
        self.cfg = pcfg
        self.block_table = np.full(
            (pcfg.max_slots, pcfg.pages_per_slot), pcfg.num_pages, np.int32)
        self.seq_lens = np.zeros((pcfg.max_slots,), np.int32)
        self._capacity = np.zeros((pcfg.max_slots,), np.int32)
        self._free_pages: List[int] = list(range(pcfg.num_pages))
        self._free_slots: List[int] = list(range(pcfg.max_slots))

    def _pages_for(self, total_tokens: int) -> int:
        return -(-total_tokens // self.cfg.page_size)

    @property
    def free_page_count(self) -> int:
        return len(self._free_pages)

    @property
    def free_slot_count(self) -> int:
        return len(self._free_slots)

    def can_allocate(self, total_tokens: int) -> bool:
        return (bool(self._free_slots)
                and 0 < total_tokens <= self.cfg.max_seq
                and self._pages_for(total_tokens) <= len(self._free_pages))

    def allocate(self, total_tokens: int) -> int:
        """Reserve a slot + pages for up to ``total_tokens``; returns slot."""
        if total_tokens <= 0 or total_tokens > self.cfg.max_seq:
            raise ValueError(
                f"request of {total_tokens} tokens exceeds max_seq="
                f"{self.cfg.max_seq}")
        npages = self._pages_for(total_tokens)
        if not self._free_slots or npages > len(self._free_pages):
            raise OutOfPagesError(
                f"cannot reserve {npages} pages + 1 slot "
                f"(free: {len(self._free_pages)} pages, "
                f"{len(self._free_slots)} slots)")
        slot = self._free_slots.pop(0)
        for i in range(npages):
            self.block_table[slot, i] = self._free_pages.pop(0)
        self.seq_lens[slot] = 0
        self._capacity[slot] = npages * self.cfg.page_size
        return slot

    def commit(self, slot: int, length: int) -> None:
        """Record ``length`` prefilled tokens for ``slot``."""
        if length > self._capacity[slot]:
            raise ValueError(
                f"slot {slot}: prefill of {length} exceeds reserved "
                f"capacity {int(self._capacity[slot])}")
        self.seq_lens[slot] = length

    def append(self, slot: int, n: int = 1) -> None:
        """Advance ``slot`` by ``n`` decoded tokens."""
        if self.seq_lens[slot] + n > self._capacity[slot]:
            raise OutOfPagesError(
                f"slot {slot}: append past reserved capacity "
                f"{int(self._capacity[slot])}")
        self.seq_lens[slot] += n

    def release(self, slot: int) -> None:
        """Recycle the slot and its pages (block-table row -> sentinel)."""
        row = self.block_table[slot]
        self._free_pages.extend(int(p) for p in row if p < self.cfg.num_pages)
        row[:] = self.cfg.num_pages
        self.seq_lens[slot] = 0
        self._capacity[slot] = 0
        self._free_slots.append(slot)

    def device_tables(self):
        """(block_table, seq_lens) as device arrays for the decode step.
        Copies: the allocator changes its host arrays in place, and a device
        array made from a numpy array without a copy may share its memory
        (zero-copy on the CPU backend), so an earlier step's tables would
        change under it."""
        return jnp.array(self.block_table), jnp.array(self.seq_lens)


def gather_pages(pages: jnp.ndarray, block_table: jnp.ndarray) -> jnp.ndarray:
    """Gather a pool's pages into per-slot contiguous KV.

    ``pages``: (num_pages, page_size, KV, hd); ``block_table``: (B, pmax)
    int32 (sentinel entries out of range clip to the last page — callers
    mask by length). Returns (B, pmax * page_size, KV, hd).
    """
    B, pmax = block_table.shape
    ps = pages.shape[1]
    g = jnp.take(pages, block_table, axis=0, mode="clip")
    return g.reshape(B, pmax * ps, *pages.shape[2:])


def commit_prefill(pages_layers: Dict, dense_layers: Dict,
                   block_row: jnp.ndarray, length, *,
                   page_size: int) -> Dict:
    """Scatter one request's dense prefill cache into its reserved pages.

    ``pages_layers``: {"pN": {"k_pages": (n_super, P, ps, KV, hd), ...}};
    ``dense_layers``: {"pN": {"k": (n_super, 1, S, KV, hd), ...}} (batch-1
    prefill, possibly padded past ``length`` — pad positions scatter to the
    sentinel and drop). ``block_row``: (pmax,) int32. Pure; jit with the
    page buffers donated.
    """
    out: Dict = {}
    for name, stacked in pages_layers.items():
        dense = dense_layers[name]
        S = dense["k"].shape[2]
        pos = jnp.arange(S)
        row = jnp.take(block_row, pos // page_size, mode="clip")
        num_pages = stacked["k_pages"].shape[1]
        page_idx = jnp.where(pos < length, row, num_pages)
        off = pos % page_size
        m = dict(stacked)
        for pooled, flat in (("k_pages", "k"), ("v_pages", "v")):
            val = dense[flat][:, 0].astype(stacked[pooled].dtype)
            m[pooled] = stacked[pooled].at[:, page_idx, off].set(
                val, mode="drop")
        out[name] = m
    return out
