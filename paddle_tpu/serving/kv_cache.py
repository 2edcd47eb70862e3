"""Paged KV-cache pool for the continuous-batching serving engine.

One fixed ``[num_pages, page_size, n_kv_heads, head_dim]`` array pair per
layer that keeps K/V pages (every layer of a uniform decoder; the
attention layers of a model that declares ``cache_layers()``, whose
recurrent layers get a per-slot state beside the pages:
``KVCachePool.state``, SERVING.md "Models with recurrent state")
(the PagedAttention pool, SOSP '23), or, for a model with latent
attention, ONE ``[num_pages, page_size, width]`` array per layer whose
row is a token's compressed latent beside its rotated shared key
(``cache_layers()`` answers ``("latent", width)``; SERVING.md "Models
with a latent cache"); sequences own pages through
per-request int32 block tables instead of contiguous ``[B, max_len]``
buffers, so cache memory fragments at page granularity instead of
request granularity and a request's reservation grows one page at a
time as it decodes.

On top of the allocator sits **automatic prefix caching** (RadixAttention,
SGLang): pages are reference counted, full pages are indexed by a
chained content hash ``h_i = H(h_{i-1}, page_tokens_i)``, and a released
request's pages stay resident as refcount-0 *cached* pages on an LRU
instead of returning to the free list. A later request whose prompt
shares the prefix maps those pages straight into its block table
(``match_prefix`` + ``acquire``) and prefills only the uncached suffix.
Partially-filled last pages are indexed too and reused copy-on-write:
a hit never writes the cached page in place — the hitter receives a
fresh page holding a device copy (``cow_into``) and extends that.
``alloc`` evicts cached pages LRU-oldest only when the free list alone
cannot satisfy it, scrubbing them back to zero on the way out.

Invariants (relied on by the engine's no-retrace + determinism
contracts, SERVING.md):
- the device arrays are allocated ONCE at pool construction and only
  ever updated functionally inside the compiled prefill/decode programs
  — alloc/free/match move host-side integers, never device memory
  (the two exceptions: ``cow_into``, a functional ``.at[]`` update,
  and scrub-on-evict, one compiled program that zeroes the pages in
  the donated arrays);
- page 0 is reserved as the scratch page: never handed out, used as the
  write/gather target for inactive slots and padded block-table entries
  (always masked by seq_lens, so its garbage is never read into a
  softmax with weight > 0);
- alloc is all-or-nothing: a partial grab is rolled back so a failed
  allocation leaves the free list unchanged (the scheduler turns the
  failure into a preemption, not a torn reservation);
- a page with refcount > 0 is never written by anyone but its single
  writer (shared full pages are immutable; partial pages are shared
  only through COW copies) and never scrubbed — quarantined pages
  (``quarantine``) are deregistered immediately but scrubbed only when
  the last holder releases them (refcount 0).
"""

from __future__ import annotations

import functools
import hashlib
import math
import struct
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..observability.trace import NULL_TRACER
from ..quantization.serving import QuantizedKV
from .errors import LatentCacheError, ServingError
from .tiering import HostTier

__all__ = ["KVCachePool", "PoolExhaustedError", "PrefixMatch", "HybridCache",
           "declared_cache_layers"]


class HybridCache(NamedTuple):
    """What the step programs hand a model whose layers do not all keep
    K/V pages: ``kv`` the page pairs of its attention layers, ``state``
    the per-slot arrays of its recurrent layers (``KVCachePool.state``),
    both in layer order. The model returns the same with ``counts`` set
    (small integers it counted in the step; the engine's counters)."""
    kv: list
    state: list
    counts: Any = None

# chain root for the page-content hash (the "parent" of the first page).
# A quantized pool chains from a DIFFERENT root (the mode tag hashed in),
# so an fp-cache hash and an int8-cache hash of the same tokens can never
# alias: the hash names the page *content* (KV bytes + scales), and the
# same tokens produce different content under the two storage formats.
_HASH_ROOT = b"\x00" * 16
_HASH_ROOT_INT8 = hashlib.blake2b(b"paddle_tpu.kv.int8",
                                  digest_size=16).digest()


def _page_copy(arr, src: int, dst: int, stacked: bool = False):
    """Device-copy one page; a QuantizedKV page carries its scale row
    along with the int8 codes (COW without the scales would dequantize
    the copy with garbage). ``stacked`` indexes pages on dim 1 of the
    pipeline-stacked ``[L, pages, ...]`` layout — the copy spans every
    layer, same as the per-layer list-comprehension it replaces."""
    if isinstance(arr, QuantizedKV):
        return QuantizedKV(_page_copy(arr.q, src, dst, stacked),
                           _page_copy(arr.scale, src, dst, stacked))
    if stacked:
        return arr.at[:, dst].set(arr[:, src])
    return arr.at[dst].set(arr[src])


def _page_zero(arr, idx, stacked: bool = False):
    """Zero pages; a QuantizedKV page zeroes codes AND scales — a scrub
    that left a poisoned (NaN) scale row behind would re-poison the next
    tenant on its first dequantized read."""
    if isinstance(arr, QuantizedKV):
        return QuantizedKV(_page_zero(arr.q, idx, stacked),
                           _page_zero(arr.scale, idx, stacked))
    if stacked:
        return arr.at[:, idx].set(0)
    return arr.at[idx].set(0)


# pages one call of the compiled scrub zeroes; a longer list takes
# several calls, a shorter one repeats its first page (one shape, so
# one program, compiled when the engine warms its step programs)
_SCRUB_WIDTH = 16


@functools.partial(jax.jit, static_argnums=(2,), donate_argnums=(0,))
def _scrub_in_place(pools, idx, stacked):
    """Zero pages ``idx`` of every pool array in the arrays themselves:
    the pool is donated, as the step programs take it."""
    return [tuple(_page_zero(a, idx, stacked) for a in pair)
            for pair in pools]


def _page_hash(parent: bytes, tokens) -> bytes:
    """Chained page-content key: H(parent_hash, page_tokens). Collision
    resistance matters — a false positive would serve another prompt's
    KV — so this is blake2b-128 over the exact token bytes, not
    Python's 64-bit ``hash``."""
    h = hashlib.blake2b(parent, digest_size=16)
    h.update(struct.pack(f"<{len(tokens)}q", *tokens))
    return h.digest()


def declared_cache_layers(config) -> tuple[list, list]:
    """What a model's config says its layers keep for a request:
    ``config.cache_layers()`` gives, in layer order, ``("pages", kv
    heads, head dim)`` (a K and a V page array), ``("latent", width)``
    (one page array: a row of ``width`` values a token), ``("state",
    ((shape, dtype), ...))`` (arrays kept per slot) or None; a config
    without it is a uniform decoder, every one of its
    ``num_hidden_layers`` keeping pages of ``num_key_value_heads`` x
    ``head_dim``. Returns the page formats (the declarations that keep
    pages, as given) and the state declarations, each in layer order."""
    declare = getattr(config, "cache_layers", None)
    layers = (declare() if declare is not None else
              [("pages", config.num_key_value_heads, config.head_dim)]
              * config.num_hidden_layers)
    return ([tuple(spec) for spec in layers
             if spec and spec[0] in ("pages", "latent")],
            [spec[1] for spec in layers if spec and spec[0] == "state"])


class PoolExhaustedError(ServingError):
    """Raised by ``alloc`` when the pool cannot satisfy a request; the
    scheduler catches it and preempts (never propagates to users)."""


@dataclass
class PrefixMatch:
    """Result of ``match_prefix``: the longest cached prefix of a token
    sequence, at page granularity. ``full_pages`` are immutable shared
    pages to map directly; ``partial_page`` (if any) must be reused via
    ``cow_into`` a freshly-allocated page, never written in place.

    With a host tier attached the walk continues past the last
    HBM-resident full page: ``chain`` holds the content-hash keys of
    the continuation full pages, each resolvable in HBM OR the host
    tier at match time (re-resolved HBM-first at restore time — a page
    re-registered since its spill wins over the host copy), and
    ``host_partial_key`` names a host-tier partial tail. ``host_tokens``
    counts the tokens that would have to be RESTORED (host-resolved at
    match time) — the scheduler's restore-budget charge is computed
    from it. ``cached_tokens`` keeps its pre-tier meaning (the
    HBM-contiguous prefix); ``total_cached`` is the full hierarchy
    match the admission actually targets."""
    full_pages: list[int] = field(default_factory=list)
    partial_page: int | None = None
    partial_len: int = 0
    cached_tokens: int = 0
    chain: list[bytes] = field(default_factory=list)
    host_tokens: int = 0
    host_partial_key: bytes | None = None
    host_partial_len: int = 0
    total_cached: int = 0

    @property
    def hit(self) -> bool:
        return self.cached_tokens > 0 or self.total_cached > 0


class KVCachePool:
    def __init__(self, num_layers: int, num_pages: int, page_size: int,
                 num_kv_heads: int, head_dim: int, dtype=jnp.bfloat16,
                 cache_enabled: bool = True, quantized: bool = False,
                 host_tier=None, sharding=None, tp_degree: int = 1,
                 pp_degree: int = 1, state_layers=(), max_slots: int = 0,
                 latent_width: int = 0):
        if num_pages < 2:
            raise ValueError("num_pages must be >= 2 (page 0 is the "
                             "reserved scratch page)")
        # a latent pool (``latent_width`` > 0): every entry of ``pools``
        # is ONE array ``[num_pages, page_size, row_width]``, a row a
        # token, where a K/V pool has a pair of ``[..., kv heads, head
        # dim]``. A row is ``latent_width`` values padded with zeros to
        # whole lanes: the TPU's default layout of an array whose last
        # dimension is not a multiple of 128 makes another dimension
        # minor (offline compile, PR 35: ``bf16[8225,16,576]{0,2,1}``),
        # and a page would no longer be one piece of memory. What has
        # not been carried over to a latent pool is refused here, by
        # name (SERVING.md "Models with a latent cache")
        self.latent_width = int(latent_width)
        self.row_width = -(-self.latent_width // 128) * 128
        if self.latent_width:
            for asked, what in (
                    (quantized, "int8 latent pages"),
                    (host_tier, "the host tier"),
                    (sharding is not None or tp_degree > 1
                     or pp_degree > 1, "a sharded or stacked pool (tp or "
                                       "pp > 1)")):
                if asked:
                    raise LatentCacheError(
                        f"a latent pool cannot honour {what} (SERVING.md "
                        f"\"Models with a latent cache\")")
        self.num_layers = num_layers
        self.num_pages = num_pages
        self.page_size = page_size
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.quantized = quantized
        self.dtype = jnp.int8 if quantized else dtype
        # tensor parallelism (serving/parallel.py): ``sharding`` is a
        # (payload, scale) NamedSharding pair splitting the kv-head dim
        # over the mp mesh. The arrays stay GLOBAL logical jax.Arrays —
        # every host-side path below (alloc/refcount/hash metadata,
        # .at[].set writes, device_get spill/snapshot capture) is
        # tp-agnostic because sharding is a layout, not a shape change.
        # Pipeline parallelism stacks the per-layer pairs into ONE
        # [num_layers, pages, ...] pair whose leading dim splits on the
        # pp mesh axis — each stage's HBM holds only its own layers'
        # pages (the ~1/pp per-chip KV saving); ``stacked`` flags the
        # layout and every content-touching path below branches on it.
        # The HOST payload format (per layer k then v) is unchanged, so
        # spills and snapshots stay portable across pp degrees.
        self.sharding = sharding
        self.tp_degree = int(tp_degree)
        self.pp_degree = int(pp_degree)
        self.stacked = self.pp_degree > 1
        shape = ((num_pages, page_size, self.row_width)
                 if self.latent_width
                 else (num_pages, page_size, num_kv_heads, head_dim))
        if self.stacked:
            shape = (num_layers,) + shape

        def _place(z, scale=False):
            if sharding is None:
                return z
            return jax.device_put(z, sharding[1] if scale else sharding[0])
        # per-layer (pool_k, pool_v), or (rows,) in a latent pool. A step
        # program is given these
        # arrays to write in place (donated: deleted when it returns) and
        # its result replaces them, so the handles here always name the
        # latest; read a page through the pool, never keep an array
        # across a step.
        # Quantized mode stores int8 codes + one fp32 absmax scale per
        # [page, slot, kv_head] row (see quantization/serving.py).
        if quantized:
            def _zeros():
                return QuantizedKV(
                    _place(jnp.zeros(shape, jnp.int8)),
                    _place(jnp.zeros(shape[:-1], jnp.float32), scale=True))
            self.pools = [(_zeros(), _zeros())
                          for _ in range(1 if self.stacked else num_layers)]
        elif self.latent_width:
            self.pools = [(jnp.zeros(shape, dtype),)
                          for _ in range(num_layers)]
        else:
            self.pools = [(_place(jnp.zeros(shape, dtype)),
                           _place(jnp.zeros(shape, dtype)))
                          for _ in range(1 if self.stacked else num_layers)]
        # per-slot recurrent state beside the pages (a model whose layers
        # are not all attention): one tuple of [max_slots, ...] arrays
        # for each layer that declares one, threaded through the step
        # programs like the pages. A slot's row belongs to the request
        # that holds the slot (``state_admit`` / ``state_release``: host
        # accounting); its content is reset inside the program, by the
        # first row a request runs at position 0.
        self.state = [tuple(jnp.zeros((max_slots, *shape), jnp.dtype(dt))
                            for shape, dt in arrays)
                      for arrays in state_layers]
        self.state_slots: dict[int, str] = {}      # slot -> request id
        self.state_bytes_per_slot = sum(
            math.prod(shape) * jnp.dtype(dt).itemsize
            for arrays in state_layers for shape, dt in arrays)
        # fp and int8 caches chain their content hashes from different
        # roots — same tokens, different page content, never aliased
        self._hash_root = _HASH_ROOT_INT8 if quantized else _HASH_ROOT
        # host-RAM spill tier (serving/tiering.py): True -> defaults,
        # an int -> byte budget, or a ready HostTier (shareable across
        # homogeneous pools — identical weights produce identical KV
        # bytes, and the dtype tag below keeps formats from aliasing)
        if host_tier is True:
            host_tier = HostTier()
        elif isinstance(host_tier, int) and not isinstance(host_tier, bool):
            host_tier = HostTier(max_bytes=host_tier)
        self.host_tier: HostTier | None = host_tier
        self._tier_tag = "int8" if quantized else str(jnp.dtype(self.dtype))
        # LIFO free list, page 0 reserved (scratch)
        self._free = list(range(num_pages - 1, 0, -1))
        # pages known to hold all-zero content: everything at
        # construction, re-added by scrub(), dropped at handout or any
        # host-payload write. audit()'s scrubbed-means-zero check reads
        # the device content of (free ∩ scrubbed) pages against this.
        self._scrubbed: set[int] = set(range(1, num_pages))
        self._peak_in_use = 0
        # fault-draw step context for the serving.alloc site, advanced by
        # the engine once per step — without it, probabilistic specs
        # would fall back to the process-global training-step cursor and
        # draw ONE outcome for the engine's whole lifetime
        self.fault_step: int | None = None
        # optional match-path for the serving.alloc site; the fleet
        # router sets it to the replica index so a FaultSpec with
        # ``match=r"^0$"`` pins an alloc storm to one replica
        self.fault_path: str | None = None

        # ---- prefix cache state (all host-side integers) ----
        self.cache_enabled = cache_enabled
        self._ref: dict[int, int] = {}          # page -> refcount (>0 only)
        self._full_index: dict[bytes, int] = {}      # chained hash -> page
        self._partial_index: dict[bytes, int] = {}   # chained hash -> page
        self._page_key: dict[int, tuple[str, bytes]] = {}  # page -> index key
        self._lru: "OrderedDict[int, None]" = OrderedDict()  # refcount-0 cached
        self._scrub_on_zero: set[int] = set()   # quarantined, shared pages
        # injected by the engine when tracing is on; pool events (LRU
        # eviction, COW copies, quarantine) land on the "pool" track
        self.tracer = NULL_TRACER
        self.counters: dict[str, int] = {
            "prefix_lookups": 0, "prefix_hits": 0, "prefix_hit_pages": 0,
            "prefix_partial_hits": 0, "prefix_evictions": 0,
            "prefix_cow_copies": 0, "prefix_pages_registered": 0,
            "rewound_tokens": 0,
        }

    @classmethod
    def from_config(cls, config, num_pages: int, page_size: int,
                    dtype=jnp.bfloat16, cache_enabled: bool = True,
                    quantized: bool = False, host_tier=None,
                    sharding=None, tp_degree: int = 1,
                    pp_degree: int = 1, max_slots: int = 0) -> "KVCachePool":
        """Build from what the model's config says each layer keeps for
        a request (``declared_cache_layers``): one page pair for each
        layer that keeps pages, ``max_slots`` rows of each array of each
        layer that keeps a per-slot state."""
        pages, state_layers = declared_cache_layers(config)
        if not pages or len(set(pages)) != 1:
            raise ValueError("the pool keeps one page format: the layers "
                             f"that keep pages declare {sorted(set(pages))}")
        kind, *dims = pages[0]
        kvh, d, width = (0, 0, dims[0]) if kind == "latent" else (*dims, 0)
        return cls(len(pages), num_pages, page_size, kvh, d, dtype,
                   cache_enabled=cache_enabled, quantized=quantized,
                   host_tier=host_tier, sharding=sharding,
                   tp_degree=tp_degree, pp_degree=pp_degree,
                   state_layers=state_layers, max_slots=max_slots,
                   latent_width=width)

    # ---- accounting ----

    @property
    def capacity(self) -> int:
        """Allocatable pages (excludes the scratch page)."""
        return self.num_pages - 1

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_cached(self) -> int:
        """Refcount-0 pages kept resident for prefix reuse (evictable)."""
        return len(self._lru)

    @property
    def num_available(self) -> int:
        """Pages an ``alloc`` can hand out: free + evictable cached."""
        return len(self._free) + len(self._lru)

    @property
    def num_in_use(self) -> int:
        """Pages pinned by live requests (refcount > 0). Cached
        refcount-0 pages are NOT in use — they are reclaimable."""
        return self.capacity - len(self._free) - len(self._lru)

    def utilization(self) -> float:
        return self.num_in_use / self.capacity

    def pages_for(self, n_tokens: int) -> int:
        """Pages needed to hold n_tokens cache positions."""
        return max(1, math.ceil(n_tokens / self.page_size))

    def kv_bytes_per_token(self) -> int:
        """HBM bytes ONE cached token position costs across all layers
        (K+V, or a latent pool's one row): the per-token KV traffic unit
        the int8 bench configs score MBU against. Quantized: 1 byte/element of codes plus the fp32
        scale per kv-head row; fp: itemsize bytes/element."""
        if self.latent_width:     # one (padded) row a token a layer
            return (self.num_layers * self.row_width
                    * jnp.dtype(self.dtype).itemsize)
        kvh, d = self.num_kv_heads, self.head_dim
        if self.quantized:
            per = kvh * d * 1 + kvh * 4   # int8 codes + fp32 scale row
        else:
            per = kvh * d * jnp.dtype(self.dtype).itemsize
        return 2 * self.num_layers * per

    def kv_bytes_per_token_shard(self) -> int:
        """Per-DEVICE bytes one cached token costs under tensor /
        pipeline parallelism: the kv-head dim is split tp ways (each
        shard holds ``kvh/tp`` heads of every page) and the stacked
        layer dim pp ways (each stage holds only its own ``L/pp``
        layers' pages), so the per-chip figure is the full cost over
        ``tp * pp`` (== the full figure at tp=pp=1). The per-chip HBM
        budget a parallel deployment plans against."""
        return (self.kv_bytes_per_token()
                // max(self.tp_degree, 1) // max(self.pp_degree, 1))

    def stats(self) -> dict:
        # host-tier breakdown rides along (schema-stable zeros when the
        # tier is off) so dashboards reading pool stats don't need a
        # second call — and observability.render_prometheus turns every
        # numeric key here into a paddle_serving_pool_* gauge (the tp_*
        # keys below become the paddle_serving_pool_tp_* family)
        tier = (self.host_tier.stats() if self.host_tier is not None
                else HostTier.zero_stats())
        shard_bpt = self.kv_bytes_per_token_shard()
        return {"num_pages": self.num_pages, "page_size": self.page_size,
                "capacity": self.capacity, "in_use": self.num_in_use,
                "pinned": self.num_in_use, "cached": self.num_cached,
                "free": self.num_free, "utilization": self.utilization(),
                "peak_in_use": self._peak_in_use,
                "indexed_pages": len(self._page_key),
                "kv_quant": int(self.quantized),
                "host_tier": int(self.host_tier is not None),
                "tp_degree": self.tp_degree,
                "pp_degree": self.pp_degree,
                "pp_stage_layers":
                    self.num_layers // max(self.pp_degree, 1),
                "tp_shard_kv_bytes_per_token": shard_bpt,
                "tp_shard_in_use_bytes":
                    self.num_in_use * self.page_size * shard_bpt,
                "tp_shard_capacity_bytes":
                    self.capacity * self.page_size * shard_bpt,
                "state_layers": len(self.state),
                "state_slots_live": len(self.state_slots),
                "state_bytes_per_slot": self.state_bytes_per_slot,
                "state_bytes_live":
                    len(self.state_slots) * self.state_bytes_per_slot,
                **tier,
                **self.counters}

    # ---- per-slot recurrent state (host accounting) ----

    def state_admit(self, slot: int, rid: str) -> None:
        """The request ``rid`` takes the state row of ``slot``. Nothing
        moves on the device: its first row runs at position 0, and the
        program starts a slot at position 0 from zero state."""
        with self.tracer.span("state_admit", slot=slot):
            if slot in self.state_slots:
                raise AssertionError(
                    f"state slot {slot} admitted to {rid!r} while "
                    f"{self.state_slots[slot]!r} holds it")
            self.state_slots[slot] = rid

    def state_release(self, slot: int) -> None:
        with self.tracer.span("state_release", slot=slot):
            del self.state_slots[slot]

    # ---- alloc / free ----

    def alloc(self, n: int) -> list[int]:
        """Grab n pages (all-or-nothing); raises PoolExhaustedError.

        The free list is consumed first; when it runs dry, refcount-0
        cached pages are evicted LRU-oldest — deregistered from the
        prefix index and scrubbed back to zero (the masked-garbage-is-
        zero invariant survives reuse) — until the grab fits. Pinned
        pages (refcount > 0) are never touched.

        Fault site ``serving.alloc``: an armed ``raise`` spec here
        surfaces as a PoolExhaustedError — the scheduler's normal
        exhaustion path — so chaos tests can drive deterministic
        pool-exhaustion storms (preemption cascades) without actually
        shrinking the pool."""
        from ..distributed import fault as _fault
        try:
            _fault.trip("serving.alloc", step=self.fault_step,
                        path=self.fault_path,
                        need=n, free=self.num_available)
        except _fault.FaultInjected as e:
            raise PoolExhaustedError(
                f"injected exhaustion (serving.alloc): {e}") from e
        if n > self.num_available:
            raise PoolExhaustedError(
                f"need {n} pages, {len(self._free)} free + "
                f"{len(self._lru)} cached (capacity {self.capacity})")
        evicted: list[int] = []
        while len(self._free) < n and self._lru:
            page, _ = self._lru.popitem(last=False)  # oldest first
            self._spill(page)   # demote to the host tier (if attached)
                                # BEFORE the index key is forgotten
            self._deregister(page)
            evicted.append(page)
            self._free.append(page)
        if evicted:
            self.scrub(evicted)
            self.counters["prefix_evictions"] += len(evicted)
            self.tracer.instant("prefix_evict", track="pool",
                                pages=len(evicted))
            self.tracer.bump("prefix_evictions", len(evicted),
                             track="pool")
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._ref[p] = 1
            self._scrubbed.discard(p)
        self._peak_in_use = max(self._peak_in_use, self.num_in_use)
        return pages

    def free(self, pages: list[int]) -> None:
        """Unconditionally return pages to the free list (no refcount /
        cache semantics — the low-level inverse of ``alloc``). The
        refcounted paths go through ``release``."""
        for p in pages:
            if p == 0 or p >= self.num_pages:
                raise ValueError(f"page {p} is not an allocatable page")
            if p in self._free:
                raise ValueError(f"double free of page {p}")
        for p in pages:
            self._ref.pop(p, None)
            self._lru.pop(p, None)
            self._scrub_on_zero.discard(p)
            self._deregister(p)
        self._free.extend(pages)

    # ---- reference counting ----

    def acquire(self, pages: list[int]) -> None:
        """Take a reference on each page (a cache hit mapping shared
        pages into a block table). A refcount-0 cached page is pinned —
        pulled off the eviction LRU — by its first new holder."""
        for p in pages:
            r = self._ref.get(p, 0)
            if r == 0:
                self._lru.pop(p, None)
            self._ref[p] = r + 1
        self._peak_in_use = max(self._peak_in_use, self.num_in_use)

    def release(self, pages: list[int]) -> None:
        """Drop one reference per page. At refcount 0 a page either
        stays resident as a cached page (registered in the prefix index
        and cache enabled), is scrubbed-then-freed (quarantined), or
        returns to the free list."""
        scrub: list[int] = []
        for p in pages:
            r = self._ref.get(p, 0) - 1
            if r > 0:
                self._ref[p] = r
                continue
            self._ref.pop(p, None)
            if p in self._scrub_on_zero:
                # quarantined while shared: only now, with no holder
                # left, is it safe to zero the poisoned content
                self._scrub_on_zero.discard(p)
                self._deregister(p)
                scrub.append(p)
                self._free.append(p)
            elif self.cache_enabled and p in self._page_key:
                self._lru[p] = None
                self._lru.move_to_end(p)
            else:
                self._deregister(p)
                self._free.append(p)
        if scrub:
            self.scrub(scrub)

    def refcount(self, page: int) -> int:
        return self._ref.get(page, 0)

    def quarantine(self, pages: list[int]) -> None:
        """Poison containment for a request whose pages may hold
        non-finite values: deregister every page from the prefix index
        immediately (no future request may match it) and mark it
        scrub-on-zero. Pages still shared with live requests are NOT
        scrubbed here — zeroing under a reader would corrupt its
        stream; the scrub happens in ``release`` when the last
        reference drops. A quarantined page's host-tier entry is purged
        too — poisoned content must not survive in ANY tier — and the
        scrub-on-zero mark keeps the page from ever spilling later."""
        todo = []
        for p in set(pages):
            kk = self._page_key.get(p)
            if kk is not None and self.host_tier is not None:
                self.host_tier.discard(self._tier_tag, *kk)
            self._deregister(p)
            if self._ref.get(p, 0) > 0:
                self._scrub_on_zero.add(p)
            elif p in self._lru:        # cached, no holders: scrub now
                self._lru.pop(p)
                todo.append(p)
                self._free.append(p)
        if todo:
            self.scrub(todo)
        self.tracer.instant("quarantine", track="pool",
                            pages=len(set(pages)))

    # ---- the prefix index ----

    def _namespaced_root(self, namespace: bytes = b"") -> bytes:
        """Chain root for a (possibly namespaced) prefix walk. A LoRA
        request's KV depends on its adapter — the same system prompt
        produces DIFFERENT page content under adapter X and adapter Y —
        so each adapter's chain starts from a root derived from the
        adapter's content digest, and a cross-adapter lookup can never
        alias (same mechanism as the fp/int8 root split above)."""
        if not namespace:
            return self._hash_root
        return hashlib.blake2b(self._hash_root + namespace,
                               digest_size=16).digest()

    def match_prefix(self, tokens, max_tokens: int | None = None,
                     count: bool = False,
                     namespace: bytes = b"") -> PrefixMatch:
        """Longest cached prefix of ``tokens`` at page granularity:
        full pages walked by the chained content hash, then the longest
        indexed partial continuation of the next page. Pure lookup —
        takes no references (callers ``acquire`` what they keep). Pass
        ``count=True`` to tally the hit counters (one tally per
        admission, not per probe). ``namespace`` scopes the walk to one
        adapter's chain (see ``_namespaced_root``)."""
        limit = len(tokens) if max_tokens is None else min(max_tokens,
                                                           len(tokens))
        m = PrefixMatch()
        if not self.cache_enabled or limit <= 0:
            return m
        ps = self.page_size
        tier = self.host_tier
        parent = self._namespaced_root(namespace)
        pos = 0
        while pos + ps <= limit:
            key = _page_hash(parent, tokens[pos:pos + ps])
            page = self._full_index.get(key)
            if page is None:
                break
            m.full_pages.append(page)
            parent = key
            pos += ps
        # host-tier continuation: keep walking the SAME content-hash
        # chain past the HBM break, accepting a page wherever it is
        # resolvable — HBM first (a mid-chain page can be HBM-resident
        # while an earlier one was evicted: eviction drops only its own
        # key), then the host tier. The keys are recorded, not pages:
        # restore_chain re-resolves each one at commit time.
        m.cached_tokens = pos
        if tier is not None:
            while pos + ps <= limit:
                key = _page_hash(parent, tokens[pos:pos + ps])
                if key in self._full_index:
                    pass
                elif tier.has(self._tier_tag, "full", key):
                    m.host_tokens += ps
                else:
                    break
                m.chain.append(key)
                parent = key
                pos += ps
        for q in range(min(limit - pos, ps - 1), 0, -1):
            key = _page_hash(parent, tokens[pos:pos + q])
            page = self._partial_index.get(key)
            if page is not None:
                m.partial_page, m.partial_len = page, q
                break
            if tier is not None and tier.has(self._tier_tag, "partial",
                                             key):
                m.host_partial_key, m.host_partial_len = key, q
                m.host_tokens += q
                break
        if not m.chain:
            m.cached_tokens += m.partial_len
        m.total_cached = pos + m.partial_len + m.host_partial_len
        if count:
            self.count_match(m)
        return m

    def count_match(self, m: PrefixMatch) -> None:
        self.counters["prefix_lookups"] += 1
        if m.hit:
            has_partial = (m.partial_page is not None
                           or m.host_partial_key is not None)
            self.counters["prefix_hits"] += 1
            self.counters["prefix_hit_pages"] += (
                len(m.full_pages) + len(m.chain) + (1 if has_partial else 0))
            if has_partial:
                self.counters["prefix_partial_hits"] += 1

    def register_prefix(self, tokens, pages: list[int],
                        include_partial: bool = True,
                        namespace: bytes = b"") -> int:
        """Index a request's materialized prefix: page i of ``pages``
        holds ``tokens[i*ps:(i+1)*ps]``. Full pages are registered under
        the chained hash; the trailing partial page (content frozen —
        callers register it only once no further writes can land, i.e.
        at release) under the partial index. The chunked engine calls
        this only when the FINAL prefill chunk lands (never for a
        prompt still streaming in chunks — a mid-prompt preemption must
        leave nothing indexed); the unchunked arm registers inside the
        admission loop right after the whole-suffix prefill. First
        writer wins: an existing index entry for the same content keeps
        its page. Pages must be held by the caller (refcount > 0);
        returns how many pages were newly registered."""
        if not self.cache_enabled:
            return 0
        ps = self.page_size
        n_full = min(len(tokens) // ps, len(pages))
        parent = self._namespaced_root(namespace)
        registered = 0
        for i in range(n_full):
            key = _page_hash(parent, tokens[i * ps:(i + 1) * ps])
            page = pages[i]
            if (key not in self._full_index and page not in self._page_key
                    and self._ref.get(page, 0) > 0
                    and page not in self._scrub_on_zero):
                self._full_index[key] = page
                self._page_key[page] = ("full", key)
                registered += 1
            parent = key  # the content chain continues either way
        q = len(tokens) - n_full * ps
        if include_partial and 0 < q < ps and n_full < len(pages):
            key = _page_hash(parent, tokens[n_full * ps:])
            page = pages[n_full]
            if (key not in self._partial_index and page not in self._page_key
                    and self._ref.get(page, 0) > 0
                    and page not in self._scrub_on_zero):
                self._partial_index[key] = page
                self._page_key[page] = ("partial", key)
                registered += 1
        self.counters["prefix_pages_registered"] += registered
        return registered

    def _deregister(self, page: int) -> None:
        kind_key = self._page_key.pop(page, None)
        if kind_key is None:
            return
        kind, key = kind_key
        index = self._full_index if kind == "full" else self._partial_index
        if index.get(key) == page:
            del index[key]

    # ---- host tier: spill on evict, restore on hit ----
    # (serving/tiering.py; SERVING.md "KV tiering & traffic harness").
    # All transfers here are host-side device_get/device_put around
    # functional .at[] updates — never inside a compiled program, so the
    # engine's decode/mixed program counts are untouched.

    def _spill(self, page: int) -> None:
        """Demote an LRU-evicted page's content to the host tier —
        called from ``alloc`` BEFORE deregistration, while the page's
        index key is still known. Quarantined content never spills:
        quarantine pulls its pages off the LRU and purges their index
        keys immediately, and the scrub-on-zero guard here covers any
        remaining window. Fault site ``serving.spill``: ``raise`` drops
        the spill (the page is simply lost, exactly as without a tier);
        ``poison`` corrupts the stored payload after the fact, so the
        restore-side digest re-verify MUST catch it."""
        tier = self.host_tier
        if tier is None:
            return
        kk = self._page_key.get(page)
        if kk is None or page in self._scrub_on_zero:
            return
        kind, key = kk
        if not tier.put(self._tier_tag, kind, key,
                        self._page_payload(page)):
            return
        from ..distributed import fault as _fault
        try:
            _fault.trip("serving.spill", step=self.fault_step,
                        path=key.hex(), page=page,
                        poison=lambda: tier.corrupt(self._tier_tag,
                                                    kind, key))
        except _fault.FaultInjected:
            tier.discard(self._tier_tag, kind, key)
            tier.counters["spill_dropped"] += 1
            return
        self.tracer.instant("spill", track="pool", page=page, kind=kind)
        self.tracer.bump("spills", 1, track="pool")

    def _page_parts(self, page: int) -> list:
        """One page's device slices in the host payload order: per layer
        k then v (quantized: codes then scales). The stacked pp layout
        iterates its layer dim so the payload format is IDENTICAL to the
        per-layer list — pp-portable by construction."""
        parts = []
        if self.stacked:
            (pk, pv), = self.pools
            for li in range(self.num_layers):
                for arr in (pk, pv):
                    if isinstance(arr, QuantizedKV):
                        parts.append(arr.q[li, page])
                        parts.append(arr.scale[li, page])
                    else:
                        parts.append(arr[li, page])
            return parts
        for pair in self.pools:
            for arr in pair:
                if isinstance(arr, QuantizedKV):
                    parts.append(arr.q[page])
                    parts.append(arr.scale[page])
                else:
                    parts.append(arr[page])
        return parts

    def _page_payload(self, page: int) -> list:
        """One page's bytes as host numpy arrays, per layer in pool
        order (k then v; a quantized pool interleaves codes and scales
        — spilling codes without scales would dequantize the restore
        with garbage). One batched device_get for the whole page."""
        parts = self._page_parts(page)
        if self.tp_degree > 1:
            # the device_get below collects every shard's kvh/tp heads
            # into the full logical page — the HostTier payload format
            # stays tp-portable (a tp=2 spill restores into tp=1)
            self.tracer.instant("shard_gather", track="pool", page=page,
                                tp=self.tp_degree, kind="spill")
        return [np.asarray(x) for x in jax.device_get(parts)]

    def export_pages(self, pages: list[int]) -> list[list[np.ndarray]]:
        """Export many pages' payloads with ONE batched device_get:
        returns one ``_page_payload``-format array list per page, in
        input order. This is the snapshot capture primitive
        (serving/snapshot.py) — a host-side transfer outside every
        compiled program, so ``step_program_counts()`` is untouched."""
        if not pages:
            return []
        parts = []
        for page in pages:
            parts.extend(self._page_parts(page))
        if self.tp_degree > 1:
            # shard-gather: snapshot payloads hold full logical pages,
            # so a tp=2 snapshot restores into a tp=1 engine (and back)
            self.tracer.instant("shard_gather", track="pool",
                                pages=len(pages), tp=self.tp_degree,
                                kind="snapshot")
        flat = [np.asarray(x) for x in jax.device_get(parts)]
        k = len(flat) // len(pages)
        return [flat[i * k:(i + 1) * k] for i in range(len(pages))]

    def _write_host_page(self, page: int, arrays) -> None:
        """device_put a host payload back into HBM page ``page`` (the
        inverse of ``_page_payload``, bit-exact: get/put round-trips
        bf16, fp32 and int8 bytes unchanged)."""
        self._scrubbed.discard(page)
        it = iter(arrays)
        if self.stacked:
            (pk, pv), = self.pools
            pair = [pk, pv]
            for li in range(self.num_layers):
                for i in range(2):
                    arr = pair[i]
                    if isinstance(arr, QuantizedKV):
                        q = jnp.asarray(next(it), arr.q.dtype)
                        s = jnp.asarray(next(it), arr.scale.dtype)
                        pair[i] = QuantizedKV(
                            arr.q.at[li, page].set(q),
                            arr.scale.at[li, page].set(s))
                    else:
                        pair[i] = arr.at[li, page].set(
                            jnp.asarray(next(it), arr.dtype))
            self.pools = [tuple(pair)]
            return
        def put(arr):
            if isinstance(arr, QuantizedKV):
                q = jnp.asarray(next(it), arr.q.dtype)
                s = jnp.asarray(next(it), arr.scale.dtype)
                return QuantizedKV(arr.q.at[page].set(q),
                                   arr.scale.at[page].set(s))
            return arr.at[page].set(jnp.asarray(next(it), arr.dtype))
        self._rewrite(put)

    def restore_charge(self, m: PrefixMatch | None) -> int:
        """Prefill-budget tokens the match's host-resolved tokens would
        cost to restore (the admission-time optimistic charge)."""
        if m is None or self.host_tier is None:
            return 0
        return self.host_tier.restore_charge(m.host_tokens)

    def restore_charge_tokens(self, restored_tokens: int) -> int:
        """Budget charge for tokens ACTUALLY restored (the post-commit
        number the engine mirrors into its own budget bookkeeping)."""
        if self.host_tier is None:
            return 0
        return self.host_tier.restore_charge(restored_tokens)

    def restore_chain(self, m: PrefixMatch) -> tuple[list[int], int]:
        """Map the continuation ``m.chain`` into HBM in chain order.
        Each key is re-resolved HBM-first — a page (re-)registered since
        the match, including by an earlier restore in this very loop,
        wins and is simply acquired (the restore-racing-re-registration
        rule) — else its payload is fetched from the host tier, written
        into a freshly-allocated page and registered under the key.
        Stops at the first failure (host miss, corrupt payload, injected
        ``serving.restore`` fault, pool exhaustion): the chain beyond it
        falls back to recompute. Returns ``(pages, restored_tokens)``;
        every returned page carries one reference for the caller."""
        pages: list[int] = []
        restored_tok = 0
        tier = self.host_tier
        from ..distributed import fault as _fault
        for key in m.chain:
            page = self._full_index.get(key)
            if page is not None:
                self.acquire([page])
                pages.append(page)
                continue
            if tier is None:
                break
            try:
                _fault.trip("serving.restore", step=self.fault_step,
                            path=key.hex(),
                            poison=lambda k=key: tier.corrupt(
                                self._tier_tag, "full", k))
            except _fault.FaultInjected:
                tier.counters["restore_failed"] += 1
                break
            arrays = tier.fetch(self._tier_tag, "full", key)
            if arrays is None:
                break
            try:
                page = self.alloc(1)[0]
            except PoolExhaustedError:
                break
            self._write_host_page(page, arrays)
            # first-writer-wins still holds: the key was absent from the
            # index at the top of this iteration and nothing since could
            # have inserted it (our own alloc only EVICTS entries)
            self._full_index[key] = page
            self._page_key[page] = ("full", key)
            nbytes = sum(a.nbytes for a in arrays)
            tier.on_restored(nbytes)
            restored_tok += self.page_size
            self.tracer.instant("restore", track="pool", page=page,
                                bytes=nbytes)
            self.tracer.bump("restores", 1, track="pool")
            pages.append(page)
        return pages, restored_tok

    def fetch_host_partial(self, m: PrefixMatch):
        """Fetch the match's host-tier partial payload (or None on
        miss/corruption/injected fault). Separate from
        ``restore_partial_into`` because the caller allocates the
        destination page between the two."""
        tier = self.host_tier
        if tier is None or m.host_partial_key is None:
            return None
        from ..distributed import fault as _fault
        key = m.host_partial_key
        try:
            _fault.trip("serving.restore", step=self.fault_step,
                        path=key.hex(),
                        poison=lambda: tier.corrupt(self._tier_tag,
                                                    "partial", key))
        except _fault.FaultInjected:
            tier.counters["restore_failed"] += 1
            return None
        return tier.fetch(self._tier_tag, "partial", key)

    def restore_partial_into(self, dst: int, arrays) -> None:
        """Restore a host partial payload straight into the hitter's
        first fresh suffix page: the copy-at-map COW rule with the copy
        sourced from host RAM. ``dst`` is private to the hitter and is
        NOT registered here — like a COW copy, it re-enters the index
        at release under its own (longer) key. Positions beyond the
        partial length were zero when the page spilled, so the
        masked-garbage-is-zero invariant rides through the round
        trip."""
        self._write_host_page(dst, arrays)
        nbytes = sum(np.asarray(a).nbytes for a in arrays)
        if self.host_tier is not None:
            self.host_tier.on_restored(nbytes)
        self.tracer.instant("restore", track="pool", page=dst,
                            bytes=nbytes, partial=True)
        self.tracer.bump("restores", 1, track="pool")

    def inject_prefix(self, tokens, payloads,
                      namespace: bytes = b"") -> int:
        """Write externally-held page payloads (a request snapshot —
        serving/snapshot.py) into the pool and register them under the
        chained content hash as refcount-0 CACHED pages, exactly as if
        a request with this prefix had just released them. Page i of
        ``payloads`` holds ``tokens[i*ps:(i+1)*ps]`` in
        ``_page_payload`` format; a trailing partial page (0 < q < ps
        tokens, zeros beyond) lands in the partial index. The ordinary
        admission path (``match_prefix`` + ``acquire`` + COW) then maps
        them — restore needs no new engine machinery, and an injected
        page LRU-evicted before its request re-admits degrades to a
        plain recompute, never a wrong token. First writer wins:
        content already indexed keeps its resident page (those tokens
        still count as injected — they are matchable). Stops early on
        pool exhaustion. Returns the matchable token count."""
        if not self.cache_enabled:
            return 0
        ps = self.page_size
        n_full = len(tokens) // ps
        parent = self._namespaced_root(namespace)
        injected = 0
        for i in range(min(n_full, len(payloads))):
            key = _page_hash(parent, tokens[i * ps:(i + 1) * ps])
            if key not in self._full_index:
                try:
                    page = self.alloc(1)[0]
                except PoolExhaustedError:
                    return injected
                self._write_host_page(page, payloads[i])
                self._full_index[key] = page
                self._page_key[page] = ("full", key)
                self.counters["prefix_pages_registered"] += 1
                self.release([page])   # registered + refcount 0 -> LRU
            parent = key
            injected += ps
        q = len(tokens) - n_full * ps
        if 0 < q < ps and n_full < len(payloads):
            key = _page_hash(parent, tokens[n_full * ps:])
            if key not in self._partial_index:
                try:
                    page = self.alloc(1)[0]
                except PoolExhaustedError:
                    return injected
                self._write_host_page(page, payloads[n_full])
                self._partial_index[key] = page
                self._page_key[page] = ("partial", key)
                self.counters["prefix_pages_registered"] += 1
                self.release([page])
            injected += q
        return injected

    # ---- device-side page ops ----

    def _rewrite(self, fn) -> None:
        """Replace every pool array by ``fn`` of it, one layer's entry (a
        K/V pair, a latent pool's one array) at a time.
        For the writers that stay eager (``cow_into``, ``rewind``, a
        host page's restore): each call builds a NEW array from the
        current one, so a whole new list built beside the old one would
        hold two pools at once, the memory the step programs no longer
        take since they write in place."""
        for i, pair in enumerate(self.pools):
            self.pools[i] = tuple(fn(a) for a in pair)

    def cow_into(self, src: int, dst: int) -> None:
        """Copy-on-write materialization: device-copy page ``src`` into
        the freshly-allocated page ``dst``. The cached source is never
        written in place — the hitter extends its own copy."""
        self._rewrite(lambda a: _page_copy(a, src, dst, self.stacked))
        self.counters["prefix_cow_copies"] += 1
        self.tracer.instant("cow_copy", track="pool", src=src, dst=dst)

    def scrub(self, pages: list[int]) -> None:
        """Zero pages (eviction / quarantine): restores the
        masked-garbage-is-zero invariant before reuse. One compiled
        program of one shape that writes the donated pool in place: an
        eviction inside a serving window costs a dispatch, neither a
        compile nor a copy of the pool."""
        pages = sorted(set(int(p) for p in pages))
        for i in range(0, len(pages), _SCRUB_WIDTH):
            part = pages[i:i + _SCRUB_WIDTH]
            idx = np.asarray(part + part[:1] * (_SCRUB_WIDTH - len(part)),
                             np.int32)
            self.pools = _scrub_in_place(self.pools, idx, self.stacked)
        self._scrubbed.update(pages)

    def warm_scrub(self) -> None:
        """Compile the scrub beside the step programs (``ServingEngine.
        warm_programs``) by zeroing the reserved scratch page 0."""
        self.pools = _scrub_in_place(
            self.pools, np.zeros(_SCRUB_WIDTH, np.int32), self.stacked)

    def rewind(self, pages: list[int], start: int, stop: int) -> None:
        """Zero cache POSITIONS ``[start, stop)`` of a request's block
        table (token-granular, unlike page-granular ``scrub``): the
        speculative rollback primitive. The verify step writes draft KV
        optimistically at positions ``context_len..context_len+n_draft``;
        the compiled step zeroes rejected rows in-program, and the
        engine calls this for the host-side cases (accepted-but-unused
        tail when eos/length lands inside the accept window) so a
        partial-page tail never leaves garbage beyond the request's
        ``context_len`` — masked-garbage-is-zero, preserved at token
        granularity. Pages written speculatively are always private to
        the request (shared full pages are immutable; COW copies partial
        heads), so zeroing here can never damage another request's KV."""
        if stop <= start:
            return
        ps = self.page_size
        pg = jnp.asarray([pages[p // ps] for p in range(start, stop)],
                         jnp.int32)
        off = jnp.asarray([p % ps for p in range(start, stop)], jnp.int32)
        self._rewrite(lambda a: self._pos_zero(a, pg, off, self.stacked))
        self.counters["rewound_tokens"] += stop - start

    @staticmethod
    def _pos_zero(arr, pages, offs, stacked: bool = False):
        """Zero individual (page, offset) rows; QuantizedKV zeroes codes
        AND scales (same reasoning as ``_page_zero``). ``stacked``
        addresses the pipeline layout's ``[L, pages, ...]`` arrays —
        the zero spans every layer, like the per-layer loop."""
        if isinstance(arr, QuantizedKV):
            return QuantizedKV(
                KVCachePool._pos_zero(arr.q, pages, offs, stacked),
                KVCachePool._pos_zero(arr.scale, pages, offs, stacked))
        if stacked:
            return arr.at[:, pages, offs].set(0)
        return arr.at[pages, offs].set(0)

    # ---- invariant audit ----

    def audit(self, block_tables=None, check_device: bool = True,
              slots=None) -> dict:
        """Invariant checker for the pool's host-side accounting —
        called from serving test teardowns and the faults-marked chaos
        suites, so every chaos scenario proves it left the pool
        consistent, not just that the streams came out right. Raises
        AssertionError listing every violated invariant:

        - free-list hygiene: no duplicates, never the scratch page,
          disjoint from held (refcount > 0) and cached (LRU) pages;
        - conservation: free ∪ cached ∪ held covers every allocatable
          page exactly once;
        - refcounts: strictly positive, and — given ``block_tables``
          (one page list per live request) — equal to the number of
          holders per page, with no held page missing a holder;
        - index agreement: ``_page_key`` and the full/partial indexes
          are exact inverses, an indexed page is never free, an LRU
          page is always registered, and a quarantined (scrub-on-zero)
          page is held and never indexed;
        - scrubbed-means-zero (``check_device``): every free page the
          pool believes it scrubbed reads back all-zero on device —
          codes AND scales in int8 mode (a NaN can't hide: NaN != 0).

        Returns a small accounting dict when everything holds."""
        problems: list[str] = []
        free_list = self._free
        free = set(free_list)
        cached = set(self._lru)
        held = set(self._ref)
        all_pages = set(range(1, self.num_pages))
        if len(free) != len(free_list):
            problems.append("duplicate pages on the free list")
        if 0 in free or 0 in cached or 0 in held:
            problems.append("scratch page 0 entered the accounting")
        for a, b, name in ((free, cached, "free∩cached"),
                           (free, held, "free∩held"),
                           (cached, held, "cached∩held")):
            both = a & b
            if both:
                problems.append(f"{name} not disjoint: {sorted(both)}")
        union = free | cached | held
        if union != all_pages:
            missing = sorted(all_pages - union)
            extra = sorted(union - all_pages)
            problems.append(f"page conservation broken: leaked={missing} "
                            f"phantom={extra}")
        for p, r in self._ref.items():
            if r <= 0:
                problems.append(f"page {p} held with refcount {r} <= 0")
        if block_tables is not None:
            holders: dict[int, int] = {}
            for table in block_tables:
                for p in table:
                    holders[p] = holders.get(p, 0) + 1
            for p, r in self._ref.items():
                if holders.get(p, 0) != r:
                    problems.append(
                        f"page {p} refcount {r} != {holders.get(p, 0)} "
                        f"block-table holders")
            for p in holders:
                if p not in self._ref:
                    problems.append(
                        f"page {p} appears in a block table but holds "
                        f"no reference")
        for page, (kind, key) in self._page_key.items():
            index = (self._full_index if kind == "full"
                     else self._partial_index)
            if index.get(key) != page:
                problems.append(
                    f"page {page} claims {kind} key {key.hex()[:8]} but "
                    f"the index maps it to {index.get(key)}")
            if page in free:
                problems.append(f"registered page {page} is on the "
                                f"free list")
        for kind, index in (("full", self._full_index),
                            ("partial", self._partial_index)):
            for key, page in index.items():
                if self._page_key.get(page) != (kind, key):
                    problems.append(
                        f"{kind} index entry {key.hex()[:8]} -> {page} "
                        f"has no matching _page_key back-pointer")
        for p in cached:
            if p not in self._page_key:
                problems.append(f"cached (LRU) page {p} is not "
                                f"registered in any index")
        for p in self._scrub_on_zero:
            if p not in held:
                problems.append(f"scrub-on-zero page {p} has no holder "
                                f"(should have been scrubbed+freed)")
            if p in self._page_key:
                problems.append(f"quarantined page {p} is still in the "
                                f"prefix index")
        if check_device:
            zeroed = sorted(free & self._scrubbed)
            if zeroed:
                idx = jnp.asarray(zeroed, jnp.int32)

                def _sel(arr):
                    # stacked pp layout: pages live on dim 1, and one
                    # slice covers every layer at once
                    return arr[:, idx] if self.stacked else arr[idx]
                for li, pair in enumerate(self.pools):
                    for name, arr in zip(("k", "v") if len(pair) == 2
                                         else ("latent",), pair):
                        if isinstance(arr, QuantizedKV):
                            ok = (bool(jnp.all(_sel(arr.q) == 0))
                                  and bool(jnp.all(_sel(arr.scale) == 0)))
                        else:
                            ok = bool(jnp.all(_sel(arr) == 0))
                        if not ok:
                            problems.append(
                                f"scrubbed free page holds nonzero "
                                f"{name} content in layer {li}")
                    if problems and problems[-1].startswith("scrubbed"):
                        break   # one layer's evidence is enough
        if self.state and slots is not None and slots != self.state_slots:
            # ``slots``: slot -> request id of every running request
            problems.append(f"state rows held {self.state_slots} but the "
                            f"running slots are {slots}")
        if self.state and check_device:
            live = jnp.asarray(sorted(self.state_slots), jnp.int32)
            for li, arrays in enumerate(self.state):
                if not all(bool(jnp.all(jnp.isfinite(a[live].astype(
                        jnp.float32)))) for a in arrays):
                    problems.append(f"a live slot's state is not finite "
                                    f"in state layer {li}")
        if problems:
            raise AssertionError(
                "KV pool audit failed:\n- " + "\n- ".join(problems))
        out = {"pages": self.num_pages - 1, "free": len(free),
               "cached": len(cached), "held": len(held)}
        if self.state:
            out["state_slots"] = len(self.state_slots)
        return out
