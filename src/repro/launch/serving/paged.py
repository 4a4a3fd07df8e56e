"""Block-table paged KV cache — the storage layer of the serving tier.

KV memory is one physical pool per attention-cache leaf, carved into
fixed-size pages:

    pool["segN"][kind]["k"] : (layers, n_pages, page_size, kv_heads, hd)

A request owns an ordered list of page ids (its *block table*); logical
cache position ``p`` lives at page ``pages[p // page_size]``, offset
``p % page_size``.  Allocation and release are O(pages) free-list moves
on the host (:class:`PagePool`), so requests of wildly different lengths
share the pool without fragmentation — the whole point of paging.

A decode step reads straight from the pools: each layer of the model's
``paged_decode_step`` gathers its own lanes' pages by block table
(``models.layers.read_pages``), and the one KV row per lane and layer
it appends is written back to its physical page (:func:`scatter_token`).
The block table and lengths are runtime data, so page churn never
recompiles.  On the chip's path the serving runners donate the pools,
so the decode step and the prefill store (:func:`store_prefill`) both
update them in place.

Physical page 0 is reserved as the *sink*: idle decode lanes point their
block tables at it, and the garbage KV their dispatches produce lands
there instead of in live pages.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax.numpy as jnp
from jax import lax

from ...configs.base import ModelConfig
from ...models import transformer

#: block-table entry for slots past a request's last page (and for every
#: slot of an idle lane) — all of them alias the sink page
SINK_PAGE = 0


class PagePool:
    """Host-side free-list over physical page ids (page 0 = sink)."""

    def __init__(self, n_pages: int, page_size: int):
        if n_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is the sink)")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.n_pages = n_pages
        self.page_size = page_size
        # LIFO so recently-freed (cache-warm) pages are reused first
        self._free = list(range(n_pages - 1, 0, -1))

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def capacity(self) -> int:
        """Usable pages (the sink is never allocatable)."""
        return self.n_pages - 1

    def pages_for(self, n_tokens: int) -> int:
        """Pages needed to hold ``n_tokens`` cache positions (>= 1)."""
        return max(1, -(-n_tokens // self.page_size))

    def alloc(self, n: int) -> Optional[List[int]]:
        """Pop ``n`` pages, or None (and no change) if the pool is short."""
        if n > len(self._free):
            return None
        return [self._free.pop() for _ in range(n)]

    def free(self, pages: List[int]) -> None:
        for p in pages:
            if not 0 < p < self.n_pages:
                raise ValueError(f"bad page id {p}")
            if p in self._free:
                raise ValueError(f"double free of page {p}")
            self._free.append(p)


def pool_init(cfg: ModelConfig, n_pages: int, page_size: int) -> Dict:
    """Physical KV pools mirroring ``transformer.cache_init``'s structure
    (one {"k", "v"} leaf pair per segment x layer-kind, layers stacked)."""
    if cfg.family not in ("dense", "moe"):
        raise NotImplementedError(
            f"paged KV serves attention families only, not {cfg.family!r} "
            "(SSM state is not paged)"
        )
    kv, hd, dt = cfg.n_kv_heads, cfg.hd, cfg.param_dtype
    pools: Dict = {}
    for si, (pattern, count) in enumerate(transformer.segment_plan(cfg)):
        pools[f"seg{si}"] = {
            kind: {
                "k": jnp.zeros((count, n_pages, page_size, kv, hd), dt),
                "v": jnp.zeros((count, n_pages, page_size, kv, hd), dt),
            }
            for kind in pattern
        }
    return pools


def scatter_token(pools: Dict, rows: Dict, block_table, lens,
                  page_size: int) -> Dict:
    """Write each lane's new KV row, of every layer, to its page.

    ``rows`` mirror ``pools`` with (layers, lanes, kv, hd) leaves: the rows
    a decode step appended at position ``lens`` (the pre-step cache
    length), which physically lives at page ``block_table[lane, lens //
    page_size]`` offset ``lens % page_size``.  Idle lanes (lens=0,
    all-sink tables) write their garbage onto the sink page; duplicate
    sink indices are resolved arbitrarily, which is fine — nothing reads
    the sink.
    """
    lane = jnp.arange(block_table.shape[0])
    page_of = block_table[lane, lens // page_size]  # (lanes,)
    off = lens % page_size
    return {
        seg: {
            kind: {
                name: pv[name].at[:, page_of, off].set(rows[seg][kind][name])
                for name in ("k", "v")
            }
            for kind, pv in kinds.items()
        }
        for seg, kinds in pools.items()
    }


def store_prefill(pools: Dict, caches: Dict, page_ids, page_size: int) -> Dict:
    """Copy a batch-1 prefill cache into physical pages.

    ``caches`` is the dense cache a ``max_len = len(page_ids) * page_size``
    prefill produced; page ``j`` of it (positions ``[j*ps, (j+1)*ps)``)
    lands on physical page ``page_ids[j]``.  Positions past the prompt's
    true length hold pad KV — harmless, because a position is only ever
    attended once ``cache_len`` exceeds it, and decode overwrites it with
    the real token's KV before that happens.
    """

    def body(pl, xs):
        j, pid = xs
        new: Dict = {}
        for seg, kinds in pl.items():
            new[seg] = {}
            for kind, pv in kinds.items():
                c = caches[seg][kind]

                def src(arr):  # (L, 1, max_len, kv, hd) -> (L, page, kv, hd)
                    return lax.dynamic_slice_in_dim(
                        arr[:, 0], j * page_size, page_size, axis=1
                    )

                new[seg][kind] = {
                    "k": pv["k"].at[:, pid].set(src(c["k"])),
                    "v": pv["v"].at[:, pid].set(src(c["v"])),
                }
        return new, None

    n = page_ids.shape[0]
    pools, _ = lax.scan(body, pools, (jnp.arange(n), page_ids))
    return pools
