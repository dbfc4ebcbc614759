"""Device-side sharded page arena: UniMem distributed over the `mem` axis.

The single pooled arena of `serve/kv_cache.py`, cut into per-device
banks (DESIGN.md §2): the K/V page leaves are laid out as
(layers, n * (pages_per_shard + 1), page, hkv, hd) and sharded over the
page-slot axis, so device s holds the contiguous physical bank
[s*(pps+1), (s+1)*(pps+1)) — its resident pages plus its OWN null slot
(every shard needs a local write/gather sink for tokens other shards
own).  Pool page ids are blocked to match: page g lives on shard
g // pps at local slot g % pps; the engine-visible null sentinel is
`num_pages`, which no shard owns, so the in-step translation maps it to
every shard's local null.

Non-page leaves (hybrid's per-slot conv/SSM state) are REPLICATED: the
batch is broadcast anyway and the recurrent state update is a pure
function of it, so every shard carries identical copies — nothing to
reduce, nothing to migrate on fork.

Device-side page copies (COW) go through jitted helpers with pinned
output shardings: an eager `.at[].set()` would silently drop the
placement and re-gather the whole arena onto one device.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.unimem import ShardedUniMemPool, is_page_leaf
from repro.launch.mesh import MEM_AXIS
from repro.serve.kv_cache import STATE_SLOT_AXIS, PagedKVArena


@dataclass
class ShardedPagedKVArena(PagedKVArena):
    """PagedKVArena whose page banks live one-per-device on `mesh`'s
    "mem" axis.  `num_pages` is the GLOBAL pool size (must divide over
    the axis); the device arrays carry one extra null slot PER SHARD.

    Pages never migrate between banks — this includes persistent
    prefix-cache pages, which keep the bank (and hence the shard
    rotation) of the request that originally wrote them even after that
    request retires.  A follower hitting the cache therefore ADOPTS the
    donor's rotation (engine `_match_prefix`), so the jitted walk's
    rotation recovery from `block_table[:, 0] // pps` stays exact, and
    a cold page restored from the host tier reallocates at its original
    `rotation + index` stride to land back on the same bank."""
    mesh: Mesh = None
    _copy_page_jit: object = field(default=None, repr=False, compare=False)
    _copy_state_jit: object = field(default=None, repr=False, compare=False)
    _write_page_jit: object = field(default=None, repr=False, compare=False)

    def __post_init__(self):
        assert self.mesh is not None and MEM_AXIS in self.mesh.axis_names
        n = self.num_shards
        if self.num_pages % n:
            raise ValueError(f"num_pages {self.num_pages} must divide over "
                             f"{n} shards")
        pps = self.num_pages // n

        def sharding(name):
            return NamedSharding(self.mesh, P(None, MEM_AXIS)
                                 if is_page_leaf(name) else P())

        if self.kv is None:
            # built in place, bank by bank: the whole arena never sits
            # on one device
            from repro.models import registry
            fam = registry.get_family(self.cfg)
            init = functools.partial(fam.init_paged_cache, self.cfg,
                                     n * (pps + 1), self.page_size,
                                     self.max_batch)
            shapes = jax.eval_shape(init)
            self.kv = jax.jit(init, out_shardings={
                name: sharding(name) for name in shapes})()
        self.kv = {name: jax.device_put(a, sharding(name))
                   for name, a in self.kv.items()}
        if self.pool is None:
            self.pool = ShardedUniMemPool(self.num_pages, self.page_size,
                                          num_shards=n)

    @property
    def num_shards(self) -> int:
        return self.mesh.shape[MEM_AXIS]

    @property
    def pages_per_shard(self) -> int:
        return self.num_pages // self.num_shards

    def phys_slot(self, page: int) -> int:
        """Device-array slot of pool page id `page`: each shard's bank is
        its pages_per_shard resident slots plus its local null slot."""
        pps = self.pages_per_shard
        if page == self.null_page:            # sentinel -> shard 0's null
            return pps
        return (page // pps) * (pps + 1) + page % pps

    @property
    def page_bytes(self) -> int:
        kv = sum(int(a.size) * a.dtype.itemsize
                 for n, a in self.kv.items() if is_page_leaf(n))
        return kv // (self.num_shards * (self.pages_per_shard + 1))

    def shard_kv_bytes(self) -> list[int]:
        """Per-device bytes of the page leaves actually resident on each
        shard (from the arrays' own placement, not arithmetic)."""
        n = self.num_shards
        totals = [0] * n
        for name, a in self.kv.items():
            if not is_page_leaf(name):
                continue
            for i, s in enumerate(a.addressable_shards):
                totals[i % n] += int(s.data.size) * s.data.dtype.itemsize
        return totals

    def _shardings(self):
        return {name: a.sharding for name, a in self.kv.items()}

    def copy_page(self, src: int, dst: int) -> None:
        """COW page copy.  src and dst serve the same logical index, so
        the strided allocator placed them on the SAME shard — the copy
        never crosses the interconnect."""
        if self._copy_page_jit is None:
            def f(kv, ps, pd):
                return {name: (a.at[:, pd].set(
                            jax.lax.dynamic_index_in_dim(a, ps, 1,
                                                         keepdims=False))
                               if is_page_leaf(name) else a)
                        for name, a in kv.items()}
            self._copy_page_jit = jax.jit(f, out_shardings=self._shardings())
        self.kv = self._copy_page_jit(self.kv, jnp.int32(self.phys_slot(src)),
                                      jnp.int32(self.phys_slot(dst)))

    def write_page(self, page: int, data: dict) -> None:
        """Host-tier restore write-back, sharded: one jitted setter with
        pinned output shardings (the eager `.at[].set` of the base class
        would silently re-gather the banks onto one device).  One
        compiled shape regardless of parcel size — the engine loops it
        per page."""
        if self._write_page_jit is None:
            def f(kv, slot, payload):
                return {name: (a.at[:, slot].set(
                                   payload[name].astype(a.dtype))
                               if name in payload else a)
                        for name, a in kv.items()}
            self._write_page_jit = jax.jit(f, out_shardings=self._shardings())
        payload = {n: jnp.asarray(v) for n, v in data.items()}
        self.kv = self._write_page_jit(
            self.kv, jnp.int32(self.phys_slot(page)), payload)

    def copy_slot_state(self, src_slot: int, dst_slot: int) -> None:
        """fork() state copy on the REPLICATED non-page leaves."""
        if self.state_bytes == 0:
            return
        if self._copy_state_jit is None:
            def f(kv, src, dst):
                out = {}
                for name, a in kv.items():
                    if is_page_leaf(name):
                        out[name] = a
                    else:
                        row = jax.lax.dynamic_index_in_dim(
                            a, src, STATE_SLOT_AXIS, keepdims=False)
                        idx = (slice(None),) * STATE_SLOT_AXIS
                        out[name] = a.at[idx + (dst,)].set(row)
                return out
            self._copy_state_jit = jax.jit(f, out_shardings=self._shardings())
        self.kv = self._copy_state_jit(self.kv, jnp.int32(src_slot),
                                       jnp.int32(dst_slot))
