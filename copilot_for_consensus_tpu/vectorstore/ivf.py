"""Two-tier IVF index over the TPU vector store's HBM matrix.

The sub-linear retrieval stage ROADMAP item 1 asks for: instead of
scoring the whole ``[capacity, dim]`` matrix per query (exact flat
search, O(corpus)), a k-means coarse quantizer routes each query to
``nprobe`` posting lists and only those lists' rows are rescored
exactly against the SAME HBM matrix the flat route scores. The index
therefore adds only int32 posting lists and a small centroid matrix on
top of the store's one long-lived vector allocation — upserts/deletes
keep mutating the matrix exactly as the flat route does, and the lists
only say *where to look*.

Layout (the PR-15 per-dp-shard allocator pattern, applied to lists):

* centroids live as ``[nlist_padded, dim]`` f32, posting lists as
  ``[nlist_padded, pad]`` int32 global row ids (``-1`` = empty slot);
  both are sharded over the mesh's ``dp`` axis when a mesh is given —
  shard ``s`` owns slot rows ``[s*sps, (s+1)*sps)``, and a host-side
  :class:`ListShardAllocator` (LPT greedy over list sizes) decides
  which k-means list lands in which shard's slots so row totals
  balance.
* the fused search dispatch runs per shard (``shard_map`` over dp):
  centroid scores → top-``nprobe`` local lists → gather candidate row
  ids → gather candidate vectors from the (replicated) matrix → exact
  rescore → shard-local top-k. Outputs stack ``[B, k]`` per shard into
  ``[B, dp*k]`` with NO collective — the cross-shard top-k reduction
  happens on host over ``dp*k`` candidates per query (k ≪ corpus, so
  the host merge is noise).
* rows added after a (re)train append into a SPILL block — a sharded
  ``[spill_cap]`` int32 id list scored exactly on every query — so
  ``add_embeddings`` never blocks on an index rebuild; the spill folds
  into posting lists at the next retrain.

Retrain policy (lazy, checked on the query path, never on ingest):

* first train once the live corpus reaches ``min_train`` rows;
* retrain when the spill fraction (spill rows / live rows) crosses
  ``spill_fraction`` — this is also how centroid-imbalance drift
  surfaces, because a list that outgrows its padded capacity
  overflows into the spill;
* retrain when the corpus outgrows the trained size by
  ``growth_factor`` (nlist is re-picked from the new corpus size).

Import stays jax-free (the analysis CLI imports the vectorstore
package on machines without jax); all device work is lazy.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Sequence

import numpy as np

#: queries rescored together inside the fused search (lax.map
#: batch_size): bounds the candidate working set to
#: [_RESCORE_GROUP, C, dim] while amortizing per-query dispatch —
#: 1 serializes the batch (10x batched-QPS loss measured at 1M), the
#: full batch materializes [B, C, dim] (512MB at B=64, C=32k, dim=64)
_RESCORE_GROUP = 8


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (>=1)."""
    return 1 << max(0, int(n - 1).bit_length()) if n > 1 else 1


@dataclass
class IVFParams:
    """Tuning knobs; every field has a serving-sane default."""

    nlist: int = 0              # 0 = auto: ~sqrt(n), pow2, in [8, 4096]
    nprobe: int = 8             # lists probed per shard per query
    train_size: int = 65536     # k-means sample = first N live rows
    min_train: int = 256        # corpus size that triggers first train
    kmeans_iters: int = 8
    spill_fraction: float = 0.25   # spill/live ratio forcing a retrain
    growth_factor: float = 2.0     # corpus growth forcing a retrain
    pad_factor: float = 4.0        # list capacity ≈ pad_factor * mean
    seed: int = 0

    @staticmethod
    def from_config(cfg: dict) -> "IVFParams":
        p = IVFParams()
        for f, cast in (("nlist", int), ("nprobe", int),
                        ("train_size", int), ("min_train", int),
                        ("kmeans_iters", int), ("spill_fraction", float),
                        ("growth_factor", float), ("pad_factor", float),
                        ("seed", int)):
            key = f"ivf_{f}"
            if key in cfg:
                setattr(p, f, cast(cfg[key]))
        return p


class ListShardAllocator:
    """Assign posting lists to dp shards balancing row totals.

    The PR-15 block-pool discipline applied to lists: the host owns
    placement, the device sees per-shard slot ranges. LPT greedy
    (largest list first, onto the shard with the least rows that still
    has a free slot) keeps per-shard scan work within ~2x of perfect
    balance; every shard gets exactly ``slots_per_shard`` slots so the
    slot axis divides evenly over dp — the divisibility contract the
    shardcheck case declares.
    """

    def __init__(self, num_shards: int, nlist: int):
        self.num_shards = int(num_shards)
        self.slots_per_shard = max(
            1, math.ceil(nlist / max(1, num_shards)))

    def assign(self, sizes: np.ndarray) -> np.ndarray:
        """``sizes[l]`` = rows in list l → global device slot per list.

        Shard s owns slots ``[s*sps, (s+1)*sps)``; unassigned slots are
        padding (zero centroid, all-empty list).
        """
        sps = self.slots_per_shard
        order = np.argsort(-sizes, kind="stable")
        load = np.zeros(self.num_shards, dtype=np.int64)
        used = np.zeros(self.num_shards, dtype=np.int64)
        slot_of_list = np.full(len(sizes), -1, dtype=np.int64)
        for l in order:
            open_shards = np.flatnonzero(used < sps)
            s = open_shards[np.argmin(load[open_shards])]
            slot_of_list[l] = s * sps + used[s]
            used[s] += 1
            load[s] += sizes[l]
        return slot_of_list


class IVFIndex:
    """The device-side index: centroids + posting lists + spill block.

    Holds GLOBAL row ids only; candidate vectors gather from the
    store's HBM matrix at query time, so the store's single vector
    allocation stays the one source of truth for every byte of vector
    data (upserted vectors rescore correctly even before the index
    catches up, because the rescore reads the live matrix).
    """

    def __init__(self, dim: int, params: IVFParams | None = None,
                 mesh: Any = None):
        self.dim = int(dim)
        self.params = params or IVFParams()
        self.mesh = mesh
        self.num_shards = (int(mesh.shape["dp"])
                           if mesh is not None else 1)
        self.trained = False
        self.generation = 0
        self.nlist = 0               # real (unpadded) list count
        self.pad = 0                 # per-list slot capacity
        self.sps = 0                 # list slots per shard
        self.trained_at_n = 0
        self.overflow_count = 0      # rows a full list pushed to spill
        self.centroids_np: np.ndarray | None = None  # [nlist, dim]
        self._locator: dict[int, tuple] = {}  # row -> ("l",slot,off)|("s",pos)
        self._d_centroids = None     # [nlist_padded, dim] f32 (dp)
        self._d_rowids = None        # [nlist_padded, pad] i32 (dp)
        self._d_spill = None         # [spill_cap] i32 (dp)
        self._spill_n = 0            # high-water append cursor
        self._spill_live = 0
        self._indexed_live = 0
        self._kmeans_fn = None
        self._assign_fn = None
        self._search_fn = None
        self._patch1d_fn = None
        self._patch2d_fn = None

    # -- lazy jax ------------------------------------------------------

    def _jax(self):
        import jax
        import jax.numpy as jnp
        return jax, jnp

    def _put(self, arr: np.ndarray, spec_axes: tuple):
        """device_put, sharded over dp when a mesh is present."""
        jax, _ = self._jax()
        if self.mesh is None:
            return jax.device_put(arr)
        from jax.sharding import NamedSharding, PartitionSpec
        return jax.device_put(
            arr, NamedSharding(self.mesh, PartitionSpec(*spec_axes)))

    # -- sizing --------------------------------------------------------

    def auto_nlist(self, n: int) -> int:
        if self.params.nlist:
            return min(self.params.nlist, max(1, n))
        return max(8, min(4096, next_pow2(int(math.sqrt(max(1, n))))))

    @property
    def live_count(self) -> int:
        return self._indexed_live + self._spill_live

    def spill_frac(self) -> float:
        live = self.live_count
        return (self._spill_live / live) if live else 0.0

    def needs_retrain(self, live_n: int) -> bool:
        if not self.trained:
            return live_n >= self.params.min_train
        if live_n < 1:
            return False
        if self.spill_frac() > self.params.spill_fraction:
            return True
        return live_n >= self.params.growth_factor * self.trained_at_n

    def max_candidates(self, nprobe: int | None = None) -> int:
        """Rows one query can reach — the escalation ceiling: probed
        list capacity plus the whole spill block, summed over shards."""
        if not self.trained:
            return 0
        npb = min(nprobe if nprobe is not None else self.params.nprobe,
                  self.sps)
        spill_cap = (int(self._d_spill.shape[0])
                     if self._d_spill is not None else 0)
        return self.num_shards * npb * self.pad + spill_cap

    # -- training ------------------------------------------------------

    def _kmeans(self, X: np.ndarray, K: int) -> np.ndarray:
        """Lloyd iterations on device over unit vectors (cosine =
        dot). The sample is truncated to a power of two so repeated
        retrains at drifting corpus sizes reuse one compiled step."""
        jax, jnp = self._jax()
        if self._kmeans_fn is None:
            def step(X, C):
                a = jnp.argmax(X @ C.T, axis=1)
                sums = jnp.zeros_like(C).at[a].add(X)
                cnt = jnp.zeros((C.shape[0],), jnp.float32).at[a].add(1.0)
                newc = jnp.where(cnt[:, None] > 0,
                                 sums / jnp.maximum(cnt[:, None], 1.0), C)
                norm = jnp.linalg.norm(newc, axis=1, keepdims=True)
                return newc / jnp.maximum(norm, 1e-30)
            self._kmeans_fn = jax.jit(step)
        m = min(len(X), self.params.train_size)
        m = max(K, 1 << (m.bit_length() - 1))  # pow2 <= m, >= K
        sample = X[:m]
        rng = np.random.default_rng(self.params.seed)
        init = sample[rng.permutation(m)[:K]].astype(np.float32)
        Xd = jax.device_put(sample.astype(np.float32))
        C = jax.device_put(init)
        for _ in range(self.params.kmeans_iters):
            C = self._kmeans_fn(Xd, C)
        return np.asarray(C)

    def _assign_all(self, X: np.ndarray, C: np.ndarray) -> np.ndarray:
        """Nearest-centroid assignment, chunked so one compiled
        program covers any corpus size."""
        jax, jnp = self._jax()
        if self._assign_fn is None:
            def assign(Xc, C):
                return jnp.argmax(Xc @ C.T, axis=1)
            self._assign_fn = jax.jit(assign)
        chunk = 8192
        Cd = jax.device_put(C.astype(np.float32))
        out = np.empty(len(X), dtype=np.int64)
        for lo in range(0, len(X), chunk):
            hi = min(lo + chunk, len(X))
            block = X[lo:hi].astype(np.float32)
            if hi - lo < chunk:  # pad the tail; pad rows are discarded
                block = np.concatenate(
                    [block, np.zeros((chunk - (hi - lo), X.shape[1]),
                                     np.float32)])
            out[lo:hi] = np.asarray(
                self._assign_fn(jax.device_put(block), Cd))[:hi - lo]
        return out

    def rebuild(self, host: np.ndarray, rows: Sequence[int],
                centroids: np.ndarray | None = None) -> None:
        """(Re)train on the live corpus and rebuild every device
        buffer: k-means (or the given centroids — the persistence
        path), full reassignment, allocator placement, spill fold."""
        rows = np.asarray(list(rows), dtype=np.int64)
        n = len(rows)
        if n == 0:
            self.trained = False
            self._locator.clear()
            self._d_centroids = self._d_rowids = self._d_spill = None
            self._spill_n = self._spill_live = self._indexed_live = 0
            return
        X = host[rows].astype(np.float32)
        if centroids is None:
            K = self.auto_nlist(n)
            K = min(K, n)
            centroids = self._kmeans(X, K)
        else:
            centroids = np.asarray(centroids, dtype=np.float32)
        K = centroids.shape[0]
        assign = self._assign_all(X, centroids)
        sizes = np.bincount(assign, minlength=K)
        mean = max(1, n // K)
        cap = max(8, int(self.params.pad_factor * mean))
        pad = next_pow2(min(int(sizes.max()) if n else 1, cap))
        alloc = ListShardAllocator(self.num_shards, K)
        slot_of_list = alloc.assign(sizes)
        sps = alloc.slots_per_shard
        lp = self.num_shards * sps
        rowids_np = np.full((lp, pad), -1, dtype=np.int32)
        cents_np = np.zeros((lp, self.dim), dtype=np.float32)
        cents_np[slot_of_list] = centroids
        self._locator.clear()
        fill = np.zeros(K, dtype=np.int64)
        spill_rows: list[int] = []
        for i in range(n):
            l = int(assign[i])
            r = int(rows[i])
            c = int(fill[l])
            if c < pad:
                slot = int(slot_of_list[l])
                rowids_np[slot, c] = r
                self._locator[r] = ("l", slot, c)
                fill[l] = c + 1
            else:  # imbalance overflow: exact-scored via the spill
                spill_rows.append(r)
        self.overflow_count = len(spill_rows)
        self._indexed_live = n - len(spill_rows)
        self.nlist, self.pad, self.sps = K, pad, sps
        self.centroids_np = centroids
        self._d_centroids = self._put(cents_np, ("dp", None))
        self._d_rowids = self._put(rowids_np, ("dp", None))
        self._rebuild_spill(spill_rows)
        self.trained = True
        self.trained_at_n = n
        self.generation += 1

    def _rebuild_spill(self, spill_rows: list[int]) -> None:
        per_shard = next_pow2(max(
            64, math.ceil(2 * max(1, len(spill_rows)) / self.num_shards)))
        cap = self.num_shards * per_shard
        arr = np.full(cap, -1, dtype=np.int32)
        for pos, r in enumerate(spill_rows):
            arr[pos] = r
            self._locator[r] = ("s", pos)
        self._d_spill = self._put(arr, ("dp",))
        self._spill_n = len(spill_rows)
        self._spill_live = len(spill_rows)

    # -- incremental maintenance --------------------------------------

    def _patches(self):
        jax, jnp = self._jax()
        if self._patch1d_fn is None:
            def patch1d(buf, pos, vals):
                return buf.at[pos].set(vals)

            def patch2d(buf, slots, offs, vals):
                return buf.at[slots, offs].set(vals)
            self._patch1d_fn = jax.jit(patch1d, donate_argnums=(0,))
            self._patch2d_fn = jax.jit(patch2d, donate_argnums=(0,))
        return self._patch1d_fn, self._patch2d_fn

    @staticmethod
    def _bucket(arrs: list[np.ndarray]) -> list[np.ndarray]:
        """Pad index/value arrays to a power-of-two length (repeating
        the first entry — scatter-set with duplicate targets writing
        the same value is idempotent) so patch program shapes stay a
        bounded set."""
        n = len(arrs[0])
        b = next_pow2(n)
        return [np.concatenate([a, np.repeat(a[:1], b - n)]) if b > n
                else a for a in arrs]

    def add(self, rows: Sequence[int]) -> None:
        """Append freshly-ingested rows to the spill block (never
        blocks on a rebuild — the fold happens at the next retrain)."""
        rows = [int(r) for r in rows if int(r) not in self._locator]
        if not rows or not self.trained:
            return
        _, jnp = self._jax()
        cap = int(self._d_spill.shape[0])
        if self._spill_n + len(rows) > cap:
            # grow + compact (drops -1 holes left by removals)
            live = [r for r, loc in self._locator.items()
                    if loc[0] == "s"]
            for r in live:
                del self._locator[r]
            self._rebuild_spill(live + rows)  # counts _spill_live itself
            return
        patch1d, _ = self._patches()
        pos = np.arange(self._spill_n, self._spill_n + len(rows),
                        dtype=np.int32)
        vals = np.asarray(rows, dtype=np.int32)
        pos, vals = self._bucket([pos, vals])
        self._d_spill = patch1d(self._d_spill, jnp.asarray(pos),
                                jnp.asarray(vals))
        for i, r in enumerate(rows):
            self._locator[r] = ("s", self._spill_n + i)
        self._spill_n += len(rows)
        self._spill_live += len(rows)

    def remove(self, rows: Sequence[int]) -> None:
        """Drop rows from their posting-list / spill slots (one
        stacked donated patch per buffer, not one dispatch per row)."""
        if not self.trained:
            return
        _, jnp = self._jax()
        slots, offs, spos = [], [], []
        for r in rows:
            loc = self._locator.pop(int(r), None)
            if loc is None:
                continue
            if loc[0] == "l":
                slots.append(loc[1])
                offs.append(loc[2])
                self._indexed_live -= 1
            else:
                spos.append(loc[1])
                self._spill_live -= 1
        patch1d, patch2d = self._patches()
        if slots:
            s, o = self._bucket([np.asarray(slots, np.int32),
                                 np.asarray(offs, np.int32)])
            vals = np.full(len(s), -1, dtype=np.int32)
            self._d_rowids = patch2d(self._d_rowids, jnp.asarray(s),
                                     jnp.asarray(o), jnp.asarray(vals))
        if spos:
            (p,) = self._bucket([np.asarray(spos, np.int32)])
            vals = np.full(len(p), -1, dtype=np.int32)
            self._d_spill = patch1d(self._d_spill, jnp.asarray(p),
                                    jnp.asarray(vals))

    # -- search --------------------------------------------------------

    @staticmethod
    def _search_body(matrix, cents, rowids, spill, q, *, nprobe, k):
        """ONE shard's fused search: centroid scores → top-nprobe
        local lists → candidate gather → exact rescore against the
        live matrix → shard-local top-k. Queries rescore in groups of
        ``_RESCORE_GROUP`` (lax.map batch_size) so the candidate
        working set stays [G, C, dim], not [B, C, dim] — G vectorizes
        enough to amortize dispatch (the batched-QPS half of the
        tentpole) without materializing the full batch's candidates."""
        import jax
        import jax.numpy as jnp
        b = q.shape[0]
        pad = rowids.shape[1]
        cs = q @ cents.T                          # [B, lists_local]
        _, pl = jax.lax.top_k(cs, nprobe)         # [B, nprobe]
        cand = rowids[pl].reshape(b, nprobe * pad)
        cand = jnp.concatenate(
            [cand, jnp.broadcast_to(spill[None, :],
                                    (b, spill.shape[0]))], axis=1)

        def per_query(args):
            qv, c = args
            safe = jnp.clip(c, 0, matrix.shape[0] - 1)
            vecs = matrix[safe]                   # [C, dim] gather
            s = (vecs @ qv.astype(matrix.dtype)).astype(jnp.float32)
            s = jnp.where(c >= 0, s, jnp.float32("-inf"))
            v, i = jax.lax.top_k(s, k)
            return v, jnp.take(c, i)

        return jax.lax.map(per_query, (q, cand),
                           batch_size=min(b, _RESCORE_GROUP))

    def _search_dispatch(self):
        jax, _ = self._jax()
        if self._search_fn is not None:
            return self._search_fn
        if self.mesh is None:
            self._search_fn = jax.jit(self._search_body,
                                      static_argnames=("nprobe", "k"))
        else:
            import functools

            from jax import shard_map
            from jax.sharding import PartitionSpec as P
            mesh = self.mesh

            def mesh_search(matrix, cents, rowids, spill, q, *,
                            nprobe, k):
                body = functools.partial(self._search_body,
                                         nprobe=nprobe, k=k)
                sm = shard_map(
                    body, mesh=mesh,
                    in_specs=(P(None, None), P("dp", None),
                              P("dp", None), P("dp"), P(None, None)),
                    out_specs=(P(None, "dp"), P(None, "dp")),
                    check_vma=False)
                return sm(matrix, cents, rowids, spill, q)

            self._search_fn = jax.jit(mesh_search,
                                      static_argnames=("nprobe", "k"))
        return self._search_fn

    def search(self, matrix, qs: np.ndarray, k: int,
               nprobe: int | None = None):
        """Search B queries; returns host arrays ``(vals, rows)`` of
        shape ``[B, shards*k]``, merged (host cross-shard top-k
        reduction = one argsort over shards*k rows per query) and a
        stats dict. ``rows`` may contain -1 (score -inf) when fewer
        than k live candidates were reachable."""
        _, jnp = self._jax()
        npb = min(nprobe if nprobe is not None else self.params.nprobe,
                  self.sps)
        spill_local = int(self._d_spill.shape[0]) // self.num_shards
        k_eff = min(int(k), npb * self.pad + spill_local)
        b = len(qs)
        bp = next_pow2(b)
        if bp > b:  # bucket B so program count stays bounded
            qs = np.concatenate(
                [qs, np.zeros((bp - b, qs.shape[1]), qs.dtype)])
        fn = self._search_dispatch()
        vals, rows = fn(matrix, self._d_centroids, self._d_rowids,
                        self._d_spill, jnp.asarray(qs, jnp.float32),
                        nprobe=npb, k=k_eff)
        vals = np.asarray(vals)[:b]
        rows = np.asarray(rows)[:b]
        order = np.argsort(-vals, axis=1, kind="stable")
        vals = np.take_along_axis(vals, order, axis=1)
        rows = np.take_along_axis(rows, order, axis=1)
        lists_scanned = min(npb * self.num_shards, self.nlist)
        stats = {
            "nprobe": npb,
            "lists_scanned": lists_scanned,
            "lists_scanned_frac": (lists_scanned / self.nlist
                                   if self.nlist else 0.0),
            "spill_fraction": round(self.spill_frac(), 4),
            "k": k_eff,
        }
        return vals, rows, stats
