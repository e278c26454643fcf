"""On-device (TPU) vector store: exact flat search + sharded IVF ANN.

The role FAISS/Qdrant play for the reference
(``adapters/copilot_vectorstore/faiss_store.py:18,101-105``,
``qdrant_store.py:78``), redesigned for the chip: vectors live as one
HBM-resident [capacity, dim] matrix; the default ``index="flat"`` route
scores a query as a single fused ``scores = M @ q`` matvec plus
``lax.top_k`` on the MXU/VPU — exact cosine search at HBM bandwidth, no
index build, no recall loss. 10M 384-dim bf16 vectors ≈ 7.4 GB: a v5e
chip holds the whole corpus.

``index="ivf"`` layers a two-tier IVF index (vectorstore/ivf.py) over
the SAME matrix for the million-chunk regime where O(corpus) per query
becomes the wall: a k-means coarse quantizer routes each query to
``nprobe`` posting lists of global row ids, candidates are gathered and
exactly rescored against the live matrix, and posting lists shard over
a dp-only mesh (``mesh="auto"``) with a host cross-shard top-k merge.
Flat stays the recall oracle; the IVF route is gated at recall@10 ≥
0.95 on the bench preset. Freshly-ingested rows append to a spill
block scored on every query, so ``add_embeddings`` never blocks on a
rebuild; the quantizer retrains lazily on the query path when spill
drift or corpus growth crosses the IVFParams thresholds.

Filtered queries (``thread_id=...``) use a host-side inverted index over
metadata: highly selective filters score just the candidate rows on
host; broad filters run the device path with top-k oversampling (the
IVF route falls back to exact flat for under-filled filtered queries,
keeping filtered results no worse than the oracle). Capacity grows
geometrically; the device buffer is rebuilt on growth and patched in
place (jitted dynamic_update_slice) for small flushes.

Retrieval is a first-class observable stage: ``set_metrics`` wires a
collector and every query records ``vectorstore_query_seconds`` /
``vectorstore_queries_total`` (per route) plus nprobe / lists_scanned /
spill-fraction series on the IVF route, and ``last_query_stats`` feeds
the orchestrator's retrieval trace span.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Mapping, Sequence

import numpy as np

from copilot_for_consensus_tpu.analysis.contracts import (
    ContractCase,
    HloSpec,
    checkable,
)
from copilot_for_consensus_tpu.obs.metrics import check_registry_labels
from copilot_for_consensus_tpu.storage.base import matches_filter
from copilot_for_consensus_tpu.vectorstore._inverted import InvertedIndexMixin
from copilot_for_consensus_tpu.vectorstore.base import (
    QueryResult,
    VectorStore,
    VectorStoreError,
)
from copilot_for_consensus_tpu.vectorstore.ivf import (
    IVFIndex,
    IVFParams,
    next_pow2,
)

_SELECTIVE_HOST_LIMIT = 4096     # filter hits below this → host-side scoring

#: retrieval telemetry families the store emits through
#: ``set_metrics`` (exposition-prefixed names) — the registry-next-to-
#: emitter discipline (PR 5): dashboards and alert exprs can only
#: reference series the code actually emits
#: (tests/test_observability_pack.py).
VECTORSTORE_METRICS = {
    "copilot_vectorstore_query_seconds": (
        "histogram", ("route",),
        "end-to-end query_batch latency per index route"),
    "copilot_vectorstore_queries_total": (
        "counter", ("route",),
        "queries answered per index route (flat | ivf | host)"),
    "copilot_vectorstore_query_nprobe": (
        "gauge", (),
        "posting lists probed per query on the ivf route"),
    "copilot_vectorstore_lists_scanned_total": (
        "counter", (),
        "posting lists scanned, summed over queries (ivf route)"),
    "copilot_vectorstore_spill_fraction": (
        "gauge", (),
        "fraction of live vectors answered from the spill block — "
        "sustained > ivf_spill_fraction means retrain is lagging"),
    "copilot_vectorstore_retrains_total": (
        "counter", (),
        "coarse-quantizer (re)trains — drift policy firings"),
}

# proc/role are stamped by the cross-process aggregator (obs/ship.py);
# declaring them here must fail at import, not at scrape time.
check_registry_labels(VECTORSTORE_METRICS, owner="VECTORSTORE_METRICS")

# hlo-peak-memory budgets for the IVF search dispatch at the contract
# factories' tiny shapes (~2× the measured compiled peak — they gate
# structural working-set blowups, not byte drift; see HloSpec).
_IVF_SEARCH_PEAK_BUDGET = 48 * 1024        # measured 23,008 B
_IVF_SEARCH_MESH_PEAK_BUDGET = 64 * 1024   # measured 34,080 B


class TPUVectorStore(InvertedIndexMixin, VectorStore):
    def __init__(self, config: Any = None):
        cfg = dict(config or {})
        self._dim: int | None = cfg.get("dimension") or None
        self._dtype_name = cfg.get("dtype", "bfloat16")
        self.persist_path = cfg.get("persist_path")
        self._index_kind = cfg.get("index", "flat")
        if self._index_kind not in ("flat", "ivf"):
            raise VectorStoreError(
                f"unknown index {self._index_kind!r} (flat|ivf)")
        self._ivf_params = IVFParams.from_config(cfg)
        self._mesh_cfg = cfg.get("mesh", "none")
        self._mesh = None
        self._mesh_built = False
        self._ivf: IVFIndex | None = None
        self.metrics = None                          # set via set_metrics
        self.last_query_stats: dict[str, Any] | None = None
        self._lock = threading.RLock()
        self._ids: list[str] = []
        self._index: dict[str, int] = {}
        self._metadata: list[dict[str, Any]] = []
        self._host: np.ndarray | None = None        # [n, dim] fp32 master
        self._init_inverted()
        self._device = None                          # [capacity, dim]
        self._device_rows = 0                        # rows synced
        self._deleted_rows: set[int] = set()
        self._batch_query_fn = None
        self._patch_fn = None
        self._zero_fn = None

    # -- lazy jax ------------------------------------------------------

    def _jax(self):
        import jax
        import jax.numpy as jnp
        return jax, jnp

    def _get_mesh(self):
        """dp-only retrieval mesh when configured; built lazily so a
        flat store never touches the device topology."""
        if self._mesh_built:
            return self._mesh
        self._mesh_built = True
        if self._mesh_cfg in (None, "none", "", 0, False):
            return None
        import jax

        from copilot_for_consensus_tpu.parallel.mesh import retrieval_mesh
        if self._mesh_cfg == "auto":
            n = len(jax.devices())
            self._mesh = retrieval_mesh(n) if n > 1 else None
        else:
            self._mesh = retrieval_mesh(int(self._mesh_cfg))
        return self._mesh

    def set_metrics(self, collector) -> None:
        """Wire a MetricsCollector; queries then emit the
        ``vectorstore_*`` series (obs/metrics.py namespace-prefixes)."""
        with self._lock:
            self.metrics = collector

    @property
    def dimension(self) -> int | None:
        with self._lock:
            return self._dim

    def count(self) -> int:
        with self._lock:
            return len(self._ids) - len(self._deleted_rows)

    # -- writes --------------------------------------------------------

    def add_embedding(self, vec_id, vector, metadata=None):
        self.add_embeddings([(vec_id, vector, metadata)])

    def add_embeddings(self, items) -> int:
        jaxmod, jnp = self._jax()
        n = 0
        with self._lock:
            rows, vecs = [], []
            for vec_id, vector, metadata in items:
                arr = np.asarray(vector, dtype=np.float32)
                if self._dim is None:
                    self._dim = int(arr.shape[0])
                if arr.shape[0] != self._dim:
                    raise VectorStoreError(
                        f"dimension mismatch: {arr.shape[0]} != {self._dim}")
                norm = float(np.linalg.norm(arr))
                if norm > 0:
                    arr = arr / norm
                meta = dict(metadata or {})
                if vec_id in self._index:            # upsert semantics
                    row = self._index[vec_id]
                    self._unindex_meta(row)
                    self._host[row] = arr
                    self._metadata[row] = meta
                    self._index_meta(row, meta)
                    self._deleted_rows.discard(row)
                    rows.append(row)
                    vecs.append(arr)
                else:
                    row = len(self._ids)
                    self._ids.append(vec_id)
                    self._index[vec_id] = row
                    self._metadata.append(meta)
                    self._index_meta(row, meta)
                    self._append_host(arr)
                    rows.append(row)
                    vecs.append(arr)
                n += 1
            self._sync_device(rows, vecs)
            if self._ivf is not None and self._ivf.trained and rows:
                # upserted rows move list→spill (their centroid may no
                # longer be nearest); new rows append to spill. Either
                # way the next query sees them — the rescore reads the
                # live matrix, the spill is scored exactly.
                self._ivf.remove(rows)
                self._ivf.add(rows)
        return n

    def _append_host(self, arr: np.ndarray) -> None:
        if self._host is None:
            self._host = np.zeros((16, self._dim), dtype=np.float32)
        if len(self._ids) > self._host.shape[0]:
            grown = np.zeros((self._host.shape[0] * 2, self._dim),
                             dtype=np.float32)
            grown[:self._host.shape[0]] = self._host
            self._host = grown
        self._host[len(self._ids) - 1] = arr

    def _unindex_meta(self, row: int) -> None:
        meta = self._metadata[row]
        for k, v in meta.items():
            if isinstance(v, (str, int, bool)):
                self._inverted[(k, v)].discard(row)

    def _sync_device(self, rows: list[int], vecs: list[np.ndarray]) -> None:
        """Patch the device buffer; rebuild on growth."""
        jaxmod, jnp = self._jax()
        dtype = getattr(jnp, self._dtype_name)
        capacity = self._host.shape[0] if self._host is not None else 0
        if (self._device is None
                or self._device.shape[0] != capacity):
            arr = self._host.astype(np.float32)
            mesh = (self._get_mesh() if self._index_kind == "ivf"
                    else None)
            if mesh is not None:
                # replicate over the retrieval mesh so the sharded IVF
                # dispatch gathers candidates without a reshard copy
                from jax.sharding import NamedSharding, PartitionSpec
                self._device = jaxmod.device_put(
                    arr, NamedSharding(mesh, PartitionSpec(None, None))
                ).astype(dtype)
            else:
                self._device = jaxmod.device_put(arr).astype(dtype)
            self._device_rows = len(self._ids)
            return
        if not rows:
            return
        if self._patch_fn is None:
            def patch(buf, updates, starts):
                def one(buf, pair):
                    vec, start = pair
                    return jaxmod.lax.dynamic_update_slice(
                        buf, vec.astype(buf.dtype)[None, :],
                        (start, 0)), None
                buf, _ = jaxmod.lax.scan(one, buf, (updates, starts))
                return buf
            self._patch_fn = jaxmod.jit(patch, donate_argnums=(0,))
        self._device = self._patch_fn(
            self._device, jnp.asarray(np.stack(vecs), dtype=jnp.float32),
            jnp.asarray(rows, dtype=jnp.int32))
        self._device_rows = len(self._ids)

    # -- IVF maintenance ----------------------------------------------

    def _ensure_ivf(self) -> IVFIndex:
        if self._ivf is None:
            self._ivf = IVFIndex(self._dim, self._ivf_params,
                                 mesh=self._get_mesh())
        return self._ivf

    def _maybe_retrain(self) -> None:
        """Lazy (re)train on the query path — never on ingest. First
        train once the corpus reaches min_train; retrain when spill
        drift or corpus growth crosses the IVFParams thresholds."""
        if self._index_kind != "ivf" or self._host is None:
            return
        live = len(self._ids) - len(self._deleted_rows)
        ivf = self._ensure_ivf()
        if not ivf.needs_retrain(live):
            return
        rows = [i for i in range(len(self._ids))
                if i not in self._deleted_rows]
        ivf.rebuild(self._host, rows)
        if self.metrics is not None:
            self.metrics.increment("vectorstore_retrains_total")

    # -- reads ---------------------------------------------------------

    def get(self, vec_id):
        with self._lock:
            row = self._index.get(vec_id)
            if row is None or row in self._deleted_rows:
                return None
            return self._host[row].tolist(), dict(self._metadata[row])

    def query(self, vector, top_k: int = 10, flt=None):
        return self.query_batch([vector], top_k=top_k, flt=flt)[0]

    def query_batch(self, vectors, top_k: int = 10, flt=None):
        """B queries in ONE device dispatch: [B, D] @ HBM matrixᵀ with a
        per-row top-k (flat), or the fused IVF probe→gather→rescore
        dispatch when the index is trained. A single query pays one
        dispatch round trip per call; batching amortizes it (neither
        rate is measured on the current chip)."""
        with self._lock:
            n = len(self._ids)
            if n == 0 or self._dim is None:
                return [[] for _ in vectors]
            t0 = time.perf_counter()
            qs = np.asarray(list(vectors), dtype=np.float32)
            norms = np.linalg.norm(qs, axis=1, keepdims=True)
            qs = np.where(norms > 0, qs / np.maximum(norms, 1e-30), qs)
            self._maybe_retrain()
            if flt:
                cand = self._filter_rows(flt)
                if cand is not None and len(cand) <= _SELECTIVE_HOST_LIMIT:
                    out = [self._host_query(q, cand, top_k, flt)
                           for q in qs]
                    self._record_query("host", len(qs), t0)
                    return out
            if (self._index_kind == "ivf" and self._ivf is not None
                    and self._ivf.trained):
                out, stats, esc = self._ivf_query_many(qs, top_k, flt)
                self._record_query("ivf", len(qs), t0, stats, esc)
                return out
            out = self._device_query_many(qs, top_k, flt)
            self._record_query("flat", len(qs), t0)
            return out

    def _record_query(self, route: str, nq: int, t0: float,
                      stats: dict | None = None,
                      escalations: int = 0) -> None:
        dur = time.perf_counter() - t0
        snap: dict[str, Any] = {
            "route": route, "queries": nq, "duration_s": dur,
            "escalations": escalations,
        }
        if stats:
            snap.update(
                nprobe=stats["nprobe"],
                lists_scanned=stats["lists_scanned"],
                lists_scanned_frac=stats["lists_scanned_frac"],
                spill_fraction=stats["spill_fraction"])
        self.last_query_stats = snap
        m = self.metrics
        if m is None:
            return
        m.observe("vectorstore_query_seconds", dur,
                  labels={"route": route})
        m.increment("vectorstore_queries_total", float(nq),
                    labels={"route": route})
        if stats:
            m.gauge("vectorstore_query_nprobe", float(stats["nprobe"]))
            m.increment("vectorstore_lists_scanned_total",
                        float(stats["lists_scanned"] * nq))
            m.gauge("vectorstore_spill_fraction",
                    float(stats["spill_fraction"]))

    def _filter_rows(self, flt: Mapping[str, Any]) -> list[int] | None:
        """Candidate rows via the shared inverted index (superset guess;
        callers re-verify with matches_filter); None = not decidable."""
        cand = self._filter_candidates(flt)
        if cand is None:
            return None
        return sorted(cand - self._deleted_rows)

    def _host_query(self, q, rows: list[int], top_k: int, flt):
        if not rows:
            return []
        sub = self._host[rows]                       # [m, dim]
        scores = sub @ q
        order = np.argsort(-scores)[:top_k]
        return [
            QueryResult(self._ids[rows[i]], float(scores[i]),
                        dict(self._metadata[rows[i]]))
            for i in order
            if matches_filter(self._metadata[rows[i]], flt)
        ]

    def _device_query(self, q, top_k: int, flt):
        return self._device_query_many(np.asarray(q, np.float32)[None],
                                       top_k, flt)[0]

    def _collect_hits(self, vals, rows, top_k, flt):
        """Host side of a device top-k: skip padding/deleted rows,
        re-verify the filter, stop at top_k. Re-enters the store RLock
        (callers already hold it) so the row-table reads are guarded."""
        out = []
        with self._lock:
            for score, row in zip(vals, rows):
                row = int(row)
                if (row < 0 or row >= len(self._ids)
                        or row in self._deleted_rows):
                    continue  # padding rows; skip
                meta = self._metadata[row]
                if flt and not matches_filter(meta, flt):
                    continue
                out.append(QueryResult(self._ids[row], float(score),
                                       dict(meta)))
                if len(out) == top_k:
                    break
        return out

    def _device_query_many(self, qs: np.ndarray, top_k: int, flt
                           ) -> list[list[QueryResult]]:
        """ONE implementation for single and batched exact device
        search: fused [B, D] @ matrixᵀ + per-row top-k, with top-k
        oversampling escalation for filtered/deleted rows. Escalation
        rounds rescore only the still-pending queries, and stop once k
        covers every live-or-dead row ever added (``len(self._ids)`` —
        deletes keep their id slot, so that IS the row count). The
        requested width rounds UP to a power of two so the escalation
        ladder compiles a bounded set of programs (k is a static arg;
        the hlo-program-cache contract pins this)."""
        jaxmod, jnp = self._jax()
        if self._batch_query_fn is None:
            def run(matrix, qv, k):
                scores = (qv.astype(matrix.dtype)
                          @ matrix.T).astype(jnp.float32)
                return jaxmod.lax.top_k(scores, k)       # [B, k] each
            self._batch_query_fn = jaxmod.jit(run, static_argnames=("k",))

        # Callers hold the store RLock; re-enter so the device-matrix
        # and row-table reads are lexically guarded.
        with self._lock:
            capacity = self._device.shape[0]
            oversample = max(top_k, 16)
            pending = list(range(len(qs)))
            results: dict[int, list[QueryResult]] = {}
            while True:
                k = min(capacity, next_pow2(oversample))
                vals, idx = self._batch_query_fn(
                    self._device, jnp.asarray(qs[pending]), k)
                vals = np.asarray(vals)
                idx = np.asarray(idx)
                still = []
                for bi, qi in enumerate(pending):
                    out = self._collect_hits(vals[bi], idx[bi],
                                             top_k, flt)
                    results[qi] = out
                    if (len(out) < top_k and k < capacity
                            and k < len(self._ids)):
                        still.append(qi)
                if not still:
                    return [results[i] for i in range(len(qs))]
                pending = still
                oversample = k * 4

    def _ivf_query_many(self, qs: np.ndarray, top_k: int, flt):
        """The ANN route: fused probe→gather→rescore dispatch (per
        shard), host cross-shard merge, then the same host-side
        verify/escalate discipline as the flat route — k escalates in
        power-of-two buckets up to everything the probed lists + spill
        can reach. Filtered queries that stay under-filled at the
        ceiling fall back to the exact route, so a filter never
        returns worse-than-oracle results."""
        ivf = self._ivf
        ceiling = max(1, ivf.max_candidates() // ivf.num_shards)
        oversample = max(top_k, 16)
        pending = list(range(len(qs)))
        results: dict[int, list[QueryResult]] = {}
        stats: dict[str, Any] = {}
        escalations = 0
        while True:
            k = min(ceiling, next_pow2(oversample))
            vals, rows, stats = ivf.search(self._device, qs[pending], k)
            still = []
            for bi, qi in enumerate(pending):
                out = self._collect_hits(vals[bi], rows[bi], top_k, flt)
                results[qi] = out
                if len(out) < top_k and k < ceiling:
                    still.append(qi)
            if not still:
                break
            pending = still
            oversample = k * 4
            escalations += 1
        if flt:
            short = [i for i in range(len(qs))
                     if len(results[i]) < top_k]
            if short:
                for i, exact in zip(
                        short,
                        self._device_query_many(qs[short], top_k, flt)):
                    results[i] = exact
        return ([results[i] for i in range(len(qs))], stats,
                escalations)

    # -- deletes / persistence ----------------------------------------

    def delete(self, vec_ids: Sequence[str]) -> int:
        jaxmod, jnp = self._jax()
        n = 0
        with self._lock:
            zero_rows = []
            for vec_id in vec_ids:
                row = self._index.get(vec_id)
                if row is None or row in self._deleted_rows:
                    continue
                self._deleted_rows.add(row)
                self._unindex_meta(row)
                zero_rows.append(row)
                n += 1
            if zero_rows:
                self._host[zero_rows] = 0.0
            if zero_rows and self._device is not None:
                # ONE stacked row-zeroing patch (donated buffer), not a
                # scan step per row; indices bucket to a power of two
                # (duplicate writes of the same zero are idempotent) so
                # delete batch sizes share compiled programs.
                if self._zero_fn is None:
                    def zero(buf, rows):
                        return buf.at[rows].set(
                            jnp.zeros((), buf.dtype))
                    self._zero_fn = jaxmod.jit(zero, donate_argnums=(0,))
                idx = np.asarray(zero_rows, dtype=np.int32)
                b = next_pow2(len(idx))
                if b > len(idx):
                    idx = np.concatenate(
                        [idx, np.repeat(idx[:1], b - len(idx))])
                self._device = self._zero_fn(self._device,
                                             jnp.asarray(idx))
                self._device_rows = len(self._ids)
            if zero_rows and self._ivf is not None:
                self._ivf.remove(zero_rows)
        return n

    def delete_by_filter(self, flt):
        with self._lock:
            rows = self._filter_rows(flt)
            if rows is None:
                rows = [i for i, m in enumerate(self._metadata)
                        if i not in self._deleted_rows
                        and matches_filter(m, flt)]
            else:
                # Index candidates are a superset guess — re-verify
                # before anything irreversible.
                rows = [i for i in rows
                        if matches_filter(self._metadata[i], flt)]
            return self.delete([self._ids[i] for i in rows])

    def clear(self):
        with self._lock:
            self._ids.clear()
            self._index.clear()
            self._metadata.clear()
            self._init_inverted()
            self._deleted_rows.clear()
            self._host = None
            self._device = None
            self._device_rows = 0
            self._ivf = None
            self.last_query_stats = None

    def save(self, path: str | None = None) -> str:
        import json
        p = path or self.persist_path
        if not p:
            raise VectorStoreError("no persist_path configured")
        with self._lock:
            extra = {}
            if self._ivf is not None and self._ivf.trained:
                # the trained quantizer travels with the corpus; load()
                # rebuilds posting lists by deterministic assignment
                # (spill folds in), skipping the k-means re-fit
                extra["ivf_centroids"] = self._ivf.centroids_np
            np.savez_compressed(
                p,
                vectors=(self._host[:len(self._ids)]
                         if self._host is not None
                         else np.zeros((0, 0))),
                ids=np.array(self._ids, dtype=object),
                metadata=np.array(
                    [json.dumps(m) for m in self._metadata], dtype=object),
                deleted=np.array(sorted(self._deleted_rows)),
                **extra,
            )
        return p

    def load(self, path: str | None = None) -> int:
        """Bulk restore: rebuild the host state in one pass and ship
        the matrix with ONE device_put — not one add_embedding (and one
        device sync) per row. Deleted rows compact away; a saved
        trained quantizer is restored without re-running k-means."""
        import json
        p = path or self.persist_path
        if not p:
            raise VectorStoreError("no persist_path configured")
        data = np.load(p if str(p).endswith(".npz") else p + ".npz",
                       allow_pickle=True)
        with self._lock:
            self.clear()
            vectors = data["vectors"]
            ids = list(data["ids"])
            metas = [json.loads(m) for m in data["metadata"]]
            deleted = set(int(i) for i in data["deleted"])
            self._dim = int(vectors.shape[1]) if vectors.size else self._dim
            live = [i for i in range(len(ids)) if i not in deleted]
            if not live:
                return 0
            n = len(live)
            capacity = 16
            while capacity < n:
                capacity *= 2
            self._host = np.zeros((capacity, self._dim), dtype=np.float32)
            sub = vectors[live].astype(np.float32)
            norms = np.linalg.norm(sub, axis=1, keepdims=True)
            self._host[:n] = np.where(norms > 0,
                                      sub / np.maximum(norms, 1e-30), sub)
            self._ids = [str(ids[i]) for i in live]
            self._index = {vid: r for r, vid in enumerate(self._ids)}
            self._metadata = [metas[i] for i in live]
            for r, meta in enumerate(self._metadata):
                self._index_meta(r, meta)
            self._sync_device([], [])                # one device_put
            if self._index_kind == "ivf" and "ivf_centroids" in data:
                self._ensure_ivf().rebuild(
                    self._host, list(range(n)),
                    centroids=data["ivf_centroids"])
            return len(self._ids)


# ---------------------------------------------------------------------------
# shardcheck / hlocheck contracts (analysis/shardcheck.py, hlocheck.py)
# ---------------------------------------------------------------------------


@checkable("tpu-vectorstore")
def _shardcheck_tpu_vectorstore():
    """Build a tiny store far enough to materialize its lazily-jitted
    programs (an upsert after the first flush builds the patch program,
    a query builds the batched search, a delete builds the row-zeroing
    patch) and verify (a) the donated HBM matrix aliases its output in
    both mutating programs — this is the store's one long-lived device
    allocation, and a dropped alias would double it on every flush —
    and (b) the escalation ladder's power-of-two k bucketing keeps the
    query program cache bounded: four requested widths, two programs."""
    import functools

    import jax
    import jax.numpy as jnp

    dim = 8
    store = TPUVectorStore({"dimension": dim})
    store.add_embeddings([(f"v{i}", np.eye(dim)[i % dim], {"i": i})
                          for i in range(40)])
    store.add_embedding("v0", np.arange(dim, dtype=np.float32), {"i": 0})
    store.query([1.0] * dim, top_k=2)
    store.delete(["v1"])
    S = jax.ShapeDtypeStruct
    capacity = store._device.shape[0]
    matrix = S((capacity, dim), store._device.dtype)
    widths = (16, 48, 64, 256)       # escalation ladder requests
    variants = tuple(
        (f"w{w}",
         functools.partial(store._batch_query_fn,
                           k=min(capacity, next_pow2(w))),
         (matrix, S((2, dim), jnp.float32)))
        for w in widths)
    return [
        ContractCase(
            label="patch", fn=store._patch_fn,
            args=(matrix, S((1, dim), jnp.float32),
                  S((1,), jnp.int32)),
            donate_argnums=(0,)),
        ContractCase(
            label="delete-zero", fn=store._zero_fn,
            args=(matrix, S((2,), jnp.int32)),
            donate_argnums=(0,)),
        ContractCase(
            label="batch-query",
            fn=functools.partial(store._batch_query_fn, k=4),
            args=(matrix, S((2, dim), jnp.float32))),
        ContractCase(
            label="query-cache",
            hlo=HloSpec(variants=variants, expected_programs=2)),
    ]


@checkable("tpu-vectorstore-ivf")
def _shardcheck_tpu_vectorstore_ivf():
    """Single-device IVF contracts: train a tiny index and verify the
    posting-list maintenance programs donate their buffers (spill
    append and list-slot clear each patch one long-lived int32 buffer
    in place) and the fused search dispatch stays within its compiled
    peak-memory budget — the lax.map rescore bounds the candidate
    working set to one query's gather, and a regression to a
    [B, C, dim] materialization trips hlo-peak-memory."""
    import functools

    import jax
    import jax.numpy as jnp

    dim = 8
    store = TPUVectorStore({
        "dimension": dim, "index": "ivf", "ivf_min_train": 32,
        "ivf_nlist": 8, "ivf_nprobe": 4, "ivf_train_size": 64,
        "ivf_kmeans_iters": 2})
    rng = np.random.default_rng(0)
    store.add_embeddings([(f"v{i}", rng.normal(size=dim), {"i": i})
                          for i in range(48)])
    store.query([1.0] * dim, top_k=4)        # trains + search program
    store.add_embedding("s0", rng.normal(size=dim), {"i": -1})  # spill
    store.delete(["v1"])                     # list-slot clear
    ivf = store._ivf
    S = jax.ShapeDtypeStruct
    f32, i32 = jnp.float32, jnp.int32
    cap = store._device.shape[0]
    lp, pad = (int(d) for d in ivf._d_rowids.shape)
    scap = int(ivf._d_spill.shape[0])
    return [
        ContractCase(
            label="ivf-search",
            fn=functools.partial(ivf._search_dispatch(), nprobe=4, k=8),
            args=(S((cap, dim), store._device.dtype), S((lp, dim), f32),
                  S((lp, pad), i32), S((scap,), i32), S((4, dim), f32)),
            hlo=HloSpec(peak_bytes=_IVF_SEARCH_PEAK_BUDGET)),
        ContractCase(
            label="ivf-spill-append", fn=ivf._patch1d_fn,
            args=(S((scap,), i32), S((4,), i32), S((4,), i32)),
            donate_argnums=(0,)),
        ContractCase(
            label="ivf-list-patch", fn=ivf._patch2d_fn,
            args=(S((lp, pad), i32), S((4,), i32), S((4,), i32),
                  S((4,), i32)),
            donate_argnums=(0,)),
    ]


@checkable("tpu-vectorstore-ivf-mesh")
def _shardcheck_tpu_vectorstore_ivf_mesh():
    """The sharded retrieval plane: posting lists and centroids
    partition over dp (slot counts are allocator-padded to divide
    evenly — the divisibility contract), and the fused per-shard search
    compiles with ZERO collectives — the cross-shard top-k reduction is
    a host merge over [B, dp*k], so a GSPMD reshard sneaking a gather
    into the hot dispatch turns the lane red."""
    import functools

    import jax
    import jax.numpy as jnp

    from copilot_for_consensus_tpu.analysis.contracts import (
        require_devices,
    )

    require_devices(8)
    dim = 8
    store = TPUVectorStore({
        "dimension": dim, "index": "ivf", "mesh": 8,
        "ivf_min_train": 64, "ivf_nlist": 16, "ivf_nprobe": 2,
        "ivf_train_size": 128, "ivf_kmeans_iters": 2})
    rng = np.random.default_rng(0)
    store.add_embeddings([(f"v{i}", rng.normal(size=dim), {"i": i})
                          for i in range(96)])
    store.query([1.0] * dim, top_k=4)
    ivf = store._ivf
    S = jax.ShapeDtypeStruct
    f32, i32 = jnp.float32, jnp.int32
    cap = store._device.shape[0]
    lp, pad = (int(d) for d in ivf._d_rowids.shape)
    scap = int(ivf._d_spill.shape[0])
    return [
        ContractCase(
            label="ivf-lists-partition", mesh=ivf.mesh,
            rules={"ivf_lists": "dp", "ivf_spill": "dp"},
            logical=(
                ("ivf-buffers",
                 {"rowids": S((lp, pad), i32),
                  "centroids": S((lp, dim), f32),
                  "spill": S((scap,), i32)},
                 {"rowids": ("ivf_lists", None),
                  "centroids": ("ivf_lists", None),
                  "spill": ("ivf_spill",)}),
            )),
        ContractCase(
            label="ivf-search-mesh",
            fn=functools.partial(ivf._search_dispatch(), nprobe=2, k=8),
            args=(S((cap, dim), store._device.dtype), S((lp, dim), f32),
                  S((lp, pad), i32), S((scap,), i32), S((8, dim), f32)),
            mesh=ivf.mesh,
            hlo=HloSpec(collectives={},
                        peak_bytes=_IVF_SEARCH_MESH_PEAK_BUDGET)),
    ]
