"""Device-resident top-k retrieval index: the corpus matrix lives on the
accelerator, padded to capacity buckets with its ROWS sharded over the
mesh's ``dp`` axis (stored transposed ``[dim, capacity]`` =
``P(None, "dp")`` — the contiguous-contraction layout; ``q @ c.T``
measured 5.5x slower on XLA CPU), so a whole query wave's ANN search is
ONE fused dispatch
(matmul -> mask -> ``lax.top_k``) instead of a per-query host
``np.argsort`` over the corpus.

``DeviceIndexedStore`` wraps any :class:`VectorStore`: every mutation is
delegated to the inner store (which stays the durable source of truth)
and mirrored into a device-side matrix; ``search``/``search_batch`` run on
device with exact-parity semantics — same top-k ids, same tie order
(score desc, then insertion row asc), metadata filters applied as an
on-device mask built from an inverted ``(key, value) -> rows`` index that
honours the same SHREDDED_KEYS union as :func:`store.base._match`.

Shape discipline follows the engine's warmup contract ([jax-tracing],
serving/engine.py): query counts pad to power-of-two buckets, the corpus
pads to a capacity bucket, k is fixed at ``k_bucket`` — so ``warmup()``
compiles exactly ``len(query_buckets)`` programs per live capacity bucket
and live traffic adds zero (asserted via ``_cache_size`` deltas in
tests/test_device_index.py).  Requests outside the warmed contract
(k > k_bucket) fall back to the inner store and are counted in the
``rag_device_index_searches_total{path="fallback"}`` metric.
"""

from __future__ import annotations

import threading
from typing import Iterable, Mapping, Sequence

import numpy as np

from githubrepostorag_tpu.metrics import (
    DEVICE_INDEX_SEARCHES,
    INDEX_CAPACITY,
    INDEX_COMPACTIONS,
    INDEX_FULL_SYNCS,
    INDEX_HOLES,
    INDEX_LIVE_ROWS,
)
from githubrepostorag_tpu.store.base import (
    SHREDDED_KEYS,
    Doc,
    SearchHit,
    VectorStore,
    shred_entry,
)
from githubrepostorag_tpu.utils import next_bucket
from githubrepostorag_tpu.utils.logging import get_logger
from githubrepostorag_tpu.obs.startup import phase as startup_phase
from githubrepostorag_tpu.utils.profiling import annotate

logger = get_logger(__name__)

# ingest seeds the mirror from the inner store's existing rows at wrap time
_SEED_LIMIT = 1_000_000


class _DeviceTable:
    """Host mirror + device copy of one table's corpus matrix.

    Row assignment mirrors the memory store's docs-dict ordering so tie
    order is identical: re-upserting an existing doc_id rewrites the SAME
    row; deletes leave an invalid hole (a re-insert then appends, exactly
    like a dict re-insert moves to the end)."""

    def __init__(self, name: str, dim: int, capacity: int) -> None:
        self.name = name
        self.dim = dim
        self.capacity = capacity
        self.ids: list[str] = []          # row -> doc_id ("" = hole)
        self.rows: dict[str, int] = {}    # doc_id -> row
        self.host = np.zeros((capacity, dim), dtype=np.float32)  # normalized
        self.valid = np.zeros(capacity, dtype=bool)
        self.meta_rows: dict[tuple[str, str], set[int]] = {}
        self.meta_docs: dict[int, dict[str, str]] = {}  # row -> metadata
        self.corpus_dev = None            # lazily synced jax array
        self.dirty_rows: set[int] = set()
        self.full_sync = True
        self.compactions = 0              # in-place hole reclaims
        self.full_syncs = 0               # whole-table transpose re-puts


class DeviceIndexedStore(VectorStore):
    """VectorStore wrapper running ANN search on device.

    One jitted search program per (query-bucket, capacity-bucket); k is a
    static ``k_bucket``.  With a mesh, the corpus rows shard over ``dp``
    (local ``lax.top_k`` per shard -> all-gather of candidates -> global
    merge); without one, a single-device program.
    """

    def __init__(
        self,
        inner: VectorStore,
        *,
        mesh=None,
        k_bucket: int = 16,
        max_wave: int = 16,
        min_capacity: int = 64,
    ) -> None:
        import jax
        import jax.numpy as jnp  # noqa: F401 - fail fast when jax is absent

        self._jax = jax
        self.inner = inner
        self.mesh = mesh
        self._dp = mesh.shape.get("dp", 1) if mesh is not None else 1
        self.k_bucket = max(1, k_bucket)
        self.max_wave = max(1, max_wave)
        self.min_capacity = max(self._dp, min_capacity)
        self._tables: dict[str, _DeviceTable] = {}
        self._lock = threading.RLock()
        self._search_jit = self._build_search()
        self._update_jit, self._repack_jit = self._build_mutation()
        self._seed_from_inner()

    # ------------------------------------------------------------ programs

    def _build_search(self):
        import jax
        import jax.numpy as jnp

        mesh, dp = self.mesh, self._dp

        def dense(corpus, queries, mask, k: int):
            # corpus is stored TRANSPOSED [dim, cap]: contracting the
            # leading axis keeps the big operand's memory walk contiguous
            # (q @ c.T measured 5.5x slower on XLA CPU, same kernel count)
            scores = queries @ corpus                       # [Qb, cap]
            scores = jnp.where(mask, scores, -jnp.inf)
            return jax.lax.top_k(scores, k)

        if mesh is None or dp == 1:
            return jax.jit(dense, static_argnames=("k",))

        from jax.sharding import PartitionSpec as P

        def sharded(corpus, queries, mask, k: int):
            local_n = corpus.shape[1] // dp                 # corpus [dim, cap]
            kk = min(k, local_n)

            def body(c_loc, q, m_loc):
                s = q @ c_loc                               # [Qb, cap/dp]
                s = jnp.where(m_loc, s, -jnp.inf)
                v, i = jax.lax.top_k(s, kk)
                # local -> global row ids; shard-major gather order keeps
                # ties breaking toward the lower global row (each shard's
                # candidates arrive score-sorted with index-order ties,
                # and shard p's rows all precede shard p+1's)
                i = i + jax.lax.axis_index("dp") * local_n
                v_all = jax.lax.all_gather(v, "dp", axis=1, tiled=True)
                i_all = jax.lax.all_gather(i, "dp", axis=1, tiled=True)
                vv, pos = jax.lax.top_k(v_all, k)
                return vv, jnp.take_along_axis(i_all, pos, axis=1)

            return jax.shard_map(
                body,
                mesh=mesh,
                in_specs=(P(None, "dp"), P(), P(None, "dp")),
                out_specs=(P(), P()),
                check_vma=False,
            )(corpus, queries, mask)

        return jax.jit(sharded, static_argnames=("k",))

    def _build_mutation(self):
        """The two mutation programs: the bucketed row-scatter ``_sync``
        dispatches for dirty rows, and the compaction gather that repacks
        live columns to the front of the SAME capacity bucket.  Both
        donate the corpus (in-place buffer reuse) and both are warmed by
        ``warmup()`` over the scatter-bucket ladder, so sustained
        mutation traffic and background compaction compile nothing live."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        update = jax.jit(
            lambda c, i, v: c.at[:, i].set(v, mode="drop"),
            donate_argnums=(0,),
        )
        # OOB src (== capacity) fills 0 — exactly the hole columns past
        # the live-row prefix after a repack
        kw = {}
        sh = self._sharding(P(None, "dp"))
        if sh is not None:
            kw["out_shardings"] = sh
        repack = jax.jit(
            lambda c, s: jnp.take(c, s, axis=1, mode="fill", fill_value=0.0),
            donate_argnums=(0,),
            **kw,
        )
        return update, repack

    def search_program_cache_size(self) -> int:
        """Compiled search-program count (the warmup-contract observable)."""
        return self._search_jit._cache_size()

    def mutation_program_cache_size(self) -> int:
        """Compiled mutation-program count: the dirty-row scatter ladder
        plus the compaction repack gather (the live-mutation observable —
        compile_guard pins its delta at zero under churn)."""
        return self._update_jit._cache_size() + self._repack_jit._cache_size()

    # ------------------------------------------------------------ mirror

    def _seed_from_inner(self) -> None:
        for table in self.inner.tables():
            docs = self.inner.find_by_metadata(table, {}, limit=_SEED_LIMIT)
            if docs:
                self._mirror_upsert(table, docs)

    def _capacity_for(self, n: int) -> int:
        cap = next_bucket(n, 1 << 30, minimum=self.min_capacity)
        if cap % self._dp:  # dp must divide the row dim for the shard_map
            cap = -(-cap // self._dp) * self._dp
        return cap

    def _table_for(self, name: str, dim: int) -> _DeviceTable:
        t = self._tables.get(name)
        if t is None:
            t = _DeviceTable(name, dim, self._capacity_for(1))
            self._tables[name] = t
        return t

    def reserve(self, table: str, capacity: int, dim: int | None = None) -> None:
        """Pre-size a table's capacity bucket (snapshot restore, bench
        setup) so a known-size corpus doesn't re-grow through every
        intermediate bucket while it streams in."""
        with self._lock:
            t = self._tables.get(table)
            if t is None:
                if dim is None:
                    raise ValueError("reserve() on a new table needs dim")
                t = _DeviceTable(table, dim, self._capacity_for(capacity))
                self._tables[table] = t
            elif self._capacity_for(capacity) > t.capacity:
                self._grow(t, capacity)

    @staticmethod
    def _meta_entries(metadata: Mapping[str, str]) -> list[tuple[str, str]]:
        return [(str(k), str(v)) for k, v in metadata.items()]

    def _index_row(self, t: _DeviceTable, row: int, metadata: Mapping[str, str]) -> None:
        for kv in self._meta_entries(metadata):
            t.meta_rows.setdefault(kv, set()).add(row)

    def _unindex_row(self, t: _DeviceTable, row: int, metadata: Mapping[str, str]) -> None:
        for kv in self._meta_entries(metadata):
            rows = t.meta_rows.get(kv)
            if rows is not None:
                rows.discard(row)
                if not rows:
                    del t.meta_rows[kv]

    def _grow(self, t: _DeviceTable, needed: int) -> None:
        """Re-pack the mirror into a bigger capacity bucket, compacting
        holes.  Compaction preserves relative row order, so tie order is
        unchanged; the device copy is re-put wholesale on next search."""
        live = [(rid, t.rows[rid]) for rid in t.ids if rid and rid in t.rows]
        live.sort(key=lambda p: p[1])
        cap = self._capacity_for(max(needed, len(live)))
        host = np.zeros((cap, t.dim), dtype=np.float32)
        valid = np.zeros(cap, dtype=bool)
        ids: list[str] = []
        rows: dict[str, int] = {}
        old_meta = t.meta_rows
        old_row_of = {old: new for new, (_, old) in enumerate(live)}
        for new, (rid, old) in enumerate(live):
            host[new] = t.host[old]
            valid[new] = t.valid[old]
            ids.append(rid)
            rows[rid] = new
        t.capacity, t.host, t.valid, t.ids, t.rows = cap, host, valid, ids, rows
        t.meta_rows = {
            kv: {old_row_of[r] for r in rs if r in old_row_of}
            for kv, rs in old_meta.items()
        }
        t.meta_rows = {kv: rs for kv, rs in t.meta_rows.items() if rs}
        t.meta_docs = {old_row_of[r]: md for r, md in t.meta_docs.items()
                       if r in old_row_of}
        t.corpus_dev, t.dirty_rows, t.full_sync = None, set(), True

    def _compact_table(self, t: _DeviceTable) -> dict:
        """Reclaim tombstoned holes IN PLACE: repack live rows to the
        front of the SAME capacity bucket.  Relative live-row order is
        preserved, so memory-store tie order survives; the device side is
        one warmed ``_repack_jit`` gather (plus a warmed dirty-row
        scatter to land pending writes first) — never the full-transpose
        re-put ``_grow`` pays.  Caller holds the lock."""
        holes = len(t.ids) - len(t.rows)
        if holes <= 0:
            return {"table": t.name, "reclaimed": 0, "live_rows": len(t.rows)}
        live = sorted(t.rows.items(), key=lambda p: p[1])  # (id, row) by row
        if t.corpus_dev is not None and not t.full_sync:
            corpus = self._sync(t)  # land dirty rows via the warmed scatter
            src = np.full(t.capacity, t.capacity, dtype=np.int32)  # OOB -> 0
            src[: len(live)] = [old for _, old in live]
            t.corpus_dev = self._repack_jit(corpus, src)
        host = np.zeros_like(t.host)
        valid = np.zeros_like(t.valid)
        ids: list[str] = []
        rows: dict[str, int] = {}
        old_row_of = {old: new for new, (_, old) in enumerate(live)}
        for new, (rid, old) in enumerate(live):
            host[new] = t.host[old]
            valid[new] = t.valid[old]
            ids.append(rid)
            rows[rid] = new
        t.host, t.valid, t.ids, t.rows = host, valid, ids, rows
        t.meta_rows = {
            kv: {old_row_of[r] for r in rs if r in old_row_of}
            for kv, rs in t.meta_rows.items()
        }
        t.meta_rows = {kv: rs for kv, rs in t.meta_rows.items() if rs}
        t.meta_docs = {old_row_of[r]: md for r, md in t.meta_docs.items()
                       if r in old_row_of}
        t.dirty_rows = set()  # the repacked device copy mirrors host exactly
        t.compactions += 1
        INDEX_COMPACTIONS.labels(table=t.name).inc()
        self._publish_gauges(t)
        logger.info("device index %s: compacted %d holes (%d live / %d cap)",
                    t.name, holes, len(rows), t.capacity)
        return {"table": t.name, "reclaimed": holes, "live_rows": len(rows)}

    def compact(self, table: str | None = None) -> list[dict]:
        """Reclaim tombstoned holes (all tables, or one).  Returns one
        report per table that actually had holes; the background
        compactor (retrieval/live_index.py) calls this off its trigger
        thresholds, operators can call it via the store handle."""
        with self._lock:
            names = [table] if table is not None else sorted(self._tables)
            out = []
            for name in names:
                t = self._tables.get(name)
                if t is not None and len(t.ids) - len(t.rows) > 0:
                    out.append(self._compact_table(t))
            return out

    def _mirror_upsert(self, table: str, docs: Sequence[Doc]) -> None:
        with self._lock:
            dims = [np.asarray(d.vector).size for d in docs if d.vector is not None]
            t = self._tables.get(table)
            if t is None:
                if not dims:
                    return  # vectorless rows never enter the matrix
                t = self._table_for(table, dims[0])
            for doc in docs:
                row = t.rows.get(doc.doc_id)
                if row is not None:
                    self._unindex_row(t, row, self._row_metadata(t, row))
                if doc.vector is None:
                    if row is not None:
                        # memory-store parity: a vectorless re-upsert drops
                        # the row from the matrix but keeps its slot, so a
                        # later vectored re-upsert lands at the same spot
                        t.valid[row] = False
                        t.host[row] = 0.0
                        t.dirty_rows.add(row)
                        self._index_row(t, row, doc.metadata)
                        t.meta_docs[row] = dict(doc.metadata)
                    continue
                if row is None:
                    if len(t.ids) >= t.capacity:
                        if len(t.rows) < len(t.ids):
                            # tombstoned holes exist: reclaim them in
                            # place instead of growing — delete/re-upsert
                            # churn stays inside one capacity bucket
                            self._compact_table(t)
                        if len(t.ids) >= t.capacity:
                            self._grow(t, len(t.ids) + 1)
                    row = len(t.ids)
                    t.ids.append(doc.doc_id)
                    t.rows[doc.doc_id] = row
                v = np.asarray(doc.vector, dtype=np.float32).reshape(-1)
                if v.size != t.dim:
                    raise ValueError(
                        f"vector dim {v.size} != table dim {t.dim} for "
                        f"{doc.doc_id!r} in {table!r}"
                    )
                n = float(np.linalg.norm(v))
                t.host[row] = v / n if n > 0 else 0.0
                t.valid[row] = True
                t.dirty_rows.add(row)
                self._index_row(t, row, doc.metadata)
                t.meta_docs[row] = dict(doc.metadata)
            self._publish_gauges(t)

    def _publish_gauges(self, t: _DeviceTable) -> None:
        INDEX_LIVE_ROWS.labels(table=t.name).set(len(t.rows))
        INDEX_HOLES.labels(table=t.name).set(len(t.ids) - len(t.rows))
        INDEX_CAPACITY.labels(table=t.name).set(t.capacity)

    def _row_metadata(self, t: _DeviceTable, row: int) -> Mapping[str, str]:
        return t.meta_docs.get(row, {})

    def _mirror_delete(self, table: str, doc_ids: Iterable[str]) -> None:
        with self._lock:
            t = self._tables.get(table)
            if t is None:
                return
            for did in doc_ids:
                row = t.rows.pop(did, None)
                if row is None:
                    continue
                self._unindex_row(t, row, self._row_metadata(t, row))
                t.meta_docs.pop(row, None)
                t.ids[row] = ""
                t.valid[row] = False
                t.host[row] = 0.0
                t.dirty_rows.add(row)
            self._publish_gauges(t)

    # ------------------------------------------------------------ device sync

    def _sharding(self, spec):
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding

        return NamedSharding(self.mesh, spec)

    def _sync(self, t: _DeviceTable):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        if t.corpus_dev is None or t.full_sync:
            # device copy is the TRANSPOSE of the host mirror ([dim, cap]):
            # see _build_search — row r lives in column r
            with startup_phase("startup.index_build") as ph:  # the upload to size
                sh = self._sharding(P(None, "dp"))
                arr = jnp.asarray(np.ascontiguousarray(t.host.T))
                t.corpus_dev = jax.device_put(arr, sh) if sh else jax.device_put(arr)
                ph.settles(t.corpus_dev)
            t.dirty_rows, t.full_sync = set(), False
            t.full_syncs += 1
            INDEX_FULL_SYNCS.labels(table=t.name).inc()
        elif t.dirty_rows:
            rows = sorted(t.dirty_rows)
            ub = next_bucket(len(rows), t.capacity, minimum=16)
            idx = np.full(ub, t.capacity, dtype=np.int32)  # OOB pad -> dropped
            idx[: len(rows)] = rows
            vals = np.zeros((t.dim, ub), dtype=np.float32)
            vals[:, : len(rows)] = t.host[rows].T
            t.corpus_dev = self._update_jit(t.corpus_dev, idx, vals)
            t.dirty_rows = set()
        return t.corpus_dev

    # ------------------------------------------------------------ filters

    def _filter_rows(self, t: _DeviceTable, flt: Mapping[str, str] | None) -> np.ndarray:
        """Valid-row mask for one filter, via the inverted metadata index.
        Shredded keys match metadata[k]==v OR the per-member shred entry,
        the exact union _match checks."""
        mask = t.valid[: t.capacity].copy()
        if not flt:
            return mask
        for k, v in flt.items():
            rows = set(t.meta_rows.get((str(k), str(v)), ()))
            if k in SHREDDED_KEYS:
                rows |= t.meta_rows.get((shred_entry(k, v), "1"), set())
            kmask = np.zeros(t.capacity, dtype=bool)
            if rows:
                kmask[sorted(rows)] = True
            mask &= kmask
            if not mask.any():
                break
        return mask

    # ------------------------------------------------------------ search

    def warmup(self, tables: Sequence[str] | None = None) -> int:
        """Compile the full live bucket set: every power-of-two query
        bucket up to ``max_wave`` against each table's current capacity
        bucket, plus the MUTATION ladder — every dirty-row scatter bucket
        ``_sync`` can dispatch (16..capacity) and the compaction repack
        gather — so live query traffic, streamed mutations, and
        background compaction all hit precompiled shapes.  Returns the
        number of compiled search programs afterwards."""
        with self._lock:
            names = list(tables) if tables is not None else sorted(self._tables)
            for name in names:
                t = self._tables.get(name)
                if t is None:
                    continue
                corpus = self._sync(t)
                k = min(self.k_bucket, t.capacity)
                qb = 1
                while True:
                    self._dispatch(t, corpus, np.zeros((qb, t.dim), np.float32),
                                   np.zeros((qb, t.capacity), bool), k)
                    if qb >= self.max_wave:
                        break
                    qb *= 2
                self._warm_mutation(t)
        return self.search_program_cache_size()

    def _warm_mutation(self, t: _DeviceTable) -> None:
        """Run every mutation shape once as an identity op: all-OOB
        scatter indices drop every update, and an arange repack src
        gathers each column onto itself.  Both programs donate the
        corpus, so the returned (unchanged) array replaces it."""
        ub = 16  # _sync's minimum scatter bucket
        while True:
            ub = min(ub, t.capacity)
            idx = np.full(ub, t.capacity, dtype=np.int32)   # all OOB
            vals = np.zeros((t.dim, ub), dtype=np.float32)
            t.corpus_dev = self._update_jit(t.corpus_dev, idx, vals)
            if ub >= t.capacity:
                break
            ub *= 2
        src = np.arange(t.capacity, dtype=np.int32)         # identity gather
        t.corpus_dev = self._repack_jit(t.corpus_dev, src)

    def _dispatch(self, t: _DeviceTable, corpus, queries: np.ndarray,
                  mask: np.ndarray, k: int):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        q = jnp.asarray(queries)
        m = jnp.asarray(mask)
        if self.mesh is not None and self._dp > 1:
            q = jax.device_put(q, self._sharding(P()))
            m = jax.device_put(m, self._sharding(P(None, "dp")))
        with annotate("index.search", queries=int(q.shape[0]), k=k):
            vals, idx = self._search_jit(corpus, q, m, k=k)
            return np.asarray(vals), np.asarray(idx)  # dispatch to result on host

    def search_batch(
        self,
        table: str,
        query_vectors: np.ndarray,
        k: int,
        filters: Sequence[Mapping[str, str] | None] | None = None,
    ) -> list[list[SearchHit]]:
        qs = np.asarray(query_vectors, dtype=np.float32)
        if qs.ndim == 1:
            qs = qs[None, :]
        nq = qs.shape[0]
        if filters is None:
            filters = [None] * nq
        if nq == 0:
            return []
        with self._lock:
            t = self._tables.get(table)
            if t is None:
                # nothing mirrored: the inner store has no vectored rows
                # either (every vectored upsert goes through the wrapper)
                return [[] for _ in range(nq)]
            if k > self.k_bucket or k <= 0:
                # outside the warmed k contract -> host path, counted
                DEVICE_INDEX_SEARCHES.labels(path="fallback").inc(nq)
                return [
                    self.inner.search(table, q, k, filter=f)
                    for q, f in zip(qs, filters)
                ]
            out: list[list[SearchHit]] = []
            for start in range(0, nq, self.max_wave):
                chunk = range(start, min(start + self.max_wave, nq))
                out.extend(self._search_wave(
                    table, t, qs[chunk.start:chunk.stop],
                    [filters[i] for i in chunk], k))
            return out

    def _search_wave(self, table: str, t: _DeviceTable, qs: np.ndarray,
                     filters: Sequence[Mapping[str, str] | None], k: int,
                     ) -> list[list[SearchHit]]:
        nq = qs.shape[0]
        corpus = self._sync(t)
        qb = next_bucket(nq, self.max_wave, minimum=1)
        queries = np.zeros((qb, t.dim), dtype=np.float32)
        mask = np.zeros((qb, t.capacity), dtype=bool)
        norms = np.linalg.norm(qs, axis=1)
        for i in range(nq):
            if norms[i] == 0:
                continue  # zero query: mask stays empty -> no hits (parity)
            queries[i] = qs[i] / norms[i]
            mask[i] = self._filter_rows(t, filters[i])
        k_prog = min(self.k_bucket, t.capacity)
        vals, idx = self._dispatch(t, corpus, queries, mask, k_prog)
        DEVICE_INDEX_SEARCHES.labels(path="device").inc(nq)
        out: list[list[SearchHit]] = []
        for i in range(nq):
            hits: list[SearchHit] = []
            for j in range(k_prog):
                if len(hits) >= k or np.isneginf(vals[i, j]):
                    break
                row = int(idx[i, j])
                doc = self.inner.get(table, t.ids[row])
                if doc is None:  # mirror/inner raced; skip defensively
                    continue
                hits.append(SearchHit(doc=doc, score=float(vals[i, j])))
            out.append(hits)
        return out

    # ------------------------------------------------------------ VectorStore

    def upsert(self, table: str, docs: Sequence[Doc]) -> int:
        n = self.inner.upsert(table, docs)
        self._mirror_upsert(table, docs)
        return n

    def search(
        self,
        table: str,
        query_vector: np.ndarray,
        k: int,
        filter: Mapping[str, str] | None = None,
    ) -> list[SearchHit]:
        return self.search_batch(table, np.asarray(query_vector)[None, :], k,
                                 [filter])[0]

    def find_by_metadata(self, table: str, filter: Mapping[str, str],
                         limit: int = 100) -> list[Doc]:
        return self.inner.find_by_metadata(table, filter, limit)

    def find_by_metadata_batch(self, table: str,
                               filters: Sequence[Mapping[str, str]],
                               limit: int = 100) -> list[list[Doc]]:
        return self.inner.find_by_metadata_batch(table, filters, limit)

    def get(self, table: str, doc_id: str) -> Doc | None:
        return self.inner.get(table, doc_id)

    def count(self, table: str) -> int:
        return self.inner.count(table)

    def delete(self, table: str, doc_ids: Iterable[str]) -> int:
        ids = list(doc_ids)
        n = self.inner.delete(table, ids)
        self._mirror_delete(table, ids)
        return n

    def tables(self) -> list[str]:
        return self.inner.tables()

    def health(self) -> dict:
        h = self.inner.health()
        dev: dict[str, dict] = {}
        with self._lock:
            for name, t in self._tables.items():
                holes = len(t.ids) - len(t.rows)
                dev[name] = {
                    "capacity": t.capacity,
                    "rows": len(t.rows),          # pre-PR13 key, kept
                    "live_rows": len(t.rows),
                    "holes": holes,
                    "dirty_rows": len(t.dirty_rows),
                    "compactions": t.compactions,
                    "full_syncs": t.full_syncs,
                }
                self._publish_gauges(t)
        h["device_index"] = dev
        return h

    def save(self) -> None:
        self.inner.save()
