"""Device-resident batched query engine — Alg 2 as a serving product.

The paper's headline number is query throughput: ρ > 95% of queries resolve
from DL/BL labels alone (Alg 2 lines 6-13) and only the residue needs pruned
BFS.  The host-side driver in ``core.query.query`` leaves that throughput on
the table: it copies the full verdict vector to the host, slices unknowns
with numpy, and re-dispatches one padded BFS chunk at a time.  The engine
keeps the whole pipeline device-resident:

- **backend selected once at construction** — the compiled Pallas
  ``dbl_query`` verdict kernel on TPU, the fused jnp path elsewhere
  (``"pallas-interpret"`` runs the kernel in the Pallas interpreter for CPU
  parity testing; each kernel backend refuses the other kind of host);
  ``streaming=True`` routes kernel backends through the PR-7 double-buffered
  streamed kernels (verdicts + BFS admit planes) instead of the grid forms —
  il-enabled verdict dispatches fall back to the grid kernel with a
  once-per-engine ``StreamILFallbackWarning``, since the streamed verdict
  kernel's fixed copy pipeline takes no interval operands;
- **one fused label phase** — verdicts, unknown-lane compaction (stable
  cumsum/scatter), and endpoint gathers run in a single compiled executable;
  the only host traffic per batch is one int32 scalar (the unknown count);
- **snapshot epochs, cross-epoch BFS coalescing** — every ``submit()`` is
  tagged with the engine's current snapshot epoch; ``insert()`` bumps the
  epoch *without* flushing outstanding submits, and ``flush()`` pools the
  BFS residues of batches from *different* epochs into one right-sized
  dispatch sequence against the newest graph.  Insert-only updates are
  monotone, which is what makes this legal:

  * submit-time label positives/negatives are exact for their snapshot and
    (positives) stay TRUE forever — they never re-enter the pipeline;
  * a coalesced re-check against the newest labels answers stale unknowns
    that have since become label-negative (new-unreachable ⇒ old-
    unreachable) for free;
  * the remaining lanes ride ONE BFS with a per-lane *edge-count cutoff*
    (``core.query.pruned_bfs``): append-only edge arrays mean
    "edge index < m-at-submit-epoch" is exactly the lane's snapshot edge
    set, so "as-of-submit" answers stay bitwise exact.  In "latest"
    consistency the cutoff is lifted and stale label positives from the
    newest labels are answered directly;
- **persistent executables, donated buffers** — jit caches are per-engine
  (``engine_for`` memoizes engines so DBLIndex.query reuses them); on
  TPU/GPU the insert path's label planes are donated, so updates rewrite
  labels in place;
- **optional query-axis sharding** — pass a mesh and the label phase fans
  the query batch out across devices (``launch.sharding.reach_query_
  shardings``), labels replicated.

- **fully-dynamic serving** — ``delete()`` tombstones edges (epoch-versioned
  ``del_at`` marks, no label recomputation) and leaves the index *dirty*;
  while dirty, the verdict phases downgrade every verdict resting on
  positive label evidence (DL positives, theorem-1/2 negatives) to
  "unknown → BFS over live edges", and the BFS drops the DL prune — BL
  negatives and the BL containment prunes stay on (sound under deletion:
  bits are never removed).  Deletes drain in-flight submits first
  (cross-delete coalescing would break the BL prune's coherence argument);
  ``rebuild()`` restores exact labels over the live edges (full Alg 1, or
  the incremental delta repair — ``mode`` passes through to
  ``DBLIndex.rebuild``), compacts tombstones, and re-binds the engine with
  the usual donation-safety rules; a delta rebuild keeps every array shape,
  so the re-bind compiles nothing new.

- **vertex-sharded labels** — construct with ``vertex_mesh=`` (a 1-axis
  ``"vertex"`` mesh) and the engine serves an index whose label planes are
  row-partitioned across devices (per-device label bytes = 1/shards): the
  verdict phase reconstructs only the eight (Q, W) row blocks with one
  psum, the BFS residue runs on row-sharded planes with per-round
  boundary-bit halo exchange, and inserts/rebuilds run the halo fixpoint —
  no label all-gather on any path, answers bitwise equal to the
  replicated engine (``core.planes`` / ``core.distributed``);

- **adaptive flushing** — ``flush_policy="deadline"`` bounds answer latency
  (resolve once the oldest unresolved submit exceeds ``flush_deadline_ms``),
  ``flush_policy="watermark"`` bounds residue pooling (resolve once the
  pooled unknown lanes reach ``flush_watermark``); checked on every submit
  and from ``maybe_flush()`` poll points;

- **AOT cold starts** — ``aot_warmup(index, cache_dir)`` round-trips the
  query-phase executables through a ``jax.export`` disk cache keyed on
  (backend, shapes, jax version), so a restarted process skips tracing and
  recompilation (see ``serve.aot``).

``core.query.query`` is retained verbatim as the reference implementation;
``tests/test_property_engine.py`` / ``tests/test_metamorphic.py`` check the
engine against it and against the dense transitive-closure oracle on random
insert/query interleavings, at every query's submit epoch;
``tests/test_deletions.py`` is the fully-dynamic differential suite.
"""
from __future__ import annotations

import functools
import math
import time
import warnings
import weakref
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import halo as HL
from repro.core import planes as PL
from repro.core import query as Q
from repro.core import update as U
from repro.core.propagate import check_halo_mode, check_plane_repr
from repro.core.dbl import (DBLIndex, LabelSaturationWarning,
                            _saturation_message)
from repro.kernels.dbl_query.ops import (StreamILFallbackWarning,
                                         verdicts_device)
from repro.kernels.bfs_prune.ops import admit_plane as bfs_admit_plane_op

#: supported consistency modes (``"latest-snapshot"`` is an alias)
CONSISTENCY_MODES = ("as-of-submit", "latest")

#: engine-initiated flush policies (``None`` = flush only when asked):
#: "deadline"  — resolve the pipeline once the oldest unresolved submit is
#:               older than ``flush_deadline_ms`` (bounded answer latency);
#: "watermark" — resolve once the pooled BFS residue reaches
#:               ``flush_watermark`` lanes (right-sized dispatches without
#:               unbounded deferral on unknown-heavy streams).
FLUSH_POLICIES = (None, "deadline", "watermark")


def select_backend(backend: str = "auto") -> str:
    """Resolve 'auto' once: the compiled Pallas kernels on TPU, jnp
    elsewhere.  Each kernel backend is tied to its device: ``"pallas"``
    compiles the kernels for a TPU and ``"pallas-interpret"`` (the CPU test
    mode) runs them in the Pallas interpreter, so asking for either on the
    other kind of host is an error, never a silent switch."""
    on_tpu = jax.default_backend() == "tpu"
    if backend == "auto":
        return "pallas" if on_tpu else "jnp"
    if backend not in ("jnp", "pallas", "pallas-interpret"):
        raise ValueError(f"unknown backend {backend!r}")
    if backend == "pallas" and not on_tpu:
        raise ValueError(
            "backend='pallas' compiles the Pallas kernels for a TPU, but "
            f"JAX's default backend is {jax.default_backend()!r}; use "
            "'pallas-interpret' to run them in the interpreter")
    if backend == "pallas-interpret" and on_tpu:
        raise ValueError(
            "backend='pallas-interpret' runs the kernels in the Pallas "
            "interpreter, a CPU test mode; on a TPU use 'pallas'")
    return backend


def select_consistency(mode: str) -> str:
    if mode == "latest-snapshot":
        return "latest"
    if mode not in CONSISTENCY_MODES:
        raise ValueError(f"unknown consistency mode {mode!r}; "
                         f"expected one of {CONSISTENCY_MODES}")
    return mode


def _donation_supported() -> bool:
    return jax.default_backend() in ("tpu", "gpu")


@dataclass
class EngineStats:
    queries: int = 0
    label_answered: int = 0
    bfs_answered: int = 0
    batches: int = 0
    inserts: int = 0
    deletes: int = 0          # delete-batch pairs tombstoned
    rebuilds: int = 0         # lazy label rebuilds (dirty -> clean)
    delta_rebuilds: int = 0   # rebuilds served by the delta (incremental) path
    bfs_dispatches: int = 0
    flushes: int = 0
    policy_flushes: int = 0   # flushes initiated by the adaptive policy
    stale_lanes: int = 0      # residue lanes resolved across an epoch gap
    saturation_events: int = 0  # inserts whose label fixpoint hit max_iters
    # vertex-sharded halo accounting, mirrored from the engine's
    # HaloTelemetry by ``QueryEngine.halo_stats()`` (zero on replicated
    # engines): modeled wire bytes / fixpoint rounds of every halo
    # exchange the engine ran, and how many (pair, round) slots were
    # skipped as all-quiet under the sparse exchange
    halo_bytes: int = 0
    halo_rounds: int = 0
    quiet_pair_rounds: int = 0
    #: per-family prune attribution over every resolved lane: "dl" counts
    #: label positives (Lemma 1 + self-queries), "bl"/"il" count negative
    #: lanes charged to BL containment / interval containment (first
    #: family whose evidence fires, in fused-verdict evaluation order),
    #: "thm" the theorem-1/2 negatives, and "bfs" the residue lanes that
    #: rode a pruned BFS — so sum(prune_hits.values()) == queries.
    prune_hits: dict = field(default_factory=lambda: {
        "dl": 0, "bl": 0, "il": 0, "thm": 0, "bfs": 0})

    def as_dict(self) -> dict:
        rho = self.label_answered / max(self.queries, 1)
        return {"queries": self.queries, "rho": rho,
                "batches": self.batches, "inserts": self.inserts,
                "deletes": self.deletes, "rebuilds": self.rebuilds,
                "delta_rebuilds": self.delta_rebuilds,
                "bfs_dispatches": self.bfs_dispatches,
                "flushes": self.flushes,
                "policy_flushes": self.policy_flushes,
                "stale_lanes": self.stale_lanes,
                "saturation_events": self.saturation_events,
                "halo_bytes": self.halo_bytes,
                "halo_rounds": self.halo_rounds,
                "quiet_pair_rounds": self.quiet_pair_rounds,
                "prune_hits": dict(self.prune_hits)}


class _Pending:
    """Handle for a submitted batch: label phase dispatched, BFS deferred.

    ``lineage``/``epoch``/``m_at_submit`` tag the index snapshot the batch
    observed.  Engine-bound pendings (lineage matches) are resolved against
    the engine's *newest* index with a per-lane edge-count cutoff — the old
    snapshot's buffers are never touched again, so a donated insert can
    consume them while the pending is still in flight."""

    __slots__ = ("engine", "index", "q", "answers", "order",
                 "u_c", "v_c", "n_unknown", "counts",
                 "lineage", "epoch", "m_at_submit", "t_submit",
                 "_result", "_nu", "__weakref__")

    def __init__(self, engine, index, q, answers, order, u_c, v_c, n_unknown,
                 counts=None, lineage=None, epoch=None, m_at_submit=None,
                 t_submit=None):
        self.engine = engine
        self.index = index
        self.q = q
        self.answers = answers
        self.order = order
        self.u_c = u_c
        self.v_c = v_c
        self.n_unknown = n_unknown
        # (4,) int32 device vector: label-phase [dl+, bl-, il-, thm-]
        # attribution, synced lazily at resolve time with everything else
        self.counts = counts
        self.lineage = lineage
        # epoch is serving telemetry (which snapshot the batch observed);
        # resolution keys off m_at_submit — the edge-count cutoff — alone
        self.epoch = epoch
        self.m_at_submit = m_at_submit
        self.t_submit = t_submit        # host clock, for the deadline policy
        self._result = None
        self._nu = None

    @property
    def nu(self) -> int:
        """Unknown-lane count, synced from device ONCE per batch (the one
        int32 D2H the label phase owes) — the watermark policy and the
        flush path share the memo instead of re-blocking per check."""
        if self._nu is None:
            self._nu = min(int(self.n_unknown), self.q)
        return self._nu

    def resolve(self) -> np.ndarray:
        if self._result is None:
            self._result = self.engine._finish(self)
        return self._result


class QueryEngine:
    """Stateless core (``run``) plus optional bound-index serving state
    (``query``/``insert`` mutate the bound index; ``submit``/``flush`` form
    the asynchronous pipeline that rides across inserts)."""

    def __init__(self, index: DBLIndex | None = None, *,
                 bfs_chunk: int = 256, max_iters: int = 256,
                 backend: str = "auto", q_block: int = 512,
                 mesh=None, vertex_mesh=None, bfs_kernel: bool = False,
                 streaming: bool = False,
                 donate: str | bool = "auto",
                 consistency: str = "as-of-submit",
                 frontier_dtype: str = "int8",
                 out_dtype: str = "int8",
                 plane_repr: str = "bool",
                 halo_mode: str = "dense",
                 hub_count: int = 0,
                 halo_caps: tuple | None = None,
                 flush_policy: str | None = None,
                 flush_deadline_ms: float = 25.0,
                 flush_watermark: int = 256):
        if bfs_chunk <= 0 or q_block <= 0:
            raise ValueError("bfs_chunk and q_block must be positive")
        if mesh is not None and vertex_mesh is not None:
            raise ValueError(
                "mesh (query-axis fan-out, labels replicated) and "
                "vertex_mesh (vertex-sharded labels) are mutually "
                "exclusive engine layouts")
        if frontier_dtype not in Q.FRONTIER_DTYPES:
            raise ValueError(f"unknown frontier dtype {frontier_dtype!r}; "
                             f"expected one of {list(Q.FRONTIER_DTYPES)}")
        if frontier_dtype == "packed" and vertex_mesh is not None:
            raise ValueError(
                "frontier_dtype='packed' packs the query-lane axis of the "
                "replicated BFS only; the vertex-sharded residue keeps its "
                "per-lane frontier planes (use 'int8'/'int32')")
        if out_dtype not in ("int8", "int32"):
            raise ValueError(f"unknown verdict out dtype {out_dtype!r}; "
                             "expected 'int8' or 'int32'")
        check_plane_repr(plane_repr)
        check_halo_mode(halo_mode)
        if hub_count < 0:
            raise ValueError("hub_count must be non-negative")
        if halo_caps is not None and (
                not halo_caps or any(int(c) <= 0 for c in halo_caps)):
            raise ValueError("halo_caps must be a non-empty tuple of "
                             "positive bucket capacities (or None = auto)")
        if flush_policy not in FLUSH_POLICIES:
            raise ValueError(f"unknown flush policy {flush_policy!r}; "
                             f"expected one of {FLUSH_POLICIES}")
        if flush_deadline_ms <= 0 or flush_watermark <= 0:
            raise ValueError("flush_deadline_ms and flush_watermark must "
                             "be positive")
        self.bfs_chunk = int(bfs_chunk)
        self.max_iters = int(max_iters)
        self.backend = select_backend(backend)
        self.q_block = int(q_block)
        self.streaming = bool(streaming)
        if self.streaming and self.backend == "jnp":
            raise ValueError(
                "streaming=True routes verdicts and admit planes through "
                "the double-buffered streamed Pallas kernels; construct "
                "with backend='pallas' or 'pallas-interpret'")
        if self.streaming and vertex_mesh is not None:
            raise ValueError(
                "the vertex-sharded layout reconstructs verdict row blocks "
                "with shard_map collectives and never dispatches the "
                "query kernels — streaming=True would be dead there")
        # per-ENGINE latch for the streaming+il grid fallback warning: the
        # ops layer warns per traced shape, which this narrows to exactly
        # one signal per engine instance without muting other engines
        self._stream_il_warned = False
        self.mesh = mesh
        self.vertex_mesh = vertex_mesh
        self.layout = "vertex_sharded" if vertex_mesh is not None \
            else "replicated"
        self.frontier_dtype = frontier_dtype
        self.out_dtype = out_dtype
        self.plane_repr = plane_repr
        # halo-exchange knobs for the vertex-sharded fixpoints (inert on
        # replicated engines, but always part of the engine config — and
        # of the AOT cache key): "sparse" routes every insert/rebuild
        # fixpoint through core.halo's compacted changed-row exchange,
        # hub_count freezes that many top-cut-degree hub vertices on the
        # shard plan for the broadcast lane, halo_caps overrides the
        # power-of-two compaction capacities (None = halo.bucket_caps(H))
        self.halo_mode = halo_mode
        self.hub_count = int(hub_count)
        self.halo_caps = None if halo_caps is None \
            else tuple(int(c) for c in halo_caps)
        self._halo_telemetry = HL.HaloTelemetry()
        self.bfs_kernel = bool(bfs_kernel)
        if self.bfs_kernel and self.backend == "jnp":
            raise ValueError(
                "bfs_kernel=True routes the BFS admit plane through the "
                "bfs_prune Pallas kernel; construct with backend='pallas' "
                "(TPU) or 'pallas-interpret' (CPU)")
        self.consistency = select_consistency(consistency)
        self.flush_policy = flush_policy
        self.flush_deadline_ms = float(flush_deadline_ms)
        self.flush_watermark = int(flush_watermark)
        self._clock = time.monotonic     # monkeypatchable in policy tests
        if donate == "auto":
            donate = _donation_supported() and vertex_mesh is None
        self.donate = bool(donate)
        self.stats = EngineStats()
        self.last_rebuild_info: dict | None = None   # set by rebuild()
        self.aot_cache = None                        # set by aot_warmup()
        # vertex-sharded layout: edge partition + halo routing, rebuilt
        # whenever the bound edge set changes shape (bind/insert/rebuild);
        # _plan_override hands a rebuild's freshly built plan to the index
        # setter so the re-bind does not build it a second time
        self._plan: PL.ShardPlan | None = None
        self._plan_override: PL.ShardPlan | None = None
        # batch shapes are padded to this granule so a serving stream with
        # varying batch sizes maps onto a handful of compiled shapes
        self._granule = math.lcm(self.q_block, self.bfs_chunk)
        # snapshot bookkeeping: lineage distinguishes re-binds (a fresh
        # index genealogy) from in-place epoch bumps (inserts on the bound
        # index); within a lineage, (epoch, edge count) is append-only
        self._lineage = 0
        self._index: DBLIndex | None = None
        self.epoch = 0
        self._m_now = 0
        # weak refs to unresolved engine-tagged submits: a re-bind must
        # resolve them against the lineage they belong to before the engine
        # lets go of it (older snapshots' buffers may already be donated)
        self._inflight: list = []
        # deferred saturation flags (one () bool per insert); drained at
        # flush boundaries so the insert path never forces a host sync
        self._sat_flags: list = []
        self._build_executables()
        if index is not None:
            self.index = index

    # ------------------------------------------------------------ binding
    @property
    def index(self) -> DBLIndex | None:
        return self._index

    @index.setter
    def index(self, idx: DBLIndex | None):
        """(Re-)bind a serving index: starts a new snapshot lineage.

        In-flight submits from the outgoing lineage are resolved first,
        against its newest snapshot with their as-of-submit cutoffs — they
        can only legally be resolved within that lineage (under donation,
        older snapshots' buffers are already consumed), and after the
        re-bind the engine no longer owns it.  A re-bind therefore never
        changes answers — it only bounds how far coalescing can defer."""
        if self._index is not None:
            self._drain_inflight()    # also clears the inflight list
        self._lineage += 1
        # consume the override unconditionally: whatever happens below, a
        # stale plan must never survive to a LATER re-bind
        override, self._plan_override = self._plan_override, None
        if idx is not None and self.vertex_mesh is not None:
            from repro.core import distributed as D
            idx = D.place_vertex_sharded(idx, self.vertex_mesh)
            m_idx = int(np.asarray(idx.graph.m))
            if (override is not None and override.m == m_idx
                    and override.n_cap == idx.n_cap):
                # rebuild() already built routing tables for exactly this
                # index's edges — don't pay the O(m) plan pass twice.  The
                # (m, n_cap) check guards the handoff: the insert path now
                # EXTENDS whatever plan is installed here, so adopting a
                # plan for a different edge prefix would corrupt every
                # subsequent routing table, not just slow one query down.
                self._plan = override
            else:
                self._plan = PL.shard_plan(idx.graph.src, idx.graph.dst,
                                           m_idx, idx.n_cap,
                                           self.vertex_mesh,
                                           hub_count=self.hub_count)
        self._index = idx
        if idx is not None:
            self.epoch = int(np.asarray(idx.epoch))
            self._m_now = int(idx.graph.m)
        else:
            self.epoch = 0
            self._m_now = 0
            self._plan = None

    def _drain_inflight(self):
        """Resolve every unresolved submit of the CURRENT lineage (with its
        as-of-submit cutoffs) and forget the inflight list.  Called before a
        re-bind, a rebuild, and every delete batch: tombstones change which
        edges post-submit label updates propagate over, so the BL-containment
        prune (and hence coalescing) is only sound while every pooled lane
        shares the dispatch's tombstone set."""
        stale = self._unresolved_inflight()
        if stale:
            self.flush(stale)
        self._inflight = []

    # ------------------------------------------------------------ compile
    def _build_executables(self):
        backend = self.backend
        q_block = self.q_block
        interpret = backend == "pallas-interpret"
        max_iters = self.max_iters
        use_bfs_kernel = self.bfs_kernel
        streaming = self.streaming
        vertex_mesh = self.vertex_mesh
        frontier_dtype = self.frontier_dtype
        plane_repr = self.plane_repr
        # the verdict kernel's store dtype is a baked knob (AOT-keyed):
        # int8 is the lean default, int32 matches accumulator-width stores
        out_dtype = jnp.int8 if self.out_dtype == "int8" else jnp.int32

        def _d_cut_vec(d_stale, shape):
            """Per-lane tombstone-cutoff operand from a traced dirty scalar:
            0 < 1 when dirty, 1 >= 1 when clean — one compiled executable
            serves both states (the flag flips at delete/rebuild time)."""
            return jnp.broadcast_to(
                jnp.where(d_stale, jnp.int32(0), jnp.int32(1)), shape)

        def verdict_streaming(il):
            """Trace-time effective ``streaming`` flag for a verdict
            dispatch: the streamed kernel takes no interval operands, so
            il-enabled dispatches route to the grid kernel here — warning
            once per engine with the ops layer's dedicated category, then
            handing ``streaming=False`` down so the per-trace ops warning
            stays silent."""
            if streaming and il is not None:
                if not self._stream_il_warned:
                    self._stream_il_warned = True
                    warnings.warn(
                        "streaming engine bound to an il-enabled index: "
                        "verdict dispatches fall back to the grid kernel "
                        "(bitwise-identical verdicts); the streamed "
                        "dbl_query kernel takes no interval-family "
                        "operands", StreamILFallbackWarning, stacklevel=2)
                return False
            return streaming

        def label_phase(p: Q.PackedLabels, il, u, v, d_stale):
            """Verdicts + on-device compaction of unknown lanes, fused.

            Compaction is an O(Q) cumsum/scatter (not a sort): unknown lanes
            keep submission order at slots [0, nu), known lanes fill the
            tail, and endpoints are scattered straight into compacted
            position so no second gather pass is needed.

            ``d_stale`` (() bool) is the index's dirty flag: with pending
            tombstones only self-positives and BL negatives answer from
            labels; DL positives / theorem negatives join the unknown lanes
            and ride the live-edge BFS.

            ``il`` is the index's ``(il_in, il_out)`` interval-family
            operand (or None — the fused-core default, which traces the
            exact pre-registry program): its containment violations join
            the negative rules on tombstone-clean dispatches and the
            per-family attribution counts get an "il" column.

            Vertex-sharded layout: the verdicts read only the eight (Q, W)
            row blocks — plus the four interval rows when enabled —
            reconstructed from the row-partitioned planes by ONE psum of
            per-shard masked gathers — all-gather-free (the planes never
            move; see ``core.planes.sharded_rows``)."""
            if vertex_mesh is not None:
                rows = PL.sharded_rows(p, u, v, mesh=vertex_mesh)
                il_rows = None if il is None else \
                    PL.sharded_il_rows(il, u, v, mesh=vertex_mesh)
                verd = Q.cut_verdicts_rows(rows, u, v, jnp.int32(1),
                                           jnp.int32(0), ~d_stale,
                                           il_rows=il_rows)
            elif backend in ("pallas", "pallas-interpret"):
                verd = verdicts_device(
                    p, u, v,
                    jnp.full(u.shape, Q.FRESH_CUT, jnp.int32), jnp.int32(0),
                    _d_cut_vec(d_stale, u.shape), jnp.int32(1), il,
                    q_block=q_block, interpret=interpret,
                    out_dtype=out_dtype, streaming=verdict_streaming(il))
                rows = Q.gather_rows(p, u, v)
                il_rows = Q.gather_il_rows(il, u, v)
            else:
                rows = Q.gather_rows(p, u, v)
                il_rows = Q.gather_il_rows(il, u, v)
                verd = Q.cut_verdicts_rows(rows, u, v, jnp.int32(1),
                                           jnp.int32(0), ~d_stale,
                                           il_rows=il_rows)
            counts = Q.verdict_counts(verd, rows, il_rows)
            unknown = verd == jnp.int8(-1)
            n_unknown = unknown.sum().astype(jnp.int32)
            rank_u = jnp.cumsum(unknown.astype(jnp.int32))
            rank_k = jnp.cumsum((~unknown).astype(jnp.int32))
            pos = jnp.where(unknown, rank_u - 1, n_unknown + rank_k - 1)
            q = u.shape[0]
            lanes = jnp.arange(q, dtype=jnp.int32)
            order = jnp.zeros(q, jnp.int32).at[pos].set(lanes)
            u_c = jnp.zeros(q, jnp.int32).at[pos].set(u)
            v_c = jnp.zeros(q, jnp.int32).at[pos].set(v)
            answers = verd == jnp.int8(1)
            return answers, order, u_c, v_c, n_unknown, counts

        def make_coalesced_phase(chunk: int):
            def coalesced(g: Q.Graph, p: Q.PackedLabels, il, uu, vv, m_cut,
                          d_stale):
                """One (chunk,)-shaped epoch-coalesced residue dispatch.

                Fuses the monotone label re-check against the NEWEST labels
                with the per-lane edge-count-cutoff BFS, so a flush costs
                ceil(total/chunk) dispatches of ONE compiled shape no matter
                how many epochs the pooled lanes span:

                - re-check verdict 0 → answer False (new-unreachable ⇒
                  old-unreachable, valid for every consistency mode);
                - re-check verdict +1 → answer True; ``cut_verdicts`` has
                  already downgraded stale-lane positives to unknown when
                  the lane's cutoff demands as-of-submit semantics, so a
                  surviving +1 is always a legal answer;
                - still-unknown lanes run the cutoff BFS (stale lanes lose
                  the DL prune inside, which keeps it sound).

                ``d_stale`` (() bool): the group's index carries un-rebuilt
                tombstones.  The re-check keeps only self-positives and BL
                negatives, the BFS drops the DL prune for every lane, and
                traversal sees only live edges (``edge_mask``).  The engine
                drains in-flight submits before tombstoning, so all pooled
                lanes share the dispatch's tombstone set and the edge-count
                cutoffs stay exact under it.

                Dead lanes (padding / answered) carry an out-of-range
                source so they never extend the BFS while-loop.

                ``il`` (or None) joins the re-check the same way it joins
                the label phase — insert-monotone, so coalesced stale lanes
                keep it without an edge-count gate — and threads into the
                residue BFS admit planes under the tombstone-clean gate."""
                n_cap = p.dl_in.shape[0]
                live_lane = uu < jnp.int32(n_cap)
                uu_safe = jnp.minimum(uu, jnp.int32(n_cap - 1))
                if backend in ("pallas", "pallas-interpret"):
                    verd = verdicts_device(
                        p, uu_safe, vv, m_cut, g.m,
                        _d_cut_vec(d_stale, uu.shape), jnp.int32(1), il,
                        q_block=min(q_block, chunk),
                        interpret=interpret, out_dtype=out_dtype,
                        streaming=verdict_streaming(il))
                else:
                    verd = Q.cut_verdicts(p, uu_safe, vv, m_cut, g.m,
                                          ~d_stale, il=il)
                need = live_lane & (verd == jnp.int8(-1))
                uu2 = jnp.where(need, uu, jnp.int32(n_cap))
                admit = None
                if use_bfs_kernel:
                    admit = bfs_admit_plane_op(
                        p, jnp.minimum(uu2, jnp.int32(n_cap - 1)), vv,
                        m_cut, g.m,
                        _d_cut_vec(d_stale, uu.shape), jnp.int32(1),
                        il, ~d_stale,
                        n_block=min(1024, max(8, n_cap)),
                        q_block=min(128, chunk), interpret=interpret,
                        out_dtype=jnp.int8, streaming=streaming)
                hit = Q.pruned_bfs(g, p, uu2, vv, admit, m_cut, ~d_stale,
                                   il, n_cap=n_cap, max_iters=max_iters,
                                   frontier_dtype=frontier_dtype)
                return ((verd == jnp.int8(1)) & live_lane) | hit
            return coalesced

        def make_coalesced_sharded(chunk: int):
            def coalesced(g, p: Q.PackedLabels, il, uu, vv, m_cut, d_stale,
                          e_slot, e_recv, e_gid, e_valid, h_send, h_valid,
                          e_start, e_tail):
                """Sharded twin of the coalesced phase: the re-check reads
                psum-reconstructed row blocks, the residue BFS runs on
                row-partitioned frontier/admit planes with per-round
                boundary-bit halo exchange — the label planes never leave
                their shards (no all-gather; see ``core.planes``).  The
                plan's routing arrays ride in as operands so insert-time
                plan rebuilds reuse this executable as long as the padded
                extents hold.

                ``il`` joins the re-check via psum-reconstructed interval
                rows.  The residue BFS deliberately skips the interval
                admit term: the prune is *sound* (a pruned vertex can reach
                no lane target), so which lanes hit is bitwise unchanged
                with or without it — the sharded loop keeps its bit-plane
                halo machinery untouched."""
                from repro.core.graph import edge_mask
                n_cap = p.dl_in.shape[0]
                live_lane = uu < jnp.int32(n_cap)
                uu_safe = jnp.minimum(uu, jnp.int32(n_cap - 1))
                rows = PL.sharded_rows(p, uu_safe, vv, mesh=vertex_mesh)
                il_rows = None if il is None else \
                    PL.sharded_il_rows(il, uu_safe, vv, mesh=vertex_mesh)
                verd = Q.cut_verdicts_rows(rows, uu_safe, vv, m_cut, g.m,
                                           ~d_stale, il_rows=il_rows)
                need = live_lane & (verd == jnp.int8(-1))
                uu2 = jnp.where(need, uu, jnp.int32(n_cap))
                plan = PL.ShardPlan(
                    vertex_mesh, n_cap, 0,
                    PL._DirPlan(e_slot, e_recv, e_gid, e_valid, h_send,
                                h_valid, e_start, e_tail), None)
                hit = PL.sharded_pruned_bfs(
                    plan, p, rows, uu2, vv, edge_mask(g), m_cut, g.m,
                    ~d_stale, max_iters=max_iters,
                    frontier_dtype=frontier_dtype)
                return ((verd == jnp.int8(1)) & live_lane) | hit
            return coalesced

        if vertex_mesh is not None:
            make_coalesced_phase = make_coalesced_sharded

        if self.mesh is not None:
            from repro.launch.sharding import reach_query_shardings
            qsh, repl = reach_query_shardings(self.mesh)
            label_shardings = Q.PackedLabels(repl, repl, repl, repl)
            # the il operand is a (None | (il_in, il_out)) pytree; `repl`
            # acts as a prefix spec, so the None (leafless) default and the
            # replicated interval planes both satisfy it
            self._label_phase = jax.jit(
                label_phase,
                in_shardings=(label_shardings, repl, qsh, qsh, repl))
        else:
            self._label_phase = jax.jit(label_phase)

        # one jitted coalesced executable per power-of-two chunk bucket, so
        # a flush with 3 pooled unknowns costs a 16-lane dispatch, not a
        # 256-lane one; totals beyond the cap loop at the cap so any flush
        # still uses exactly ONE compiled BFS shape
        self._coal_phases = {c: jax.jit(make_coalesced_phase(c))
                             for c in self._chunk_buckets()}

        def insert_impl(g, dl_in, dl_out, bl_in, bl_out, ns, nd, epoch):
            n_cap = dl_in.shape[0]
            g2, a, b, c, d, iters, epoch2 = U.insert_and_update(
                g, dl_in, dl_out, bl_in, bl_out, ns, nd, epoch,
                n_cap=n_cap, max_iters=max_iters, plane_repr=plane_repr)
            sat = U.saturated(iters, max_iters)
            return g2, a, b, c, d, Q.pack_labels(a, b, c, d), epoch2, sat

        donate_ins = (0, 1, 2, 3, 4) if self.donate else ()
        self._insert_fn = jax.jit(insert_impl, donate_argnums=donate_ins)
        # delete path: tombstone + epoch bump only, labels untouched
        self._delete_fn = jax.jit(
            lambda g, ds, dd, e: U.delete_and_mark(g, ds, dd, e),
            donate_argnums=(0,) if self.donate else ())

    def _coalesced_extra_args(self) -> tuple:
        """Trailing operands for a coalesced-phase call: the vertex-sharded
        layout threads its plan's routing arrays through (so the compiled
        executable survives plan rebuilds); replicated has none."""
        if self.vertex_mesh is None:
            return ()
        dp = self._plan.fwd
        return (dp.e_slot, dp.e_recv, dp.e_gid, dp.e_valid, dp.h_send,
                dp.h_valid, dp.e_start, dp.e_tail)

    def _chunk_buckets(self):
        sizes, c = [], 16
        while c < self.bfs_chunk:
            sizes.append(c)
            c *= 2
        sizes.append(self.bfs_chunk)
        return sizes

    def _bucket_for(self, nu: int) -> int:
        for c in self._chunk_buckets():
            if nu <= c:
                return c
        return self.bfs_chunk

    # ------------------------------------------------------------ queries
    def _pad_queries(self, u, v):
        u = np.asarray(u, np.int32).ravel()
        v = np.asarray(v, np.int32).ravel()
        q = u.shape[0]
        qp = max(self._granule, -(-q // self._granule) * self._granule)
        if qp != q:
            # pad with self-queries on vertex 0: verdict +1, never unknown
            u = np.pad(u, (0, qp - q))
            v = np.pad(v, (0, qp - q))
        return jnp.asarray(u), jnp.asarray(v), q

    def submit(self, index: DBLIndex, u, v) -> _Pending:
        """Dispatch the fused label phase; BFS resolution is deferred until
        ``resolve()``/``flush()`` so streams of batches pipeline on device.

        Submits against the engine's bound index are tagged with the current
        snapshot epoch and edge count; they survive subsequent ``insert()``
        calls and are later resolved against the newest snapshot with a
        per-lane edge-count cutoff (exact as-of-submit answers) or without
        one (latest consistency)."""
        if self.vertex_mesh is not None and index is not self._index:
            # fail at submit, not data-dependently at flush: resolving a
            # foreign snapshot's residue needs a shard plan for ITS edges,
            # and the engine's plan is lineage-scoped
            raise ValueError(
                "vertex-sharded engines serve only their bound index; "
                "bind the snapshot first (engine.index = idx)")
        uj, vj, q = self._pad_queries(u, v)
        if self.mesh is not None:
            from repro.launch.sharding import reach_query_shardings
            qsh, _ = reach_query_shardings(self.mesh)
            uj = jax.device_put(uj, qsh)
            vj = jax.device_put(vj, qsh)
        answers, order, u_c, v_c, n_unknown, counts = self._label_phase(
            index.packed, index.il, uj, vj, index.dirty_flag)
        if self._index is not None and index is self._index:
            tag = dict(lineage=self._lineage, epoch=self.epoch,
                       m_at_submit=self._m_now)
        else:
            tag = {}
        pend = _Pending(self, index, q, answers, order, u_c, v_c, n_unknown,
                        counts, t_submit=self._clock(), **tag)
        if tag:
            self._inflight = [r for r in self._inflight
                              if r() is not None and r()._result is None]
            self._inflight.append(weakref.ref(pend))
            self.maybe_flush()
        return pend

    # ------------------------------------------------- adaptive flushing
    def _unresolved_inflight(self) -> list:
        return [p for p in (r() for r in self._inflight)
                if p is not None and p._result is None
                and p.lineage == self._lineage]

    def flush_due(self) -> bool:
        """Whether the adaptive policy wants the pipeline resolved NOW.

        - ``"deadline"``: the oldest unresolved submit has been in flight
          longer than ``flush_deadline_ms`` — deferral is only free until
          someone is waiting on an answer;
        - ``"watermark"``: the pooled BFS residue reached
          ``flush_watermark`` lanes — the dispatch is already right-sized,
          further pooling just adds latency.  (Costs one int32 host sync
          per unresolved batch; the label phase has to surface the unknown
          count anyway at resolve time.)
        """
        if self.flush_policy is None:
            return False
        pending = self._unresolved_inflight()
        if not pending:
            return False
        if self.flush_policy == "deadline":
            oldest = min(p.t_submit for p in pending)
            return (self._clock() - oldest) * 1e3 >= self.flush_deadline_ms
        return sum(p.nu for p in pending) >= self.flush_watermark

    def maybe_flush(self) -> bool:
        """Run the adaptive flush policy once (called on every submit; the
        serving layer also calls it from its poll points so a deadline can
        fire without new traffic).  Returns True when a flush ran."""
        if not self.flush_due():
            return False
        self.flush(self._unresolved_inflight())
        self.stats.policy_flushes += 1
        return True

    def _current_lineage(self, p: _Pending) -> bool:
        """True iff ``p`` was submitted against THIS engine's live lineage
        (the engine-identity check matters: lineage counters are per-engine,
        so a foreign engine's pending must fall back to its own index)."""
        return (p.engine is self and p.lineage is not None
                and p.lineage == self._lineage and self._index is not None)

    def _finish(self, pend: _Pending) -> np.ndarray:
        results: dict[int, np.ndarray] = {}
        self._finish_group([(0, pend)], results, self.consistency,
                           self._current_lineage(pend))
        return results[0]

    def flush(self, pendings, *, consistency: str | None = None) -> list:
        """Resolve submitted batches together, coalescing their BFS residues
        ACROSS snapshot epochs.

        Engine-bound pendings — even ones submitted before intervening
        ``insert()`` calls — pool their unknown lanes into one right-sized
        padded chunk sequence against the NEWEST index, so K micro-batches
        spanning E epochs cost ~one BFS instead of K (or E): each dispatch
        pays a fixed cost plus an iteration tail set by its slowest lane,
        so merging residues is far cheaper than running them separately.
        Per-lane edge-count cutoffs keep as-of-submit answers bitwise exact;
        ``consistency="latest"`` lifts the cutoffs and answers every lane
        against the newest snapshot instead.  The compacted endpoint
        buffers cross to the host to be pooled (bounded by the padded batch
        sizes); the re-check + BFS run on device."""
        mode = select_consistency(consistency or self.consistency)
        results: dict[int, np.ndarray] = {}
        groups: dict[tuple, list] = {}
        for i, p in enumerate(pendings):
            if p._result is not None:
                results[i] = p._result
                continue
            if self._current_lineage(p):
                key = ("lineage", self._lineage)
            else:
                key = ("index", id(p.index.packed.dl_in))
            groups.setdefault(key, []).append((i, p))
        for key, grp in groups.items():
            self._finish_group(grp, results, mode, key[0] == "lineage")
        self.stats.flushes += 1
        if self._sat_flags:
            self.check_saturation()   # flush already syncs; piggy-back here
        return [results[i] for i in range(len(pendings))]

    def _finish_group(self, grp, results, mode, engine_group):
        infos = [(i, p, p.nu) for i, p in grp]   # p.nu memoizes the sync
        total = sum(nu for _, _, nu in infos)
        hits_all = np.zeros(0, np.bool_)
        if total:
            index = self._index if engine_group else grp[0][1].index
            n_cap = index.packed.dl_in.shape[0]
            uu = np.concatenate([np.asarray(p.u_c)[:nu]
                                 for _, p, nu in infos if nu])
            vv = np.concatenate([np.asarray(p.v_c)[:nu]
                                 for _, p, nu in infos if nu])
            if engine_group and mode == "as-of-submit":
                cuts = np.concatenate([
                    np.full(nu, p.m_at_submit, np.int32)
                    for _, p, nu in infos if nu])
                self.stats.stale_lanes += int((cuts < self._m_now).sum())
            else:
                # latest consistency / foreign snapshot group: every lane
                # sees the group's full edge set and keeps the DL prune
                cuts = np.full(total, Q.FRESH_CUT, np.int32)
            chunk = (self.bfs_chunk if total > self.bfs_chunk
                     else self._bucket_for(total))
            pad = -total % chunk
            if pad:
                # dead lanes: out-of-range source -> empty frontier; fresh
                # cutoff so they never ride the stale path
                uu = np.concatenate([uu, np.full(pad, n_cap, np.int32)])
                vv = np.concatenate([vv, np.zeros(pad, np.int32)])
                cuts = np.concatenate([cuts,
                                       np.full(pad, Q.FRESH_CUT, np.int32)])
            fn = self._coal_phases[chunk]
            d_stale = jnp.asarray(index.dirty_flag)
            extra = self._coalesced_extra_args() if engine_group else ()
            if self.vertex_mesh is not None and not engine_group:
                raise ValueError(
                    "vertex-sharded engines resolve only batches submitted "
                    "against their bound index (the shard plan is "
                    "lineage-scoped)")
            hit_parts = []
            for start in range(0, total, chunk):
                hit_parts.append(fn(index.graph, index.packed, index.il,
                                    jnp.asarray(uu[start:start + chunk]),
                                    jnp.asarray(vv[start:start + chunk]),
                                    jnp.asarray(cuts[start:start + chunk]),
                                    d_stale, *extra))
                self.stats.bfs_dispatches += 1
            # all chunks are enqueued before the first D2H forces a wait
            hits_all = np.concatenate([np.asarray(h)
                                       for h in hit_parts])[:total]
        off = 0
        for i, p, nu in infos:
            ans = np.array(p.answers)      # writable host copy
            if nu:
                order = np.asarray(p.order)[:nu]
                ans[order] = hits_all[off:off + nu]
                off += nu
            out = ans[:p.q]
            p._result = out
            results[i] = out
            self.stats.queries += p.q
            self.stats.batches += 1
            self.stats.bfs_answered += nu
            self.stats.label_answered += p.q - nu
            if p.counts is not None:
                # padding lanes are vertex-0 self-queries: always label
                # positives, charged to "dl" on device — back them out so
                # the attribution covers exactly the p.q real lanes
                dl, bl, il, thm = (int(x) for x in np.asarray(p.counts))
                pad = int(np.asarray(p.answers).shape[0]) - p.q
                ph = self.stats.prune_hits
                ph["dl"] += dl - pad
                ph["bl"] += bl
                ph["il"] += il
                ph["thm"] += thm
                ph["bfs"] += nu

    def run(self, index: DBLIndex, u, v, *, return_stats: bool = False):
        """Full Alg 2 on ``index`` for one batch; returns (Q,) np.bool_."""
        q = int(np.asarray(u).size)
        if q == 0:
            ans = np.zeros(0, np.bool_)
            return (ans, {"rho": 1.0, "n_bfs": 0}) if return_stats else ans
        pend = self.submit(index, u, v)
        ans = pend.resolve()
        if return_stats:
            nu = min(int(pend.n_unknown), q)
            return ans, {"rho": 1.0 - nu / q, "n_bfs": nu}
        return ans

    # ------------------------------------------------------ bound serving
    def query(self, u, v, *, return_stats: bool = False):
        if self._index is None:
            raise ValueError("engine has no bound index; use run()")
        return self.run(self._index, u, v, return_stats=return_stats)

    def insert(self, new_src, new_dst) -> DBLIndex:
        """Insert edges into the bound index (Alg 3), bumping the snapshot
        epoch.  Outstanding submits are NOT flushed: they are tagged with
        their submit epoch and will be resolved against the newest snapshot
        with per-lane cutoffs, so mixed insert/query streams no longer
        serialize on index mutations.  With donation on (TPU/GPU) the
        previous snapshot's label planes are consumed in place — the engine
        owns its index; callers must not retain old references."""
        if self._index is None:
            raise ValueError("engine has no bound index; use run()")
        idx = self._index
        ns = jnp.asarray(np.asarray(new_src, np.int32))
        nd = jnp.asarray(np.asarray(new_dst, np.int32))
        if self.vertex_mesh is not None:
            from repro.core import distributed as D
            # sharded Alg-3: psum'd seed rows + halo fixpoint; the plan is
            # extended to cover the appended edges (host-side routing
            # tables — the label planes stay put on their shards)
            idx2, self._plan, sat = D.insert_vertex_sharded(
                idx, self._plan, ns, nd, max_iters=self.max_iters,
                check="defer", plane_repr=self.plane_repr,
                halo_mode=self.halo_mode, halo_caps=self.halo_caps,
                telemetry=self._halo_telemetry)
            self._index = idx2._replace(epoch=jnp.int32(self.epoch + 1))
        else:
            g2, a, b, c, d, packed, epoch2, sat = self._insert_fn(
                idx.graph, idx.dl_in, idx.dl_out, idx.bl_in, idx.bl_out,
                ns, nd, jnp.int32(self.epoch))
            il_kw = {}
            if idx.il_in is not None:
                # plug-in family maintenance rides the same Alg-3 batch:
                # min-monoid seed + fixpoint over the already-extended
                # graph (one executable per family; planes not donated —
                # they are int32 rank planes, tiny next to the bit planes)
                il_in, il_out, it_il = U.insert_update_plugin(
                    "il", g2, idx.il_in, idx.il_out, ns, nd,
                    n_cap=idx.n_cap, max_iters=self.max_iters)
                il_kw = dict(il_in=il_in, il_out=il_out)
                sat = sat | U.saturated(it_il, self.max_iters)
            # direct field write: an insert advances the epoch WITHIN the
            # current lineage (the property setter would start a new one)
            self._index = idx._replace(
                graph=g2, dl_in=a, dl_out=b, bl_in=c, bl_out=d,
                packed=packed, epoch=epoch2,
                saturated=jnp.asarray(idx.saturated) | sat, **il_kw)
        self._sat_flags.append(sat)   # checked lazily at flush boundaries
        self.epoch += 1
        self._m_now += int(ns.size)
        self.stats.inserts += int(ns.size)
        return self._index

    def delete(self, del_src, del_dst) -> DBLIndex:
        """Tombstone every live edge matching a (src, dst) pair — NO label
        recomputation.  The bound index goes (or stays) *dirty*: until the
        next ``rebuild()``, label positives and theorem negatives downgrade
        to live-edge BFS while BL negatives keep answering from labels.

        Outstanding submits ARE drained first (unlike ``insert``): label
        maintenance after the delete propagates over a different live edge
        set than the one the in-flight lanes observed, which breaks the
        BL-containment prune's coherence argument for those lanes — so
        cross-DELETE coalescing is unsound, and deletes (rare next to
        inserts) pay the drain instead of every query paying the prune."""
        if self._index is None:
            raise ValueError("engine has no bound index; use run()")
        self._drain_inflight()
        idx = self._index
        ds = jnp.asarray(np.asarray(del_src, np.int32))
        dd = jnp.asarray(np.asarray(del_dst, np.int32))
        g2, epoch2 = self._delete_fn(idx.graph, ds, dd,
                                     jnp.int32(self.epoch))
        self._index = idx._replace(graph=g2, epoch=epoch2)
        if self.vertex_mesh is not None:
            # keep one sharding flavor per leaf (see insert_vertex_sharded)
            from repro.core import distributed as D
            self._index = D.place_vertex_sharded(self._index,
                                                 self.vertex_mesh)
        self.epoch += 1
        self.stats.deletes += int(ds.size)
        return self._index

    def rebuild(self, **build_kw) -> DBLIndex:
        """Lazy label rebuild over the live edge set (clears the dirty
        state, compacts tombstones by default).  ``mode`` passes through to
        ``DBLIndex.rebuild`` ("full" default / "delta" / "auto"); whichever
        path ran is recorded in ``last_rebuild_info`` and the delta counter.
        A delta rebuild keeps every array shape (n_cap, k, m_cap), so the
        re-bind compiles nothing new — the dispatch-shape budget survives.
        Re-binds the engine to the rebuilt index, which resolves in-flight
        submits against the outgoing lineage first — the same
        donation-safety rules as any re-bind."""
        if self._index is None:
            raise ValueError("engine has no bound index; use run()")
        build_kw.setdefault("max_iters", self.max_iters)
        build_kw.setdefault("plane_repr", self.plane_repr)
        if self.vertex_mesh is not None:
            from repro.core import distributed as D
            build_kw.setdefault("halo_mode", self.halo_mode)
            build_kw.setdefault("halo_caps", self.halo_caps)
            build_kw.setdefault("telemetry", self._halo_telemetry)
            new_idx, plan, info = D.rebuild_vertex_sharded(
                self._index, self._plan, mesh=self.vertex_mesh, **build_kw)
            self._plan_override = plan   # setter adopts it (no second pass)
            self.index = new_idx         # property setter: drain + re-bind
        else:
            new_idx, info = self._index.rebuild_info(**build_kw)
            self.index = new_idx      # property setter: drain + new lineage
        self.stats.rebuilds += 1
        if info["mode"] == "delta":
            self.stats.delta_rebuilds += 1
        self.last_rebuild_info = info
        return new_idx

    def halo_stats(self) -> dict:
        """Drain the halo telemetry (syncing any dense-mode pending round
        counts) and mirror the headline numbers into ``stats``.  Returns
        the full accounting dict — modeled wire bytes, round counts by
        transport regime, quiet/non-quiet pair-round counters."""
        d = self._halo_telemetry.as_dict()
        self.stats.halo_bytes = d["halo_bytes"]
        self.stats.halo_rounds = d["halo_rounds"]
        self.stats.quiet_pair_rounds = d["quiet_pair_rounds"]
        return d

    def check_saturation(self, *, warn: bool = True) -> int:
        """Drain the deferred per-insert saturation flags (syncs them) and
        return how many insert batches saturated; optionally warns.  Called
        automatically at every ``flush()``."""
        flags, self._sat_flags = self._sat_flags, []
        n = sum(bool(np.asarray(f)) for f in flags)
        if n:
            self.stats.saturation_events += n
            if warn:
                warnings.warn(_saturation_message(self.max_iters),
                              LabelSaturationWarning, stacklevel=2)
        return n

    # ------------------------------------------------------------- AOT
    def aot_warmup(self, index: DBLIndex, cache_dir, *,
                   batch_sizes=(1,), bfs_buckets=None) -> "QueryEngine":
        """Warm the query-phase executables from an AOT disk cache
        (``jax.export``), keyed on (backend, input avals, jax version):
        hits swap deserialized executables in — cold starts skip tracing
        and recompilation entirely; misses export the freshly compiled
        executables so the next process hits.  Query answers are bitwise
        identical either way.  Replicated layout only: shard_map
        collectives bake in a device assignment a restarted process cannot
        guarantee, so sharded/mesh engines refuse."""
        from repro.serve.aot import AOTCache, ShapeDispatcher
        if self.vertex_mesh is not None or self.mesh is not None:
            raise ValueError("the AOT cache supports the replicated "
                             "single-process layout only")
        cache = AOTCache(cache_dir)
        self.aot_cache = cache
        # every engine knob the compiled executables bake in beyond their
        # input avals MUST be in the key — a hit under different knobs
        # would silently serve the old semantics (e.g. a smaller max_iters
        # truncating BFS lanes into false negatives).  The enabled label
        # families are part of that contract: the interval planes change
        # the input avals, but dim-equal planes from a different rank seed
        # (or a families flip at equal shapes) would alias without the
        # explicit (families, il_dim, il_seed) triple in the blob.
        config = {"max_iters": self.max_iters, "q_block": self.q_block,
                  "bfs_chunk": self.bfs_chunk, "bfs_kernel": self.bfs_kernel,
                  "streaming": self.streaming,
                  "frontier_dtype": self.frontier_dtype,
                  "out_dtype": self.out_dtype,
                  "plane_repr": self.plane_repr,
                  "halo_mode": self.halo_mode,
                  "hub_count": self.hub_count,
                  "halo_caps": None if self.halo_caps is None
                  else list(self.halo_caps),
                  "families": list(index.families),
                  "il_dim": index.il_dim,
                  "il_seed": None if index.il_seed is None
                  else int(np.asarray(index.il_seed))}
        if not isinstance(self._label_phase, ShapeDispatcher):
            self._label_phase = ShapeDispatcher(self._label_phase)
        n_cap = index.packed.dl_in.shape[0]
        for q in batch_sizes:
            qp = max(self._granule, -(-int(q) // self._granule)
                     * self._granule)
            args = (index.packed, index.il, jnp.zeros(qp, jnp.int32),
                    jnp.zeros(qp, jnp.int32), jnp.asarray(False))
            key = AOTCache.key("label", self.backend, args, config=config)
            fn = cache.load(key)
            if fn is None:
                cache.store(key, self._label_phase.fallback, args)
            else:
                self._label_phase.add(args, fn)
        for chunk in (bfs_buckets or self._chunk_buckets()):
            c = self._bucket_for(chunk)
            if not isinstance(self._coal_phases[c], ShapeDispatcher):
                self._coal_phases[c] = ShapeDispatcher(self._coal_phases[c])
            args = (index.graph, index.packed, index.il,
                    jnp.full((c,), n_cap, jnp.int32),
                    jnp.zeros((c,), jnp.int32),
                    jnp.full((c,), Q.FRESH_CUT, jnp.int32),
                    jnp.asarray(False))
            key = AOTCache.key(f"coalesced-{c}", self.backend, args,
                               config=config)
            fn = cache.load(key)
            if fn is None:
                cache.store(key, self._coal_phases[c].fallback, args)
            else:
                self._coal_phases[c].add(args, fn)
        return self

    # ------------------------------------------------------ introspection
    def dispatch_shape_counts(self) -> dict:
        """Compiled-executable counts by phase (jit cache entries)."""
        return {"label": self._label_phase._cache_size(),
                "bfs": sum(f._cache_size()
                           for f in self._coal_phases.values())}

    def dispatch_shapes(self) -> int:
        """Number of distinct compiled executables behind query dispatches."""
        c = self.dispatch_shape_counts()
        return c["label"] + c["bfs"]

    def warmup(self, index: DBLIndex, batch_sizes=(1,),
               bfs_buckets=None) -> "QueryEngine":
        """Pre-compile label + coalesced-BFS executables for the given
        batch sizes (all-dead lanes: the BFS while-loop exits at once)."""
        n_cap = index.packed.dl_in.shape[0]
        for q in batch_sizes:
            self.submit(index, np.zeros(q, np.int32), np.zeros(q, np.int32))
        # derive the warmup's clean flag FROM the index so it carries the
        # same (committed) sharding flavor serving calls will pass — an
        # uncommitted literal False would compile a second executable per
        # bucket on multi-device meshes
        d_clean = jnp.logical_and(jnp.asarray(index.dirty_flag), False)
        for chunk in (bfs_buckets or (self.bfs_chunk,)):
            c = self._bucket_for(chunk)
            self._coal_phases[c](
                index.graph, index.packed, index.il,
                jnp.full((c,), n_cap, jnp.int32),
                jnp.zeros((c,), jnp.int32),
                jnp.full((c,), Q.FRESH_CUT, jnp.int32),
                d_clean, *self._coalesced_extra_args())
        return self


@functools.lru_cache(maxsize=64)
def engine_for(*, bfs_chunk: int, max_iters: int, backend: str = "auto",
               q_block: int = 512) -> QueryEngine:
    """Memoized stateless engines so DBLIndex.query reuses jit caches across
    index instances (labels/graph are per-call arguments, never captured).
    Bounded: callers cycling through many (bfs_chunk, max_iters) pairs evict
    the least-recent engine (and its compiled executables) instead of
    growing without limit."""
    return QueryEngine(None, bfs_chunk=bfs_chunk, max_iters=max_iters,
                       backend=backend, q_block=q_block, donate=False)
