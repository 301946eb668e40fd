"""AOT-serialized engine executables: cold-start serving without recompiles.

A serving process restarting on the same (backend, index shapes, batch
granule) re-traces and re-compiles the exact executables its predecessor
already built — pure startup latency.  This module persists the engine's
fused verdict and coalesced-BFS executables with ``jax.export`` under a
disk cache keyed on everything that determines the compiled artifact:

    key = sha256(tag, backend, jax version, flattened input avals
                 (shape + dtype per leaf), mesh descriptor)

``QueryEngine.aot_warmup(index, cache_dir)`` drives it: cache hits swap the
deserialized executables in — the whole Python tracing + lowering pipeline
is skipped, and the persisted StableHLO hits JAX's persistent compilation
cache byte-identically, so backend codegen is skipped too when that cache
is enabled (``jax.config.jax_compilation_cache_dir``).  Misses export +
persist the freshly compiled executables so the NEXT cold start hits.
Answers are bitwise identical either way — the exported artifact is the
same StableHLO the live jit produces (pinned in ``tests/test_engine.py``).

Scope: the replicated single-process layout.  The vertex-sharded layout's
shard_map collectives are excluded deliberately — their executables bake in
a concrete device assignment, exactly what a restarted process cannot
guarantee; ``aot_warmup`` refuses rather than caching placement bugs.
"""
from __future__ import annotations

import hashlib
import json
import pathlib
import warnings

import jax
from jax import export as jexport

from repro.core.graph import Graph
from repro.core.pull import InEdgeLayout
from repro.core.query import PackedLabels


class AOTCacheWarning(UserWarning):
    """An AOT cache entry could not be exported/loaded; serving falls back
    to normal jit compilation (correctness is unaffected)."""


_REGISTERED = False


def _ensure_serialization_registered():
    """jax.export refuses unregistered NamedTuple pytrees; register ours
    once (idempotent across engines and tests)."""
    global _REGISTERED
    if _REGISTERED:
        return
    for cls in (PackedLabels, Graph, InEdgeLayout):
        try:
            jexport.register_namedtuple_serialization(
                cls, serialized_name=f"repro.core.{cls.__name__}")
        except ValueError:
            pass  # a previous process-wide registration already holds
    _REGISTERED = True


def avals_desc(args) -> list:
    """Flattened (shape, dtype) description of a call's inputs — the
    shape-polymorphism-free cache key component."""
    leaves = jax.tree.leaves(args)
    return [(tuple(x.shape), str(x.dtype)) for x in leaves]


class ShapeDispatcher:
    """Callable that routes by input avals: exact-shape hits go to their
    AOT-loaded executable, anything else falls back to the live jit.

    ``jax.export`` artifacts are monomorphic (one aval set each), while an
    engine phase serves several padded shapes — this adapter lets the two
    coexist without the engine knowing which shapes were cached."""

    def __init__(self, fallback):
        self.fallback = fallback
        self.table: dict[str, object] = {}

    @staticmethod
    def _k(args) -> str:
        return repr(avals_desc(args))

    def add(self, args, fn):
        self.table[self._k(args)] = fn

    def __call__(self, *args):
        fn = self.table.get(self._k(args))
        return fn(*args) if fn is not None else self.fallback(*args)

    def _cache_size(self) -> int:
        # dispatch-shape accounting: every loaded artifact is one compiled
        # shape, exactly like a jit cache entry
        return self.fallback._cache_size() + len(self.table)

    def lower(self, *args, **kw):
        return self.fallback.lower(*args, **kw)


class AOTCache:
    """Disk cache of ``jax.export``-serialized executables."""

    def __init__(self, path: str | pathlib.Path):
        # both directions need the NamedTuple registrations: store() to
        # serialize, load() to rebuild the pytree in a FRESH process (the
        # whole point of the cache)
        _ensure_serialization_registered()
        self.path = pathlib.Path(path)
        self.path.mkdir(parents=True, exist_ok=True)
        self.hits = 0
        self.misses = 0
        self.stores = 0

    @staticmethod
    def key(tag: str, backend: str, args, mesh_desc=None,
            config: dict | None = None) -> str:
        """``config`` must carry every engine knob baked into the compiled
        executable beyond its input avals — max_iters (the BFS while-loop
        bound!), frontier_dtype, q_block, bfs_kernel — otherwise a process
        restarted with different knobs would silently serve the old
        executable's semantics."""
        blob = json.dumps({"tag": tag, "backend": backend,
                           "jax": jax.__version__,
                           "avals": avals_desc(args),
                           "mesh": mesh_desc,
                           "config": config or {}}, sort_keys=True)
        return hashlib.sha256(blob.encode()).hexdigest()[:32]

    def _file(self, key: str) -> pathlib.Path:
        return self.path / f"{key}.jaxexp"

    def load(self, key: str):
        """Deserialized executable as a jit-dispatchable callable, or None.
        Corrupt/incompatible entries degrade to a miss with a warning —
        never to a serving failure."""
        f = self._file(key)
        if not f.exists():
            self.misses += 1
            return None
        try:
            exp = jexport.deserialize(bytearray(f.read_bytes()))
            fn = jax.jit(exp.call)
        except Exception as e:  # version skew, truncated file, ...
            warnings.warn(f"AOT cache entry {f.name} unusable ({e!r}); "
                          "recompiling", AOTCacheWarning, stacklevel=2)
            self.misses += 1
            return None
        self.hits += 1
        return fn

    def store(self, key: str, jitted, args) -> None:
        """Export ``jitted`` at ``args``' avals and persist it.  Export
        failures warn and skip — the live jit keeps serving."""
        try:
            exp = jexport.export(jitted)(*args)
            self._file(key).write_bytes(exp.serialize())
            self.stores += 1
        except Exception as e:
            warnings.warn(f"AOT export failed for {key} ({e!r}); entry "
                          "skipped", AOTCacheWarning, stacklevel=2)
