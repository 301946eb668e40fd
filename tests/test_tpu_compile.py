"""The served path's Pallas kernels compile for a described TPU v5e.

Nothing here runs on a chip: each test lowers and compiles one kernel
dispatch at the widths the engine serves the Wiki deployment with (k = k' =
64, so W = 2 words; the label phase's q_block and batch; the coalesced
residue's bfs_chunk buckets; n of the Wiki row of the paper's Table 2) for
one device of a described ``v5e:2x2`` topology.  The BFS residue's level
loop is pinned too: no scatter and no sort in it.  What the TPU compiler
refuses — a block layout Mosaic cannot tile, a select it cannot lower, more
VMEM than a kernel may hold — fails here, in interpret mode's blind spot.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and every test worker imports this
file.  Keep these tests in this one file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.query import PackedLabels
from repro.kernels.bfs_prune.ops import admit_plane
from repro.kernels.dbl_query.ops import verdicts_device

N_WIKI = 2_400_000          # Table 2 "Wiki" vertices
W = 2                       # k = k' = 64 label words
IL_DIM = 4                  # core.families.DEFAULT_IL_DIM
BATCH = 16_384              # label-phase batch (multiple of the granule)
Q_BLOCK = 512               # QueryEngine default q_block
BFS_CHUNK = 256             # QueryEngine default bfs_chunk


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _labels(sharding):
    plane = _spec(sharding, (N_WIKI, W), jnp.uint32)
    return PackedLabels(plane, plane, plane, plane)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _cut_operands(sharding, q):
    return (_spec(sharding, (q,), jnp.int32), _spec(sharding, (), jnp.int32),
            _spec(sharding, (q,), jnp.int32), _spec(sharding, (), jnp.int32))


@pytest.mark.parametrize("q,q_block", [
    (BATCH, Q_BLOCK),              # label phase
    (BFS_CHUNK, BFS_CHUNK),        # coalesced re-check, largest bucket
    (16, 16),                      # coalesced re-check, smallest bucket
])
@pytest.mark.parametrize("with_il", [False, True], ids=["cut_del", "cut_del_il"])
def test_dbl_query_grid_compiles(one_chip, q, q_block, with_il):
    ids = _spec(one_chip, (q,), jnp.int32)
    il = None
    if with_il:
        rank = _spec(one_chip, (N_WIKI, 2 * IL_DIM), jnp.int32)
        il = (rank, rank)

    def fn(p, u, v, m_cut, m_total, d_cut, d_total, il):
        return verdicts_device(p, u, v, m_cut, m_total, d_cut, d_total, il,
                               q_block=q_block, interpret=False,
                               out_dtype=jnp.int8)

    _compile(fn, _labels(one_chip), ids, ids, *_cut_operands(one_chip, q), il)


@pytest.mark.parametrize("q,q_block", [(BATCH, Q_BLOCK),
                                       (BFS_CHUNK, BFS_CHUNK)])
def test_dbl_query_streamed_compiles(one_chip, q, q_block):
    ids = _spec(one_chip, (q,), jnp.int32)

    def fn(p, u, v, m_cut, m_total, d_cut, d_total):
        return verdicts_device(p, u, v, m_cut, m_total, d_cut, d_total,
                               q_block=q_block, interpret=False,
                               out_dtype=jnp.int8, streaming=True)

    _compile(fn, _labels(one_chip), ids, ids, *_cut_operands(one_chip, q))


@pytest.mark.parametrize("streaming", [False, True], ids=["grid", "streamed"])
def test_bfs_prune_compiles(one_chip, streaming):
    q = BFS_CHUNK
    ids = _spec(one_chip, (q,), jnp.int32)

    def fn(p, u, v, m_cut, m_total, d_cut, d_total):
        return admit_plane(p, u, v, m_cut, m_total, d_cut, d_total,
                           n_block=1024, q_block=128, interpret=False,
                           out_dtype=jnp.int8, streaming=streaming)

    compiled = _compile(fn, _labels(one_chip), ids, ids,
                        *_cut_operands(one_chip, q))
    # the (n, Qc) int8 plane is the bulk of what the dispatch writes
    assert compiled.memory_analysis().output_size_in_bytes >= N_WIKI * q


# ---------------------------------------------- the residue's level loop
_CALLEE = re.compile(r"\b(?:calls|to_apply|body|condition|branch_computations)"
                     r"=\{?([%\w.\-, ]+)\}?")
_HEAD = re.compile(r"^(?:ENTRY )?(%[\w.\-]+) ")
_OP = re.compile(r"= \S+ (scatter|sort)\(")


def _computations(hlo: str) -> dict[str, list[str]]:
    comps, cur = {}, None
    for line in hlo.splitlines():
        m = _HEAD.match(line)
        if m and line.rstrip().endswith("{"):
            cur = comps.setdefault(m.group(1), [])
        elif cur is not None and line.startswith("  "):
            cur.append(line)
    return comps


def _loop_ops(hlo: str) -> list[str]:
    """Every ``scatter`` and ``sort`` instruction that a ``while`` body of
    the compiled module runs, directly or through the computations it
    calls (fusions, reducers, nested loops)."""
    comps = _computations(hlo)
    todo = [name for lines in comps.values() for line in lines
            if " while(" in line
            for name in re.findall(r"body=(%[\w.\-]+)", line)]
    seen = set()
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name]:
            for group in _CALLEE.findall(line):
                todo += [c.strip() for c in group.split(",")]
    return [line for name in seen for line in comps[name] if _OP.search(line)]


@pytest.fixture(scope="module")
def residue_layout():
    """An in-edge layout of a Wiki-like graph at 1/32 of its size: the
    shapes of the tables the engine's ``coalesced`` program pulls over."""
    from repro.core import pull
    from repro.core.graph import make_graph
    from repro.graphs.generators import dag_like
    n, m = 2 ** 16, 2 ** 17
    src, dst = dag_like(n, m, seed=2101, back_frac=0.05)
    return n, m, pull.build(make_graph(src, dst, n, m_cap=m), m, n)[0]


@pytest.mark.parametrize("tail", [0, 1024], ids=["no_insert", "tail"])
def test_coalesced_level_loop_has_no_scatter(one_chip, residue_layout, tail):
    """The replicated engine's ``coalesced`` program, compiled for a v5e:
    its BFS level loop pulls, so no ``scatter`` and no ``sort`` runs in
    it, except in the push-form relaxation of the layout's insert tail
    (``bfs.tail``), which a no-insert index does not have."""
    from repro.core.graph import Graph
    from repro.core.pull import InEdgeLayout
    from repro.serve.engine import QueryEngine
    n, m, lay = residue_layout
    i32 = jnp.int32
    edges = _spec(one_chip, (m,), i32)
    g = Graph(edges, edges, _spec(one_chip, (), i32),
              _spec(one_chip, (), i32), edges, _spec(one_chip, (), i32))
    plane = _spec(one_chip, (n, W), jnp.uint32)
    p = PackedLabels(plane, plane, plane, plane)
    layout = InEdgeLayout(
        _spec(one_chip, lay.pos.shape, i32),
        _spec(one_chip, lay.inv.shape, i32),
        tuple(_spec(one_chip, t.shape, i32) for t in lay.src),
        tuple(_spec(one_chip, t.shape, i32) for t in lay.eid),
        _spec(one_chip, (), i32), _spec(one_chip, (tail,), i32))
    lanes = _spec(one_chip, (BFS_CHUNK,), i32)
    eng = QueryEngine(backend="jnp", bfs_chunk=BFS_CHUNK)
    hlo = eng._coal_phases[BFS_CHUNK].lower(
        g, p, None, lanes, lanes, lanes, _spec(one_chip, (), jnp.bool_),
        layout).compile().as_text()
    ops = _loop_ops(hlo)
    assert all("bfs.tail" in op for op in ops), [
        op for op in ops if "bfs.tail" not in op]
    assert bool(ops) == bool(tail), ops
    # the push form this replaces scatters in its loop: the check sees it
    push = _loop_ops(eng._coal_phases[BFS_CHUNK].lower(
        g, p, None, lanes, lanes, lanes,
        _spec(one_chip, (), jnp.bool_)).compile().as_text())
    assert "scatter" in {_OP.search(op).group(1) for op in push}
