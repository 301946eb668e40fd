"""The served path's Pallas kernels compile for a described TPU v5e.

Nothing here runs on a chip: each test lowers and compiles one kernel
dispatch at the widths the engine serves the Wiki deployment with (k = k' =
64, so W = 2 words; the label phase's q_block and batch; the coalesced
residue's bfs_chunk buckets; n of the Wiki row of the paper's Table 2) for
one device of a described ``v5e:2x2`` topology.  What the TPU compiler
refuses — a block layout Mosaic cannot tile, a select it cannot lower, more
VMEM than a kernel may hold — fails here, in interpret mode's blind spot.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and every test worker imports this
file.  Keep these tests in this one file.
"""
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
