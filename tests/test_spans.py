"""The serving path's own profiler spans (``repro.serve.spans``): a trace
of one submit/flush/insert/delete/rebuild/poll round holds every span,
each nested in the call it belongs to, with the waits on device results
inside ``server.flush`` (or the drain that starts a delete), and every
read of a device result inside a server call lies in a wait span."""
import pathlib
import tempfile

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import DBLIndex, make_graph
from repro.serve.reach_server import ReachabilityServer

N = 64
#: the span JAX opens around every copy of a device array to the host
READ = "np.asarray(jax.Array)"
#: a batch is resolved by a flush, or by the drain that starts a delete
RESOLVE = ("server.flush", "server.delete")
#: span -> the server calls it may lie inside (none: a server call)
NESTING = {
    "server.submit": (), "server.flush": (), "server.poll": (),
    "server.insert": (), "server.delete": (), "server.rebuild": (),
    "engine.label_dispatch": ("server.submit",),
    "engine.wait_nu": RESOLVE,
    "engine.wait_residue": RESOLVE,
    "engine.pool": RESOLVE,
    "engine.bfs_dispatch": RESOLVE,
    "engine.wait_hits": RESOLVE,
    "engine.scatter": RESOLVE,
    "engine.insert_dispatch": ("server.insert",),
    "engine.drain": ("server.delete",),
    "server.wait_graph": ("server.delete",),
    "index.rebuild.wait_sat": ("server.rebuild",),
    "index.rebuild.wait_plan": ("server.rebuild",),
    "index.rebuild.wait_fixpoint": ("server.rebuild",),
    "engine.wait_rebuild": ("server.rebuild",),
    "engine.wait_bind": ("server.rebuild",),
    "engine.wait_sat": RESOLVE,
    "server.wait_labels": ("server.insert", "server.rebuild"),
}


def _server():
    rng = np.random.default_rng(0)
    src = rng.integers(0, N - 1, 160).astype(np.int32)
    dst = np.minimum(src + rng.integers(1, 6, 160), N - 1).astype(np.int32)
    idx = DBLIndex.build(make_graph(src, dst, N, m_cap=256), n_cap=N, k=2,
                         k_prime=2, max_iters=32)
    srv = ReachabilityServer(idx, bfs_chunk=32, max_iters=32,
                             rebuild_dead_ratio=None, rebuild_mode="delta")
    u = rng.integers(0, N, 100).astype(np.int32)
    v = rng.integers(0, N, 100).astype(np.int32)
    srv.submit(u, v)
    srv.flush()                  # compiles every program the round runs
    return srv, src, dst, u, v


@pytest.fixture(scope="module")
def spans():
    srv, src, dst, u, v = _server()
    with tempfile.TemporaryDirectory() as d:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            srv.submit(u, v)
            srv.flush()
            srv.insert(src[:3], dst[:3])
            srv.submit(u, v)     # in flight: the delete drains it
            srv.delete(src[:2], dst[:2])
            srv.rebuild()
            srv.poll()
            srv.flush()
        finally:
            jax.profiler.stop_trace()
        (path,) = pathlib.Path(d).rglob("*.xplane.pb")
        pd = ProfileData.from_file(str(path))
        return [(e.name, int(e.start_ns), int(e.end_ns))
                for plane in pd.planes if plane.name.startswith("/host:")
                for line in plane.lines for e in line.events
                if e.name.startswith(("server.", "engine.", "index."))
                or e.name == READ]


@pytest.mark.parametrize("name", sorted(NESTING))
def test_span_is_recorded_inside_its_call(spans, name):
    mine = [(s, e) for n, s, e in spans if n == name]
    assert mine, f"no {name} span in the trace"
    if not NESTING[name]:
        return
    calls = [(s, e) for n, s, e in spans if n in NESTING[name]]
    for s, e in mine:
        assert any(a <= s and e <= b for a, b in calls), name


def test_server_calls_do_not_overlap(spans):
    calls = sorted((s, e) for n, s, e in spans if n.startswith("server.")
                   and ".wait_" not in n)
    for (_, e0), (s1, _) in zip(calls, calls[1:]):
        assert s1 >= e0


def test_flush_waits_on_the_device_inside_its_span(spans):
    flushes = [(s, e) for n, s, e in spans if n == "server.flush"]
    for wait in ("engine.wait_nu", "engine.wait_residue", "engine.wait_hits"):
        assert any(a <= s and e <= b for n, s, e in spans if n == wait
                   for a, b in flushes), wait


def test_every_device_read_in_a_call_is_a_wait(spans):
    """The host time of the front end (``bench/program_spans.py``: program
    spans less wait spans) holds no wait on the device: every read of a
    device array inside a server call, the rebuild's included, lies in a
    ``.wait_`` span."""
    calls = [(s, e) for n, s, e in spans if n.startswith("server.")
             and ".wait_" not in n]
    waits = [(s, e) for n, s, e in spans if ".wait_" in n]
    reads = [(s, e) for n, s, e in spans if n == READ
             and any(a <= s and e <= b for a, b in calls)]
    assert reads, "no device read inside a server call"
    for s, e in reads:
        assert any(a <= s and e <= b for a, b in waits), (s, e)
