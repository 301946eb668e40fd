"""One run of one benchmark cell: set-up, the measured window, the check
against the plain reference, and the record the metric readers read.

Everything a cell needs is found by name: ``BENCHMARK.json`` pairs a
configuration (``bench/configs/<config>.json``) with a traffic mix
(``bench/traffic/<mix>.json``, whose ``kind`` names a generator module
``bench/generators/<kind>.py``), and each metric the cell reports is read
by ``bench/metrics/<metric>.py``.  Nothing here names a cell.

The record handed to the metric readers (``read(rec) -> float | None``):

- ``setup_s``: process start to the window's opening;
- ``window_s``: the window's opening to the last answer on the host;
- ``queries``: queries answered in the window;
- ``latency_s``: per query, due time to answer on the host (open loop);
  an answer is stamped when the server call that resolved it returns (a
  flush, a policy flush inside ``submit`` or ``poll``, or the drain at the
  start of a delete or a rebuild, which is stamped after the rebuild);
- ``ops``: the window's updates, each ``{kind, due, start, end, info}``
  in seconds after the opening;
- ``late_s``: per issued item, how late the generator issued it;
- ``engine``: the engine's counters over the window (``EngineStats``);
- ``trace``: the reduced device trace of a ``--trace 1`` run, else None.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import pathlib
import re
import shutil
import sys
import tempfile
import time

import numpy as np

from bench import reference as R

ROOT = pathlib.Path(__file__).resolve().parents[1]
#: the longest window any run may ask for: the index's edge capacity
#: leaves room for what a window this long inserts, so compiled shapes do
#: not change with ``--seconds``
MAX_SECONDS = 51
#: label-answered lanes sampled per stratum by the check (``sample_lanes``)
PER_STRATUM = 128


def log(*parts):
    print(*parts, flush=True)


# ------------------------------------------------------------ by name
def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    """The module in ``path`` (a generator or a metric reader), loaded
    once per process under a name made from its path."""
    name = "bench_file_" + re.sub(r"\W", "_", str(path))
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def benchmark(root: pathlib.Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def find_cell(bm: dict, workload: str, root: pathlib.Path = ROOT):
    """(workload entry, configuration, traffic mix) of a cell, by name."""
    for w in bm["workloads"]:
        if w["name"] == workload:
            cfg = load_json(root / "bench" / "configs" / f"{w['config']}.json")
            mix = load_json(root / "bench" / "traffic"
                            / f"{w['traffic']}.json")
            return w, cfg, mix
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def generator(mix: dict, root: pathlib.Path = ROOT):
    return load_module(root / "bench" / "generators" / f"{mix['kind']}.py")


def cell_metrics(bm: dict, workload: str, trace: bool) -> list[dict]:
    """The metrics a run of ``workload`` reports: its end-to-end metrics,
    or with ``trace`` its per-layer ones."""
    group = bm["per_layer"] if trace else bm["end_to_end"]
    return [m for m in group
            if workload in m.get("workloads", [workload])]


def read_metric(name: str, rec: dict, root: pathlib.Path = ROOT):
    return load_module(root / "bench" / "metrics" / f"{name}.py").read(rec)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generators for the graph, the traffic and the sample,
    each a function of the seed alone (any integer, negatives too)."""
    return np.random.default_rng([stream, seed % 2 ** 64])


# ------------------------------------------------------------ set-up
def edge_capacity(cfg: dict, room: int) -> int:
    """Edge slots of the index: the graph plus ``room`` for inserted edges,
    rounded up so that the compiled shapes are one per deployment and mix,
    never per run."""
    return -(-(cfg["m"] + room) // 4096) * 4096


def make_server(idx, cfg: dict, mix: dict, backend: str,
                control: bool = False):
    """The served path as the configuration states it; with ``control``,
    with the configuration's ``control`` overrides applied to the engine
    (a path of the program's own that breaks one stated guarantee)."""
    from repro.serve.engine import QueryEngine
    from repro.serve.reach_server import ReachabilityServer
    e = dict(cfg["engine"], **(cfg["control"]["engine"] if control else {}))
    engine = QueryEngine(
        idx, backend=backend, q_block=e["q_block"], bfs_chunk=e["bfs_chunk"],
        max_iters=e["max_iters"], consistency=cfg["consistency"],
        flush_policy=mix.get("flush_policy"),
        flush_deadline_ms=mix.get("flush_deadline_ms", 25.0))
    return ReachabilityServer(None, engine=engine, rebuild_dead_ratio=None,
                              rebuild_mode=mix.get("rebuild_mode", "auto"))


def label_sizes(engine, sched) -> list[int]:
    """Every padded label-phase batch size the traffic can submit."""
    granule = math.lcm(engine.q_block, engine.bfs_chunk)
    top = -(-sched.batch // granule) * granule
    if sched.loop == "closed":
        return [top]
    return list(range(granule, top + 1, granule))


def bfs_buckets(engine) -> list[int]:
    sizes, c = [], 16
    while c < engine.bfs_chunk:
        sizes.append(c)
        c *= 2
    return sizes + [engine.bfs_chunk]


def run_op(server, op):
    if op.kind == "insert":
        server.insert(op.src, op.dst)
    elif op.kind == "delete":
        server.delete(op.src, op.dst)
    else:
        server.rebuild()
        return dict(server.engine.last_rebuild_info or {})
    return None


def warm_up(server, sched):
    """Compile every shape the window uses: each label-phase batch size,
    each BFS chunk bucket, then one real batch, then the set-up updates
    (each followed by a real batch, dirty and clean)."""
    eng = server.engine
    for q in label_sizes(eng, sched):
        z = np.zeros(q, np.int32)
        server.submit(z, z)
        server.flush()
    eng.warmup(eng.index, batch_sizes=(), bfs_buckets=bfs_buckets(eng))
    server.submit(sched.warm_u, sched.warm_v)
    server.flush()
    for op in sched.warm_ops:
        run_op(server, op)
        server.submit(sched.warm_u, sched.warm_v)
        server.flush()


def make_data(cfg: dict, mix: dict, gen_mod, seed: int, seconds: float):
    """The graph and the whole schedule of one run, from the seed.

    A configuration whose ``generator`` names a ``fixed_seed`` holds one
    graph, made from that number, and the run's seed only shuffles the
    order of its edge list; a mix with a ``fixed_seed`` draws its schedule
    from that number.  So every seed gets the same work in another order.
    Without them, each seed draws its own graph and schedule.  Returns the
    initial (src, dst) to build from, the schedule and the edge log the
    reference reads."""
    g = cfg["generator"]
    fixed = g.get("fixed_seed")
    src, dst = R.dag_like(
        cfg["n"], cfg["m"], back_frac=g["back_frac"],
        seed=fixed if fixed is not None
        else int(rng_for(seed, 0).integers(2 ** 63)))
    edge_log = R.EdgeLog(cfg["n"], src, dst)
    t = time.perf_counter()
    sched = gen_mod.make(mix, edge_log, rng_for(mix.get("fixed_seed", seed),
                                                1), seconds)
    log(f"schedule: {sched.u.size} queries, {len(sched.ops)} updates, "
        f"{len(sched.warm_ops)} set-up updates "
        f"({time.perf_counter() - t:.3f} s)")
    if fixed is not None:
        order = rng_for(seed, 4).permutation(src.size)
        src, dst = src[order], dst[order]
    return src, dst, sched, edge_log


def set_up(cfg: dict, mix: dict, gen_mod, seed: int, seconds: float,
           backend: str, *, control: bool = False,
           edge_room: int | None = None):
    """Everything before a window opens: the graph and the whole schedule
    (``make_data``), the index (built with ``check="raise"``), the server
    and its warm-up.  ``edge_room`` is the room for inserted edges, by
    default what the mix inserts in the longest window.  Returns the
    warmed server, the schedule and the edge log the reference reads."""
    import jax
    from repro.core.dbl import DBLIndex
    from repro.core.graph import make_graph
    src, dst, sched, edge_log = make_data(cfg, mix, gen_mod, seed, seconds)
    if edge_room is None:
        edge_room = gen_mod.max_inserted_edges(mix, MAX_SECONDS)
    m_cap = edge_capacity(cfg, edge_room)
    t = time.perf_counter()
    idx = DBLIndex.build(make_graph(src, dst, cfg["n"], m_cap=m_cap),
                         n_cap=cfg["n"], k=cfg["k"], k_prime=cfg["k_prime"],
                         max_iters=cfg["engine"]["max_iters"], check="raise")
    jax.block_until_ready(idx)
    log(f"build: n={cfg['n']} m={cfg['m']} m_cap={m_cap} "
        f"{time.perf_counter() - t:.3f} s")
    del src, dst
    server = make_server(idx, cfg, mix, backend, control)
    del idx
    t = time.perf_counter()
    warm_up(server, sched)
    log(f"warm-up: {time.perf_counter() - t:.3f} s, dispatch shapes "
        f"{server.engine.dispatch_shape_counts()}")
    return server, sched, edge_log


# ------------------------------------------------------------ the window
class Window:
    """Drives the server through a schedule and records what happened.
    Times are seconds after the window opened, on ``time.perf_counter``."""

    def __init__(self, server, sched, seconds: float, annotate):
        self.server = server
        self.stats = server.engine.stats
        self.policy_flushes = self.stats.policy_flushes
        self.sched = sched
        self.seconds = seconds
        self.span = annotate
        self.batches = []       # one dict per submitted batch
        self.ops = []
        self.late = []          # issue time minus due time, per item
        self.late_due = []      # the due time of each of those items
        self.version = 0        # the EdgeLog version the server holds
        self.dirty = False
        self.open = []          # submitted batches not yet answered
        self.queued = 0         # batches in the server's queue since a flush
        self.t0 = None

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def submit(self, lo: int, hi: int, due_first: float | None):
        s = self.sched
        t = self.now()
        with self.span("submit"):
            pend = self.server.submit(s.u[lo:hi], s.v[lo:hi])
        if s.version[lo] != self.version or s.version[hi - 1] != self.version:
            raise RuntimeError(f"queries {lo}:{hi} were made for version "
                               f"{s.version[lo]}, the server is at "
                               f"{self.version}")
        b = dict(lo=lo, hi=hi, pend=pend, submit=t, done=None,
                 version=self.version, dirty=self.dirty, spans=False)
        self.batches.append(b)
        self.queued += 1
        if due_first is not None:
            self.late.extend(t - s.due[lo:hi])
            self.late_due.extend(s.due[lo:hi])
        return b

    def stamp(self, drained: bool = False):
        """Time the open batches if the call that just returned answered
        them: the server's flush policy resolves every batch in flight
        when it fires inside ``submit`` or ``poll`` (the engine's public
        ``policy_flushes`` counter moves), and a delete or a rebuild
        (``drained``) resolves them before it starts.  Then empty the
        server's queue of answered batches."""
        fired = self.stats.policy_flushes != self.policy_flushes
        if not (drained or fired) or not self.open:
            self.policy_flushes = self.stats.policy_flushes
            return
        t = self.now()
        for b in self.open:
            b["done"] = t
        self.open = []
        self.policy_flushes = self.stats.policy_flushes
        self.server.flush()             # resolves nothing: all are answered
        self.queued = 0

    def run_op(self, op):
        if op.kind == "insert":
            # inserts do not drain: these batches resolve at a later version
            for b in self.open:
                b["spans"] = True
        t = self.now()
        self.late.append(t - op.due)
        self.late_due.append(op.due)
        with self.span(op.kind):
            info = run_op(self.server, op)
        end = self.now()
        self.ops.append(dict(kind=op.kind, due=op.due, start=t, end=end,
                             info=info))
        if op.kind != "insert":
            self.stamp(drained=True)
        if op.kind != "rebuild":
            self.version += 1
        if op.version != self.version:
            raise RuntimeError(f"{op.kind} made version {self.version}, "
                               f"the schedule says {op.version}")
        self.dirty = (op.kind == "delete") or (self.dirty
                                               and op.kind != "rebuild")

    def closed(self):
        s, b = self.sched, self.sched.batch
        lo = 0
        while lo + b <= s.u.size:
            bt = self.submit(lo, lo + b, None)
            with self.span("flush"):
                self.server.flush()
            bt["done"] = self.now()
            lo += b
            if bt["done"] >= self.seconds:
                return
        raise RuntimeError(f"the schedule's {s.u.size // b} batches ran out "
                           f"before {self.seconds} s; raise max_batches")

    def open_loop(self):
        s = self.sched
        due, ops, cap = s.due, s.ops, s.batch
        deadline = self.server.engine.flush_deadline_ms / 1e3
        iq = iop = 0
        while iq < due.size or iop < len(ops):
            now = self.now()
            op_due = ops[iop].due if iop < len(ops) else math.inf
            q_due = due[iq] if iq < due.size else math.inf
            if op_due <= now and op_due <= q_due:
                self.run_op(ops[iop])
                iop += 1
                continue
            if q_due <= now:
                # what is due now, but nothing due at or after the next op
                hi = int(np.searchsorted(due, op_due, "left")) \
                    if op_due <= now else \
                    int(np.searchsorted(due, now, "right"))
                hi = min(hi, iq + cap)
                self.open.append(self.submit(iq, hi, q_due))
                iq = hi
                self.stamp()
                continue
            if self.open:
                with self.span("poll"):
                    self.server.poll()
                self.stamp()
            nxt = min(op_due, q_due)
            if self.open:
                nxt = min(nxt, self.open[0]["submit"] + deadline)
            wait = nxt - self.now()
            if wait > 0:
                with self.span("gen-wait"):
                    time.sleep(wait)
        if self.open:
            with self.span("flush"):
                self.server.flush()
            self.stamp(drained=True)

    def run(self, version: int):
        self.version = version
        self.t0 = time.perf_counter()
        with self.span("window"):
            if self.sched.loop == "closed":
                self.closed()
            else:
                self.open_loop()
        return self.now()


# ------------------------------------------------------------ checking
def sample_lanes(batches, sched, rng, per_stratum: int = PER_STRATUM):
    """Indices (into the schedule) of the lanes to check: every lane that
    rode the BFS residue, and up to ``per_stratum``, drawn from the seed,
    from each stratum of label-answered lanes by (answer) x (index dirty
    at submit) x (resolved after a later update).  So every route, dirty
    serving and answers across update epochs are always in the sample,
    and a residue BFS cut short shows wherever it answers wrong."""
    idx, key = [], []
    for b in batches:
        if b["answers"] is None:
            continue
        bfs = np.zeros(b["hi"] - b["lo"], bool)
        bfs[b["bfs"]] = True
        key.append(bfs * 8 + b["answers"] * 4 + b["dirty"] * 2 + b["spans"])
        idx.append(np.arange(b["lo"], b["hi"]))
    if not idx:
        return np.zeros(0, np.int64), {}
    idx, key = np.concatenate(idx), np.concatenate(key).astype(int)
    take, strata = [], {}
    for k in np.unique(key):
        members = idx[key == k]
        pick = members if k >= 8 else \
            rng.choice(members, min(per_stratum, members.size), replace=False)
        take.append(pick)
        strata[int(k)] = int(pick.size)
    return np.sort(np.concatenate(take)), strata


def check_answers(batches, sched, edge_log: R.EdgeLog, rng):
    """Compare the window's answers (``sample_lanes``) with a host BFS over
    the edges live at each batch's snapshot.  Returns the numbers compared
    (each with its limit) and a few facts about the sample."""
    lanes, strata = sample_lanes(batches, sched, rng)
    if not lanes.size:
        raise RuntimeError("the window answered no query to check")
    got = np.zeros(sched.u.size, np.int8) - 1
    for b in batches:
        if b["answers"] is not None:
            got[b["lo"]:b["hi"]] = b["answers"]
    issued = sum(b["hi"] - b["lo"] for b in batches)
    unanswered = int((got[:issued] < 0).sum())
    wrong = 0
    t = time.perf_counter()
    vers = sched.version[lanes]
    for v in np.unique(vers):
        sel = lanes[vers == v]
        want = edge_log.reach(int(v), sched.u[sel], sched.v[sel])
        wrong += int((got[sel] != want).sum())
    checks = {"wrong_answers": {"value": wrong, "limit": 0},
              "unanswered": {"value": unanswered, "limit": 0}}
    facts = {"sampled": int(lanes.size), "strata": strata,
             "reference_s": time.perf_counter() - t,
             "reachable": int((got[lanes] == 1).sum())}
    return checks, facts


def settle(batches):
    """After the window: keep on the host what the check needs of each
    batch (its answers, and which lanes rode the BFS residue) and drop the
    pending handles, with the device state they hold."""
    for b in batches:
        p = b.pop("pend")
        done = b["done"] is not None
        b["answers"] = p.resolve() if done else None
        # the handle's lane order puts the ``nu`` lanes the label phase
        # left unknown (the BFS residue) first
        b["bfs"] = np.asarray(p.order)[:p.nu] if done \
            else np.zeros(0, np.int64)


def checks_pass(checks: dict) -> bool:
    """Every number compared is at most its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())


# ------------------------------------------------------------ one run
class CompileCounter:
    """Counts XLA compilations (persistent-cache loads included) through
    JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n = 0
        self.names = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.n += 1
            self.names.append(kw.get("fun_name", "?"))


def run_cell(workload: str, seed: int, seconds: float, *, trace: bool,
             backend: str = "pallas", t_process: float | None = None,
             root: pathlib.Path = ROOT, devices=None,
             control: bool = False) -> dict:
    """One run; returns the result object the last line prints.
    ``backend`` is the engine's: ``pallas`` on the chip, and
    ``pallas-interpret`` only where tests drive a run on the CPU.
    ``control`` serves through the configuration's control path (see
    ``make_server``); the benchmark's own runs never set it."""
    import jax

    t_process = time.perf_counter() if t_process is None else t_process
    bm = benchmark(root)
    w, cfg, mix = find_cell(bm, workload, root)
    gen_mod = generator(mix, root)
    if not 0 < seconds <= MAX_SECONDS:
        raise ValueError(f"--seconds {seconds} is not in (0, {MAX_SECONDS}]")
    devices = jax.devices() if devices is None else devices
    comp = CompileCounter()

    # ---- set-up: data, index, server, warm-up
    server, sched, edge_log = set_up(cfg, mix, gen_mod, seed, seconds,
                                     backend, control=control)
    eng = server.engine
    before = eng.stats.as_dict()
    shapes_before = eng.dispatch_shape_counts()

    # ---- the window
    tracer = Tracer() if trace else None
    annotate = tracer.annotate if trace else _no_span
    if tracer:
        tracer.start()
    win = Window(server, sched, seconds, annotate)
    comp_before = comp.n
    setup_s = time.perf_counter() - t_process
    window_s = win.run(version=sum(o.kind != "rebuild"
                                   for o in sched.warm_ops))
    compiles = comp.n - comp_before
    compiled = comp.names[comp_before:]
    mem = (devices[0].memory_stats() or {}).get("peak_bytes_in_use")
    reduced = None
    if tracer:
        from bench import trace_reduce
        reduced = trace_reduce.reduce(tracer.stop())
        tracer.close()
    after = eng.stats.as_dict()
    engine = {k: after[k] - before[k] for k in after
              if isinstance(after[k], (int, float))}
    engine["prune_hits"] = {k: after["prune_hits"][k]
                            - before["prune_hits"][k]
                            for k in after["prune_hits"]}
    shapes_after = eng.dispatch_shape_counts()
    settle(win.batches)
    del server, eng

    answered = [b for b in win.batches if b["done"] is not None]
    latency = np.concatenate(
        [b["done"] - sched.due[b["lo"]:b["hi"]] for b in answered]) \
        if sched.due is not None and answered else np.zeros(0)
    rec = dict(workload=workload, seed=seed, seconds=seconds, config=cfg,
               mix=mix, setup_s=setup_s, window_s=window_s,
               queries=int(sum(b["hi"] - b["lo"] for b in answered)),
               latency_s=latency, ops=win.ops, late_s=np.asarray(win.late),
               engine=engine, trace=reduced,
               device_kind=devices[0].device_kind)
    log(f"window: {window_s:.3f} s, {rec['queries']} queries in "
        f"{len(win.batches)} batches, "
        + ", ".join(f"{k} {sum(o['kind'] == k for o in win.ops)}"
                    for k in ("insert", "delete", "rebuild")))
    late = rec["late_s"]
    log(f"generator lateness: {late.size} items, p50 "
        f"{np.median(late) * 1e3 if late.size else 0:.3f} ms, max "
        f"{late.max() * 1e3 if late.size else 0:.3f} ms")
    log(f"routes: {json.dumps(engine['prune_hits'])}, bfs dispatches "
        f"{engine['bfs_dispatches']}, policy flushes "
        f"{engine['policy_flushes']}")
    modes = [o["info"].get("mode") for o in win.ops if o["kind"] == "rebuild"]
    log(f"rebuilds: {json.dumps(modes)}")
    log(f"compilations in the window: {compiles} {sorted(set(compiled))}; "
        f"dispatch shapes {shapes_before} -> {shapes_after}")

    # ---- the check, after the window, on the host
    checks, facts = check_answers(win.batches, sched, edge_log,
                                  rng_for(seed, 2))
    log(f"reference: {facts['sampled']} lanes in strata "
        f"{facts['strata']} (bfs*8+answer*4+dirty*2+spans_update), "
        f"{facts['reachable']} reachable, {facts['reference_s']:.3f} s")

    metrics = {}
    for m in cell_metrics(bm, workload, trace):
        value = read_metric(m["name"], rec, root)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    out = {"correct": checks_pass(checks),
           "attempted": sum(b["hi"] - b["lo"] for b in win.batches)
           + len(win.ops),
           "failed": checks["unanswered"]["value"],
           "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = reduced["breakdown"]
    out["checks"] = checks
    return out


@contextlib.contextmanager
def _no_span(name):
    yield


class Tracer:
    """The JAX profiler around the window, writing into a fresh temporary
    directory that ``close`` removes.  Host spans go into the same trace
    through ``jax.profiler.TraceAnnotation``."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")

    def annotate(self, name):
        import jax
        return jax.profiler.TraceAnnotation(name)

    def start(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> pathlib.Path:
        import jax
        jax.profiler.stop_trace()
        files = sorted(pathlib.Path(self.dir).rglob("*.xplane.pb"))
        if len(files) != 1:
            raise RuntimeError(f"expected one trace file, found {files}")
        return files[0]

    def close(self):
        shutil.rmtree(self.dir, ignore_errors=True)


def report(out: dict, stream=None):
    """The numbers compared, beside their limits, as the last lines on
    standard error; then the result as the last line on standard out."""
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), file=stream or sys.stdout, flush=True)
