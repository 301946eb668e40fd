"""A copy of the benchmark's tree in a temporary directory, with both
deployments cut to a size the CPU runs in seconds (the kernels in the
Pallas interpreter).  The harness finds everything in it by name, as it
does in a checkout."""
import json
import pathlib
import shutil

REPO = pathlib.Path(__file__).resolve().parents[2]
TINY_GRAPH = dict(n=3000, m=6000)
TINY_MIX = {
    "bulk-walk": dict(batch=512, max_batches=4000),
    "churn": dict(query_rate=1000, insert_rate=2, delete_rate=0.5,
                  warm_cycles=2),
    "churn-cycle": dict(batch=512, max_batches=320, insert_batch=32,
                        delete_batch=8, warm_cycles=2),
}
#: a small BFS chunk, so that each coalesced dispatch answers few lanes
TINY_ENGINE = dict(bfs_chunk=16)
#: a 3,000-vertex graph needs fewer BFS levels and fixpoint rounds than
#: the full-size controls' caps, so the tiny copies cap at 2
TINY_CONTROL = {"wiki-talk": {"engine": {"max_iters": 2}},
                "email-euall": {"engine": {"max_iters": 2}}}


def edit_json(path: pathlib.Path, **changes):
    data = json.loads(path.read_text())
    for k, v in changes.items():
        if isinstance(v, dict) and isinstance(data.get(k), dict):
            data[k] = {**data[k], **v}
        else:
            data[k] = v
    path.write_text(json.dumps(data, indent=1))
    return data


def benchmark() -> dict:
    """BENCHMARK.json with the entries of ``bench/pending.json`` (cells
    kept out of it for now) added, so that their files stay tested.  A
    pending metric that BENCHMARK.json names too adds its workloads to
    that entry."""
    bm = json.loads((REPO / "BENCHMARK.json").read_text())
    pending = json.loads((REPO / "bench" / "pending.json").read_text())
    for k in ("configs", "workloads", "end_to_end", "per_layer"):
        known = {e["name"]: e for e in bm[k]}
        for e in pending[k]:
            if e["name"] in known:
                known[e["name"]]["workloads"] += e["workloads"]
            else:
                bm[k].append(e)
    return bm


def make_root(tmp: pathlib.Path) -> pathlib.Path:
    root = tmp / "checkout"
    root.mkdir()
    (root / "BENCHMARK.json").write_text(json.dumps(benchmark(), indent=1))
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for cfg in (root / "bench" / "configs").glob("*.json"):
        edit_json(cfg, engine=TINY_ENGINE, **TINY_GRAPH)
        if cfg.stem in TINY_CONTROL:
            edit_json(cfg, control=TINY_CONTROL[cfg.stem])
    for name, changes in TINY_MIX.items():
        edit_json(root / "bench" / "traffic" / f"{name}.json", **changes)
    return root


def cells():
    return [w["name"] for w in benchmark()["workloads"]]
