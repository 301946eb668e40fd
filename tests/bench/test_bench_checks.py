"""The comparison that decides ``correct`` fails where it must: a run
with the served path broken underneath, or served through the
configuration's control path, comes out not correct.  Tiny sizes on the
CPU, kernels in the Pallas interpreter; the harness's look for a chip is
skipped and the rest of a run is driven as on the chip."""
import jax.numpy as jnp
import numpy as np
import pytest

import benchtiny
from bench import harness as H

SEED = 2 ** 33 + 7


def _run(cell, tmp_path, **kw):
    root = benchtiny.make_root(tmp_path)
    return H.run_cell(cell, SEED, 1.5, trace=False,
                      backend="pallas-interpret", root=root, **kw)


@pytest.mark.parametrize("cell", benchtiny.cells())
def test_control_is_not_correct(cell, tmp_path):
    out = _run(cell, tmp_path, control=True)
    assert not out["correct"]
    assert out["checks"]["wrong_answers"]["value"] > 0


def _unchanged_insert(monkeypatch):
    """Every insert returns the index it was given."""
    from repro.serve.engine import QueryEngine
    monkeypatch.setattr(QueryEngine, "insert", lambda self, s, d: self.index)


def _half_batch(monkeypatch):
    """Only the first half of every batch is computed; the rest of the
    lanes get the answers of the first half."""
    from repro.serve.engine import QueryEngine
    real = QueryEngine.submit

    def submit(self, index, u, v):
        u, v = np.asarray(u), np.asarray(v)
        h = max(1, u.size // 2)
        take = np.arange(u.size) % h
        return real(self, index, u[take], v[take])
    monkeypatch.setattr(QueryEngine, "submit", submit)


def _altered_answer(monkeypatch):
    """The first lane of every BFS-residue dispatch has its answer
    negated where the BFS produces it."""
    from repro.core import query as Q
    real = Q.pruned_bfs

    def pruned_bfs(*a, **kw):
        hit = real(*a, **kw)
        return hit.at[0].set(jnp.logical_not(hit[0]))
    monkeypatch.setattr(Q, "pruned_bfs", pruned_bfs)


FAULTS = {"unchanged_insert": _unchanged_insert,
          "half_batch": _half_batch,
          "altered_answer": _altered_answer}


@pytest.mark.parametrize("fault,cell", [
    (f, c) for f in FAULTS for c in benchtiny.cells()
    # a cell without inserts has no insert step to break
    if not (f == "unchanged_insert" and c == "wiki.bulk")])
def test_fault_is_not_correct(fault, cell, tmp_path, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = _run(cell, tmp_path)
    assert not out["correct"]
    assert out["checks"]["wrong_answers"]["value"] > 0


def test_every_residue_lane_is_checked():
    """The check takes every lane that rode the BFS residue, and at most
    ``per_stratum`` of each label-answered stratum."""
    rng = np.random.default_rng(3)
    batches = []
    for lo in range(0, 4000, 1000):
        answers = rng.random(1000) < 0.5
        bfs = np.flatnonzero(rng.random(1000) < 0.3)
        batches.append(dict(lo=lo, hi=lo + 1000, answers=answers, bfs=bfs,
                            dirty=False, spans=False))
    lanes, strata = H.sample_lanes(batches, None, rng, per_stratum=16)
    residue = np.concatenate([b["lo"] + b["bfs"] for b in batches])
    assert np.isin(residue, lanes).all()
    assert lanes.size == residue.size + 2 * 16
    assert strata[0] == strata[4] == 16
