"""``bow_tie``: a directed graph of the bow-tie shape that web and e-mail
graphs have (a strongly connected core, an IN set that reaches it, an OUT
set it reaches, and small components apart), with heavy-tailed degrees,
made to the component statistics a source publishes.

The configuration gives ``n``, ``m`` and, under ``generator``, the shares
of the largest strongly and weakly connected components, of vertices and
of edges (``scc_vertex_share``, ``scc_edge_share``, ``wcc_vertex_share``,
``wcc_edge_share``), and three shape parameters:

- ``degree_exponent``: the core's vertices carry Chung-Lu weights of a
  power law with this exponent.  A chord of the core, and the core end of
  every edge to or from the periphery, picks its core vertex by weight, so
  a few hubs hold thousands of edges;
- ``out_share``: the share of periphery vertices that the core reaches
  (OUT, the receivers); the rest reach the core (IN, the senders);
- ``tendril_share``: the share of periphery vertices that hang off an
  earlier periphery vertex of their side instead of the core, which gives
  the longest shortest paths.

The core is a directed cycle through its vertices in a random order (so
it is strongly connected) plus weighted chords.  Every periphery vertex
has one edge that attaches it; the rest of the weakly connected
component's edges join a core vertex (by weight) and a periphery vertex
(uniform) in its side's direction, so neither side joins the core.  The
vertices outside it form random trees of two or more vertices.  Edges are
distinct and have no self-loops; vertex names are a random permutation.
"""
import numpy as np


def _counts(cfg: dict):
    n, m, g = cfg["n"], cfg["m"], cfg["generator"]
    c, ec = round(g["scc_vertex_share"] * n), round(g["scc_edge_share"] * m)
    w, ew = round(g["wcc_vertex_share"] * n), round(g["wcc_edge_share"] * m)
    comps = (n - w) - (m - ew)          # trees outside the largest WCC
    if not (2 <= c <= ec and w - c <= ew - ec
            and (n == w or 1 <= comps <= (n - w) // 2)):
        raise ValueError(f"bow_tie: no graph has n={n}, m={m} and the "
                         f"component shares {g}")
    return c, ec, w, ew, comps


def _fill(count: int, draw, taken: set, rng):
    """``count`` edges from ``draw(k) -> (src, dst)``, none a self-loop
    and none in ``taken`` or drawn twice; adds them to ``taken``."""
    src, dst = [], []
    while count > 0:
        s, d = draw(count + count // 4 + 16)
        keep = []
        for a, b in zip(s.tolist(), d.tolist()):
            if a != b and (a, b) not in taken:
                taken.add((a, b))
                keep.append((a, b))
                if len(keep) == count:
                    break
        if keep:
            ks, kd = zip(*keep)
            src.append(np.array(ks, np.int64))
            dst.append(np.array(kd, np.int64))
        count -= len(keep)
    return src, dst


def make(cfg: dict, seed: int):
    n, m, g = cfg["n"], cfg["m"], cfg["generator"]
    c, ec, w, ew, comps = _counts(cfg)
    rng = np.random.default_rng(seed)
    weight = np.arange(1, c + 1, dtype=np.float64) \
        ** (-1.0 / (g["degree_exponent"] - 1.0))
    weight /= weight.sum()

    def hubs(k):
        return rng.choice(c, k, p=weight)

    # the core: vertices 0..c-1, a cycle in a random order, then chords
    ring = rng.permutation(c)
    src, dst = [ring], [np.roll(ring, -1)]
    taken = set(zip(src[0].tolist(), dst[0].tolist()))
    s, d = _fill(ec - c, lambda k: (hubs(k), hubs(k)), taken, rng)
    src += s
    dst += d

    # the periphery: vertices c..w-1, each attached by one edge
    per = np.arange(c, w)
    out = rng.random(per.size) < g["out_share"]
    sides = {True: per[out], False: per[~out]}
    for is_out, vs in sides.items():
        anchor = hubs(vs.size)
        tendril = rng.random(vs.size) < g["tendril_share"]
        tendril[0:1] = False
        j = np.flatnonzero(tendril)
        anchor[j] = vs[(rng.random(j.size) * j).astype(np.int64)]
        a, b = (anchor, vs) if is_out else (vs, anchor)
        src.append(a)
        dst.append(b)
        taken.update(zip(a.tolist(), b.tolist()))

    o, i = sides[True], sides[False]
    share = g["out_share"] if o.size and i.size else float(o.size > 0)

    def extra(k):
        is_out = rng.random(k) < share
        hub = hubs(k)
        far = np.where(is_out,
                       o[rng.integers(0, o.size, k)] if o.size else hub,
                       i[rng.integers(0, i.size, k)] if i.size else hub)
        return np.where(is_out, hub, far), np.where(is_out, far, hub)
    s, d = _fill(ew - ec - (w - c), extra, taken, rng)
    src += s
    dst += d

    # outside: vertices w..n-1 in random trees of two or more vertices
    if n > w:
        size = 2 + rng.multinomial(n - w - 2 * comps,
                                   np.full(comps, 1.0 / comps))
        start = np.repeat(np.cumsum(size) - size, size)
        at = np.arange(n - w) - start          # place within the tree
        kid = np.flatnonzero(at > 0)
        parent = start[kid] + (rng.random(kid.size) * at[kid]).astype(
            np.int64)
        a, b = w + parent, w + kid
        flip = rng.random(kid.size) < 0.5
        src.append(np.where(flip, b, a))
        dst.append(np.where(flip, a, b))

    name = rng.permutation(n)
    src, dst = name[np.concatenate(src)], name[np.concatenate(dst)]
    order = rng.permutation(m)
    assert src.size == m
    return src[order].astype(np.int32), dst[order].astype(np.int32)
