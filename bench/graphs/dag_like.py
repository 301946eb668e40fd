"""``dag_like``: mostly-forward random edges with a ``back_frac`` share of
back edges (``bench.reference.dag_like``): uniform endpoints, so light
degrees, no hubs and long forward chains.  The configuration gives ``n``,
``m`` and ``generator.back_frac``."""
from bench import reference as R


def make(cfg: dict, seed: int):
    return R.dag_like(cfg["n"], cfg["m"], seed=seed,
                      back_frac=cfg["generator"]["back_frac"])
