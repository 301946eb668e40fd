"""XLA compilations inside the measured window (persistent-cache loads
included), counted by the harness's ``CompileCounter``: every shape the
window runs, BFS-residue programs of each chunk and tail among them, is
meant to be compiled in set-up, so this reads 0."""


def read(rec):
    return float(rec["window_compiles"])
