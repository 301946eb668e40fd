"""The benchmark of the served DBL path: ``python bench/run.py --help``."""
