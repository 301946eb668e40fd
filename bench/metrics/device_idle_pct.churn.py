"""The device's idle share in a cell whose end-to-end metrics are the
latencies of batches and updates; the same reading as
``device_idle_pct``."""
import pathlib

from bench import harness


def read(rec):
    return harness.read_metric("device_idle_pct", rec,
                               root=pathlib.Path(__file__).parents[2])
