"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.  A device missing from the table is an error, never a
default: a share of a peak taken against the wrong chip means nothing."""
from __future__ import annotations

#: Google Cloud documentation, "TPU v5e" (system architecture page):
#: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM2 at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str) -> dict:
    """The peak table row of ``device_kind``; raises :class:`UnknownDevice`
    for a chip the table does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; add a "
            f"row to bench/peaks.py (known: {sorted(PEAKS)})") from None
