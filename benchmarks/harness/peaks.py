"""Published peaks of the chips the benchmark may run on, keyed by the exact
``device_kind`` JAX reports. A device that is not here is an error, never a
default. (The program has its own table in ``utils/profiling.py``; this is the
yardstick's copy, with the memory bandwidth beside the FLOP rate.)"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM2e
    # at 819 GB/s per chip.
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


class UnknownDevice(Exception):
    pass


def peaks_of(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device_kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
