"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports. A kind that is not here is an error: a
roofline share against a guessed peak is no measurement."""

from __future__ import annotations

_V5E = {
    "hbm_GBps": 819.0,       # HBM bandwidth, GB/s
    "ici_GBps": 200.0,       # 1,600 Gbit/s chip-to-chip interconnect
    "bf16_TFLOPs": 197.0,
    "source": "Google Cloud TPU documentation, 'TPU v5e': 16 GB HBM at "
              "819 GB/s, 1,600 Gbit/s ICI, 197 TFLOP/s bf16",
}

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


class UnknownDevice(KeyError):
    pass


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; add "
            f"them to perfbench/peaks.py with their source") from None
