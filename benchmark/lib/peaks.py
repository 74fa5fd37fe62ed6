"""Published peaks of one chip, keyed by ``device_kind``.

Copied from the program's ``obs/profiler.py CHIP_PEAKS``
(flink_jpmml_tpu/obs/profiler.py:61-66; Google Cloud documentation,
"TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s)
with the int8 peak added. A device kind that is not here is an error,
never a default.
"""

from __future__ import annotations

_V5E = {"bf16_flops": 197e12, "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
# substring of device_kind (lower case) → peaks; JAX reports a v5e as
# "TPU v5 lite"
PEAKS = (("v5 lite", _V5E), ("v5e", _V5E))


def peaks_for(device_kind: str) -> dict:
    kind = (device_kind or "").lower()
    for sub, peaks in PEAKS:
        if sub in kind:
            return peaks
    raise KeyError(f"no peaks on file for device kind {device_kind!r}")
