"""Published peaks of one chip, keyed by the ``device_kind`` JAX reports,
and the peak of device memory a run reached, from ``memory_stats()``.

A device that is not in the table is an error, never a default: a rate
against another chip's peak would be meaningless.  (Copied from
``examples/bench_kernels.CHIP_PEAKS``, extended by the memory size and the
source of each number.)
"""
from __future__ import annotations

#: device_kind -> peaks of ONE chip.  Source: Google Cloud documentation,
#: "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
CHIP_PEAKS = {
    "TPU v5 lite": {"bf16_tflops": 197.0, "int8_tops": 393.0,
                    "hbm_gbs": 819.0, "hbm_gb": 16.0,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def chip_peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises for a device the table lacks."""
    if device_kind not in CHIP_PEAKS:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r} in "
            f"perfbench/peaks.py (have {sorted(CHIP_PEAKS)})")
    return CHIP_PEAKS[device_kind]


def memory_peak(stats: dict) -> int:
    """Peak bytes on one chip from its ``memory_stats()``: a LOWER bound.
    On this runtime ``peak_bytes_in_use`` counts live buffers only; what a
    running program takes for its temporaries shows as
    ``peak_bytes_reserved`` (my chip run, PR 22: a program with 8.59 GB of
    temporaries left ``peak_bytes_in_use`` at 19 MB and
    ``peak_bytes_reserved`` at 8.59 GB).  Both are on the chip while the
    program runs, but the two peaks need not coincide, so the larger of the
    two is reported, not their sum."""
    return int(max(stats.get("peak_bytes_in_use") or 0,
                   stats.get("peak_bytes_reserved") or 0))
