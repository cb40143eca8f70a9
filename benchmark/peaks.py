"""The table of peaks and the logical byte count of the chained kernel.

Peaks: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 819 GB/s HBM, 16 GB HBM per chip.  A device that is
not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819.0e9,
        "bf16_flop_per_s": 197.0e12,
        "hbm_bytes": 16.0e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e system architecture)",
    },
}

# node columns a pick-step has to read to score a fleet: used and
# capacity of cpu, memory and disk, one float each
SCORE_COLUMNS = 6
# usage entries a pick-step writes back for the chosen row
WRITE_COLUMNS = 3


def peak(device_kind: str, key: str) -> float:
    try:
        return float(PEAKS[device_kind][key])
    except KeyError as exc:
        raise KeyError(
            f"device kind {device_kind!r} is not in the table of peaks"
        ) from exc


def chain_kernel_bytes(
    evals: int, picks: int, arena_rows: int, itemsize: int = 4
) -> int:
    """The least bytes the chained plan kernel's algorithm moves for
    ``evals`` evaluations of ``picks`` placements each over an arena of
    ``arena_rows`` node rows, from LOGICAL shapes only (never the HLO,
    never the padded launch width): every pick-step reads the six usage
    and capacity columns of every row once to score the fleet, and
    writes the three usage entries of the chosen row.  ``itemsize``:
    bytes of one entry, 4 where the configuration runs float32 and 8
    where it runs float64."""
    if min(evals, picks, arena_rows) < 0:
        raise ValueError("negative shape")
    per_step = (SCORE_COLUMNS * arena_rows + WRITE_COLUMNS) * itemsize
    return evals * picks * per_step


def roofline_pct(bytes_moved: float, device_seconds: float, device_kind: str):
    """Least time the chip needs for the bytes over the time it took.
    The kernel is bound by bytes (a handful of flops per byte)."""
    if device_seconds <= 0:
        return None
    least = bytes_moved / peak(device_kind, "hbm_bytes_per_s")
    return 100.0 * least / device_seconds
