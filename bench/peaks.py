"""Published chip peaks, keyed by JAX's ``device_kind``, and the work
model the kernel layer's roofline share is measured against."""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e" (system architecture): per chip
PEAKS = {
    "TPU v5 lite": dict(
        hbm_bytes_per_s=819e9,
        hbm_bytes=16e9,
        bf16_flops_per_s=197e12,
        int8_ops_per_s=393e12,
        ici_bits_per_s=1600e9,
        source="Google Cloud documentation, TPU v5e"),
}


def peaks_for(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a device not in the table is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/peaks.py "
                       "with their source") from None


def least_bytes_per_round(n: int, live_columns: int,
                          populated_links: int) -> float:
    """The fewest bytes any round body must move in one simulated round:
    two bits per process and live column (its delivered state read and
    written) and, in a round with a live column, four bytes per populated
    out-link (the link's target read).  The same work whatever body
    runs, so a share of it cannot pass 100%."""
    if live_columns <= 0:
        return 0.0
    return n * live_columns * 2 / 8 + 4 * populated_links
