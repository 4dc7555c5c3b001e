"""What the benchmark reads from the device itself."""

from __future__ import annotations


def memory_peak_bytes(devices) -> int:
    """HBM held at its fullest, on the fullest chip. On the TPU the
    runtime counts the buffers of the process (`peak_bytes_in_use`) apart
    from the scratch it reserves for the loaded programs' temporaries
    (`peak_bytes_reserved`, a standing reservation): what the chip holds
    is their sum."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use", 0)
                     + stats.get("peak_bytes_reserved", 0))
    return int(max(peaks))
