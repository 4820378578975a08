"""Published peaks per chip, and the least bytes each collective must move.

The least-bytes functions count what any implementation of the collective
has to put on a chip's interconnect, from the work alone (kind, payload,
group size) and never from PCCL's schedule, so a roofline share built on
them reads the same whatever implements the collective. ``payload_bytes``
is each member's input, as nccl-tests counts its ``size``.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "ici_bytes_per_s": 1600e9 / 8,  # 1,600 Gbit/s per chip
        "source": "Google Cloud documentation, 'TPU v5e' "
                  "(cloud.google.com/tpu/docs/v5e): per chip 197 TFLOP/s "
                  "bf16, 819 GB/s HBM, 1,600 Gbit/s inter-chip interconnect",
    },
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind`` as JAX names it; an unknown kind is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks for device kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)}") from None


def least_bytes(kind: str, payload_bytes: float, n: int) -> float:
    """Bytes each of ``n`` chips must send for one ``kind`` collective of
    ``payload_bytes`` input per chip.

    all_reduce: 2(n-1)/n * S (reduce-scatter then all-gather of the shards).
    all_gather: (n-1) * S (every chip receives every other chip's input).
    reduce_scatter and all_to_all: (n-1)/n * S.
    """
    if n < 1:
        raise ValueError(f"group size {n} < 1")
    s = float(payload_bytes)
    if kind == "all_reduce":
        return 2.0 * (n - 1) / n * s
    if kind == "all_gather":
        return (n - 1) * s
    if kind in ("reduce_scatter", "all_to_all"):
        return (n - 1) / n * s
    raise ValueError(f"no least-bytes function for collective {kind!r}")


def least_seconds(kind: str, payload_bytes: float, n: int,
                  device_kind: str) -> float:
    """Least time of one collective over the chip's interconnect peak."""
    return least_bytes(kind, payload_bytes, n) / peaks(device_kind)[
        "ici_bytes_per_s"]
