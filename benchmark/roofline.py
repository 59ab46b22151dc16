"""Peak table and operation counts for roofline shares.

``PEAKS`` is copied from ``bench.py`` (PR 21).  Published figures:
Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
16 GB HBM at 819 GB/s.  ``vpu_ops`` is DERIVED, not published: the
TensorCore clock from the bf16 peak (197e12 = 2·128·128 per MXU · 4 MXUs
· clock, so 1.5 GHz) times the nominal 32-bit VPU rate (8 sublanes × 128
lanes × 4 ALUs).  A device kind missing here is an error, never a
default: a share of another chip's peak is a wrong number.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "vpu_ops": 8 * 128 * 4 * 1.5e9,  # derived, see the module docstring
    },
}


def device_peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak table for device_kind {device_kind!r}; add its published "
            "peaks, with their source, to benchmark/roofline.py"
        ) from None


def minhash_ops(shingles: int, num_perm: int, k: int) -> int:
    """32-bit VPU lane operations the MinHash of ``shingles`` real
    k-byte windows needs (the count of ``bench._vpu_roofline``): per
    window and permutation a multiply, an add and a min; per window ~2k
    for the rolling hash.  Counted over real article lengths, so tile
    padding and block overlap are not work."""
    return shingles * (num_perm * 3 + 2 * k)


def minhash_bytes(article_bytes: int, articles: int, num_perm: int) -> int:
    """Bytes the MinHash must move at least: every article byte read once
    and one uint32 signature row written per article."""
    return article_bytes + articles * num_perm * 4


def roofline_share(ops: float, nbytes: float, seconds: float, device_kind: str,
                   ops_peak: str) -> tuple[float, str]:
    """``(percent, bound)``: the least time the chip could take — the
    larger of ops over the ``ops_peak`` rate and bytes over HBM bandwidth —
    as a percentage of the measured kernel time, and which bound binds."""
    peaks = device_peaks(device_kind)
    t_ops = ops / peaks[ops_peak]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    least = max(t_ops, t_bytes)
    return 100.0 * least / seconds, (ops_peak if t_ops >= t_bytes else "hbm_bytes_per_s")
