import pytest

from benchmark import roofline


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError, match="no peak table"):
        roofline.device_peaks("cpu")


def test_v5e_peaks_are_the_published_ones():
    p = roofline.device_peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["int8_ops"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["vpu_ops"] == 8 * 128 * 4 * 1.5e9  # derived from the bf16 peak's clock


def test_minhash_ops_count_real_windows():
    # 1,000 windows x (128 perms x (mul, add, min) + 2k hash ops)
    assert roofline.minhash_ops(1000, 128, 5) == 1000 * (384 + 10)
    assert roofline.minhash_bytes(5000, 10, 128) == 5000 + 10 * 128 * 4


def test_roofline_share_names_the_binding_bound():
    kind = "TPU v5 lite"
    peaks = roofline.device_peaks(kind)
    ops = peaks["vpu_ops"] * 0.01  # 10 ms of VPU work
    share, bound = roofline.roofline_share(ops, 1.0, 0.04, kind, "vpu_ops")
    assert bound == "vpu_ops" and share == pytest.approx(25.0)
    nbytes = peaks["hbm_bytes_per_s"] * 0.02  # 20 ms of HBM traffic
    share, bound = roofline.roofline_share(ops, nbytes, 0.04, kind, "vpu_ops")
    assert bound == "hbm_bytes_per_s" and share == pytest.approx(50.0)
