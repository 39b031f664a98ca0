"""Ideal bytes against hand counts, and the table of peaks."""
import pytest

from bench import ideal_bytes as IB


@pytest.mark.parametrize("shape,itemsize,count", [
    ((3, 2160, 4096), 4, 2 * 3 * 2160 * 4096 * 4),     # DCI 4K frame
    ((1080, 2048), 4, 2 * 1080 * 2048 * 4),            # DCI 2K component
    ((16384, 16384), 4, 2 * 16384 * 16384 * 4),        # slide region
    ((16, 1080, 2048), 2, 2 * 16 * 1080 * 2048 * 2),   # bf16 batch
])
def test_transform_bytes_hand_counts(shape, itemsize, count):
    assert IB.transform_bytes(shape, itemsize) == count


def test_dci4k_least_time_on_one_and_four_v5e():
    one = IB.least_seconds((3, 2160, 4096), 4, "TPU v5 lite")
    assert one == pytest.approx(212336640 / 819e9)
    assert IB.least_seconds((3, 2160, 4096), 4, "TPU v5 lite",
                            chips=4) == pytest.approx(one / 4)


def test_v5e_peaks_and_unknown_device():
    p = IB.peaks("TPU v5 lite")
    assert (p["hbm_bytes_per_s"], p["hbm_bytes"],
            p["bf16_flops_per_s"]) == (819e9, 16e9, 197e12)
    with pytest.raises(KeyError, match="TPU v9"):
        IB.peaks("TPU v9")


def test_hbm_bandwidth_bounds_the_configured_transform():
    """The MACs a pixel of the configuration's compiled tap program, over
    its levels, against the bytes a pixel: far under the v5e's ridge
    point, so the HBM roofline is the bound that applies."""
    import json

    from bench import run as R
    from repro.kernels.ops import scheme_stats
    cfg = json.loads((R.BENCH / "configs" / "dci4k-j2k97.json").read_text())
    macs = scheme_stats(cfg["wavelet"], cfg["scheme"], False,
                        tuple(cfg["shape"][-2:]))["macs_per_pixel"]
    assert macs == 19.75
    flops = 2 * macs * sum(4.0 ** -lvl for lvl in range(cfg["levels"]))
    intensity = flops / (IB.transform_bytes((1,), 4))    # per pixel
    p = IB.peaks("TPU v5 lite")
    ridge = p["bf16_flops_per_s"] / p["hbm_bytes_per_s"]
    assert intensity < 7 and ridge > 240 and intensity < ridge / 30
