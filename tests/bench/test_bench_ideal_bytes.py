"""Ideal bytes against hand counts, and the table of peaks."""
import pytest

from bench import ideal_bytes as IB


@pytest.mark.parametrize("shape,in_itemsize,out_itemsize,count", [
    ((3, 2160, 4096), 4, 4, 2 * 3 * 2160 * 4096 * 4),     # DCI 4K frame
    ((1080, 2048), 4, 4, 2 * 1080 * 2048 * 4),            # DCI 2K component
    ((16384, 16384), 4, 4, 2 * 16384 * 16384 * 4),        # slide region
    ((16, 1080, 2048), 2, 2, 2 * 16 * 1080 * 2048 * 2),   # bf16 batch
    # uint16 samples in, float32 coefficients out: 6 bytes a sample
    ((10980, 10980), 2, 4, 723362400),                    # Sentinel-2 band
    ((4, 10980, 10980), 2, 4, 4 * 723362400),             # its 4 bands
    ((3, 32, 64), 4, 2, 3 * 32 * 64 * 6),                 # the inverse
])
def test_transform_bytes_hand_counts(shape, in_itemsize, out_itemsize,
                                     count):
    assert IB.transform_bytes(shape, in_itemsize, out_itemsize) == count


def test_dci4k_least_time_on_one_and_four_v5e():
    one = IB.least_seconds((3, 2160, 4096), 4, 4, "TPU v5 lite")
    assert one == pytest.approx(212336640 / 819e9)
    assert IB.least_seconds((3, 2160, 4096), 4, 4, "TPU v5 lite",
                            chips=4) == pytest.approx(one / 4)


def test_hbm_roofline_reads_as_one_itemsize_did():
    """For float32 in and out the reader gives what the single-itemsize
    count gave: 2 * samples * 4 bytes over the peak, per transform."""
    from types import SimpleNamespace

    from bench import run as R
    from bench import trace as TR
    shape = (3, 2160, 4096)
    ctx = SimpleNamespace(
        config={"shape": list(shape)},
        devices=[SimpleNamespace(device_kind="TPU v5 lite")],
        trace=TR.Summary(window_s=30.0, busy_s=29.663, kernel_s=0.0,
                         devices=1, op_s={}, idle_gaps=[]),
        window=SimpleNamespace(extra={"transforms": 12329,
                                      "io_dtypes": ("float32",
                                                    "float32")}))
    want = 100.0 * (2 * 3 * 2160 * 4096 * 4 / 819e9) / (29.663 / 12329)
    assert R.load_metric("hbm_roofline.encode").read(ctx) == \
        pytest.approx(want, rel=1e-12)
    ctx.window.extra.pop("io_dtypes")
    assert R.load_metric("hbm_roofline.decode").read(ctx) is None


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
    intensity = flops / (IB.transform_bytes((1,), 4, 4))    # per pixel
    p = IB.peaks("TPU v5 lite")
    ridge = p["bf16_flops_per_s"] / p["hbm_bytes_per_s"]
    assert intensity < 7 and ridge > 240 and intensity < ridge / 30
