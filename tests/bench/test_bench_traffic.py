"""Traffic generation: the seeded sample of a closed loop, and data
drawn from seeds wider than 32 bits."""
import numpy as np
import pytest

from bench import closed_loop, data


def test_reservoir_keeps_k_uniformly_and_by_seed():
    def sample(seed):
        r = closed_loop.Reservoir(2, data.host_rng(seed))
        for i in range(100):
            r.offer(i)
        return r.items
    assert sample(7) == sample(7) and len(sample(7)) == 2
    hits = np.zeros(100)
    for seed in range(2000):
        hits[sample(seed)] += 1
    assert hits.min() > 10 and hits.max() < 80       # about 40 each


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 5, 2 ** 40 + 3])
def test_images_are_seeded_12_bit_samples(seed):
    a, = data.images(seed, 1, (8, 16), 12)
    b, = data.images(seed, 1, (8, 16), 12)
    c, = data.images(seed + 1, 1, (8, 16), 12)
    a, b, c = (np.asarray(x) for x in (a, b, c))
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.dtype == np.float32 and a.min() >= -2048 and a.max() < 2048
    assert np.all(a == np.round(a))


def test_dci4k_images_are_the_same_draws_as_before_integer_samples():
    """The DCI 4K cell's frames for a fixed seed: bit for bit what the
    harness drew before it knew integer samples (digest of two frames
    at the configuration's shape, float32, DC-shifted)."""
    import hashlib
    import json

    from bench import run as R
    cfg = json.loads((R.BENCH / "configs" / "dci4k-j2k97.json").read_text())
    assert data.samples_dtype(cfg) == np.float32
    assert not data.integer_samples(cfg)
    frames = data.images(2 ** 33 + 7, 2, cfg["shape"], cfg["bit_depth"],
                         data.samples_dtype(cfg))
    h = hashlib.sha256()
    for x in frames:
        h.update(np.asarray(x).tobytes())
    assert h.hexdigest() == ("7ceaad353d04feebdf7f21406c565ab1"
                             "ddc588f7f0de443b8e1e93a60afb1104")


@pytest.mark.parametrize("bit_depth,dtype", [(16, "uint16"), (12, "uint16"),
                                             (8, "uint8")])
def test_integer_samples_are_unshifted_in_their_container(bit_depth, dtype):
    x, y = (np.asarray(a) for a in data.images(2 ** 40 + 3, 2, (64, 256),
                                                bit_depth, dtype))
    for a in (x, y):
        assert a.dtype == np.dtype(dtype)
        assert a.min() >= 0 and a.max() < 2 ** bit_depth
        assert a.max() >= 2 ** bit_depth * 0.99        # the full range
    assert not np.array_equal(x, y)
    # the same draws as the float samples of the seed, unshifted
    f, _ = data.images(2 ** 40 + 3, 2, (64, 256), bit_depth)
    assert np.array_equal(x.astype(np.float32) - 2 ** (bit_depth - 1),
                          np.asarray(f))
    cfg = {"dtype": "float32", "samples": dtype, "bit_depth": bit_depth}
    assert data.samples_dtype(cfg) == np.dtype(dtype)
    assert data.integer_samples(cfg)


def test_samples_that_do_not_fit_their_container_are_refused():
    with pytest.raises(ValueError, match="16-bit samples do not fit uint8"):
        data.images(1, 1, (8, 16), 16, "uint8")


def test_pyramids_have_the_engine_layout():
    assert data.pyramid_shapes((3, 32, 64), 2) == [
        (3, 8, 16), (3, 8, 16), (3, 8, 16), (3, 8, 16),
        (3, 16, 32), (3, 16, 32), (3, 16, 32)]
    p, = data.pyramids(9, 1, (3, 32, 64), 2, 12)
    assert [tuple(x.shape) for x in p] == data.pyramid_shapes((3, 32, 64), 2)
