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


def test_pyramids_have_the_engine_layout():
    assert data.pyramid_shapes((3, 32, 64), 2) == [
        (3, 8, 16), (3, 8, 16), (3, 8, 16), (3, 8, 16),
        (3, 16, 32), (3, 16, 32), (3, 16, 32)]
    p, = data.pyramids(9, 1, (3, 32, 64), 2, 12)
    assert [tuple(x.shape) for x in p] == data.pyramid_shapes((3, 32, 64), 2)
