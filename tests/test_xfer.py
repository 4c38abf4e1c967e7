"""Upload helper (ops/xfer.py): one device_put moves the bytes, stats
record the wall."""

import numpy as np
import pytest

from dsi_tpu.ops import xfer


@pytest.fixture()
def views():
    rng = np.random.default_rng(7)
    return [rng.integers(0, 255, size=1 << 12, dtype=np.uint8)
            for _ in range(3)]


def test_put_views_roundtrip(views):
    before = xfer.stats["upload_s"]
    out = xfer.put_views(views)
    assert len(out) == len(views)
    for host, dev in zip(views, out):
        np.testing.assert_array_equal(host, np.asarray(dev))
    assert xfer.stats["upload_s"] > before


def test_explicit_device(views):
    import jax

    dev = jax.devices()[0]
    out = xfer.put_views(views, device=dev)
    assert all(list(d.devices()) == [dev] for d in out)
