"""Spatially-sharded blur: shard_map halo exchange must match the
single-device normalized-conv blur exactly (same math, different layout).
Needs 8 devices (conftest's XLA_FLAGS or real hardware)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from imaginary_tpu.ops.stages import BlurSpec
from imaginary_tpu.parallel.spatial import sharded_blur

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs 8 devices (XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def _mesh(batch, spatial):
    devs = np.array(jax.devices()[: batch * spatial]).reshape(batch, spatial)
    return Mesh(devs, ("batch", "spatial"))


@pytest.mark.parametrize("spatial", [2, 4])
def test_sharded_blur_matches_local(spatial):
    mesh = _mesh(8 // spatial, spatial)
    rng = np.random.default_rng(0)
    b = 8 // spatial * 2
    x = rng.integers(0, 256, (b, 64, 128, 3)).astype(np.float32)
    h = np.full((b,), 60, np.int32)   # valid region smaller than bucket
    w = np.full((b,), 120, np.int32)
    sigma = np.full((b,), 3.0, np.float32)

    out_sh = np.asarray(sharded_blur(jnp.asarray(x), jnp.asarray(h), jnp.asarray(w),
                                     jnp.asarray(sigma), radius=8, mesh=mesh))

    ref, _, _ = BlurSpec(radius=8).apply(jnp.asarray(x), jnp.asarray(h), jnp.asarray(w),
                                         {"sigma": jnp.asarray(sigma)})
    ref = np.asarray(ref)
    np.testing.assert_allclose(out_sh, ref, atol=1e-2)


def test_halo_radius_guard():
    mesh = _mesh(2, 4)
    x = jnp.zeros((2, 16, 64, 3))
    with pytest.raises(ValueError, match="halo radius"):
        sharded_blur(x, jnp.array([16, 16]), jnp.array([64, 64]),
                     jnp.array([1.0, 1.0]), radius=16, mesh=mesh)
