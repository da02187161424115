"""Spatially-sharded kernels with halo exchange.

The reference bounds huge-image memory via libvips' demand-driven tiling
(SURVEY.md section 5.7); the TPU-native equivalent is sharding the image's
width axis across mesh devices and exchanging halos over ICI for
neighborhood ops. This module implements the canonical case — separable
gaussian blur — as a `shard_map` program whose horizontal pass ppermutes
R-wide halo strips between neighbor shards (the image-service analogue of
ring attention's neighbor exchange).

Correctness at image edges and shard seams falls out of normalized
convolution: each shard also exchanges its *validity mask*, so wrapped
halos (ring neighbors that aren't real neighbors) and padding contribute
zero weight.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_EPS = 1e-6


def _gauss_kernel(sigma: jnp.ndarray, radius: int) -> jnp.ndarray:
    taps = jnp.arange(-radius, radius + 1, dtype=jnp.float32)[None, :]
    s = jnp.maximum(sigma, 1e-3)[:, None]
    k = jnp.exp(-0.5 * (taps / s) ** 2)
    k = k / jnp.sum(k, axis=-1, keepdims=True)
    delta = (jnp.abs(taps) < 0.5).astype(jnp.float32)
    return jnp.where(sigma[:, None] > 0, k, delta)


def _conv1d(x: jnp.ndarray, kern: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Per-batch separable conv along H (axis=1) or W (axis=2); x [B,H,W,1|C]."""
    r = (kern.shape[1] - 1) // 2
    kh, kw = ((2 * r + 1, 1) if axis == 1 else (1, 2 * r + 1))
    dn = lax.conv_dimension_numbers((1, 1, 1, 1), (kh, kw, 1, 1), ("NHWC", "HWIO", "NHWC"))

    def one(img, k):
        t = jnp.transpose(img, (2, 0, 1))[..., None]  # [C,H,W,1]
        out = lax.conv_general_dilated(t, k.reshape(kh, kw, 1, 1), (1, 1), "SAME",
                                       dimension_numbers=dn)
        return jnp.transpose(out[..., 0], (1, 2, 0))

    return jax.vmap(one)(x, kern)


def sharded_blur(x, h, w, sigma, radius: int, mesh: Mesh, axis_name: str = "spatial"):
    """Gaussian blur of [B,Hb,Wb,C] images sharded on the W axis.

    The vertical pass is shard-local; the horizontal pass exchanges
    radius-wide halo strips (pixels AND mask) with ring neighbors via
    ppermute before convolving, then keeps the local core.
    """
    n = mesh.shape[axis_name]
    hb, wb = x.shape[1], x.shape[2]
    local_w = wb // n
    if radius >= local_w:
        raise ValueError(f"halo radius {radius} >= local shard width {local_w}")

    x_sh = NamedSharding(mesh, P("batch", None, axis_name, None))
    vec_sh = NamedSharding(mesh, P("batch"))
    x = jax.device_put(x.astype(jnp.float32), x_sh)
    h = jax.device_put(h, vec_sh)
    w = jax.device_put(w, vec_sh)
    sigma = jax.device_put(sigma, vec_sh)

    def local_fn(xl, hl, wl, sl):
        # xl [Bl, Hb, local_w, C]; global col offset of this shard:
        idx = lax.axis_index(axis_name)
        col0 = idx * local_w
        kern = _gauss_kernel(sl, radius)

        ys = jnp.arange(hb, dtype=jnp.int32)[None, :, None]
        xs = col0 + jnp.arange(local_w, dtype=jnp.int32)[None, None, :]
        mask = ((ys < hl[:, None, None]) & (xs < wl[:, None, None]))
        mask = mask.astype(jnp.float32)[..., None]  # [Bl,Hb,local_w,1]

        num = _conv1d(xl * mask, kern, axis=1)
        den = _conv1d(mask, kern, axis=1)

        # halo exchange on W: strips of width `radius` from ring neighbors;
        # wrapped strips are neutralized because their mask rides along
        right_perm = [(i, (i + 1) % n) for i in range(n)]
        left_perm = [(i, (i - 1) % n) for i in range(n)]

        def with_halo(t):
            pad = jnp.zeros(t.shape[:2] + (radius,) + t.shape[3:], t.dtype)
            from_left = lax.ppermute(t[:, :, -radius:], axis_name, right_perm) if n > 1 else pad
            from_right = lax.ppermute(t[:, :, :radius], axis_name, left_perm) if n > 1 else pad
            return jnp.concatenate([from_left, t, from_right], axis=2)

        # mask out wrapped halos: shard 0's left halo and shard n-1's right
        # halo come from ring wraparound and must not contribute
        halo_num = with_halo(num)
        halo_den = with_halo(den)
        left_valid = jnp.where(idx > 0, 1.0, 0.0)
        right_valid = jnp.where(idx < n - 1, 1.0, 0.0)
        edge = jnp.ones((1, 1, local_w + 2 * radius, 1), jnp.float32)
        edge = edge.at[:, :, :radius].mul(left_valid)
        edge = edge.at[:, :, -radius:].mul(right_valid)
        halo_num = halo_num * edge
        halo_den = halo_den * edge

        num2 = _conv1d(halo_num, kern, axis=2)[:, :, radius:-radius]
        den2 = _conv1d(halo_den, kern, axis=2)[:, :, radius:-radius]
        out = num2 / jnp.maximum(den2, _EPS)
        return jnp.where(mask > 0, out, 0.0)

    fn = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P("batch", None, axis_name, None), P("batch"), P("batch"), P("batch")),
        out_specs=P("batch", None, axis_name, None),
    )
    return jax.jit(fn)(x, h, w, sigma)
