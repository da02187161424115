"""The main path's device programs pass the TPU compiler at real sizes.

Nothing runs: each program is compiled for a described (not attached)
v5e 2x2 host, so a shape, a sharding or a memory footprint the chip's
compiler refuses fails here at no chip time. The topology is described
only inside the `topo` fixture — never while a module is imported — and
only the worker given this file loads the TPU library. Code that asks
jax.default_backend() sees the CPU here, so the bf16 matmul path the chip
takes (ops/stages._mm_dtype) is forced inside the tests.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from imaginary_tpu.options import Colorspace, ImageOptions
from imaginary_tpu.ops import chain as chain_mod
from imaginary_tpu.ops import stages
from imaginary_tpu.ops.buckets import bucket_shape
from imaginary_tpu.ops.plan import plan_operation

HBM_BYTES = 16 * 1024**3  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture
def bf16_matmuls(monkeypatch):
    monkeypatch.setattr(stages, "_mm_dtype", lambda: jnp.bfloat16)


def _flagship_plan(in_h, in_w):
    """The fused resize -> blur -> grayscale chain (__graft_entry__)."""
    opts = ImageOptions(width=300, height=200, sigma=2.0,
                        colorspace=Colorspace.BW)
    return plan_operation("resize", opts, in_h, in_w, 0, 3)


def _chain_args(plan, batch, in_h, in_w, x_sharding, vec_sharding):
    """(x, params, wide, layout) as a launch stages them."""
    hb, wb = bucket_shape(in_h, in_w)
    x = jax.ShapeDtypeStruct((batch, hb, wb, 3), jnp.uint8,
                             sharding=x_sharding)
    params, wide, layout = chain_mod.pack_operands([plan] * batch, in_h, in_w)
    params, *wide = (jax.ShapeDtypeStruct(v.shape, v.dtype,
                                          sharding=vec_sharding)
                     for v in (params, *wide))
    return x, params, tuple(wide), layout


def _fits(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 0 < used < HBM_BYTES, used


@pytest.mark.parametrize("in_h,in_w,batch", [
    (1080, 1920, 8),   # 1080p bucket, a mid batch rung
    (2160, 3840, 4),   # 4K bucket
])
def test_flagship_chain_compiles_on_one_chip(topo, bf16_matmuls,
                                             in_h, in_w, batch):
    one = SingleDeviceSharding(topo.devices[0])
    plan = _flagship_plan(in_h, in_w)
    args = _chain_args(plan, batch, in_h, in_w, one, one)
    fn = jax.jit(chain_mod._run_chain, static_argnums=(0, 4),
                 donate_argnums=(1,))
    compiled = fn.lower(plan.spec_key(), *args).compile()
    _fits(compiled)
    assert "bf16" in compiled.as_text()  # the MXU path the chip runs


def test_spatial_chain_compiles_on_2x2_mesh(topo, bf16_matmuls):
    """The oversize-single route (--mesh-policy auto, --spatial 2): the
    served 4K blur chain with W sharded over the mesh's spatial axis, as
    chip_smoke.py --chips 4 drives it."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("batch", "spatial"))
    x_sh = NamedSharding(mesh, P("batch", None, "spatial", None))
    vec_sh = NamedSharding(mesh, P("batch"))
    plan = plan_operation("blur", ImageOptions(sigma=2.0), 2160, 3840, 0, 3)
    args = _chain_args(plan, 2, 2160, 3840, x_sh, vec_sh)
    fn = jax.jit(chain_mod._run_chain, static_argnums=(0, 4),
                 donate_argnums=(1,))
    compiled = fn.lower(plan.spec_key(), *args).compile()
    _fits(compiled)


def test_sharded_blur_compiles_on_2x2_mesh(topo):
    """parallel/spatial.sharded_blur: the shard_map halo exchange."""
    from imaginary_tpu.parallel.spatial import sharded_blur

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("batch", "spatial"))
    x_sh = NamedSharding(mesh, P("batch", None, "spatial", None))
    vec_sh = NamedSharding(mesh, P("batch"))
    hb, wb = bucket_shape(2160, 3840)
    x = jax.ShapeDtypeStruct((2, hb, wb, 3), jnp.float32, sharding=x_sh)
    vec = jax.ShapeDtypeStruct((2,), jnp.int32, sharding=vec_sh)
    sigma = jax.ShapeDtypeStruct((2,), jnp.float32, sharding=vec_sh)
    fn = jax.jit(lambda x, h, w, s: sharded_blur(x, h, w, s, 6, mesh))
    compiled = fn.lower(x, vec, vec, sigma).compile()
    _fits(compiled)
    assert "collective-permute" in compiled.as_text()  # the halo exchange
