"""A launch stages its pixels plus ONE packed int32 array of every item's
small params (ops/chain.pack_operands), and _run_chain unpacks it into the
same (h, w, dyns) the stages always saw: the same shapes, dtypes and bits
as a per-key jnp.asarray of the stacked values. Values of more than
_PACK_MAX elements an item (a watermark overlay, the DCT egress quantizer
tables) keep their own operand. The executor counts the arrays its
launches put host->device (stats.launch_puts)."""

import copy
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from PIL import Image
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from imaginary_tpu.cache import CacheSet, DeviceFrameCache
from imaginary_tpu.engine import Executor, ExecutorConfig
from imaginary_tpu.options import Extend, ImageOptions
from imaginary_tpu.ops import chain as chain_mod
from imaginary_tpu.ops.buckets import dct_packed_geometry
from imaginary_tpu.ops.plan import (ImagePlan, StageInstance, plan_operation,
                                    wrap_plan_dct, wrap_plan_yuv420)
from imaginary_tpu.prewarm import _dummy_input, warm_chain
from tests.conftest import fixture_bytes
from tests.gen_goldens import (GOLDEN_DIR, MATRIX, PIPELINES, _run_case,
                               _run_pipeline_case)

H, W = 120, 160


def _img(h=H, w=W, seed=0):
    return np.random.default_rng(seed).integers(0, 256, (h, w, 3), dtype=np.uint8)


def _plan(route):
    if route == "resize":
        return plan_operation("resize", ImageOptions(width=64), H, W, 0, 3)
    if route == "crop":
        return plan_operation("crop", ImageOptions(width=60, height=50), H, W, 0, 3)
    if route == "extract":
        o = ImageOptions(top=10, left=20, area_width=70, area_height=40)
        return plan_operation("extract", o, H, W, 0, 3)
    if route == "embed":
        o = ImageOptions(width=90, height=120, embed=True,
                         extend=Extend.BACKGROUND, background=(10, 200, 30))
        o.mark_defined("embed")
        return plan_operation("resize", o, H, W, 0, 3)
    if route == "blur":
        return plan_operation("blur", ImageOptions(sigma=1.7), H, W, 0, 3)
    if route == "watermark":
        o = ImageOptions(width=100, text="hello tpu", opacity=0.7)
        return plan_operation("watermark", o, H, W, 0, 3)
    if route == "dct":
        shrink = 2
        _, h2, w2, _, _ = dct_packed_geometry(H, W, shrink)
        p = plan_operation("resize", ImageOptions(width=48), h2, w2, 0, 3)
        return wrap_plan_dct(p, H, W, shrink, egress="dct", egress_quality=80)
    raise AssertionError(route)


ROUTES = ["resize", "crop", "extract", "embed", "blur", "watermark", "dct"]


def _varied(plan, n=3):
    """n copies of plan whose every dyn value differs per item."""
    out = []
    for i in range(n):
        p = copy.deepcopy(plan)
        for st in p.stages:
            for k, v in st.dyn.items():
                a = np.asarray(v)
                st.dyn[k] = (a * (1.0 + 0.37 * i) if a.dtype.kind == "f"
                             else a + 3 * i).astype(a.dtype)
        out.append(p)
    return out


def _same_bits(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.dtype == b.dtype and a.tobytes() == b.tobytes()


@pytest.mark.parametrize("route", ROUTES)
def test_unpacked_operands_match_per_key_asarray(route):
    plans = _varied(_plan(route))
    h = np.array([H, H - 1, H - 2], np.int32)
    w = np.array([W, W - 3, W - 5], np.int32)
    params, wide, layout = chain_mod.pack_operands(plans, h, w)
    assert params.dtype == np.int32 and params.shape[0] == 3
    uh, uw, dyns = jax.jit(chain_mod.unpack_operands, static_argnums=2)(
        params, wide, layout)
    assert _same_bits(uh, jnp.asarray(h)) and _same_bits(uw, jnp.asarray(w))
    assert len(dyns) == len(plans[0].stages)
    for i, d in enumerate(dyns):
        keys = plans[0].stages[i].dyn.keys()
        assert d.keys() == keys
        for k in keys:
            ref = jnp.asarray(np.stack([p.stages[i].dyn[k] for p in plans]))
            assert _same_bits(d[k], ref), (route, i, k)
    n_wide = {"watermark": 1, "dct": 2}.get(route, 0)  # overlay; qy, qc
    assert len(wide) == n_wide


@pytest.mark.parametrize("route", ROUTES)
def test_launch_matches_per_key_operands(route):
    """The packed launch's output bits equal _run_chain's with every dyn
    value staged as its own operand, as launches staged them before."""
    plans = _varied(_plan(route), 2)
    if route == "dct":
        arrs = [_dummy_input(plans[0], "dct", H, W)] * 2
        x, h, w = np.stack(arrs), [p.in_h for p in plans], [p.in_w for p in plans]
    else:
        arrs = [_img(seed=s) for s in range(2)]
        x, h, w = np.stack([chain_mod.pad_to_bucket(a) for a in arrs]), H, W
    got = chain_mod.launch_batch(arrs, plans)
    params, _, layout = chain_mod.pack_operands(plans, h, w)
    per_key = tuple(tuple((k, s, d, None) for k, s, d, _ in e) for e in layout)
    wide = tuple(jnp.asarray(np.stack([p.stages[i].dyn[k] for p in plans]))
                 for i, e in enumerate(layout) for k, *_ in e)
    want, _, _ = jax.jit(chain_mod._run_chain, static_argnums=(0, 4))(
        plans[0].spec_key(), jnp.asarray(x), jnp.asarray(params[:, :2]), wide,
        per_key)
    assert _same_bits(got, want)


def test_packing_canonicalises_wide_python_values():
    """float64 and int64 values pack as the float32 / int32 jnp.asarray
    gives; a 1-byte value keeps its own operand."""
    plans = [ImagePlan(stages=[StageInstance(None, {
        "f": 0.1 * (i + 1), "i": np.int64(-7 * i), "v": np.array([1.5, -2.25]),
        "b": np.bool_(i % 2)})], out_h=1, out_w=1) for i in range(2)]
    params, wide, layout = chain_mod.pack_operands(plans, 5, 6)
    assert [e[2] for e in layout[0]] == ["float32", "int32", "float32", "bool"]
    assert [e[3] is None for e in layout[0]] == [False, False, False, True]
    _, _, (d,) = chain_mod.unpack_operands(jnp.asarray(params), wide, layout)
    for k in ("f", "i", "v", "b"):
        assert _same_bits(d[k], jnp.asarray(np.stack([p.stages[0].dyn[k] for p in plans])))


CASES = ([(n, "op", (op, kw)) for n, op, kw, _ in MATRIX]
         + [(n, "pipeline", ops) for n, ops, _, _ in PIPELINES])


@pytest.mark.parametrize("name,kind,case", CASES, ids=[c[0] for c in CASES])
def test_launch_is_byte_identical_to_golden(name, kind, case):
    buf = fixture_bytes("imaginary.jpg")
    arr = _run_case(buf, *case) if kind == "op" else _run_pipeline_case(buf, case)
    golden = np.asarray(Image.open(os.path.join(GOLDEN_DIR, f"{name}.png")).convert("RGB"))
    assert _same_bits(arr, golden), name


def _counted(fn):
    p0 = chain_mod.thread_puts()
    out = fn()
    return out, chain_mod.thread_puts() - p0


@pytest.mark.parametrize("route,puts", [("resize", 2), ("embed", 2),
                                        ("watermark", 3), ("dct", 4)])
def test_launch_puts(route, puts):
    plan = _plan(route)
    if route == "dct":
        arrs = [_dummy_input(plan, "dct", H, W)] * 2
    else:
        arrs = [_img(seed=s) for s in range(2)]
    _, n = _counted(lambda: chain_mod.run_batch(arrs, [plan] * 2))
    assert n == puts


@pytest.mark.parametrize("route,puts", [("resize", 2), ("embed", 2),
                                        ("watermark", 3)])
def test_executor_books_launch_puts(route, puts):
    plan = _plan(route)
    ex = Executor(ExecutorConfig(max_form_ms=1, host_spill=False))
    try:
        futs = [ex.submit(_img(seed=s), plan) for s in range(3)]
        for f in futs:
            f.result(timeout=120)
        d = ex.stats.to_dict()
    finally:
        ex.shutdown()
    assert d["launches"] >= 1
    assert d["launch_puts"] == puts * d["launches"]


@pytest.mark.parametrize("route,puts", [("resize", 2), ("watermark", 3)])
@pytest.mark.parametrize("placement", ["pinned", "sharded"])
def test_pinned_and_sharded_launches_put_the_same(placement, route, puts):
    devs = jax.devices()
    assert len(devs) >= 2
    if placement == "pinned":
        kw = {"device": devs[1]}
    else:
        kw = {"sharding": NamedSharding(Mesh(np.array(devs[:2]), ("batch",)),
                                        P("batch"))}
    plan = _plan(route)
    arrs = [_img(seed=s) for s in range(2)]
    want = chain_mod.run_batch(arrs, [plan] * 2)
    got, n = _counted(lambda: chain_mod.run_batch(arrs, [plan] * 2, **kw))
    assert n == puts
    for a, b in zip(got, want):
        assert _same_bits(a, b)


def test_lane_launches_book_two_puts():
    ex = Executor(ExecutorConfig(max_form_ms=1, host_spill=False,
                                 mesh_policy="lanes", n_devices=2))
    try:
        for s in range(3):
            ex.process(_img(seed=s), _plan("resize"))
        d = ex.stats.to_dict()
    finally:
        ex.shutdown()
    assert d["launches"] >= 1
    assert d["launch_puts"] == 2 * d["launches"]


def test_frame_cache_launch_puts_params_only():
    """A device frame cache hit stages no pixels: the launch puts the
    packed params alone; its miss put the frame once."""
    base = plan_operation("resize", ImageOptions(width=64), H, W, 0, 3)
    plan = wrap_plan_yuv420(base, H, W)
    plan.frame_key = ("digest", 1, "yuv420", plan.in_bucket)
    arr = _dummy_input(plan, "yuv", H, W)
    cs = CacheSet(device_mb=8.0)
    chain_mod.set_device_frame_cache(DeviceFrameCache(cs.device, cs.stats))
    try:
        first, n1 = _counted(lambda: chain_mod.run_batch([arr], [plan]))
        second, n2 = _counted(lambda: chain_mod.run_batch([arr], [plan]))
    finally:
        chain_mod.set_device_frame_cache(None)
    assert (n1, n2) == (2, 1)
    assert cs.stats.device_hits == 1
    assert all(_same_bits(getattr(first[0], k), getattr(second[0], k))
               for k in ("y", "u", "v"))


def test_warm_chain_keys_match_serving():
    """warm_chain compiles the keys serving uses: single_is_warm reads
    warm and an executor serving that chain pays no compile."""
    chain_mod.clear_cache()
    plan = plan_operation("resize", ImageOptions(width=40), 100, 80, 0, 3)
    arr = _img(100, 80)
    assert not chain_mod.single_is_warm(arr, plan)
    warm_chain("resize", ImageOptions(width=40), 100, 80, (1, 2))
    assert chain_mod.single_is_warm(arr, plan)
    before = chain_mod.cache_size()
    ex = Executor(ExecutorConfig(max_form_ms=1, host_spill=False))
    try:
        ex.process(arr, plan)
        assert ex.stats.compile_misses == 0
    finally:
        ex.shutdown()
    assert chain_mod.cache_size() == before
