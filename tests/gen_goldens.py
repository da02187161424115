"""Golden regression outputs for the reference op matrix.

The reference's own tests grade *dimensions* per op (image_test.go:8-142,
assertSize); libvips is not installable in this environment, so true
libvips pixel goldens cannot be produced here. These goldens are the next
strongest thing: the framework's device-path output pixels for the
reference matrix, committed once and graded on every run — they pin the
numerics (any kernel/dtype/default change that moves pixels more than
~1 LSB fails the floor) on top of the exact-dimension parity the
reference asserts. Pixel-accuracy parity against independent oracles
(PIL Lanczos, dense float conv) is test_quality.py's job.

Regenerate deliberately with: python -m tests.gen_goldens
"""

from __future__ import annotations

import io
import json
import os

import numpy as np

GOLDEN_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "goldens")

# (name, operation, options-kwargs, expected (w, h) from image_test.go /
# the reference's dimension semantics on the 550x740 fixture)
MATRIX = [
    ("resize_w300", "resize", {"width": 300}, (300, 404)),            # image_test.go:25-38
    ("resize_300x300", "resize", {"width": 300, "height": 300}, (300, 300)),  # :9-23
    ("resize_w300_nocrop", "resize", {"width": 300, "no_crop": True}, (300, 404)),  # :58-74
    ("fit_300x300", "fit", {"width": 300, "height": 300}, (223, 300)),  # :78-94
    ("enlarge_1440x900", "enlarge", {"width": 1440, "height": 900}, (1440, 900)),
    ("extract_100_100_300x150", "extract",
     {"top": 100, "left": 100, "area_width": 300, "area_height": 150}, (300, 150)),
    ("crop_300x260", "crop", {"width": 300, "height": 260}, (300, 260)),  # :110-142
    ("rotate_90", "rotate", {"rotate": 90}, (740, 550)),
    ("flip", "flip", {}, (550, 740)),
    ("thumbnail_100", "thumbnail", {"width": 100}, (100, 135)),  # aspect kept (image.go:279-284)
    ("blur_s5", "blur", {"sigma": 5.0}, (550, 740)),
    ("zoom_2", "zoom",
     {"factor": 2, "top": 80, "left": 80, "area_width": 200, "area_height": 150},
     (400, 300)),
]

SMARTCROP = ("smartcrop_300x260", "smartcrop", {"width": 300, "height": 260},
             (300, 260))

# Multi-op /pipeline chains: pins the COMBINED plan end-to-end across the
# three resample topologies — FUSED (crop whose target aspect matches the
# source plans a pure cover-resize, so crop+resize collapse into ONE
# direct sample: the r4 adjacent-resample fusion), EXTRACT-BLOCKED (crop
# with an aspect-mismatched window keeps Sample->Extract->Sample), and
# SINGLE-SAMPLE (rotate+thumbnail: nothing to fuse). n_samples is
# asserted at generation AND grading time so a fusion regression is
# caught as a plan-shape change, not just pixel drift. Expected dims
# derive from the reference's per-op semantics on the 550x740 fixture.
PIPELINES = [
    ("pipeline_fused_crop_resize",
     [{"operation": "crop", "params": {"width": 440, "height": 592}},
      {"operation": "resize", "params": {"width": 240}},
      {"operation": "blur", "params": {"sigma": 1.5}},
      {"operation": "convert", "params": {"type": "png"}}],
     (240, 323), 1),
    ("pipeline_crop_resize_blur",
     [{"operation": "crop", "params": {"width": 480, "height": 360}},
      {"operation": "resize", "params": {"width": 240}},
      {"operation": "blur", "params": {"sigma": 1.5}},
      {"operation": "convert", "params": {"type": "png"}}],
     (240, 180), 2),
    ("pipeline_rotate_thumbnail",
     [{"operation": "rotate", "params": {"rotate": 90}},
      {"operation": "thumbnail", "params": {"width": 120}},
      {"operation": "convert", "params": {"type": "png"}}],
     (120, 89), 1),
]


def _pipeline_sample_count(ops: list, src_h: int = 740, src_w: int = 550) -> int:
    import json as _json

    from imaginary_tpu.options import ImageOptions
    from imaginary_tpu.params import parse_json_operations
    from imaginary_tpu.pipeline import _build_pipeline_plan
    from imaginary_tpu.ops.stages import SampleSpec

    o = ImageOptions(operations=parse_json_operations(_json.dumps(ops)))
    plan, *_ = _build_pipeline_plan(o, src_h, src_w, 0, 3, None, None)
    return sum(isinstance(st.spec, SampleSpec) for st in plan.stages)


def _run_pipeline_case(buf: bytes, ops: list):
    import json as _json

    from PIL import Image

    from imaginary_tpu.options import ImageOptions
    from imaginary_tpu.params import parse_json_operations
    from imaginary_tpu.pipeline import process_pipeline

    o = ImageOptions(operations=parse_json_operations(_json.dumps(ops)))
    out = process_pipeline(buf, o)
    return np.asarray(Image.open(io.BytesIO(out.body)).convert("RGB"))


def _setup_cpu():
    import jax

    jax.config.update("jax_platforms", "cpu")


def _run_case(buf: bytes, op: str, kw: dict):
    from PIL import Image

    from imaginary_tpu.options import ImageOptions
    from imaginary_tpu.pipeline import process_operation

    defined = [k for k in kw]
    o = ImageOptions(type="png", **kw)  # PNG out: lossless, no JPEG wobble
    for k in defined:
        o.mark_defined(k)
    out = process_operation(op, buf, o)
    arr = np.asarray(Image.open(io.BytesIO(out.body)).convert("RGB"))
    return arr


def _smartcrop_window(buf: bytes, kw: dict) -> dict:
    """(top, left, new_h, new_w) the smartcrop saliency chose — the window
    offsets are computed on device inside SmartExtractSpec, so replay the
    chain eagerly up to that stage and capture smart_offsets' choice.
    Golden-pinned so a saliency change is caught as a window MOVE, not
    just pixel drift."""
    import jax.numpy as jnp

    from imaginary_tpu import codecs
    from imaginary_tpu.options import ImageOptions
    from imaginary_tpu.ops import chain as chain_mod
    from imaginary_tpu.ops.plan import plan_operation
    from imaginary_tpu.ops.saliency import smart_offsets
    from imaginary_tpu.ops.stages import SmartExtractSpec

    o = ImageOptions(**kw)
    for k in kw:
        o.mark_defined(k)
    # decode exactly as the production path does: smartcrop is
    # shrink-on-load-safe, so the window must be pinned on the SAME
    # (possibly 1/N) decode process_operation grades against
    from imaginary_tpu.pipeline import _pick_shrink

    d = codecs.decode(buf, _pick_shrink("smartcrop", buf, o))
    plan = plan_operation("smartcrop", o, d.array.shape[0], d.array.shape[1],
                          d.orientation, d.array.shape[2])
    params, wide, layout = chain_mod.pack_operands(
        [plan], d.array.shape[0], d.array.shape[1])
    h, w, dyns = chain_mod.unpack_operands(jnp.asarray(params), wide, layout)
    x = jnp.asarray(chain_mod.pad_to_bucket(d.array)[None]).astype(jnp.float32)
    for st, dyn in zip(plan.stages, dyns):
        if isinstance(st.spec, SmartExtractSpec):
            top, left = smart_offsets(x, h, w, dyn["new_h"], dyn["new_w"])
            return {
                "top": int(np.asarray(top).ravel()[0]),
                "left": int(np.asarray(left).ravel()[0]),
                "new_h": int(np.asarray(dyn["new_h"]).ravel()[0]),
                "new_w": int(np.asarray(dyn["new_w"]).ravel()[0]),
            }
        x, h, w = st.spec.apply(x, h, w, dyn)
    raise SystemExit("smartcrop plan has no SmartExtractSpec stage")


def generate_all(out_dir: str = GOLDEN_DIR) -> None:
    _setup_cpu()
    from PIL import Image

    os.makedirs(out_dir, exist_ok=True)
    from tests.conftest import fixture_bytes  # regenerates missing fixtures

    jpg = fixture_bytes("imaginary.jpg")
    smart = fixture_bytes("smart-crop.jpg")

    for name, op, kw, expect_wh in MATRIX:
        arr = _run_case(jpg, op, kw)
        assert (arr.shape[1], arr.shape[0]) == expect_wh, (name, arr.shape)
        Image.fromarray(arr).save(os.path.join(out_dir, f"{name}.png"))
        print(f"golden {name}: {arr.shape[1]}x{arr.shape[0]}")

    for name, ops, expect_wh, n_samples in PIPELINES:
        assert _pipeline_sample_count(ops) == n_samples, (name, "plan shape")
        arr = _run_pipeline_case(jpg, ops)
        assert (arr.shape[1], arr.shape[0]) == expect_wh, (name, arr.shape)
        Image.fromarray(arr).save(os.path.join(out_dir, f"{name}.png"))
        print(f"golden {name}: {arr.shape[1]}x{arr.shape[0]} samples={n_samples}")

    name, op, kw, expect_wh = SMARTCROP
    arr = _run_case(smart, op, kw)
    assert (arr.shape[1], arr.shape[0]) == expect_wh, (name, arr.shape)
    Image.fromarray(arr).save(os.path.join(out_dir, f"{name}.png"))
    window = _smartcrop_window(smart, kw)
    with open(os.path.join(out_dir, "smartcrop_window.json"), "w") as f:
        json.dump(window, f, indent=1, sort_keys=True)
    print(f"golden {name}: window={window}")


if __name__ == "__main__":
    generate_all()
    print("goldens written to", GOLDEN_DIR)
