"""The comparison that decides `correct`.

Every reply of the window is judged by its header: a 200 served by the
device, of the route's format and of the dimensions the reference gives
(`wrong_shape`, limit 0). A sample drawn from the seed, a few replies per
route, is decoded and compared pixel by pixel with the plain reference
(benchmark/oracles/), computed here on the host from the same source bytes
after the program has exited.

The number compared per route is `excess.<route>`: how much more the
served frame is off the reference than the reference itself is once coded
by the same codec at the same quality (PIL: libjpeg-turbo, libwebp),

    MSE(served, ref) / MSE(codec(ref), ref) - 1,

with the MSE over every pixel and channel in 8-bit levels. The codec's
own loss is what the service is allowed; what a wrong kernel, a coarser
precision, a wrong geometry, a wrong blend or another request's frame
adds shows on top of it. Each route's limit is in its traffic file.

Each sampled reply over its limit is also compared with the reference of
every input (`nearest`): whether it is nearest to its own input's, to
another input's (a frame served for the wrong request), or to none within
the limit (garbage), over the whole frame and band by band. That is a
diagnosis kept in the run's detail; it decides nothing.

The control (a run's numbers with the program's replies replaced by the
reference computed at 4 bits per sample, coded the same way) is
`control_bodies`; benchmark/control.py reads both on the chip.
"""

from __future__ import annotations

import functools
import io

import numpy as np
from PIL import Image

from harness import imaging, registry

PIXEL_SAMPLE_PER_ROUTE = 8


class Source:
    """One input: its bytes and, decoded on demand, its pixels."""

    def __init__(self, inp: dict):
        self.data, self.mime, self._px = inp["data"], inp["mime"], inp.get("pixels")

    @property
    def pixels(self) -> np.ndarray:
        if self._px is None:
            self._px = imaging.decode(self.data)
        return self._px

    def size(self) -> tuple:
        if self._px is not None:
            return self._px.shape[:2]
        _, w, h = imaging.image_size(self.data)
        return h, w

    def shrunk(self, w: int, h: int) -> np.ndarray:
        """A JPEG decoded at the smallest DCT scale (1/8, 1/4, 1/2) whose
        frame still covers w x h, as libvips' shrink-on-load; else whole."""
        if self.mime != "image/jpeg":
            return self.pixels
        sh, sw = self.size()
        for d in (8, 4, 2):
            dw, dh = -(-sw // d), -(-sh // d)
            if dw >= w and dh >= h:
                with Image.open(io.BytesIO(self.data)) as im:
                    im.draft("RGB", (dw, dh))
                    px = np.asarray(im.convert("RGB"))
                if px.shape[:2] != (dh, dw):
                    raise RuntimeError(f"draft decode gave {px.shape[:2]}, wanted {(dh, dw)}")
                return px
        return self.pixels


class Ops:
    """What the oracles are built from."""

    def __init__(self, resample=imaging.lanczos):
        self._resample = resample
        self.blur = imaging.gaussian_blur

    @staticmethod
    def size(x) -> tuple:
        return x.size() if isinstance(x, Source) else x.shape[:2]

    @staticmethod
    def load(x, w: int = 0, h: int = 0) -> np.ndarray:
        """Pixels of a source (shrunk on load to cover w x h) or of a frame."""
        if isinstance(x, Source):
            return x.shrunk(w, h) if w and h else x.pixels
        return x

    def resample(self, x: np.ndarray, w: int, h: int) -> np.ndarray:
        if x.shape[:2] == (h, w):
            return x.astype(np.float64)
        return self._resample(x, w, h)

    @staticmethod
    def oracle(name: str):
        return registry.oracle(name)


def route_query(path: str) -> dict:
    from urllib.parse import parse_qsl, urlsplit

    return dict(parse_qsl(urlsplit(path).query))


def expected_frame(route: dict, src: Source, ops: Ops) -> np.ndarray:
    return registry.oracle(route["oracle"])(src, route_query(route["path"]), ops)


def code(frame: np.ndarray, route: dict) -> bytes:
    """The frame coded as the route's replies are: format and quality."""
    buf = io.BytesIO()
    Image.fromarray(imaging.to_u8(frame)).save(buf, route["format"], quality=route["quality"])
    return buf.getvalue()


def mse(a: np.ndarray, b: np.ndarray) -> float:
    d = np.asarray(a, np.float64) - b
    return float(np.mean(d * d))


def excess(served: np.ndarray, ref: np.ndarray, route: dict) -> float:
    if served.shape != ref.shape:
        return float("inf")
    floor = mse(imaging.decode(code(ref, route)), ref)
    return mse(served, ref) / max(floor, 1e-9) - 1.0


def quantize4(frame: np.ndarray) -> np.ndarray:
    """The frame at 4 bits per sample: 16 levels over 0..255."""
    return np.round(np.clip(frame, 0, 255) / 17.0) * 17.0


def control_bodies(records: list, routes: list, inputs: list) -> list:
    """The records with each reply replaced by the control: the reference,
    computed at 4 bits per sample (the precision step below the 8 bits
    the configuration states), coded as the route codes its replies."""
    by_name = {r["name"]: r for r in routes}
    ops, out = Ops(), []
    for rec in records:
        route = by_name[rec["route"]]
        frame = quantize4(expected_frame(route, Source(inputs[rec["input"]]), ops))
        out.append(dict(rec, body=code(frame, route)))
    return out


def sample(window: list, routes: list, seed: int, per_route: int) -> dict:
    """{route: [records]}, drawn from the seed among the served replies."""
    rng = np.random.default_rng([seed & 0xFFFFFFFFFFFFFFFF, 0xC0DE])
    out = {}
    for route in routes:
        pool = [r for r in window if r["ok"] and r["route"] == route["name"]]
        pick = rng.choice(len(pool), size=min(per_route, len(pool)), replace=False) if pool else []
        out[route["name"]] = [pool[j] for j in sorted(pick)]
    return out


NEAREST_BANDS = 8


def nearest(served: np.ndarray, rec: dict, route: dict, refs, count: int) -> dict:
    """Which input's reference a reply over its limit lies nearest to, by
    `excess`: "own", "input <k>", or "none" where no input's is within the
    route's limit; for the whole frame and for each of NEAREST_BANDS bands
    of rows (a frame torn between two requests shows as bands of two
    inputs), with the reply's least, mean and greatest level (a frame all
    white or all black reads alike). `refs(k)` gives the reference_pair of
    input k, of `count`."""
    def name(k, v):
        if v > route["limit"]:
            return "none"
        return "own" if k == rec["input"] else f"input {k}"

    out = {"route": route["name"], "i": rec["i"], "input": rec["input"],
           "levels": [float(f(served)) for f in (np.min, np.mean, np.max)] if served.size else []}
    ref0, _ = refs(rec["input"])
    if served.shape != ref0.shape:
        return dict(out, nearest="none", shape=list(served.shape))
    edges = np.linspace(0, served.shape[0], NEAREST_BANDS + 1).astype(int)
    rows = [slice(None)] + [slice(r0, r1) for r0, r1 in zip(edges[:-1], edges[1:])]
    scores = [{} for _ in rows]  # the whole frame, then each band
    for k in range(count):
        ref, coded = refs(k)
        for score, r in zip(scores, rows):
            score[k] = mse(served[r], ref[r]) / max(mse(coded[r], ref[r]), 1e-9) - 1.0
    whole, bands = scores[0], scores[1:]
    k = min(whole, key=whole.get)
    return dict(out, excess=whole[rec["input"]], nearest=name(k, whole[k]), nearest_excess=whole[k],
                bands=[name(kb, band[kb]) for band in bands for kb in [min(band, key=band.get)]])


def reference_pair(route: dict, src: Source, ops: Ops) -> tuple:
    """The reference and its copy coded as the route codes its replies."""
    ref = expected_frame(route, src, ops)
    return ref, imaging.decode(code(ref, route))


def judge(window: list, routes: list, inputs: list, seed: int, ops: Ops | None = None,
          per_route: int = PIXEL_SAMPLE_PER_ROUTE, picked: dict | None = None,
          diagnose: bool = False) -> tuple:
    """(checks, detail). `checks` maps each compared number to
    {"value", "limit"}; the run is correct where every value is within its
    limit. `window` holds the window's records; `picked` overrides the
    seeded sample (the control judges the program's sample). With
    `diagnose`, `detail["nearest"]` says for each sampled reply over its
    limit which input's reference it lies nearest to."""
    ops = ops or Ops()
    by_name = {r["name"]: r for r in routes}
    sources = {}

    def source(k):
        if k not in sources:
            sources[k] = Source(inputs[k])
        return sources[k]

    shapes: dict = {}
    wrong = []
    for rec in window:
        if not rec["ok"]:
            continue
        route = by_name[rec["route"]]
        size = source(rec["input"]).size()
        key = (route["name"], size)
        if key not in shapes:  # one size per configuration: once per route
            ref = expected_frame(route, source(rec["input"]), ops)
            shapes[key] = (route["format"], ref.shape[1], ref.shape[0])
        try:
            got = imaging.image_size(rec["body"])
        except (OSError, ValueError):
            got = None
        if got != shapes[key]:
            wrong.append((rec["i"], route["name"], got, shapes[key]))
    checks = {"wrong_shape": {"value": len(wrong), "limit": 0}}
    detail = {"wrong_shape": wrong[:10], "excess": {}, "over_limit": [], "nearest": []}
    picked = picked if picked is not None else sample(window, routes, seed, per_route)
    for route in routes:
        vals = []
        refs = functools.cache(lambda k, route=route: reference_pair(route, source(k), ops))
        for rec in picked.get(route["name"], []):
            ref = expected_frame(route, source(rec["input"]), ops)
            try:
                served = imaging.decode(rec["body"])
            except (OSError, ValueError):
                served = np.zeros((0, 0, 3))
            vals.append(excess(served, ref, route))
            if vals[-1] > route["limit"]:
                detail["over_limit"].append((route["name"], rec["i"], rec["body"]))
                if diagnose:
                    detail["nearest"].append(nearest(served, rec, route, refs, len(inputs)))
        worst = max(vals) if vals else float("inf")
        checks[f"excess.{route['name']}"] = {"value": worst, "limit": route["limit"]}
        detail["excess"][route["name"]] = vals
    return checks, detail


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
