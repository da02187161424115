"""Build the native codec extension in-place.

Usage: python -m imaginary_tpu.native.build  (or `make native`).
Compiles codecs.cpp against system libjpeg/libpng/libwebp into
imaginary_tpu/native/_imaginary_codecs.*.so; codecs/native_backend.py picks
it up on next interpreter start.

Hosts missing the codec dev headers (libwebp-dev is the usual gap) still
get the native SPILL-PATH resampler: build_resample() compiles the same
source with -DITPU_RESAMPLE_ONLY into _imaginary_resample.*.so — no
external libraries at all, just a C++ toolchain. `python -m
imaginary_tpu.native.build` tries the full module first and falls back to
the resample-only one, so `make native` always leaves the fastest
available host resize behind.
"""

from __future__ import annotations

import os
import subprocess
import sys
import sysconfig

HERE = os.path.dirname(os.path.abspath(__file__))

# -O3: the separable resampler's tap loops vectorize only at this level
# (measured 135 -> 46 ms on a 1080p->1440p lanczos3; the codecs just ride
# along — their hot loops live inside libjpeg/libpng anyway).
_CXX_FLAGS = ["-O3", "-shared", "-fPIC", "-std=c++17"]


def _compile(out_name: str, extra: list, verbose: bool,
             src_name: str = "codecs.cpp") -> str:
    src = os.path.join(HERE, src_name)
    suffix = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    out = os.path.join(HERE, out_name + suffix)
    include = sysconfig.get_path("include")
    # install by rename: a process importing the module meanwhile loads
    # the old build or the new one, never a half-written file
    tmp = f"{out}.{os.getpid()}.tmp"
    cmd = ["g++", *_CXX_FLAGS, f"-I{include}", src, "-o", tmp, *extra]
    if verbose:
        print(" ".join(cmd))
    try:
        subprocess.run(cmd, check=True)
        os.replace(tmp, out)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    return out


def build(verbose: bool = True) -> str:
    """Full codec module (needs libjpeg/libpng/libwebp headers; libtiff is
    bound by hand against the runtime .so)."""
    return _compile("_imaginary_codecs", ["-ljpeg", "-lpng", "-lwebp", "-ltiff"],
                    verbose)


def build_no_webp(verbose: bool = True) -> str:
    """Codec module minus webp (libwebp-dev is the usual missing header;
    the binding routes webp traffic to cv2/PIL on such hosts)."""
    return _compile("_imaginary_codecs",
                    ["-DITPU_NO_WEBP", "-ljpeg", "-lpng", "-ltiff"], verbose)


def build_resample(verbose: bool = True) -> str:
    """Dependency-free separable resampler (always buildable with g++)."""
    return _compile("_imaginary_resample", ["-DITPU_RESAMPLE_ONLY"], verbose)


def build_entropy(verbose: bool = True) -> str:
    """Dependency-free JPEG entropy scan codec (always buildable with g++).

    Separate translation unit (entropy.cpp -> _imaginary_entropy) so hosts
    without any codec dev headers still get the native Huffman decode the
    dct transport leans on; codecs/jpeg_dct.py picks it up on next start."""
    return _compile("_imaginary_entropy", [], verbose, src_name="entropy.cpp")


def build_any(verbose: bool = True) -> str:
    """Best available native codec module, most- to least-capable: full
    codecs, codecs minus webp, else the resample-only module. The entropy
    module builds independently (it needs no codec headers at all)."""
    try:
        build_entropy(verbose)
    except Exception as e:
        if verbose:
            print(f"entropy codec build failed ({e}); dct transport falls "
                  "back to the python/numpy decoders", file=sys.stderr)
    try:
        return build(verbose)
    except Exception as e:
        if verbose:
            print(f"full codec build failed ({e}); trying no-webp codec "
                  "build", file=sys.stderr)
    try:
        return build_no_webp(verbose)
    except Exception as e:
        if verbose:
            print(f"no-webp codec build failed ({e}); building "
                  "resample-only module", file=sys.stderr)
        return build_resample(verbose)


if __name__ == "__main__":
    only = sys.argv[1] if len(sys.argv) > 1 else ""
    if only == "entropy":
        path = build_entropy()
    else:
        path = build_any()
    sys.path.insert(0, HERE)
    name = os.path.basename(path).split(".")[0]
    __import__(name)  # smoke import

    print(f"built {path}")
