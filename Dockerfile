# imaginary-tpu container image (role of the reference's multi-stage
# Dockerfile: build native code, run tests, ship a slim runtime with the
# loader libraries + an allocator tuned for a long-lived image service).
#
# Build:  docker build -t imaginary-tpu .
# Run:    docker run -p 9000:9000 imaginary-tpu --enable-url-source
#
# TPU note: on a TPU VM run with the libtpu device mounted
# (`--device /dev/accel0 --privileged` or the tpu-device-plugin on GKE) and
# a jax[tpu]-capable base; JAX_PLATFORMS=cpu makes the same image serve on
# CPU-only hosts.

# ---- build stage: compile the native codec extension -----------------------
FROM python:3.12-slim-bookworm AS build

RUN apt-get update && apt-get install -y --no-install-recommends \
    g++ make libjpeg62-turbo-dev libpng-dev libwebp-dev libtiff-dev \
    && rm -rf /var/lib/apt/lists/*

WORKDIR /src
COPY imaginary_tpu/ imaginary_tpu/
RUN python -m imaginary_tpu.native.build

# ---- test stage: unit suite on an 8-device CPU mesh (race-detector role) ---
FROM build AS test

RUN pip install --no-cache-dir jax flax optax einops numpy pillow pytest \
    opencv-python-headless aiohttp
COPY tests/ tests/
COPY conftest.py* ./
RUN JAX_PLATFORMS=cpu python -m pytest tests/ -x -q && touch /tests-passed

# ---- runtime ---------------------------------------------------------------
FROM python:3.12-slim-bookworm

# Loader libraries for SVG/PDF/HEIF/AVIF (ctypes bindings in
# codecs/vector_backend.py), codec shared objects for the native extension,
# and real truetype fonts for pango-style watermark specs (ops/text.py).
RUN apt-get update && apt-get install -y --no-install-recommends \
    libjpeg62-turbo libpng16-16 libwebp7 libtiff6 \
    librsvg2-2 libcairo2 libpoppler-glib8 libheif1 \
    libnghttp2-14 \
    fonts-dejavu-core curl \
    && rm -rf /var/lib/apt/lists/*

RUN pip install --no-cache-dir jax flax optax einops numpy pillow \
    opencv-python-headless aiohttp
# For TPU VMs swap the line above for:
#   pip install 'jax[tpu]' -f https://storage.googleapis.com/jax-releases/libtpu_releases.html

WORKDIR /app
COPY imaginary_tpu/ imaginary_tpu/
COPY --from=build /src/imaginary_tpu/native/_imaginary_codecs*.so imaginary_tpu/native/
# depending on the test stage forces `docker build` to actually run it
# (BuildKit prunes stages the final image doesn't reference)
COPY --from=test /tests-passed /tmp/tests-passed

# Long-lived glibc processes fragment under per-request allocation churn;
# capping arenas is the stock mitigation (the reference LD_PRELOADs jemalloc
# for the same reason, and documents MALLOC_ARENA_MAX=2 — README.md:235).
# The runtime user `nobody` can write neither a home directory nor the
# checkout, so JAX's persistent compile cache is placed under /tmp.
ENV MALLOC_ARENA_MAX=2 \
    PYTHONUNBUFFERED=1 \
    HOME=/tmp \
    JAX_COMPILATION_CACHE_DIR=/tmp/jax_cache \
    PORT=9000

EXPOSE 9000
USER nobody

HEALTHCHECK --interval=30s --timeout=5s --start-period=120s \
    CMD curl -sf http://127.0.0.1:9000/health || exit 1

ENTRYPOINT ["python", "-m", "imaginary_tpu"]
CMD ["--port", "9000"]
