# TPU worker image. The reference built on pytorch/cuda11.7 and bind-mounted
# the HF cache (reference Dockerfile:26-37); here the base is a plain Python
# image with jax[tpu] from the libtpu release channel, and the converted-
# weights model root plus the XLA compilation cache are the volumes.
FROM python:3.12-slim

RUN apt-get update && apt-get install -y --no-install-recommends \
        ffmpeg libgl1 libglib2.0-0 \
    && rm -rf /var/lib/apt/lists/*

WORKDIR /app
COPY pyproject.toml README.md ./
COPY chiaswarm_tpu ./chiaswarm_tpu
# golden-image manifest (chiaswarm-tpu-golden --check against pinned hashes)
COPY goldens ./goldens

RUN pip install --no-cache-dir -e ".[media,download,tpu]" \
         -f https://storage.googleapis.com/jax-releases/libtpu_releases.html

# settings.json + logs; converted model weights; persistent XLA cache.
# The cache is placed from outside the program (compile_cache.py): its
# directory is part of its key, so it is one fixed path on the volume.
VOLUME ["/root/.sdaas"]
ENV SDAAS_ROOT=/root/.sdaas
ENV JAX_COMPILATION_CACHE_DIR=/root/.sdaas/xla_cache

# first run: chiaswarm-tpu-init --download (prefetch + convert + check)
CMD ["chiaswarm-tpu-worker"]
