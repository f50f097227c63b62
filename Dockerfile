# Training image: the pinned stack of requirements.txt on a slim Python base.
#
# Reference analogue: Dockerfile:1-23 builds on a CUDA 10.2 / cuDNN 7 base
# because the accelerator stack lives in the container.  Here it is a pip
# pin like the rest: requirements.txt names jax, jaxlib and libtpu together.
FROM python:3.12-slim

RUN apt-get update \
    && apt-get install -y --no-install-recommends ca-certificates \
    && rm -rf /var/lib/apt/lists/*

WORKDIR /workspace

COPY requirements.txt requirements-dev.txt ./
RUN pip install --no-cache-dir -r requirements.txt

COPY . .

# run as a non-root user, like the reference image (Dockerfile:18-23)
RUN useradd -m trainer && chown -R trainer /workspace
USER trainer

# The default command is the CPU rehearsal, and says so: the CPU backend on
# an 8-virtual-device mesh.  On a TPU host start the container with
# JAX_PLATFORMS unset (e.g. `-e JAX_PLATFORMS=`) and run chip_smoke.py first.
ENV JAX_PLATFORMS=cpu
ENV XLA_FLAGS=--xla_force_host_platform_device_count=8

CMD ["sh", "src/tpu_jax/run_tpu.sh", "--synthetic-data"]
