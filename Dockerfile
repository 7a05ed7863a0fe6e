# gym-tpu development/runtime container.
#
# Role parity with the reference's Dockerfile (CUDA 12.4 + torch dev
# container, /root/reference/Dockerfile:1-44), re-targeted at TPU hosts:
# on a Cloud TPU VM the TPU runtime (libtpu) is provided by the host image;
# this container carries the Python stack + native toolchain. For CPU-only
# CI the same image runs the whole test suite on a virtual 8-device mesh.
#
#   docker build -t gym-tpu .
#   docker run --rm gym-tpu pytest tests/ -q          # CPU mesh tests
#   docker run --rm --privileged --net=host \
#     -e JAX_PLATFORMS=tpu gym-tpu python chip_smoke.py   # on a TPU VM

FROM python:3.12-slim

RUN apt-get update && apt-get install -y --no-install-recommends \
        g++ make git \
    && rm -rf /var/lib/apt/lists/*

WORKDIR /workspace/gym-tpu

# Pinned stack (versions this repo is developed and benchmarked against —
# see requirements.lock). On a TPU VM install jax[tpu] instead of the CPU
# wheel: pip install 'jax[tpu]==0.9.0' -f https://storage.googleapis.com/jax-releases/libtpu_releases.html
COPY requirements.lock .
RUN pip install --no-cache-dir -r requirements.lock

COPY pyproject.toml .
COPY gym_tpu/ gym_tpu/
COPY tests/ tests/
COPY examples/ examples/
COPY scripts/ scripts/
COPY chip_smoke.py ./
RUN pip install --no-cache-dir -e .

# default: prove the build works (8 virtual CPU devices, same as CI)
ENV XLA_FLAGS=--xla_force_host_platform_device_count=8
ENV JAX_PLATFORMS=cpu
CMD ["python", "-m", "pytest", "tests/", "-q"]
