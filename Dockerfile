# tpulab serving image (reference Dockerfile/devel.sh analog).
# Base: a JAX TPU image (GKE TPU node pools mount libtpu; for CPU-only CI
# use the same image — tests force the CPU backend).
FROM python:3.12-slim

RUN apt-get update && apt-get install -y --no-install-recommends \
    build-essential cmake ninja-build protobuf-compiler \
    && rm -rf /var/lib/apt/lists/*

# serving deps (jax[tpu] resolves libtpu on TPU VMs)
RUN pip install --no-cache-dir \
    "jax[tpu]" -f https://storage.googleapis.com/jax-releases/libtpu_releases.html \
    grpcio protobuf prometheus_client cffi numpy ml_dtypes

WORKDIR /app
COPY tpulab/ tpulab/
COPY cpp/ cpp/
COPY examples/ examples/
COPY tools/ tools/
COPY __graft_entry__.py ./

# native runtime core
RUN cmake -S cpp -B cpp/build -G Ninja && ninja -C cpp/build

ENV PYTHONPATH=/app \
    TPULAB_COMPILE_CACHE=/cache/xla
VOLUME ["/cache"]
EXPOSE 50051 9090

ENTRYPOINT ["python", "examples/02_inference_service.py"]
CMD ["--model", "resnet50", "--uint8", "--batching", \
     "--port", "50051", "--metrics-port", "9090"]
