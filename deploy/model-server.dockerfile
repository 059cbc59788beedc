# TPU model-server image: the in-tree replacement for the reference's
# tf-serving.dockerfile (tensorflow/serving:2.3.0 + baked-in SavedModel,
# reference tf-serving.dockerfile:1-5).  Same pattern: base runtime, bake the
# versioned model artifact into /models, select the model via env.
#
# Build (repo root):
#   docker build -t kdlt-model-server -f deploy/model-server.dockerfile .
# The artifact is produced beforehand with:
#   kdlt-export --model clothing-model --weights xception_v4.h5 --output ./models
# MULTI-MODEL: export any further models into the same root before the build
# (e.g. `kdlt-export --model vit --output ./models`); the server's registry
# scans /models and serves every <name>/<version>/ it finds from one process,
# with the unified scheduler (KDLT_SCHED_POLICY/KDLT_SCHED_WEIGHTS, GUIDE 10h)
# arbitrating their shared device time.  Route via /predict/<model> at the
# gateway or /v1/models/<name>:predict here.
#
# GPU-vs-CPU in the reference is a one-line image swap (tf-serving.dockerfile:1);
# here TPU-vs-CPU is one pip extra: jax[tpu] resolves the TPU PJRT plugin on a
# GKE TPU node, and the identical image falls back to CPU off-TPU (the exported
# StableHLO is lowered for both platforms, export/exporter.py DEFAULT_PLATFORMS).

FROM python:3.11-slim

ENV PYTHONUNBUFFERED=TRUE

# Constrained from the very first resolve: an unpinned jax[tpu] here would
# pull a libtpu matched to a NEWER jaxlib than the pinned one installed
# below, and the stale PJRT plugin fails at runtime on the TPU node.
COPY requirements.lock /tmp/requirements.lock
RUN pip install --no-cache-dir -c /tmp/requirements.lock "jax[tpu]" \
      -f https://storage.googleapis.com/jax-releases/libtpu_releases.html || \
    pip install --no-cache-dir -c /tmp/requirements.lock jax

WORKDIR /app
COPY pyproject.toml requirements.lock ./
COPY kubernetes_deep_learning_tpu ./kubernetes_deep_learning_tpu
# requirements.lock pins the full transitive closure (the reference's
# Pipfile.lock role).
RUN pip install --no-cache-dir -c requirements.lock ".[grpc]"

# Versioned artifact layout /models/<name>/<version>/ -- the same convention
# the reference bakes its SavedModel with (tf-serving.dockerfile:5).
COPY models /models

# Bake a hot XLA compile cache into the image layer (zero-cold-start
# scale-up, GUIDE 10k): AOT-compile every baked model's full bucket ladder
# NOW so each pod this image ever starts warms from disk -- cache hits in
# seconds, exactly when the HPA added the pod because load spiked.  Cache
# keys include the target platform and the build host has no TPU, so this
# bakes the cpu programs; TPU pods pre-fill their shared cache volume at
# init instead (KDLT_AOT_WARM=1, model-server-deployment.yaml).  Fail-soft:
# a warm failure costs cold-start time, never the image build.  The ENV makes
# the bake and every process the image later starts agree on one directory
# (a JAX_COMPILATION_CACHE_DIR set at deploy time still wins over it).
ENV KDLT_COMPILE_CACHE_DIR=/var/cache/kdlt-xla
RUN kdlt-warm --models /models --platform cpu || \
    echo "kdlt-warm: bake failed; pods will compile at first warmup" >&2

# 8500 = msgpack/JSON HTTP (probes, gateway); 8501 = the reference's
# exact gRPC PredictionService wire (serving/grpc_predict.py) so
# TF-Serving-era clients work against this tier unmodified.
EXPOSE 8500 8501
ENTRYPOINT ["kdlt-model-server", "--models", "/models", "--port", "8500", "--grpc-port", "8501"]
