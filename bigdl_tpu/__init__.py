"""bigdl_tpu — a TPU-native distributed deep-learning framework.

A brand-new framework with the capabilities of early BigDL (reference:
jebtang/BigDL, surveyed in SURVEY.md), re-designed for TPU:

- ``bigdl_tpu.nn``        Torch-style layer & criterion library over a pure
                          init/apply core (JAX autodiff; no hand-written
                          backward passes like the reference's
                          ``updateGradInput``/``accGradParameters``).
- ``bigdl_tpu.optim``     Training loops (Local/Distri optimizer), optim
                          methods (SGD/Adagrad/LBFGS), triggers, validation.
- ``bigdl_tpu.dataset``   Composable Transformer data pipelines (images, text).
- ``bigdl_tpu.parallel``  Mesh construction, data/tensor/sequence-parallel
                          shardings, XLA-collective allreduce (replaces the
                          reference's Spark BlockManager parameter server,
                          parameters/AllReduceParameter.scala:53-229).
- ``bigdl_tpu.models``    LeNet, VGG, Inception v1/v2, ResNet, RNN, ...
- ``bigdl_tpu.utils``     Table, checkpoint File IO, Torch .t7 / Caffe import.
- ``bigdl_tpu.observability``  Metric registry, span tracer (Chrome trace
                          JSON), Train/ValidationSummary event logs —
                          host-only (never imports jax at module level).
"""

__version__ = "0.1.0"

from bigdl_tpu import nn, optim, dataset, parallel, utils, models, tensor  # noqa: F401,E402
from bigdl_tpu import observability  # noqa: F401,E402
from bigdl_tpu.utils import compile_cache as _compile_cache  # noqa: E402

# jax's persistent compilation cache: honour JAX_COMPILATION_CACHE_DIR,
# else a fixed directory inside the checkout (utils/compile_cache.py)
_compile_cache.configure()
