"""machine_learning_apache_spark_tpu — a TPU-native ML framework.

A brand-new JAX/XLA framework with the capabilities of the reference repo
``Makkan13/Machine_Learning---Apache-Spark`` (Spark-launched PyTorch training),
re-designed TPU-first:

- ``session``   — Spark-session-equivalent run configuration layer (reference L0,
  ``mllib_multilayer_perceptron_classifier.py:12-19``).
- ``data``      — ingestion: libsvm reader, image/text dataset loaders, distributed
  sampler, device-feeding loader (reference L1-L3).
- ``text``      — tokenizer / vocab / transform chains (reference C13).
- ``models``    — the model zoo: MLP, CNN, LSTM, encoder-decoder Transformer
  (reference C2/C5/C8/C14-C23) as reusable Flax modules.
- ``ops``       — attention core, masks, positional encodings, layer norm; Pallas
  kernels for the hot paths.
- ``parallel``  — mesh construction, data/tensor/sequence parallelism. The
  reference's DDP-over-gloo (C11) becomes ``lax.pmean`` of grads over the mesh
  axis ``"data"`` inside a compiled step.
- ``train``     — losses, metrics, train state, fit/evaluate loops, timing spans
  (reference L7, the loop machinery every script re-implements inline).
- ``launcher``  — the TorchDistributor equivalent (reference C12): spawn one
  process per host, rendezvous, run a function by reference, rank-0 result.
- ``mllib``     — L-BFGS MLP baseline trainer + evaluator (reference C1 parity).
- ``utils``     — prng, logging, checkpointing, profiling hooks.

The package directory name is the importable form of the project name
``machine_learning---apache-spark_tpu`` (dashes are not valid in Python
identifiers).
"""

__version__ = "0.1.0"

import os as _os

# Backend override through the config API, applied at first package
# import — the ``spark.master local`` analogue:
#
#   MLSPARK_PLATFORM=cpu MLSPARK_CPU_DEVICES=8 python examples/cnn.py
#
# ``JAX_PLATFORMS`` does the same job when it is in the environment before
# jax is first imported (checked on the CPU sandbox and on the TPU host,
# PERF.md "Bring-up"); these knobs are the spelling that still works when
# an embedding program imported jax first, because the config API is
# honoured until the first backend touch. The launcher sets both on the
# children it spawns. After a backend exists the update has no effect.
#
# Direct reads by design: this block must run before the first jax import
# settles a platform, and utils.env sits in the jax-importing utils package.
# Both names ARE registered; only the accessor differs.
# mlspark-lint: ok env-direct-read -- pre-platform bootstrap, see comment above
if _os.environ.get("MLSPARK_PLATFORM") or _os.environ.get("MLSPARK_CPU_DEVICES"):
    import jax as _jax

    if _os.environ.get("MLSPARK_PLATFORM"):  # mlspark-lint: ok env-direct-read -- pre-platform bootstrap, see top of block
        _jax.config.update("jax_platforms", _os.environ["MLSPARK_PLATFORM"])  # mlspark-lint: ok env-direct-read -- pre-platform bootstrap
    if _os.environ.get("MLSPARK_CPU_DEVICES"):  # mlspark-lint: ok env-direct-read -- pre-platform bootstrap, see top of block
        _jax.config.update("jax_num_cpu_devices", int(_os.environ["MLSPARK_CPU_DEVICES"]))  # mlspark-lint: ok env-direct-read -- pre-platform bootstrap

from machine_learning_apache_spark_tpu.utils.compilation_cache import (
    ensure_compilation_cache as _ensure_compilation_cache,
)

# One persistent compile cache for every process that imports the package
# (recipes/fit, ServingEngine warm-up, gang children, bench.py,
# chip_smoke.py) — placed before any of them can compile.
_ensure_compilation_cache()

from machine_learning_apache_spark_tpu.session import Session, SessionBuilder

__all__ = ["Session", "SessionBuilder", "__version__"]
