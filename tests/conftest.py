"""Test bootstrap: force an 8-virtual-device CPU mesh BEFORE jax import.

This is the JAX analogue of the reference's fake cluster — TorchDistributor
``local_mode=True`` (``distributed_multilayer_perceptron.py:179``) and the
manual ``MASTER_ADDR=localhost`` rendezvous block
(``pytorch_multilayer_perceptron.py:15-21``) — letting every distributed code
path run on one CPU host (SURVEY.md §4).
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

# XLA flags go into the environment (before the CPU client exists) so that
# subprocesses a test starts inherit them; appending preserves any flags the
# host set.
#
# - 8 virtual CPU devices: the launcher scrubs this one for gang children
#   (one device per rank).
# - Codegen capped at AVX, i.e. no FMA. LLVM contracts a*b+c into one
#   rounding wherever a fusion happens to put the multiply next to the add,
#   so two programs doing the same elementwise math in differently shaped
#   fusions (ZeRO-1's flat shard vs the per-leaf optimizer update) differ in
#   the last bit on an FMA host. The bit-identity gates check the algorithm,
#   not the host's contraction choices.
for _flag in (
    "--xla_force_host_platform_device_count=8",
    "--xla_cpu_max_isa=AVX",
):
    if _flag.split("=")[0] not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " " + _flag
        ).strip()

# Tests compile cold, as a fresh checkout does: no compiled program crosses
# from one run (or one test) to the next through <checkout>/.xla_cache.
# The placement rule itself is tested in subprocesses (test_core.py).
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax

jax.config.update("jax_num_cpu_devices", 8)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(1234)


@pytest.fixture(scope="session")
def make_tiny_translator():
    """``make(n_pairs) -> (Translator, source texts)``: an untrained tiny
    MT bundle over ``n_pairs`` synthetic sentence pairs — serving
    semantics don't need a trained model, and init is ~instant where
    training is not."""
    from machine_learning_apache_spark_tpu.data.datasets import (
        synthetic_translation_pairs,
    )
    from machine_learning_apache_spark_tpu.data.text import TextPipeline
    from machine_learning_apache_spark_tpu.inference import Translator
    from machine_learning_apache_spark_tpu.models import (
        Transformer,
        TransformerConfig,
    )

    def make(n_pairs: int):
        pairs = synthetic_translation_pairs(
            n_pairs, min_len=3, max_len=8, seed=0
        )
        src_pipe = TextPipeline.fit([s for s, _ in pairs], max_seq_len=14)
        trg_pipe = TextPipeline.fit([t for _, t in pairs], max_seq_len=14)
        cfg = TransformerConfig(
            src_vocab_size=len(src_pipe.vocab.itos),
            trg_vocab_size=len(trg_pipe.vocab.itos),
            d_model=32, ffn_hidden=64, num_heads=2, num_layers=1,
            max_len=16, dropout=0.0,
        )
        model = Transformer(cfg)
        dummy = np.ones((2, 8), np.int32)
        params = model.init(jax.random.key(0), dummy, dummy)["params"]
        return Translator(model, params, src_pipe, trg_pipe), [
            s for s, _ in pairs
        ]

    return make


def pytest_sessionfinish(session, exitstatus):
    """Sweep stray gang process groups at session end: a launcher test
    that timed out or crashed mid-gang must not leave orphaned ranks
    burning CPU past the pytest run (they would also hold the session's
    coordinator ports open). No-op (returns 0) in any healthy run."""
    del session, exitstatus
    try:
        from machine_learning_apache_spark_tpu.launcher.distributor import (
            kill_stray_gangs,
        )
    except Exception:
        return  # collection-only / broken-import runs have nothing to sweep
    kill_stray_gangs()
