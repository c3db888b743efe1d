"""Distributed MLP — the ``distributed_multilayer_perceptron.py`` entry point.

Session from an empty conf whose ``executor.instances`` is the world size
(``distributed_multilayer_perceptron.py:37-39``), then the same MLP recipe
launched as a local-mode gang (``local_mode=True`` is the reference's own
bring-up path, ``:179``): one process per rank, ``jax.distributed``
rendezvous, gradient psum over the mesh, rank 0's metrics returned.

Usage: python examples/distributed_multilayer_perceptron.py [n_processes]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from machine_learning_apache_spark_tpu import Session
from machine_learning_apache_spark_tpu.launcher import Distributor

spark = (
    Session.builder.appName("DistributedMLP")
    .config("spark.executor.instances", sys.argv[1] if len(sys.argv) > 1 else "2")
    .getOrCreate()
)
executors_n = spark.conf.executor_instances

distributor = Distributor(
    num_processes=executors_n, local_mode=True
)
out = distributor.run(
    "machine_learning_apache_spark_tpu.recipes.mlp:train_mlp",
    log_every=0,
)

print(f"world: {out['world_processes']} processes")
print(f"Training Time: {out['train_seconds']:.3f} sec")
print(f"Test loss: {out['test_loss']:.5f}")
print(f"Test accuracy: {out['accuracy']:.2f}%")
spark.stop()
