"""Distributed CNN — the ``distributed_cnn.py`` entry point (the reference's
flagship spark-submit workload, SURVEY.md §3.1).

The reference reads world size from spark-submit's conf
(``distributed_cnn.py:41-43``) and gang-launches ``train_func`` under
TorchDistributor with gloo DDP. Here: same contract — conf-driven world size,
gang of jax.distributed processes, psum-of-grads in the compiled step. On a
real multi-host TPU slice, use ``Distributor.commands_for_hosts`` from the
cluster scheduler instead of local_mode.

Usage: python examples/distributed_cnn.py [n_processes] [data_root]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from machine_learning_apache_spark_tpu import Session
from machine_learning_apache_spark_tpu.launcher import Distributor

spark = (
    Session.builder.appName("DistributedCNN")
    .config("spark.executor.instances", sys.argv[1] if len(sys.argv) > 1 else "2")
    .getOrCreate()
)

out = Distributor(
    num_processes=spark.conf.executor_instances, local_mode=True
).run(
    "machine_learning_apache_spark_tpu.recipes.cnn:train_cnn",
    data_root=sys.argv[2] if len(sys.argv) > 2 else None,
    log_every=0,
)

print(f"world: {out['world_processes']} processes")
print(f"Training Time: {out['train_seconds']:.3f} sec")
print(f"Test loss: {out['test_loss']:.5f}")
print(f"Test accuracy: {out['accuracy']:.2f}%")
spark.stop()
