"""Distributed LSTM — the ``distributed_lstm.py`` entry point.

Same recipe as ``examples/lstm.py`` under a process gang; the datapipe
sharding the reference builds but never uses (quirk Q5) is here a real
``DistributedSampler`` shard per rank with epoch reshuffling.

Usage: python examples/distributed_lstm.py [n_processes] [ag_news_root]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from machine_learning_apache_spark_tpu import Session
from machine_learning_apache_spark_tpu.launcher import Distributor

spark = (
    Session.builder.appName("DistributedLSTM")
    .config("spark.executor.instances", sys.argv[1] if len(sys.argv) > 1 else "2")
    .getOrCreate()
)

out = Distributor(
    num_processes=spark.conf.executor_instances, local_mode=True
).run(
    "machine_learning_apache_spark_tpu.recipes.lstm:train_lstm",
    data_root=sys.argv[2] if len(sys.argv) > 2 else None,
    log_every=0,
)

print(f"world: {out['world_processes']} processes")
print(f"Training Time: {out['train_seconds']:.3f} sec")
print(f"Test accuracy: {out['accuracy']:.2f}%")
spark.stop()
