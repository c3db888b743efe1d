"""Rendezvous coordination — env vars → ``jax.distributed.initialize``.

The reference bootstraps its process group from
``{MASTER_ADDR, MASTER_PORT, WORLD_SIZE, RANK, LOCAL_RANK}`` env vars set
either manually (``pytorch_multilayer_perceptron.py:15-21``) or by
TorchDistributor under spark-submit (commented fallback block,
``distributed_cnn.py:22-27``). The TPU mapping (SURVEY.md §2.4):

    MASTER_ADDR:MASTER_PORT → coordinator_address
    WORLD_SIZE              → num_processes
    RANK                    → process_id

Single-process runs (no env vars, world size 1) skip initialization entirely —
the single-controller JAX runtime needs no rendezvous, just like the
reference's sequential scripts never call ``init_process_group``.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

import jax

from machine_learning_apache_spark_tpu.config import SessionConfig
from machine_learning_apache_spark_tpu.utils import env as envcfg

# Framework-native env names, with the reference's torch names as fallbacks.
ENV_COORDINATOR = "MLSPARK_COORDINATOR"
ENV_NUM_PROCESSES = "MLSPARK_NUM_PROCESSES"
ENV_PROCESS_ID = "MLSPARK_PROCESS_ID"

_initialized = False


@dataclass
class RendezvousSpec:
    coordinator_address: str  # "host:port"
    num_processes: int
    process_id: int

    @classmethod
    def from_env(cls, conf: SessionConfig | None = None) -> "RendezvousSpec | None":
        """Resolve the rendezvous from (in priority order) explicit session
        conf, framework env vars, then the reference's torch-style env vars.
        Returns None when this is a single-process run."""
        conf = conf or SessionConfig()
        if conf.coordinator_address and conf.num_processes > 1:
            return cls(conf.coordinator_address, conf.num_processes, max(conf.process_id, 0))

        addr = envcfg.get_str(ENV_COORDINATOR)
        if addr is None and "MASTER_ADDR" in os.environ:
            addr = f"{os.environ['MASTER_ADDR']}:{os.environ.get('MASTER_PORT', '29500')}"
        world = envcfg.get_int(ENV_NUM_PROCESSES, default=None)
        if world is None:
            world = int(os.environ.get("WORLD_SIZE", "1"))
        rank = envcfg.get_int(ENV_PROCESS_ID, default=None)
        if rank is None:
            rank = int(os.environ.get("RANK", "0"))
        if addr is None or world <= 1:
            return None
        return cls(addr, world, rank)

    def apply_env(self, env: dict[str, str]) -> dict[str, str]:
        """Write this spec into an env mapping (what the launcher sets on each
        spawned worker — TorchDistributor's env distribution step)."""
        env[ENV_COORDINATOR] = self.coordinator_address
        env[ENV_NUM_PROCESSES] = str(self.num_processes)
        env[ENV_PROCESS_ID] = str(self.process_id)
        # Torch-style aliases so reference-shaped user code keeps working.
        host, _, port = self.coordinator_address.partition(":")
        env["MASTER_ADDR"] = host
        env["MASTER_PORT"] = port or "29500"
        env["WORLD_SIZE"] = str(self.num_processes)
        env["RANK"] = str(self.process_id)
        return env


def initialize_from_env(conf: SessionConfig | None = None) -> RendezvousSpec | None:
    """The ``dist.init_process_group('gloo')`` analogue
    (``distributed_cnn.py:152``): idempotent multi-host bootstrap."""
    global _initialized
    spec = RendezvousSpec.from_env(conf)
    if spec is None or _initialized:
        return spec
    # CPU gangs (local_mode bring-up, the fault-drill test gangs) run real
    # cross-process psums/allgathers through gloo, which is
    # ``jax_cpu_collectives_implementation``'s default on the installed jax.
    jax.distributed.initialize(
        coordinator_address=spec.coordinator_address,
        num_processes=spec.num_processes,
        process_id=spec.process_id,
    )
    _initialized = True
    return spec


def shutdown() -> None:
    """``destroy_process_group()`` analogue (``distributed_cnn.py:193``)."""
    global _initialized
    if _initialized:
        jax.distributed.shutdown()
        _initialized = False
