"""Importable worker functions for launcher tests (the launcher runs
functions by reference — they must live in a real module, which is itself
the Q13-fix behavior under test)."""

import os


def echo_rank(tag="none"):
    return {
        "rank": int(os.environ.get("MLSPARK_PROCESS_ID", "-1")),
        "world": int(os.environ.get("MLSPARK_NUM_PROCESSES", "-1")),
        "master": os.environ.get("MASTER_ADDR"),
        "tag": tag,
    }


def boom():
    raise RuntimeError("worker exploded (intentional)")


def flaky_until(marker_path):
    """Fails the whole gang until the marker exists; every failing rank
    writes it (any single writer could be SIGKILLed by gang teardown before
    its write lands) — exercises the restart path."""
    import os

    if not os.path.exists(marker_path):
        rank = os.environ.get("MLSPARK_PROCESS_ID", "?")
        with open(f"{marker_path}.{rank}", "w") as f:
            f.write("failed once")
        os.replace(f"{marker_path}.{rank}", marker_path)
        raise RuntimeError("flaky failure (intentional)")
    return {"attempt": "recovered"}


def fail_rank(target=1):
    """Exit nonzero on the targeted rank of the CURRENT world; everyone
    else returns their coordinates (plus the elastic env contract). The
    always-failing rank for the elastic-policy tests: once a shrink
    removes it from the world, the gang succeeds."""
    rank = int(os.environ.get("MLSPARK_PROCESS_ID", "0"))
    if rank == int(target):
        raise RuntimeError(f"rank {rank} exploded (injected permanent loss)")
    return {
        "rank": rank,
        "world": int(os.environ.get("MLSPARK_NUM_PROCESSES", "1")),
        "elastic_env": os.environ.get("MLSPARK_ELASTIC"),
    }


def unpicklable_result():
    return lambda: None  # cannot cross the result-file boundary


def sleep_forever():
    """Never returns (but keeps heartbeating) — only the gang deadline
    can end this worker."""
    import time

    while True:
        time.sleep(0.25)


def cross_process_sum():
    """Verifies jax.distributed actually rendezvoused: allgather each rank's
    value and sum — the collective path the reference delegates to gloo."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import multihost_utils

    rank = jax.process_index()
    world = jax.process_count()
    gathered = multihost_utils.process_allgather(jnp.asarray([rank + 1.0]))
    return {"rank": rank, "world": world, "sum": float(gathered.sum())}


def dp_train_step_parity():
    """Real 2-process DP training: jax.distributed rendezvous, a psum train
    step over a cross-process mesh, replica-sync assertion — the full gloo
    DDP loop (``distributed_multilayer_perceptron.py:122-143``) as compiled
    collectives. Deterministic: the test re-runs the same workload
    single-process and compares losses + the param fingerprint."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from machine_learning_apache_spark_tpu.models import MLP
    from machine_learning_apache_spark_tpu.parallel import make_mesh
    from machine_learning_apache_spark_tpu.parallel.data_parallel import (
        assert_replicas_in_sync,
        make_data_parallel_step,
        params_fingerprint,
    )
    from machine_learning_apache_spark_tpu.parallel.mesh import (
        DATA_AXIS,
        shard_batch,
    )
    from machine_learning_apache_spark_tpu.train.losses import cross_entropy
    from machine_learning_apache_spark_tpu.train.state import (
        TrainState,
        make_optimizer,
    )

    rank, world = jax.process_index(), jax.process_count()
    rng = np.random.default_rng(0)
    feats = rng.normal(size=(16, 4)).astype(np.float32)
    labels = rng.integers(0, 3, 16).astype(np.int64)

    model = MLP(layers=(4, 5, 3))
    params = model.init(jax.random.key(0), jnp.ones((1, 4)))["params"]
    state = TrainState.create(
        apply_fn=model.apply, params=params, tx=make_optimizer("sgd", 0.1)
    )
    mesh = make_mesh({DATA_AXIS: world})

    def loss_fn(p, batch, step_rng):
        x, y = batch
        del step_rng
        return cross_entropy(model.apply({"params": p}, x), y), {}

    step = make_data_parallel_step(loss_fn, mesh)
    shard = 16 // world
    local = (
        feats[rank * shard : (rank + 1) * shard],
        labels[rank * shard : (rank + 1) * shard],
    )
    batch = shard_batch(mesh, local)
    losses = []
    for _ in range(3):
        state, loss, _ = step(state, batch, jax.random.key(1))
        losses.append(float(loss))
    divergence = assert_replicas_in_sync(state.params)
    return {
        "rank": rank,
        "world": world,
        "losses": losses,
        "fingerprint": params_fingerprint(state.params),
        "divergence": divergence,
    }


def fault_drill_train(workdir, epochs=4, checkpoint_every=1):
    """Restart-safe training workload for the fault drill: deterministic
    per-rank MLP training with per-rank checkpoint dirs and
    ``fit(resume=True)``. When the gang is killed mid-run (an injected
    crash/stall on one rank) and retried, every rank resumes from its last
    complete checkpoint and the final loss must match an unfaulted run —
    the tentpole's loss-parity acceptance check. Per-rank checkpoint dirs:
    local-process orbax needs no cross-rank coordination, and the drill
    asserts every rank independently recovers."""
    import os

    import jax
    import jax.numpy as jnp
    import numpy as np

    from machine_learning_apache_spark_tpu.models import MLP
    from machine_learning_apache_spark_tpu.train.checkpoint import (
        CheckpointManager,
    )
    from machine_learning_apache_spark_tpu.train.loop import fit
    from machine_learning_apache_spark_tpu.train.losses import cross_entropy
    from machine_learning_apache_spark_tpu.train.state import (
        TrainState,
        make_optimizer,
    )

    rank = jax.process_index()
    rng = np.random.default_rng(7)
    feats = rng.normal(size=(32, 4)).astype(np.float32)
    labels = rng.integers(0, 3, 32).astype(np.int64)
    loader = [
        (feats[i * 8 : (i + 1) * 8], labels[i * 8 : (i + 1) * 8])
        for i in range(4)
    ]

    model = MLP(layers=(4, 8, 3))
    params = model.init(jax.random.key(0), jnp.ones((1, 4)))["params"]
    state = TrainState.create(
        apply_fn=model.apply, params=params, tx=make_optimizer("sgd", 0.1)
    )

    def loss_fn(p, batch, step_rng):
        del step_rng
        x, y = batch
        return cross_entropy(model.apply({"params": p}, x), y), {}

    with CheckpointManager(os.path.join(workdir, f"ckpt_r{rank}")) as ckpt:
        res = fit(
            state, loss_fn, loader,
            epochs=epochs,
            checkpointer=ckpt,
            checkpoint_every=checkpoint_every,
            resume=True,
            log_every=0,
        )
    return {
        "rank": rank,
        "final_loss": res.final_loss,
        "resumed_step": res.resumed_step,
        "epochs_run": len(res.history),
    }


def multihost_probe():
    """Multi-host control-plane probe: prints a parseable line with this
    rank's view of the world plus a cross-process collective sum — consumed
    by the commands_for_hosts end-to-end test, which drives the LITERAL
    launch commands an external scheduler (spark-submit's role,
    ``distributed_cnn.py:227-231``) would execute."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import multihost_utils

    rank = jax.process_index()
    world = jax.process_count()
    gathered = multihost_utils.process_allgather(jnp.asarray([rank + 1.0]))
    print(
        f"MULTIHOST_RESULT rank={rank} world={world} sum={float(gathered.sum())}",
        flush=True,
    )


def echo_dp_mode():
    """The zero1 env contract as a worker sees it (Distributor(dp_mode=...)
    must plumb MLSPARK_DP_MODE into every rank's environment)."""
    return {
        "dp_mode": os.environ.get("MLSPARK_DP_MODE"),
        "rank": int(os.environ.get("MLSPARK_PROCESS_ID", "-1")),
    }


def echo_serve_env():
    """The serving env contract as a worker sees it: every
    ``MLSPARK_SERVE_*`` variable in the rank's environment."""
    return {
        k: v for k, v in os.environ.items() if k.startswith("MLSPARK_SERVE_")
    }


def echo_ingest_env():
    """The ingest env contract as a worker sees it (Distributor(ingest=...)
    must plumb MLSPARK_INGEST_* into every rank's environment), resolved
    through IngestConfig.from_env exactly as a worker's StreamingPipeline
    would."""
    from machine_learning_apache_spark_tpu.ingest.config import IngestConfig

    cfg = IngestConfig.from_env()
    return {
        "buffer": cfg.buffer,
        "tail": cfg.tail,
        "rank": int(os.environ.get("MLSPARK_PROCESS_ID", "-1")),
    }


def echo_telemetry_http():
    """The observability-plane env contract as a worker sees it
    (Distributor(telemetry_http=...) must plumb MLSPARK_TELEMETRY_HTTP
    into every rank's environment)."""
    return {
        "telemetry_http": os.environ.get("MLSPARK_TELEMETRY_HTTP"),
        "rank": int(os.environ.get("MLSPARK_PROCESS_ID", "-1")),
    }


def elastic_drill_train(workdir, epochs=4, checkpoint_every=1,
                        global_batch=168, steps_per_epoch=2):
    """Elastic-resume workload for the shrink drill: ZeRO-1 training over
    the gang-wide ``data`` mesh with per-rank checkpoint directories and
    ``fit(resume=True)``. Elastic resume itself is resolved through the
    env contract — ``Distributor(elastic=True)`` sets ``MLSPARK_ELASTIC=1``
    — so a shrunken retry reshards the surviving group automatically.

    The default ``global_batch=168 = lcm(8, 7, 6)`` divides every world
    size on the 8 -> 7 -> 6 shrink path: each world slices the SAME
    global rows per step, so the batch schedule (and hence the loss
    trajectory, up to collective reduction order) is world-independent —
    the drill's loss-parity acceptance check depends on it.
    ``bucket_bytes=128`` forces multiple ZeRO-1 buckets, so the reshard
    crosses bucket seams, not just shard boundaries."""
    import os

    import jax
    import jax.numpy as jnp
    import numpy as np

    from machine_learning_apache_spark_tpu.models import MLP
    from machine_learning_apache_spark_tpu.parallel import make_mesh
    from machine_learning_apache_spark_tpu.parallel.mesh import DATA_AXIS
    from machine_learning_apache_spark_tpu.train.checkpoint import (
        CheckpointManager,
    )
    from machine_learning_apache_spark_tpu.train.loop import fit
    from machine_learning_apache_spark_tpu.train.losses import cross_entropy
    from machine_learning_apache_spark_tpu.train.state import (
        TrainState,
        make_optimizer,
    )

    rank, world = jax.process_index(), jax.process_count()
    if global_batch % world:
        raise ValueError(
            f"global_batch {global_batch} must divide world {world}"
        )
    rng = np.random.default_rng(7)
    n = global_batch * steps_per_epoch
    feats = rng.normal(size=(n, 4)).astype(np.float32)
    labels = rng.integers(0, 3, n).astype(np.int64)
    per = global_batch // world
    loader = []
    for s in range(steps_per_epoch):
        rows = slice(s * global_batch, (s + 1) * global_batch)
        gx, gy = feats[rows], labels[rows]
        loader.append(
            (gx[rank * per:(rank + 1) * per], gy[rank * per:(rank + 1) * per])
        )

    model = MLP(layers=(4, 8, 3))
    params = model.init(jax.random.key(0), jnp.ones((1, 4)))["params"]
    state = TrainState.create(
        apply_fn=model.apply, params=params, tx=make_optimizer("adam", 0.05)
    )

    def loss_fn(p, batch, step_rng):
        del step_rng
        x, y = batch
        return cross_entropy(model.apply({"params": p}, x), y), {}

    mesh = make_mesh({DATA_AXIS: world})
    with CheckpointManager(os.path.join(workdir, f"ckpt_r{rank}")) as ckpt:
        res = fit(
            state, loss_fn, loader,
            epochs=epochs,
            mesh=mesh,
            dp_mode="zero1",
            dp_bucket_bytes=128,
            checkpointer=ckpt,
            checkpoint_every=checkpoint_every,
            resume=True,
            log_every=0,
        )
    return {
        "rank": rank,
        "world": world,
        "final_loss": res.final_loss,
        "resumed_step": res.resumed_step,
        "epochs_run": len(res.history),
    }
