#!/usr/bin/env python
"""Comms bench: DP update mode × bucket size × comms dtype, CPU mesh.

Sweeps the data-parallel update path on the virtual 8-device CPU mesh
(the same fake cluster the test suite uses):

- ``replicated`` — ``make_data_parallel_step`` (full-gradient allreduce,
  replicated optimizer state);
- ``zero1`` — ``parallel.zero.make_zero1_step`` (bucketed reduce-scatter
  → 1/N sharded update → allgather) across bucket sizes, comms dtypes
  (fp32 / bf16 / int8-with-per-bucket-scale), and the ``overlap`` knob
  (pipelined bucket schedule on/off);
- ``zero1-hybrid`` — the same fused step on a 2-D ``data x model`` mesh
  composing ZeRO-1 with tensor parallelism, checked to parity against a
  pure-TP + replicated-DP reference (``shard_state`` +
  ``make_train_step``), swept across wire dtypes (fp32 anchor, bf16,
  int8-with-per-bucket-scale) with per-dtype parity drift and
  reduce-scatter byte-shrink columns.

Each zero1 sweep point carries an ``exposed_collective_ms_est`` column:
the standalone measured reduce-scatter + allgather time scaled by the
static exposed fraction from ``zero.comms_bytes_per_step`` (1/n_buckets
with overlap on, 1.0 with overlap off) — the number that makes the
overlap win legible instead of buried in a fused step time.

Besides the throughput sweep it records the PR's acceptance evidence:
the ZeRO-1 trajectory-equivalence check against the replicated step
(bit-identity for fp32 comms — in BOTH overlap modes — max-abs-diff for
the lossy dtypes) and the per-chip optimizer-state-bytes ratio (≈ 1/N
of replicated). Collective phases run standalone under
``comms.reduce_scatter``/``comms.allgather`` telemetry spans so the
artifact (and any merged gang report) carries their p50/p99.

Writes one JSON artifact (``--out``, default stdout). ``--smoke`` is the
tier-1 CI configuration: a 2-point sweep with tiny step counts, seconds
on CPU. CPU collective *times* say nothing about ICI — the artifact is
about semantics (equivalence, memory) and relative wire-byte accounting;
the mode × bucket × dtype surface transfers to TPU, the absolute
numbers do not.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# The virtual 8-device CPU mesh must be requested BEFORE jax import
# (tests/conftest.py contract).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# ... and codegen capped at AVX (no FMA contraction), without which the
# bit-identity gates measure the host's fusion choices (tests/conftest.py).
for _flag in (
    "--xla_force_host_platform_device_count=8",
    "--xla_cpu_max_isa=AVX",
):
    if _flag.split("=")[0] not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " " + _flag
        ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from machine_learning_apache_spark_tpu import telemetry  # noqa: E402
from machine_learning_apache_spark_tpu.models import MLP  # noqa: E402
from machine_learning_apache_spark_tpu.parallel import (  # noqa: E402
    DATA_AXIS,
    MODEL_AXIS,
    data_model_mesh,
    make_mesh,
)
from machine_learning_apache_spark_tpu.parallel import zero  # noqa: E402
from machine_learning_apache_spark_tpu.parallel.data_parallel import (  # noqa: E402
    make_data_parallel_step,
)
from machine_learning_apache_spark_tpu.parallel.mesh import shard_batch  # noqa: E402
from machine_learning_apache_spark_tpu.parallel.tensor_parallel import (  # noqa: E402
    shard_state,
)
from machine_learning_apache_spark_tpu.telemetry import aggregate  # noqa: E402
from machine_learning_apache_spark_tpu.train.loop import (  # noqa: E402
    make_train_step,
)
from machine_learning_apache_spark_tpu.train.state import (  # noqa: E402
    TrainState,
    make_optimizer,
)
from jax.sharding import PartitionSpec as P  # noqa: E402

WIDTH = 256  # ~100k params with the in/out stems: enough for real buckets


def _workload(tp_rules: bool = False):
    """Deterministic regression workload: MLP(64→256→256→64), fixed
    batches. Everything derives from fixed seeds so every mode sees the
    identical trajectory inputs. ``tp_rules=True`` annotates the kernels
    with logical TP axes (boxed params) for the hybrid-mesh leg."""
    model = MLP(layers=(64, WIDTH, WIDTH, 64), tp_rules=tp_rules)
    params0 = model.init(jax.random.key(0), jnp.ones((8, 64)))["params"]

    def loss_fn(params, batch, rng):
        del rng
        x, y = batch
        out = model.apply({"params": params}, x)
        loss = jnp.mean((out - y) ** 2)
        return loss, {}

    gen = np.random.default_rng(1234)

    def batch_at(i):
        del i  # the generator stream orders them
        x = jnp.asarray(gen.normal(size=(64, 64)), jnp.float32)
        y = jnp.asarray(gen.normal(size=(64, 64)), jnp.float32)
        return x, y

    return model, params0, loss_fn, batch_at


def _fresh_state(model, params0, tx):
    return TrainState.create(
        apply_fn=model.apply,
        params=jax.tree.map(jnp.copy, params0),
        tx=tx,
    )


def _run_replicated(mesh, model, params0, loss_fn, tx, batches, rngs):
    step = make_data_parallel_step(loss_fn, mesh)
    state = _fresh_state(model, params0, tx)
    for b, r in zip(batches, rngs):
        state, loss, _ = step(state, shard_batch(mesh, b), r)
    jax.block_until_ready(state.params)
    return state


def _run_zero1(mesh, model, params0, loss_fn, tx, batches, rngs, config):
    state = zero.init_sharded(
        apply_fn=model.apply,
        params=jax.tree.map(jnp.copy, params0),
        tx=tx,
        mesh=mesh,
        config=config,
    )
    step = zero.make_zero1_step(loss_fn, mesh, state)
    for b, r in zip(batches, rngs):
        state, loss, _ = step(state, shard_batch(mesh, b), r)
    jax.block_until_ready(state.params)
    return state, step


def _max_diff(a, b) -> float:
    return max(
        jax.tree.leaves(
            jax.tree.map(
                lambda x, y: float(
                    np.max(np.abs(np.asarray(x) - np.asarray(y)))
                ),
                a, b,
            )
        )
    )


def equivalence_check(mesh, steps: int, dtypes=zero.COMMS_DTYPES) -> dict:
    """N-step trajectory parity: zero1(fp32) must be bit-identical to the
    replicated step in BOTH overlap modes (the pipelined schedule is
    elementwise-identical to the serial barrier, so overlap on/off must
    also match each other bit-for-bit); bf16/int8 report their drift.
    Plus the per-chip optimizer-memory ratio the ZeRO-1 rewrite exists
    for. ``dtypes`` must include float32 (the gate); smoke passes just
    that one. Bucket size 65536 keeps several buckets in play so the
    bit-identity check crosses bucket seams."""
    model, params0, loss_fn, batch_at = _workload()
    tx = make_optimizer("adam", 1e-2)
    batches = [batch_at(i) for i in range(steps)]
    rngs = [jax.random.fold_in(jax.random.key(7), i) for i in range(steps)]

    rep = _run_replicated(mesh, model, params0, loss_fn, tx, batches, rngs)
    rep_params = jax.device_get(rep.params)
    replicated_bytes = zero.opt_state_bytes(rep.opt_state)

    n = mesh.shape[DATA_AXIS]
    out: dict = {"steps": steps, "n_devices": int(n)}
    per_chip = None
    fp32_params = None
    for dtype in dtypes:
        cfg = zero.Zero1Config(bucket_bytes=65536, comms_dtype=dtype)
        z, _ = _run_zero1(
            mesh, model, params0, loss_fn, tx, batches, rngs, cfg
        )
        diff = _max_diff(rep_params, jax.device_get(z.params))
        out[f"max_abs_diff_{dtype}"] = diff
        if dtype == "float32":
            out["bit_identical_float32"] = diff == 0.0
            per_chip = zero.opt_state_bytes_per_chip(z)
            fp32_params = jax.device_get(z.params)
    # The serial barrier schedule (overlap=False) against the pipelined
    # default: same trajectory, bit for bit.
    cfg_off = zero.Zero1Config(
        bucket_bytes=65536, comms_dtype="float32", overlap=False
    )
    z_off, _ = _run_zero1(
        mesh, model, params0, loss_fn, tx, batches, rngs, cfg_off
    )
    diff_off = _max_diff(fp32_params, jax.device_get(z_off.params))
    out["max_abs_diff_overlap_off_vs_on"] = diff_off
    out["bit_identical_overlap_fp32"] = diff_off == 0.0
    ratio = per_chip / replicated_bytes
    bound = 1.0 / n + 0.01  # ε: pad tail + replicated step-count scalars
    out.update(
        opt_state_bytes_per_chip=per_chip,
        replicated_opt_state_bytes=replicated_bytes,
        opt_state_ratio=round(ratio, 5),
        opt_state_bound=round(bound, 5),
        opt_state_ok=ratio <= bound,
    )
    out["ok"] = bool(
        out["bit_identical_float32"]
        and out["bit_identical_overlap_fp32"]
        and out["opt_state_ok"]
    )
    return out


def bench_point(mesh, mode: str, steps: int, config=None) -> dict:
    """One sweep point: steps/sec of the fused step after warmup."""
    model, params0, loss_fn, batch_at = _workload()
    tx = make_optimizer("adam", 1e-2)
    batch = shard_batch(mesh, batch_at(0))
    rng = jax.random.key(3)
    point = {"mode": mode}
    if mode == "replicated":
        step = make_data_parallel_step(loss_fn, mesh)
        state = _fresh_state(model, params0, tx)
    else:
        state = zero.init_sharded(
            apply_fn=model.apply,
            params=jax.tree.map(jnp.copy, params0),
            tx=tx,
            mesh=mesh,
            config=config,
        )
        step = zero.make_zero1_step(loss_fn, mesh, state)
        point.update(
            bucket_bytes=config.bucket_bytes,
            comms_dtype=config.comms_dtype,
            opt_state_bytes_per_chip=zero.opt_state_bytes_per_chip(state),
            **{
                k: step.comms_stats[k]
                for k in (
                    "reduce_scatter_bytes",
                    "allgather_bytes",
                    "n_buckets",
                    "overlap",
                    "hidden_fraction",
                    "bytes_overlapped",
                    "bytes_exposed",
                )
            },
        )
    for _ in range(2):  # compile + settle
        state, loss, _ = step(state, batch, rng)
    jax.block_until_ready(state.params)
    t0 = time.perf_counter()
    for _ in range(steps):
        state, loss, _ = step(state, batch, rng)
    jax.block_until_ready(state.params)
    dt = time.perf_counter() - t0
    point.update(
        steps=steps,
        steps_per_sec=round(steps / dt, 2),
        step_ms=round(dt / steps * 1e3, 3),
        loss=round(float(loss), 4),
    )
    return point


def bench_collectives(mesh, config, reps: int) -> dict:
    """Standalone reduce-scatter / allgather timings under telemetry spans
    — inside the fused step XLA overlaps them with compute, so the span
    p50/p99 the report wants has to come from separately-jitted phases.
    Returns the mean per-phase milliseconds; ``main`` scales them by the
    static exposed fraction into ``exposed_collective_ms_est``."""
    axis = config.axis
    n = mesh.shape[axis]
    model, params0, _, _ = _workload()
    plan = zero.make_flat_plan(params0, n, config.bucket_bytes)

    def rs_shard(flat):
        pieces = [
            zero._reduce_scatter_bucket(
                flat[s:e], axis, n, config.comms_dtype
            )
            for s, e in plan.buckets
        ]
        return jnp.concatenate(pieces)

    def ag_shard(shard):
        segments, offset = [], 0
        for s, e in plan.buckets:
            piece_len = (e - s) // n
            segments.append(
                jax.lax.all_gather(
                    shard[offset:offset + piece_len], axis, tiled=True
                )
            )
            offset += piece_len
        return jnp.concatenate(segments)

    # check_vma=False as in zero.make_zero1_step: the standalone
    # collectives mirror that step's body, whose all_gather(tiled=True)
    # output is replicated by construction but typed varying.
    rs = jax.jit(jax.shard_map(
        rs_shard, mesh=mesh, in_specs=(P(),), out_specs=P(axis),
        check_vma=False,
    ))
    ag = jax.jit(jax.shard_map(
        ag_shard, mesh=mesh, in_specs=(P(axis),), out_specs=P(),
        check_vma=False,
    ))
    flat = jnp.ones((plan.padded,), jnp.float32)
    shard = jax.block_until_ready(rs(flat))  # also compiles
    jax.block_until_ready(ag(shard))
    attrs = {
        "bucket_bytes": config.bucket_bytes,
        "comms_dtype": config.comms_dtype,
        "n_buckets": len(plan.buckets),
    }
    rs_ms, ag_ms = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        with telemetry.span("comms.reduce_scatter", **attrs):
            jax.block_until_ready(rs(flat))
        t1 = time.perf_counter()
        with telemetry.span("comms.allgather", **attrs):
            jax.block_until_ready(ag(shard))
        t2 = time.perf_counter()
        rs_ms.append((t1 - t0) * 1e3)
        ag_ms.append((t2 - t1) * 1e3)
    return {
        "reduce_scatter_ms": sum(rs_ms) / len(rs_ms),
        "allgather_ms": sum(ag_ms) / len(ag_ms),
    }


#: Hybrid parity tolerances per wire dtype: fp32 is reduction-order
#: noise only; bf16/int8 add per-bucket QDQ rounding each step, so the
#: bound scales with the wire's quantization granularity (bf16 ~3
#: mantissa decimal digits, int8 bucket-absmax/127 steps) compounding
#: through Adam over the trajectory — the pure-mesh equivalence check
#: reports ~0.09 int8 drift on this same workload, so 0.2 is the
#: trains-equivalently bound, not a tightness claim.
HYBRID_PARITY_TOL = {"float32": 1e-5, "bfloat16": 5e-3, "int8": 0.2}


def bench_hybrid(steps: int, comms_dtypes=("float32",)) -> dict:
    """The hybrid ``data x model`` leg: ZeRO-1 composed with tensor
    parallelism on a 2-D mesh, checked against the pure-TP +
    replicated-DP reference (``shard_state`` + ``make_train_step``).
    Both steps compute one global-batch loss under jit, so the fp32
    trajectories agree to float32 reduction-order tolerance — parity,
    not bit-identity (the fp32 bit-identity gate is the pure-mesh one).

    ``comms_dtypes`` sweeps the compressed-wire column: every dtype
    reruns the same trajectory against the one shared reference, and
    the per-dtype ``wire`` columns carry the parity drift, the
    reduce-scatter byte shrink vs fp32 (bf16 2x, int8 4x minus the
    per-bucket scale scalars), and the unchanged fp32 allgather bytes.
    Must include ``float32`` — it anchors the shrink ratios and the
    top-level compatibility columns."""
    if "float32" not in comms_dtypes:
        raise ValueError("comms_dtypes must include 'float32'")
    n = jax.device_count()
    model_ways = 4 if n % 4 == 0 and n >= 8 else 2
    if n % model_ways or n // model_ways < 2:
        return {"skipped": f"need a 2-D mesh, got {n} devices", "ok": True}
    mesh = data_model_mesh(model_ways)
    model, params0, loss_fn, batch_at = _workload(tp_rules=True)
    tx = make_optimizer("adam", 1e-2)
    batches = [batch_at(i) for i in range(steps)]
    rngs = [jax.random.fold_in(jax.random.key(7), i) for i in range(steps)]

    # Pure-TP + replicated-DP reference: logical-rule placement on the
    # same mesh, plain jitted train step (replicated optimizer state).
    # Built ONCE — every wire dtype is judged against the same params.
    ref = shard_state(
        TrainState.create(
            apply_fn=model.apply,
            params=jax.tree.map(jnp.copy, params0),
            tx=tx,
        ),
        mesh,
    )
    ref_step = make_train_step(loss_fn)
    for b, r in zip(batches, rngs):
        ref, _, _ = ref_step(ref, shard_batch(mesh, b), r)
    jax.block_until_ready(ref.params)
    ref_params = jax.device_get(ref.params)
    replicated_bytes = zero.opt_state_bytes(ref.opt_state)

    wire: dict = {}
    fp32_col: dict = {}
    for dtype in comms_dtypes:
        cfg = zero.Zero1Config(bucket_bytes=65536, comms_dtype=dtype)
        state = zero.init_sharded(
            apply_fn=model.apply,
            params=jax.tree.map(jnp.copy, params0),
            tx=tx,
            mesh=mesh,
            config=cfg,
        )
        step = zero.make_zero1_step(loss_fn, mesh, state)
        for b, r in zip(batches, rngs):
            state, loss, _ = step(state, shard_batch(mesh, b), r)
        jax.block_until_ready(state.params)
        diff = _max_diff(ref_params, jax.device_get(state.params))
        # TP placement must survive the flatten/QDQ/update/unflatten
        # round trip: the wide kernels stay model-sharded every step.
        tp_sharded = any(
            MODEL_AXIS in str(getattr(leaf.sharding, "spec", ""))
            for leaf in jax.tree.leaves(state.params)
        )

        batch = shard_batch(mesh, batch_at(0))
        rng = jax.random.key(3)
        for _ in range(2):  # settle after the trajectory run
            state, loss, _ = step(state, batch, rng)
        jax.block_until_ready(state.params)
        t0 = time.perf_counter()
        for _ in range(steps):
            state, loss, _ = step(state, batch, rng)
        jax.block_until_ready(state.params)
        dt = time.perf_counter() - t0

        col = {
            "comms_dtype": dtype,
            "max_abs_diff_vs_tp_reference": diff,
            "parity_tol": HYBRID_PARITY_TOL[dtype],
            "parity_ok": diff <= HYBRID_PARITY_TOL[dtype],
            "tp_sharding_preserved": bool(tp_sharded),
            "opt_state_bytes_per_chip": zero.opt_state_bytes_per_chip(
                state
            ),
            "steps_per_sec": round(steps / dt, 2),
            "step_ms": round(dt / steps * 1e3, 3),
            "loss": round(float(loss), 4),
            **{
                k: step.comms_stats[k]
                for k in (
                    "reduce_scatter_bytes", "allgather_bytes", "n_buckets"
                )
            },
        }
        if dtype == "float32":
            fp32_col = col
        else:
            col["rs_shrink_vs_fp32"] = round(
                fp32_col["reduce_scatter_bytes"]
                / col["reduce_scatter_bytes"],
                3,
            )
        wire[dtype] = col

    per_chip = fp32_col["opt_state_bytes_per_chip"]
    ratio = per_chip / replicated_bytes
    bound = 1.0 / n + 0.01
    out = {
        "mode": "zero1-hybrid",
        "mesh": {k: int(v) for k, v in mesh.shape.items()},
        "steps": steps,
        "bucket_bytes": 65536,
        # fp32 columns stay at the top level: the anchor leg, and the
        # shape older report tooling reads.
        "comms_dtype": "float32",
        "max_abs_diff_vs_tp_reference": (
            fp32_col["max_abs_diff_vs_tp_reference"]
        ),
        "parity_ok": fp32_col["parity_ok"],
        "tp_sharding_preserved": fp32_col["tp_sharding_preserved"],
        "opt_state_bytes_per_chip": per_chip,
        "replicated_opt_state_bytes": replicated_bytes,
        "opt_state_ratio": round(ratio, 5),
        "opt_state_bound": round(bound, 5),
        "opt_state_ok": ratio <= bound,
        "steps_per_sec": fp32_col["steps_per_sec"],
        "step_ms": fp32_col["step_ms"],
        "loss": fp32_col["loss"],
        "wire": wire,
    }
    out["ok"] = bool(
        out["opt_state_ok"]
        and all(
            c["parity_ok"] and c["tp_sharding_preserved"]
            for c in wire.values()
        )
    )
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--out", default=None, help="artifact path (default stdout)")
    ap.add_argument("--steps", type=int, default=20, help="timed steps/point")
    ap.add_argument(
        "--equiv-steps", type=int, default=8,
        help="trajectory length for the equivalence check",
    )
    ap.add_argument(
        "--reps", type=int, default=10,
        help="standalone collective repetitions (span p50/p99 sample size)",
    )
    ap.add_argument(
        "--smoke", action="store_true",
        help="tier-1 CI config: 2-point sweep, tiny step counts",
    )
    ns = ap.parse_args(argv)
    if ns.smoke:
        ns.steps, ns.equiv_steps, ns.reps = 3, 3, 3

    n = jax.device_count()
    artifact: dict = {
        "artifact": "comms_bench",
        "n_devices": n,
        "platform": jax.devices()[0].platform,
        "smoke": bool(ns.smoke),
    }
    if n < 2:
        artifact.update(ok=False, error=f"need >=2 devices, got {n}")
        _write(artifact, ns.out)
        return 1

    mesh = make_mesh({DATA_AXIS: n})
    artifact["equivalence"] = equivalence_check(
        mesh, ns.equiv_steps,
        dtypes=("float32",) if ns.smoke else zero.COMMS_DTYPES,
    )

    # Bucket x dtype combos; each one gets overlap on AND off legs so
    # the exposed-collective-time delta is a pair of rows, not a claim.
    # Smoke uses the small bucket (several buckets on this workload —
    # the overlap pipeline actually has stages to hide).
    if ns.smoke:
        combos = [(65536, "float32")]
    else:
        combos = [
            (bb, dt)
            for bb in (65536, zero.DEFAULT_BUCKET_BYTES)
            for dt in zero.COMMS_DTYPES
        ]
    sweep = [bench_point(mesh, "replicated", ns.steps)]
    for bb, dt in combos:
        coll = bench_collectives(
            mesh, zero.Zero1Config(bucket_bytes=bb, comms_dtype=dt), ns.reps
        )
        standalone_ms = coll["reduce_scatter_ms"] + coll["allgather_ms"]
        for ov in (True, False):
            cfg = zero.Zero1Config(
                bucket_bytes=bb, comms_dtype=dt, overlap=ov
            )
            point = bench_point(mesh, "zero1", ns.steps, cfg)
            exposed_frac = 1.0 - point["hidden_fraction"]
            point["collective_ms_standalone"] = round(standalone_ms, 3)
            point["exposed_collective_ms_est"] = round(
                standalone_ms * exposed_frac, 3
            )
            sweep.append(point)
    artifact["sweep"] = sweep
    # Hybrid wire sweep: smoke proves the compressed-wire path composes
    # (fp32 + bf16); full adds the int8-with-per-bucket-scale column.
    artifact["hybrid"] = bench_hybrid(
        ns.steps,
        comms_dtypes=(
            ("float32", "bfloat16") if ns.smoke else zero.COMMS_DTYPES
        ),
    )

    # Fold this process's comms.* spans into the same rollup shape the
    # gang report uses (telemetry_report.py "Comms" section).
    events = [ev.to_dict() for ev in telemetry.get_log().snapshot()]
    artifact["comms"] = aggregate.comms_report(events)
    tdir = telemetry.telemetry_dir()
    if tdir:
        telemetry.write_rank_file(tdir)

    artifact["ok"] = bool(
        artifact["equivalence"]["ok"]
        and artifact["hybrid"]["ok"]
        and all("steps_per_sec" in p for p in sweep)
    )
    _write(artifact, ns.out)
    return 0 if artifact["ok"] else 1


def _write(artifact: dict, out: str | None) -> None:
    text = json.dumps(artifact, indent=2) + "\n"
    if out:
        with open(out, "w") as f:
            f.write(text)
        print(
            f"comms_bench: ok={artifact.get('ok')} -> {out}", file=sys.stderr
        )
    else:
        print(text, end="")


if __name__ == "__main__":
    sys.exit(main())
