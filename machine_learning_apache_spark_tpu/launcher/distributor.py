"""Distributor — the TorchDistributor equivalent (reference C12).

The reference launches distributed training with
``TorchDistributor(num_processes=executors_n, local_mode=..., use_gpu=False)
.run(train_func)`` (``distributed_cnn.py:227-231``): Spark gang-schedules one
barrier task per process, sets the torch rendezvous env vars, pickles
``train_func`` with its module globals, and returns rank 0's result.

Design deltas (SURVEY.md §7 design stance):

- **Function by reference, not pickle-by-value**: the train function must be
  importable (``module:qualname`` or a module-level callable). This kills the
  reference's accidental re-execution of module-level downloads on every
  executor (quirk Q13) — each worker imports the module once, deliberately.
- **Rendezvous**: the launcher picks a free coordinator port and writes the
  ``{MLSPARK_COORDINATOR, NUM_PROCESSES, PROCESS_ID}`` env contract (plus the
  torch-style aliases) that ``launcher.coordinator`` maps onto
  ``jax.distributed.initialize`` (SURVEY.md §2.4).
- **Result**: rank 0's return value is actually returned (the reference's
  ``train_func``s return None yet assign the result — quirk Q7).
- **Gang failure semantics**: any worker dying kills the gang and raises —
  the Spark-barrier all-or-nothing behavior (SURVEY.md §5 failure detection).

``local_mode=True`` (the reference's bring-up path,
``distributed_multilayer_perceptron.py:179``) spawns all ranks on this host.
Multi-host mode emits the per-host command lines instead (control-plane
integration with an external scheduler; see ``commands_for_hosts``).
"""

from __future__ import annotations

import atexit
import glob
import math
import os
import pickle
import random
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Sequence

from machine_learning_apache_spark_tpu import telemetry
from machine_learning_apache_spark_tpu.launcher.monitor import (
    GangFailure,
    GangMonitor,
    terminate_gang,
)
from machine_learning_apache_spark_tpu.utils import env as envcfg
from machine_learning_apache_spark_tpu.utils.logging import get_logger

log = get_logger(__name__)

# Process groups of gangs this interpreter spawned and has not yet reaped.
# Safety net against orphaned workers: the normal path unregisters after
# reaping, and the atexit sweep (plus tests/conftest.py's session-finish
# sweep) SIGKILLs whatever a crashed/interrupted driver left behind —
# otherwise a timed-out pytest run leaves rogue ranks burning CPU past the
# CI timeout.
_LIVE_PGIDS: set[int] = set()
_PGIDS_LOCK = threading.Lock()


def _register_gang(procs: Sequence[subprocess.Popen]) -> None:
    with _PGIDS_LOCK:
        _LIVE_PGIDS.update(p.pid for p in procs)


def _unregister_gang(procs: Sequence[subprocess.Popen]) -> None:
    with _PGIDS_LOCK:
        _LIVE_PGIDS.difference_update(p.pid for p in procs)


def kill_stray_gangs() -> int:
    """SIGKILL every registered-but-unreaped gang process group. Returns
    the number of groups signalled (0 in any healthy run)."""
    with _PGIDS_LOCK:
        pgids, stray = list(_LIVE_PGIDS), len(_LIVE_PGIDS)
        _LIVE_PGIDS.clear()
    for pgid in pgids:
        try:
            os.killpg(pgid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError, OSError):
            stray -= 1
    if stray:
        log.warning("killed %d stray gang process group(s)", stray)
    return stray


atexit.register(kill_stray_gangs)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def local_tpu_chips() -> int:
    """TPU chips attached to this host, counted from their device nodes
    (``/dev/accel<n>``, or ``/dev/vfio/<n>`` under VFIO). The spawning
    parent must learn this without a JAX backend: a process that opens the
    chips holds them, and its children then cannot."""
    return len(glob.glob("/dev/accel[0-9]*")) or len(
        glob.glob("/dev/vfio/[0-9]*")
    )


def tpu_pinning_env(
    rank: int,
    platforms: str,
    *,
    gang_ports: Sequence[int] | None = None,
) -> dict[str, str]:
    """Environment that gives locally-spawned child ``rank`` exactly TPU
    chip ``rank`` — libtpu reads it before jax imports, and without it N
    children each try to open every chip of the host. Empty when the
    child will not run on a TPU: ``platforms`` (the child's
    ``JAX_PLATFORMS``; ``""`` lets JAX pick) names no ``tpu``, or the host
    has no chips.

    ``gang_ports=None`` pins an independent one-chip process (a serving
    replica: visibility only). With ``gang_ports`` (one free port per rank)
    the children also form a single-host multi-process TPU runtime, so
    cross-process collectives run over the chips' interconnect; libtpu lays
    the processes out on the host's chip grid, so that takes exactly one
    process per chip. Both forms were checked on a four-chip v5e host
    (PERF.md "Bring-up"). Raises ``ValueError`` with the reason where a
    pinning cannot be formed, rather than letting a child hang in libtpu.
    """
    if platforms and "tpu" not in platforms.split(","):
        return {}
    chips = local_tpu_chips()
    if chips == 0:
        return {}
    if rank >= chips:
        raise ValueError(
            f"local rank {rank} needs TPU chip {rank}, but this host has "
            f"{chips} chip(s); a chip belongs to one process"
        )
    env = {
        "TPU_VISIBLE_CHIPS": str(rank),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }
    if gang_ports is None:
        return env
    bounds = os.environ.get("TPU_CHIPS_PER_HOST_BOUNDS", "")
    grid = [int(b) for b in bounds.split(",") if b.strip().isdigit()]
    if len(gang_ports) != chips or math.prod(grid or [0]) != chips:
        raise ValueError(
            f"a single-host TPU gang takes one process per chip: "
            f"{len(gang_ports)} processes requested on a host with {chips} "
            f"chip(s) (TPU_CHIPS_PER_HOST_BOUNDS={bounds!r}). Use "
            f"num_processes={chips}, or one process driving all chips"
        )
    env.update(
        TPU_PROCESS_BOUNDS=bounds,
        TPU_PROCESS_ADDRESSES=",".join(
            f"localhost:{port}" for port in gang_ports
        ),
        TPU_PROCESS_PORT=str(gang_ports[rank]),
        CLOUD_TPU_TASK_ID=str(rank),
    )
    return env


def fn_reference(fn: Callable | str) -> str:
    """``module:qualname`` reference for an importable function."""
    if isinstance(fn, str):
        if ":" not in fn:
            raise ValueError(f"function reference must be 'module:qualname', got {fn!r}")
        return fn
    module = getattr(fn, "__module__", None)
    qualname = getattr(fn, "__qualname__", None)
    if not module or not qualname or "<" in qualname:
        raise ValueError(
            f"{fn!r} is not an importable module-level function; the launcher "
            "runs functions by reference (no closure pickling — SURVEY.md Q13)"
        )
    return f"{module}:{qualname}"


def resolve_fn(ref: str) -> Callable:
    """Import a ``module:qualname`` reference (shared by Distributor and the
    per-worker runner)."""
    import importlib

    module, _, qual = fn_reference(ref).partition(":")
    obj: Any = importlib.import_module(module)
    for part in qual.split("."):
        obj = getattr(obj, part)
    return obj


@dataclass
class WorkerResult:
    rank: int
    value: Any = None
    error: str | None = None


class Distributor:
    """``Distributor(num_processes=N, local_mode=True).run(train_fn, *args)``.

    ``use_gpu`` is accepted for API parity with TorchDistributor and ignored
    (the accelerator is whatever the JAX platform provides; the reference
    always passed ``use_gpu=False`` anyway, ``distributed_cnn.py:230``).
    """

    def __init__(
        self,
        num_processes: int | None = None,
        *,
        local_mode: bool = True,
        use_gpu: bool = False,  # noqa: ARG002 - API parity
        platform: str | None = None,
        env: dict[str, str] | None = None,
        dp_mode: str | None = None,
        dp_overlap: bool | None = None,
        serve_kv_dtype: str | None = None,
        telemetry_http: int | None = None,
        ingest: dict | None = None,
        timeout: float = 600.0,
        max_restarts: int = 0,
        heartbeat_interval: float = 1.0,
        heartbeat_timeout: float | None = 300.0,
        term_grace: float = 5.0,
        backoff_base: float = 0.5,
        backoff_max: float = 30.0,
        elastic: bool = False,
        elastic_min_world: int = 1,
        rank_restart_budget: int | None = None,
    ) -> None:
        self.num_processes = num_processes or 1
        self.local_mode = local_mode
        self.platform = platform
        self.extra_env = env or {}
        # Data-parallel update mode for the workers' fit() (parallel.zero
        # env contract): "zero1" opts the whole gang into the fused
        # sharded-update step via MLSPARK_DP_MODE. Kept as a first-class
        # knob (not just env=) so driver scripts read as intent, and
        # validated here — a typo must fail at Distributor construction,
        # not inside every worker after rendezvous.
        if dp_mode is not None and dp_mode not in ("replicated", "zero1"):
            raise ValueError(
                f"unknown dp_mode {dp_mode!r} (expected 'replicated' or "
                "'zero1')"
            )
        self.dp_mode = dp_mode
        # The zero1 overlap schedule rides the same contract: the boolean
        # knob becomes MLSPARK_ZERO1_OVERLAP in every worker
        # (Zero1Config.from_env resolves it; workers default to overlap
        # on when neither knob nor env is set).
        if dp_overlap is not None and not isinstance(dp_overlap, bool):
            raise ValueError(
                f"dp_overlap must be a bool or None, got {dp_overlap!r}"
            )
        self.dp_overlap = dp_overlap
        # Serving KV-store dtype, same env contract shape: the knob
        # becomes MLSPARK_SERVE_KV_DTYPE in every worker, which
        # ServingEngine resolves when kv_dtype isn't passed explicitly
        # ("float32" is the engine default; "int8" quantizes the KV pages
        # with per-page scales). Validated here so a typo fails in the
        # driver, not inside every rank after rendezvous.
        if serve_kv_dtype is not None and serve_kv_dtype not in (
            "float32", "int8"
        ):
            raise ValueError(
                f"unknown serve_kv_dtype {serve_kv_dtype!r} (expected "
                "'float32' or 'int8')"
            )
        self.serve_kv_dtype = serve_kv_dtype
        # Live observability plane, same env-contract shape: the knob
        # becomes MLSPARK_TELEMETRY_HTTP in every worker, which runner.main
        # resolves into a per-rank HTTP server. 0 means "ephemeral port per
        # rank" (the only sane choice for a local gang — fixed ports would
        # collide); each rank publishes its bound port in an
        # http_rank<k>.json sidecar for tools/gang_status.py to find.
        if telemetry_http is not None and not (
            0 <= int(telemetry_http) <= 65535
        ):
            raise ValueError(
                f"telemetry_http must be a port in [0, 65535] or None, "
                f"got {telemetry_http!r}"
            )
        self.telemetry_http = telemetry_http
        # Input-pipeline plumbing, same shape as dp_mode: the
        # Distributor(ingest={"buffer": 4, "tail": "pad", ...}) knob
        # becomes MLSPARK_INGEST_* in every worker's environment (the
        # contract ingest.IngestConfig.from_env resolves), validated at
        # construction so a typo'd knob fails in the driver, not inside
        # every rank after rendezvous.
        if ingest:
            from machine_learning_apache_spark_tpu.ingest.config import (
                validate_ingest_knobs,
            )

            self.ingest_env = validate_ingest_knobs(ingest)
        else:
            self.ingest_env = {}
        self.timeout = timeout
        # Spark-barrier recovery semantics (SURVEY.md §5 failure detection):
        # a failed stage is retried whole — all-or-nothing gang restarts.
        self.max_restarts = max_restarts
        # Liveness detection (docs/FAULT_TOLERANCE.md): each worker touches
        # a per-rank heartbeat file every `heartbeat_interval`; a rank silent
        # past `heartbeat_timeout` is declared stalled and the gang torn
        # down (None disables — exit codes and the deadline still apply).
        self.heartbeat_interval = heartbeat_interval
        self.heartbeat_timeout = heartbeat_timeout
        # Teardown escalation: SIGTERM, wait `term_grace`, then SIGKILL.
        self.term_grace = term_grace
        # Restart pacing: exponential backoff with jitter, so co-failing
        # gangs on one host don't re-stampede the same resource in lockstep.
        self.backoff_base = backoff_base
        self.backoff_max = backoff_max
        # Elastic shrink policy (docs/FAULT_TOLERANCE.md "Elastic
        # resume"): when one rank keeps failing past its per-rank restart
        # budget (`rank_restart_budget`, defaulting to `max_restarts`),
        # it is judged PERMANENTLY LOST — a preempted chip, a bad host.
        # With elastic=True the gang retries at world-1 (never below
        # `elastic_min_world`) instead of raising, and the workers see
        # MLSPARK_ELASTIC=1 so fit(resume=True) reshards the old world's
        # checkpoints onto the shrunken mesh (train/reshard.py). With
        # elastic=False (but a budget set) the exhaustion raises a
        # GangFailure with permanent=True naming the rank, cause, and
        # attempt count. Deadline expiries never count against a rank —
        # they blame the whole gang, not a member.
        self.elastic = bool(elastic)
        if int(elastic_min_world) < 1:
            raise ValueError(
                f"elastic_min_world must be >= 1, got {elastic_min_world}"
            )
        if int(elastic_min_world) > self.num_processes:
            raise ValueError(
                f"elastic_min_world={elastic_min_world} exceeds "
                f"num_processes={self.num_processes}"
            )
        self.elastic_min_world = int(elastic_min_world)
        if rank_restart_budget is not None and int(rank_restart_budget) < 0:
            raise ValueError(
                f"rank_restart_budget must be >= 0 or None, got "
                f"{rank_restart_budget}"
            )
        self.rank_restart_budget = (
            None if rank_restart_budget is None else int(rank_restart_budget)
        )

    # -- multi-host control plane --------------------------------------------
    def commands_for_hosts(
        self, fn: Callable | str, hosts: Sequence[str], coordinator_port: int = 29500
    ) -> list[str]:
        """One launch command per host for an external scheduler (the analogue
        of spark-submit's role): host 0 is the coordinator."""
        ref = fn_reference(fn)
        coord = f"{hosts[0]}:{coordinator_port}"
        return [
            sys.executable
            + " -m machine_learning_apache_spark_tpu.launcher.runner"
            + f" --fn {ref} --coordinator {coord}"
            + f" --num-processes {len(hosts)} --process-id {rank}"
            for rank, _ in enumerate(hosts)
        ]

    # -- local gang spawn ----------------------------------------------------
    def run(self, fn: Callable | str, *args: Any, **kwargs: Any) -> Any:
        """Spawn the gang, wait, return rank 0's result
        (``distributor.run(train_func)`` contract, ``distributed_cnn.py:231``)."""
        if not self.local_mode:
            raise RuntimeError(
                "cluster mode is driven by an external scheduler: use "
                "commands_for_hosts() to obtain per-host launch commands"
            )
        n = self.num_processes
        if n == 1 and not (self.platform or self.extra_env):
            # Single process: run inline, as the reference's sequential
            # scripts do (no rendezvous needed). With platform/env overrides
            # we must still spawn (they only apply to a fresh interpreter —
            # this one's JAX backend may already be initialized).
            fn = self._resolve(fn)
            return fn(*args, **kwargs)

        ref = fn_reference(fn)
        coord = f"127.0.0.1:{_free_port()}"
        workdir = tempfile.mkdtemp(prefix="mlspark_gang_")
        args_path = os.path.join(workdir, "args.pkl")
        with open(args_path, "wb") as f:
            pickle.dump((args, kwargs), f)

        try:
            attempt = 0
            # Per-rank failure counts since the last shrink — the elastic
            # policy's permanent-loss ledger (deadline expiries excluded:
            # they blame the gang, not a member).
            rank_failures: dict[int, int] = {}
            while True:
                # Clear any stale result/heartbeat files from a failed
                # attempt so a restart can't return a dead rank's leftovers
                # (or judge liveness off a corpse's last beat). Sweep the
                # ORIGINAL world's files — after a shrink, a departed
                # rank's leftovers must not linger either.
                for rank in range(self.num_processes):
                    for name in (f"result_{rank}.pkl", f"heartbeat_{rank}"):
                        stale = os.path.join(workdir, name)
                        if os.path.exists(stale):
                            os.unlink(stale)
                try:
                    with telemetry.span(
                        "launcher.gang_attempt",
                        attempt=attempt, num_processes=n,
                    ):
                        value = self._run_gang(
                            ref, coord, workdir, args_path, n, attempt
                        )
                    self._write_telemetry_report(workdir)
                    return value
                except GangFailure as failure:
                    attempt += 1
                    budget = (
                        self.max_restarts
                        if self.rank_restart_budget is None
                        else self.rank_restart_budget
                    )
                    lost: int | None = None
                    if failure.rank is not None and failure.cause != "deadline":
                        rank_failures[failure.rank] = (
                            rank_failures.get(failure.rank, 0) + 1
                        )
                        if rank_failures[failure.rank] > budget:
                            lost = failure.rank
                    if lost is not None and (
                        self.elastic or self.rank_restart_budget is not None
                    ):
                        fails = rank_failures[lost]
                        if not self.elastic:
                            telemetry.annotate(
                                "launcher.gang_exhausted",
                                attempt=attempt, rank=lost,
                                cause=failure.cause,
                            )
                            raise GangFailure(
                                f"rank {lost} permanently lost "
                                f"(cause={failure.cause}) after {fails} "
                                f"failed attempt(s) — per-rank restart "
                                f"budget {budget} exhausted and elastic "
                                "resume is disabled",
                                rank=lost, cause=failure.cause,
                                attempt=attempt,
                                exit_code=failure.exit_code,
                                permanent=True,
                            ) from failure
                        if n - 1 < self.elastic_min_world:
                            telemetry.annotate(
                                "launcher.gang_exhausted",
                                attempt=attempt, rank=lost,
                                cause=failure.cause,
                            )
                            raise GangFailure(
                                f"rank {lost} permanently lost "
                                f"(cause={failure.cause}) after {fails} "
                                f"failed attempt(s) and the gang cannot "
                                f"shrink below elastic_min_world="
                                f"{self.elastic_min_world} (world is {n})",
                                rank=lost, cause=failure.cause,
                                attempt=attempt,
                                exit_code=failure.exit_code,
                                permanent=True,
                            ) from failure
                        telemetry.annotate(
                            "launcher.gang_shrink",
                            old_world=n, new_world=n - 1, rank=lost,
                            cause=failure.cause, failures=fails,
                        )
                        log.warning(
                            "rank %d permanently lost (cause=%s, %d "
                            "failure(s) > budget %d); shrinking gang "
                            "%d -> %d and resuming elastically from the "
                            "group checkpoints",
                            lost, failure.cause, fails, budget, n, n - 1,
                        )
                        n -= 1
                        attempt = 0
                        rank_failures.clear()
                        time.sleep(min(self.backoff_max, self.backoff_base))
                        coord = f"127.0.0.1:{_free_port()}"
                        continue
                    telemetry.annotate(
                        "launcher.gang_retry" if attempt <= self.max_restarts
                        else "launcher.gang_exhausted",
                        attempt=attempt, rank=failure.rank,
                        cause=failure.cause,
                    )
                    if attempt > self.max_restarts:
                        raise
                    delay = min(
                        self.backoff_max,
                        self.backoff_base * (2 ** (attempt - 1)),
                    ) * (0.5 + random.random() / 2)  # full-jitter-lite
                    log.warning(
                        "gang attempt %d/%d failed (rank=%s cause=%s); "
                        "restarting whole gang in %.2fs (Spark-barrier "
                        "all-or-nothing semantics)",
                        attempt, self.max_restarts, failure.rank,
                        failure.cause, delay,
                    )
                    time.sleep(delay)
                    coord = f"127.0.0.1:{_free_port()}"  # stale port may linger
        finally:
            import shutil

            shutil.rmtree(workdir, ignore_errors=True)

    def _telemetry_out_dir(self, workdir: str) -> str:
        """Where this gang's telemetry files land — the same precedence the
        worker env gets in ``_run_gang`` (explicit env= > inherited env >
        the ephemeral workdir)."""
        return (
            self.extra_env.get("MLSPARK_TELEMETRY_DIR")
            or envcfg.get_str("MLSPARK_TELEMETRY_DIR")
            or workdir
        )

    def _write_telemetry_report(self, workdir: str) -> None:
        """Rank-0-side gang merge: after a successful run, fold the per-rank
        ``telemetry_rank<k>.jsonl`` exports into ``telemetry_report.json``
        (+ ``.md``) in the telemetry dir. Best-effort — reporting must never
        fail a run that trained fine."""
        if not telemetry.enabled():
            return
        try:
            tdir = self._telemetry_out_dir(workdir)
            from machine_learning_apache_spark_tpu.telemetry import aggregate

            if not aggregate.find_rank_files(tdir):
                return
            report = aggregate.merge_gang_dir(tdir)
            import json

            with open(os.path.join(tdir, "telemetry_report.json"), "w") as f:
                json.dump(report, f, indent=2)
                f.write("\n")
            with open(os.path.join(tdir, "telemetry_report.md"), "w") as f:
                f.write(aggregate.render_markdown(report))
            log.info(
                "telemetry report merged from %d rank(s) into %s",
                len(report["ranks"]), tdir,
            )
        except Exception:
            log.exception("telemetry report generation failed (ignored)")

    def _run_gang(
        self,
        ref: str,
        coord: str,
        workdir: str,
        args_path: str,
        n: int,
        attempt: int = 0,
    ) -> Any:
        procs: list[subprocess.Popen] = []
        result_paths, heartbeat_paths = [], []
        # This parent never touches a JAX backend (no jax.devices() /
        # process_count() anywhere in launcher/): on a TPU host the chips
        # must still be free for the children it pins them to.
        tpu_ports = [_free_port() for _ in range(n)]
        for rank in range(n):
            result_path = os.path.join(workdir, f"result_{rank}.pkl")
            heartbeat_path = os.path.join(workdir, f"heartbeat_{rank}")
            result_paths.append(result_path)
            heartbeat_paths.append(heartbeat_path)
            env = dict(os.environ)
            # A driver running under the test harness carries
            # --xla_force_host_platform_device_count in XLA_FLAGS (virtual
            # multi-device CPU). Workers must NOT inherit it: the gang
            # contract is one device per rank (world == num_processes), and
            # an inherited 8x multiplier breaks every worker-side mesh.
            # Explicit Distributor(env=...) still wins (applied below).
            flags = env.get("XLA_FLAGS", "")
            if "xla_force_host_platform_device_count" in flags:
                kept = " ".join(
                    f for f in flags.split()
                    if "xla_force_host_platform_device_count" not in f
                )
                if kept:
                    env["XLA_FLAGS"] = kept
                else:
                    env.pop("XLA_FLAGS", None)
            # DP-mode plumbing: the constructor knob becomes the workers'
            # MLSPARK_DP_MODE (fit() resolves it when dp_mode isn't passed
            # explicitly); an inherited MLSPARK_DP_MODE flows through
            # dict(os.environ) above, and explicit env= still wins below.
            # Writes go through the registry (envcfg.put_into): a typo'd
            # contract name fails here at the driver, not as a silently
            # ignored variable in every rank.
            if self.dp_mode is not None:
                envcfg.put_into(env, "MLSPARK_DP_MODE", self.dp_mode)
            if self.dp_overlap is not None:
                envcfg.put_into(
                    env, "MLSPARK_ZERO1_OVERLAP",
                    "1" if self.dp_overlap else "0",
                )
            # Serving KV dtype rides the same contract (constructor >
            # inherited env; explicit env= still wins below).
            if self.serve_kv_dtype is not None:
                envcfg.put_into(env, "MLSPARK_SERVE_KV_DTYPE", self.serve_kv_dtype)
            # Observability-plane port knob, same contract shape.
            if self.telemetry_http is not None:
                envcfg.put_into(env, "MLSPARK_TELEMETRY_HTTP", self.telemetry_http)
            # Elastic opt-in rides the same contract: the workers' fit()
            # resolves MLSPARK_ELASTIC when elastic= isn't passed, so a
            # shrunken gang reshards old-topology checkpoints instead of
            # refusing them (train/reshard.py).
            if self.elastic:
                envcfg.put_into(env, "MLSPARK_ELASTIC", "1")
            # Ingest knobs ride the same contract: constructor > inherited
            # env (explicit env= still wins below).
            env.update(self.ingest_env)
            env.update(self.extra_env)
            # Workers default their telemetry output (rank JSONLs, flight
            # dumps) next to the heartbeat files; an inherited or explicit
            # MLSPARK_TELEMETRY_DIR (e.g. a persistent dir from the fault
            # drill) wins — the workdir is ephemeral (rmtree'd below).
            env.setdefault("MLSPARK_TELEMETRY_DIR", workdir)
            envcfg.put_into(env, "MLSPARK_COORDINATOR", coord)
            envcfg.put_into(env, "MLSPARK_NUM_PROCESSES", n)
            envcfg.put_into(env, "MLSPARK_PROCESS_ID", rank)
            envcfg.put_into(env, "MLSPARK_GANG_ATTEMPT", attempt)
            envcfg.put_into(env, "MLSPARK_HEARTBEAT_FILE", heartbeat_path)
            envcfg.put_into(
                env, "MLSPARK_HEARTBEAT_INTERVAL", self.heartbeat_interval
            )
            host, _, port = coord.partition(":")
            env["MASTER_ADDR"], env["MASTER_PORT"] = host, port
            env["WORLD_SIZE"], env["RANK"] = str(n), str(rank)
            if self.platform:
                # Both spellings (package __init__): the env var settles it
                # at jax import, MLSPARK_PLATFORM through the config API.
                env["JAX_PLATFORMS"] = self.platform
                envcfg.put_into(env, "MLSPARK_PLATFORM", self.platform)
            env.update(
                tpu_pinning_env(
                    rank, env.get("JAX_PLATFORMS", ""), gang_ports=tpu_ports
                )
            )
            env["PYTHONPATH"] = os.pathsep.join(
                p for p in sys.path if p
            )
            cmd = [
                sys.executable,
                "-m",
                "machine_learning_apache_spark_tpu.launcher.runner",
                "--fn", ref,
                "--args-file", args_path,
                "--result-file", result_path,
            ]
            # start_new_session: each worker leads its own process group, so
            # teardown signals reach the worker AND anything it spawned.
            procs.append(
                subprocess.Popen(cmd, env=env, start_new_session=True)
            )
        _register_gang(procs)
        log.info(
            "spawned %d-process gang (coordinator %s, attempt %d)",
            n, coord, attempt,
        )

        try:
            failure = self._wait_gang(procs, heartbeat_paths)
        finally:
            # Belt and suspenders for non-GangFailure exits (KeyboardInterrupt
            # etc.): nothing outlives the attempt.
            terminate_gang(procs, grace=0.0)
            _unregister_gang(procs)

        results = [self._read_result(path, rank) for rank, path in enumerate(result_paths)]
        errors = [r for r in results if r.error]
        if failure is None and not errors:
            return results[0].value

        # Ranks killed by the gang teardown leave placeholder errors;
        # surface the rank that actually crashed (its real traceback). A
        # rank with only a placeholder is an EFFECT of teardown, never the
        # blamed cause — a deadline expiry, where every rank is healthy but
        # slow, must keep rank=None.
        real = next(
            (r for r in errors if "produced no result" not in r.error), None
        )
        primary = real or (errors[0] if errors else None)
        detail = (
            f"\n[rank {primary.rank}] {primary.error}" if primary else ""
        )
        cause = failure.cause if failure is not None else "exit"
        raise GangFailure(
            "gang failed on rank(s) "
            + (", ".join(str(r.rank) for r in errors) or "?")
            + f" (cause={cause}, attempt={attempt})"
            + (f": {failure}" if failure is not None else "")
            + detail,
            rank=(
                failure.rank if failure is not None and failure.rank is not None
                else (real.rank if real else None)
            ),
            cause=cause,
            attempt=attempt,
            exit_code=failure.exit_code if failure is not None else None,
        )

    def _wait_gang(
        self,
        procs: list[subprocess.Popen],
        heartbeat_paths: list[str] | None = None,
    ) -> GangFailure | None:
        """All-or-nothing barrier semantics, delegated to a ``GangMonitor``
        thread: the first nonzero exit, stalled heartbeat, or deadline
        expiry tears the gang down (SIGTERM -> SIGKILL). Returns the
        detected failure, or None if every rank exited 0."""
        watcher = GangMonitor(
            procs,
            heartbeat_paths,
            timeout=self.timeout,
            heartbeat_timeout=self.heartbeat_timeout,
            grace=self.term_grace,
        )
        watcher.start()
        while watcher.is_alive():
            # join with a timeout so the driver stays interruptible
            # (Ctrl-C in a notebook must not wedge behind a daemon join).
            watcher.join(timeout=1.0)
        return watcher.failure

    @staticmethod
    def _resolve(fn: Callable | str) -> Callable:
        return fn if callable(fn) else resolve_fn(fn)

    @staticmethod
    def _read_result(path: str, rank: int) -> WorkerResult:
        if not os.path.exists(path):
            return WorkerResult(rank=rank, error=f"rank {rank} produced no result (crashed?)")
        try:
            with open(path, "rb") as f:
                return pickle.load(f)
        except Exception as e:
            # Truncated/corrupt file (e.g. the worker died mid-dump, or its
            # return value wasn't picklable): treat as a worker failure so the
            # gang error carries the rank, not a bare unpickling traceback.
            return WorkerResult(
                rank=rank, error=f"rank {rank} produced no result (unreadable result file: {e!r})"
            )


# API-parity alias: reference user code says TorchDistributor
# (distributed_cnn.py:227).
TorchDistributor = Distributor
