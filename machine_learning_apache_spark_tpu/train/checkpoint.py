"""Checkpoint / resume — param + optimizer-state persistence.

The reference has **no** checkpointing (SURVEY.md §5: no ``torch.save`` /
``state_dict`` anywhere; models are trained and discarded, and
``distributor.run`` returns None — quirk Q7). Its only "persistence" is
train-then-evaluate in-process. The framework provides the real thing:
step-numbered checkpoints via orbax (sharding-aware — params keep their
``NamedSharding`` layout on restore, so a TP/DP-sharded run resumes without
a resharding pass), latest-step resume, and bounded retention.

Only the pytree half of ``TrainState`` (step / params / opt_state) is
persisted; ``apply_fn``/``tx`` are code, recreated by the caller — which is
why ``restore`` takes a template state built by ``TrainState.create``.
"""

from __future__ import annotations

import asyncio
import json
import os
import re
import threading
from typing import Any

import jax
import numpy as np
import orbax.checkpoint as ocp

from machine_learning_apache_spark_tpu.train.state import TrainState
from machine_learning_apache_spark_tpu.utils.logging import get_logger

log = get_logger(__name__)

LATEST_POINTER = "latest"  # <dir>/latest — JSON {"step": N}

# Gang group convention: rank k of a gang checkpoints to a sibling
# directory `<root>/ckpt_r<k>`. Managers whose directory matches can
# locate their peers — the basis for group-agreed fallback and for
# cross-topology resharding (train/reshard.py).
GROUP_DIR_RE = re.compile(r"^ckpt_r(\d+)$")


def _per_rank_multiprocessing_options():
    """Inside a jax.distributed gang, each rank checkpoints to its OWN
    directory, so its manager must form a single-process orbax group:
    ``active_processes={rank}`` routes every barrier through the
    coordination-service client (works on any backend) instead of
    ``sync_global_devices`` — an XLA collective the CPU backend cannot
    execute — and ``primary_host=rank`` makes each rank responsible for
    creating/renaming under its own directory. Orbax defaults outside a
    gang."""
    if jax.process_count() <= 1:
        return ocp.options.MultiprocessingOptions()
    rank = jax.process_index()
    return ocp.options.MultiprocessingOptions(
        primary_host=rank,
        active_processes={rank},
        barrier_sync_key_prefix=f"rank{rank}",
    )


class _AnyProcessNumpyHandler(ocp.type_handlers.NumpyHandler):
    """NumpyHandler that writes from whichever process owns the directory.

    ``NumpyHandler._background_serialize`` issues its tensorstore writes
    only when ``process_index() == 0``, and takes no ``primary_host``
    (``ArrayHandler(primary_host=None, replica_id=None)``, orbax's own
    per-host writer, refuses the host-local arrays a rank checkpoints).
    In a per-rank group (``active_processes={rank}``) this process is the
    sole writer of its directory, so a non-zero rank would finalize a step
    that holds metadata and no data. The write spec comes from the public
    ``type_handlers.get_json_tspec_write`` + ``get_cast_tspec_serialize``;
    with OCDBT the rank writes under its own ``ocdbt.process_<rank>``
    subdirectory, which the manager's finalize merges."""

    async def _background_serialize(self, values, infos, args=None):
        def write_spec(value, info, arg):
            spec = ocp.type_handlers.get_json_tspec_write(
                info,
                use_ocdbt=info.is_ocdbt_checkpoint,
                global_shape=value.shape,
                local_shape=value.shape,
                dtype=value.dtype,
                process_index=(
                    jax.process_index() if info.is_ocdbt_checkpoint else None
                ),
                metadata_key=self._metadata_key,
                arg=arg,
            )
            return ocp.type_handlers.get_cast_tspec_serialize(spec, value, arg)

        await asyncio.gather(*(
            self._open_and_write(
                value, write_spec(value, info, arg), info.ts_context
            )
            for value, info, arg in zip(values, infos, args)
        ))


class _AnyProcessScalarHandler(
    _AnyProcessNumpyHandler, ocp.type_handlers.ScalarHandler
):
    """ScalarHandler routed through the gate-free numpy write path (MRO:
    ScalarHandler's scalar<->ndarray conversion, then the override's
    ``_background_serialize``)."""


_gang_handlers_installed = False


def _install_gang_type_handlers() -> None:
    """Swap the process-0-gated numpy/scalar handlers out of orbax's global
    type registry for this gang process. Safe globally: inside a gang every
    manager this process creates is a single-process group writing to its
    own directory, so unconditional writes are exactly right."""
    global _gang_handlers_installed
    if _gang_handlers_installed or jax.process_count() <= 1:
        return
    _gang_handlers_installed = True
    ocp.type_handlers.register_type_handler(
        np.ndarray, _AnyProcessNumpyHandler(), override=True
    )
    scalar = _AnyProcessScalarHandler()
    for ty in (int, float, bytes, np.number):
        ocp.type_handlers.register_type_handler(ty, scalar, override=True)


def _per_rank_item_handler():
    """Item handler for per-rank gang managers, or None (orbax defaults)
    outside a gang. Manager-level ``MultiprocessingOptions`` never reach
    the pytree handler, whose own ``primary_host`` defaults to 0 — so a
    non-zero rank would skip writing the ``_METADATA`` structure file and
    its checkpoints would restore as "no structure could be identified".
    Handler-level options fix the structure file; the registry swap above
    fixes the tensor data itself."""
    if jax.process_count() <= 1:
        return None
    _install_gang_type_handlers()
    return ocp.StandardCheckpointHandler(
        multiprocessing_options=_per_rank_multiprocessing_options()
    )


def _detach_local(x):
    """numpy view of a rank-local array. Orbax refuses jax.Arrays that are
    fully addressable while ``process_count > 1`` ("host local" — it can't
    tell them from a half-visible global array), but a per-rank checkpoint
    is EXACTLY a host-local state dump, so detaching to numpy is the
    correct serialization, not a workaround.

    Arrays that span the whole gang (a cross-process mesh) cannot go to
    orbax's sharded writer either — each rank's manager is a
    single-process group (``active_processes={rank}``). Their host-local
    serialization is the addressable fragment: one replica for a
    fully-replicated array, the concatenation of this rank's shards
    (device-order, which for the 1-D ZeRO-1 vectors is a contiguous run)
    for a 1-D sharded array. ``attach_local`` is the inverse."""
    if not isinstance(x, jax.Array):
        return x
    if x.is_fully_addressable:
        return np.asarray(jax.device_get(x))
    shards = sorted(
        x.addressable_shards, key=lambda s: s.index[0].start or 0
    ) if x.ndim else list(x.addressable_shards)
    if x.is_fully_replicated:
        return np.asarray(shards[0].data)
    if x.ndim == 1:
        return np.concatenate([np.asarray(s.data) for s in shards])
    raise ValueError(
        "per-rank checkpointing of a multi-dimensional cross-process "
        f"sharded array (shape {x.shape}) is not supported — ZeRO-1 "
        "keeps params replicated and moments as flat 1-D vectors"
    )


def attach_local(value, orig):
    """Inverse of ``_detach_local``: put a host numpy leaf back onto
    ``orig``'s devices/sharding. ``value`` may hold either the full
    global content (cross-topology reshard hands every rank the whole
    vector) or just this rank's local run — disambiguated by length."""
    if not isinstance(orig, jax.Array):
        return value
    value = np.asarray(value)
    if orig.is_fully_addressable:
        return jax.device_put(value, orig.sharding)
    if orig.is_fully_replicated:
        return jax.make_array_from_callback(
            orig.shape, orig.sharding, lambda idx: value[idx]
        )
    if orig.ndim != 1:
        raise ValueError(
            "cannot reattach a multi-dimensional cross-process sharded "
            f"array (shape {orig.shape})"
        )
    n = int(orig.shape[0])
    starts = [s.index[0].start or 0 for s in orig.addressable_shards]
    offset = 0 if value.shape[0] == n else min(starts)

    def _cb(idx):
        sl = idx[0]
        return value[(sl.start or 0) - offset:(n if sl.stop is None else sl.stop) - offset]

    return jax.make_array_from_callback(orig.shape, orig.sharding, _cb)


def detached_payload(state) -> dict:
    """The host-numpy checkpoint payload tree for ``state`` — what this
    rank's orbax manager reads/writes, and the shaped target
    ``read_raw_payload`` needs when reading ANOTHER topology's payload
    (reshaped per-rank by the caller)."""
    payload = {
        "step": jax.device_get(state.step),
        "params": state.params,
        "opt_state": state.opt_state,
    }
    return jax.tree.map(_detach_local, payload)


def topology_stamp(state) -> dict:
    """The topology under which ``state`` checkpoints: gang world size,
    mesh axis sizes, data-parallel mode, and (ZeRO-1) the flat bucket
    layout. Stamped into every ``meta_<step>.json`` sidecar; a resume
    whose own stamp differs must either reshard (``train/reshard.py``)
    or fail loudly — never silently misload per-rank shards."""
    stamp: dict = {
        "world_size": int(jax.process_count()),
        "dp_mode": "replicated",
        "mesh": None,
        "layout": None,
    }
    plan = getattr(state, "plan", None)
    if plan is not None:
        from machine_learning_apache_spark_tpu.parallel import zero as _zero

        stamp["dp_mode"] = "zero1"
        stamp["layout"] = _zero.plan_layout(plan)
    for leaf in jax.tree.leaves((state.params, state.opt_state)):
        mesh = getattr(getattr(leaf, "sharding", None), "mesh", None)
        shape = getattr(mesh, "shape", None)
        if shape:
            stamp["mesh"] = {str(k): int(v) for k, v in dict(shape).items()}
            break
    return stamp


def same_topology(a: dict | None, b: dict | None) -> bool:
    """Whether two topology stamps describe the same checkpoint layout
    (JSON-normalized, so a stamp read back from a sidecar compares equal
    to a live one)."""

    def _norm(stamp: dict | None) -> str:
        stamp = stamp or {}
        return json.dumps(
            {
                "world_size": int(stamp.get("world_size", 1)),
                "dp_mode": stamp.get("dp_mode", "replicated"),
                "mesh": stamp.get("mesh"),
                "layout": stamp.get("layout"),
            },
            sort_keys=True,
        )

    return _norm(a) == _norm(b)


def pointed_step_of(directory: str) -> int | None:
    """``latest`` pointer target of an arbitrary checkpoint directory
    (None when absent/torn) — group peers are read without opening a
    manager on them."""
    try:
        with open(os.path.join(directory, LATEST_POINTER)) as f:
            return int(json.load(f)["step"])
    except (OSError, ValueError, KeyError, TypeError):
        return None


def read_meta_at(directory: str, step: int) -> dict:
    try:
        with open(os.path.join(directory, f"meta_{int(step)}.json")) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def group_agreed_step(dirs: dict[int, str | None]) -> int | None:
    """The newest step COMPLETE on every rank of a checkpoint group: the
    min over rank directories of each ``latest`` pointer (a pointer only
    advances past durability, so its step is whole on that rank; the min
    is therefore whole on all). None when any rank has no pointer — the
    group then has no step it can agree on and every rank must conclude
    the same (a fresh run), which is the agreement property itself."""
    steps = []
    for _, d in sorted(dirs.items()):
        s = pointed_step_of(d) if d else None
        if s is None:
            return None
        steps.append(s)
    return min(steps) if steps else None


_META_RE = re.compile(r"^meta_(\d+)\.json$")


def sidecar_steps_of(directory: str) -> list[int]:
    """Steps with a ``meta_<step>.json`` sidecar in ``directory``, newest
    first — the candidate restore points whose rng/epoch/topology
    authority survived."""
    try:
        names = os.listdir(directory)
    except OSError:
        return []
    return sorted(
        (int(m.group(1)) for m in map(_META_RE.match, names) if m),
        reverse=True,
    )


def durable_steps_of(directory: str) -> set[int]:
    """Steps with FINALIZED orbax data in ``directory``: orbax renames
    the step directory into place atomically, so a plain integer-named
    directory is a complete payload even when the ``latest`` pointer
    (which flushes lazily, after async-save durability) never caught up
    — exactly the state a rank killed between saves leaves behind."""
    try:
        names = os.listdir(directory)
    except OSError:
        return set()
    return {
        int(n) for n in names
        if n.isdigit() and os.path.isdir(os.path.join(directory, n))
    }


def group_durable_step(
    dirs: dict[int, str | None], *, meta_dir: str | None = None
) -> int | None:
    """The newest step whose data is finalized on EVERY rank of a group,
    preferring (when ``meta_dir`` is given) steps whose sidecar exists
    there — the authority directory the caller reads rng / epoch /
    topology from. Looser than :func:`group_agreed_step`: it does not
    require any ``latest`` pointer, so a gang shrunk around a rank that
    died with its pointer unflushed can still recover the last step that
    is durable everywhere (the elastic-resume case)."""
    common: set[int] | None = None
    for _, d in sorted(dirs.items()):
        steps = durable_steps_of(d) if d else set()
        if not steps:
            return None
        common = steps if common is None else (common & steps)
    if not common:
        return None
    ordered = sorted(common, reverse=True)
    if meta_dir is not None:
        for s in ordered:
            if os.path.exists(os.path.join(meta_dir, f"meta_{s}.json")):
                return s
    return ordered[0]


def read_raw_payload(directory: str, step: int, target) -> Any:
    """One-shot orbax read of ``directory``'s step ``step`` into shaped
    host ``target`` (numpy leaves). Used by cross-topology resharding to
    read OTHER ranks' payloads: inside a gang the temporary manager is
    the same single-process group as this rank's own, so reading a peer
    directory involves no cross-process barrier."""
    mgr = ocp.CheckpointManager(
        os.path.abspath(directory),
        options=ocp.CheckpointManagerOptions(
            create=False,
            multiprocessing_options=_per_rank_multiprocessing_options(),
        ),
        item_handlers=_per_rank_item_handler(),
    )
    try:
        return mgr.restore(int(step), args=ocp.args.StandardRestore(target))
    finally:
        mgr.close()


def _atomic_write_json(path: str, payload: dict) -> None:
    """Write-then-rename: readers see the old file or the new file, never
    a torn one — the invariant resume correctness rides on."""
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(payload, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


class CheckpointManager:
    """Step-numbered checkpoints under one directory.

    >>> ckpt = CheckpointManager(dir, max_to_keep=3)
    >>> ckpt.save(state)                       # step taken from state.step
    >>> state, step = ckpt.restore(template)   # latest by default

    Crash-consistency layer (docs/FAULT_TOLERANCE.md): alongside orbax's
    own atomic step directories, ``save`` maintains

    - ``meta_<step>.json`` — small sidecar (epoch counter, host rng key)
      written atomically, so a resumed ``fit`` continues the *epoch loop
      and rng stream*, not just the params;
    - ``latest`` — an atomically-replaced pointer naming the newest step
      whose data AND sidecar are both durable. The pointer is advanced
      only after ``wait_until_finished`` confirms the async write
      landed, so it always names a *complete* checkpoint — a worker
      killed mid-save leaves the pointer on the previous step.

    ``restore_latest_valid`` walks steps newest-first (pointer target
    first) and falls back past any checkpoint that fails to load —
    corrupt or partial data costs one checkpoint interval, never the run.
    """

    def __init__(self, directory: str, *, max_to_keep: int = 3):
        self.directory = os.path.abspath(directory)
        self._last_saved: int | None = None
        # Steps whose orbax save was issued but whose durability (and so
        # pointer advance) hasn't been confirmed yet: [(step, meta)].
        self._unpointed: list[tuple[int, dict]] = []
        # Background pointer flusher for wait=False saves: the pointer
        # and sidecar go durable as soon as the async save lands, not at
        # the NEXT save — a rank killed mid-epoch would otherwise leave
        # its whole last checkpoint unpointed and unstamped, and a gang
        # could never agree past it. Joined before any manager touch, so
        # _unpointed is only ever owned by one thread at a time.
        self._flusher: threading.Thread | None = None
        # Root dir is made here, not by orbax (`create=True` is rejected
        # when `active_processes` narrows the group): every rank owns its
        # own directory, so plain makedirs is race-free.
        os.makedirs(self.directory, exist_ok=True)
        self._mgr = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep,
                create=False,
                multiprocessing_options=_per_rank_multiprocessing_options(),
            ),
            item_handlers=_per_rank_item_handler(),
        )

    # -- write ---------------------------------------------------------------
    def save(
        self,
        state: TrainState,
        *,
        step: int | None = None,
        wait: bool = True,
        meta: dict | None = None,
    ) -> int:
        step = int(state.step if step is None else step)
        # Saving the same step twice WITHIN this run (e.g. a zero-batch epoch
        # leaves state.step unchanged, then the epoch-end hook fires again)
        # is a no-op. A step left on disk by a PRIOR run is different — after
        # a restore-and-retrain the new trajectory must win, so it is
        # deleted and rewritten, never silently skipped.
        if step == self._last_saved:
            log.info("checkpoint step %d already saved this run; skipping", step)
            return step
        # Advance the pointer over any prior async save before starting the
        # next one (normally the background flusher already has —
        # joining it here is cheap: the previous save had a whole
        # checkpoint interval to complete).
        self._join_flusher()
        if self._unpointed:
            self._mgr.wait_until_finished()
            self._flush_pointer()
        if step in self._mgr.all_steps():
            log.info("overwriting stale checkpoint step %d from a prior run", step)
            self._mgr.delete(step)
        self._last_saved = step
        payload = {
            "step": jax.device_get(state.step),
            "params": state.params,
            "opt_state": state.opt_state,
        }
        if jax.process_count() > 1:
            payload = jax.tree.map(_detach_local, payload)
        self._mgr.save(step, args=ocp.args.StandardSave(payload))
        meta = dict(meta or {})
        # Every sidecar carries the topology the payload was sharded
        # under; a later resume validates it (and reshards on mismatch).
        meta.setdefault("topology", topology_stamp(state))
        self._unpointed.append((step, meta))
        if wait:
            self._mgr.wait_until_finished()
            self._flush_pointer()
        else:
            self._flusher = threading.Thread(
                target=self._flush_when_durable,
                name="mlspark-ckpt-flusher", daemon=True,
            )
            self._flusher.start()
        log.info("checkpoint step %d -> %s", step, self.directory)
        return step

    def _join_flusher(self) -> None:
        if self._flusher is not None:
            self._flusher.join()
            self._flusher = None

    def _flush_when_durable(self) -> None:
        try:
            self._mgr.wait_until_finished()
            self._flush_pointer()
        except Exception:  # pragma: no cover - durability races at teardown
            log.exception("background pointer flush failed (ignored)")

    def _flush_pointer(self) -> None:
        """Sidecars + pointer for every save confirmed durable. Called only
        after ``wait_until_finished`` — ordering is the correctness."""
        if not self._unpointed:
            return
        for step, meta in self._unpointed:
            _atomic_write_json(self._meta_path(step), meta)
        newest = max(step for step, _ in self._unpointed)
        _atomic_write_json(
            os.path.join(self.directory, LATEST_POINTER), {"step": newest}
        )
        self._unpointed.clear()
        # Retention hygiene: drop sidecars whose step orbax already pruned.
        live = set(self._mgr.all_steps())
        for name in os.listdir(self.directory):
            if name.startswith("meta_") and name.endswith(".json"):
                try:
                    s = int(name[len("meta_"):-len(".json")])
                except ValueError:
                    continue
                if s not in live:
                    try:
                        os.unlink(os.path.join(self.directory, name))
                    except OSError:
                        pass

    def _meta_path(self, step: int) -> str:
        return os.path.join(self.directory, f"meta_{step}.json")

    def read_meta(self, step: int) -> dict:
        """The sidecar saved with ``step`` ({} if absent/unreadable)."""
        try:
            with open(self._meta_path(step)) as f:
                return json.load(f)
        except (OSError, ValueError):
            return {}

    def pointed_step(self) -> int | None:
        """The ``latest`` pointer's target, or None (no pointer / torn)."""
        try:
            with open(os.path.join(self.directory, LATEST_POINTER)) as f:
                return int(json.load(f)["step"])
        except (OSError, ValueError, KeyError, TypeError):
            return None

    # -- read ----------------------------------------------------------------
    def latest_step(self) -> int | None:
        return self._mgr.latest_step()

    def all_steps(self) -> list[int]:
        return sorted(self._mgr.all_steps())

    def restore(
        self, template: TrainState, *, step: int | None = None
    ) -> tuple[TrainState, int]:
        """Restore into the shapes/dtypes/shardings of ``template`` (a state
        built by ``TrainState.create`` with the same model/optimizer)."""
        step = self.latest_step() if step is None else step
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {self.directory}")
        target = {
            "step": jax.device_get(template.step),
            "params": template.params,
            "opt_state": template.opt_state,
        }
        if jax.process_count() > 1:
            # Mirror of the save path: restore through a host numpy
            # target (this rank's local fragment of every leaf), then
            # reattach each leaf onto the template's devices/sharding —
            # including gang-spanning replicated/1-D-sharded arrays.
            payload = self._mgr.restore(
                step,
                args=ocp.args.StandardRestore(
                    jax.tree.map(_detach_local, target)
                ),
            )
            payload = jax.tree.map(attach_local, payload, target)
        else:
            payload = self._mgr.restore(
                step, args=ocp.args.StandardRestore(target)
            )
        state = template.replace(
            step=payload["step"],
            params=payload["params"],
            opt_state=payload["opt_state"],
        )
        log.info("restored checkpoint step %d from %s", step, self.directory)
        return state, step

    def group_rank_dirs(self) -> dict[int, str] | None:
        """Sibling rank directories of this checkpoint's gang group
        (``<root>/ckpt_r<k>``), keyed by rank and including self — or
        None when the directory does not follow the group convention."""
        m = GROUP_DIR_RE.match(os.path.basename(self.directory))
        if not m:
            return None
        parent = os.path.dirname(self.directory)
        try:
            names = os.listdir(parent)
        except OSError:
            return None
        out = {}
        for name in names:
            mm = GROUP_DIR_RE.match(name)
            if mm and os.path.isdir(os.path.join(parent, name)):
                out[int(mm.group(1))] = os.path.join(parent, name)
        return out or None

    def _group_scope(self) -> dict[int, str | None] | None:
        """Rank directories participating in fallback agreement. Inside a
        gang, exactly the CURRENT world's ranks — stale higher-rank
        directories left by a pre-shrink run must not drag the agreed
        step down. Offline (single process), every sibling present. None
        when agreement does not apply (no group / no peers)."""
        dirs = self.group_rank_dirs()
        if dirs is None:
            return None
        world = jax.process_count()
        if world > 1:
            return {r: dirs.get(r) for r in range(world)}
        return dirs if len(dirs) > 1 else None

    def newest_topology_stamp(self) -> dict | None:
        """The topology stamp a resume should validate against, BEFORE
        any restore is attempted (a cross-topology restore would fail
        shapes-first with a misleading error). Authority order: lowest-
        ranked group sibling with a stamped pointer, then self — so
        every rank of a gang resolves the SAME old topology even when
        its own directory is stale (pre-shrink leftovers) or empty (a
        re-expanded gang's new ranks)."""
        dirs = self.group_rank_dirs()
        candidates = (
            [self.directory] if dirs is None
            else [dirs[r] for r in sorted(dirs)]
        )
        for d in candidates:
            # Pointer target first, then every finalized step newest-first
            # — a rank torn down before its pointer flushed still has
            # stamped sidecars for earlier steps.
            steps = [pointed_step_of(d)] + sorted(
                durable_steps_of(d), reverse=True
            )
            seen: set[int] = set()
            for step in steps:
                if step is None or step in seen:
                    continue
                seen.add(step)
                stamp = read_meta_at(d, step).get("topology")
                if stamp:
                    return stamp
        return None

    def restore_latest_valid(
        self, template: TrainState
    ) -> tuple[TrainState, int, dict] | None:
        """Restore the newest checkpoint that actually loads.

        Candidate order: the ``latest`` pointer's step first (the newest
        one known COMPLETE), then every other on-disk step newest-first —
        so a corrupt or partial checkpoint (worker killed mid-save, torn
        disk) costs one checkpoint interval, not the run. Returns
        ``(state, step, meta)``, or None when nothing on disk restores.

        When the directory belongs to a ``ckpt_r<k>`` gang group, the
        candidates are first capped at the GROUP-AGREED step (min over
        every rank's pointer): rank k may hold durable data for step S
        while another rank's S is torn, and without the cap the ranks
        would restore different steps and deadlock the next collective.
        Steps whose sidecar is missing-while-others-exist (torn sidecar
        write) or stamped with a different topology (pre-reshard
        leftovers) are skipped the same way as unreadable data.
        """
        steps = sorted(self._mgr.all_steps(), reverse=True)
        scope = self._group_scope()
        if scope is not None:
            agreed = group_agreed_step(scope)
            if agreed is None:
                if steps:
                    log.warning(
                        "checkpoint group %s has no step complete on "
                        "every rank; starting fresh",
                        os.path.dirname(self.directory),
                    )
                return None
            steps = [s for s in steps if s <= agreed]
        pointed = self.pointed_step()
        if pointed in steps:
            steps.remove(pointed)
            steps.insert(0, pointed)
        stamp = topology_stamp(template)
        any_meta = any(os.path.exists(self._meta_path(s)) for s in steps)
        for step in steps:
            if any_meta and not os.path.exists(self._meta_path(step)):
                log.warning(
                    "checkpoint step %d has no meta sidecar while other "
                    "steps do (torn sidecar write); skipping", step,
                )
                continue
            meta = self.read_meta(step)
            old = meta.get("topology")
            if old and not same_topology(old, stamp):
                log.warning(
                    "checkpoint step %d was written under topology %s, "
                    "this run is %s; skipping", step, old, stamp,
                )
                continue
            try:
                state, _ = self.restore(template, step=step)
            except Exception as e:  # noqa: BLE001 - any load failure → fall back
                log.warning(
                    "checkpoint step %d failed to restore (%r); falling "
                    "back to the previous one", step, e,
                )
                continue
            return state, step, meta
        return None

    def wait(self) -> None:
        """Block until in-flight async saves are durable (and the
        ``latest`` pointer acknowledges them)."""
        self._join_flusher()
        self._mgr.wait_until_finished()
        self._flush_pointer()

    def close(self) -> None:
        try:
            self._join_flusher()
            self._mgr.wait_until_finished()
            self._flush_pointer()
        finally:
            self._mgr.close()

    def __enter__(self) -> "CheckpointManager":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()


def save_params(path: str, params) -> None:
    """One-shot param-only save (the minimal eval-after-train handoff)."""
    with ocp.StandardCheckpointer() as ckptr:
        ckptr.save(os.path.abspath(path), params)


def load_params(path: str, template=None):
    with ocp.StandardCheckpointer() as ckptr:
        return ckptr.restore(os.path.abspath(path), template)
