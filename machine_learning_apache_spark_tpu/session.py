"""Session layer — the SparkSession equivalent (reference L0).

The reference opens every script with either an inline-configured
``SparkSession.builder`` (``mllib_multilayer_perceptron_classifier.py:12-19``)
or an empty ``SparkConf`` populated by spark-submit whose
``spark.executor.instances`` is read back as the world size
(``distributed_cnn.py:41-43``). Here the session wraps the JAX runtime: the
"cluster" is the TPU slice, world size is ``jax.process_count()`` /
``jax.device_count()``, and the ``read`` attribute exposes the Spark-style
``session.read.format("libsvm").load(path)`` ingestion API.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Optional

import jax

from machine_learning_apache_spark_tpu.config import SessionConfig, _coerce
from machine_learning_apache_spark_tpu.utils.logging import get_logger

log = get_logger(__name__)

_ACTIVE_SESSION: Optional["Session"] = None
_LOCK = threading.Lock()


class SessionBuilder:
    """``Session.builder.app_name(...).config(k, v).get_or_create()``.

    Mirrors ``SparkSession.builder.appName(...).config(...).getOrCreate()``
    (``pytorch_multilayer_perceptron.py:24-30``). Both snake_case and the
    Spark-style camelCase method names are provided.
    """

    def __init__(self) -> None:
        self._conf: dict[str, Any] = {}

    def app_name(self, name: str) -> "SessionBuilder":
        self._conf["app_name"] = name
        return self

    appName = app_name

    def config(self, key: str, value: Any) -> "SessionBuilder":
        # Accept Spark-style dotted keys ("spark.executor.instances") and
        # map them onto SessionConfig fields.
        norm = key.replace("spark.", "").replace(".", "_")
        self._conf[norm] = value
        return self

    def master(self, _url: str) -> "SessionBuilder":
        # Spark's master URL has no TPU meaning; accepted for API parity.
        return self

    def get_or_create(self) -> "Session":
        global _ACTIVE_SESSION
        with _LOCK:
            if _ACTIVE_SESSION is not None and self._conf:
                # Spark semantics: getOrCreate() returns the existing
                # session and conf on the builder is NOT applied. Silent
                # drops are expensive (e.g. a platform request that never
                # applies) — but only keys that actually DIFFER from the
                # active session are dropped in any meaningful sense;
                # idempotent re-creation with identical conf should stay
                # quiet.
                active = _ACTIVE_SESSION.conf
                fields = {f.name: f for f in dataclasses.fields(SessionConfig)}

                def _resolved(k, v):
                    # Compare post-coercion, the way creation would apply it
                    # ("8" matches an active executor count of 8). An
                    # uncoercible value can't match anything — return it
                    # raw so it counts as differing (warn, never raise:
                    # the conf is ignored either way under Spark
                    # getOrCreate semantics).
                    if k in fields and isinstance(v, str):
                        try:
                            return _coerce(v, type(fields[k].default))
                        except (TypeError, ValueError):
                            return v
                    return v

                unknown = sorted(k for k in self._conf if k not in fields)
                differing = sorted(
                    k for k, v in self._conf.items()
                    if k in fields and getattr(active, k) != _resolved(k, v)
                )
                if differing:
                    log.warning(
                        "getOrCreate(): active session exists; builder conf "
                        "%s ignored (stop() the session first to apply it)",
                        differing,
                    )
                if unknown:
                    # Not a stop()-and-retry situation: creation would drop
                    # these too. Distinct message so the user isn't sent on
                    # a futile restart cycle.
                    log.warning(
                        "getOrCreate(): conf keys %s match no SessionConfig "
                        "field and are unsupported (ignored on creation too)",
                        unknown,
                    )
            if _ACTIVE_SESSION is None:
                fields = {f.name: f for f in dataclasses.fields(SessionConfig)}
                kwargs = {}
                for k, v in self._conf.items():
                    if k not in fields:
                        continue
                    # spark-submit hands every conf value over as a string;
                    # coerce to the field's declared type like Spark does.
                    target = type(fields[k].default)
                    kwargs[k] = _coerce(v, target) if isinstance(v, str) else v
                _ACTIVE_SESSION = Session(SessionConfig.from_env(**kwargs))
            return _ACTIVE_SESSION

    getOrCreate = get_or_create


class _BuilderDescriptor:
    def __get__(self, obj: Any, objtype: Any = None) -> SessionBuilder:
        return SessionBuilder()


class Session:
    """A live handle on the (possibly multi-host) JAX runtime.

    Interface up (SURVEY.md §1 L0): the session object plus the world size —
    the reference's ``executors_n`` (``distributed_cnn.py:43``) is
    ``session.executor_count`` here, derived from the runtime rather than conf.
    """

    builder = _BuilderDescriptor()

    def __init__(self, conf: SessionConfig | None = None) -> None:
        self.conf = conf or SessionConfig()
        if self.conf.platform:
            # Respect an explicit platform request (e.g. tests force "cpu").
            # Env vars are unreliable here — jax may already be imported — so
            # use the config API, which works until first backend init.
            try:
                jax.config.update("jax_platforms", self.conf.platform)
            except RuntimeError as e:
                raise RuntimeError(
                    f"platform={self.conf.platform!r} requested after the JAX "
                    "backend was already initialized; request it before any "
                    "device use"
                ) from e
        self._stopped = False

    # -- cluster facts (derived from runtime, never from conf) ----------------
    @property
    def device_count(self) -> int:
        return jax.device_count()

    @property
    def local_device_count(self) -> int:
        return jax.local_device_count()

    @property
    def process_count(self) -> int:
        return jax.process_count()

    @property
    def process_index(self) -> int:
        return jax.process_index()

    @property
    def executor_count(self) -> int:
        """The reference's ``executors_n``: one 'executor' per participating
        process (``distributed_multilayer_perceptron.py:39``)."""
        return jax.process_count()

    @property
    def devices(self):
        return jax.devices()

    # -- ingestion ------------------------------------------------------------
    @property
    def read(self):
        from machine_learning_apache_spark_tpu.data.reader import DataReader

        return DataReader(self)

    # -- mesh -----------------------------------------------------------------
    def mesh(self, **axes: int):
        """Build a device mesh, e.g. ``session.mesh(data=8)`` or
        ``session.mesh(data=2, model=4)``. Axis size 0 or -1 means "all
        remaining devices"."""
        from machine_learning_apache_spark_tpu.parallel.mesh import make_mesh

        return make_mesh(axes or None)

    # -- distributed bootstrap ------------------------------------------------
    def initialize_distributed(self) -> None:
        """Multi-host bootstrap: the ``MASTER_ADDR/MASTER_PORT/WORLD_SIZE/RANK``
        env-var rendezvous of the reference (``pytorch_multilayer_perceptron.py:15-21``,
        commented block ``distributed_cnn.py:22-27``) maps onto
        ``jax.distributed.initialize(coordinator_address, num_processes,
        process_id)`` (SURVEY.md §2.4)."""
        from machine_learning_apache_spark_tpu.launcher.coordinator import (
            initialize_from_env,
        )

        initialize_from_env(self.conf)

    def stop(self) -> None:
        """``spark.stop()`` equivalent (``distributed_cnn.py:232``)."""
        global _ACTIVE_SESSION
        with _LOCK:
            if _ACTIVE_SESSION is self:
                _ACTIVE_SESSION = None
        self._stopped = True

    def __repr__(self) -> str:
        return (
            f"Session(app={self.conf.app_name!r}, devices={self.device_count}, "
            f"processes={self.process_count}, backend={jax.default_backend()})"
        )


def active_session() -> Session:
    """The current session, creating a default one if needed."""
    return Session.builder.get_or_create()
