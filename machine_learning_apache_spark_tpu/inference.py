"""Text-in/text-out inference — the deployment story the reference lacks.

The reference trains its MT model and discards it (``distributor.run``
returns None, quirk Q7; no ``torch.save`` anywhere — SURVEY.md §5). This
module closes the loop for users: a ``Translator`` bundles the trained
params with the exact preprocessing pipelines that produced them, translates
raw strings via any of the three decoders (greedy / beam / sampling), and
round-trips through ``save``/``load`` so a trained model is a directory,
not a process lifetime.

>>> out = train_translator(..., _return_translator=True)
>>> t = out["translator"]
>>> t(["a sentence to translate"])            # → ["ein satz ..."]
>>> t.save("/models/en_de"); t2 = Translator.load("/models/en_de")
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Sequence

import jax
import jax.numpy as jnp

from machine_learning_apache_spark_tpu.data.text import (
    EOS_ID,
    SOS_ID,
    TextPipeline,
    Vocab,
)
from machine_learning_apache_spark_tpu.models import (
    Transformer,
    TransformerConfig,
    beam_translate,
    greedy_translate_cached,
    sample_translate,
)
from machine_learning_apache_spark_tpu.train.metrics import strip_special_ids


def _check_registered_tokenizer(pipe: TextPipeline) -> None:
    """The recorded tokenizer name must resolve from the registry on a
    fresh process — and to the SAME callable this pipeline used (a custom
    function whose ``__name__`` shadows a registry key would be silently
    swapped for the built-in on load, tokenizing differently)."""
    from machine_learning_apache_spark_tpu.data.text import get_tokenizer

    name = pipe.spec["tokenizer"]
    try:
        resolved = get_tokenizer(name)
    except Exception as e:
        raise ValueError(
            f"tokenizer {name!r} is not a registered name; save requires "
            "pipelines built with a registry tokenizer so load() can "
            "rebuild them — register custom callables via "
            "data.text.register_tokenizer(name, fn) before building the "
            "pipeline"
        ) from e
    if resolved is not pipe.tokenizer:
        raise ValueError(
            f"tokenizer {name!r} resolves to a different callable than "
            "this pipeline uses; register the custom tokenizer under its "
            "own name (data.text.register_tokenizer) before saving"
        )


def _overwrite_params(path: str, params) -> None:
    """orbax refuses to overwrite: clear a stale tree, then save."""
    from machine_learning_apache_spark_tpu.train.checkpoint import save_params

    if os.path.exists(path):
        import shutil

        shutil.rmtree(path)
    save_params(path, params)


def _activation_registry():
    import flax.linen as nn

    return {"sigmoid": nn.sigmoid, "relu": nn.relu, "tanh": nn.tanh}


def _model_spec(model) -> dict:
    """Serializable (class name, init kwargs) for a zoo classifier model."""
    import dataclasses as dc

    acts = {fn: name for name, fn in _activation_registry().items()}
    kwargs = {}
    for f in dc.fields(model):
        if f.name in ("parent", "name"):
            continue
        v = getattr(model, f.name)
        if f.name == "dtype":
            kwargs[f.name] = {"__dtype__": jnp.dtype(v).name}
        elif callable(v) and not isinstance(v, type):
            if v not in acts:
                raise ValueError(
                    f"field {f.name!r} holds an unserializable callable "
                    f"{v!r}; use one of {sorted(acts.values())}"
                )
            kwargs[f.name] = {"__activation__": acts[v]}
        elif isinstance(v, (list, tuple)):
            kwargs[f.name] = list(v)
        else:
            kwargs[f.name] = v
    return {"model_class": type(model).__name__, "model_kwargs": kwargs}


def _model_from_spec(spec: dict):
    from machine_learning_apache_spark_tpu import models as zoo

    cls = getattr(zoo, spec["model_class"])
    kwargs = {}
    for k, v in spec["model_kwargs"].items():
        if isinstance(v, dict) and "__activation__" in v:
            kwargs[k] = _activation_registry()[v["__activation__"]]
        elif isinstance(v, dict) and "__dtype__" in v:
            kwargs[k] = jnp.dtype(v["__dtype__"])
        elif isinstance(v, list):
            kwargs[k] = tuple(v)
        else:
            kwargs[k] = v
    return cls(**kwargs)


class Classifier:
    """Trained zoo classifier (MLP / TinyVGG / LSTMClassifier) + optional
    text pipeline, callable on raw inputs — the ``model.eval()`` +
    softmax→argmax block every reference script re-implements
    (``pytorch_cnn.py:154-176``), as a reusable predict surface.

    ``inputs``: feature arrays for MLP/CNN, raw strings (via ``pipeline``)
    or token-id arrays for the LSTM. ``last_timestep=True`` scores
    ``logits[:, -1, :]`` (the LSTM recipe's head, ``pytorch_lstm.py:160``).
    """

    def __init__(
        self,
        model,
        params,
        *,
        pipeline: TextPipeline | None = None,
        last_timestep: bool = False,
        head_pad_id: int | None = None,
        batch_size: int = 256,
    ):
        import flax.linen as nn

        self.model = model
        self.params = nn.unbox(params)
        self.pipeline = pipeline
        self.last_timestep = last_timestep
        # With head_pad_id set, last_timestep reads each row's last NON-PAD
        # position (the classify_from="last_valid" training semantics) —
        # prediction must select the same position the loss trained.
        self.head_pad_id = head_pad_id
        self.batch_size = batch_size

    def _logits(self, inputs) -> jnp.ndarray:
        # len()-based guards: bare truthiness on a multi-element array raises.
        if len(inputs) == 0:
            raise ValueError("predict called with an empty input batch")
        if self.pipeline is not None and isinstance(inputs[0], str):
            inputs = self.pipeline(list(inputs))
        x = jnp.asarray(inputs)
        outs = []
        for i in range(0, len(x), self.batch_size):
            chunk = x[i : i + self.batch_size]
            logits = self.model.apply({"params": self.params}, chunk)
            if self.last_timestep:
                if self.head_pad_id is not None:
                    from machine_learning_apache_spark_tpu.train.loop import (
                        select_last_valid,
                    )

                    logits = select_last_valid(logits, chunk, self.head_pad_id)
                else:
                    logits = logits[:, -1, :]
            outs.append(logits.astype(jnp.float32))
        return jnp.concatenate(outs, axis=0)

    def predict_proba(self, inputs):
        return jax.nn.softmax(self._logits(inputs), axis=-1)

    def predict(self, inputs):
        """argmax class ids — the reference's softmax→argmax eval pattern
        (softmax is monotonic, so argmax of logits suffices)."""
        return jnp.argmax(self._logits(inputs), axis=-1)

    # -- persistence ----------------------------------------------------------
    def save(self, directory: str) -> None:
        directory = os.path.abspath(directory)
        os.makedirs(directory, exist_ok=True)
        meta = {
            **_model_spec(self.model),
            "last_timestep": self.last_timestep,
            "head_pad_id": self.head_pad_id,
        }
        if self.pipeline is not None:
            _check_registered_tokenizer(self.pipeline)
            meta["pipeline"] = self.pipeline.spec
            meta["vocab"] = self.pipeline.vocab.itos
        # Params first, metadata last — a failed save can leave an old
        # params tree behind, but never NEW metadata pointing at OLD params.
        _overwrite_params(os.path.join(directory, "params"), self.params)
        with open(os.path.join(directory, "classifier.json"), "w") as fh:
            json.dump(meta, fh)

    @classmethod
    def load(cls, directory: str) -> "Classifier":
        from machine_learning_apache_spark_tpu.train.checkpoint import (
            load_params,
        )

        directory = os.path.abspath(directory)
        with open(os.path.join(directory, "classifier.json")) as fh:
            meta = json.load(fh)
        model = _model_from_spec(meta)
        pipeline = None
        if "pipeline" in meta:
            spec = meta["pipeline"]
            pipeline = TextPipeline(
                Vocab(meta["vocab"], specials=()),
                spec["tokenizer"],
                max_seq_len=spec["max_seq_len"],
                fixed_len=spec["fixed_len"],
                add_sos=spec["add_sos"],
                add_eos=spec["add_eos"],
            )
        return cls(
            model,
            load_params(os.path.join(directory, "params")),
            pipeline=pipeline,
            last_timestep=meta["last_timestep"],
            head_pad_id=meta.get("head_pad_id"),
        )


class Translator:
    """Trained MT model + its tokenize/detokenize pipelines, callable on
    raw strings. Decoding method per call: ``"greedy"`` (default, KV-cache),
    ``"beam"`` (banked-hypothesis beam search), or ``"sample"``
    (temperature / top-k / nucleus)."""

    def __init__(
        self,
        model: Transformer,
        params,
        src_pipe: TextPipeline,
        trg_pipe: TextPipeline,
    ):
        import flax.linen as nn

        from machine_learning_apache_spark_tpu.parallel.mesh import (
            on_one_device,
        )

        self.model = model
        # Plain-array params: a mesh-less training run leaves the Flax
        # Partitioned boxes on (shard_state strips them only under a mesh),
        # and boxed trees neither apply nor serialize uniformly. On one
        # device: decoding is a one-device program, and params left
        # replicated over a training mesh would run it on every chip —
        # which its Pallas kernels cannot do without a mesh to shard over.
        self.params, _ = on_one_device(nn.unbox(params))
        self.src_pipe = src_pipe
        self.trg_pipe = trg_pipe

    def __call__(
        self,
        texts: Sequence[str],
        *,
        method: str = "greedy",
        max_new_tokens: int | None = None,
        beam_size: int = 4,
        length_penalty: float = 0.6,
        temperature: float = 1.0,
        top_k: int | None = None,
        top_p: float | None = None,
        rng: jax.Array | None = None,
    ) -> list[str]:
        src = jnp.asarray(self.src_pipe(list(texts)))
        kw = dict(max_new_tokens=max_new_tokens, sos_id=SOS_ID, eos_id=EOS_ID)
        if method == "greedy":
            ys = greedy_translate_cached(self.model, self.params, src, **kw)
        elif method == "beam":
            ys = beam_translate(
                self.model, self.params, src,
                beam_size=beam_size, length_penalty=length_penalty, **kw,
            )
        elif method == "sample":
            if rng is None:
                # A silent fixed default would return identical "samples"
                # on every call — the opposite of what sampling is for.
                raise ValueError(
                    "method='sample' requires an explicit rng "
                    "(e.g. rng=jax.random.key(seed))"
                )
            ys = sample_translate(
                self.model, self.params, src, rng,
                temperature=temperature, top_k=top_k, top_p=top_p, **kw,
            )
        else:
            raise ValueError(
                f"method must be 'greedy', 'beam', or 'sample', got {method!r}"
            )
        rows = strip_special_ids(
            ys, pad_id=self.model.cfg.pad_id, sos_id=SOS_ID, eos_id=EOS_ID
        )
        vocab = self.trg_pipe.vocab
        return [" ".join(vocab.lookup_tokens(row)) for row in rows]

    def serve(self, *, start: bool = True, **engine_kwargs):
        """Continuous-batching server over this translator — the
        request-level layer ``__call__`` lacks: concurrent callers share
        an admission queue, and every hot step lands on a program
        precompiled at warmup. Requests decode out of a shared paged KV
        store — one ragged launch program for any occupancy/length mix,
        chunk-padded prefill, and an LRU prefix cache so repeated prompts
        skip their prefill. Decoding is greedy, with outputs identical to
        ``__call__`` (docs/SERVING.md); beam search is not served: it is
        ``__call__(method="beam")``, offline. ``kv_dtype="int8"`` (or env
        ``MLSPARK_SERVE_KV_DTYPE``) quantizes the KV pages to int8 with
        per-page scales — ~4x the concurrency ceiling per HBM byte at
        >= 0.99 greedy token agreement.

        >>> with t.serve(max_active=8, boundaries=(16, 32)) as eng:
        ...     futs = [eng.submit(s) for s in sentences]
        ...     outs = [f.result(timeout=30) for f in futs]

        ``start=False`` returns an unstarted engine (callers control
        warmup/lifecycle); otherwise the engine arrives warmed up and
        serving. Knobs pass through to ``serving.ServingEngine``.
        """
        from machine_learning_apache_spark_tpu.serving import ServingEngine

        engine = ServingEngine(self, **engine_kwargs)
        return engine.start() if start else engine

    # -- what ``ServingEngine`` asks of a bundle ---------------------------------
    @property
    def max_positions(self) -> int:
        return self.model.cfg.max_len

    def encode(self, text: str):
        return self.src_pipe.ragged([text])[0]

    def decode(self, ids) -> str:
        return " ".join(self.trg_pipe.vocab.lookup_tokens(ids))

    def make_runtime(self, **kwargs):
        from machine_learning_apache_spark_tpu.serving.paged_runtime import (
            PagedDecodeRuntime,
        )

        return PagedDecodeRuntime(
            self.model, self.params, sos_id=SOS_ID, eos_id=EOS_ID,
            pad_id=self.model.cfg.pad_id, **kwargs,
        )

    # -- persistence ----------------------------------------------------------
    def save(self, directory: str) -> None:
        """One directory = one deployable model: params (orbax) + config +
        both vocab/pipeline specs."""
        directory = os.path.abspath(directory)
        os.makedirs(directory, exist_ok=True)
        # Fail at save time, not at load time with the model already
        # persisted unrecoverably.
        for pipe in (self.src_pipe, self.trg_pipe):
            _check_registered_tokenizer(pipe)
        cfg = dataclasses.asdict(self.model.cfg)
        cfg["dtype"] = jnp.dtype(cfg["dtype"]).name
        meta = {
            "config": cfg,
            "src_vocab": self.src_pipe.vocab.itos,
            "trg_vocab": self.trg_pipe.vocab.itos,
            "src_pipe": self.src_pipe.spec,
            "trg_pipe": self.trg_pipe.spec,
        }
        # Params first, metadata last — a failed save can leave an old
        # params tree behind, but never a NEW translator.json pointing at
        # OLD params.
        _overwrite_params(os.path.join(directory, "params"), self.params)
        with open(os.path.join(directory, "translator.json"), "w") as fh:
            json.dump(meta, fh)

    @classmethod
    def load(cls, directory: str) -> "Translator":
        from machine_learning_apache_spark_tpu.train.checkpoint import (
            load_params,
        )

        directory = os.path.abspath(directory)
        with open(os.path.join(directory, "translator.json")) as fh:
            meta = json.load(fh)
        cfg_dict = dict(meta["config"])
        cfg_dict["dtype"] = jnp.dtype(cfg_dict["dtype"])
        cfg = TransformerConfig(**cfg_dict)
        model = Transformer(cfg)

        def pipe(vocab_tokens, spec):
            # itos is the full orderd token list (specials included) —
            # rebuild verbatim with an empty specials prefix.
            vocab = Vocab(vocab_tokens, specials=())
            return TextPipeline(
                vocab,
                spec["tokenizer"],
                max_seq_len=spec["max_seq_len"],
                fixed_len=spec["fixed_len"],
                add_sos=spec["add_sos"],
                add_eos=spec["add_eos"],
            )

        params = load_params(os.path.join(directory, "params"))
        return cls(
            model,
            params,
            pipe(meta["src_vocab"], meta["src_pipe"]),
            pipe(meta["trg_vocab"], meta["trg_pipe"]),
        )


class LanguageModel:
    """A decoder-only language model (``models.sala_lm``, ``models.dsa_lm``:
    the runtime finds the model through its configuration's module) with its
    parameters, callable on token ids and servable through the same
    ``ServingEngine`` as a ``Translator``. Prompts and answers are arrays of
    token ids: the bundle holds no tokenizer.

    >>> lm = LanguageModel(cfg, params)
    >>> lm([prompt_ids], max_new_tokens=16)          # greedy, one shot
    >>> with lm.serve(max_context=4096, max_active=8, max_new_tokens=16) as eng:
    ...     answer = eng.submit(prompt_ids).future.result(timeout=60)
    """

    def __init__(self, cfg, params):
        from machine_learning_apache_spark_tpu.parallel.mesh import (
            on_one_device,
        )

        self.cfg = cfg
        self.params, _ = on_one_device(params)

    def __call__(self, prompts, *, max_new_tokens: int, **runtime_kwargs):
        """Greedy continuations of ``prompts`` (a list of id arrays), each an
        int32 array of at most ``max_new_tokens`` ids: every prompt takes a
        row of a runtime of its own size, prefilled and decoded to the end
        (the engine's programs, without its queue)."""
        import numpy as np

        from machine_learning_apache_spark_tpu.serving.lm_runtime import (
            LMDecodeRuntime,
        )
        from machine_learning_apache_spark_tpu.serving.queue import ServeRequest

        prompts = [np.asarray(p, np.int32) for p in prompts]
        runtime = LMDecodeRuntime(
            self.cfg, self.params, max_active=len(prompts),
            max_context=max(len(p) for p in prompts) + max_new_tokens,
            max_new_tokens=max_new_tokens, snapshot_capacity=0,
            **runtime_kwargs,
        )
        requests = [
            ServeRequest(text="", ids=p, submit_time=0.0) for p in prompts
        ]
        for row, req in enumerate(requests):
            if runtime.admit(req, row) is None:
                raise RuntimeError("the default page pool holds every row")
        answers = {}
        while runtime.any_active():
            if runtime.grow():
                raise RuntimeError("the default page pool holds every row")
            for req, ids, row, _ in runtime.launch().completed:
                runtime.retire(row)
                answers[req.id] = np.asarray(ids, np.int32)
        return [answers[r.id] for r in requests]

    def serve(self, *, start: bool = True, max_context: int, **engine_kwargs):
        """Continuous-batching server over this model: ``ServingEngine`` with
        the decoder-only runtime (``serving.lm_runtime``). ``max_context``
        bounds prompt plus new tokens; ``prefix_cache_size`` is the number of
        prefix snapshots kept. ``submit`` takes a sequence of token ids and
        the future resolves to an int32 array of the new ids.

        >>> with lm.serve(max_context=66560, max_active=32,
        ...               max_new_tokens=64, prefill_chunk=512,
        ...               steps_per_launch=8) as eng:
        ...     req = eng.submit(ids)
        """
        from machine_learning_apache_spark_tpu.serving import ServingEngine

        new = engine_kwargs.get("max_new_tokens")
        if new is None:
            raise TypeError("serve() needs max_new_tokens")
        engine_kwargs.setdefault("boundaries", (max_context - new,))
        engine_kwargs.setdefault("page_size", self.cfg.page_size)
        engine = ServingEngine(self, **engine_kwargs)
        return engine.start() if start else engine

    # -- what ``ServingEngine`` asks of a bundle ---------------------------------
    @property
    def max_positions(self) -> int:
        return self.cfg.max_positions

    def encode(self, ids):
        import numpy as np

        return np.asarray(ids, np.int32)

    def decode(self, ids):
        import numpy as np

        return np.asarray(ids, np.int32)

    def make_runtime(self, *, max_src, max_new_tokens, page_size,
                     prefix_cache_size, kv_dtype, quantize_self, **kwargs):
        from machine_learning_apache_spark_tpu.serving.lm_runtime import (
            LMDecodeRuntime,
        )

        if page_size != self.cfg.page_size:
            raise ValueError(
                f"page_size {page_size}: the model's page is "
                f"{self.cfg.page_size} positions"
            )
        if kv_dtype != "float32" or quantize_self:
            raise ValueError(
                "the decoder-only runtime keeps its pages in the model's "
                "dtype; kv_dtype / quantize_self belong to the "
                "encoder-decoder runtime"
            )
        return LMDecodeRuntime(
            self.cfg, self.params, max_context=max_src + max_new_tokens,
            max_new_tokens=max_new_tokens,
            snapshot_capacity=prefix_cache_size, **kwargs,
        )
