"""Machine-translation recipe — the Multi30k Transformer workload (C24).

Reference: ``pytorch_machine_translator.py:107-209`` — en→de pairs, dual
vocabs with fixed length-200 transform chains, encoder-decoder Transformer
(d_model=512, ffn=1024, heads=8, layers=1, dropout=0.1), per-token CE with
pad masking (``:182-188``), Adam(lr=1e-3), batch 32, 1 epoch, per-100-batch
loss+time prints. Deltas by design: masks are built inside the model with
``where(mask, -inf)`` semantics and separate src/trg lengths (fixing quirks
Q8/Q9), teacher forcing shifts the target by one (the reference feeds the
full target and scores it against itself — intent is standard seq2seq), and
tokenization happens once up front, not inside the hot loop
(``:156-161``; SURVEY.md §7 hard parts).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from machine_learning_apache_spark_tpu.data import ArrayDataset
from machine_learning_apache_spark_tpu.data.datasets import (
    load_multi30k,
    synthetic_translation_pairs,
)
from machine_learning_apache_spark_tpu.data.text import translation_pipelines
from machine_learning_apache_spark_tpu.models import Transformer, TransformerConfig
from machine_learning_apache_spark_tpu.train.loop import evaluate, fit
from machine_learning_apache_spark_tpu.train.losses import masked_token_cross_entropy
from machine_learning_apache_spark_tpu.train.state import TrainState, make_optimizer
from machine_learning_apache_spark_tpu.recipes._common import (
    checkpointing,
    default_compute_dtype,
    make_loaders,
    with_overrides,
    resolve_mesh,
    summarize,
)


@dataclass
class TranslationRecipe:
    """Reference hypers: ``pytorch_machine_translator.py:108-129``."""

    d_model: int = 512
    ffn_hidden: int = 1024
    num_heads: int = 8
    num_layers: int = 1
    dropout: float = 0.1
    max_len: int = 200
    epochs: int = 1
    learning_rate: float = 1e-3
    batch_size: int = 32
    seed: int = 0
    data_root: str | None = None  # multi30k files; None → synthetic pairs
    synthetic_n: int = 2048
    use_mesh: bool = True
    log_every: int = 100  # the reference's per-100-batch print cadence
    # None → platform default (bfloat16 on TPU's MXU, float32 elsewhere);
    # an explicit dtype string is honored on any platform.
    dtype: str | None = None
    # Parallelism beyond DP (SURVEY.md §2.3): an inner "model" mesh axis
    # tensor-shards the zoo's annotated weights; a "seq" axis routes
    # self-attention through the ppermute ring (sequence lengths that the
    # axis size divides — the encoder's max_len — ride the ring, others fall
    # through to the dense/flash path).
    model_parallel: int = 1
    sequence_parallel: int = 1
    # Sequence-parallel mechanism: "ring" (ppermute K/V rotation; any head
    # count) or "ulysses" (head↔sequence all_to_all; needs num_heads %
    # sequence_parallel == 0 — fewer, larger collectives).
    sequence_parallel_method: str = "ring"
    # GPipe-style pipeline parallelism over a mesh "pipeline" axis: the
    # encoder and decoder layer stacks each run as a microbatched ppermute
    # ring (parallel.pipeline_transformer), embeddings/LM-head outside the
    # pipelined region. Requires num_layers % pipeline_parallel == 0; the
    # training forward is pipelined, eval uses the (numerically identical)
    # sequential path so ragged tails stay supported. Composes with DP only.
    pipeline_parallel: int = 1
    # Microbatches per pipelined batch (None → one per stage). More
    # microbatches shrink the pipeline bubble (S−1 idle ticks amortized
    # over M) at the cost of smaller per-tick matmuls; the global batch
    # must divide by it, and each microbatch by the data axis.
    pipeline_microbatches: int | None = None
    # Mixture-of-experts FFN (models.moe): moe_experts switch-routed experts
    # per FFN site; expert_parallel shards their weights over a mesh
    # "expert" axis. The Switch aux loss joins the task loss automatically.
    moe_experts: int = 0
    expert_parallel: int = 1
    moe_capacity_factor: float = 1.25  # per-expert slots = ceil(cf·s/E)
    moe_aux_weight: float = 1e-2  # load-balance loss weight
    # jax.checkpoint over encoder/decoder layers: recompute activations in
    # the backward instead of saving them — the FLOPs-for-HBM trade for
    # long-context / deep-stack training.
    remat: bool = False
    # ZeRO stage 1: shard optimizer moments 1/N over the mesh "data" axis
    # (each replica stores its slice of the Adam state instead of a full
    # copy; XLA inserts the gathers). Same math, less HBM per chip.
    zero1: bool = False
    # Training-scale knobs beyond the reference's fixed-lr Adam: lr schedule
    # ("constant" | "cosine" | "warmup_cosine" over the full run), linear
    # warmup steps, global-norm gradient clipping, and gradient accumulation
    # (grad_accum microbatches averaged per optimizer update).
    schedule: str | None = None
    warmup_steps: int = 0
    grad_clip: float | None = None
    grad_accum: int = 1
    # Decode the validation set after training and report corpus BLEU — the
    # MT quality metric the reference never computes (loss only,
    # ``pytorch_machine_translator.py:189``).
    compute_bleu: bool = False
    # Checkpoint/resume (SURVEY.md §5): save every checkpoint_every epochs
    # under checkpoint_dir; resume from the latest checkpoint when present.
    checkpoint_dir: str | None = None
    checkpoint_every: int = 1
    resume: bool = True
    # Structured observability: append per-epoch + end-of-run JSON lines
    # (train.metrics.MetricsLogger) alongside the print vocabulary.
    metrics_path: str | None = None
    # Paired length-bucketed TRAINING batches (SURVEY.md §7: keep XLA's
    # static shapes but stop paying corpus-max attention FLOPs on short
    # sentence pairs). Eval keeps the fixed width. Incompatible with
    # sequence_parallel (the ring needs one divisible length).
    bucket_by_length: bool = False
    bucket_boundaries: tuple[int, ...] = ()  # () → (1/4, 1/2, full) of max_len
    # Sequence packing (data.packing): fill each fixed max_len row with
    # SEVERAL sentence pairs behind block-diagonal segment masks +
    # per-segment positional restart — one static shape, near-zero pad
    # work. Per-pair numerics match the unpacked run (tests/test_packing).
    # Training only; eval keeps one pair per row. Incompatible with
    # bucket_by_length (different answer to the same waste), SP (the ring
    # classifies chunks globally, not per segment), PP (microbatch split
    # needs the plain loss), and MoE (capacity routing untested on mixed
    # rows — rejected loudly rather than silently unvalidated).
    # Trade-off: segment masks are dense [B,1,S,S] overrides, so packed
    # attention takes the fused-XLA path, not the Pallas flash kernel —
    # immaterial at this workload's seq 200 (40K scores/head), and the
    # packing win is in the matmuls; a flash-consumable segment spec is
    # the kernel-side follow-up if long-context packing is ever needed.
    pack_sequences: bool = False
    # K batches per host dispatch via the scanned trainer (fixed-width
    # loaders only: stacked scan batches need one static shape, so this is
    # incompatible with bucket_by_length's per-bucket widths).
    steps_per_call: int = 1
    # Shard batches onto the mesh N ahead of consumption
    # (parallel.device_prefetch): host->device transfers overlap device
    # compute. Identical values (pinned by TestDevicePrefetch); 0 disables.
    prefetch_to_device: int = 2


def make_translation_loss(model, pad_id: int, *, train: bool = True):
    """Teacher-forced pad-masked CE over ``(src, trg)`` batches — the manual
    mask-mean at ``pytorch_machine_translator.py:182-188``.

    MoE models additionally sow Switch load-balancing losses into the
    ``"losses"`` collection; their mean joins the task loss at
    ``cfg.moe_aux_weight`` (reported as ``moe_aux`` in the step metrics).
    """
    moe = getattr(model.cfg, "moe_experts", 0) > 0

    def loss_fn(params, batch, rng):
        src, trg = batch
        kwargs = dict(
            deterministic=not train,
            rngs={"dropout": rng} if train else None,
        )
        if moe:
            logits, mutated = model.apply(
                {"params": params}, src, trg[:, :-1],
                mutable=["losses"], **kwargs,
            )
            aux_terms = jax.tree.leaves(mutated.get("losses", {}))
            aux = sum(aux_terms) / max(len(aux_terms), 1)
            loss = masked_token_cross_entropy(logits, trg[:, 1:], pad_id)
            return loss + model.cfg.moe_aux_weight * aux, {"moe_aux": aux}
        logits = model.apply({"params": params}, src, trg[:, :-1], **kwargs)
        loss = masked_token_cross_entropy(logits, trg[:, 1:], pad_id)
        return loss, {}

    return loss_fn


def make_packed_translation_loss(model, pad_id: int, *, train: bool = True):
    """Teacher-forced CE over PACKED batches
    (``src, src_seg, src_pos, trg, trg_seg, trg_pos`` — ``data.packing``).

    Same per-token CE as ``make_translation_loss`` on the equivalent
    unpacked rows (pinned by ``tests/test_packing.py`` logit/loss parity):
    block-diagonal segment masks at all three attention sites, per-segment
    positional restart, and a loss mask that additionally drops the
    boundary position where one segment's last token would otherwise be
    scored against the NEXT segment's first.
    """
    import optax

    from machine_learning_apache_spark_tpu.ops.masks import (
        combine_masks,
        make_causal_mask,
        make_segment_mask,
    )

    def loss_fn(params, batch, rng):
        src, src_seg, src_pos, trg, trg_seg, trg_pos = batch
        tin_seg = trg_seg[:, :-1]
        logits = model.apply(
            {"params": params},
            src,
            trg[:, :-1],
            src_mask=make_segment_mask(src_seg, src_seg),
            trg_mask=combine_masks(
                make_segment_mask(tin_seg, tin_seg),
                make_causal_mask(tin_seg.shape[1]),
            ),
            cross_mask=make_segment_mask(tin_seg, src_seg),
            src_positions=src_pos,
            trg_positions=trg_pos[:, :-1],
            deterministic=not train,
            rngs={"dropout": rng} if train else None,
        )
        labels = trg[:, 1:]
        # Score a position only when its label belongs to the SAME segment
        # as its input token: pad labels drop (segment 0) and so does each
        # segment's boundary into the next. The pad_id conjunct is
        # redundant under the packer's segment-0-iff-pad convention; it
        # keeps the signature's pad contract honest if that ever diverges.
        scored = (
            (trg_seg[:, 1:] == tin_seg) & (tin_seg > 0) & (labels != pad_id)
        )
        per_tok = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels
        )
        loss = (per_tok * scored).sum() / jnp.maximum(scored.sum(), 1)
        return loss, {}

    return loss_fn


def make_pipeline_translation_loss(
    model, pad_id: int, mesh, *, n_micro: int | None = None, train: bool = True
):
    """The training loss with the forward scheduled as two GPipe rings over
    the mesh's ``"pipeline"`` axis (``parallel.pipeline_transformer``) —
    same pad-masked CE semantics as ``make_translation_loss``."""
    from machine_learning_apache_spark_tpu.parallel.pipeline_transformer import (
        pipeline_transformer_logits,
    )

    def loss_fn(params, batch, rng):
        src, trg = batch
        logits = pipeline_transformer_logits(
            model, params, src, trg[:, :-1], mesh,
            n_micro=n_micro,
            rng=rng if train else None,
            deterministic=not train,
        )
        return masked_token_cross_entropy(logits, trg[:, 1:], pad_id), {}

    return loss_fn


def train_translator(
    recipe: TranslationRecipe | None = None,
    *,
    _return_state: bool = False,
    _return_translator: bool = False,
    **overrides,
) -> dict:
    r = with_overrides(recipe or TranslationRecipe(), overrides)

    if r.pack_sequences:
        # Validate BEFORE the data section: packing a real corpus is an
        # O(corpus) host pass — never pay it just to raise afterwards.
        blockers = {
            "bucket_by_length": r.bucket_by_length,
            "sequence_parallel": r.sequence_parallel > 1,
            "pipeline_parallel": r.pipeline_parallel > 1,
            "moe_experts": r.moe_experts > 0,
        }
        bad = [k for k, v in blockers.items() if v]
        if bad:
            raise ValueError(
                f"pack_sequences is incompatible with {bad} (see the "
                f"recipe field's rationale)"
            )
    if r.data_root:
        pairs = load_multi30k(r.data_root, "train")
        val_pairs = load_multi30k(r.data_root, "valid")
    else:
        pairs = synthetic_translation_pairs(r.synthetic_n, seed=r.seed)
        val_pairs = synthetic_translation_pairs(
            max(r.synthetic_n // 8, 64), seed=r.seed + 1
        )

    # Under SP, pad targets one longer so the teacher-forced decoder input
    # (trg[:, :-1]) has length max_len and rides the ring like the encoder —
    # otherwise its length max_len-1 shares no divisor with any seq axis.
    src_pipe, trg_pipe = translation_pipelines(
        pairs,
        max_len=r.max_len,
        trg_max_len=r.max_len + 1 if r.sequence_parallel > 1 else None,
    )
    to_ids = lambda ps: (
        src_pipe([s for s, _ in ps]),
        trg_pipe([t for _, t in ps]),
    )
    packed = None
    if r.pack_sequences:
        from machine_learning_apache_spark_tpu.data.packing import (
            pack_translation_pairs,
        )
        from machine_learning_apache_spark_tpu.data.text import PAD_ID

        packed = pack_translation_pairs(
            src_pipe.ragged([s for s, _ in pairs]),
            trg_pipe.ragged([t for _, t in pairs]),
            src_len=r.max_len,
            trg_len=r.max_len,
            pad_id=PAD_ID,
        )
        train_ds = ArrayDataset(*packed.arrays())
    else:
        train_ds = ArrayDataset(*to_ids(pairs))
    val_ds = ArrayDataset(*to_ids(val_pairs))

    cfg = TransformerConfig(
        src_vocab_size=len(src_pipe.vocab),
        trg_vocab_size=len(trg_pipe.vocab),
        # Megatron-style vocab padding: keep the LM head — the largest
        # matmul — shardable over the "model" axis whatever the vocab size;
        # logits are sliced back inside the model, so losses are unchanged.
        logit_pad=(
            (-len(trg_pipe.vocab)) % r.model_parallel
            if r.model_parallel > 1
            else 0
        ),
        d_model=r.d_model,
        ffn_hidden=r.ffn_hidden,
        num_heads=r.num_heads,
        num_layers=r.num_layers,
        dropout=r.dropout,
        max_len=r.max_len,
        remat=r.remat,
        moe_experts=r.moe_experts,
        moe_capacity_factor=r.moe_capacity_factor,
        moe_aux_weight=r.moe_aux_weight,
        dtype=default_compute_dtype(r.dtype),
    )
    model = Transformer(cfg)

    if r.moe_experts and r.moe_experts % max(r.expert_parallel, 1):
        raise ValueError(
            f"moe_experts={r.moe_experts} must divide evenly over "
            f"expert_parallel={r.expert_parallel}"
        )
    if r.expert_parallel > 1 and not r.moe_experts:
        # Never silently carve a dead mesh axis: without MoE weights no
        # param carries the "expert" logical axis, so the devices would
        # replicate identical work while the user believes EP ran.
        raise ValueError(
            f"expert_parallel={r.expert_parallel} requires moe_experts > 0"
        )
    if r.bucket_by_length and r.sequence_parallel > 1:
        raise ValueError(
            "bucket_by_length is incompatible with sequence_parallel: the "
            "ring needs one fixed seq-axis-divisible length"
        )
    if r.bucket_by_length and r.steps_per_call > 1:
        raise ValueError(
            "steps_per_call > 1 is incompatible with bucket_by_length: "
            "scanned dispatch stacks K batches into one static shape, but "
            "buckets emit per-bucket widths"
        )
    if r.pipeline_parallel > 1:
        # The pipeline schedule supports dp×pp meshes only (TP/SP inside a
        # stage and MoE capacity routing are out of scope for the ring).
        incompatible = {
            "model_parallel": r.model_parallel,
            "sequence_parallel": r.sequence_parallel,
            "expert_parallel": r.expert_parallel,
        }
        bad = {k: v for k, v in incompatible.items() if v > 1}
        if bad or r.moe_experts:
            raise ValueError(
                f"pipeline_parallel={r.pipeline_parallel} composes with "
                f"data parallelism only; incompatible settings: "
                f"{bad or {'moe_experts': r.moe_experts}}"
            )
        if r.bucket_by_length:
            raise ValueError(
                "pipeline_parallel is incompatible with bucket_by_length "
                "(the microbatch split needs one fixed batch shape)"
            )
        if r.num_layers % r.pipeline_parallel:
            raise ValueError(
                f"num_layers={r.num_layers} must divide into "
                f"{r.pipeline_parallel} pipeline stages"
            )
    mesh = resolve_mesh(
        r.use_mesh,
        model_parallel=r.model_parallel,
        sequence_parallel=r.sequence_parallel,
        expert_parallel=r.expert_parallel,
        pipeline_parallel=r.pipeline_parallel,
    )
    # Under bucketing the fixed-width train loader is never used: build only
    # the eval loader (full-coverage contract keeps the fixed width).
    train_loader, val_loader = make_loaders(
        None if r.bucket_by_length else train_ds,
        val_ds,
        batch_size=r.batch_size,
        mesh=mesh,
        seed=r.seed,
    )
    if r.bucket_by_length:
        from machine_learning_apache_spark_tpu.data.bucketing import (
            BucketByLengthPairsLoader,
        )
        from machine_learning_apache_spark_tpu.recipes._common import (
            make_bucketed_loader,
        )

        train_loader = make_bucketed_loader(
            BucketByLengthPairsLoader,
            src_pipe.ragged([s for s, _ in pairs]),
            trg_pipe.ragged([t for _, t in pairs]),
            batch_size=r.batch_size,
            mesh=mesh,
            full_width=r.max_len,
            boundaries=r.bucket_boundaries,
            seed=r.seed,
        )

    if r.pack_sequences:
        src0, trg0 = train_ds[:2][0], train_ds[:2][3]
    else:
        src0, trg0 = train_ds[:2]
    params = model.init(jax.random.key(r.seed), src0, trg0[:, :-1])["params"]
    # total_steps counts OPTIMIZER updates: under accumulation only every
    # grad_accum-th microbatch updates, and MultiSteps' microbatch counter
    # carries across epoch boundaries — so divide the GLOBAL batch count.
    n_micro = len(train_loader) * r.epochs
    if r.grad_accum > max(n_micro, 1):
        raise ValueError(
            f"grad_accum={r.grad_accum} exceeds the run's {n_micro} "
            "microbatches; the optimizer would never update"
        )
    if r.grad_accum > 1 and n_micro % r.grad_accum:
        from machine_learning_apache_spark_tpu.utils.logging import get_logger

        get_logger(__name__).warning(
            "grad_accum=%d does not divide the run's %d microbatches; the "
            "final %d gradient(s) stay in the accumulator and never update "
            "the params",
            r.grad_accum, n_micro, n_micro % r.grad_accum,
        )
    total_updates = max(n_micro // max(r.grad_accum, 1), 1)
    state = TrainState.create(
        apply_fn=model.apply,
        params=params,
        tx=make_optimizer(
            "adam",
            r.learning_rate,
            schedule=r.schedule,
            warmup_steps=r.warmup_steps,
            total_steps=total_updates,
            grad_clip=r.grad_clip,
            accumulate_steps=r.grad_accum,
        ),
    )

    # Under sequence parallelism the attention dispatch context must wrap
    # tracing (fit/evaluate jit their steps on first batch).
    import contextlib

    from machine_learning_apache_spark_tpu.ops.attention import (
        sequence_parallel,
    )

    if r.sequence_parallel > 1 and r.sequence_parallel_method == "ulysses":
        if r.num_heads % r.sequence_parallel:
            raise ValueError(
                f"sequence_parallel_method='ulysses' needs num_heads "
                f"({r.num_heads}) divisible by sequence_parallel "
                f"({r.sequence_parallel}); use 'ring'"
            )
    sp_ctx = (
        sequence_parallel(mesh, method=r.sequence_parallel_method)
        if mesh is not None and r.sequence_parallel > 1
        else contextlib.nullcontext()
    )
    with checkpointing(
        r.checkpoint_dir, state, resume=r.resume
    ) as (ckpt, state, resumed):
        if resumed and r.schedule in ("cosine", "warmup_cosine"):
            # The restored optimizer count sits at the prior run's update
            # total; a schedule whose horizon was sized for a fresh run
            # would evaluate at/past its end and train the whole resumed
            # run at the decayed floor LR. Extend the horizon by the
            # restored update count (the step counter counts microbatches;
            # updates are 1/grad_accum of those) so training continues
            # mid-curve. The opt_state STRUCTURE is unchanged — only the
            # lr curve differs.
            prior_updates = resumed // max(r.grad_accum, 1)
            state = state.replace(
                tx=make_optimizer(
                    "adam",
                    r.learning_rate,
                    schedule=r.schedule,
                    warmup_steps=r.warmup_steps,
                    total_steps=prior_updates + total_updates,
                    grad_clip=r.grad_clip,
                    accumulate_steps=r.grad_accum,
                )
            )
        if r.pipeline_parallel > 1:
            train_loss = make_pipeline_translation_loss(
                model, cfg.pad_id, mesh, n_micro=r.pipeline_microbatches
            )
        elif r.pack_sequences:
            train_loss = make_packed_translation_loss(model, cfg.pad_id)
        else:
            train_loss = make_translation_loss(model, cfg.pad_id)
        with sp_ctx:
            result = fit(
                state,
                train_loss,
                train_loader,
                epochs=r.epochs,
                rng=jax.random.key(r.seed),
                mesh=mesh,
                log_every=r.log_every,
                checkpointer=ckpt,
                checkpoint_every=r.checkpoint_every,
                metrics_file=r.metrics_path,
                zero1=r.zero1,
                steps_per_call=r.steps_per_call,
                prefetch_to_device=r.prefetch_to_device,
            )
            metrics = evaluate(
                result.state,
                make_translation_loss(model, cfg.pad_id, train=False),
                val_loader,
                mesh=mesh,
            )
    extra: dict = {}
    if resumed is not None:
        extra["resumed_from_step"] = resumed
    if r.bucket_by_length:
        extra["padding_efficiency"] = train_loader.padding_efficiency
    if packed is not None:
        # Non-pad fraction of the packed token grid, vs what the same
        # corpus costs one-pair-per-row (the reference's layout).
        extra["packing_token_efficiency"] = round(packed.token_efficiency, 4)
        extra["unpacked_token_efficiency"] = round(
            packed.unpacked_efficiency, 4
        )
        extra["packed_rows"] = len(packed.src)
        extra["packed_pairs"] = packed.pair_count
    if r.compute_bleu and val_loader is not None:
        from machine_learning_apache_spark_tpu.data.text import EOS_ID, SOS_ID
        from machine_learning_apache_spark_tpu.models.transformer import (
            greedy_translate_cached,
        )
        from machine_learning_apache_spark_tpu.train.metrics import (
            corpus_bleu,
            strip_special_ids,
        )

        # One jitted decode, reusing the eval loader's batching (including
        # its ragged tail — one extra compile, zero skipped rows). Target
        # width is the pipeline's fixed length, so gen length is static.
        gen = min(val_ds[:1][1].shape[1], r.max_len) - 1
        decode = jax.jit(
            lambda params, src: greedy_translate_cached(
                model, params, src,
                max_new_tokens=gen, sos_id=SOS_ID, eos_id=EOS_ID,
            )
        )
        kw = dict(pad_id=cfg.pad_id, sos_id=SOS_ID, eos_id=EOS_ID)
        cands: list[list[int]] = []
        refs: list[list[int]] = []
        from machine_learning_apache_spark_tpu.ops.attention import (
            kernel_mesh,
        )

        with kernel_mesh(mesh):  # params live on the mesh; so does decode
            for src_b, trg_b in val_loader:
                cands.extend(strip_special_ids(
                    decode(result.state.params, src_b), **kw
                ))
                refs.extend(strip_special_ids(trg_b, **kw))
        extra["bleu"] = corpus_bleu(cands, refs)

    out = summarize(
        result,
        metrics,
        metrics_path=r.metrics_path,
        src_vocab=len(src_pipe.vocab),
        trg_vocab=len(trg_pipe.vocab),
        **extra,
    )
    if _return_state:
        # Test/inspection hook — the state is NOT picklable across the
        # launcher boundary, so it never rides the default result dict.
        out["state"] = result.state
    if _return_translator:
        # Text-in/text-out handle on the trained model (inference.Translator)
        # — like the state, it never crosses the launcher boundary.
        from machine_learning_apache_spark_tpu.inference import Translator

        out["translator"] = Translator(
            model, result.state.params, src_pipe, trg_pipe
        )
    return out
