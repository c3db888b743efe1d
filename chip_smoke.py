#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the framework's main path once, through the entry points a user
calls, at the full width of the reference MT Transformer (depth 1, random
weights from a seed): generate a Multi30k-shaped corpus, train with
``recipes.train_translator``, serve the trained model through the paged
engine (``Translator.serve``), call every Pallas kernel compiled against
its XLA path and, when more than one chip is visible, check the same path
under data parallelism and ZeRO-1. One process, which uses every chip it
sees and starts no child.

    python3 chip_smoke.py [--out DIR] [--seed N]

It needs a TPU: anywhere else it exits non-zero, naming the platform JAX
found, and prints no result. It sets no platform and catches no phase's
exception — a phase that fails ends the run non-zero. On success the last
line of stdout is ``{"ok": true, "device": {"platform": ..., "kind": ...,
"count": ...}}`` with the device as JAX reports it; the line before it (and
``<out>/report.json``) is the full report: per-phase seconds, compile
seconds, which attention implementation each site compiled to, which
kernels compiled, where the engine lived. Timings are smoke observations,
not metrics.

``--rehearse`` runs the same phases at toy width on whatever platform JAX
has, with the kernels in interpret mode — for debugging this script on a
CPU sandbox before it is sent to the chip. The report is stamped with the
platform it ran on.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import logging
import os
import re
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass(frozen=True)
class Size:
    """One sizing of the smoke. ``FULL`` is the reference MT model at the
    recipe defaults; ``TOY`` is the CPU rehearsal."""

    name: str
    # data: word types per side chosen so the built vocabularies (types +
    # 4 specials) equal bench.py's SRC_VOCAB / TRG_VOCAB; one epoch is
    # train_pairs / (32 x chips) steps.
    src_types: int
    trg_types: int
    train_pairs: int
    valid_pairs: int
    max_words: int
    recipe: dict  # overrides on TranslationRecipe's defaults
    log_every: int
    # serve
    boundaries: tuple
    max_new_tokens: int
    n_prompts: int
    n_threads: int
    # multi-chip ZeRO-1 check
    zero_pairs_per_chip: int
    # kernels
    interpret: bool
    kernel_dtype: str
    tolerance: float
    flash_fwd_shape: tuple  # (B, H, S, d)
    flash_bwd_shape: tuple  # S*S >= ops.attention.FLASH_MIN_SCORES
    paged_heads: int
    scan_shape: tuple  # (B, T, key heads, value heads, dk, dv, chunk)


FULL = Size(
    name="full",
    src_types=8188, trg_types=10236, train_pairs=4096, valid_pairs=512,
    max_words=40, recipe={}, log_every=8,
    boundaries=(16, 32, 64), max_new_tokens=48, n_prompts=24, n_threads=4,
    zero_pairs_per_chip=256,
    interpret=False, kernel_dtype="bfloat16", tolerance=0.05,
    flash_fwd_shape=(2, 8, 200, 64), flash_bwd_shape=(1, 2, 512, 64),
    paged_heads=8, scan_shape=(2, 200, 2, 4, 128, 128, 64),
)
TOY = Size(
    name="rehearsal",
    src_types=200, trg_types=260, train_pairs=512, valid_pairs=64,
    max_words=20,
    recipe=dict(d_model=32, ffn_hidden=64, num_heads=2, max_len=32,
                batch_size=8),
    log_every=2,
    boundaries=(8, 16, 24), max_new_tokens=10, n_prompts=16, n_threads=4,
    zero_pairs_per_chip=32,
    interpret=True, kernel_dtype="float32", tolerance=1e-4,
    flash_fwd_shape=(2, 2, 40, 16), flash_bwd_shape=(1, 1, 512, 16),
    paged_heads=2, scan_shape=(1, 40, 1, 2, 16, 8, 16),
)

PHASES: dict = {}  # name -> {"ok", "seconds", observations...}


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(f"chip_smoke check failed: {what}")


@contextlib.contextmanager
def phase(name: str):
    """Time a phase. No except clause on purpose: a phase that raises ends
    the process with its traceback, and the report is never printed."""
    say(f"phase {name} ...")
    info: dict = {}
    t0 = time.perf_counter()
    yield info
    info["ok"] = True
    info["seconds"] = round(time.perf_counter() - t0, 2)
    PHASES[name] = info
    say(f"phase {name} ok in {info['seconds']}s: "
        + json.dumps({k: v for k, v in info.items()
                      if k not in ("ok", "seconds")}, default=str)[:1500])


# -- data ---------------------------------------------------------------------
def _word(prefix: str, i: int) -> str:
    """Alphabetic word #i: one token under the recipe's tokenizer."""
    letters = ""
    for _ in range(3):
        letters = chr(ord("a") + i % 26) + letters
        i //= 26
    return prefix + letters


def write_corpus(
    root: str, size: Size, seed: int, *, n_train: int, n_valid: int,
    fixed_words: int | None = None,
) -> None:
    """A parallel corpus in the Multi30k file layout under
    ``<root>/multi30k`` (what ``data.datasets.load_multi30k`` reads),
    Multi30k-like sentence lengths, Zipf-like word frequencies, and a
    learnable mapping: source word i becomes target word i, and a quarter
    of the source words are followed by one of the remaining target-only
    words (so the target vocabulary is the larger one, as in en->de). The
    first sentences walk the whole vocabulary, so every type is in train.
    ``fixed_words`` makes every pair the same length on both sides."""
    import numpy as np

    rng = np.random.default_rng(seed)
    extra = size.trg_types - size.src_types
    ranks = np.arange(1, size.src_types + 1)
    zipf = (1.0 / ranks) / np.sum(1.0 / ranks)

    def sentences(n: int, walk: bool):
        cursor = 0
        for _ in range(n):
            if fixed_words:
                length = fixed_words
            else:
                length = int(np.clip(rng.normal(13, 4), 4, size.max_words))
            if walk and cursor < size.src_types:
                ids = (cursor + np.arange(length)) % size.src_types
                cursor += length
            else:
                ids = rng.choice(size.src_types, size=length, p=zipf)
            src, trg = [], []
            for i in ids:
                i = int(i)
                src.append(_word("e", i))
                trg.append(_word("d", i))
                if not fixed_words and i % 4 == 0:
                    trg.append(_word("d", size.src_types + i // 4 % extra))
            yield " ".join(src), " ".join(trg)

    out = os.path.join(root, "multi30k")
    os.makedirs(out, exist_ok=True)
    for split, n, walk in (("train", n_train, True), ("valid", n_valid, False)):
        pairs = list(sentences(n, walk))
        with open(os.path.join(out, f"{split}.en"), "w") as f:
            f.write("\n".join(s for s, _ in pairs) + "\n")
        with open(os.path.join(out, f"{split}.de"), "w") as f:
            f.write("\n".join(t for _, t in pairs) + "\n")


# -- observation helpers --------------------------------------------------------
class _LoopLog(logging.Handler):
    """Collects ``fit``'s log lines: ``epoch E step N | loss: X | T sec/K
    batches`` (X is the epoch's running mean; the lap T ends in a device
    sync, so it is honest wall time)."""

    LINE = re.compile(
        r"step (\d+) \| loss: ([-\w.]+).* \| ([\d.]+) sec/(\d+) batches"
    )

    def __init__(self):
        super().__init__(level=logging.INFO)
        self.points: list[tuple[int, float, float, int]] = []

    def emit(self, record):
        m = self.LINE.search(record.getMessage())
        if m:
            self.points.append(
                (int(m[1]), float(m[2]), float(m[3]), int(m[4]))
            )


@contextlib.contextmanager
def loop_log():
    logger = logging.getLogger("machine_learning_apache_spark_tpu.train.loop")
    handler = _LoopLog()
    logger.addHandler(handler)
    try:
        yield handler
    finally:
        logger.removeHandler(handler)


def window_losses(points) -> list[float]:
    """Per-window mean losses from the running epoch mean at each log
    point: mean_k = (n_k * m_k - n_{k-1} * m_{k-1}) / (n_k - n_{k-1})."""
    out, prev_n, prev_m = [], 0, 0.0
    for n, m, _, _ in points:
        out.append((n * m - prev_n * prev_m) / (n - prev_n))
        prev_n, prev_m = n, m
    return out


def dispatch_events(since: int) -> tuple[list[dict], int]:
    """The ``ops.attention_dispatch`` annotations recorded after event
    index ``since`` — which implementation each attention site compiled to
    (trace-time facts, one per site per traced program) — deduplicated."""
    from machine_learning_apache_spark_tpu import telemetry

    events = telemetry.get_log().snapshot()
    seen, out = set(), []
    for ev in events[since:]:
        if ev.kind == "annotation" and ev.name == "ops.attention_dispatch":
            a = ev.attrs
            key = (a["site"], a["impl"], a["reason"])
            if key not in seen:
                seen.add(key)
                out.append({"site": a["site"], "impl": a["impl"],
                            "reason": a["reason"]})
    return out, len(events)


def first_span_seconds(name: str, since: int) -> float | None:
    from machine_learning_apache_spark_tpu import telemetry

    for ev in telemetry.get_log().snapshot()[since:]:
        if ev.kind == "span_end" and ev.name == name:
            return round(ev.value, 2)
    return None


def mosaic_calls(compiled_text: str) -> list[str]:
    """The Mosaic custom calls of a compiled (per-device) HLO module, as
    ``result shape [op_name]`` strings."""
    calls = []
    for line in compiled_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        result = re.search(r"= \(?([a-z0-9]+\[[\d,]*\])", line)
        op = re.search(r'op_name="([^"]*)"', line)
        calls.append(f"{result[1] if result else '?'} [{op[1] if op else ''}]")
    return calls


# -- phases ---------------------------------------------------------------------
def run_train(size: Size, data_root: str) -> dict:
    """train_translator at the recipe defaults; returns the recipe's result
    dict (with ``state`` and ``translator``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from machine_learning_apache_spark_tpu import telemetry
    from machine_learning_apache_spark_tpu.ops.attention import kernel_mesh
    from machine_learning_apache_spark_tpu.recipes import train_translator
    from machine_learning_apache_spark_tpu.recipes._common import (
        default_compute_dtype,
        resolve_mesh,
    )
    from machine_learning_apache_spark_tpu.recipes.translation import (
        TranslationRecipe,
        make_translation_loss,
    )
    from machine_learning_apache_spark_tpu.train.loop import make_train_step

    n_dev = len(jax.devices())
    with phase("train") as info:
        mark = len(telemetry.get_log().snapshot())
        with loop_log() as log:
            out = train_translator(
                data_root=data_root, log_every=size.log_every,
                _return_state=True, _return_translator=True, **size.recipe,
            )
        cfg = out["translator"].model.cfg
        info["vocab"] = [out["src_vocab"], out["trg_vocab"]]
        if size is FULL:
            import bench

            for got, want in zip(
                info["vocab"], (bench.SRC_VOCAB, bench.TRG_VOCAB)
            ):
                require(abs(got - want) <= 0.1 * want,
                        f"vocabulary {got} within 10% of {want}")
        info["widths"] = dict(
            d_model=cfg.d_model, ffn=cfg.ffn_hidden, heads=cfg.num_heads,
            layers=cfg.num_layers, max_len=cfg.max_len,
        )
        info["dtype"] = jnp.dtype(cfg.dtype).name
        require(cfg.dtype == default_compute_dtype(),
                f"compute dtype {cfg.dtype} is the platform default")
        if jax.devices()[0].platform == "tpu":
            require(info["dtype"] == "bfloat16", "bfloat16 on the TPU")

        losses = window_losses(log.points)
        info["steps"] = log.points[-1][0]
        info["loss_first_window"] = round(losses[0], 4)
        info["loss_last_window"] = round(losses[-1], 4)
        info["eval_loss"] = round(float(out["test_loss"]), 4)
        require(all(np.isfinite(losses)) and np.isfinite(out["test_loss"]),
                f"finite losses {losses}")
        require(len(losses) >= 3 and losses[-1] < losses[0],
                f"loss fell: {losses[0]:.3f} -> {losses[-1]:.3f}")
        # Smoke observations, not metrics: the first step's span is trace +
        # compile + dispatch; later laps end in a device sync.
        info["first_step_seconds"] = first_span_seconds("train.step", mark)
        laps = sorted(t / k for _, _, t, k in log.points[1:])
        info["steady_seconds_per_step"] = round(laps[len(laps) // 2], 5)
        info["attention"], _ = dispatch_events(mark)

        # What the compiled train step contains — read from the program,
        # not from the dispatcher's belief. Same loss, same step builder,
        # same shapes, shardings and kernel mesh as fit used, so this
        # compile is a persistent-cache hit.
        mesh = resolve_mesh()
        info["mesh_devices"] = mesh.size if mesh is not None else 1
        require(info["mesh_devices"] == n_dev, "default mesh covers every chip")
        batch = TranslationRecipe(**size.recipe).batch_size * n_dev
        spec = jax.ShapeDtypeStruct((batch, cfg.max_len), jnp.int32)
        if mesh is not None:
            from machine_learning_apache_spark_tpu.parallel.mesh import (
                batch_sharding,
            )

            spec = jax.ShapeDtypeStruct(
                spec.shape, spec.dtype, sharding=batch_sharding(mesh)
            )
        step = make_train_step(make_translation_loss(out["translator"].model,
                                                     cfg.pad_id))
        t0 = time.perf_counter()
        with kernel_mesh(mesh):
            text = step.lower(
                out["state"], (spec, spec), jax.random.key(0)
            ).compile().as_text()
        info["step_recompile_seconds"] = round(time.perf_counter() - t0, 2)
        calls = mosaic_calls(text)
        info["mosaic_calls_in_step"] = len(calls)
        if jax.devices()[0].platform == "tpu":
            # max_len 200: every site is under the shape gate, so the step
            # is XLA's alone; the kernels phase is where Mosaic compiles.
            sites = [a for a in info["attention"] if a["site"] == "dot_product"]
            require(sites and not calls and all(
                a["impl"] == "xla_dense" and " scores < " in a["reason"]
                for a in sites),
                    "every attention site of the train step took the dense "
                    f"path by its shape, and the step holds no Mosaic call "
                    f"(sites {sites}, calls {calls[:3]})")
        if n_dev > 1:
            multichip_train_checks(info, out, text, mesh, batch, cfg)
    return out


def multichip_train_checks(info, out, text, mesh, batch, cfg) -> None:
    """Data parallelism is real: params on every chip, a batch in n shards
    of B/n rows, and nothing gathering it back together."""
    import jax
    import numpy as np

    from machine_learning_apache_spark_tpu.parallel.mesh import shard_batch

    n_dev = mesh.size
    leaves = jax.tree.leaves(out["state"].params)
    require(all(len(l.sharding.device_set) == n_dev
                and l.is_fully_replicated for l in leaves),
            f"params replicated on {n_dev} devices")
    probe = shard_batch(mesh, np.zeros((batch, cfg.max_len), np.int32))
    shards = [s.data.shape for s in probe.addressable_shards]
    info["batch_shards"] = [len(shards), list(shards[0])]
    require(len(shards) == n_dev
            and all(s == (batch // n_dev, cfg.max_len) for s in shards),
            f"batch in {n_dev} shards of {batch // n_dev} rows: {shards}")
    # An all-gather whose result leads with the global batch, or global
    # batch x heads, would be attention's operands being replicated.
    gathered = {int(m) for m in re.findall(
        r"= [a-z0-9]+\[(\d+),[\d,]*\][^=]* all-gather(?:-start)?\(", text)}
    info["all_gather_leading_dims"] = sorted(gathered)
    require(not gathered & {batch, batch * cfg.num_heads},
            f"no all-gather of the batch in the train step: {sorted(gathered)}")


def run_serve(size: Size, translator, data_root: str) -> None:
    import numpy as np

    from machine_learning_apache_spark_tpu import telemetry
    from machine_learning_apache_spark_tpu.data.datasets import load_multi30k

    with phase("serve") as info:
        mark = len(telemetry.get_log().snapshot())
        texts = [s for s, _ in load_multi30k(data_root, "valid")]
        texts = sorted(texts[: 4 * size.n_prompts], key=len)[:: 4]
        texts = texts[: size.n_prompts]
        info["prompt_words"] = [len(texts[0].split()), len(texts[-1].split())]
        t0 = time.perf_counter()
        with translator.serve(
            boundaries=size.boundaries, max_new_tokens=size.max_new_tokens,
        ) as eng:
            info["warmup_seconds"] = round(time.perf_counter() - t0, 2)
            info["page_size"] = eng.runtime.page_size
            results: list = [None] * len(texts)

            def client(k: int) -> None:
                reqs = [(i, eng.submit(texts[i]))
                        for i in range(k, len(texts), size.n_threads)]
                for i, req in reqs:
                    results[i] = req.result(timeout=600)

            t1 = time.perf_counter()
            threads = [threading.Thread(target=client, args=(k,))
                       for k in range(size.n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=900)
            require(not any(t.is_alive() for t in threads),
                    "every client thread finished")
            info["serve_seconds"] = round(time.perf_counter() - t1, 2)
            require(all(isinstance(r, str) for r in results),
                    f"every request completed: {results}")
            deadline = time.monotonic() + 10
            while eng.runtime.active_count() and time.monotonic() < deadline:
                time.sleep(0.05)
            stats = eng.runtime.stats()
            summary = eng.metrics.summary()
            info["ledger"] = eng.metrics.check_conservation()
            info["recompiles_after_warmup"] = eng.recompiles_after_warmup
            info["launches"] = summary.get("batches")
            launch = [ev.value for ev in telemetry.get_log().snapshot()[mark:]
                      if ev.kind == "span_end" and ev.name == "serving.batch"]
            if launch:
                info["seconds_per_launch_median"] = round(
                    sorted(launch)[len(launch) // 2], 5
                )
            info["engine_devices"] = sorted(
                d.id for d in eng.runtime.kv_mem.sharding.device_set
            )
            require(info["recompiles_after_warmup"] == 0,
                    "0 recompiles after warm-up")
            require(info["ledger"]["completed"] == len(texts),
                    f"ledger completed all: {info['ledger']}")
            for key in ("quarantined", "loop_restarts", "failed"):
                require(summary[key] == 0,
                        f"{key} == 0 (a contained device error would hide "
                        f"here): {summary}")
            require(stats["active_rows"] == 0
                    and stats["self_pages_in_use"] == 0,
                    f"pools drained: {stats}")
        # Against the one-shot decoder: token-identical is the invariant in
        # float32; in bfloat16 report the agreement and hold the repo's
        # int8-vs-fp32 bar. ``first_disagreements`` names where a sentence
        # first left the reference ([prompt, position]): everything after
        # it in that sentence is two decoders reading different prefixes.
        t2 = time.perf_counter()
        reference = translator(texts, max_new_tokens=size.max_new_tokens)
        info["one_shot_seconds"] = round(time.perf_counter() - t2, 2)
        same = total = 0
        flips = []
        for i, (got, want) in enumerate(zip(results, reference)):
            g, w = got.split(), want.split()
            same += sum(a == b for a, b in zip(g, w))
            total += max(len(g), len(w))
            shared = next(
                (j for j, (a, b) in enumerate(zip(g, w)) if a != b),
                min(len(g), len(w)),
            )
            if shared < max(len(g), len(w)):
                flips.append([i, shared])
        info["token_agreement"] = round(same / max(total, 1), 4)
        info["first_disagreements"] = flips
        info["identical_outputs"] = [
            int(np.sum([g == w for g, w in zip(results, reference)])),
            len(texts),
        ]
        require(info["token_agreement"] >= 0.99,
                f"token agreement with the one-shot decoder "
                f"{info['token_agreement']} >= 0.99 "
                f"(first disagreements {flips})")
        info["attention"], _ = dispatch_events(mark)


def run_kernels(size: Size) -> None:
    """Every Pallas kernel once, compiled (``interpret=False`` on the chip),
    at the smallest shape that passes its own dispatch gate, in the dtype
    the chip path uses, against the XLA path."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from machine_learning_apache_spark_tpu.ops.attention import (
        dot_product_attention,
        kernel_mesh,
        ragged_paged_attention,
    )
    from machine_learning_apache_spark_tpu.ops.gated_delta import (
        gated_delta_recurrent,
        gated_delta_rule,
    )
    from machine_learning_apache_spark_tpu.ops.pallas_attention import (
        _use_pallas_bwd,
        flash_attention,
    )
    from machine_learning_apache_spark_tpu.parallel.mesh import batch_sharding
    from machine_learning_apache_spark_tpu.recipes._common import resolve_mesh

    dtype = jnp.dtype(size.kernel_dtype)
    f32 = jnp.float32

    def rnd(shape, seed, dt=dtype):
        return jax.random.normal(jax.random.key(seed), shape, f32).astype(dt)

    def err(a, b) -> float:
        return float(jnp.max(jnp.abs(a.astype(f32) - b.astype(f32))))

    def flash_case(causal: bool, masked: bool, dt) -> dict:
        """Forward at the train shape, backward (dq, dk, dv) at a shape
        that takes the Pallas backward, against the XLA path."""
        tag = f"causal={int(causal)},kv_valid={int(masked)}"
        if dt != dtype:
            tag += f",{jnp.dtype(dt).name}"
        b, h, s, d = size.flash_fwd_shape
        q, k, v = (rnd((b, h, s, d), i, dt) for i in range(3))
        valid = (
            jnp.arange(s)[None, :] < jnp.array([[s], [s // 2]])
            if masked else None
        )
        out = {f"flash_fwd[{tag}]": err(
            flash_attention(q, k, v, causal=causal, kv_valid=valid,
                            interpret=size.interpret),
            dot_product_attention(q, k, v, causal=causal, kv_valid=valid,
                                  use_pallas=False),
        )}
        b, h, s, d = size.flash_bwd_shape
        require(_use_pallas_bwd(s, s), "backward shape takes the Pallas path")
        q, k, v, w = (rnd((b, h, s, d), i, dt) for i in range(4))
        valid = (jnp.arange(s)[None, :] < s - 100) if masked else None

        def grads(attend):
            return jax.jit(jax.grad(
                lambda q, k, v: jnp.sum(
                    attend(q, k, v).astype(f32) * w.astype(f32)
                ), argnums=(0, 1, 2),
            ))(q, k, v)

        got = grads(lambda q, k, v: flash_attention(
            q, k, v, causal=causal, kv_valid=valid, interpret=size.interpret))
        want = grads(lambda q, k, v: dot_product_attention(
            q, k, v, causal=causal, kv_valid=valid, use_pallas=False))
        for name, g, r in zip(("dq", "dk", "dv"), got, want):
            out[f"flash_bwd_{name}[{tag}]"] = err(g, r)
        return out

    def flash_per_shard(mesh) -> float:
        """More than one chip: the forward under ``kernel_mesh`` on a
        batch sharded as ``fit`` shards it. No train step of this script
        is long enough to reach the kernel, so this is where the per-shard
        launch meets the chip: each device's call works on B/n rows."""
        _, h, s, d = size.flash_fwd_shape
        b = 2 * mesh.size
        q, k, v = (
            jax.device_put(rnd((b, h, s, d), i), batch_sharding(mesh))
            for i in range(3)
        )
        attend = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=size.interpret))
        with kernel_mesh(mesh):
            got = attend(q, k, v)
            calls = mosaic_calls(attend.lower(q, k, v).compile().as_text())
        if not size.interpret:
            rows = {int(m) for c in calls
                    for m in re.findall(r"^[a-z0-9]+\[(\d+),", c)}
            require(rows == {b // mesh.size * h},
                    f"flash custom call runs on B/n*H = {b // mesh.size * h} "
                    f"rows per chip, found {sorted(rows)}")
        return err(got, dot_product_attention(q, k, v, causal=True,
                                              use_pallas=False))

    def scan_case(dt, shift=None) -> tuple[dict, dict, str]:
        """The chunked gated delta rule, forward and the five gradients,
        against its per-token recurrence (a length that is no multiple of
        the chunk): ``(largest error, the recurrence's largest value, the
        path the site took)``. float32 runs every product at
        ``Precision.HIGHEST``; bfloat16 is what the benchmark's cell runs.
        On the chip both take the Pallas chunk kernels (``pallas_chunk``),
        in a rehearsal the ``lax.scan`` path: the dispatch observes the
        backend, the smoke does not steer it. ``shift`` gives the keys a
        common component, as a positive activation leaves them (1.0: mean
        cosine 0.5, 4.0: 0.9), where the chunk's triangular system is
        ill-conditioned and a blocked inverse has to stay as exact as
        substitution was."""
        from machine_learning_apache_spark_tpu import telemetry

        b, t, hk, hv, dk, dv, chunk = size.scan_shape
        unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
        q = (unit(rnd((b, t, hk, dk), 0, f32)) * dk ** -0.5).astype(dt)
        k = rnd((b, t, hk, dk), 1, f32)
        k = unit(k if shift is None else jax.nn.silu(k + shift)).astype(dt)
        v, w = rnd((b, t, hv, dv), 2, dt), rnd((b, t, hv, dv), 3, f32)
        g = -0.1 * jax.nn.sigmoid(rnd((b, t, hv), 4, f32))
        beta = jax.nn.sigmoid(rnd((b, t, hv), 5, f32))

        def both(rule):
            def loss(*a):
                return jnp.sum(rule(*a)[0].astype(f32) * w)
            return jax.jit(lambda *a: (
                rule(*a)[0], jax.grad(loss, argnums=range(5))(*a)
            ))(q, k, v, g, beta)

        telemetry.get_log().clear()
        out, grads = both(lambda *a: gated_delta_rule(
            *a, chunk=chunk, site="chip_smoke"))
        took = sorted({
            f"{e.attrs['impl']} ({e.attrs['reason']})"
            for e in telemetry.get_log().snapshot()
            if e.name == "ops.gated_delta_dispatch"
        })
        want, want_grads = both(gated_delta_recurrent)
        tag = jnp.dtype(dt).name + ("" if shift is None else f",shift{shift:g}")
        found = {f"gated_delta_fwd[{tag}]": (out, want)}
        for name, a, r in zip(("dq", "dk", "dv", "dg", "dbeta"), grads, want_grads):
            found[f"gated_delta_bwd_{name}[{tag}]"] = (a, r)
        return (
            {k: err(a, r) for k, (a, r) in found.items()},
            {k: float(jnp.max(jnp.abs(r.astype(f32)))) for k, (_, r) in found.items()},
            "; ".join(took),
        )

    with phase("kernels") as info:
        results: dict = {}
        scan, _, took = scan_case(f32)
        info["gated_delta_path"] = {"float32": took}
        for shift in (1.0, 4.0):  # keys alike: mean cosine 0.5 and 0.9
            alike, _, _ = scan_case(f32, shift)
            scan.update(alike)
        info["gated_delta_max_abs_err_vs_recurrence"] = {
            k: round(v, 7) for k, v in scan.items()
        }
        bad = {k: v for k, v in scan.items() if not v <= 1e-3}
        require(not bad, f"chunked gated delta rule agrees with the "
                f"recurrence within 1e-3 in float32: {bad}")
        # The path the benchmark's cell takes: bfloat16 operands. A rounding
        # is 2^-8 of a value and a chunk holds a few of them, so the bar is
        # 3 % of the recurrence's own largest value (and of 1).
        scan, scale, took = scan_case(jnp.bfloat16)
        info["gated_delta_max_abs_err_vs_recurrence"].update(
            {k: round(v, 5) for k, v in scan.items()}
        )
        info["gated_delta_recurrence_max_abs"] = {
            k: round(v, 4) for k, v in scale.items()
        }
        info["gated_delta_path"]["bfloat16"] = took
        bad = {k: (v, scale[k]) for k, v in scan.items()
               if not v <= 0.03 * (1.0 + scale[k])}
        require(not bad, f"chunked gated delta rule agrees with the "
                f"recurrence within 3 % in bfloat16: {bad}")
        on_chip = not size.interpret
        for tag, path in info["gated_delta_path"].items():
            require(path.startswith("pallas_chunk") == on_chip,
                    f"gated delta site ({tag}) took "
                    f"{'the chunk kernels' if on_chip else 'the lax.scan path'}"
                    f": {path}")
        for causal in (False, True):
            for masked in (False, True):
                results.update(flash_case(causal, masked, dtype))
        # an explicit dtype="float32" run takes the same kernels
        results.update(flash_case(True, True, jnp.dtype(f32)))

        def paged(store, page_size):
            rows, pages_per_row, n_pages = 4, 4, 32
            heads, dh = size.paged_heads, 128
            q = rnd((rows, heads, dh), 0)
            kp = rnd((n_pages, page_size, heads * dh), 1, f32)
            vp = rnd((n_pages, page_size, heads * dh), 2, f32)
            ks = vs = None
            if store == "int8":
                ks = jnp.max(jnp.abs(kp), axis=-1) / 127.0
                vs = jnp.max(jnp.abs(vp), axis=-1) / 127.0
                kp = jnp.round(kp / ks[..., None]).astype(jnp.int8)
                vp = jnp.round(vp / vs[..., None]).astype(jnp.int8)
            else:
                kp, vp = kp.astype(dtype), vp.astype(dtype)
            table = jnp.asarray(
                np.arange(1, 1 + rows * pages_per_row)
                .reshape(rows, pages_per_row) % n_pages, jnp.int32)
            lengths = jnp.asarray(
                [0, 5, page_size, 2 * page_size + 3], jnp.int32)
            cur_k, cur_v = rnd((rows, heads * dh), 3), rnd((rows, heads * dh), 4)
            run = lambda use: jax.jit(lambda: ragged_paged_attention(
                q, kp, vp, table, lengths, k_scale=ks, v_scale=vs,
                cur_k=cur_k, cur_v=cur_v, use_pallas=use,
                interpret=size.interpret and use))()
            return err(run(True), run(False))

        mesh = resolve_mesh()
        if mesh is not None:
            results["flash_fwd[per shard]"] = flash_per_shard(mesh)

        results[f"ragged_paged[{size.kernel_dtype},page=8]"] = paged("model", 8)
        results[f"ragged_paged[{size.kernel_dtype},page=16]"] = paged("model", 16)
        results["ragged_paged[int8+scales,page=32]"] = paged("int8", 32)
        info["mode"] = "interpret" if size.interpret else "compiled (Mosaic)"
        info["tolerance"] = size.tolerance
        info["max_abs_err_vs_xla"] = {k: round(v, 6) for k, v in results.items()}
        bad = {k: v for k, v in results.items() if not v <= size.tolerance}
        require(not bad, f"kernels agree with the XLA path within "
                f"{size.tolerance}: {bad}")


def run_zero1(size: Size, out_dir: str, seed: int) -> None:
    """More than one chip: train_translator through fit(dp_mode="zero1")
    against the replicated run, in float32, a few steps. Pairs are all one
    length and dropout is off, so the two modes compute the same mean (a
    per-shard pad-masked mean and per-shard dropout keys are where they
    legitimately differ)."""
    import jax
    import numpy as np

    from machine_learning_apache_spark_tpu.parallel import zero
    from machine_learning_apache_spark_tpu.parallel.data_parallel import (
        params_fingerprint,
    )
    from machine_learning_apache_spark_tpu.recipes import train_translator

    n_dev = len(jax.devices())
    with phase("zero1") as info:
        root = os.path.join(out_dir, "zero1")
        n = size.zero_pairs_per_chip * n_dev
        write_corpus(root, size, seed + 1, n_train=n, n_valid=n // 8,
                     fixed_words=12)
        common = dict(
            data_root=root, dtype="float32", dropout=0.0, log_every=0,
            _return_state=True, **size.recipe,
        )
        runs = {}
        for mode in ("replicated", "zero1"):
            os.environ[zero.ENV_DP_MODE] = mode
            try:
                runs[mode] = train_translator(**common)
            finally:
                del os.environ[zero.ENV_DP_MODE]
        z, r = runs["zero1"], runs["replicated"]
        require(isinstance(z["state"], zero.Zero1State), "fit took dp_mode=zero1")
        info["steps"] = int(z["state"].step)
        info["final_loss"] = [round(r["final_loss"], 5), round(z["final_loss"], 5)]
        require(np.isfinite(z["final_loss"]), "finite ZeRO-1 loss")
        logical = zero.opt_state_bytes(z["state"].opt_state)
        per_chip = zero.opt_state_bytes_per_chip(z["state"])
        info["opt_bytes_per_chip_over_logical"] = round(per_chip / logical, 4)
        require(per_chip / logical <= 1.0 / n_dev + 0.01,
                f"optimizer bytes per chip ~ 1/{n_dev} of logical: "
                f"{per_chip}/{logical}")
        fz, fr = params_fingerprint(z["state"]), params_fingerprint(r["state"])
        info["fingerprint"] = [fr, fz]
        info["fingerprint_identical"] = fz == fr
        info["fingerprint_rel_diff"] = abs(fz - fr) / abs(fr)
        require(info["fingerprint_rel_diff"] <= 1e-4,
                f"ZeRO-1 reaches the replicated run's params: {fr} vs {fz}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=os.path.join(HERE, "chiprun_out", "chip_smoke"))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy width, kernels in interpret mode, any platform")
    ns = ap.parse_args(argv)
    size = TOY if ns.rehearse else FULL

    import jax

    # importing the package places the compile cache (utils.compilation_cache)
    from machine_learning_apache_spark_tpu import native, telemetry
    from machine_learning_apache_spark_tpu.utils.logging import (
        route_logging_to_stderr,
    )

    route_logging_to_stderr()  # stdout ends in the result line
    device = jax.devices()[0]
    cache_dir = jax.config.jax_compilation_cache_dir
    say(f"jax {jax.__version__} platform={device.platform} "
        f"device_kind={device.device_kind!r} devices={len(jax.devices())} "
        f"compile_cache={cache_dir} size={size.name}")
    if device.platform != "tpu" and not ns.rehearse:
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{device.platform!r} ({device.device_kind!r})", file=sys.stderr)
        return 1
    require(telemetry.enabled(), "telemetry on (the smoke reads its spans)")

    def cache_entries() -> int:
        return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0

    cached_before = cache_entries()
    os.makedirs(ns.out, exist_ok=True)
    t_start = time.perf_counter()

    with phase("data") as info:
        write_corpus(ns.out, size, ns.seed, n_train=size.train_pairs,
                     n_valid=size.valid_pairs)
        info["pairs"] = [size.train_pairs, size.valid_pairs]
        info["word_types"] = [size.src_types, size.trg_types]
    out = run_train(size, ns.out)
    run_serve(size, out["translator"], ns.out)
    run_kernels(size)
    if len(jax.devices()) > 1:
        run_zero1(size, ns.out, ns.seed)

    require(jax.config.jax_compilation_cache_dir == cache_dir,
            "no phase moved the compile cache")
    # Reaching this line means every phase passed: a failed one raised.
    report = {
        "ok": True,
        "size": size.name,
        "device": {
            "platform": device.platform,
            "kind": device.device_kind,
            "count": len(jax.devices()),
        },
        "jax": jax.__version__,
        "seed": ns.seed,
        "native": native.status(),
        "compile_cache": {
            "dir": cache_dir,
            "from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
            "entries_before": cached_before,
            "entries_written": cache_entries() - cached_before,
        },
        "total_seconds": round(time.perf_counter() - t_start, 1),
        "phases": PHASES,
    }
    with open(os.path.join(ns.out, "report.json"), "w") as f:
        json.dump(report, f, indent=1, default=str)
    print(json.dumps(report, default=str), flush=True)
    print(json.dumps({"ok": True, "device": report["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
