"""The Gated DeltaNet chunk kernels' ``head_block``, swept on the chip: device
ms a call of ``gdn_chunk_fwd`` (the primal, and the saving forward a
differentiated call runs) and ``gdn_chunk_bwd`` at one site, for each
``head_block`` given and for the ``lax.scan`` path, read from a profiler
trace by the kernels' names. The table behind
``ops.gated_delta._choose_head_block``'s rule (PERF.md section 6, PR 29).

    chiprun -- python tools/gdn_chunk_sweep.py \
        [--shape 4,4096,32,128] [--key-heads 16] [--dtype bfloat16] \
        [--chunk 64] [--cases chosen,1,2,4,8,16,scan]

``chosen`` leaves ``head_block`` to the chooser, ``scan`` sends the site down
the ``lax.scan`` path. A case is two jitted modules, the forward alone (the
primal kernel) and value-and-gradients (the saving forward and the backward
kernel); ``module_ms`` is the whole module, in-chunk preparation included, so
the kernel cases' and the scan case's differ by what the kernels replace.
One JSON line a case; the table also lands in ``chiprun_out/gdn_chunk_sweep/``.

``--part prepare`` times the in-chunk inverse alone instead (PERF.md
section 6, PR 32): ``ops.gated_delta._solve_unit_lower`` on the ``a`` and
``rhs`` the op forms at the site, as two modules, the forward (build of
``(I + a)^-1`` and its application, rounded to the operands' dtype) and
value-and-gradients (the forward and the VJP's products). It prints each
module's device ms a call, its five longest operations, where the inverse was
built (``gdn_inverse`` at a site that takes the kernels; ``--cases xla``
reads the plain-JAX form there instead), and how far the
first ``--check-chunks`` chunks' values and cotangents lie from a float64
solve on the host, over the largest entry of that solve; ``--key-shift``
gives the keys a common component as the tests do (1.0: mean cosine 0.5,
4.0: 0.9).
Chip-only, like ``tools/flash_tile_sweep.py``; ``--rehearse 1`` interprets
the kernels on the CPU at whatever (small) shape is given and reports no
time.
"""

import argparse
import json
import os
import statistics
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

KERNELS = ("gdn_chunk_fwd", "gdn_chunk_bwd")
REPS = 3  # traced calls a module; the median is reported
DEFAULT_CASES = "chosen,1,2,4,8,16,scan"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--shape", default="4,4096,32,128",
                    help="batch, length, value heads, dk = dv")
    ap.add_argument("--key-heads", type=int, default=16)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--chunk", type=int, default=64)
    ap.add_argument("--cases", default=DEFAULT_CASES)
    ap.add_argument("--rehearse", type=int, default=0)
    ap.add_argument("--part", default="scan", choices=("scan", "prepare"))
    ap.add_argument("--key-shift", type=float, default=0.0)
    ap.add_argument("--check-chunks", type=int, default=64)
    args = ap.parse_args(argv)

    if args.rehearse:
        import jax
    else:
        import bench

        jax = bench.init_chip()
    import jax.numpy as jnp

    from benchmark import readers, trace_reduce
    from machine_learning_apache_spark_tpu import telemetry
    from machine_learning_apache_spark_tpu.ops import gated_delta

    b, t, h, d = map(int, args.shape.split(","))
    dtype = jnp.dtype(args.dtype)
    f32 = jnp.float32

    def rnd(shape, seed, dt=f32):
        return jax.random.normal(jax.random.key(seed), shape, f32).astype(dt)

    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = (unit(rnd((b, t, args.key_heads, d), 0)) * d ** -0.5).astype(dtype)
    k = rnd((b, t, args.key_heads, d), 1)
    if args.key_shift:  # keys as alike as a positive activation leaves them
        k = jax.nn.silu(k + args.key_shift)
    k = unit(k).astype(dtype)
    v, w = rnd((b, t, h, d), 2, dtype), rnd((b, t, h, d), 3, dtype)
    g = -0.1 * jax.nn.sigmoid(rnd((b, t, h), 4))
    beta = jax.nn.sigmoid(rnd((b, t, h), 5))
    operands = (q, k, v, g, beta)
    weighted = (w, *operands)  # the loss's weights ride as an argument
    if args.part == "prepare":
        return prepare_part(args, jax, operands, w)

    # The sweep steers the dispatch from outside, as a test would: the
    # program has no option for either.
    observed = gated_delta._kernel_refusal, gated_delta._choose_head_block

    def steer(case: str) -> None:
        gated_delta._kernel_refusal, gated_delta._choose_head_block = observed
        if case == "scan":
            gated_delta._kernel_refusal = lambda *a: "the sweep's scan case"
        elif args.rehearse:
            gated_delta._kernel_refusal = lambda *a: None
        if case not in ("scan", "chosen"):
            gated_delta._choose_head_block = lambda *a: int(case)

    def modules(case: str):
        def forward(*a):
            return gated_delta.gated_delta_rule(*a, chunk=args.chunk)[0]

        def loss(w, *a):
            return jnp.sum(forward(*a).astype(f32) * w.astype(f32))

        # The modules' names in the trace tell the cases apart.
        forward.__name__ = f"sweep_{case}_fwd"
        loss.__name__ = f"sweep_{case}_grad"
        return {
            "fwd": jax.jit(forward),
            "grad": jax.jit(jax.value_and_grad(loss, argnums=range(1, 6))),
        }

    chosen = observed[1](b * h, args.chunk, d, d, dtype.itemsize)
    rows, fns, want = [], {}, None
    for case in args.cases.split(","):
        row = dict(shape=[b, t, h, d], key_heads=args.key_heads,
                   dtype=dtype.name, chunk=args.chunk, case=case)
        if case == str(chosen) and "chosen" in fns:
            # the same program: the compile cache would hand back the
            # chosen case's executable, under its name
            rows.append(dict(row, same_as="chosen"))
            continue
        telemetry.get_log().clear()
        steer(case)
        try:
            fns[case] = modules(case)
            out = jax.block_until_ready(fns[case]["fwd"](*operands))
            got = jax.block_until_ready(fns[case]["grad"](*weighted))
        except Exception as e:  # a block Mosaic refuses is a row, not the end
            row["error"] = str(e)[:300]
            fns.pop(case, None)
            rows.append(row)
            continue
        row["dispatch"] = sorted({
            f"{e.attrs['impl']} ({e.attrs['reason']})"
            for e in telemetry.get_log().snapshot()
            if e.name == "ops.gated_delta_dispatch"
        })
        found = [out.astype(f32)] + [x.astype(f32) for x in got[1]]
        if want is None:
            want = found  # the first case is the one the others are held to
        row["gap_to_first"] = {
            name: float(jnp.max(jnp.abs(a - r)))
            for name, a, r in zip(("o", "dq", "dk", "dv", "dg", "dbeta"), found, want)
        }
        rows.append(row)
    steer("chosen")

    if not args.rehearse and fns:
        with tempfile.TemporaryDirectory() as trace_dir:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # device events are all it reads
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            for case in fns.values():
                for which, fn in case.items():
                    for _ in range(REPS):
                        out = fn(*(weighted if which == "grad" else operands))
                    jax.block_until_ready(out)
            jax.profiler.stop_trace()
            trace = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
        for row in rows:
            if "error" in row or "same_as" in row:
                continue
            for which in ("fwd", "grad"):
                runs = trace_reduce.module_runs(
                    trace, rf"^jit_sweep_{row['case']}_{which}\b"
                )
                row[f"{which}_module_ms"] = statistics.median(
                    e.dur * 1e3 for spans in runs.values() for e in spans
                ) if any(runs.values()) else None
                for name in KERNELS:
                    calls = trace_reduce.ops_matching(
                        trace, rf"^%?{name}[.\d]* = "
                    )
                    durs = [
                        e.dur * 1e3
                        for chip, spans in runs.items()
                        for e in readers._inside(calls.get(chip, []), spans)
                    ]
                    if durs:
                        row[f"{which}_{name}_ms"] = statistics.median(durs)

    os.makedirs("chiprun_out/gdn_chunk_sweep", exist_ok=True)
    name = f"{b}x{t}x{h}x{d}_{dtype.name}.jsonl"
    with open(os.path.join("chiprun_out/gdn_chunk_sweep", name), "w") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")
            # the dispatch records stay in the file: stdout is capped
            row.pop("dispatch", None)
            print(json.dumps(row), flush=True)
    return 0


def prepare_part(args, jax, operands, w) -> int:
    """The ``--part prepare`` reading (module docstring)."""
    import jax.numpy as jnp
    import numpy as np

    from benchmark import trace_reduce
    from machine_learning_apache_spark_tpu.ops import gated_delta

    q, k, v, g, beta = operands
    b, t, h, d = v.shape
    dtype, f32, c = v.dtype, jnp.float32, args.chunk
    n = t // c

    def form(k, v, g, beta):
        """``a`` and ``rhs`` as ``gated_delta_rule`` forms them."""
        chunks = lambda x: jnp.moveaxis(  # noqa: E731
            x.reshape(b, n, c, *x.shape[2:]), 3, 1
        )
        k = chunks(jnp.repeat(k, h // k.shape[2], axis=2))
        v, g, beta = chunks(v), chunks(g), chunks(beta)
        big_g = jnp.cumsum(g, axis=-1)
        strict = jnp.tril(jnp.ones((c, c), bool), -1)
        diff = big_g[..., :, None] - big_g[..., None, :]
        kk = jnp.einsum("bhnid,bhnjd->bhnij", k, k, preferred_element_type=f32)
        a = jnp.where(
            strict, kk * jnp.exp(jnp.where(strict, diff, 0.0))
            * beta[..., :, None], 0.0,
        )
        rhs = jnp.concatenate(
            [v.astype(f32), k.astype(f32) * jnp.exp(big_g)[..., None]], -1
        ) * beta[..., None]
        return a, rhs

    a, rhs = jax.jit(form)(k, v, g, beta)
    weights = jnp.concatenate([w, w], -1).reshape(b, n, c, h, 2 * d)
    weights = jnp.moveaxis(weights, 3, 1).astype(f32)

    refusal = None if args.rehearse else gated_delta._kernel_refusal(dtype, c, d, d)
    place = "xla" if args.cases == "xla" else gated_delta._inverse_place(refusal, c)

    def sweep_prepare_fwd(a, rhs):
        return gated_delta._solve_unit_lower(a, rhs, place).astype(dtype)

    def sweep_prepare_grad(a, rhs, weights):
        return jnp.sum(sweep_prepare_fwd(a, rhs).astype(f32) * weights)

    fns = {
        "fwd": (jax.jit(sweep_prepare_fwd), (a, rhs)),
        "grad": (
            jax.jit(jax.value_and_grad(sweep_prepare_grad, argnums=(0, 1))),
            (a, rhs, weights),
        ),
    }
    solved = jax.block_until_ready(fns["fwd"][0](a, rhs))
    _, (d_a, d_rhs) = jax.block_until_ready(fns["grad"][0](a, rhs, weights))

    # float64 on the host, over the first chunks: the solve, and the
    # cotangents of sum(solved * weights) with solved left unrounded
    few = lambda x: np.asarray(  # noqa: E731
        x.reshape(-1, *x.shape[3:])[: args.check_chunks], np.float64
    )
    a64, rhs64, w64 = few(a), few(rhs), few(weights)
    inverse = np.linalg.inv(np.eye(c) + a64)
    want = inverse @ rhs64
    want_d_rhs = np.swapaxes(inverse, -1, -2) @ w64
    want_d_a = -np.tril(want_d_rhs @ np.swapaxes(want, -1, -2), -1)
    gap = lambda got, ref: float(  # noqa: E731
        np.max(np.abs(few(got.astype(f32)) - ref)) / np.max(np.abs(ref))
    )
    kh = np.asarray(k[0, :c, 0], np.float64)
    row = dict(
        part="prepare", shape=[b, t, h, d], dtype=dtype.name, chunk=c,
        key_shift=args.key_shift, inverse=gated_delta._inverse_form(c, place),
        mean_key_cosine=float(np.mean(kh @ kh.T)),
        gap_to_float64=dict(
            solved=gap(solved, want), d_a=gap(d_a, want_d_a),
            d_rhs=gap(d_rhs, want_d_rhs),
        ),
        solved_rounding=float(jnp.finfo(dtype).eps) / 2,
    )
    if not args.rehearse:
        with tempfile.TemporaryDirectory() as trace_dir:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            for fn, inputs in fns.values():
                for _ in range(REPS):
                    out = fn(*inputs)
                jax.block_until_ready(out)
            jax.profiler.stop_trace()
            trace = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
        for which in fns:
            runs = [
                e for spans in trace_reduce.module_runs(
                    trace, rf"^jit_sweep_prepare_{which}\b"
                ).values() for e in spans
            ]
            row[f"{which}_module_ms"] = statistics.median(
                e.dur * 1e3 for e in runs
            )
            inside: dict[str, float] = {}
            for events in trace.ops.values():
                for e in events:
                    if any(m.start <= e.start < m.end for m in runs):
                        name = trace_reduce.op_short_name(e.name)
                        inside[name] = inside.get(name, 0.0) + e.dur * 1e3
            row[f"{which}_longest_ops_ms"] = [
                [name, ms / len(runs)] for name, ms in
                sorted(inside.items(), key=lambda kv: -kv[1])[:5]
            ]
    os.makedirs("chiprun_out/gdn_chunk_sweep", exist_ok=True)
    name = f"prepare_{b}x{t}x{h}x{d}_{dtype.name}_shift{args.key_shift:g}.json"
    with open(os.path.join("chiprun_out/gdn_chunk_sweep", name), "w") as f:
        json.dump(row, f)
    print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
