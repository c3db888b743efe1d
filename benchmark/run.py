"""``python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell, in one process.

Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for. Otherwise the last line of standard output is one
JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and last ``compared``);
everything else goes to standard error.

Builder's conveniences, outside the contract's command: ``--seeds a,b,c``
runs several seeds in one process after one start-up (each seed's result
goes to standard error, the last line sums them up), ``--control all`` (or
a list of stand-ins, ``control_int8,fault_half_batch``) also reads the
control and the planted faults and puts each through ``compare.decide``,
``--rehearse 1`` runs toy widths on whatever backend there is (never a
measurement: the line names its platform).
"""

from __future__ import annotations

import time

_T0 = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

from benchmark import compare, manifest as manifest_mod  # noqa: E402


def _process_age_s() -> float:
    """Seconds since this process started, by the kernel's clock."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(uptime - ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return 0.0


_AGE_AT_T0 = _process_age_s()


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Run:
    """One run's inputs, and what its kind's driver fills in."""

    def __init__(self, *, cell, cell_file, cfg, mix, seed, seconds, trace,
                 out_dir, control=()):
        self.cell, self.cell_file, self.cfg, self.mix = cell, cell_file, cfg, mix
        self.seed, self.seconds, self.trace = seed, float(seconds), bool(trace)
        self.out_dir, self.control = out_dir, tuple(control)
        self.chips = int(cell["chips"])
        self.setup: dict[str, float] = {}
        self.setup_s: float | None = None
        self.window_s: float | None = None
        self.e2e: dict[str, float] = {}
        self.attempted = self.failed = 0
        self.counters: dict = {}
        self.events: list = []
        self.setup_events: list = []
        self.compared: list = []
        self.control_report: dict | None = None
        self.trace_dir: str | None = None
        self.trace_data = None
        self.memory_peak_bytes: int | None = None
        self._tracer: list[threading.Timer] = []
        self._trace_lock = threading.Lock()
        self._tracing = False
        self.t0, self.age_at_t0 = _T0, _AGE_AT_T0

    def note(self, msg: str) -> None:
        log(f"[{self.cell['name']} seed {self.seed}] {msg}")

    @contextlib.contextmanager
    def phase(self, name: str):
        t = time.monotonic()
        yield
        self.setup[name + "_s"] = time.monotonic() - t

    def mark_window_start(self, t: float) -> None:
        """Set-up is everything from the process's start to here."""
        self.setup_s = self.age_at_t0 + (t - self.t0)

    def read_memory(self) -> None:
        """The peak on the fullest chip. The allocator counts live arrays
        (``peak_bytes_in_use``) and the scratch a running program reserves
        (``peak_bytes_reserved``) apart; a step's activations are in the
        second, so the peak is their sum (PERF.md, section 2)."""
        import jax

        peak, parts = 0, None
        for d in jax.local_devices():
            stats = d.memory_stats() or {}
            total = stats.get("peak_bytes_in_use", 0) + stats.get(
                "peak_bytes_reserved", 0
            )
            if total >= peak:
                peak, parts = total, stats
        self.memory_peak_bytes = int(peak)
        if parts:
            self.note(
                "memory on the fullest chip: peak_bytes_in_use "
                f"{parts.get('peak_bytes_in_use')}, peak_bytes_reserved "
                f"{parts.get('peak_bytes_reserved')}, bytes_limit "
                f"{parts.get('bytes_limit')}"
            )

    # -- tracing a part of a window from the harness (serving cells) ---------
    def start_trace(self, *, after: float, seconds: float) -> str:
        import jax

        trace_dir = os.path.join(self.out_dir, "trace")

        def begin():
            with self._trace_lock:
                jax.profiler.start_trace(trace_dir)
                self._tracing = True

        self._tracer = [
            threading.Timer(after, begin),
            threading.Timer(after + seconds, self.stop_trace),
        ]
        for t in self._tracer:
            t.daemon = True
            t.start()
        return trace_dir

    def stop_trace(self) -> None:
        import jax

        with self._trace_lock:
            if self._tracing:
                jax.profiler.stop_trace()
                self._tracing = False
        for t in self._tracer:
            t.cancel()


class GcWatch:
    """Seconds the collector ran, its longest pause and its collections by
    generation between ``start`` and ``stop`` (``gc.callbacks``)."""

    def __init__(self):
        self.total = self.longest = 0.0
        self.by_generation = [0, 0, 0]
        self._t = 0.0

    def _callback(self, phase, info):
        if phase == "start":
            self._t = time.monotonic()
        else:
            pause = time.monotonic() - self._t
            self.total += pause
            self.longest = max(self.longest, pause)
            self.by_generation[info["generation"]] += 1

    def start(self):
        import gc

        gc.callbacks.append(self._callback)

    def stop(self) -> str:
        import gc

        gc.callbacks.remove(self._callback)
        return (f"gc in window {self.total * 1e3:.1f} ms over "
                f"{self.by_generation} collections by generation, longest "
                f"pause {self.longest * 1e3:.1f} ms")


def _device_info(run: Run) -> dict:
    import jax

    d = jax.local_devices()[0]
    return {
        "platform": d.platform,
        "kind": d.device_kind,
        "count": jax.device_count(),
        "memory_peak_bytes": run.memory_peak_bytes or 0,
    }


def _require_chips(chips: int) -> None:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"benchmark: JAX found platform {devices[0].platform!r}, not a "
            "TPU; no result")
        raise SystemExit(3)
    if len(devices) < chips:
        log(f"benchmark: the cell asks for {chips} chips, JAX found "
            f"{len(devices)}; no result")
        raise SystemExit(3)


def _program_files() -> set[str]:
    root = os.path.join(manifest_mod.ROOT, "machine_learning_apache_spark_tpu")
    return {
        f for _, _, files in os.walk(root) for f in files if f.endswith(".py")
    }


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, control: tuple = (),
             rehearse: bool = False, manifest: dict | None = None,
             config_overrides: dict | None = None,
             mix_overrides: dict | None = None,
             cell_overrides: dict | None = None,
             out_dir: str | None = None) -> dict:
    """One run of cell ``name``; returns the result object. Tests call this
    with ``require_chip=False`` and ``rehearse=True`` to drive everything
    but the look for a chip, and give each run an ``out_dir`` of its own
    (two processes rehearsing one cell would clear each other's trace)."""
    manifest = manifest or manifest_mod.load_manifest()
    cell = manifest_mod.find_cell(manifest, name)
    cell_file = manifest_mod.load_cell_file(name)
    cfg = manifest_mod.load_config(manifest, cell["config"])
    mix = manifest_mod.load_traffic(cell["traffic"])
    if rehearse:
        from benchmark import rehearse as rehearse_mod

        cfg, mix, cell_file = rehearse_mod.shrink(cfg, mix, cell_file)
    for target, overrides in (
        (cfg, config_overrides), (mix, mix_overrides), (cell_file, cell_overrides)
    ):
        for key, value in (overrides or {}).items():
            if isinstance(value, dict) and isinstance(target.get(key), dict):
                target[key].update(value)  # a group: only the keys named
            else:
                target[key] = value
    t_import = time.monotonic()
    import jax

    import machine_learning_apache_spark_tpu  # noqa: F401  (places the compile cache)

    if require_chip:
        _require_chips(int(cell["chips"]))
    out_dir = out_dir or os.path.join(manifest_mod.ROOT, "benchmark_out", name)
    shutil.rmtree(os.path.join(out_dir, "trace"), ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)
    run = Run(cell=cell, cell_file=cell_file, cfg=cfg, mix=mix, seed=seed,
              seconds=seconds, trace=trace, out_dir=out_dir, control=control)
    if rehearse:
        run.chips = min(run.chips, jax.device_count())
    run.setup["imports_and_backend_s"] = time.monotonic() - t_import
    d = jax.local_devices()[0]
    run.note(f"device: {d.platform} {d.device_kind} x{jax.device_count()}, "
             f"compile cache at {jax.config.jax_compilation_cache_dir}")

    manifest_mod.load_kind(cell_file["kind"]).run(run)

    run.note("set-up: " + json.dumps(
        {"setup_s": run.setup_s, "before_python_s": run.age_at_t0,
         **{k: round(v, 3) for k, v in run.setup.items()}}
    ))
    _report_dispatch(run)
    metrics = {"setup_s": {"value": run.setup_s, "unit": "s"}}
    device = _device_info(run)
    result = {
        "correct": compare.decide(run.compared),
        "attempted": run.attempted, "failed": run.failed,
        "metrics": metrics, "device": device,
    }
    if not trace:
        for m in manifest_mod.metrics_for(manifest, name, "end_to_end"):
            if m["name"] in run.e2e:
                metrics[m["name"]] = {"value": run.e2e[m["name"]], "unit": m["unit"]}
    else:
        metrics.clear()
        _reduce_trace(run, result)
        for m in manifest_mod.metrics_for(manifest, name, "per_layer"):
            value = manifest_mod.load_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    if run.control_report is not None:
        run.note("control and faults: " + json.dumps(run.control_report))
        result["control"] = run.control_report
    result["compared"] = compare.report(run.compared)
    return result


def _report_dispatch(run: Run) -> None:
    seen = {}
    for e in run.setup_events + run.events:
        if e.name == "ops.attention_dispatch" and e.attrs:
            key = (e.attrs.get("site"), e.attrs.get("impl"), e.attrs.get("reason"))
            seen[key] = seen.get(key, 0) + 1
    for (site, impl, reason), n in seen.items():
        run.note(f"attention site {site}: {impl} ({reason}) x{n}")


def _reduce_trace(run: Run, result: dict) -> None:
    from benchmark import trace_reduce

    path = trace_reduce.find_xplane(run.trace_dir) if run.trace_dir else None
    if path is None:
        run.note("no trace was written")
        return
    t = time.monotonic()
    run.trace_data = trace_reduce.load(path)
    bw = trace_reduce.busy_and_window(run.trace_data)
    if bw is not None:
        result["device"]["busy_s"], result["device"]["window_s"] = bw
    result["breakdown"] = {
        "device_ops": trace_reduce.top_device_ops(run.trace_data),
        "idle_gaps": trace_reduce.idle_gaps(
            run.trace_data, program_files=_program_files()
        ),
    }
    run.note(f"trace {os.path.getsize(path)} bytes read in "
             f"{time.monotonic() - t:.1f} s; idle share "
             f"{1 - bw[0] / bw[1] if bw else float('nan'):.4f}")
    shutil.rmtree(run.trace_dir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control", default="",
                    help="builder: 'all' or a list of stand-ins to read")
    ap.add_argument("--rehearse", type=int, default=0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s] or [args.seed]
    results = []
    for seed in seeds:
        results.append(run_cell(
            args.workload, seed, args.seconds, bool(args.trace),
            require_chip=not args.rehearse,
            control=tuple(c for c in args.control.split(",") if c),
            rehearse=bool(args.rehearse),
        ))
        if len(seeds) > 1:
            log("result " + json.dumps(results[-1]))
    if len(seeds) > 1:
        final = {"seeds": seeds, "correct": [r["correct"] for r in results],
                 "compared": [r["compared"] for r in results]}
    else:
        final = results[0]
    sys.stderr.flush()
    print(json.dumps(final), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
