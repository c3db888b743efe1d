"""Run every example end to end and report rc per example.

`python tools/examples_sweep.py [--platform cpu|default] [--timeout S]`

Each example runs in its own subprocess, so this parent never holds a
device. `--platform cpu` (the default) runs them on 8 virtual CPU devices;
`--platform default` leaves the platform to JAX (on a TPU host: the chip —
run that through the chip tool).
"""

import argparse
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

EXAMPLES = [
    "mllib_multilayer_perceptron_classifier",
    "multilayer_perceptron",
    "lstm",
    "cnn",
    "machine_translator",
    "distributed_lstm",
    "advanced_translator",
    "high_throughput_cnn",
]

_CPU_ENV = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip(),
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", choices=["cpu", "default"], default="cpu")
    ap.add_argument("--timeout", type=float, default=3600.0)
    ap.add_argument("examples", nargs="*", default=None)
    ns = ap.parse_args()

    failures = 0
    for name in ns.examples or EXAMPLES:
        cmd = [sys.executable, f"examples/{name}.py"]
        env = dict(os.environ)
        if ns.platform == "cpu":
            env.update(_CPU_ENV)
        # high_throughput_cnn's comparison doubles the wall time; a smaller
        # K keeps the CPU sweep within budget (the knob targets TPUs).
        if name == "high_throughput_cnn" and ns.platform == "cpu":
            cmd.append("8")
        print(f"=== {name} ===", flush=True)
        try:
            rc = subprocess.run(
                cmd, cwd=REPO, env=env, timeout=ns.timeout
            ).returncode
        except subprocess.TimeoutExpired:
            rc = 124
        print(f"=== {name} rc={rc} ===", flush=True)
        failures += rc != 0
    print(f"examples sweep: {len(ns.examples or EXAMPLES) - failures}/"
          f"{len(ns.examples or EXAMPLES)} rc=0")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
