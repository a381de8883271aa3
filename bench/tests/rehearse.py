"""Run ``bench/run.py`` (or the readings tool) in a child process on the CPU
at the tiny sizes, optionally with a fault planted first, and return its
exit code, standard output lines and the parsed last line."""

from __future__ import annotations

import json
import os
import subprocess
import sys

from bench.lib.harness import ROOT

TESTS = os.path.dirname(os.path.abspath(__file__))


def run(args: list[str], tmp_path, *, fault: str | None = None,
        devices: int = 1, tool: str = "run.py", timeout: float = 600,
        root: str = ROOT):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache")}
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    script = os.path.join(root, "bench", *tool.split("/"))
    code = (
        "import runpy, sys\n"
        f"sys.path[:0] = [{TESTS!r}, {root!r}, {os.path.join(root, 'src')!r}]\n"
        + (f"import faults; faults.plant({fault!r})\n" if fault else "")
        + f"sys.argv = [{script!r}] + {args!r}\n"
        f"runpy.run_path({script!r}, run_name='__main__')\n")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=root,
                          capture_output=True, text=True, timeout=timeout)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    last = json.loads(lines[-1]) if lines else None
    return proc.returncode, lines, last, proc.stderr


def cell_args(workload: str, seed: int = 2**31 + 12345, seconds: float = 3,
              trace: int = 0) -> list[str]:
    return ["--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace), "--tiny"]
