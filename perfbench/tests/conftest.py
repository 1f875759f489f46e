"""The benchmark's own tests: ``python -m pytest perfbench/tests -q``.

They are not part of the repo's tier-1 command.  Everything that needs the
program runs ``perfbench/run.py --rehearse-cpu`` in a child process, so this
process never imports JAX or the program.
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(PERFBENCH)
sys.path.insert(0, PERFBENCH)


def rehearse(workload: str, trace: int, root: str = ROOT, seconds: float = 5.0,
             seed: int = 2147483659, fault: str = "") -> tuple:
    """(exit code, result line as a dict or None, stderr) of one CPU rehearsal."""
    command = [sys.executable, os.path.join(root, "perfbench", "run.py"),
               "--workload", workload, "--seed", str(seed), "--seconds",
               str(seconds), "--trace", str(trace), "--rehearse-cpu"]
    if fault:
        command += ["--fault", fault]
    # a cache of CPU programs placed outside the checkout's own
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": CACHE["dir"]}
    done = subprocess.run(command, cwd=root, env=env, capture_output=True,
                          text=True, timeout=600)
    lines = [l for l in done.stdout.splitlines() if l.startswith("{")]
    return done.returncode, (json.loads(lines[-1]) if lines else None), done.stderr


CACHE = {}


@pytest.fixture(scope="session", autouse=True)
def rehearsal_compile_cache(tmp_path_factory):
    CACHE["dir"] = str(tmp_path_factory.mktemp("rehearsal-compile-cache"))


@pytest.fixture(scope="session")
def benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as handle:
        return json.load(handle)
