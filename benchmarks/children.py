"""One process for each chip: how the benches start and label processes.

A chip belongs to one process at a time, so every bench process is one of
two kinds and never both:

  * it **holds the chip** — it computes with JAX itself, starts with
    :func:`hold_chip` (compile cache placed, platform verified) and puts
    the platform it found into its JSON; or
  * it **launches** — it never initializes a JAX backend
    (:func:`assert_launcher`), and runs the processes that do, one at a
    time, through :func:`run_child` (a child that prints its JSON) or its own
    ``Popen`` of a service that calls :func:`hold_chip`.

``platform`` defaults to ``"tpu"`` everywhere; the Tier-1 harness tests
name ``"cpu"`` explicitly, and a process that finds another platform than
the one named exits non-zero instead of carrying on.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Dict, List, Optional

from platform_aware_scheduling_tpu.utils import backend

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def hold_chip(who: str, platform: str = "tpu") -> Dict:
    """Entry prologue of a process that computes on the device: place the
    persistent compile cache, then require ``platform``; returns the
    device identity for the process's JSON."""
    backend.enable_compile_cache()
    return backend.require_platform(who, platform)


def assert_launcher(who: str) -> None:
    """A launcher that touched JAX holds the chip its children need."""
    if backend.backend_initialized():
        raise RuntimeError(
            f"{who} initialized a JAX backend in a process that launches "
            f"device children; they could not get the chip"
        )


def run_child(argv: List[str], timeout: Optional[float] = None) -> Dict:
    """Run ``python <argv>`` from the repo root to completion and return
    the JSON object on the last line of its stdout.  A non-zero exit or a
    missing result raises with the end of the child's stderr — a failed
    child is a failed section, never a silently absent one."""
    proc = subprocess.run(
        [sys.executable] + list(argv),
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
        timeout=timeout,
        cwd=REPO_ROOT,
    )
    sys.stderr.write(proc.stderr)
    lines = proc.stdout.strip().splitlines()
    if proc.returncode != 0 or not lines:
        last = proc.stderr.strip().splitlines()[-1:] or ["no output"]
        raise RuntimeError(
            f"child {' '.join(argv)} exited {proc.returncode}: {last[0][:400]}"
        )
    return json.loads(lines[-1])


def nested_errors(result: Dict) -> Dict[str, str]:
    """{entry: error} for every first-level entry of a result that kept an
    ``{"error": ...}`` beside its siblings' partial results — they fail
    the run without discarding what did finish."""
    return {
        name: entry["error"]
        for name, entry in result.items()
        if isinstance(entry, dict) and "error" in entry
    }


def probe_devices() -> Dict:
    """The device identity, found by a short-lived child so the calling
    launcher stays off JAX (the child exits, releasing the chip, before
    this returns)."""
    return run_child(
        ["-m", "platform_aware_scheduling_tpu.utils.backend"], timeout=300
    )
