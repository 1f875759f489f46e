"""Which device this process computes on, and where its compiled programs
are kept.

A process that cannot reach its accelerator does not fail by itself: JAX
logs the init error and carries on on the CPU.  Everything that labels a
number or a service ``device`` therefore goes through this module — the
mains log and export what they found (:func:`export_device_identity`),
the bench children and ``chip_smoke.py`` refuse anything but a TPU
(:func:`require_tpu`), and launchers that must leave the chip to their
children assert they never touched it (:func:`backend_initialized`).

The persistent compile cache lives where ``JAX_COMPILATION_CACHE_DIR``
says — JAX reads that variable itself, so nothing is set in code — and
otherwise at one fixed path inside the checkout
(:data:`DEFAULT_COMPILE_CACHE_DIR`).  The path is part of a cache entry's
key, so it never contains a temp name, a process id or a clock value.

This module must import without jax (the host layer's rule); everything
jax touches is imported lazily inside the functions.
"""

from __future__ import annotations

import os
from typing import Dict

from platform_aware_scheduling_tpu.utils import klog, trace

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` — a constant relative to the package
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


class NoAcceleratorError(RuntimeError):
    """A process that must hold a TPU found another platform."""


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache before the first
    compile; returns the directory in use.  With
    ``JAX_COMPILATION_CACHE_DIR`` set this does nothing (JAX already
    reads it, and an operator on a read-only rootfs points it at a
    volume); otherwise the cache goes to :data:`DEFAULT_COMPILE_CACHE_DIR`."""
    placed = os.environ.get(COMPILE_CACHE_ENV)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
    return DEFAULT_COMPILE_CACHE_DIR


def device_identity() -> Dict:
    """``{"platform", "kind", "count"}`` as JAX reports them.  Initializes
    the backend — a launcher that leaves the chip to its children must not
    call this."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def require_platform(who: str, platform: str) -> Dict:
    """The device identity, or :class:`NoAcceleratorError` naming what JAX
    found instead of ``platform``.  There is no fallback: a number or a
    service labelled with a platform comes from that platform or not at
    all."""
    identity = device_identity()
    if identity["platform"] != platform:
        raise NoAcceleratorError(
            f"{who} needs platform {platform!r} but JAX found platform="
            f"{identity['platform']!r} ({identity['kind']}, "
            f"{identity['count']} device(s)); the chip may be held by "
            f"another process, or JAX_PLATFORMS excludes it"
        )
    return identity


def require_tpu(who: str) -> Dict:
    return require_platform(who, "tpu")


def export_device_identity() -> Dict:
    """Log the device identity and export it as the ``pas_device_info``
    gauge (value = device count) — the service mains' start-up line, so a
    replica that silently fell back to the CPU says so on /metrics."""
    identity = device_identity()
    klog.v(1).info_s(
        f"jax backend: platform={identity['platform']} "
        f"device_kind={identity['kind']} count={identity['count']}",
        component="extender",
    )
    trace.COUNTERS.set_gauge(
        "pas_device_info",
        float(identity["count"]),
        labels={"platform": identity["platform"], "kind": identity["kind"]},
    )
    return identity


def backend_initialized() -> bool:
    """True once this process has initialized any JAX backend (and so
    holds the chip, if there is one)."""
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


if __name__ == "__main__":
    import json

    print(json.dumps(device_identity()))
