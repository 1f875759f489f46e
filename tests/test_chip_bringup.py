"""The chip bring-up contract, as far as a CPU box can hold it
(utils/backend.py, benchmarks/children.py, chip_smoke.py):

  * the compile cache goes where JAX_COMPILATION_CACHE_DIR says, else to
    one fixed path inside the checkout;
  * chip_smoke.py refuses a CPU by name and prints no result;
  * perfbench's generator child and the drivers it loads — and a process
    spawning a device service — never initialize a JAX backend, and a
    device service refuses another platform than the one it was told;
  * every device failure the served path catches is counted;
  * the Pallas-or-scan choice reads the operands, not the host's chip count.
"""

import glob
import json
import os
import subprocess
import sys
import types

import jax
import pytest

from benchmarks.http_load import build_extender, make_bodies
from platform_aware_scheduling_tpu.extender.server import HTTPRequest
from platform_aware_scheduling_tpu.models import batch_scheduler
from platform_aware_scheduling_tpu.utils import backend, trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_python(args, **env_overrides):
    env = {**os.environ, **env_overrides}
    return subprocess.run(
        [sys.executable] + args, cwd=REPO, env=env, capture_output=True,
        text=True, timeout=300,
    )


class TestCompileCachePlacement:
    def test_env_placement_is_left_to_jax(self, monkeypatch):
        monkeypatch.setenv(backend.COMPILE_CACHE_ENV, "/some/dir")
        before = jax.config.jax_compilation_cache_dir
        assert backend.enable_compile_cache() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_one_fixed_path_in_the_checkout(self, monkeypatch):
        monkeypatch.delenv(backend.COMPILE_CACHE_ENV, raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            chosen = backend.enable_compile_cache()
            assert jax.config.jax_compilation_cache_dir == chosen
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
        assert chosen == os.path.join(REPO, ".jax_cache")
        assert chosen == backend.DEFAULT_COMPILE_CACHE_DIR
        # the path is part of every entry's key: nothing that moves
        assert "tmp" not in chosen.lower()
        assert str(os.getpid()) not in chosen
        assert not any(ch.isdigit() for ch in os.path.basename(chosen))

    def test_cache_dir_is_ignored_by_git_and_docker(self):
        for name in (".gitignore", ".dockerignore"):
            with open(os.path.join(REPO, name)) as f:
                assert ".jax_cache/" in f.read().split(), name


class TestNoSilentCpu:
    def test_chip_smoke_refuses_a_cpu_by_name(self):
        proc = run_python(["chip_smoke.py"], JAX_PLATFORMS="cpu")
        assert proc.returncode != 0
        assert "platform='cpu'" in proc.stderr
        assert '"ok"' not in proc.stdout  # no result line of any kind

    def test_verdict_line_has_exactly_the_contract_keys(self):
        """The driver parses the smoke's last stdout line and refuses any
        key beyond ``ok`` and ``device{platform, kind, count}`` (how this
        PR's first submission was refused): the rest of what the run
        learned goes on the ``[summary]`` line before it."""
        sys.path.insert(0, REPO)
        import chip_smoke

        line = chip_smoke.verdict_line(True, backend.device_identity())
        assert "\n" not in line
        verdict = json.loads(line)
        assert set(verdict) == {"ok", "device"}
        assert verdict["ok"] is True
        assert set(verdict["device"]) == {"platform", "kind", "count"}
        assert verdict["device"]["platform"] == jax.devices()[0].platform
        assert verdict["device"]["kind"] == jax.devices()[0].device_kind
        assert verdict["device"]["count"] == len(jax.devices())
        assert type(verdict["device"]["count"]) is int

    def test_require_platform_names_what_it_found(self):
        assert backend.require_platform("test", "cpu")["platform"] == "cpu"
        with pytest.raises(backend.NoAcceleratorError, match="'cpu'"):
            backend.require_tpu("test")

    def test_device_service_refuses_another_platform(self):
        """A bench service told ``tpu`` must exit on this CPU box, not
        serve from the CPU under a device label."""
        proc = run_python(
            ["-m", "benchmarks.http_load", "--serve", "16", "1",
             "threaded", "1", "0", "0", "tpu"],
            JAX_PLATFORMS="cpu",
        )
        assert proc.returncode != 0
        assert "READY" not in proc.stdout
        assert "platform='cpu'" in proc.stderr

    def test_main_exports_the_device_it_found(self):
        backend.export_device_identity()
        families = trace.parse_prometheus_text(trace.exposition())
        (_name, labels, value), = families["pas_device_info"]["samples"]
        assert labels == {"platform": "cpu", "kind": "cpu"}
        assert value == len(jax.devices())


#: perfbench's generator child and every driver it loads by name: the
#: processes that drive a chip-holding service and must never import JAX
PERFBENCH = os.path.join(REPO, "perfbench")
GENERATOR_MODULES = [os.path.join(PERFBENCH, "generator.py")] + sorted(
    glob.glob(os.path.join(PERFBENCH, "drivers", "*.py"))
)


class TestOneProcessPerChip:
    @pytest.mark.parametrize(
        "path", GENERATOR_MODULES,
        ids=[os.path.relpath(p, PERFBENCH) for p in GENERATOR_MODULES],
    )
    def test_generator_side_stays_off_jax(self, path):
        """Loading the module as the benchmark does, from its file with
        ``perfbench/`` on the path, imports neither JAX nor any module of
        the package: a generator that did would hold the chip its
        service needs."""
        proc = run_python(["-c", (
            "import importlib.util, sys\n"
            f"sys.path.insert(0, {PERFBENCH!r})\n"
            f"spec = importlib.util.spec_from_file_location('under_test', {path!r})\n"
            "spec.loader.exec_module(importlib.util.module_from_spec(spec))\n"
            "assert 'jax' not in sys.modules\n"
            "held = [m for m in sys.modules\n"
            "        if m.split('.')[0] == 'platform_aware_scheduling_tpu']\n"
            "assert not held, held\n"
            "print('generator clean')\n"
        )])
        assert proc.returncode == 0, proc.stderr[-800:]
        assert "generator clean" in proc.stdout

    def test_no_module_takes_the_chip_at_import(self):
        """A device value at module level (``X = jnp.int32(-1)``)
        initializes the backend in every process that imports the module
        — on the chip machine that process then holds the TPU and the
        children it launches are refused it (how the forecast bench
        section died on its first chip run).  Import everything; nothing
        may have touched a backend."""
        proc = run_python(["-c", (
            "import importlib, pkgutil\n"
            "import chip_smoke, __graft_entry__\n"
            "import benchmarks, platform_aware_scheduling_tpu as pas\n"
            "from platform_aware_scheduling_tpu.utils import backend\n"
            "for pkg in (pas, benchmarks):\n"
            "    for m in pkgutil.walk_packages(pkg.__path__, pkg.__name__ + '.'):\n"
            "        if m.name.endswith('__main__') or '._wirec' in m.name:\n"
            "            continue\n"
            "        importlib.import_module(m.name)\n"
            "        assert not backend.backend_initialized(), m.name\n"
            "print('imports clean')\n"
        )])
        assert proc.returncode == 0, proc.stderr[-800:]
        assert "imports clean" in proc.stdout

    def test_spawning_a_tpu_service_after_touching_jax_is_refused(self):
        from benchmarks import http_load

        assert backend.backend_initialized()  # this test process computes
        with pytest.raises(RuntimeError, match="initialized a JAX backend"):
            http_load._spawn_service(16, device=True)


def _post(path, body):
    return HTTPRequest(
        method="POST", path=path,
        headers={"Content-Type": "application/json"}, body=body,
    )


def _errors(site):
    return trace.COUNTERS.get(
        "pas_device_path_errors_total", labels={"site": site}
    )


def _boom(*_args, **_kwargs):
    raise RuntimeError("injected device failure")


class TestCaughtDeviceFailuresAreCounted:
    """Each catch site keeps its production behaviour (the verb or the
    refresh thread carries on) AND moves pas_device_path_errors_total."""

    def test_warm_fastpath(self, monkeypatch):
        ext, _names = build_extender(32, device=True)
        monkeypatch.setattr(ext.fastpath, "precompute", _boom)
        before = _errors("warm_fastpath")
        ext.warm_fastpath()  # must not raise into the refresh thread
        assert _errors("warm_fastpath") == before + 1

    def test_warm_forecast(self, monkeypatch):
        ext, _names = build_extender(32, device=True)
        view = ext.mirror.device_view()
        ext.forecaster = types.SimpleNamespace(ranking_view=lambda _m: view)
        monkeypatch.setattr(ext.fastpath, "warm_pairs", _boom)
        before = _errors("warm_forecast")
        ext.warm_forecast_rankings()
        assert _errors("warm_forecast") == before + 1

    def test_warm_batch(self, monkeypatch):
        ext, names = build_extender(32, device=True)
        monkeypatch.setattr(ext.fastpath, "warm_rankings_batched", _boom)
        body = make_bodies(names, "nodenames", count=1)[0]
        before = _errors("warm_batch")
        assert ext.warm_batch(
            "/scheduler/prioritize", [_post("/scheduler/prioritize", body)]
        ) == 0
        assert _errors("warm_batch") == before + 1

    def test_filter_probe_and_violation_set(self, monkeypatch):
        """A Filter whose device violation set fails is still answered,
        byte-identically, by the exact host path — and both catch sites
        it passed through say so."""
        ext, names = build_extender(32, device=True)
        host, _ = build_extender(32, device=False)
        body = make_bodies(names, "nodenames", count=1)[0]
        want = host.filter(_post("/scheduler/filter", body))
        monkeypatch.setattr(ext.fastpath, "violation_reasons", _boom)
        before = (_errors("filter_probe"), _errors("filter_violations"))
        got = ext.filter(_post("/scheduler/filter", body))
        assert (got.status, got.body) == (want.status, want.body)
        assert _errors("filter_probe") == before[0] + 1
        assert _errors("filter_violations") == before[1] + 1

    def test_deschedule(self):
        from platform_aware_scheduling_tpu.tas.strategies import deschedule

        mirror = types.SimpleNamespace(policy_with_view_by_name=_boom)
        before = _errors("deschedule")
        assert deschedule.Strategy(policy_name="p").violated_device(mirror) is None
        assert _errors("deschedule") == before + 1


class TestAssignerChoice:
    """8-device virtual mesh (conftest): the choice follows the operands."""

    def _inputs(self):
        return batch_scheduler.example_inputs(
            num_metrics=4, num_nodes=64, num_pods=8
        )

    def test_never_reads_the_host_device_count(self, monkeypatch):
        state, pods = self._inputs()
        monkeypatch.setattr(jax, "device_count", _boom)
        monkeypatch.setattr(jax, "default_backend", _boom)
        assert batch_scheduler.choose_assigner(state, pods) == "scan"

    def test_unsharded_operands_pick_pallas_on_a_many_device_host(
        self, monkeypatch
    ):
        """What a four-chip TPU host gets: one device holds the operands,
        so the Pallas kernel runs however many devices are visible."""
        assert jax.device_count() == 8
        state, pods = self._inputs()
        monkeypatch.setattr(batch_scheduler, "PALLAS_PLATFORM", "cpu")
        assert batch_scheduler.choose_assigner(state, pods) == "pallas"

    def test_sharded_or_traced_operands_pick_the_scan(self, monkeypatch):
        from jax.sharding import NamedSharding, PartitionSpec

        from platform_aware_scheduling_tpu.parallel.mesh import (
            NODE_AXIS,
            make_mesh,
        )

        state, pods = self._inputs()
        monkeypatch.setattr(batch_scheduler, "PALLAS_PLATFORM", "cpu")
        sharded = jax.device_put(
            state.capacity, NamedSharding(make_mesh(), PartitionSpec(NODE_AXIS))
        )
        assert batch_scheduler.choose_assigner(sharded, pods) == "scan"
        seen = []
        jax.jit(
            lambda s, p: seen.append(batch_scheduler.choose_assigner(s, p))
        )(state, pods)
        assert seen == ["scan"]

    def test_both_assigners_agree_through_scheduling_step(self):
        import numpy as np

        state, pods = self._inputs()
        out = batch_scheduler.scheduling_step(state, pods)
        again = batch_scheduler.scheduling_step(state, pods, assigner="scan")
        assert np.array_equal(
            np.asarray(out.assignment.node_for_pod),
            np.asarray(again.assignment.node_for_pod),
        )


class TestMultichipEntries:
    def test_real_chip_entry_refuses_a_cpu(self):
        sys.path.insert(0, REPO)
        import __graft_entry__

        with pytest.raises(backend.NoAcceleratorError, match="'cpu'"):
            __graft_entry__.multichip_on_chips()

    def test_bulk_metric_swap_is_atomic_per_metric(self):
        from platform_aware_scheduling_tpu.testing.fake_kube import (
            FakeKubeClient,
        )

        kube = FakeKubeClient()
        kube.set_node_metric("m", "old-node", "1")
        kube.replace_node_metric("m", {"a": "5", "b": "6"}, "2026-01-01T00:00:00Z")
        items = kube.get_node_custom_metric("m")["items"]
        assert {
            i["describedObject"]["name"]: i["value"] for i in items
        } == {"a": "5", "b": "6"}
        assert json.dumps(items)  # wire-serializable like the API's answer
