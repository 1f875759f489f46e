"""benchmarks/configs.py — the BASELINE config benches must run and agree
with host semantics at tiny shapes (the full shapes run in bench.py on
real hardware; these tests pin correctness, not performance)."""

from benchmarks import configs


class TestConfigBenches:
    def test_config1_runs_and_reports(self):
        out = configs.config1_single_metric(num_nodes=3, platform="cpu")
        assert out["platform"] == "cpu"
        assert out["device_p99_ms"] > 0
        assert out["control_p99_ms"] > 0
        assert "speedup_p99" in out

    def test_config2_runs_and_reports(self):
        out = configs.config2_multi_metric(num_nodes=64, num_pods=8)
        assert out["assigner"] == "scan"  # CPU operands never pick Pallas
        assert out["device_ms_per_solve"] > 0
        assert out["control_ms_per_solve"] > 0
        assert "speedup" in out

    def test_config3_parity_small(self):
        out = configs.config3_gas_binpack(num_nodes=16, num_cards=4)
        assert out["parity"] is True
        assert 0 <= out["nodes_fitting"] <= 16

    def test_config3_parity_default_shape(self):
        out = configs.config3_gas_binpack()
        assert out["parity"] is True

    def test_config5_runs(self):
        out = configs.config5_churn(num_nodes=128, num_pods=8, ticks=2)
        assert out["device_ms_per_tick"] > 0
        assert out["control_ms_per_tick"] > 0

    def test_host_first_fit_rejects_when_full(self):
        import numpy as np

        state, request, max_gpus, hosts = configs._binpack_problem(
            num_nodes=4, num_cards=2
        )
        hosts["used"] = np.broadcast_to(
            hosts["cap"][:, None, :], hosts["used"].shape
        ).copy()  # every card already at capacity
        fits = configs._host_first_fit(hosts)
        assert not fits.any()
