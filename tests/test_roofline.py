"""Roofline math (paper Eq. 1 + the three-term extension)."""

import math

import pytest

from repro.core.hlo_analysis import (CollectiveRecord, KernelRecord,
                                     ModuleAnalysis)
from repro.core.machine import TPU_V5E, get_machine
from repro.core.roofline import (RooflineTerms, attainable, kernel_points,
                                 model_flops_ratio, roofline_terms)


def _kernel(flops=1e9, hbm=1e6, vmem=4e6, cls="bf16", x=1):
    return KernelRecord(name="k", opcode="fusion", op_name="", exec_count=x,
                        flops_by_class={cls: flops}, hbm_bytes=int(hbm),
                        vmem_bytes=int(vmem), category="matmul")


class TestEq1:
    def test_memory_bound_region(self):
        m = TPU_V5E
        ai = 1.0   # well under the bf16 ridge (~240)
        assert attainable(ai, m) == pytest.approx(m.hbm.bytes_per_s * ai)

    def test_compute_bound_region(self):
        m = TPU_V5E
        assert attainable(1e4, m) == m.peak_flops["bf16"]

    def test_ridge_point(self):
        m = TPU_V5E
        r = m.ridge_point("bf16")
        assert attainable(r, m) == pytest.approx(m.peak_flops["bf16"],
                                                 rel=1e-6)
        assert r == pytest.approx(197e12 / 819e9)

    def test_precision_ceilings_ordered(self):
        m = TPU_V5E
        assert (m.peak_flops["int8"] > m.peak_flops["bf16"]
                > m.peak_flops["f32"])


class TestHierarchicalPoints:
    def test_triplet_spread_encodes_locality(self):
        """High VMEM reuse → vmem AI < hbm AI gap (paper: cache locality)."""
        rec = _kernel(flops=1e9, hbm=1e6, vmem=1e8)
        pts = {p.level: p for p in kernel_points(rec, TPU_V5E)}
        assert pts["hbm"].ai > pts["vmem"].ai
        assert pts["hbm"].bound_flops_per_s >= pts["vmem"].bound_flops_per_s \
            or True  # bounds depend on both bw and ai

    def test_zero_byte_kernel_is_compute_bound(self):
        rec = _kernel(hbm=0, vmem=0)
        pts = kernel_points(rec, TPU_V5E)
        for p in pts:
            assert math.isinf(p.ai)
            assert p.bound_flops_per_s == TPU_V5E.peak_flops["bf16"]


class TestThreeTerms:
    def _analysis(self):
        kernels = [_kernel(flops=197e12, hbm=819e9, cls="bf16")]
        colls = [CollectiveRecord("c", "all-reduce", 1, int(100e9),
                                  100e9 * 1.875, 16, False),
                 CollectiveRecord("d", "all-gather", 1, int(25e9),
                                  25e9, 2, True)]
        return ModuleAnalysis(kernels, colls)

    def test_terms(self):
        t = roofline_terms(self._analysis(), TPU_V5E)
        assert t.compute_s == pytest.approx(1.0)
        assert t.memory_s == pytest.approx(1.0)
        assert t.collective_ici_s == pytest.approx(
            100e9 * 1.875 / (50e9 * 4))
        assert t.collective_dcn_s == pytest.approx(1.0)
        assert t.dominant in ("memory", "compute", "collective")
        assert t.bound_overlap_s <= t.bound_serial_s

    def test_fraction(self):
        t = roofline_terms(self._analysis(), TPU_V5E)
        assert 0.0 <= t.roofline_fraction <= 1.0

    def test_model_flops_ratio(self):
        an = self._analysis()
        r = model_flops_ratio(197e12 * 16, an, 16)
        assert r == pytest.approx(1.0)


class TestMachineSpec:
    def test_with_empirical_overrides(self):
        m2 = TPU_V5E.with_empirical({"bf16": 150e12}, {"hbm": 700e9})
        assert m2.empirical
        assert m2.peak_flops["bf16"] == 150e12
        assert m2.hbm.bytes_per_s == 700e9
        # untouched ceilings survive
        assert m2.peak_flops["int8"] == TPU_V5E.peak_flops["int8"]

    def test_registry(self):
        assert get_machine("tpu-v5e").name == "tpu-v5e"
        with pytest.raises(KeyError):
            get_machine("nope")

    @pytest.mark.parametrize("kind,name", [("TPU v5 lite", "tpu-v5e"),
                                           ("cpu", "cpu-host")])
    def test_machine_for_device_kind(self, kind, name):
        from types import SimpleNamespace
        from repro.core.machine import machine_for_device
        assert machine_for_device(
            SimpleNamespace(device_kind=kind)).name == name

    def test_machine_for_this_process_and_unknown_kinds(self, tmp_path):
        from types import SimpleNamespace
        from repro.core.machine import machine_for_device
        from repro.session import Session
        assert machine_for_device().name == "cpu-host"   # the test backend
        assert Session(workspace=str(tmp_path)).machine.name == "cpu-host"
        with pytest.raises(KeyError, match="TPU v9"):
            machine_for_device(SimpleNamespace(device_kind="TPU v9"))
