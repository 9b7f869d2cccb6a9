"""repro.net: collective characterization, attribution, and mesh sweeps.

Store-level and formula-level tests run without jax execution; the
hypothesis property tests fall back to seeded random sampling when
hypothesis is not installed (CI installs it; the container may not),
so the suite never gains a skip either way.
"""

import random

import pytest

from repro.net import characterize as C
from repro.net import collectives as NC
from repro.net import report as NR
from repro.net.collectives import (fit_ceiling, payload_bytes,
                                   wire_bytes)

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                              # container without it
    HAVE_HYPOTHESIS = False


def _check_many(prop, cases):
    """Run ``prop`` over generated cases: hypothesis when available,
    seeded random sampling otherwise — either way the property runs."""
    if HAVE_HYPOTHESIS:
        ints = [st.integers(lo, hi) for lo, hi in cases]

        @settings(max_examples=100, deadline=None)
        @given(st.tuples(*ints))
        def inner(args):
            prop(*args)

        inner()
    else:
        rng = random.Random(0)
        for _ in range(100):
            prop(*[rng.randint(lo, hi) for lo, hi in cases])


# --------------------------------------------------------------------------
# ring wire-byte formulas (property: match a counted dense reference)
# --------------------------------------------------------------------------

def _counted_ring_bytes(op, payload, n):
    """Literally count the per-link chunk traffic of a ring algorithm.

    The ring moves ``payload / n``-sized chunks: all-reduce does a
    reduce-scatter pass plus an all-gather pass (2(n-1) chunk hops per
    link), the one-pass collectives do n-1.
    """
    n = max(n, 2)
    chunk = payload / n
    hops = 2 * (n - 1) if op == "all_reduce" else (n - 1)
    return sum(chunk for _ in range(hops))


class TestWireFormulas:
    def test_all_reduce_multiplier(self):
        assert wire_bytes("all_reduce", 100.0, 4) == pytest.approx(150.0)

    def test_one_pass_multiplier(self):
        for op in ("all_gather", "reduce_scatter", "all_to_all"):
            assert wire_bytes(op, 100.0, 4) == pytest.approx(75.0)

    def test_group_floor(self):
        # a "group" of 1 still crosses a 2-device link (hlo_analysis floor)
        assert wire_bytes("all_reduce", 100.0, 1) == \
            wire_bytes("all_reduce", 100.0, 2)

    def test_all_gather_payload_is_output_sized(self):
        assert payload_bytes("all_gather", 16, 4) == 16 * 4 * 4
        assert payload_bytes("all_reduce", 16, 4) == 16 * 4

    def test_property_wire_matches_counted_reference(self):
        itemsizes = (1, 2, 4, 8)                 # s8 / bf16 / f32 / f64

        def prop(elems, n, isz_idx):
            isz = itemsizes[isz_idx]
            for op in NC.OPS:
                pay = payload_bytes(op, elems, n, itemsize=isz)
                assert wire_bytes(op, pay, n) == \
                    pytest.approx(_counted_ring_bytes(op, pay, n))
        _check_many(prop, [(1, 1 << 20), (2, 64), (0, 3)])

    def test_property_all_reduce_is_twice_one_pass(self):
        def prop(elems, n):
            pay = float(elems * 4)
            assert wire_bytes("all_reduce", pay, n) == pytest.approx(
                2 * wire_bytes("reduce_scatter", pay, n))
        _check_many(prop, [(1, 1 << 20), (2, 64)])

    def test_property_mirrors_hlo_analysis_multipliers(self):
        from repro.core.hlo_analysis import _COLL_MULT

        def prop(n):
            assert wire_bytes("all_reduce", 1.0, n) == pytest.approx(
                _COLL_MULT["all-reduce"](max(n, 2)))
            assert wire_bytes("all_gather", 1.0, n) == pytest.approx(
                _COLL_MULT["all-gather"](max(n, 2)))
        _check_many(prop, [(1, 128)])


# --------------------------------------------------------------------------
# alpha-beta fit
# --------------------------------------------------------------------------

class TestFitCeiling:
    def test_recovers_exact_model(self):
        bw, lat = 2e9, 50e-6
        samples = [(w, lat + w / bw)
                   for w in (1e3, 1e4, 1e5, 1e6)]
        fbw, flat = fit_ceiling(samples)
        assert fbw == pytest.approx(bw, rel=1e-6)
        assert flat == pytest.approx(lat, rel=1e-6)

    def test_degenerate_slope_falls_back_to_best_throughput(self):
        # constant time regardless of size: slope 0 → best observed bw
        samples = [(1e3, 1e-3), (1e6, 1e-3)]
        bw, lat = fit_ceiling(samples)
        assert bw == pytest.approx(1e6 / 1e-3)
        assert lat == 0.0

    def test_empty_raises(self):
        with pytest.raises(ValueError):
            fit_ceiling([])


# --------------------------------------------------------------------------
# store round-trip: persist → ceilings → machine spec → store hit
# --------------------------------------------------------------------------

def _synthetic_rows(n_devices=8):
    """What measure_collectives would return, minus the timing."""
    rows = []
    for leg in NC.LEGS:
        gsize = 2 if leg == "dcn" else n_devices
        for op in NC.OPS:
            for elems in (1024, 8192):
                pay = payload_bytes(op, elems, gsize)
                wire = wire_bytes(op, pay, gsize)
                bw = 4e9 if leg == "ici" else 1e9
                rows.append({"leg": leg, "op": op, "group_size": gsize,
                             "elems": elems, "payload_bytes": pay,
                             "wire_bytes": wire,
                             "t_s": 10e-6 + wire / bw})
    return rows


class TestStoreRoundTrip:
    def _store(self, tmp_path):
        from repro.tune.store import TuneStore
        return TuneStore(str(tmp_path / "tune.json"))

    def test_persist_then_ceilings(self, tmp_path):
        store = self._store(tmp_path)
        fits = C._fit_rows(_synthetic_rows())
        ceil = C._persist(fits, "cpu-host", 8, (1024, 8192), store)
        assert set(ceil) == {"ici", "dcn"}
        # leg summary = best throughput any collective achieved over it
        assert ceil["ici"]["bytes_per_s"] == pytest.approx(4e9, rel=1e-3)
        assert ceil["dcn"]["bytes_per_s"] == pytest.approx(1e9, rel=1e-3)
        assert ceil["ici"]["n_devices"] == 8

    def test_machine_with_net_folds_ceilings(self, tmp_path):
        store = self._store(tmp_path)
        C._persist(C._fit_rows(_synthetic_rows()), "cpu-host", 8,
                   (1024,), store)
        spec = C.machine_with_net("cpu-host", store)
        assert spec.net_levels
        assert spec.net_level("ici").bytes_per_s == \
            pytest.approx(4e9, rel=1e-3)
        assert spec.net_level("dcn").latency_s == \
            pytest.approx(10e-6, rel=1e-2)

    def test_machine_without_store_is_datasheet(self, tmp_path):
        from repro.core.machine import get_machine
        spec = C.machine_with_net("cpu-host", self._store(tmp_path))
        assert spec == get_machine("cpu-host")
        assert not spec.net_levels

    def test_second_characterize_is_pure_store_hit(self, tmp_path):
        store = self._store(tmp_path)
        C._persist(C._fit_rows(_synthetic_rows()), "cpu-host", 8,
                   (1024,), store)
        # both leg summaries stored → short-circuits before any worker
        out = C.characterize_net("cpu-host", store=store)
        assert out["cached"] is True
        assert set(out["ceilings"]) == {"ici", "dcn"}

    def test_missing_leg_means_no_ceilings(self, tmp_path):
        store = self._store(tmp_path)
        fits = {k: v for k, v in C._fit_rows(_synthetic_rows()).items()
                if k[0] == "ici"}
        with pytest.raises(AssertionError):
            C._persist(fits, "cpu-host", 8, (1024,), store)
        assert C.net_ceilings("cpu-host", store) is None

    def test_odd_device_count_rejected(self, tmp_path):
        with pytest.raises(ValueError):
            C.characterize_net("cpu-host", n_devices=7,
                               store=self._store(tmp_path), force=True)


# --------------------------------------------------------------------------
# report rows + flip detection
# --------------------------------------------------------------------------

def _rec(config, mesh, compute_s, memory_s, ici_s, dcn_s=0.0,
         run_id="r0", ts=1.0):
    from repro.trace.store import TraceRecord
    return TraceRecord(
        schema_version=1, run_id=run_id, timestamp=ts, git_sha="deadbeef",
        config=config, machine="cpu-host", mesh=dict(mesh),
        host={"host": "h"}, phases={"step": {
            "compute_s": compute_s, "memory_s": memory_s,
            "ici_bound_s": ici_s, "dcn_bound_s": dcn_s,
            "wall_s": 0.0, "net_bytes": (ici_s + dcn_s) * 1e9}},
        meta={})


class TestNetReport:
    def test_net_row_classifies_bound(self):
        row = NR.net_row(_rec("a", {"data": 1, "model": 8},
                              compute_s=1e-3, memory_s=2e-3, ici_s=5e-3))
        assert row["bound"] == "net"
        assert row["n_devices"] == 8
        assert row["net_s"] == pytest.approx(5e-3)
        assert row["step_bound_s"] == pytest.approx(5e-3)

    def test_flip_detected_along_scale_axis(self):
        rows = NR.net_rows([
            _rec("a", {"data": 1, "model": 1}, 1e-3, 4e-3, 0.0),
            _rec("a", {"data": 1, "model": 8}, 1e-3, 2e-3, 5e-3),
        ])
        lines = NR.flip_lines(rows)
        assert len(lines) == 1
        assert "flips" in lines[0] and "1x8" in lines[0]

    def test_never_network_bound(self):
        lines = NR.flip_lines(NR.net_rows([
            _rec("a", {}, 1e-3, 4e-3, 1e-4)]))
        assert "never network-bound" in lines[0]

    def test_render_includes_ceilings_and_ranking(self, tmp_path):
        from repro.tune.store import TuneStore
        store = TuneStore(str(tmp_path / "tune.json"))
        text = NR.render_net_report(
            [_rec("a", {"data": 1, "model": 8}, 1e-3, 2e-3, 5e-3)],
            machine="cpu-host", store=store)
        assert "datasheet" in text           # never characterized
        assert "mesh-scale ranking" in text
        assert "net" in text

    def test_render_empty_mentions_mesh_shapes(self, tmp_path):
        from repro.tune.store import TuneStore
        store = TuneStore(str(tmp_path / "tune.json"))
        text = NR.render_net_report([], machine="cpu-host", store=store)
        assert "mesh_shapes" in text


# --------------------------------------------------------------------------
# sweep-spec alias
# --------------------------------------------------------------------------

class TestMeshShapesAxis:
    def test_alias_maps_to_meshes(self):
        from repro.sweep.spec import normalize_axes
        kw = normalize_axes({"mesh_shapes": ["1x8", (2, 4)]})
        assert kw == {"meshes": ((1, 8), (2, 4))}

    def test_both_spellings_rejected(self):
        from repro.sweep.spec import normalize_axes
        with pytest.raises(ValueError):
            normalize_axes({"mesh_shapes": ["1x8"], "meshes": [(1, 1)]})

    def test_from_dict_accepts_alias(self):
        from repro.sweep.spec import SweepSpec
        spec = SweepSpec.from_dict({"name": "n", "configs": ["a"],
                                    "mesh_shapes": ["1x1", "1x8"]})
        assert spec.meshes == ((1, 1), (1, 8))


# --------------------------------------------------------------------------
# async-lowered collectives: payload counted exactly once (regression)
# --------------------------------------------------------------------------

_ASYNC_AR = """
HloModule m, entry_computation_layout={(f32[1024]{0})->f32[1024]{0}}

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

ENTRY %main (p: f32[1024]) -> f32[1024] {
  %p = f32[1024]{0} parameter(0)
  %ar-start = (f32[1024]{0}, f32[1024]{0}) all-reduce-start(%p), replica_groups={{0,1,2,3}}, to_apply=%add
  ROOT %ar-done = f32[1024]{0} all-reduce-done(%ar-start)
}
"""

_SYNC_AR = """
HloModule m, entry_computation_layout={(f32[1024]{0})->f32[1024]{0}}

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

ENTRY %main (p: f32[1024]) -> f32[1024] {
  %p = f32[1024]{0} parameter(0)
  ROOT %ar = f32[1024]{0} all-reduce(%p), replica_groups={{0,1,2,3}}, to_apply=%add
}
"""


class TestAsyncCollectivePayload:
    def test_start_done_pair_counts_payload_once(self):
        from repro.core.hlo_analysis import analyze_hlo_text
        an = analyze_hlo_text(_ASYNC_AR)
        assert len(an.collectives) == 1
        c = an.collectives[0]
        # the (operand, result) tuple must not double the 4KiB payload
        assert c.payload_bytes == pytest.approx(1024 * 4)
        assert c.wire_bytes == pytest.approx(1024 * 4 * 2 * 3 / 4)

    def test_async_matches_sync_lowering(self):
        from repro.core.hlo_analysis import analyze_hlo_text
        a = analyze_hlo_text(_ASYNC_AR).collectives[0]
        s = analyze_hlo_text(_SYNC_AR).collectives[0]
        assert a.payload_bytes == s.payload_bytes
        assert a.wire_bytes == s.wire_bytes
        assert a.group_size == s.group_size == 4


# --------------------------------------------------------------------------
# compressed cross-pod traffic: int8 all-reduce at 1/4 of fp32 wire
# --------------------------------------------------------------------------

_AR_DTYPE = """
HloModule m, entry_computation_layout={{({dt}[4096]{{0}})->{dt}[4096]{{0}}}}

%add (a: {dt}[], b: {dt}[]) -> {dt}[] {{
  %a = {dt}[] parameter(0)
  %b = {dt}[] parameter(1)
  ROOT %s = {dt}[] add(%a, %b)
}}

ENTRY %main (p: {dt}[4096]) -> {dt}[4096] {{
  %p = {dt}[4096]{{0}} parameter(0)
  ROOT %ar = {dt}[4096]{{0}} all-reduce(%p), replica_groups={{{{0,1}}}}, to_apply=%add
}}
"""


class TestCompressedWireBytes:
    def test_int8_all_reduce_quarter_of_fp32_on_dcn(self):
        from repro.core.hlo_analysis import analyze_hlo_text
        f32 = analyze_hlo_text(_AR_DTYPE.format(dt="f32"),
                               devices_per_pod=1).collectives[0]
        s8 = analyze_hlo_text(_AR_DTYPE.format(dt="s8"),
                              devices_per_pod=1).collectives[0]
        # pod size 1 ⇒ the {0,1} group spans pods: this is DCN traffic
        assert f32.cross_pod and s8.cross_pod
        assert s8.wire_bytes == pytest.approx(f32.wire_bytes / 4)


# --------------------------------------------------------------------------
# workspace tags + pinned regression gate
# --------------------------------------------------------------------------

class TestPinnedBaseline:
    def _series(self, values, metric="wall_s"):
        from repro.obs.trend import TrendPoint, TrendSeries
        s = TrendSeries(key="k", source="trace", metric=metric,
                        lower_is_better=True)
        for i, v in enumerate(values):
            s.points.append(TrendPoint(float(i), v, ref=f"run r{i}"))
        return s

    def test_tag_roundtrip_survives_header_rewrite(self, tmp_path):
        from repro.session.workspace import Workspace
        ws = Workspace(str(tmp_path / "ws"))
        ws.tag_run("good", "abc123")
        ws.write_header("cpu-host")          # refresh must keep tags
        assert ws.resolve_tag("good") == "abc123"
        assert ws.resolve_tag("abc123def") == "abc123def"  # passthrough

    def test_pinned_gate_flags_drift_median_misses(self):
        from repro.obs.trend import gate_series
        # slow creep: each point +5%, newest vs rolling median is small
        # but vs the pinned first run it is past tolerance
        vals = [1.0 * (1.05 ** i) for i in range(6)]
        s = self._series(vals)
        assert gate_series([s], tolerance=0.25) == []
        flagged = gate_series([s], tolerance=0.25, baseline_run="r0")
        assert len(flagged) == 1
        assert flagged[0].baseline == pytest.approx(1.0)
        assert "pinned" in flagged[0].describe()

    def test_pinned_gate_skips_series_without_the_run(self):
        from repro.obs.trend import gate_series
        s = self._series([1.0, 2.0])
        assert gate_series([s], tolerance=0.1, baseline_run="zzz") == []

    def test_pin_on_newest_point_is_skipped(self):
        from repro.obs.trend import gate_series
        s = self._series([1.0, 2.0])
        assert gate_series([s], tolerance=0.1, baseline_run="r1") == []


# --------------------------------------------------------------------------
# advisor rules
# --------------------------------------------------------------------------

class TestNetworkBoundRule:
    def test_fires_with_ceiling_provenance(self):
        from repro.obs.advisor import rule_network_bound
        rec = _rec("a", {"data": 1, "model": 8}, 1e-3, 2e-3, 5e-3)
        rec.meta["net_ceilings"] = {
            "ici": {"bytes_per_s": 4e9, "n_devices": 8,
                    "git_sha": "deadbeef", "key": "net_ici|..."}}
        (f,) = rule_network_bound([rec])
        assert f.rule == "network_bound"
        assert 0.5 < f.severity <= 1.0
        assert any("measured over 8" in e for e in f.evidence)

    def test_datasheet_note_without_ceilings(self):
        from repro.obs.advisor import rule_network_bound
        (f,) = rule_network_bound(
            [_rec("a", {"data": 1, "model": 8}, 1e-3, 2e-3, 5e-3)])
        assert any("datasheet" in e for e in f.evidence)

    def test_silent_when_memory_bound(self):
        from repro.obs.advisor import rule_network_bound
        assert rule_network_bound(
            [_rec("a", {}, 1e-3, 5e-3, 1e-3)]) == []

    def test_each_mesh_shape_is_its_own_finding(self):
        from repro.obs.advisor import rule_network_bound
        found = rule_network_bound([
            _rec("a", {"data": 1, "model": 4}, 1e-3, 2e-3, 5e-3,
                 run_id="r1", ts=1.0),
            _rec("a", {"data": 1, "model": 8}, 1e-3, 2e-3, 9e-3,
                 run_id="r2", ts=2.0),
        ])
        assert {f.subject for f in found} == {"a@1x4", "a@1x8"}


class TestDecodeBandwidthRule:
    def _serve_rec(self, slots, frac, ts):
        from repro.core.machine import get_machine
        from repro.trace.store import TraceRecord
        hbm_bw = get_machine("cpu-host").hbm.bytes_per_s
        wall = 1e-3
        return TraceRecord(
            schema_version=1, run_id=f"run{slots}-{ts}", timestamp=ts,
            git_sha="d", config="serve/a", machine="cpu-host", mesh={},
            host={"host": "h"},
            phases={"decode": {"wall_s": wall,
                               "hbm_bytes": frac * hbm_bw * wall}},
            meta={"n_slots": slots})

    def test_flags_drop_past_threshold(self):
        from repro.obs.advisor import rule_decode_bandwidth_regress
        recs = [self._serve_rec(1, 0.4, 1.0),
                self._serve_rec(4, 0.3, 2.0)]
        (f,) = rule_decode_bandwidth_regress(recs)
        assert f.rule == "decode_bandwidth_regress"
        assert "4 slot(s)" in f.evidence[0]

    def test_silent_when_batching_amortizes(self):
        from repro.obs.advisor import rule_decode_bandwidth_regress
        recs = [self._serve_rec(1, 0.3, 1.0),
                self._serve_rec(4, 0.4, 2.0)]
        assert rule_decode_bandwidth_regress(recs) == []

    def test_ignores_non_serve_records(self):
        from repro.obs.advisor import rule_decode_bandwidth_regress
        assert rule_decode_bandwidth_regress(
            [_rec("a", {}, 1e-3, 2e-3, 0.0)]) == []
