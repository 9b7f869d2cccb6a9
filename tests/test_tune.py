"""repro.tune: store round-trip/corruption, search + 100%-store-hit
invariant, best_config routing, tuned-variant oracle parity, CLI."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.config import KernelConfig, default_config
from repro.tune import space as sp
from repro.tune import store as ts
from repro.tune.search import search, tune_ceilings
from repro.tune.store import (TuneStore, best_config, config_source,
                              make_record, tune_key)

KEY = jax.random.PRNGKey(3)


def _fake_timer(walls: dict):
    """Deterministic timer: wall per params-tuple; counts invocations."""
    calls = []

    def timer(cand, iters, warmup):
        calls.append(cand.dict)
        return walls.get(tuple(sorted(cand.dict.items())), 1.0)

    timer.calls = calls
    return timer


class TestKernelConfig:
    def test_resolve_layering(self):
        from repro.kernels.config import resolve
        cfg = resolve("triad", None)
        assert cfg.get("block") == 16384 and not cfg.get("double_buffer")
        cfg2 = resolve("triad", cfg.replace(block=8192), block=4096)
        assert cfg2.get("block") == 4096          # explicit beats config
        with pytest.raises(ValueError):
            resolve("fma_chain", cfg)             # wrong kernel's config

    def test_roundtrip(self):
        cfg = default_config("ert_gemm").replace(block_m=128)
        back = KernelConfig.from_dict(cfg.to_dict())
        assert back == cfg


class TestTuneStore:
    def _rec(self, kernel="triad", shape=(1024,), params=None,
             machine="cpu-host"):
        return make_record(kernel, shape, "float32", machine, "pallas",
                           params or {"block": 512, "double_buffer": False},
                           wall_s=1e-4, metric=3e9,
                           metric_name="bytes_per_s",
                           default_wall_s=2e-4, default_metric=1.5e9,
                           n_candidates=4)

    def test_roundtrip(self, tmp_path):
        store = TuneStore(str(tmp_path / "tune.json"))
        rec = store.put(self._rec())
        got = store.get(rec.key)
        assert got is not None
        assert got.params == {"block": 512, "double_buffer": False}
        assert got.speedup == pytest.approx(2.0)
        assert store.records()[0].key == rec.key

    def test_corrupt_file_not_fatal(self, tmp_path):
        path = str(tmp_path / "tune.json")
        with open(path, "w") as f:
            f.write("{not json")
        store = TuneStore(path)
        with pytest.warns(UserWarning, match="corrupt"):
            assert store.get("anything") is None
        # a put over a corrupt file recovers (fresh document)
        rec = store.put(self._rec())
        assert TuneStore(path).get(rec.key) is not None

    def test_newer_schema_skipped(self, tmp_path):
        path = str(tmp_path / "tune.json")
        with open(path, "w") as f:
            json.dump({"schema_version": ts.SCHEMA_VERSION + 1,
                       "records": {"k": {"kernel": "triad"}}}, f)
        with pytest.warns(UserWarning, match="newer"):
            assert TuneStore(path).records() == []

    def test_non_dict_record_value_dropped(self, tmp_path):
        path = str(tmp_path / "tune.json")
        with open(path, "w") as f:
            json.dump({"schema_version": ts.SCHEMA_VERSION,
                       "records": {"k1": "junk", "k2": 7}}, f)
        store = TuneStore(path)
        assert store.records() == []
        # and best_config falls back to the default, no crash
        assert best_config("triad", (1024,), store=store) == \
            default_config("triad")

    def test_machine_keying(self, tmp_path):
        store = TuneStore(str(tmp_path / "tune.json"))
        store.put(self._rec(machine="cpu-host"))
        key_tpu = tune_key("triad", (1024,), "float32", "tpu-v5e", "pallas")
        assert store.get(key_tpu) is None


class TestSearch:
    def test_winner_and_persistence(self, tmp_path):
        store = TuneStore(str(tmp_path / "tune.json"))
        timer = _fake_timer({})   # all walls equal → default-ish winner

        def timer2(cand, iters, warmup):
            timer.calls.append(cand.dict)
            # make block=65536 the clear triad winner
            return 1e-4 if cand.dict.get("block") == 65536 else 5e-4

        out = search("triad", (1 << 20,), store=store, timer=timer2)
        assert not out.cached
        assert out.record.params["block"] == 65536
        assert out.record.default_wall_s == pytest.approx(5e-4)
        assert out.speedup > 1.0
        assert best_config("triad", (1 << 20,),
                           store=store).get("block") == 65536

    def test_second_search_is_pure_store_hit(self, tmp_path):
        """Acceptance: same space twice → 100% hit, zero re-timing."""
        store = TuneStore(str(tmp_path / "tune.json"))
        t1 = _fake_timer({})
        first = search("ert_gemm", (512, 512, 512), store=store, timer=t1)
        assert not first.cached and len(t1.calls) > 0
        t2 = _fake_timer({})
        second = search("ert_gemm", (512, 512, 512), store=store, timer=t2)
        assert second.cached
        assert t2.calls == []                 # nothing re-timed
        assert second.record.params == first.record.params
        t3 = _fake_timer({})
        forced = search("ert_gemm", (512, 512, 512), store=store,
                        timer=t3, force=True)
        assert not forced.cached and len(t3.calls) == len(t1.calls)

    @pytest.mark.parametrize("kernel,shape", [("triad", (8192,)),
                                              ("fma_chain", (2048,))])
    def test_small_shapes_keep_default_candidate(self, kernel, shape):
        # shapes below the default block still tune (the kernel pads)
        cands = sp.candidates(kernel, shape)
        assert any(sp.is_default(kernel, "pallas", shape, c.dict)
                   for c in cands)

    @pytest.mark.parametrize("kernel,shape", [
        ("ert_gemm", (384, 384, 384)),
        ("flash_attention", (2, 768, 768, 64)),
        ("ssd_scan", (1, 2, 192, 16, 16)),
    ])
    def test_non_divisible_shapes_get_fitted_default(self, kernel, shape):
        # the clamped default doesn't tile these shapes; the space fits
        # it (halve-to-divisor) instead of crashing, and every candidate
        # is feasible
        cands = sp.candidates(kernel, shape)
        assert sum(sp.is_default(kernel, "pallas", shape, c.dict)
                   for c in cands) == 1
        dflt = sp._clamped_default(kernel, "pallas", shape)
        if kernel == "ert_gemm":
            assert dflt == {"block_m": 128, "block_n": 128, "block_k": 128}
        elif kernel == "flash_attention":
            assert dflt == {"block_q": 256, "block_k": 256}
        else:
            assert dflt == {"chunk": 64}

    @pytest.mark.parametrize("kernel,shape", [
        ("fused_norm", (8192, 3072)),
        ("fused_swiglu", (8192, 9216)),
    ])
    def test_row_blocks_capped_by_vmem(self, kernel, shape):
        # the kernel caps its row block by VMEM; the space names only
        # blocks that run as named, the default among them
        from repro.kernels.fused.common import vmem_rows
        cap = vmem_rows(shape[1], ["bfloat16"] * (
            4 if kernel == "fused_norm" else 3))
        cands = sp.candidates(kernel, shape, "bfloat16")
        blocks = [c.dict["block_rows"] for c in cands]
        assert max(blocks) == cap < sp.default_params(kernel)["block_rows"]
        assert len(set(blocks)) == len(blocks)
        assert sum(sp.is_default(kernel, "pallas", shape, c.dict, "bfloat16")
                   for c in cands) == 1
        assert sp.is_default(kernel, "pallas", shape,
                             {"block_rows": cap}, "bfloat16")

    def test_fit_block(self):
        assert sp.fit_block(256, 384) == 128
        assert sp.fit_block(512, 768) == 256
        assert sp.fit_block(128, 192) == 64
        assert sp.fit_block(128, 128) == 128
        assert sp.fit_block(128, 7) == 7     # clamps to dim, which divides

    def test_every_space_contains_default(self):
        for kernel in sp.PALLAS_KERNELS:
            for smoke in (False, True):
                shape = sp.default_shape(kernel, smoke)
                cands = sp.candidates(kernel, shape, smoke=smoke)
                assert sum(
                    sp.is_default(kernel, "pallas", shape, c.dict)
                    for c in cands) == 1, (kernel, smoke)

    def test_real_smoke_search_beats_or_ties_default(self, tmp_path):
        """Real timing path (tiny space): winner metric >= default's."""
        store = TuneStore(str(tmp_path / "tune.json"))
        out = search("triad", store=store, smoke=True, iters=2, warmup=1)
        assert out.record.metric >= out.record.default_metric
        assert store.get(out.record.key) is not None

    def test_ceilings_persisted_and_hit(self, tmp_path):
        store = TuneStore(str(tmp_path / "tune.json"))
        c1 = tune_ceilings(store=store, smoke=True, iters=1, warmup=1)
        assert set(c1) == {"flops_f32", "flops_bf16", "gemm_bf16",
                           "bw_hbm", "bw_vmem"}
        assert all(not oc.cached for oc in c1.values())
        c2 = tune_ceilings(store=store, smoke=True, iters=1, warmup=1)
        assert all(oc.cached for oc in c2.values())
        # ceilings are positive rates
        assert c1["flops_f32"].record.metric > 0
        assert c1["bw_hbm"].record.metric > 0


class TestBestConfigRouting:
    def test_miss_falls_back_to_default(self, tmp_path):
        store = TuneStore(str(tmp_path / "empty.json"))
        src, cfg = config_source("flash_attention", (2, 256, 256, 64),
                                 store=store)
        assert src == "default" and cfg == default_config("flash_attention")

    def test_hit_returns_tuned(self, tmp_path):
        store = TuneStore(str(tmp_path / "tune.json"))
        store.put(make_record(
            "flash_attention", (2, 256, 256, 64), "float32", "cpu-host",
            "pallas", {"block_q": 128, "block_k": 256}, 1e-4, 1e9,
            "flops_per_s", 2e-4, 5e8, 3))
        src, cfg = config_source("flash_attention", (2, 256, 256, 64),
                                 store=store)
        assert src == "tuned"
        assert cfg.get("block_q") == 128 and cfg.get("block_k") == 256
        # structural semantics are merged from the default, not searched
        assert cfg.dimension_semantics == \
            default_config("flash_attention").dimension_semantics

    def test_empirical_cpu_spec_from_tuned_store(self, tmp_path):
        from repro.core.machine import empirical_cpu_spec
        store = TuneStore(str(tmp_path / "tune.json"))
        spec = empirical_cpu_spec(tuned=True, store=store, smoke=True)
        assert spec.empirical
        assert spec.peak_flops["f32"] > 0 and spec.hbm.bytes_per_s > 0
        # ceilings come from the store's winners (best-of-tuned)
        ceil = store.get(tune_key(
            "fma_chain", (1 << 14,), "float32", "cpu-host", "xla"))
        assert ceil is not None
        assert spec.peak_flops["f32"] == pytest.approx(ceil.metric)

    def test_active_kernel_configs_sources(self, tmp_path):
        from repro.tune import active_kernel_configs
        store = TuneStore(str(tmp_path / "tune.json"))
        before = active_kernel_configs(store=store)
        assert before["flash_attention"]["source"] == "default"
        store.put(make_record(
            "flash_attention", (2, 64, 64, 8), "float32", "cpu-host",
            "pallas", {"block_q": 64, "block_k": 64}, 1e-4, 1e9,
            "flops_per_s", 2e-4, 5e8, 2))
        after = active_kernel_configs(store=store)
        assert after["flash_attention"]["source"] == "tuned_available"
        assert after["ssd_scan"]["source"] == "default"


class TestTunedVariantParity:
    """Every config the tuner can emit stays bit-compatible with the jnp
    oracle, across dtypes and odd (non-tiling) shapes."""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("n", [1000, 1 << 14, 40000])
    def test_triad_all_smoke_candidates(self, dtype, n):
        from repro.kernels.ert import bandwidth as BW
        from repro.kernels.ert import ref
        a = (jax.random.normal(KEY, (n,), jnp.float32)).astype(dtype)
        b = (a * 0.25).astype(dtype)
        want = np.asarray(ref.triad_ref(a, b), np.float32)
        seen = set()
        for cand in sp.candidates("triad", sp.default_shape("triad", True),
                                  smoke=True):
            cfg = default_config("triad").replace(**cand.dict)
            seen.add(cand.params)
            got = BW.triad(a, b, config=cfg)
            np.testing.assert_allclose(np.asarray(got, np.float32), want,
                                       rtol=1e-2)
        assert len(seen) >= 2

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("n", [1000, 12000])
    def test_fma_all_smoke_candidates(self, dtype, n):
        from repro.kernels.ert import flops as FL
        from repro.kernels.ert import ref
        x = jax.random.normal(KEY, (n,), jnp.float32).astype(dtype)
        want = np.asarray(ref.fma_chain_ref(x, 8, 2), np.float32)
        for cand in sp.candidates(
                "fma_chain", sp.default_shape("fma_chain", True),
                smoke=True):
            cfg = default_config("fma_chain").replace(**cand.dict)
            got = FL.fma_chain(x, 8, 2, config=cfg)
            tol = 1e-5 if dtype == jnp.float32 else 5e-2
            np.testing.assert_allclose(np.asarray(got, np.float32), want,
                                       rtol=tol, atol=tol)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_gemm_all_smoke_candidates(self, dtype):
        from repro.kernels.ert import gemm, ref
        m = n = k = 256
        a = jax.random.normal(KEY, (m, k), jnp.float32).astype(dtype)
        b = jax.random.normal(KEY, (k, n), jnp.float32).astype(dtype)
        want = np.asarray(ref.matmul_ref(a, b), np.float32)
        for cand in sp.candidates("ert_gemm", (m, n, k), smoke=True):
            cfg = default_config("ert_gemm").replace(**cand.dict)
            got = gemm.matmul(a, b, config=cfg)
            tol = 1e-4 if dtype == jnp.float32 else 5e-2
            np.testing.assert_allclose(np.asarray(got, np.float32), want,
                                       rtol=tol, atol=tol)

    def test_flash_all_smoke_candidates(self):
        from repro.kernels.flash_attention import kernel as FA
        from repro.kernels.flash_attention import ref as FA_REF
        bh, sq, sk, hd = sp.default_shape("flash_attention", True)
        q = jax.random.normal(KEY, (bh, sq, hd), jnp.float32)
        k = jax.random.normal(KEY, (bh, sk, hd), jnp.float32)
        v = jax.random.normal(KEY, (bh, sk, hd), jnp.float32)
        want = np.asarray(FA_REF.attention_ref(q, k, v, causal=True))
        for cand in sp.candidates("flash_attention", (bh, sq, sk, hd),
                                  smoke=True):
            cfg = default_config("flash_attention").replace(**cand.dict)
            got = FA.flash_attention(q, k, v, causal=True, config=cfg)
            np.testing.assert_allclose(np.asarray(got), want, rtol=2e-2,
                                       atol=2e-3)

    def test_ssd_all_smoke_candidates(self):
        from repro.kernels.ssd_scan import kernel as SSD
        from repro.kernels.ssd_scan import ref as SSD_REF
        b, h, s, p, nst = sp.default_shape("ssd_scan", True)
        xdt = jax.random.normal(KEY, (b, h, s, p)) * 0.1
        a = -jnp.abs(jax.random.normal(KEY, (b, h, s))) * 0.1
        Bc = jax.random.normal(KEY, (b, s, nst)) * 0.1
        Cc = jax.random.normal(KEY, (b, s, nst)) * 0.1
        for cand in sp.candidates("ssd_scan", (b, h, s, p, nst),
                                  smoke=True):
            chunk = cand.dict["chunk"]
            cfg = default_config("ssd_scan").replace(chunk=chunk)
            got = SSD.ssd_scan(xdt, a, Bc, Cc, config=cfg)
            want = SSD_REF.ssd_ref(xdt, a, Bc, Cc, chunk=chunk)
            scale = float(jnp.max(jnp.abs(want))) or 1.0
            assert float(jnp.max(jnp.abs(got - want))) / scale < 1e-4


class TestCli:
    def test_search_show_apply_loop(self, tmp_path, capsys):
        from repro.tune.cli import main
        store = str(tmp_path / "tune.json")
        rc = main(["search", "--smoke", "--kernel", "triad",
                   "--store", store, "--iters", "1"])
        assert rc == 0
        out1 = capsys.readouterr().out
        assert "cands]" in out1 and "store hit" not in out1
        # ceilings ran too (--smoke implies them)
        assert "[bw_hbm]" in out1
        rc = main(["search", "--smoke", "--kernel", "triad",
                   "--store", store, "--iters", "1"])
        assert rc == 0
        assert "store hit" in capsys.readouterr().out
        assert main(["show", "--store", store]) == 0
        assert "triad" in capsys.readouterr().out
        rc = main(["apply", "--store", store, "--iters", "1",
                   "--tolerance", "1.0"])
        assert rc == 0

    def test_show_empty_store_exits_2(self, tmp_path, capsys):
        from repro.tune.cli import main
        assert main(["show", "--store", str(tmp_path / "none.json")]) == 2
        assert "no tuned records" in capsys.readouterr().err

    def test_search_shape_needs_single_kernel(self, tmp_path, capsys):
        from repro.tune.cli import main
        rc = main(["search", "--shape", "128", "--store",
                   str(tmp_path / "t.json")])
        assert rc == 2

    def test_search_xla_backend_defaults_to_xla_kernels(self, tmp_path,
                                                        capsys):
        from repro.tune.cli import main
        store = str(tmp_path / "tune.json")
        rc = main(["search", "--backend", "xla", "--smoke",
                   "--store", store, "--iters", "1"])
        assert rc == 0
        assert "[FAIL]" not in capsys.readouterr().err
        # a kernel without an xla space is a friendly exit 2, no traceback
        rc = main(["search", "--backend", "xla", "--kernel",
                   "flash_attention", "--store", store])
        assert rc == 2
        assert "no xla search space" in capsys.readouterr().err


class TestSweepProvenance:
    def test_tune_mismatch_flags(self, tmp_path):
        from repro.sweep.aggregate import tune_mismatches
        from repro.trace.store import record_from_payloads
        store = TuneStore(str(tmp_path / "tune.json"))
        rec = record_from_payloads(
            "cfg", {"fwd": {"wall_s": 0.1}}, machine="cpu-host",
            meta={"sweep_point": "p1", "label": "cfg/p1",
                  "kernel_configs": {
                      "flash_attention": {"source": "default"},
                      "ssd_scan": {"source": "default"}}})
        # no tuned winners yet → consistent
        assert tune_mismatches([rec], store) == []
        store.put(make_record(
            "flash_attention", (2, 64, 64, 8), "float32", "cpu-host",
            "pallas", {"block_q": 64, "block_k": 64}, 1e-4, 1e9,
            "flops_per_s", 2e-4, 5e8, 2))
        flags = tune_mismatches([rec], store)
        assert len(flags) == 1 and "flash_attention" in flags[0]
        assert "tuned winner now exists" in flags[0]
