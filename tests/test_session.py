"""repro.session tests: workspace root/env precedence, the Session
round-trip (characterize → profile → record → report → compare against
one workspace), RooflineResult rendering parity with the raw
``profile_fn`` path, and the unified ``python -m repro`` CLI including
the deprecated delegation shims."""

import json
import os
import subprocess
import sys

import pytest

from repro.session import (RooflineResult, Session, Workspace,
                           default_workspace_root, resolve_sweep_cache,
                           resolve_sweep_store, resolve_trace_store,
                           resolve_tune_store)
from repro.session.workspace import (LEGACY_SWEEP_STORE, LEGACY_TRACE_STORE,
                                     LEGACY_TUNE_STORE, WORKSPACE_ENV)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "minitron-4b"


@pytest.fixture()
def no_ws_env(monkeypatch):
    monkeypatch.delenv(WORKSPACE_ENV, raising=False)
    monkeypatch.delenv("REPRO_TUNE_STORE", raising=False)


# --------------------------------------------------------------------------
# store-path resolution and env precedence (the consolidation satellite)
# --------------------------------------------------------------------------

class TestResolution:
    def test_explicit_beats_env(self, monkeypatch, no_ws_env):
        monkeypatch.setenv(WORKSPACE_ENV, "/ws")
        assert resolve_trace_store("/mine.jsonl") == "/mine.jsonl"
        assert resolve_sweep_store("/mine.jsonl") == "/mine.jsonl"
        assert resolve_tune_store("/mine.json") == "/mine.json"

    def test_workspace_env_governs_all_three(self, monkeypatch, no_ws_env):
        monkeypatch.setenv(WORKSPACE_ENV, "/ws")
        assert resolve_trace_store() == os.path.join("/ws", "trace.jsonl")
        assert resolve_sweep_store() == os.path.join("/ws", "sweep.jsonl")
        assert resolve_sweep_cache() == os.path.join("/ws", "sweep_cache")
        assert resolve_tune_store() == os.path.join("/ws", "tune.json")

    def test_legacy_defaults_without_env(self, no_ws_env):
        assert resolve_trace_store() == LEGACY_TRACE_STORE
        assert resolve_sweep_store() == LEGACY_SWEEP_STORE
        assert resolve_tune_store() == LEGACY_TUNE_STORE

    def test_tune_env_overrides_workspace_with_warning(self, monkeypatch,
                                                       no_ws_env):
        monkeypatch.setenv(WORKSPACE_ENV, "/ws")
        monkeypatch.setenv("REPRO_TUNE_STORE", "/old/tune.json")
        with pytest.warns(FutureWarning, match="REPRO_TUNE_STORE"):
            assert resolve_tune_store() == "/old/tune.json"

    def test_tune_default_store_path_is_workspace_backed(self, monkeypatch,
                                                         no_ws_env):
        from repro.tune.store import default_store_path
        monkeypatch.setenv(WORKSPACE_ENV, "/ws")
        assert default_store_path() == os.path.join("/ws", "tune.json")

    def test_default_root_precedence(self, monkeypatch, tmp_path,
                                     no_ws_env):
        monkeypatch.setenv(WORKSPACE_ENV, "/envws")
        assert default_workspace_root() == "/envws"
        monkeypatch.delenv(WORKSPACE_ENV)
        checkout = tmp_path / "repo"
        (checkout / ".git").mkdir(parents=True)
        monkeypatch.chdir(checkout)
        assert default_workspace_root() == str(checkout
                                               / ".repro-workspace")
        plain = tmp_path / "plain"
        plain.mkdir()
        monkeypatch.chdir(plain)
        assert default_workspace_root() == os.path.join(
            os.path.expanduser("~"), ".repro")


class TestCompileCache:
    def test_env_dir_wins_and_nothing_else_is_set(self, monkeypatch,
                                                  tmp_path):
        import jax
        from repro import compile_cache
        monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_cpu_backend_turns_nothing_on(self, monkeypatch):
        import jax
        from repro import compile_cache
        monkeypatch.delenv(compile_cache.ENV, raising=False)
        before = jax.config.jax_compilation_cache_dir
        assert compile_cache.enable_compile_cache() is None
        assert jax.config.jax_compilation_cache_dir == before

    def test_accelerator_without_env_uses_the_checkouts_dir(self,
                                                            monkeypatch):
        import jax
        from repro import compile_cache
        monkeypatch.delenv(compile_cache.ENV, raising=False)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        before = jax.config.jax_compilation_cache_dir
        try:
            assert compile_cache.enable_compile_cache() \
                == compile_cache.DEFAULT_DIR
            assert jax.config.jax_compilation_cache_dir \
                == compile_cache.DEFAULT_DIR
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
        assert compile_cache.DEFAULT_DIR == os.path.join(REPO_ROOT,
                                                         ".jax-cache")
        with open(os.path.join(REPO_ROOT, ".gitignore")) as f:
            assert ".jax-cache/" in f.read().split()


class TestWorkspace:
    def test_one_root_owns_every_store(self, tmp_path):
        ws = Workspace(str(tmp_path / "ws"))
        for path in ws.store_paths().values():
            assert os.path.dirname(path) == ws.root
        assert ws.trace_store.path == ws.trace_path
        assert ws.sweep_store.path == ws.sweep_path
        assert ws.tune_store.path == ws.tune_path

    def test_header_roundtrip_preserves_created(self, tmp_path):
        ws = Workspace(str(tmp_path / "ws"))
        first = ws.write_header("cpu-host")
        second = ws.write_header("tpu-v5e")
        assert second["created"] == first["created"]
        assert second["machine"] == "tpu-v5e"
        got = ws.read_header()
        assert got["stores"] == {"trace": "trace.jsonl",
                                 "sweep": "sweep.jsonl",
                                 "tune": "tune.json"}
        assert got["git_sha"]

    def test_corrupt_header_never_fatal(self, tmp_path):
        ws = Workspace(str(tmp_path / "ws")).ensure()
        with open(ws.header_path, "w") as f:
            f.write("{nope")
        assert ws.read_header() == {}
        assert "workspace:" in ws.describe()


class TestRooflineResult:
    def test_unknown_kind_rejected(self):
        from repro.core.machine import get_machine
        with pytest.raises(ValueError, match="unknown RooflineResult"):
            RooflineResult(kind="nope", name="x",
                           machine=get_machine("cpu-host"))

    def test_level_stats_math(self):
        from repro.core.machine import get_machine
        m = get_machine("cpu-host")
        res = RooflineResult(
            kind="record", name="x", machine=m,
            phases={"fwd": {"wall_s": 1e-3, "hbm_bytes": 2e6,
                            "vmem_bytes": 8e6}})
        stats = {lv.level: lv for lv in res.levels("fwd")}
        assert stats["hbm"].achieved_bytes_per_s == pytest.approx(2e9)
        assert stats["hbm"].bound_s == pytest.approx(
            2e6 / m.hbm.bytes_per_s)
        assert stats["vmem"].frac_of_peak == pytest.approx(
            8e9 / m.vmem.bytes_per_s)
        assert res.measured


# --------------------------------------------------------------------------
# the Session round-trip (jax; one shared workspace per class)
# --------------------------------------------------------------------------

@pytest.fixture(scope="class")
def session(tmp_path_factory):
    ws = Workspace(str(tmp_path_factory.mktemp("session") / "ws"))
    return Session(machine="cpu-host", workspace=ws)


class TestSessionRoundTrip:
    def test_characterize_datasheet_stamps_header(self, session):
        res = session.characterize()
        assert res.kind == "characterize"
        assert "machine cpu-host [datasheet]" in res.render()
        assert session.workspace.read_header()["machine"] == "cpu-host"

    def test_profile_matches_raw_profile_fn(self, session):
        """Rendering parity: Session.profile is the old
        build-phases + profile_fn path, not a reimplementation."""
        import jax.numpy as jnp

        from repro.configs.base import RunConfig
        from repro.configs.registry import get_smoke
        from repro.core.profiler import profile_fn
        from repro.core.report import kernel_table, terms_table
        from repro.models import api as M
        from repro.trace.cli import build_phase_args

        res = session.profile(CONFIG, seq=16, batch=2, phases=("fwd",))
        run = RunConfig(amp="O1", fusion="off")
        model = M.build(get_smoke(CONFIG))
        fn, args = build_phase_args(model, run, seq=16, batch=2,
                                    concrete=False)["fwd"]
        direct = profile_fn(
            fn, args=args, name="fwd", machine=session.machine,
            matmul_class="bf16" if run.compute_dtype == jnp.bfloat16
            else None)
        assert kernel_table(res.analyses["fwd"], session.machine) \
            == kernel_table(direct.analysis, session.machine)
        assert res.phases["fwd"]["bound_overlap_s"] == pytest.approx(
            direct.terms.bound_overlap_s)
        rendered = res.render()
        assert terms_table({f"{CONFIG}/fwd": direct}) in rendered
        assert kernel_table(direct.analysis, session.machine,
                            top_n=10) in rendered

    def test_profile_custom_callable(self, session):
        import jax
        import jax.numpy as jnp

        def toy(a, b):
            return jnp.einsum("ij,jk->ik", a, b).sum()

        spec = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        res = session.profile(toy, args=(spec, spec))
        assert list(res.phases) == ["toy"]
        assert res.phases["toy"]["flops"] > 0

    def test_record_report_compare_same_workspace(self, session):
        r1 = session.record(CONFIG, seq=16, batch=2, iters=2, warmup=1)
        r2 = session.record(CONFIG, seq=16, batch=2, iters=2, warmup=1)
        assert r1.measured and r1.data.run_id != r2.data.run_id
        assert os.path.exists(session.workspace.trace_path)

        rep = session.report(CONFIG)
        assert rep.data.run_id == r2.data.run_id
        assert rep.phases.keys() == r2.phases.keys()

        cmp_ = session.compare(CONFIG)
        assert cmp_.kind == "compare" and cmp_.data
        assert cmp_.exit_code in (0, 1)
        by_id = session.compare(base=r1.data.run_id, new=r2.data.run_id)
        assert by_id.data

    def test_report_without_records_raises(self, session):
        with pytest.raises(LookupError, match="no records"):
            session.report("glm4-9b")

    def test_sweep_into_workspace(self, session):
        res = session.sweep(configs=(CONFIG,), seqs=(16,), batches=(2,),
                            iters=2, warmup=1, workers=0)
        assert res.exit_code == 0 and res.data.n_ok == 1
        assert os.path.exists(session.workspace.sweep_path)
        assert CONFIG in res.text
        with pytest.raises(TypeError, match="not both"):
            session.sweep(object(), configs=(CONFIG,))

    def test_tune_into_workspace(self, session, monkeypatch):
        import repro.tune as tune_pkg
        from repro.tune.store import make_record

        def fake_search(kernel, shape=None, dtype="float32",
                        machine="cpu-host", backend="pallas", store=None,
                        **kw):
            rec = store.put(make_record(
                kernel, shape or [128], dtype, machine, backend,
                params={"block": 128}, wall_s=1e-6, metric=1e9,
                metric_name="bytes_per_s", default_wall_s=2e-6,
                default_metric=5e8, n_candidates=2))
            from repro.tune.search import TuneOutcome
            return TuneOutcome(record=rec, candidates=[], cached=False)

        monkeypatch.setattr(tune_pkg, "search", fake_search)
        res = session.tune(["triad"])
        assert res.data["triad"].record.kernel == "triad"
        assert os.path.exists(session.workspace.tune_path)
        with pytest.raises(KeyError, match="no pallas search space"):
            session.tune(["definitely-not-a-kernel"])

    def test_one_root_holds_everything(self, session):
        present = set(os.listdir(session.workspace.root))
        assert {"trace.jsonl", "sweep.jsonl", "tune.json",
                "workspace.json"} <= present


# --------------------------------------------------------------------------
# unified CLI (in-process) + delegation shims (subprocess)
# --------------------------------------------------------------------------

class TestUnifiedCli:
    def test_record_report_compare_one_workspace(self, tmp_path, capsys):
        from repro.cli import main
        ws = str(tmp_path / "ws")
        base = ["--workspace", ws]
        # the gate compares median walls: with one sample, a single
        # preempted iteration under a loaded host can mask the 1.6x
        rc = main(base + ["record", "--config", CONFIG, "--seq", "16",
                          "--batch", "2", "--iters", "5", "--warmup", "1"])
        assert rc == 0
        rc = main(base + ["record", "--config", CONFIG, "--seq", "16",
                          "--batch", "2", "--iters", "5", "--warmup", "1",
                          "--scale-wall", "1.6"])
        assert rc == 0
        out = capsys.readouterr().out
        assert os.path.join(ws, "trace.jsonl") in out
        assert sorted(os.listdir(ws)) == ["trace.jsonl", "workspace.json"]

        assert main(base + ["report"]) == 0
        assert CONFIG in capsys.readouterr().out
        # the injected 1.6x slowdown must trip the regression gate
        assert main(base + ["compare", "--config", CONFIG]) == 1

    def test_characterize_and_profile(self, tmp_path, capsys):
        from repro.cli import main
        ws = str(tmp_path / "ws")
        assert main(["--workspace", ws, "characterize"]) == 0
        out = capsys.readouterr().out
        assert "machine cpu-host [datasheet]" in out
        assert json.load(open(os.path.join(ws, "workspace.json")))[
            "machine"] == "cpu-host"
        assert main(["--workspace", ws, "profile", "--config", CONFIG,
                     "--seq", "16", "--batch", "2", "--phase", "fwd"]) == 0
        assert "kernel" in capsys.readouterr().out

    def test_forwarded_subsystems(self, tmp_path, capsys):
        from repro.cli import main
        ws = str(tmp_path / "ws")
        assert main(["sweep", "--help"]) == 0
        assert "python -m repro sweep" in capsys.readouterr().out
        assert main(["tune", "--help"]) == 0
        assert "python -m repro tune" in capsys.readouterr().out
        # forwarded report on an empty workspace store: sweep's own exit 2
        assert main(["--workspace", ws, "sweep", "report"]) == 2

    def test_every_subcommand_answers_help(self, capsys):
        from repro.cli import SUBCOMMANDS, main
        for sub in SUBCOMMANDS:
            if sub in ("sweep", "tune", "net"):
                assert main([sub, "--help"]) == 0
            else:
                with pytest.raises(SystemExit) as ei:
                    main([sub, "--help"])
                assert ei.value.code == 0
            assert f"python -m repro {sub}" in capsys.readouterr().out

    def test_workspace_env_not_leaked(self, tmp_path, monkeypatch):
        from repro.cli import main
        monkeypatch.delenv(WORKSPACE_ENV, raising=False)
        with pytest.raises(SystemExit):
            main(["--workspace", str(tmp_path), "characterize", "--help"])
        assert WORKSPACE_ENV not in os.environ


class TestDelegationShims:
    """The old entry points still answer (same flags) and say where to go."""

    @pytest.mark.parametrize("module", ["repro.trace", "repro.sweep",
                                        "repro.tune"])
    def test_shim_help_and_notice(self, module):
        env = dict(os.environ)
        env["PYTHONPATH"] = (os.path.join(REPO_ROOT, "src") + os.pathsep
                             + env.get("PYTHONPATH", ""))
        proc = subprocess.run(
            [sys.executable, "-m", module, "--help"], cwd=REPO_ROOT,
            env=env, capture_output=True, text=True, timeout=180)
        assert proc.returncode == 0, proc.stderr
        assert f"python -m {module}" in proc.stdout
        assert "deprecated" in proc.stderr
        assert "python -m repro" in proc.stderr
