"""Training substrate tests: step semantics, AMP/loss scaling, optimizers,
microbatch equivalence, checkpoint/restart, trainer fault-tolerance hooks.
"""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import RunConfig, ShapeSpec
from repro.configs.registry import get_smoke
from repro.distributed import amp
from repro.models import build, synthetic_batch
from repro.models.params import init
from repro.train import optim
from repro.train.step import TrainState, init_state, make_train_step

SHAPE = ShapeSpec("t", 32, 8, "train")


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke("granite-8b")
    model = build(cfg)
    batch = synthetic_batch(cfg, SHAPE, 8)
    return cfg, model, batch


class TestTrainStep:
    def test_loss_decreases(self, setup):
        _, model, batch = setup
        run = RunConfig(amp="O1")
        state = init_state(model, run, jax.random.PRNGKey(0))
        step = jax.jit(make_train_step(model, run, lr=1e-3))
        losses = []
        for _ in range(8):
            state, metrics = step(state, batch)   # same batch → must overfit
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0] - 0.1
        assert int(state.step) == 8

    def test_microbatch_equivalence(self, setup):
        """mb=1 and mb=4 produce (nearly) the same update in fp32."""
        _, model, batch = setup
        s1 = init_state(model, RunConfig(amp="O0"), jax.random.PRNGKey(0))
        s4 = init_state(model, RunConfig(amp="O0"), jax.random.PRNGKey(0))
        st1 = jax.jit(make_train_step(model, RunConfig(amp="O0"), lr=1e-3))
        st4 = jax.jit(make_train_step(
            model, RunConfig(amp="O0", microbatches=4), lr=1e-3))
        s1, m1 = st1(s1, batch)
        s4, m4 = st4(s4, batch)
        assert abs(float(m1["loss"]) - float(m4["loss"])) < 2e-4
        d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                         s1.params, s4.params)
        assert max(jax.tree.leaves(d)) < 2e-4

    def test_o2_runs(self, setup):
        _, model, batch = setup
        run = RunConfig(amp="O2", microbatches=2)
        state = init_state(model, run, jax.random.PRNGKey(0))
        assert jax.tree.leaves(state.params)[0].dtype == jnp.bfloat16
        step = jax.jit(make_train_step(model, run))
        state, metrics = step(state, batch)
        assert bool(jnp.isfinite(metrics["loss"]))


class TestLossScaling:
    def test_overflow_shrinks_and_skips(self):
        s = amp.DynLossScale.init(1024.0)
        grads = {"w": jnp.array([jnp.inf, 1.0])}
        g2, s2, finite = amp.unscale_and_update(grads, s)
        assert not bool(finite)
        assert float(s2.scale) == 512.0

    def test_growth_after_interval(self):
        s = amp.DynLossScale(jnp.float32(8.0), jnp.int32(1))
        grads = {"w": jnp.ones(3)}
        _, s2, finite = amp.unscale_and_update(grads, s, growth_interval=2)
        assert bool(finite)
        assert float(s2.scale) == 16.0
        assert int(s2.good_steps) == 0

    def test_unscale_divides(self):
        s = amp.DynLossScale.init(64.0)
        grads = {"w": jnp.full(3, 64.0)}
        g2, _, _ = amp.unscale_and_update(grads, s)
        np.testing.assert_allclose(np.asarray(g2["w"]), 1.0)


class TestOptimizers:
    def _quad_losses(self, run, steps=60):
        target = jnp.array([1.0, -2.0, 3.0])
        params = {"w": jnp.zeros(3)}
        state = optim.optimizer_init(params, run)
        losses = []
        for _ in range(steps):
            g = jax.grad(lambda p: jnp.sum((p["w"] - target) ** 2))(params)
            params, state = optim.optimizer_update(g, state, params, run,
                                                   lr=5e-2)
            losses.append(float(jnp.sum((params["w"] - target) ** 2)))
        return losses

    def test_adamw_converges(self):
        losses = self._quad_losses(RunConfig(optimizer="adamw"))
        assert losses[-1] < 0.1 * losses[0]

    def test_adafactor_converges(self):
        losses = self._quad_losses(RunConfig(optimizer="adafactor"))
        assert losses[-1] < 0.5 * losses[0]

    def test_blocked_update_matches_unblocked(self):
        """lax.map-blocked AdamW must equal the plain elementwise update."""
        L, D, F = 4, 16, 32
        key = jax.random.PRNGKey(3)
        params = {"w": jax.random.normal(key, (L, D, F))}
        grads = {"w": jax.random.normal(key, (L, D, F)) * 0.1}
        run = RunConfig()
        st = optim.adamw_init(params, run)
        p1, _ = optim.adamw_update(grads, st, params)
        old = optim._BLOCK_BYTES
        try:
            optim._BLOCK_BYTES = 0        # force blocking
            p2, _ = optim.adamw_update(grads, st, params)
        finally:
            optim._BLOCK_BYTES = old
        np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(p2["w"]),
                                   rtol=1e-6)

    def test_adafactor_factored_memory(self):
        """Second moment is O(rows+cols), not O(rows*cols)."""
        params = {"w": jnp.zeros((64, 128))}
        st = optim.adafactor_init(params, RunConfig(optimizer="adafactor"))
        assert st.vr["w"].shape == (64,)
        assert st.vc["w"].shape == (128,)


class TestCheckpoint:
    def test_roundtrip_and_latest(self, setup):
        from repro.checkpoint import checkpointer as ckpt
        _, model, _ = setup
        run = RunConfig()
        state = init_state(model, run, jax.random.PRNGKey(0))
        with tempfile.TemporaryDirectory() as d:
            ckpt.save(d, 3, state, {"step": 3})
            ckpt.save(d, 7, state, {"step": 7})
            assert ckpt.latest_step(d) == 7
            like = jax.eval_shape(lambda: init_state(
                model, run, jax.random.PRNGKey(0)))
            restored, meta = ckpt.restore(d, like)
            assert meta["step"] == 7
            a = jax.tree.leaves(state.params)[0]
            b = jax.tree.leaves(restored.params)[0]
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_gc_keeps_three(self, setup):
        from repro.checkpoint import checkpointer as ckpt
        _, model, _ = setup
        state = init_state(model, RunConfig(), jax.random.PRNGKey(0))
        with tempfile.TemporaryDirectory() as d:
            for s in range(5):
                ckpt.save(d, s, state)
            dirs = [x for x in os.listdir(d) if x.startswith("step_")]
            assert len(dirs) == 3
            assert ckpt.latest_step(d) == 4

    def test_async_checkpointer(self, setup):
        from repro.checkpoint.checkpointer import AsyncCheckpointer, restore
        _, model, _ = setup
        state = init_state(model, RunConfig(), jax.random.PRNGKey(0))
        with tempfile.TemporaryDirectory() as d:
            ac = AsyncCheckpointer()
            ac.save(d, 1, state, {"step": 1})
            ac.wait()
            like = jax.eval_shape(lambda: init_state(
                model, RunConfig(), jax.random.PRNGKey(0)))
            _, meta = restore(d, like)
            assert meta["step"] == 1

    def test_dtype_cast_on_restore(self):
        """Restore casts to the target tree's dtypes (elastic re-precision)."""
        from repro.checkpoint import checkpointer as ckpt
        tree = {"w": jnp.ones((4, 4), jnp.float32)}
        with tempfile.TemporaryDirectory() as d:
            ckpt.save(d, 0, tree)
            like = {"w": jax.ShapeDtypeStruct((4, 4), jnp.bfloat16)}
            out, _ = ckpt.restore(d, like)
            assert out["w"].dtype == jnp.bfloat16


class TestTrainerFaultTolerance:
    def test_restart_resumes_exactly(self, setup):
        from repro.data.pipeline import TokenStream
        from repro.train.trainer import Trainer
        cfg, model, _ = setup
        run = RunConfig(amp="O1")
        stream = TokenStream(cfg, SHAPE, batch=8)
        with tempfile.TemporaryDirectory() as d:
            t1 = Trainer(model, run, stream, ckpt_dir=d, ckpt_every=4,
                         lr=1e-3)
            t1.fit(8, log_every=0, log=lambda *_: None)
            t2 = Trainer(model, run, stream, ckpt_dir=d, ckpt_every=4,
                         lr=1e-3)
            assert t2.report.resumed_from == 8
            assert int(t2.state.step) == 8
            rep = t2.fit(10, log_every=0, log=lambda *_: None)
            assert rep.steps == 2          # only the remaining steps run

    def test_straggler_detection_fields(self, setup):
        from repro.train.trainer import Trainer
        cfg, model, _ = setup
        stream = lambda step: synthetic_batch(cfg, SHAPE, 8, seed=step)
        t = Trainer(model, RunConfig(), stream, straggler_factor=1e-9)
        rep = t.fit(3, log_every=0, log=lambda *_: None)
        # with an absurd factor every post-warmup step is a "straggler"
        assert len(rep.stragglers) >= 1
        step_idx, dt, ewma = rep.stragglers[0]
        assert dt > 0 and ewma > 0


class TestTrainerOverlap:
    """``fit`` dispatches step i+1 before it reads step i's metrics; the
    last step of a call stays in flight until something needs it."""

    N = 5

    @pytest.fixture(scope="class")
    def base(self, setup):
        """One compiled step shared by every trainer of the class, and a
        plain synchronous loop over it: the losses, grad norms and final
        parameters every overlapped run must equal bit for bit."""
        from repro.train.trainer import Trainer
        cfg, model, _ = setup
        stream = lambda step: synthetic_batch(cfg, SHAPE, 8, seed=step)
        t = Trainer(model, RunConfig(amp="O1"), stream, lr=1e-3)
        state, losses, norms = t.state, [], []
        for i in range(self.N):
            state, m = t.step_fn(state, t._put_batch(t.make_batch(i)))
            losses.append(float(m["loss"]))
            norms.append(float(m["grad_norm"]))
        return t.step_fn, stream, (losses, norms, state.params)

    def _trainer(self, setup, base, **kw):
        from repro.train.trainer import Trainer
        _, model, _ = setup
        step_fn, stream, _ = base
        t = Trainer(model, RunConfig(amp="O1"), kw.pop("stream", stream),
                    lr=1e-3, **kw)
        t.step_fn = step_fn
        return t

    @pytest.mark.parametrize("calls", ["one-call", "one-step-per-call"])
    def test_matches_a_synchronous_loop_bit_for_bit(self, setup, base,
                                                    calls):
        losses, norms, params = base[2]
        t = self._trainer(setup, base)
        if calls == "one-call":
            t.fit(self.N, log_every=0)
        else:
            for k in range(self.N):
                t.fit(k + 1, log_every=0)
        rep = t.report
        assert [x.hex() for x in rep.losses] == [x.hex() for x in losses]
        assert [x.hex() for x in rep.grad_norms] == [x.hex() for x in norms]
        for a, b in zip(jax.tree.leaves(t.state.params),
                        jax.tree.leaves(params)):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
        assert int(t.state.step) == self.N

    @pytest.mark.parametrize("resolve", ["report-read", "field-read",
                                         "state-assigned",
                                         "report-assigned"])
    def test_one_step_stays_in_flight_until_needed(self, setup, base,
                                                   resolve):
        t = self._trainer(setup, base)
        rep = t.fit(3, log_every=0)
        assert t._pending is not None and t._pending.step == 2
        t.state.params                       # reading the state: no wait
        assert t._pending is not None
        assert len(object.__getattribute__(rep, "losses")) == 2
        if resolve == "report-read":
            t.report
        elif resolve == "field-read":
            rep.steps
        elif resolve == "state-assigned":
            t.state = t.state
        else:
            t.report = type(rep)()
        assert t._pending is None
        assert rep.steps == 3
        assert len(rep.losses) == len(rep.grad_norms) == \
            len(rep.step_times) == 3
        assert all(dt > 0 for dt in rep.step_times)

    def test_overlapped_counts_each_step_after_the_first(self, setup, base):
        one = self._trainer(setup, base)
        assert one.fit(4, log_every=0).overlapped == 3
        each = self._trainer(setup, base)
        for k in range(4):
            each.fit(k + 1, log_every=0)
        assert each.report.overlapped == 3
        assert each.report.steps == 4

    def test_next_step_is_dispatched_before_a_step_is_read(self, setup,
                                                           base):
        events = []

        class Probe:
            def __init__(self, i):
                self.i = i

            def block_until_ready(self):
                events.append(("wait", self.i))
                return self

            def __float__(self):
                events.append(("read", self.i))
                return 1.0

        def fake_step(state, batch):
            i = sum(kind == "dispatch" for kind, _ in events)
            events.append(("dispatch", i))
            return state, {"loss": Probe(i), "grad_norm": Probe(i)}

        t = self._trainer(setup, base)
        t.step_fn = fake_step
        t.fit(4, log_every=0)
        assert ("read", 3) not in events     # the last is still in flight
        assert t.report.steps == 4
        for i in range(3):
            nxt = events.index(("dispatch", i + 1))
            assert nxt < events.index(("wait", i))
            assert nxt < events.index(("read", i))

    def test_sigterm_checkpoints_the_step_in_flight(self, setup, base):
        from repro.checkpoint import checkpointer as ckpt
        losses = base[2][0]
        stream = base[1]
        with tempfile.TemporaryDirectory() as d:
            t = None

            def preempted(i):
                if i == 2:
                    t._stop = True           # what the SIGTERM handler does
                return stream(i)

            t = self._trainer(setup, base, stream=preempted, ckpt_dir=d,
                              ckpt_every=0)
            rep = t.fit(self.N, log_every=0, log=lambda *_: None)
            # step 2 was in flight at the SIGTERM: resolved, then saved
            assert t._pending is None
            assert rep.steps == 3
            assert ckpt.available_steps(d) == [3]
            t2 = self._trainer(setup, base, ckpt_dir=d, ckpt_every=0)
            assert t2.report.resumed_from == 3
            rep2 = t2.fit(self.N, log_every=0, log=lambda *_: None)
            assert [x.hex() for x in rep.losses + rep2.losses] == \
                [x.hex() for x in losses]
