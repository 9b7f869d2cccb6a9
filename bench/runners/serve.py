"""Serving cells: the program's ``Engine`` driven by arrivals on the wall
clock.

Set-up makes the weights from the seed with the configuration's
reference, builds one ``repro.serve.engine.Engine`` from the program's
registry configuration, compiles (or loads) its three executables with
``warmup()`` and serves one request through all three.  Then requests
arrive for the mix's ``lead_seconds``, so that the window opens on a busy
engine, and the window runs for ``--seconds``.  Arrivals are open loop:
each request is submitted at the first tick boundary after it is due and
is timed from when it was due.  A backlog mix keeps its queue topped up
instead.  After the window no request is submitted; the runner ticks on
until every counted request has its first token (open loop) and every
request sampled for the check has finished.

Each executable call runs inside a host span of its name, and each
engine tick inside ``bench.serve.tick``; every call is recorded (a
prefill's start and valid tokens, a decode's active slots' live
lengths) for the per-layer readers.  For the requests sampled from the
seed, the logits the timed path produced are kept from the host copy the
engine makes for its argmax.  After the window, with the engine freed,
the reference runs the full forward pass over each sampled prompt and its
served tokens and the two are compared at every served position.
"""

from __future__ import annotations

import gc
import math
import time

import numpy as np

from benchlib import harness
from benchlib import lmcost
from benchlib import traffic as T

#: the engine's compiled executables, each wrapped in a span of its name
EXECUTABLES = ("prefill_first", "prefill_ext", "decode")

#: the engine configuration's widths that must equal the file's
WIDTHS = ("n_layers", "d_model", "d_ff", "n_heads", "n_kv_heads",
          "head_dim", "vocab_size", "act", "rope_theta", "norm_eps",
          "tie_embeddings")


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in [0, 100]) of a non-empty sample."""
    vs = sorted(values)
    return vs[min(max(1, math.ceil(q / 100.0 * len(vs))), len(vs)) - 1]


# --------------------------------------------------------------------------
# the program
# --------------------------------------------------------------------------

class Program:
    """One engine over weights made from a seed, its executables wrapped
    in spans and recorded."""

    def __init__(self, ctx: harness.Ctx):
        import jax
        with harness.program_on_path():
            from repro.configs.base import RunConfig
            from repro.configs.registry import get_config, get_smoke
            from repro.models.api import build
            from repro.models.params import abstract
            from repro.serve.engine import Engine, Request
        cfg = ctx.cfg
        prog = cfg["program"]
        self.ctx = ctx
        self.Request = Request
        self.mcfg = (get_smoke if prog.get("smoke") else get_config)(
            prog["registry"])
        for k in WIDTHS:
            if getattr(self.mcfg, k) != cfg[k]:
                raise harness.BenchError(
                    f"the program's {prog['registry']} has {k} "
                    f"{getattr(self.mcfg, k)!r}, the configuration "
                    f"{cfg[k]!r}")
        run = RunConfig(**prog["run"])
        self.ref = harness.reference_of(cfg)
        self.init = jax.jit(lambda k: self.ref.init_params(cfg, k))
        params = self.init(harness.seed_key(ctx.seed))
        harness.check_layout(params, abstract(build(self.mcfg).spec,
                                       run.param_dtype))
        self.engine = Engine(self.mcfg, run, params,
                             n_slots=cfg["n_slots"], max_len=cfg["max_len"],
                             page_size=cfg["page_size"],
                             prefill_chunk=cfg["prefill_chunk"])
        self.engine.warmup()
        self.vocab = cfg["vocab_size"]
        self.calls: list[dict] = []
        self.sample: set[int] = set()
        self.rows: dict[int, list[np.ndarray]] = {}
        self._first: list = []          # (uid, logits) of this tick's prefills
        self._decode = None              # (logits, [(slot, request)])
        self._wrap()

    def idle(self) -> None:
        """Cancel every request the engine still holds."""
        eng = self.engine
        for uid in [r.uid for r in eng.queue] + [
                s.req.uid for s in eng._slots if s is not None]:
            eng.cancel(uid)

    def start(self, seed: int) -> None:
        """Weights from ``seed``; the engine idle."""
        eng = self.engine
        self.idle()
        eng.params = None
        gc.collect()
        eng.params = self.init(harness.seed_key(seed))

    def _wrap(self) -> None:
        eng = self.engine
        #: the engine's own executables, which the spans call
        self.executables = {n: eng._compiled[n] for n in EXECUTABLES}
        for name in EXECUTABLES:
            eng._compiled[name] = self._span_call(name)
        chunk = eng._prefill_chunk

        def prefill_chunk(slot_idx, prompt, start):
            n = len(self.calls)
            logits, valid = chunk(slot_idx, prompt, start)
            if len(self.calls) != n + 1:
                raise harness.BenchError("a prompt chunk ran no prefill "
                                         "executable, or more than one")
            rec = self.calls[-1]
            rec["start"], rec["valid"] = int(start), int(valid)
            rec["final"] = start + valid == len(prompt)
            req = eng._slots[slot_idx].req
            if req.uid in self.sample and start + valid == len(prompt):
                self._first.append((req.uid, logits))
            return logits, valid

        eng._prefill_chunk = prefill_chunk

    def _span_call(self, name: str):
        import jax
        eng = self.engine

        def call(*args):
            tracer = self.ctx.tracer
            rec = {"kind": name, "traced": tracer.active}
            if name == "decode":
                active = [(i, s.req) for i, s in enumerate(eng._slots)
                          if s is not None and s.phase == "decode"]
                rec["lengths"] = [int(eng.cache.lengths[i])
                                  for i, _r in active]
            self.calls.append(rec)
            with tracer.span(f"bench.serve.{name}"):
                out = self.executables[name](*args)
                jax.block_until_ready(out)
            if name == "decode":
                self._decode = (out[0], active)
            return out
        return call

    def after_tick(self) -> None:
        """Keep the sampled requests' logits of the tick just run: the
        prefill's last position, then their rows of the decode, each read
        from the host copy the engine made."""
        for uid, logits in self._first:
            self.rows[uid] = [np.array(np.asarray(logits)[:self.vocab])]
        self._first = []
        if self._decode is not None:
            logits, active = self._decode
            self._decode = None
            host = None
            for i, req in active:
                if req.uid in self.sample:
                    if host is None:
                        host = np.asarray(logits)
                    self.rows[req.uid].append(np.array(host[i, :self.vocab]))

    def free(self) -> None:
        """Drop the engine, its weights and every array of the program."""
        self._decode, self._first = None, []
        self.engine, self.executables = None, {}
        gc.collect()


# --------------------------------------------------------------------------
# one drive of the engine
# --------------------------------------------------------------------------

class Drive:
    """What a drive leaves: every request, the tick-end time of each of
    its output tokens, the window's edges and the check's sample."""

    def __init__(self):
        self.requests = []
        self.stamps: dict[int, list[float]] = {}
        self.counted = []
        self.late_ms: list[float] = []
        self.t_arr = self.t0 = self.t_close = None   # arrivals begin; window
        self.sample = []
        self.queued_at_close = self.active_at_close = 0


def drive(prog: Program, spec: dict, seed: int, seconds: float,
          check: int) -> Drive:
    """Serve a mix for its lead and then a window of ``seconds`` and drain
    as set out in the module's docstring; ``check`` requests are sampled
    for the comparison with the reference."""
    ctx, eng = prog.ctx, prog.engine
    clock, tracer, counter = ctx.tracer.clock, ctx.tracer, ctx.counter
    plan = T.serve_plan(spec, seed, seconds, prog.vocab)
    backlog = spec["arrivals"] == "backlog"
    stall_s = float(spec.get("stall_seconds", 60))
    d = Drive()
    live = []
    nxt = 0
    n_calls = len(prog.calls)
    prog.sample, prog.rows = set(), {}
    if not backlog:
        d.sample = _sample(plan, spec, seed, seconds, check)
        prog.sample = set(d.sample)

    def submit(due_at: float, now: float) -> None:
        nonlocal nxt
        i = nxt % len(plan)
        req = prog.Request(uid=nxt, prompt=plan.prompt(i),
                           max_new=int(plan.max_new[i]))
        req.t_arrival = due_at
        nxt += 1
        eng.submit(req)
        d.requests.append(req)
        d.stamps[req.uid] = []
        live.append(req)
        if d.t0 is not None and due_at >= d.t0 and d.t_close is None:
            d.counted.append(req)
            d.late_ms.append((now - due_at) * 1e3)

    t_arr = d.t_arr = clock()
    t0 = t_arr + float(spec["lead_seconds"])
    last_token = t_arr
    while True:
        now = clock()
        if d.t0 is None and now >= t0:
            d.t0 = t0
            counter.on = True
            tracer.open_window(t0)
            if backlog:
                d.sample = _queued_sample(list(eng.queue), seed, check)
                prog.sample = set(d.sample)
        if d.t0 is not None and d.t_close is None:
            tracer.poll(now)
            now = clock()          # starting or stopping the profiler waits
            if now >= t0 + seconds:
                # every request due in the window is submitted, however
                # late the loop came round to it
                while not backlog and nxt < len(plan) \
                        and t_arr + plan.due[nxt] < t0 + seconds:
                    submit(float(t_arr + plan.due[nxt]), now)
                d.t_close = now
                d.queued_at_close = len(eng.queue)
                d.active_at_close = eng.n_active
                counter.on = False
                tracer.close()
        if d.t_close is None:
            if backlog:
                while len(eng.queue) < spec["backlog"]:
                    submit(now, now)
            else:
                while nxt < len(plan) and t_arr + plan.due[nxt] <= now:
                    submit(float(t_arr + plan.due[nxt]), now)
        else:
            waiting = [r for r in d.counted if not backlog
                       and r.t_first is None and r.status != "rejected"]
            sampled = [r for r in d.requests
                       if r.uid in prog.sample and not r.done]
            if not waiting and not sampled:
                break
        busy = bool(eng.queue) or eng.n_active > 0
        if not busy:
            due = [t0] if d.t0 is None else [t0 + seconds]
            if not backlog and nxt < len(plan):
                due.append(t_arr + plan.due[nxt])
            if d.t_close is not None:
                raise harness.BenchError("requests sampled for the check "
                                         "were never served")
            time.sleep(max(0.0, min(due) - now))
            last_token = clock()
            continue
        with tracer.span("bench.serve.tick"):
            eng.tick()
        prog.after_tick()
        now = clock()
        produced = False
        for req in live:
            new = len(req.out) - len(d.stamps[req.uid])
            if new > 0:
                d.stamps[req.uid].extend([now] * new)
                produced = True
        live[:] = [r for r in live if not r.done and r.status != "rejected"]
        if produced:
            last_token = now
        elif now - last_token > stall_s:
            raise harness.BenchError(
                f"the engine produced no token in {stall_s} s with "
                f"{len(eng.queue)} requests queued and {eng.n_active} "
                "active; it no longer serves the cell's work")
    _check_calls(prog, d, n_calls)
    return d


def _sample(plan: T.ServePlan, spec: dict, seed: int, seconds: float,
            check: int) -> list[int]:
    """The longest request due inside the window and ``check - 1`` more,
    drawn from the seed."""
    if check == 0:
        return []
    lead = float(spec["lead_seconds"])
    inside = [i for i in range(len(plan))
              if lead <= plan.due[i] < lead + seconds]
    if len(inside) < check:
        raise harness.BenchError(f"only {len(inside)} requests are due in "
                                 f"the window; the check needs {check}")
    size = plan.prompt_len + plan.max_new
    longest = max(inside, key=lambda i: (size[i], -i))
    rest = [i for i in inside if i != longest]
    pick = T.rng_for(seed, "check").choice(len(rest), check - 1,
                                           replace=False)
    return sorted([longest] + [rest[j] for j in pick])


def _queued_sample(queue: list, seed: int, check: int) -> list[int]:
    """For a backlog: the longest request queued when the window opens
    and ``check - 1`` more of them, drawn from the seed.  All of them are
    admitted, prefilled and served in the window or after it."""
    if check == 0:
        return []
    if len(queue) < check:
        raise harness.BenchError(f"only {len(queue)} requests are queued "
                                 f"when the window opens; the check needs "
                                 f"{check}")
    longest = max(queue, key=lambda r: (len(r.prompt) + r.max_new, -r.uid))
    rest = [r.uid for r in queue if r is not longest]
    pick = T.rng_for(seed, "check").choice(len(rest), check - 1,
                                           replace=False)
    return sorted([longest.uid] + [rest[j] for j in pick])


def _check_calls(prog: Program, d: Drive, n_calls: int) -> None:
    """Every token served in the drive came out of a call the benchmark
    wrapped (a prompt's last chunk, or a decode's active slot), and the
    benchmark saw the logits of every token of the sampled requests."""
    calls = prog.calls[n_calls:]
    made = sum(len(c["lengths"]) if c["kind"] == "decode" else c["final"]
               for c in calls)
    served = sum(len(r.out) for r in d.requests)
    if made != served:
        raise harness.BenchError(
            f"the engine served {served} tokens, the calls the benchmark "
            f"wraps made {made}; it no longer serves through them")
    for uid in prog.sample:
        req = d.requests[uid] if uid < len(d.requests) else None
        seen = len(prog.rows.get(uid, []))
        if req is None or not req.done or seen != len(req.out):
            raise harness.BenchError(
                f"sampled request {uid}: {len(req.out) if req else 0} "
                f"tokens served, {seen} logits seen by the benchmark")


# --------------------------------------------------------------------------
# the comparison
# --------------------------------------------------------------------------

def _blocks(vocab: int, most: int = 32768) -> int:
    n = -(-vocab // most)
    while vocab % n:
        n += 1
    return n


def _acc_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def acc(state, ref, cand, start, toks):
        """Fold one vocabulary block into the per-position sums: squared
        gap, squared reference, the reference's best, its logit at the
        served token (``toks`` >= 0) or at the candidate's best."""
        sq, rr, best, at_tok, cbest, at_cbest = state
        n, width = ref.shape
        rows = jnp.arange(n)
        cand = cand.astype(jnp.float32)
        sq = sq + jnp.sum(jnp.square(cand - ref), axis=1)
        rr = rr + jnp.sum(jnp.square(ref), axis=1)
        best = jnp.maximum(best, jnp.max(ref, axis=1))
        j = toks - start
        here = (j >= 0) & (j < width)
        at_tok = jnp.where(here, ref[rows, jnp.clip(j, 0, width - 1)],
                           at_tok)
        cj = jnp.argmax(cand, axis=1)
        cm = cand[rows, cj]
        up = cm > cbest
        return (sq, rr, best, at_tok, jnp.where(up, cm, cbest),
                jnp.where(up, ref[rows, cj], at_cbest))

    @jax.jit
    def cut(rows, start, width_like):
        return jax.lax.dynamic_slice_in_dim(rows, start,
                                            width_like.shape[1], axis=1)
    return acc, cut


class Reference:
    """The configuration's reference over the sampled requests."""

    def __init__(self, ctx: harness.Ctx):
        import jax
        self.cfg = ctx.cfg
        self.ref = harness.reference_of(ctx.cfg)
        self.init = jax.jit(lambda k: self.ref.init_params(self.cfg, k))
        self.acc, self.cut = _acc_fn()

    def gaps(self, params, prompt: np.ndarray, served: list[int],
             rows: list[np.ndarray] | None, quant: bool = False) -> dict:
        """Per served position, for the engine's logits ``rows`` (or, with
        ``rows`` None, the reference's own control in their place): the
        relative L2 gap of the logits and the gap of the served token's
        (the control's best token's) logit below the reference's best."""
        import jax.numpy as jnp
        cfg = self.cfg
        V, P, n = cfg["vocab_size"], len(prompt), len(served)
        S = cfg["max_len"]
        n_pad = max(128, 1 << (n - 1).bit_length())
        seq = np.zeros(S, np.int32)
        seq[:P] = prompt
        seq[P:P + n - 1] = served[:-1]
        pos = np.full(n_pad, P - 1, np.int32)
        pos[:n] = np.arange(P - 1, P - 1 + n)
        tokens, where = jnp.asarray(seq), jnp.asarray(pos)
        x = self.ref.hidden(params, tokens, where, cfg)
        xc = self.ref.hidden(params, tokens, where, cfg, quant=True) \
            if quant else None
        toks = np.full(n_pad, -1, np.int32)
        if not quant:
            toks[:n] = served
            eng = np.zeros((n_pad, V), rows[0].dtype)
            eng[:n] = np.stack(rows)
            eng = jnp.asarray(eng)
        toks = jnp.asarray(toks)
        z = jnp.zeros(n_pad, jnp.float32)
        state = (z, z, z - jnp.inf, z, z - jnp.inf, z)
        table = params["embed"]["tokens"]
        nb = _blocks(V)
        width = V // nb
        for b in range(nb):
            ref = self.ref.logits_block(table, x, jnp.int32(b * width),
                                        size=width)
            if quant:
                cand = self.ref.logits_block(table, xc, jnp.int32(b * width),
                                             size=width, quant=True)
            else:
                cand = self.cut(eng, jnp.int32(b * width), ref)
            state = self.acc(state, ref, cand, jnp.int32(b * width), toks)
        sq, rr, best, at_tok, _cb, at_cbest = (np.asarray(a)[:n]
                                               for a in state)
        return {"logit_gap": np.sqrt(sq / rr),
                "token_gap": best - (at_cbest if quant else at_tok)}


def compare(per_request: list[dict]) -> dict[str, float]:
    """The numbers compared, each the widest over every served position
    of the sampled requests:
    ``token_gap``  how far the served token's logit lies below the
                   reference's best logit at that position;
    ``logit_gap``  ||engine - reference||_2 / ||reference||_2 of the
                   position's logits.
    Their medians are returned too, for the readings behind the limits."""
    out = {}
    for k in ("token_gap", "logit_gap"):
        v = np.concatenate([g[k] for g in per_request])
        out[k] = float(np.max(v)) if np.all(np.isfinite(v)) else math.inf
        out[k + "_median"] = float(np.median(v))
    return out


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------

def metrics(d: Drive) -> dict[str, float]:
    """The end-to-end metrics of a drive (milliseconds, tokens/s)."""
    out = {}
    window = d.t_close - d.t0
    tokens = sum(1 for s in d.stamps.values() for t in s
                 if d.t0 <= t <= d.t_close)
    out["output_tokens_per_s"] = tokens / window
    ttft = [(r.t_first - r.t_arrival) * 1e3 for r in d.counted
            if r.t_first is not None]
    if ttft:
        out["ttft_p50_ms"] = percentile(ttft, 50)
        out["ttft_p90_ms"] = percentile(ttft, 90)
    itl = [(b - a) * 1e3 for r in d.counted
           for a, b in zip(d.stamps[r.uid], d.stamps[r.uid][1:])
           if b <= d.t_close]
    if itl:
        out["itl_mean_ms"] = sum(itl) / len(itl)
        out["itl_p50_ms"] = percentile(itl, 50)
        out["itl_p99_ms"] = percentile(itl, 99)
    return out


def run(ctx: harness.Ctx, prog: Program | None = None,
        reference: Reference | None = None) -> harness.Outcome:
    """One run; a test may hand in a program and a reference it built."""
    clock = ctx.tracer.clock
    harness.say(f"set-up: imports and device {clock() - ctx.t_start:.3f} s")
    owned = prog is None
    if owned:
        prog = Program(ctx)
    else:
        prog.start(ctx.seed)
    harness.say(f"set-up: engine built {clock() - ctx.t_start:.3f} s")
    warm(prog)
    spec, cfg = ctx.traffic, ctx.cfg
    d = drive(prog, spec, ctx.seed, ctx.seconds, int(spec["check_requests"]))
    setup_s = d.t0 - ctx.t_start
    harness.say(f"compilations in the window: {ctx.counter.n}")
    e2e = metrics(d)
    failed = sum(1 for r in d.counted
                 if r.status == "rejected" or r.finish_reason == "truncated")
    harness.say(f"window {d.t_close - d.t0!r} s: {len(d.counted)} requests "
                f"due, {failed} failed, {e2e!r}")
    mem = harness.peak_bytes(ctx.devices)
    late = d.late_ms or [0.0]
    ttft = [(r.t_first - r.t_arrival) * 1e3 for r in d.counted
            if r.t_first is not None] or [0.0]
    calls = [c for c in prog.calls if c.get("traced")]
    served = {u: (d.requests[u].prompt, list(d.requests[u].out),
                  prog.rows[u]) for u in d.sample}
    if owned:       # the reference runs with the engine gone from the chip
        prog.free()
    del prog
    gc.collect()

    reference = reference or Reference(ctx)
    params = reference.init(harness.seed_key(ctx.seed))
    per = [reference.gaps(params, p, out, rows)
           for p, out, rows in served.values()]
    del params
    numbers = compare(per)
    harness.say(f"compared {sum(len(v[1]) for v in served.values())} served "
                f"tokens of {len(served)} requests: {numbers!r}")
    checks = harness.fmt_checks(numbers, ctx.limits)
    ok = harness.all_within(checks) and ctx.counter.n == 0
    return harness.Outcome(
        correct=ok, attempted=len(d.counted), failed=failed,
        end_to_end={**e2e, "setup_s": setup_s}, checks=checks,
        memory_peak_bytes=mem,
        reading={"calls": calls, "model": lmcost.shapes(cfg),
                 "itl_p99_ms": e2e.get("itl_p99_ms")},
        extra={"lateness_ms": {"p50": percentile(late, 50),
                               "p99": percentile(late, 99)},
               "ttft_ms": {"p50": percentile(ttft, 50),
                           "p90": percentile(ttft, 90)},
               "itl_ms": {k: e2e.get(f"itl_{k}_ms")
                          for k in ("mean", "p50", "p99")}})


def describe(reading) -> str:
    """Each executable's calls in the traced window and their mean device
    milliseconds on the first chip."""
    from benchlib import readers
    ex = readers.serve_executions(reading) or []
    per: dict[str, list[float]] = {}
    for rec, t in ex:
        per.setdefault(rec["kind"], []).append(t * 1e3)
    return "device ms per call: " + ", ".join(
        f"{k} {float(np.mean(v))!r} over {len(v)}" for k, v in sorted(per.items()))


def warm(prog: Program) -> None:
    """Serve one request through every executable: a prompt of two chunks
    and a token, and two decodes."""
    eng = prog.engine
    req = prog.Request(uid=-1, prompt=np.arange(2 * eng.chunk + 1,
                                                dtype=np.int32),
                       max_new=3)
    eng.submit(req)
    for _ in range(8 * (eng.n_slots + 3)):
        if req.done:
            return
        eng.tick()
        prog.after_tick()
    raise harness.BenchError("the engine did not serve the warm-up request")
