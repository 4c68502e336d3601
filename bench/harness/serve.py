"""Serving cells: the program's continuous-batching engine under open-loop
traffic.

Set-up makes the packed 8-bit weights on the device from the seed, builds
the engine as the configuration states, and warms every prefill bucket the
mix can draw plus the decode step. A lead-in of the same traffic fills the
slots; the window then runs ``--seconds``. Requests are submitted when due
and timed from their due time, so a late generator or a stalled engine
shows in the latency of every request behind it.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import lnsgen, reference, spec, traffic, weights

TEMPERATURE = 0.8
CHECK_TOKENS = 300          # served tokens the reference rechecks, at least


def build_engine(cfg, params, opts: dict):
    """The program's engine (the timed path)."""
    from repro.core.lns import LNSFormat
    from repro.core.quantizer import QuantConfig
    from repro.optim.madam import MadamConfig
    from repro.serving import Engine
    q = opts["quant"]
    qcfg = QuantConfig.lns_madam(bits=q["bits"], gamma=q["gamma"])
    mcfg = MadamConfig(update_format=LNSFormat(bits=q["bits"],
                                               gamma=q["gamma"]))
    return Engine(cfg, qcfg, mcfg, params, **opts["engine"])


def needed_buckets(engine, mix: dict) -> List[int]:
    """Every prefill bucket a prompt of the mix's lengths can land in; and,
    where the page pool can run dry (a preempted request is re-prefilled
    with what it generated), every bucket up to prompt plus output."""
    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    if engine.page_size and engine.num_pages < engine.num_slots * (
            -(-min(hi + mix["output"]["max"], engine.max_len)
              // engine.page_size)):
        hi = hi + mix["output"]["max"]
    hi = min(hi, engine.max_len)
    return sorted({engine._bucket(n) for n in range(lo, hi + 1)})


class ServeRun:
    """Set-up, window and check of one serving cell run."""

    def __init__(self, cell: spec.Cell, seed: int):
        self.cell = cell
        self.seed = seed
        self.words = lnsgen.seed_words(seed)
        self.cfg = spec.arch_config(cell.config)
        self.opts = cell.config["serve"]
        self.dims = reference.Dims.from_model(cell.model)
        self.engine = None
        self.states: Dict[int, object] = {}
        self.plan: List[traffic.Planned] = []
        self.counters: Dict[str, float] = {}
        self.decode_contexts: List[List[int]] = []
        self.record_contexts = False

    # -- set-up ------------------------------------------------------------

    def make_params(self):
        q = self.opts["quant"]
        params = jax.jit(lambda w: weights.make_params(
            w, self.cfg, q["bits"], q["gamma"]))(self.words)
        return jax.block_until_ready(params)

    def setup(self, engine=None) -> None:
        """Weights from the seed, then the engine and its warm-up; an
        ``engine`` already built and warmed for this cell is reused with
        the new weights."""
        if engine is not None:
            engine.params = None
            engine.params = self.make_params()
            engine.reset()
            self.engine = engine
            return
        self.engine = build_engine(self.cfg, self.make_params(), self.opts)
        self.warm_up()

    def _request(self, p: traffic.Planned, arrival: float):
        from repro.server.sampling import SamplingParams
        from repro.serving import Request
        samp = None if p.greedy else SamplingParams(
            temperature=TEMPERATURE, seed=p.sample_seed)
        return Request(rid=p.rid, prompt=p.prompt,
                       max_new_tokens=p.max_new_tokens, arrival=arrival,
                       sampling=samp)

    def warm_up(self) -> None:
        eng = self.engine
        rng = np.random.default_rng(self.seed ^ 0x5EED)
        reqs = []
        for i, b in enumerate(needed_buckets(eng, self.cell.traffic)):
            p = traffic.Planned(rid=-1 - i, due=0.0,
                                prompt=rng.integers(1, self.cfg.vocab_size,
                                                    b).tolist(),
                                max_new_tokens=2, greedy=bool(i % 2),
                                sample_seed=i)
            reqs.append(self._request(p, 0.0))
        eng.run(reqs)
        eng.reset()

    # -- window ------------------------------------------------------------

    def _generated(self) -> int:
        return sum(len(rs.generated) for rs in self.states.values())

    def window(self, seconds: float, tracer=None) -> Dict[str, float]:
        """Lead-in, then the window. ``tracer(t)`` is called after each
        engine step with the seconds since the window opened."""
        eng = self.engine
        mix = self.cell.traffic
        lead = traffic.window_start(mix)
        self.plan = traffic.plan(mix, self.seed, seconds, self.cfg.vocab_size)
        requests = [self._request(p, 0.0) for p in self.plan]
        comp0 = (eng.prefill_compiles, eng.decode_compiles)
        e0 = eng.now()
        for r, p in zip(requests, self.plan):
            r.arrival = e0 + p.due
        t_open, t_close = e0 + lead, e0 + lead + seconds
        self.t_open = t_open
        nxt = 0
        opened = False
        base = self._base = {}
        steps_s: List[float] = []      # host time of each window step
        gc_s = _GcClock()
        t_loop, loop_max = time.perf_counter(), 0.0   # loop time outside steps
        while True:
            now = eng.now()
            if not opened and now >= t_open:
                opened = True
                base = self._base = {"gen": self._generated(),
                                     "decode_steps": eng.decode_steps,
                                     "prefills": eng.prefills}
                gc_s.start()
            if now >= t_close:
                t_close = now
                break
            with jax.profiler.TraceAnnotation("bench.submit"):
                while nxt < len(requests) and requests[nxt].arrival <= now:
                    eng.submit(requests[nxt])
                    nxt += 1
            n_fin = len(eng.finished)
            steps0 = eng.decode_steps
            t_step = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.engine_step"):
                did = eng.step()
            t_end = time.perf_counter()
            if opened:
                steps_s.append(t_end - t_step)
                loop_max = max(loop_max, t_step - t_loop)
            t_loop = t_end
            self._track(n_fin)
            if self.record_contexts and eng.decode_steps > steps0:
                self._contexts(n_fin)
            if opened and tracer is not None:
                tracer(eng.now() - t_open)
            if not did:
                wait = min(requests[nxt].arrival if nxt < len(requests)
                           else t_close, t_close) - eng.now()
                if wait > 0:
                    with jax.profiler.TraceAnnotation("bench.idle_wait"):
                        time.sleep(min(wait, 0.002))
        gc_s.stop()
        self.t_open, self.t_close = t_open, t_close
        self.counters = {
            "generated": self._generated() - base.get("gen", 0),
            "decode_steps": eng.decode_steps - base.get("decode_steps", 0),
            "prefills": eng.prefills - base.get("prefills", 0),
            "compiles_in_window": (eng.prefill_compiles - comp0[0]
                                   + eng.decode_compiles - comp0[1]),
            "preemptions": eng.preemptions,
            "admit_failures": eng.admit_failures,
            "submitted": nxt,
            # where host time went, to tell a slow run from a stalled one
            "step_ms_p50": 1e3 * float(np.median(steps_s)) if steps_s else 0,
            "step_ms_max": 1e3 * max(steps_s, default=0.0),
            "steps_over_250ms": sum(1 for x in steps_s if x > 0.25),
            "between_steps_ms_max": 1e3 * loop_max,
            "gc_s": gc_s.total, "gc_ms_max": 1e3 * gc_s.longest,
        }
        return {"window_s": t_close - t_open}

    def host_counters(self) -> Dict[str, int]:
        """Decode steps since the window opened, and the tokens they
        emitted (every token emitted since then, less each first token,
        which a prefill emits)."""
        eng = self.engine
        now = eng.now()
        first = sum(1 for rs in self.states.values()
                    if rs.t_first_token is not None
                    and self.t_open <= rs.t_first_token <= now)
        return {"decode_steps": eng.decode_steps - self._base["decode_steps"],
                "decode_tokens": max(self._generated() - self._base["gen"]
                                     - first, 0)}

    def _track(self, n_fin: int) -> None:
        eng = self.engine
        for rs in eng.scheduler.running.values():
            self.states[rs.request.rid] = rs
        for rs in eng.finished[n_fin:]:
            self.states[rs.request.rid] = rs

    def _contexts(self, n_fin: int) -> None:
        """Context lengths of the rows the last decode step served."""
        eng = self.engine
        rows = list(eng.scheduler.running.values()) + eng.finished[n_fin:]
        self.decode_contexts.append(
            [rs.request.prompt_len + len(rs.generated) - 1 for rs in rows
             if len(rs.generated) > 1])

    # -- end-to-end readings ------------------------------------------------

    def window_requests(self):
        """(state or None, arrival) of every request due in the window."""
        out = []
        by_rid = {p.rid: p for p in self.plan}
        e_due = self.t_open - traffic.window_start(self.cell.traffic)
        for p in self.plan:
            arrival = e_due + p.due
            if self.t_open <= arrival < self.t_close:
                out.append((self.states.get(p.rid), arrival, by_rid[p.rid]))
        return out

    def latencies(self) -> Dict[str, List[float]]:
        """TTFT, TPOT and queue wait of the requests due in the window; a
        request with no first token or admission yet counts its wait until
        the window closed."""
        end = self.t_close
        ttft, tpot, wait = [], [], []
        for rs, arrival, _ in self.window_requests():
            first = rs.t_first_token if rs is not None else None
            ttft.append((first if first is not None else end) - arrival)
            admit = rs.t_admit if rs is not None else None
            wait.append((admit if admit is not None else end) - arrival)
            if rs is None or first is None:
                continue
            n = len(rs.generated)
            fin = rs.t_finish if rs.t_finish is not None else end
            if n >= 2 and fin > first:
                tpot.append((fin - first) / (n - 1))
        return {"ttft": ttft, "tpot": tpot, "queue_wait": wait}

    def free(self) -> None:
        """Drop the engine, its weights and its KV pool (the engine's jitted
        steps refer back to it, so the cycle is collected here)."""
        self.engine = None
        gc.collect()

    # -- correctness --------------------------------------------------------

    def check_sample(self) -> List[dict]:
        """Greedy requests finished by the window's close: the longest,
        then others in an order drawn from the seed, until CHECK_TOKENS
        served tokens."""
        by_rid = {p.rid: p for p in self.plan}
        done = sorted(((rs, by_rid[rid]) for rid, rs in self.states.items()
                       if rs.finish_reason in ("length", "stop", "capacity")
                       and by_rid[rid].greedy), key=lambda x: x[1].rid)
        if not done:
            return []
        longest = max(done, key=lambda x: len(x[1].prompt) + len(
            x[0].generated))
        rng = np.random.default_rng(self.seed ^ 0xC0FFEE)
        order = [longest] + [done[i] for i in rng.permutation(len(done))
                             if done[i] is not longest]
        out, total = [], 0
        for rs, p in order:
            out.append({"rid": p.rid, "prompt": list(p.prompt),
                        "served": [int(t) for t in rs.generated]})
            total += len(rs.generated)
            if total >= CHECK_TOKENS:
                break
        return out


class _GcClock:
    """Seconds the garbage collector ran while started."""

    def __init__(self):
        self.total = self.longest = 0.0
        self._t = None

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            d = time.perf_counter() - self._t
            self.total += d
            self.longest = max(self.longest, d)
            self._t = None

    def start(self):
        gc.callbacks.append(self._cb)

    def stop(self):
        if self._cb in gc.callbacks:
            gc.callbacks.remove(self._cb)


def served_gaps(words, sample: List[dict], dims: reference.Dims,
                store: tuple, fwd: tuple, control: Optional[tuple] = None,
                bucket: int = 256) -> List[float]:
    """Per request, the widest gap by which a served token's reference
    logit lies below the reference's best at its position. With
    ``control`` (a lower-precision grid), the gap of the token the control
    puts first instead."""
    gaps = []
    w = jnp.asarray(words)
    for req in sample:
        seq = req["prompt"] + req["served"][:-1]
        n = len(seq)
        padded = -(-n // bucket) * bucket
        toks = jnp.asarray(seq + [0] * (padded - n), jnp.int32)
        ref = reference.serve_logits(w, toks, n, dims, store, fwd)
        p0 = len(req["prompt"]) - 1
        rows = ref[p0:n]
        best = jnp.max(rows, axis=-1)
        if control is None:
            served = jnp.asarray(req["served"], jnp.int32)
        else:
            ctl = reference.serve_logits(w, toks, n, dims, store, control)
            served = jnp.argmax(ctl[p0:n], axis=-1)
        got = jnp.take_along_axis(rows, served[:, None], axis=-1)[:, 0]
        gaps.append(float(jnp.max(best - got)))
    return gaps


def rehearse(cell: spec.Cell, eng_kw: dict, place):
    """Compile the engine's decode step and largest prefill bucket for a
    described chip; yields (name, compiled)."""
    from repro.core.lns import LNSFormat
    from repro.core.quantizer import QuantConfig
    from repro.models.model import init_caches
    from repro.serving.engine import Engine

    cfg = spec.arch_config(cell.config)
    q = cell.config["serve"]["quant"]
    params = place(jax.eval_shape(lambda w: weights.make_params(
        w, cfg, q["bits"], q["gamma"]), jnp.zeros((2,), jnp.uint32)))
    n_slots, max_len = eng_kw["num_slots"], eng_kw["max_len"]
    page = eng_kw["page_size"]
    caches = place(jax.eval_shape(lambda: init_caches(
        n_slots, max_len, cfg, page_size=page,
        num_pages=eng_kw.get("num_pages"))))
    qcfg = QuantConfig.lns_madam(bits=q["bits"], gamma=q["gamma"])
    eng = Engine.__new__(Engine)
    eng.cfg, eng.qcfg = cfg, qcfg
    from repro.training.steps import build_decode_step
    from repro.server.sampling import sample_logits
    decode = build_decode_step(cfg, qcfg, None)

    def decode_sample(params, caches, batch, pos, samp):
        logits, caches = decode(params, caches, batch, pos)
        return sample_logits(logits, samp, num_codebooks=0,
                             vocab_size=cfg.vocab_size), caches

    i32 = jnp.int32
    max_pages = -(-max_len // page)
    S = jax.ShapeDtypeStruct
    batch = place({"tokens": S((n_slots, 1), i32),
                   "block_tables": S((n_slots, max_pages), i32)})
    samp = place({"temp": S((n_slots,), jnp.float32),
                  "top_k": S((n_slots,), i32),
                  "top_p": S((n_slots,), jnp.float32),
                  "seed": S((n_slots,), jnp.uint32),
                  "step": S((n_slots,), i32)})
    pos = place(S((n_slots,), i32))
    yield "decode_sample", jax.jit(decode_sample, donate_argnums=(1,)).lower(
        params, caches, batch, pos, samp).compile()
    eng._paged = True
    hi = cell.traffic["prompt"]["max"]
    bucket = min(b for b in (16, 32, 64, 128, 256, 512, 1024, 2048)
                 if b >= min(hi, max_len))
    sc = lambda: place(S((), i32))
    yield f"prefill_{bucket}", jax.jit(
        Engine._prefill_paged_impl.__get__(eng), donate_argnums=(1,)).lower(
        params, caches, place(S((1, bucket), i32)), sc(), sc(), sc(),
        place(S((max_pages,), i32)), sc(), sc(), sc()).compile()
