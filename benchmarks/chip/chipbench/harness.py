"""Builds the served path from a configuration file and drives it open loop.

The system under test is the program's own path: ``FleetRouter.submit``
routes each request with the paper's threshold policy to one of two pools,
each a ``PagedContinuousBatcher`` on one shared ``InferenceEngine``, whose
paged steps call the Pallas paged decode kernel on a TPU. The harness
submits each request at its due time and, between arrivals, ticks every
busy pool's ``step()`` in turn, as ``FleetRouter.drain()`` does with
handoffs pending. It never calls ``drain()``: that runs pools to
completion and cannot hold a window.

After each tick it stamps, on the host clock, when a request took a lane
and when each of its tokens appeared in ``out_tokens``. A tick ends in the
program's own host sync, so a stamp is a time at which the host holds the
token. A token that the final prefill chunk yields and the decode token of
the same tick carry the same stamp.
"""
from __future__ import annotations

import contextlib
import dataclasses
import tempfile
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Optional

import jax
import numpy as np

from chipbench import trace as T
from chipbench.e2e import Record
from chipbench.flops import window
from chipbench.weights import DTYPES

GRACE_S = 60.0          # after the window, wait at most this for first tokens
PROGRAM_RMS_EPS = 1e-5  # the program's RMSNorm epsilon, which it fixes


def program_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file: its named base
    with every size the file gives. Refuses a file the program cannot
    serve as written."""
    from repro.configs import get_config
    base = get_config(conf["program_config"])
    if (base.family, base.activation, base.norm, base.pos_emb) != \
            ("dense", "swiglu", "rmsnorm", "rope"):
        raise ValueError(f"{base.name}: the reference covers dense SwiGLU "
                         f"RMSNorm RoPE decoders only")
    if conf["rms_norm_eps"] != PROGRAM_RMS_EPS:
        raise ValueError(f"the program's RMSNorm uses eps {PROGRAM_RMS_EPS}, "
                         f"the file says {conf['rms_norm_eps']}")
    return dataclasses.replace(
        base, num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        head_dim=conf.get("head_dim"), rope_theta=float(conf["rope_theta"]),
        qkv_bias=conf["attention_bias"],
        sliding_window=window(conf),
        tie_embeddings=conf["tie_word_embeddings"])


def build_router(conf: dict, params):
    """The served path over the given weights, as ``launch.serve`` builds
    it: one engine behind a ``FleetRouter`` over ``tpu_fleet()``'s
    (efficiency, performance) pair, with a paged batcher per pool that can
    hold every lane at ``max_len`` at once."""
    from repro.core.scheduler import kv_blocks_needed
    from repro.core.systems import tpu_fleet
    from repro.serving.engine import InferenceEngine
    from repro.serving.router import FleetRouter
    sv, rt = conf["serving"], conf["serving"]["router"]
    cfg = program_config(conf)
    engine = InferenceEngine(cfg, params, max_len=sv["max_len"],
                             dtype=DTYPES[sv["dtype"]])
    eff, perf = tpu_fleet()
    router = FleetRouter(cfg, {eff.name: eff, perf.name: perf},
                         {eff.name: engine, perf.name: engine},
                         policy=rt["policy"], t_in=rt["t_in"], axis=rt["axis"],
                         counts={eff.name: 4, perf.name: 1})
    lanes, bs = sv["lanes_per_pool"], sv["block_size"]
    router.attach_batchers(
        lanes, paged=True, block_size=bs, chunk=sv["chunk"],
        num_blocks=lanes * kv_blocks_needed(sv["max_len"], bs) + 1)
    return router


def warm_up(router, conf: dict) -> None:
    """Compile what the window runs: both paged steps and the small device
    ops a tick issues for 1 to ``lanes`` lanes finishing prefill at once.
    Both pools share the engine and their caches' shapes, so one pool
    warms both. Prompts are shorter than a block, so no prefix block is
    left registered."""
    from repro.serving.batching import Request
    sv = conf["serving"]
    cb = next(iter(router.batchers.values()))
    m = min(sv["block_size"] - 1, sv["chunk"])
    rid = -1
    for k in range(1, sv["lanes_per_pool"] + 1):
        for _ in range(k):
            cb.submit(Request(rid, np.zeros((m,), np.int32), max_new_tokens=2))
            rid -= 1
        cb.run()
    jax.block_until_ready(cb.cache)


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache while
    on: in the window there should be none."""

    def __init__(self):
        self.on, self.events = False, []
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event: str, duration: float, **_):
        if self.on and event.endswith("backend_compile_duration"):
            self.events.append((event, duration))


class Tracer:
    """Profiles the last ``seconds`` of the window and names the host work
    around each call into the program."""

    def __init__(self, window_s: float, seconds: float):
        self.start_s = max(0.0, window_s - seconds)
        self.stop_s = window_s
        self.active = self.done = False
        self.logdir = tempfile.mkdtemp(prefix="chipbench_trace_")

    def poll(self, now_s: float) -> None:
        if not self.active and not self.done and now_s >= self.start_s:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # the harness's spans suffice
            jax.profiler.start_trace(self.logdir, profiler_options=opts)
            self.active = True
        elif self.active and now_s >= self.stop_s:
            self.stop()

    def stop(self) -> None:
        if self.active:
            jax.profiler.stop_trace()
            self.active, self.done = False, True

    def span(self, name: str):
        return jax.profiler.TraceAnnotation(name)

    def events(self):
        return T.read_xplane(T.find_xplane(self.logdir))


@dataclass
class Served:
    """What one window gave, in seconds from its start."""
    records: list
    window_s: float
    end_s: float                       # end of observation, after grace
    submit_s: list = field(default_factory=list)     # (due_s, duration)
    late_s: list = field(default_factory=list)       # submit - due
    decode_calls: list = field(default_factory=list)  # (t, live lanes)
    traced_calls: list = field(default_factory=list)  # while profiling
    ticks: int = 0


def serve_window(router, traffic, conf: dict, seconds: float, *,
                 tracer: Optional[Tracer] = None) -> tuple:
    """Offer the traffic for ``seconds`` and record. Returns (the window's
    start on the host clock, ``Served``)."""
    clock = time.perf_counter
    chunk = conf["serving"]["chunk"]
    pools = list(router.batchers.items())
    pending = {n: deque() for n, _ in pools}
    running = {n: [] for n, _ in pools}
    out = Served(records=[], window_s=seconds, end_s=seconds)
    span = tracer.span if tracer else (lambda name: contextlib.nullcontext())
    backlog = traffic.process == "backlog"
    due = [] if backlog else traffic.requests_due_before(seconds + GRACE_S)
    nxt = 0

    def submit(req, now_s):
        t = clock()
        with span("submit"):
            routed = router.submit(req.prompt, req.n)
        dt = clock() - t
        rec = Record(req.index, routed.pool, req.m, req.n,
                     due_s=now_s if backlog else req.due_s,
                     submit_s=t - t0, prompt=req.prompt, req=routed.request,
                     in_window=not backlog and req.due_s < seconds)
        out.records.append(rec)
        out.submit_s.append((rec.due_s, dt))
        out.late_s.append(rec.submit_s - rec.due_s)
        pending[routed.pool].append(rec)

    def stamp(name, cb, t_s):
        q = pending[name]
        for _ in range(len(q) - len(cb.queue)):      # FIFO admission
            rec = q.popleft()
            rec.admit_s = t_s
            if backlog:
                rec.in_window = t_s < seconds
            running[name].append(rec)
        lanes, prefills = [], []
        for rec in running[name]:
            if rec.prefilled < rec.m:                # one chunk this tick
                c = min(chunk, rec.m - rec.prefilled)
                prefills.append((rec.prefilled, c))
                rec.prefilled += c
            k = len(rec.req.out_tokens)
            new = k - len(rec.token_s)
            if new:
                if len(rec.token_s) + new > 1:       # a decode token came
                    lanes.append(rec.m + k - 1)     # its context length
                rec.token_s += [t_s] * new
        running[name] = [r for r in running[name] if not r.req.done]
        if lanes:
            out.decode_calls.append((t_s, len(lanes)))
        if tracer is not None and tracer.active:
            out.traced_calls += [("prefill", p) for p in prefills]
            if lanes:
                out.traced_calls.append(("decode", lanes))

    stream = 0
    t0 = clock()
    while True:
        now = clock() - t0
        if tracer is not None:
            tracer.poll(now)
        if backlog:
            while now < seconds and any(len(cb.queue) < traffic.depth_per_pool
                                        for _, cb in pools):
                submit(traffic.request(stream), now)
                stream += 1
        else:
            while nxt < len(due) and due[nxt].due_s <= now:
                submit(due[nxt], now)
                nxt += 1
        if now >= seconds:
            waiting = [r for r in out.records if r.in_window and not r.token_s]
            if not waiting or now >= seconds + GRACE_S:
                out.end_s = now
                break
        busy = [(n, cb) for n, cb in pools if cb.busy]
        if not busy:
            wake = due[nxt].due_s if nxt < len(due) else seconds + GRACE_S
            with span("wait_arrival"):
                time.sleep(max(0.0, min(wake, seconds + GRACE_S) - now))
            continue
        for name, cb in busy:
            with span("tick." + name):
                cb.step()
            stamp(name, cb, clock() - t0)
            out.ticks += 1
    if tracer is not None:
        tracer.stop()
    return t0, out
