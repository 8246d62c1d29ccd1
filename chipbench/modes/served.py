"""Served: one vehicle's camera ticks through ``BayesRouter`` -> ``FrameDriver`` -> ``CompiledNetwork.run``.

Open loop, one host thread: every tick of the mix's schedule
(``loadgen.tick_schedule``) is submitted when it is due, one ``submit`` per
network; then ``pump`` and ``harvest``, and each result is stamped as the
host receives it.  A frame's latency runs from its tick's due time to that
stamp, so the generator's own lag counts.

The router sheds and degrades nothing: each frame's deadline is the whole
run (window and drain) and the degrade ladder is off, so a host stall makes
frames late, never failed, and its whole cost shows in the latency.  The
configuration's ``deadline_ms`` (one camera period) is the target the notes
count late frames against.  After the window no new tick falls due; the
loop pumps until every submitted frame is terminal or ``drain_s`` has
passed.  A frame not answered ``OK`` by then counts as failed, at a latency
above every answered one: the whole time the run observed.  A frame never
answered is missing for the check.

Set-up registers the networks and launches every (network, bucket) twice,
so the window compiles nothing.  Under ``--trace 1`` the router gets a
``Tracer(annotate=True)`` and a ``MetricsRegistry``; untraced runs carry
neither.  Every frame answered ``OK`` is compared with the reference.
"""

from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from chipbench import harness, loadgen, program
from chipbench.stats import percentile

OK = "OK"
SETUP_DEADLINE_MS = 600e3    # set-up launches may compile: no deadline to speak of


class Mode:
    def __init__(self, ctx):
        self.ctx = ctx
        self.mix = ctx.traffic
        self.max_batch = int(ctx.config["max_batch"])
        self.deadline_s = float(ctx.config["deadline_ms"]) / 1e3
        self.networks = list(self.mix["object_networks"]) + list(self.mix["scene_networks"])
        missing = set(self.networks) - set(ctx.tenants)
        if missing:
            raise harness.BenchError(f"the mix names networks the config lacks: {sorted(missing)}")
        self.rng = np.random.default_rng([ctx.seed, 5])
        self.tracer = self.registry = None
        self.host_span_names = frozenset(
            {"router.pump", "step", "pack", "dispatch", "run", "run.evidence", "run.dispatch"}
            if ctx.traced else ())
        self.counts = {}

    def _buckets(self):
        b = 1
        while b < self.max_batch:
            yield b
            b <<= 1
        yield self.max_batch

    def setup(self) -> dict:
        import jax

        from repro.obs import MetricsRegistry, Tracer
        from repro.serve.router import BayesRouter, RouterPolicy

        ctx = self.ctx
        t0 = time.perf_counter()
        if ctx.traced:
            self.tracer, self.registry = Tracer(annotate=True), MetricsRegistry()
        self.router = BayesRouter(
            RouterPolicy(max_degrade=0),
            base_key=jax.random.PRNGKey(int(self.rng.integers(1 << 31))),
            n_bits=ctx.program_n_bits, max_batch=self.max_batch, retry=None, fault=None,
            drift=None, trace=self.tracer, metrics=self.registry,
            max_cached_tenants=len(ctx.tenants),
        )
        for t in ctx.tenants:
            if self.router.register(program.spec(ctx.docs[t])) != t:
                raise harness.BenchError(f"network file {t!r} names another network")
        self.ticks = loadgen.tick_schedule(self.mix, ctx.nets, ctx.seconds, ctx.seed)
        t1 = time.perf_counter()
        if ctx.require_tpu:
            t = self.networks[0]
            net = dataclasses.replace(self.router.tenant(t).driver(0)[0].net, trace=None)
            ctx.check_kernel(net.run, self.router.base_key, ctx.nets[t].sample_rows(self.rng, 1))
        # every program the window can run: each network at each bucket, twice,
        # so the launch-time floor that admission reads is a warm launch's,
        # not a compile's
        for b in [*self._buckets()] * 2:
            for t in ctx.tenants:
                self.router.submit(t, ctx.nets[t].sample_rows(self.rng, b),
                                   deadline_ms=SETUP_DEADLINE_MS)
            bad = {r.status for r in self.router.drain().values()} - {OK}
            if bad:
                raise harness.BenchError(f"set-up launches ended {sorted(bad)}")
        # set-up's own garbage is collected in set-up: left pending, it set off
        # a full collection of the heap JAX built (about 145 ms) early in the window
        gc.collect()
        t2 = time.perf_counter()
        return {"plan_s": t1 - t0, "warm_s": t2 - t1}

    def _launches(self) -> dict:
        return {t: sum(d.launches for d in self.router.tenant(t).drivers.values())
                for t in self.ctx.tenants}

    def window(self) -> None:
        ctx, rec, router, ticks = self.ctx, self.ctx.rec, self.router, self.ticks
        drain_s = float(self.mix["drain_s"])
        patience_ms = (ctx.seconds + drain_s) * 1e3   # the router's deadline: it sheds nothing
        sizes = [sum(len(rows) for _, rows in tick.frames) for tick in ticks]
        stamp = np.full(sum(sizes), np.inf)   # host time each frame's result was received
        got = [None] * len(stamp)             # and the result; frames in schedule order
        subs = []                             # (due, host time submitted, frames) a submit
        base, sent, answered = None, 0, 0     # a frame's index is its rid less ``base``
        n_spans = len(self.tracer.spans) if self.tracer is not None else 0
        counts0 = dict(self.registry.counters) if self.registry is not None else {}
        compiles0 = self.tracer.count("run.compiles") if self.tracer is not None else 0
        launches0 = self._launches()
        clog = harness.CompileLog()
        clog.install()
        try:
            t_start = time.perf_counter()
            close = t_start + ctx.seconds + drain_s
            i, n = 0, len(ticks)
            while True:
                now = time.perf_counter()
                while i < n and t_start + ticks[i].due_s <= now:
                    d = t_start + ticks[i].due_s
                    with ctx.span("bench.submit"):
                        for net, rows in ticks[i].frames:
                            now = time.perf_counter()
                            rids = router.submit(net, rows, deadline_ms=patience_ms)
                            base = rids[0] if base is None else base
                            if rids[0] != base + sent:
                                raise harness.BenchError("the router's rids are not consecutive")
                            subs.append((d, now, len(rids)))
                            sent += len(rids)
                    i += 1
                    if i == n:
                        close = max(close, now + drain_s)
                if answered < sent:
                    router.pump()
                    fresh = router.harvest()
                    now = time.perf_counter()
                    for rid, res in fresh.items():
                        stamp[rid - base] = now
                        got[rid - base] = res
                    answered += len(fresh)
                elif i < n:
                    with ctx.span("bench.idle"):
                        time.sleep(max(0.0, t_start + ticks[i].due_s - time.perf_counter()))
                if (i == n and answered == sent) or time.perf_counter() > close:
                    break
            t_end = time.perf_counter()   # what is still owed has failed
        finally:
            clog.remove()
        self.got = got
        due = t_start + np.repeat([tick.due_s for tick in ticks], sizes)
        status = np.asarray([r.status if r is not None else "no answer" for r in got[:sent]]
                            + ["never submitted"] * (len(got) - sent))
        ok = status == OK
        cap = (t_end - t_start) * 1e3    # above every answered frame's latency
        lat = np.where(ok, (stamp - due) * 1e3, cap)
        names, counts = np.unique(status, return_counts=True)
        rec.t_window, rec.t_end = t_start, t_end
        rec.attempted, rec.decisions = len(got), int(ok.sum())
        rec.failed = rec.attempted - rec.decisions
        rec.latency_ms = lat.tolist()
        rec.lag_ms = [(t - d) * 1e3 for d, t, k in subs for _ in range(k)]
        rec.answered = answered
        self.missing = rec.attempted - answered
        first, last = (lat[(due - t_start) * 4 // ctx.seconds == q] for q in (0, 3))
        launches = self._launches()
        self.counts = {
            "ticks": n, "frames": rec.attempted, "frames answered": answered,
            "by status": {str(k): int(v) for k, v in zip(names, counts)},
            f"frames answered past the {self.deadline_s * 1e3:g} ms deadline":
                int((ok & (stamp - due > self.deadline_s)).sum()),
            "compile events in the window": len(clog.events),
            "p50 ms, first and last quarter": [percentile(q, 50) if q.size else None
                                               for q in (first, last)],
            "launches per network": {t: launches[t] - launches0[t] for t in launches},
        }
        if self.tracer is not None:
            rec.program_spans = [(s.name, s.t_start, s.t_end)
                                 for s in self.tracer.spans[n_spans:] if s.done]
            rec.program_counts = {k: v - counts0.get(k, 0)
                                  for k, v in self.registry.counters.items()}
            buckets = {int(k[len("bucket_"):]): v for k, v in rec.program_counts.items()
                       if k.startswith("bucket_") and v}
            self.counts["launches per bucket"] = dict(sorted(buckets.items()))
            self.counts["run.compiles in the window"] = self.tracer.count("run.compiles") - compiles0

    def compare(self, comparison) -> None:
        self.router = self.tracer = self.registry = None   # the program's state goes first
        kept = {}
        j = 0
        for tick in self.ticks:
            for net, rows in tick.frames:
                for row, res in zip(rows, self.got[j:j + len(rows)]):
                    if res is not None and res.status == OK:
                        kept.setdefault(net, []).append((row, res.post, res.accepted))
                j += len(rows)
        for net, frames in kept.items():
            ev, post, acc = zip(*frames)
            comparison.add(self.ctx.nets[net], np.stack(ev), np.stack(post), np.asarray(acc))
        comparison.missing = self.missing

    def notes(self) -> dict:
        return {**self.counts, "program n_bits": self.ctx.program_n_bits}
