"""Replay: logged evidence through ``CompiledNetwork.decide``, batch after batch.

The config's networks are cycled, one launch of ``frames_per_chip x chips``
frames each, with at most ``in_flight`` launches dispatched and not yet
fetched; every launch's posteriors, decisions and accepted counts are fetched
to the host, one ``np.asarray`` each.  Each launch draws its own entropy: its key
is ``(seed word, launch index)``.  A sample of each network's launches, drawn
from the seed, is kept for the check: ``check_frames`` frames in all, and at
least one launch of every network.
"""

from __future__ import annotations

import time
from collections import deque

import numpy as np

from chipbench import loadgen, program


class Mode:
    host_span_names = frozenset()

    def __init__(self, ctx):
        self.ctx = ctx
        self.mix = ctx.traffic
        self.frames = int(self.mix["frames_per_chip"]) * ctx.chips
        self.in_flight = int(self.mix["in_flight"])
        self.rng = np.random.default_rng([ctx.seed, 3])
        self.base = np.uint32(self.rng.integers(1 << 32))
        self.kept = {t: [] for t in ctx.tenants}   # (batch, post, decisions, accepted)
        self.launches = 0

    def _key(self, i: int) -> np.ndarray:
        return np.asarray([self.base, i & 0xFFFFFFFF], np.uint32)

    def setup(self) -> dict:
        ctx = self.ctx
        t0 = time.perf_counter()
        self.nets = {
            t: program.compile_network(
                program.spec(ctx.docs[t]), n_bits=ctx.program_n_bits,
                devices=ctx.chips if ctx.chips > 1 else None,
            )
            for t in ctx.tenants
        }
        self.batches = loadgen.replay_batches(self.mix, ctx.nets, ctx.tenants, ctx.chips, ctx.seed)
        t1 = time.perf_counter()
        if ctx.require_tpu:
            t = ctx.tenants[0]
            ctx.check_kernel(self.nets[t].decide, self._key(0), self.batches[t][0])
        # every program the window runs, once: one shape per network
        for t in ctx.tenants:
            for x in self.nets[t].decide(self._key(0), self.batches[t][0]):
                np.asarray(x)
        t2 = time.perf_counter()
        return {"plan_s": t1 - t0, "warm_s": t2 - t1}

    def window(self) -> None:
        ctx, rec = self.ctx, self.ctx.rec
        order = ctx.tenants
        n_batches = int(self.mix["batches_per_network"])
        keep = max(1, int(self.mix["check_frames"]) // (self.frames * len(order)))
        nodes = {t: len(ctx.nets[t].nodes) for t in order}
        pending = deque()
        fetched = bits = 0
        i = 0
        t_start = time.perf_counter()
        end = t_start + ctx.seconds
        while True:
            if time.perf_counter() < end:
                t = order[i % len(order)]
                b = (i // len(order)) % n_batches
                with ctx.span("bench.dispatch"):
                    out = self.nets[t].decide(self._key(i), self.batches[t][b])
                pending.append((i, t, b, out))
                i += 1
                if len(pending) < self.in_flight:
                    continue
            elif not pending:
                break
            j, t, b, out = pending.popleft()
            with ctx.span("bench.fetch"):
                post, dec, acc = (np.asarray(x) for x in out)
            fetched += acc.shape[0]
            bits += acc.shape[0] * self.nets[t].n_bits * nodes[t]
            # a reservoir sample of each network's launches for the check
            kept, n = self.kept[t], j // len(order)
            if len(kept) < keep:
                kept.append((b, post, dec, acc))
            else:
                r = int(self.rng.integers(n + 1))
                if r < keep:
                    kept[r] = (b, post, dec, acc)
        rec.t_window, rec.t_end = t_start, time.perf_counter()
        self.launches = i
        rec.decisions = rec.attempted = fetched
        rec.failed = i * self.frames - fetched
        rec.launch_bits = float(bits)

    def compare(self, comparison) -> None:
        self.nets = None   # the program's state goes before the reference runs
        for t, kept in self.kept.items():
            for b, post, dec, acc in kept:
                if len(acc) == len(self.batches[t][b]):   # a short launch is counted as missing
                    comparison.add(self.ctx.nets[t], self.batches[t][b], post, acc, dec)
        comparison.missing = self.ctx.rec.failed

    def notes(self) -> dict:
        return {
            "launches": self.launches,
            "frames per launch": self.frames,
            "launches checked": sum(len(k) for k in self.kept.values()),
            "program n_bits": self.ctx.program_n_bits,
        }
