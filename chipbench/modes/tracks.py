"""Tracks: one vehicle's object tracker, each frame carrying its track's class belief.

The served loop (``modes/served.py``, reused as it is) drives the window: open
loop, one host thread, every tick submitted when it is due, latency from the
tick's due time to the host receiving the result.  What this mode adds:

* the networks are registered tracked: each tenant's root named in the
  configuration's ``tracked`` is its state, carried by the router's driver
  in a belief table of ``max_tracks`` rows on the device, with the
  transition ``transition_stay`` on the diagonal and the rest split evenly;
* the schedule (:func:`track_schedule`) gives each frame its track slot and
  a fresh-track flag, and every submit passes them on;
* set-up launches every (network, bucket) twice on fresh tracks, then resets
  the tables, so the window starts with no track and compiles nothing;
* the comparison holds each frame answered ``OK`` to the reference network
  under the prior predicted from its track's previous answered belief,
  recomputed by ``chipbench/track_reference.py`` without the program.  A
  track whose earlier frame was not answered ``OK`` is not compared from
  there on.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import time
from collections import defaultdict
from typing import Dict, List

import numpy as np

from chipbench import harness, loadgen, program
from chipbench.modes import served
from chipbench.track_reference import Tracked

OK = served.OK


@dataclasses.dataclass
class Submit:
    """One submit of the schedule: a network's frames of one tick, with their tracks."""

    net: str
    slots: np.ndarray     # table row of each frame's track
    fresh: np.ndarray     # the frame starts its track
    uids: np.ndarray      # the track's id over the whole run (a slot is reused)


def track_schedule(mix: dict, refs: Dict[str, Tracked], seconds: float, seed: int):
    """``(ticks, submits, slots_needed)`` of a window of ``seconds``, from the seed.

    ``cameras`` cameras at ``camera_hz``, evenly staggered; Poisson(``objects_
    per_tick_mean``) objects a tick, the same multiset for every seed in its
    own order (``loadgen.poisson_quantiles``).  Camera ``c``'s slot ``(c, j)``
    holds a track until it ends, with probability ``1 / track_mean_ticks``
    at each tick of its camera, seen or not (an object the camera misses for
    a tick keeps its track).  Object ``j`` of the tick continues the slot's
    track if it is live, else starts a fresh track there.  Each network
    draws a track's true class from its published prior at the start and
    moves it by the transition at each of its frames, the step the tracker
    predicts; the frame's evidence is drawn from the network with the root
    clamped to that class.
    """
    rng = np.random.default_rng([seed, 6])
    cams = int(mix["cameras"])
    rate = cams * float(mix["camera_hz"])
    n = int(math.ceil(seconds * rate))
    objects = rng.permutation(loadgen.poisson_quantiles(float(mix["objects_per_tick_mean"]), n))
    per_cam = int(objects.max()) if n else 0
    end_p = 1.0 / float(mix["track_mean_ticks"])
    nets = list(mix["object_networks"])
    cum_prior = {t: np.cumsum(refs[t].published) for t in nets}
    cum_trans = {t: np.cumsum(refs[t].trans, axis=1) for t in nets}
    live = np.zeros((cams, max(per_cam, 1)), bool)
    uid = np.zeros((cams, max(per_cam, 1)), np.int64)
    cls = {t: np.zeros((cams, max(per_cam, 1)), np.int64) for t in nets}
    next_uid = 0
    objs = []                                   # (slot, fresh, uid, {net: class})
    for i, k in enumerate(objects.tolist()):
        c = i % cams
        live[c] &= rng.random(live.shape[1]) >= end_p
        for j in range(k):
            fresh = not live[c, j]
            if fresh:
                live[c, j] = True
                uid[c, j] = next_uid
                next_uid += 1
            for t in nets:
                cum = cum_prior[t] if fresh else cum_trans[t][cls[t][c, j]]
                cls[t][c, j] = min(int(np.searchsorted(cum, rng.random(), side="right")),
                                   refs[t].k - 1)
            objs.append((c * per_cam + j, fresh, int(uid[c, j]),
                         {t: int(cls[t][c, j]) for t in nets}))
    rows = {t: np.zeros((len(objs), len(refs[t].doc["evidence"])), np.int32) for t in nets}
    for t in nets:
        by_class = defaultdict(list)
        for o, obj in enumerate(objs):
            by_class[obj[3][t]].append(o)
        for s, where in sorted(by_class.items()):
            net = refs[t].clamped(s)
            rows[t][where] = net.pattern_rows(
                rng.choice(net.p_e.size, size=len(where), p=net.p_e / net.p_e.sum()))
    slot = np.asarray([o[0] for o in objs], np.int64)
    fresh = np.asarray([o[1] for o in objs], bool)
    uids = np.asarray([o[2] for o in objs], np.int64)
    ticks, submits, at = [], [], 0
    for i, k in enumerate(objects.tolist()):
        sl = slice(at, at + k)
        ticks.append(loadgen.Tick(i / rate, k, [(t, rows[t][sl]) for t in nets] if k else []))
        if k:
            submits += [Submit(t, slot[sl], fresh[sl], uids[sl]) for t in nets]
        at += k
    return ticks, submits, cams * per_cam


class _TrackedSubmits:
    """The router as the served loop sees it: each submit also carries its frames' tracks.

    The loop submits in schedule order, so the n-th ``submit`` is the
    schedule's n-th; everything else goes to the router itself.
    """

    def __init__(self, router, submits: List[Submit]):
        self._router = router
        self._submits = iter(submits)

    def submit(self, net, rows, deadline_ms=None):
        s = next(self._submits)
        if s.net != net or len(s.slots) != len(rows):
            raise harness.BenchError("the served loop submitted out of schedule order")
        return self._router.submit(net, rows, deadline_ms=deadline_ms,
                                   track_ids=s.slots, fresh=s.fresh)

    def __getattr__(self, name):
        return getattr(self._router, name)


class Mode(served.Mode):
    def __init__(self, ctx):
        super().__init__(ctx)
        cfg = ctx.config
        self.max_tracks = int(cfg["max_tracks"])
        roots = dict(zip(ctx.tenants, cfg["tracked"]))
        if len(roots) != len(ctx.tenants):
            raise harness.BenchError("the config names no tracked root for some tenant")
        stay = float(cfg["transition_stay"])
        self.refs = {t: Tracked(ctx.docs[t], roots[t], stay) for t in ctx.tenants}
        try:
            self.specs = {
                t: dataclasses.replace(program.spec(ctx.docs[t]),
                                       state=(roots[t], self.refs[t].trans.tolist()))
                for t in ctx.tenants
            }
        except TypeError as exc:
            raise harness.BenchError(f"the program has no tracked networks ({exc})") from exc
        if ctx.traced:
            self.host_span_names = self.host_span_names | {"track.index"}

    def setup(self) -> dict:
        import jax

        from repro.obs import MetricsRegistry, Tracer
        from repro.serve.router import BayesRouter, RouterPolicy

        ctx = self.ctx
        t0 = time.perf_counter()
        if ctx.traced:
            self.tracer, self.registry = Tracer(annotate=True), MetricsRegistry()
        router = BayesRouter(
            RouterPolicy(max_degrade=0),
            base_key=jax.random.PRNGKey(int(self.rng.integers(1 << 31))),
            n_bits=ctx.program_n_bits, max_batch=self.max_batch, retry=None, fault=None,
            drift=None, trace=self.tracer, metrics=self.registry,
            max_cached_tenants=len(ctx.tenants),
        )
        for t in ctx.tenants:
            if router.register(self.specs[t], max_tracks=self.max_tracks) != t:
                raise harness.BenchError(f"network file {t!r} names another network")
        self.ticks, self.submits, needed = track_schedule(
            self.mix, self.refs, ctx.seconds, ctx.seed)
        if needed > self.max_tracks:
            raise harness.BenchError(
                f"the schedule needs {needed} track rows, the config holds {self.max_tracks}")
        if self.max_batch > self.max_tracks:
            raise harness.BenchError("max_batch exceeds max_tracks: set-up fills a launch")
        drivers = [router.tenant(t).driver(0)[0] for t in ctx.tenants]
        t1 = time.perf_counter()
        if ctx.require_tpu:
            net = dataclasses.replace(drivers[0].net, trace=None)
            ctx.check_kernel(net.run, router.base_key, ctx.nets[ctx.tenants[0]].sample_rows(self.rng, 1),
                             net.new_table(self.max_tracks), np.zeros(1, np.int32), np.ones(1, bool))
        # every program the window can run, twice, on fresh tracks
        for b in [*self._buckets()] * 2:
            for t in ctx.tenants:
                router.submit(t, ctx.nets[t].sample_rows(self.rng, b),
                              deadline_ms=served.SETUP_DEADLINE_MS,
                              track_ids=np.arange(b), fresh=np.ones(b, bool))
            bad = {r.status for r in router.drain().values()} - {OK}
            if bad:
                raise harness.BenchError(f"set-up launches ended {sorted(bad)}")
        for d in drivers:
            d.reset_tracks()
        self.router = _TrackedSubmits(router, self.submits)
        gc.collect()
        t2 = time.perf_counter()
        return {"plan_s": t1 - t0, "warm_s": t2 - t1}

    def compare(self, comparison) -> None:
        self.router = self.tracer = self.registry = None   # the program's state goes first
        belief = {}                # (network, track uid) -> belief, None once a frame failed
        groups = defaultdict(list)
        broken = edge = held = 0
        rows = [row for tick in self.ticks for _, frames in tick.frames for row in frames]
        j = 0
        for s in self.submits:
            ref = self.refs[s.net]
            for n in range(len(s.slots)):
                res, row = self.got[j], rows[j]
                j += 1
                key = (s.net, int(s.uids[n]))
                if s.fresh[n]:
                    pred = ref.published
                else:
                    prev = belief.get(key)
                    if prev is None:
                        broken += 1
                        continue
                    pred = ref.predict(prev)
                if res is None or res.status != OK:
                    belief[key] = None
                    broken += 1
                    continue
                edge += (not s.fresh[n]) and ref.at_edge(pred)
                held += res.accepted == 0
                groups[(s.net, ref.thresholds(pred))].append((row, res.post, res.accepted))
                belief[key] = ref.belief(res.post, res.accepted, pred)
        for (net, thr), frames in groups.items():
            ev, post, acc = zip(*frames)
            comparison.add(self.refs[net].under(thr), np.stack(ev), np.stack(post), np.asarray(acc))
        comparison.missing = self.missing
        fresh = int(sum(s.fresh.sum() for s in self.submits))
        self.counts.update({
            "frames fresh / carried": [fresh, int(sum(len(s.slots) for s in self.submits)) - fresh],
            "frames held (accepted 0, kept the prediction)": int(held),
            "frames not compared (an earlier frame of the track not OK)": broken,
            "frames compared at a DAC rounding edge": int(edge),
            "distinct predicted priors compared": len(groups),
        })
        counts = getattr(self.ctx.rec, "program_counts", None)
        if counts is not None:
            self.counts["track counters (traced)"] = {
                k: counts.get(f"track.{k}", 0) for k in ("carried", "fresh", "held", "deferred")}
