#!/usr/bin/env python3
"""Drive compile -> decide -> serve once on a TPU and check every result.

    python chip_smoke.py               # one chip: device, batch, single-frame, served
    python chip_smoke.py --four-chips  # only the sharded decide on 4 chips vs 1

One process holds the chip for the whole run; nothing is started in a child.
Phases (one chip):

* device: JAX must see a TPU (no CPU fallback), the Pallas kernels must be
  compiled rather than interpreted, and the decide program must contain the
  ``tpu_custom_call`` of the fused sweep.
* batch: every scenario at 1024 frames x 4096 bits.  Kernel counts equal the
  jnp reference bit for bit on the same device, in-kernel decisions equal
  ``posterior_argmax`` of the posterior, and posteriors sit within the
  enumeration oracle's stochastic bound.
* single frame: every scenario at batch 1 x 128 bits, same identities.
* served: one ``BayesRouter`` with all 7 scenarios as tenants; backlogs of
  1, 2, 4, ..., 1024 frames per tenant launch every driver bucket once.  Every
  frame must end ``OK`` (none lost, unreliable, degraded or rejected) and the
  posteriors pass the oracle check.

Timings printed on the way are information only.  The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``; any
failed check exits non-zero before it is printed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.bayesnet import (  # noqa: E402
    SCENARIOS,
    by_name,
    compile_network,
    make_posterior_fn,
    posterior_argmax,
    sample_evidence,
    sweep_plan,
)
from repro.bayesnet.reliability import STATUS_OK  # noqa: E402
from repro.kernels import backend  # noqa: E402
from repro.kernels.bayes_decide import bayes_decide  # noqa: E402
from repro.kernels.net_sweep import net_sweep  # noqa: E402
from repro.obs import MetricsRegistry  # noqa: E402
from repro.serve.router import BayesRouter, RouterPolicy  # noqa: E402

N_BITS = 4096
BATCH = 1024
SINGLE_BITS = 128
MAX_BATCH = 1024
FOUR_CHIP_BATCH = 4096
# Oracle check (see oracle_check): family-wise false-alarm rate of the
# per-entry binomial tail test, and the band for the mean squared z-score.
ORACLE_ALPHA = 1e-6
Z2_BAND = (0.8, 1.25)


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def info(msg: str) -> None:
    print(msg, flush=True)


def oracle_check(spec, ev, post, accepted) -> str:
    """Posteriors against exact enumeration of the DAC-quantised network.

    Given ``n`` accepted bits, a value's count is Binomial(n, p_exact), so:

    * every entry passes a Chernoff tail test, ``n * KL(k/n || p) <=
      ln(N / ORACLE_ALPHA)`` over the N entries compared -- valid for rare
      events too, where the normal z-score's tails are far too thin (at 1024
      frames a max |z| < 5 fails on chance alone);
    * the mean of ``z**2`` over entries with ``n p (1-p) >= 5`` lies in
      ``Z2_BAND``: it is exactly 1 in expectation for any binomial, so a
      small bias across many frames shows here.

    Returns a summary line; raises SmokeFailure on a failed check.
    """
    exact, _ = make_posterior_fn(spec, dac_quantize=True)(ev)
    post = np.asarray(post, np.float64)
    exact = np.asarray(exact, np.float64)
    n = np.asarray(accepted, np.float64).reshape((-1,) + (1,) * (post.ndim - 1))
    n = np.broadcast_to(n, post.shape)
    keep = n > 0
    check(bool(np.any(keep)), f"{spec.name}: no frame accepted a bit")
    n, p, q = n[keep], exact[keep], np.rint(post[keep] * n[keep]) / n[keep]
    eps = 1e-300
    kl = (q * np.log(np.maximum(q, eps) / np.maximum(p, eps))
          + (1 - q) * np.log(np.maximum(1 - q, eps) / np.maximum(1 - p, eps)))
    worst = float(np.max(n * kl))
    limit = float(np.log(q.size / ORACLE_ALPHA))
    check(worst <= limit,
          f"{spec.name}: binomial tail test failed (n*KL {worst:.2f} > {limit:.2f})")
    var = p * (1 - p)
    normal = n * var >= 5
    z2 = float(np.mean((q - p)[normal] ** 2 * n[normal] / var[normal]))
    check(Z2_BAND[0] <= z2 <= Z2_BAND[1],
          f"{spec.name}: mean z^2 {z2:.3f} outside {Z2_BAND}")
    return (f"oracle: {q.size} entries, max n*KL {worst:.3f} (limit {limit:.3f}), "
            f"mean z^2 {z2:.4f} over {int(normal.sum())}")


def check_decide(spec, n_bits, ev, key, *, oracle: bool):
    """One scenario's decide launch against the jnp reference and the oracle."""
    name = spec.name
    net = compile_network(spec, n_bits=n_bits)
    check(net.fused, f"{name}: not the fused lowering")
    t0 = time.perf_counter()
    decide = jax.jit(net.decide).lower(key, ev).compile()
    t_compile = time.perf_counter() - t0
    t0 = time.perf_counter()
    post, dec, acc = jax.block_until_ready(decide(key, ev))
    t_run = time.perf_counter() - t0
    plan = sweep_plan(spec, spec.queries, spec.evidence)
    kern = net_sweep(key, ev, plan=plan, n_bits=n_bits, decide=True)
    ref = net_sweep(key, ev, plan=plan, n_bits=n_bits, decide=True, use_kernel=False)
    for part, k, r in zip(("numer", "denom", "decisions"), kern, ref):
        check(
            np.array_equal(np.asarray(k), np.asarray(r)),
            f"{name}@{n_bits}: kernel {part} differ from the jnp reference",
        )
    check(np.array_equal(np.asarray(acc), np.asarray(kern[1])),
          f"{name}@{n_bits}: decide's accepted counts differ from the sweep's")
    check(np.array_equal(np.asarray(dec), np.asarray(posterior_argmax(post))),
          f"{name}@{n_bits}: decisions differ from posterior_argmax")
    check(bool(np.all(np.isfinite(np.asarray(post)))),
          f"{name}@{n_bits}: non-finite posterior")
    line = f"  {name}: compile {t_compile:.3f}s, first run {t_run:.3f}s"
    if oracle:
        line += ", " + oracle_check(spec, ev, post, acc)
    info(line)


def phase_device(four: bool = False):
    devs = jax.devices()
    d = devs[0]
    info(f"device: platform={d.platform} kind={d.device_kind} count={len(devs)}")
    check(d.platform == "tpu", f"JAX sees no TPU (platform {d.platform!r})")
    if four:
        check(len(devs) >= 4, f"--four-chips needs 4 devices, JAX sees {len(devs)}")
    check(backend.default_interpret() is False, "Pallas kernels would run interpreted")
    spec = by_name("pedestrian-night")
    net = compile_network(spec, n_bits=N_BITS, devices=4 if four else None)
    ev = sample_evidence(spec, jax.random.PRNGKey(1), 8)
    hlo = jax.jit(net.decide).lower(jax.random.PRNGKey(0), ev).as_text()
    check("tpu_custom_call" in hlo, "decide program holds no Pallas TPU kernel")
    return d.platform, d.device_kind, len(devs)


def phase_batch():
    # the fusion operator (paper Fig 4) at the README quickstart shape
    key = jax.random.PRNGKey(2)
    p = jax.random.uniform(jax.random.PRNGKey(1), (2, 4096, 2))
    kern = bayes_decide(key, p, n_bits=128)
    ref = bayes_decide(key, p, n_bits=128, use_kernel=False)
    for part, k, r in zip(("decisions", "counts"), kern, ref):
        check(np.array_equal(np.asarray(k), np.asarray(r)),
              f"bayes_decide kernel {part} differ from the jnp reference")
    info("  bayes_decide (2, 4096, 2)@128: kernel == reference")
    for name in sorted(SCENARIOS):
        spec = by_name(name)
        ev = sample_evidence(spec, jax.random.PRNGKey(1), BATCH)
        check_decide(spec, N_BITS, ev, jax.random.PRNGKey(0), oracle=True)


def phase_single():
    for name in sorted(SCENARIOS):
        spec = by_name(name)
        ev = sample_evidence(spec, jax.random.PRNGKey(2), 1)
        check_decide(spec, SINGLE_BITS, ev, jax.random.PRNGKey(3), oracle=False)


def phase_served():
    """All 7 scenarios as tenants of one router; every bucket 1..1024 launches."""
    metrics = MetricsRegistry()
    router = BayesRouter(
        # no degradation ladder, nothing shed: this phase checks the device
        policy=RouterPolicy(capacity=1 << 30, deadline_mult=1e7),
        base_key=jax.random.PRNGKey(7), n_bits=N_BITS, max_batch=MAX_BATCH,
        metrics=metrics,
    )
    names = sorted(SCENARIOS)
    buckets = []
    b = 1
    while b <= MAX_BATCH:
        buckets.append(b)
        b <<= 1
    submitted = {name: [] for name in names}   # (rids, evidence) per round
    for r, size in enumerate(buckets):
        t0 = time.perf_counter()
        for i, name in enumerate(names):
            ev = np.asarray(sample_evidence(
                by_name(name), jax.random.PRNGKey(1000 + 16 * r + i), size))
            rids = router.submit(name, ev, deadline_ms=3_600_000.0)
            submitted[name].append((rids, ev))
        router.drain()
        info(f"  round bucket {size}: {time.perf_counter() - t0:.3f}s")
    n_sub = sum(len(rids) for runs in submitted.values() for rids, _ in runs)
    counts = router.status_counts()
    info(f"  statuses: {counts}, submitted {n_sub}, terminal {len(router.results)}")
    check(len(router.results) == n_sub, f"{n_sub - len(router.results)} frames lost")
    check(counts[STATUS_OK] == n_sub, f"not every frame ended OK: {counts}")
    for name in names:
        t = router.tenant(name)
        check(set(t.drivers) == {0}, f"{name}: served on degraded rungs {set(t.drivers)}")
        check(not t.drivers[0].launch_failures,
              f"{name}: launch failures {t.drivers[0].launch_failures}")
    for size in buckets:
        got = metrics.count(f"bucket_{size}")
        check(got == len(names), f"bucket {size} launched {got} times, want {len(names)}")
    for name in names:
        rids = [rid for rs, _ in submitted[name] for rid in rs]
        ev = np.concatenate([e for _, e in submitted[name]])
        post = np.stack([router.results[rid].post for rid in rids])
        acc = np.asarray([router.results[rid].accepted for rid in rids])
        summary = oracle_check(by_name(name), ev, post, acc)
        info(f"  served {name}: {len(rids)} frames, {summary}")


def phase_four_chips():
    """The README's scale-out path: decide sharded over 4 chips == 1 chip."""
    for name in sorted(SCENARIOS):
        spec = by_name(name)
        ev = sample_evidence(spec, jax.random.PRNGKey(1), FOUR_CHIP_BATCH)
        key = jax.random.PRNGKey(0)
        net4 = compile_network(spec, n_bits=N_BITS, devices=4)
        net1 = compile_network(spec, n_bits=N_BITS)
        check(net4.n_shards == 4, f"{name}: {net4.n_shards} shards, want 4")
        t0 = time.perf_counter()
        out4 = jax.block_until_ready(net4.decide(key, ev))
        t4 = time.perf_counter() - t0
        out1 = net1.decide(key, ev)
        for part, a, b in zip(("posterior", "decisions", "accepted"), out4, out1):
            check(np.array_equal(np.asarray(a), np.asarray(b)),
                  f"{name}: sharded {part} differ from single-device")
        info(f"  {name}: sharded decide bit-identical, first call {t4:.3f}s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded decide and its 1-chip twin")
    args = ap.parse_args(argv)
    cache = backend.enable_compile_cache()
    info(f"compile cache: {cache}")
    phases = [("device", lambda: phase_device(args.four_chips))]
    if args.four_chips:
        phases.append(("four-chips", phase_four_chips))
    else:
        phases += [("batch", phase_batch), ("single", phase_single),
                   ("served", phase_served)]
    device = None
    try:
        for name, fn in phases:
            t0 = time.perf_counter()
            info(f"phase {name}")
            out = fn()
            if name == "device":
                device = out
            info(f"phase {name}: ok, wall {time.perf_counter() - t0:.3f}s")
    except SmokeFailure as exc:
        print(f"FAILED: {exc}", file=sys.stderr, flush=True)
        return 1
    platform, kind, count = device
    print(json.dumps(
        {"ok": True, "device": {"platform": platform, "kind": kind, "count": count}}
    ), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
