"""Timeliness claim: <0.4 ms/frame (>=2,500 fps) at 100-bit encoding, and the
TPU-mapped throughput of the packed kernels.

The decision pipeline is timed three ways over the same workload
(4096 binary decisions, 2 modalities, 128-bit streams):

* ``seed``    -- the seed composition: three separate launches
  (sne_encode kernel -> pand_popcount kernel -> argmax) with the Pallas
  kernels pinned on (interpret mode on CPU), exactly as the harness shipped.
* ``unfused`` -- the packed-domain composition (counter-based encode ->
  AND -> popcount -> argmax) as jitted jnp stages, each materialising its
  packed intermediate.
* ``fused``   -- one ``bayes_decide`` launch, nothing per-bit materialised.

The printed speedups are the tentpole's acceptance numbers.

A fourth family measures the paper's budget the way it is stated -- per
*frame*, not per batch: ``latency.frame_decide_<scenario>@128bit`` times one
fused single-frame ``CompiledNetwork.decide`` per scenario, retains every
sample, and emits p50/p99 next to the min.  The samples also feed
:class:`~repro.obs.histogram.LatencyHistogram` instances annotated with the
0.4 ms budget, exported as the ``latency_hist.csv`` artifact together with a
traced :class:`~repro.bayesnet.driver.FrameDriver` run exported as
``trace_framedriver.json`` (load it in Perfetto / chrome://tracing to see the
async launch pipeline).  ``check_bench.check_latency_budget`` gates the
p50/p99 of every frame_decide row.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from benchmarks.common import emit, timeit
from repro.core import latency
from repro.kernels.bayes_decide.ops import bayes_decide, bayes_decide_packed
from repro.kernels.pand_popcount.ops import pand_popcount
from repro.kernels.sne_encode.ops import sne_encode

N_DEC = 4096
N_BITS = 128
M, K = 2, 2

# single-frame budget rows: one decision at the paper's ~100-bit operating
# point, across the binary trio + the 4-class categorical scenario
FRAME_N_BITS = 128
FRAME_ITERS = 200
FRAME_SCENARIOS = ("sensor-degradation", "pedestrian-night", "intersection",
                   "obstacle-class")


def run():
    # memristor-substrate model (the paper's own numbers)
    rep = latency.memristor_latency(n_bits=100, n_sne=5)
    emit("latency.memristor@100bit", rep.frame_latency_s * 1e6,
         f"{rep.frame_latency_s*1e3:.2f}ms/frame fps={rep.fps:.0f} "
         f"meets_paper={rep.meets_paper_claim()} "
         f"energy={rep.energy_per_decision_j*1e9:.1f}nJ/decision")
    emit("latency.reference_points", 0.0,
         f"human={latency.HUMAN_REACTION_S} ADAS_fps={latency.ADAS_FPS} "
         f"camera_fps={latency.CAMERA_FPS} edge_net_fps={latency.EDGE_NET_FPS}")

    # TPU mapping: throughput model + measured decision-pipeline timings
    model = latency.tpu_throughput_model(n_bits=N_BITS)
    emit("latency.tpu_model@128bit", 0.0, f"{model:.2e} decisions/s/core (model)")

    key = jax.random.PRNGKey(0)
    p = jax.random.uniform(key, (M, N_DEC, K))

    def decide_seed(p):
        # the composition the seed harness timed: kernel launches pinned on
        streams = sne_encode(key, p, N_BITS, use_kernel=True, interpret=True)
        counts = pand_popcount(
            streams.reshape(M, -1, N_BITS // 32), use_kernel=True, interpret=True
        ).reshape(N_DEC, K)
        return jnp.argmax(counts, -1)

    def decide_unfused(p):
        dec, _ = bayes_decide_packed(key, p, N_BITS)
        return dec

    def decide_fused(p):
        dec, _ = bayes_decide(key, p, N_BITS)
        return dec

    # min-of-N, like the bayesnet rows: a shared-tenant interference spike can
    # run 10-20x slow and poison a small-sample median, but never the min --
    # the speedup ratios below feed the committed perf trajectory, so they
    # must compare machine capability, not scheduler luck.
    us_seed = timeit(jax.jit(decide_seed), p, iters=3, stat="min")
    us_unfused = timeit(jax.jit(decide_unfused), p, warmup=2, iters=15, stat="min")
    us_fused = timeit(jax.jit(decide_fused), p, warmup=2, iters=15, stat="min")

    emit(f"latency.seed_pipeline_{N_DEC}dec@{N_BITS}bit", us_seed,
         f"{N_DEC/(us_seed/1e6):.2e} decisions/s (seed: 3 launches, interpret)")
    emit(f"latency.unfused_packed_{N_DEC}dec@{N_BITS}bit", us_unfused,
         f"{N_DEC/(us_unfused/1e6):.2e} decisions/s (packed stages, jnp)")
    emit(f"latency.packed_pipeline_{N_DEC}dec@{N_BITS}bit", us_fused,
         f"{N_DEC/(us_fused/1e6):.2e} decisions/s (fused bayes_decide; "
         f"paper hardware: 2.5e3 fps)")
    emit("latency.fused_speedup_vs_seed", us_seed / us_fused,
         f"fused is {us_seed/us_fused:.1f}x faster than the seed composition")
    emit("latency.fused_speedup_vs_unfused", us_unfused / us_fused,
         f"fused is {us_unfused/us_fused:.2f}x vs unfused packed stages "
         f"(~1x on CPU where XLA fuses both; the kernel gain shows on TPU)")

    run_frame_budget()


def run_frame_budget(artifact_dir: str = ".") -> None:
    """Per-frame budget rows + the observability artifacts.

    One fused ``decide`` launch per single evidence frame, per scenario --
    the shape of the paper's claim ("every decision inside 0.4 ms"), where
    the batched rows above measure throughput.  All per-iteration samples
    are retained, so the emitted p50/p99 are exact; ``check_bench`` gates
    them (p50 against the budget itself, p99 against budget x a documented
    container multiplier).
    """
    from repro.bayesnet import by_name, compile_network, sample_evidence
    from repro.bayesnet.driver import FrameDriver
    from repro.obs import PAPER_BUDGET_MS, MetricsRegistry, Tracer

    key = jax.random.PRNGKey(0)
    reg = MetricsRegistry()
    for name in FRAME_SCENARIOS:
        spec = by_name(name)
        net = compile_network(spec, n_bits=FRAME_N_BITS)
        ev = sample_evidence(spec, jax.random.PRNGKey(2), 1)
        us = timeit(
            lambda n=net, e=ev: n.decide(key, e),
            warmup=5, iters=FRAME_ITERS, stat="min",
        )
        h = reg.hist(f"frame_decide_{name}", budget_ms=PAPER_BUDGET_MS)
        h.observe_many([u / 1e3 for u in us.samples_us])
        emit(
            f"latency.frame_decide_{name}@{FRAME_N_BITS}bit", us,
            f"1 frame/launch, fused decide | p50 {us.p50:.0f}us "
            f"p99 {us.p99:.0f}us | {h.budget_fraction():.0%} of calls within "
            f"the paper's {PAPER_BUDGET_MS}ms budget",
            extra={"budget_ms": PAPER_BUDGET_MS,
                   "budget_fraction": round(h.budget_fraction(), 4)},
        )

    # traced FrameDriver run -> Perfetto artifact.  96 frames through a
    # 32-lane async driver = 3 pipelined launches; the exported `launch`
    # spans overlap, which is the async pipeline made visible.  The driver
    # shares the registry above, so its frame_ms / launch_ms / watchdog
    # histograms land in the same latency_hist.csv.
    tr = Tracer()
    spec = by_name("pedestrian-night")
    net = compile_network(spec, n_bits=FRAME_N_BITS)
    drv = FrameDriver(net, max_batch=32, salt=0, trace=tr, metrics=reg)
    drv.submit(sample_evidence(spec, jax.random.PRNGKey(3), 96))
    drv.drain_async()
    trace_path = tr.export_chrome_trace(
        os.path.join(artifact_dir, "trace_framedriver.json")
    )
    hist_path = reg.write_hist_csv(os.path.join(artifact_dir, "latency_hist.csv"))
    emit(
        "latency.obs_artifacts", 0.0,
        f"{len(tr.spans)} spans -> {os.path.basename(trace_path)} "
        f"(chrome://tracing / Perfetto) | "
        f"{len(reg.histograms)} histograms -> {os.path.basename(hist_path)}",
        extra={"n_spans": len(tr.spans),
               "driver_launches": reg.count("launches")},
    )


if __name__ == "__main__":
    run()
