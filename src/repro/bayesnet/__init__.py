"""Bayesian-network compiler: declarative DAG specs lowered to the packed
stochastic domain (DESIGN.md SS8-SS10).

    spec.py       NetworkSpec / Node -- the source language; nodes carry a
                  cardinality k (binary = the k=2 special case)
    compile.py    lowering: fused net_sweep (production; devices= shards the
                  frame axis bit-identically, decide rides an in-kernel
                  argmax epilogue) or per-node rng/node_mux/cordiv packed
                  programs (verification baseline); k-ary nodes ride value
                  bit-planes + 8-bit DAC CDFs; noise= perturbs every DAC
                  threshold through the crossbar non-ideality model
    noise.py      NoiseModel: plan-build-time device-to-device / read-noise /
                  IR-drop / stuck-at perturbation of the DAC thresholds
    analytic.py   exact mixed-radix enumeration oracle + ancestral sampling;
                  noise= builds the perturbed-CPT oracle twin;
                  filter_posteriors is the tracked networks' reference
    reliability.py decision-margin confidence signal, RetryPolicy, and the
                  flip-rate / harvest reliability statistics
    scenarios.py  5-12 node driving networks over data/detection statistics
                  (binary quartet + categorical trio)
    driver.py     serve-style continuous batching of evidence frames, with
                  non-blocking dispatch (step(block=False) / drain_async),
                  power-of-two launch buckets for short tails,
                  confidence-gated retry with escalating n_bits (retry=),
                  online drift monitoring (drift=) and between-launch
                  hot-swap of recalibrated plans (swap_net)
    calibrate.py  calibrate-back loop: CPT fitting from synthetic detection
                  rollouts, drift-compensated threshold programming, and
                  hot recalibration of live drivers (DESIGN §15)
"""

from repro.bayesnet.analytic import (  # noqa: F401
    filter_posteriors,
    make_posterior_fn,
    sample_evidence,
)
from repro.bayesnet.calibrate import (  # noqa: F401
    calibration_report,
    compensated_program,
    fit_scene_config,
    recalibrate_driver,
    recalibrated_network,
)
from repro.bayesnet.compile import (  # noqa: F401
    CompiledNetwork,
    compile_network,
    posterior_argmax,
    sweep_plan,
)
from repro.bayesnet.driver import FrameDriver  # noqa: F401
from repro.bayesnet.noise import NoiseModel, perturbed_cdf_rows  # noqa: F401
from repro.bayesnet.reliability import (  # noqa: F401
    HEALTH_DRIFTING,
    HEALTH_HEALTHY,
    HEALTH_RECALIBRATING,
    HEALTH_STATES,
    DriftMonitor,
    DriftPolicy,
    FrameReport,
    ReliabilityStats,
    RetryPolicy,
    decision_confidence,
    flip_rate,
)
from repro.bayesnet.scenarios import SCENARIOS, by_name  # noqa: F401
from repro.bayesnet.spec import NetworkSpec, Node  # noqa: F401
