"""``CompiledNetwork.decide``'s outputs, whose host copies it starts itself.

``decide`` starts each output's device -> host copy before it returns.  That
may change neither the values nor the calling convention: the outputs equal
the jitted program's, called directly, bit for bit; they stay ``jax.Array``s
(``decide`` does not wait on the device); reading one twice gives the same
values.  Host evidence goes to the jitted call as a host array: it gives the
outputs device evidence gives, bit for bit, in ``decide`` and ``run``.  The
sharded program runs in a subprocess with forced host devices, as in
``test_sharded.py``, because jax pins the device count at first init.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.bayesnet import by_name, compile_network, sample_evidence

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..", "src"))
HERE = os.path.dirname(os.path.abspath(__file__))


def check_decide_outputs(**compile_kw):
    """decide == the jitted ``_decide`` on the same key and device evidence.

    Host evidence (a numpy array, a list) and device evidence give the same
    outputs, and ``run`` does as ``decide`` does.
    """
    spec = by_name("intersection-cat")   # a 3-valued query: the (B, n_q, k) layout
    net = compile_network(spec, n_bits=512, **compile_kw)
    ev = np.asarray(sample_evidence(spec, jax.random.PRNGKey(1), 16))
    key = jax.random.PRNGKey(3)
    on_device = jnp.asarray(ev, jnp.int32)
    for call, fn, n_out in ((net.decide, net._decide, 3), (net.run, net._run, 2)):
        want = fn(key, on_device)
        for evidence in (ev, ev.tolist(), on_device):
            out = call(key, evidence)
            assert len(out) == len(want) == n_out
            for got, ref in zip(out, want):
                assert isinstance(got, jax.Array) and not isinstance(got, np.ndarray)
                first = np.asarray(got)
                assert first.dtype == ref.dtype
                np.testing.assert_array_equal(first, np.asarray(ref))
                np.testing.assert_array_equal(np.asarray(got), first)
    return net


SHARDED = f"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, {HERE!r})
from test_decide_outputs import check_decide_outputs
net = check_decide_outputs(devices=4)
assert net.n_shards == 4, net.n_shards
print("ALL OK")
"""


@pytest.mark.parametrize("program", ["fused", "unfused", "sharded"])
def test_decide_outputs_async_and_bit_identical(program):
    if program == "sharded":
        env = dict(os.environ, PYTHONPATH=SRC)
        proc = subprocess.run(
            [sys.executable, "-c", SHARDED], env=env,
            capture_output=True, text=True, timeout=600,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "ALL OK" in proc.stdout
        return
    net = check_decide_outputs(fused=program == "fused")
    assert net.fused == (program == "fused")
