"""Distributed tests: posit-compressed collectives on a simulated 8-device
mesh (subprocess isolation so other tests keep a single-device view), plus
single-process tests for ftz / auto_es / pow2 scaling."""
import json
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.codec import auto_es, posit_decode, posit_encode
from repro.core import ref_codec

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core.types import P8_0, P16_1
from repro.core.codec import posit_encode
from repro.core import ref_codec
from repro.distributed.collectives import (compressed_allreduce,
                                           compressed_psum, quire_psum_posit)

_sm, _sm_kw = jax.shard_map, {"check_vma": False}
mesh = jax.make_mesh((2, 4), ("pod", "data"), devices=jax.devices()[:8])
rng = np.random.default_rng(0)
M = 1 << 14
x = jnp.asarray(rng.normal(0, 1e-3, (8, M)).astype(np.float32))
out = {}

# two-hop compressed allreduce == true sum (within p16 tolerance)
f = jax.jit(_sm(
    lambda v: compressed_allreduce(v, P16_1, "pod"),
    mesh=mesh, in_specs=P(("pod", "data")), out_specs=P(("pod", "data")),
    **_sm_kw))
got = np.asarray(f(x), np.float64)
true = np.tile(x.reshape(2, 4, M).sum(0), (2, 1, 1)).reshape(8, M)
out["allreduce_rel"] = float(np.abs(got - true).mean() / np.abs(true).mean())

# compressed_psum f32 bypass is exact
g = jax.jit(_sm(
    lambda v: compressed_psum(v, None)[0],
    mesh=mesh, in_specs=P(("pod", "data")), out_specs=P(("pod", "data")),
    **_sm_kw))
got2 = np.asarray(g(x), np.float64)
true2 = np.tile(x.astype(np.float64).sum(0), (8, 1))
out["bypass_exact"] = bool(np.allclose(got2, true2, rtol=1e-6))

# error feedback: residual returned and nonzero for p8
h = jax.jit(_sm(
    lambda v, r: compressed_psum(v, P8_0, residual=r)[1],
    mesh=mesh, in_specs=(P(("pod", "data")),) * 2,
    out_specs=P(("pod", "data")), **_sm_kw))
res = np.asarray(h(x, jnp.zeros_like(x)))
out["residual_nonzero"] = bool(np.abs(res).max() > 0)

# quire-domain psum of posit codes is EXACT: bit-identical to the Fraction
# sum of the per-device values with one terminal rounding
Mq = 256
xq = jnp.asarray(rng.normal(0, 1.0, (8, Mq)).astype(np.float32))
codes = posit_encode(xq, 16, 1)
qf = jax.jit(_sm(
    lambda c: quire_psum_posit(c, P16_1, "pod"),
    mesh=mesh, in_specs=P(("pod", "data")), out_specs=P(("pod", "data")),
    **_sm_kw))
got_q = np.asarray(qf(codes)).reshape(2, 4 * Mq)
host = np.asarray(codes).reshape(2, 4 * Mq)
want_q = np.empty(4 * Mq, np.uint16)
for j in range(4 * Mq):
    acc = sum(ref_codec.ref_decode(int(host[d, j]), 16, 1) for d in range(2))
    want_q[j] = ref_codec.ref_encode_exact(acc, 16, 1)
out["quire_psum_exact"] = bool((got_q == want_q[None, :]).all())

# exact compressed_psum: inter hop in the quire domain, still accurate
pe = jax.jit(_sm(
    lambda v: compressed_psum(v, P16_1, exact=True)[0],
    mesh=mesh, in_specs=P(("pod", "data")), out_specs=P(("pod", "data")),
    **_sm_kw))
got_e = np.asarray(pe(x), np.float64)
true_e = np.tile(x.astype(np.float64).sum(0), (8, 1))
rel = np.abs(got_e - true_e).mean() / np.abs(true_e).mean()
out["exact_psum_rel"] = float(rel)
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def child_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                       capture_output=True, text=True, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT ")]
    assert lines, f"child failed:\n{r.stderr[-2000:]}"
    return json.loads(lines[0][7:])


def test_compressed_allreduce_accurate(child_results):
    assert child_results["allreduce_rel"] < 5e-4  # p16 + pow2 scaling


def test_psum_f32_bypass_exact(child_results):
    assert child_results["bypass_exact"]


def test_error_feedback_residual(child_results):
    assert child_results["residual_nonzero"]


def test_quire_psum_bitexact(child_results):
    """Quire-domain psum == Fraction-exact sum + one rounding, bit-for-bit."""
    assert child_results["quire_psum_exact"]


def test_exact_compressed_psum_accurate(child_results):
    """exact=True inter hop: only the per-device encode rounds, so the error
    is bounded by the p16 encode alone (comfortably under the two-hop path)."""
    assert child_results["exact_psum_rel"] < 5e-4


# ------------------------------------------------------- single-process -------
def test_ftz_matches_rne_to_zero_union():
    """ftz encode == RNE against {0} U posits (checked vs oracle + midpoint)."""
    n, es = 16, 1
    from repro.core.types import PositFmt
    fmt = PositFmt(n, es)
    xs = np.array([0.0, fmt.minpos / 4, fmt.minpos / 2, fmt.minpos * 0.51,
                   fmt.minpos, -fmt.minpos / 4, -fmt.minpos / 2], np.float32)
    got = np.asarray(posit_encode(jnp.asarray(xs), n, es, ftz=True)).astype(int)
    # below or at half-minpos -> 0; above -> minpos code (1 / 2^n-1 for neg)
    want = [0, 0, 0, 1, 1, 0, 0]
    assert list(got) == want, got
    # far from zero, ftz must be identical to standard encode
    rng = np.random.default_rng(0)
    big = jnp.asarray(rng.normal(0, 10, 4096).astype(np.float32))
    assert (np.asarray(posit_encode(big, n, es, ftz=True)) ==
            np.asarray(posit_encode(big, n, es))).all()


@pytest.mark.parametrize("scale,expect_small_es", [(1.0, True), (1e30, False)])
def test_auto_es_scales_with_range(scale, expect_small_es):
    rng = np.random.default_rng(1)
    x = jnp.asarray((rng.normal(0, scale, 1024)).astype(np.float32))
    es = int(auto_es(x, 16))
    assert 0 <= es <= 3
    if expect_small_es:
        assert es == 0
    else:
        assert es >= 2


def test_auto_es_covers_range():
    """Chosen es must put max|x| within posit range (no saturation at the top)."""
    for scale in (1e-6, 1e-2, 1.0, 1e4, 1e12):
        rng = np.random.default_rng(2)
        x = jnp.asarray((rng.normal(0, scale, 512)).astype(np.float32))
        es = int(auto_es(x, 16))
        smax = 14 << es
        amax = float(jnp.max(jnp.abs(x)))
        assert abs(np.log2(amax)) <= smax, (scale, es)


def test_decode_encode_with_ftz_roundtrip():
    """ftz only affects the sub-minpos band: all posit values round-trip."""
    codes = jnp.asarray(np.arange(65536, dtype=np.uint16))
    vals = posit_decode(codes, 16, 2)
    back = posit_encode(vals, 16, 2, ftz=True)
    assert (np.asarray(back) == np.asarray(codes)).all()
