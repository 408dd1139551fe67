"""Test configuration.

Mirrors the reference's distributed-test strategy (SURVEY §4.3): the
reference runs Spark ``local[1]`` with 4 logical partitions to test the
distributed path without a cluster; here we force an 8-virtual-device CPU
platform so mesh/pjit/collective code paths run exactly as they would on an
8-chip TPU slice. The real chip is for chip_smoke.py and bench.py.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _deterministic_host_rng():
    """Host-side RNG is process-global (reference RandomGenerator thread-local
    singleton); reseed per test so shuffle-order-sensitive tests are
    isolated from tests that reseed it."""
    from bigdl_tpu.utils.random import RandomGenerator
    RandomGenerator.set_seed(1)
    yield


@pytest.fixture
def kernel_calls():
    """``kernel_calls(jaxpr) -> {kernel name: occurrences}`` of the
    Pallas calls in a jaxpr, those inside nested jaxprs (a checkpoint
    region, a custom_vjp) included."""
    import jax

    def count(jaxpr, found=None):
        found = {} if found is None else found
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params["name"]
                found[name] = found.get(name, 0) + 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                count(sub, found)
        return found

    return count


@pytest.fixture(autouse=True, scope="module")
def _dtype_policy_put_back():
    """The dtype policy is process-global and a benchmark rehearsal sets
    it (bfloat16 compute); a file that leaves it set changes the numbers
    of whatever file the same xdist worker runs next. Every file hands
    the policy on as it found it."""
    from bigdl_tpu.tensor import get_policy, set_policy
    old = get_policy()
    yield
    set_policy(old)
