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


@pytest.fixture
def two_spans_against_one(monkeypatch):
    """``compare(layer, params, state, x, first, interpret)``: an
    ``ExpertShare`` whose sorted rows are cut at ``first`` (None: where
    its own rule cuts them) against the same layer with ALL rows in one
    span, on megablox interpreted or ``lax.ragged_dot``: asserts that
    the result, the state a training step hands on (but for the two keys
    that say how the rows were cut) and every gradient — each parameter's,
    the input's, and the combine weights' taken alone — are equal, and
    that those two keys and the ``moe_share`` instant say which spans
    ran; returns (the cut layer's scalar state as floats, spans run,
    the live rows, the first span as cut)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.observability import trace
    from bigdl_tpu.parallel import expert

    own_rule, in_chunks = expert._chunk_rows, expert._in_chunks

    def run(layer, params, state, x, first):
        monkeypatch.setattr(expert, "_chunk_rows",
                            own_rule if first is None else lambda *a: first)
        handed = []

        def keep(chunk, first, weights, tokens, cw, where, live):
            handed.append((first, weights, tokens, cw, where, live))
            return in_chunks(chunk, first, weights, tokens, cw, where, live)

        trace.clear()
        trace.enable()
        try:
            with monkeypatch.context() as m:
                m.setattr(expert, "_in_chunks", keep)
                y, new = layer.apply(params, state, x, training=True)
            (said,) = [e["args"] for e in trace.to_dict()["traceEvents"]
                       if e["name"] == "moe_share"]
        finally:
            trace.disable()
        grads = jax.grad(lambda p, x: jnp.sum(layer.apply(
            p, state, x, training=True)[0] ** 2), argnums=(0, 1))(params, x)
        (cut, weights, tokens, cw, where, live), = handed
        d_cw = jax.grad(lambda cw: jnp.sum(in_chunks(
            layer._chunk, cut, weights, tokens, cw, where, live) ** 2))(cw)
        leaves = dict({jax.tree_util.keystr(path): leaf for path, leaf in
                       jax.tree.leaves_with_path(grads[0])},
                      x=grads[1], combine_weights=d_cw)
        return y, new, leaves, said, int(live), cut

    def compare(layer, params, state, x, first, interpret):
        monkeypatch.setattr(expert, "grouped_matmul", functools.partial(
            expert.grouped_matmul, interpret=interpret))
        y, new, grads, said, live, cut = run(layer, params, state, x, first)
        rows = x.size // x.shape[-1] * layer.top_k
        one_y, one_new, one_grads, _, one_live, _ = run(layer, params, state,
                                                        x, rows)
        assert live == one_live
        scale = float(jnp.abs(one_y).max())
        assert float(jnp.abs(y - one_y).max()) <= 1e-5 * scale
        new, one_new = (jax.tree.map(np.asarray, s) for s in (new, one_new))
        assert float(one_new["moe_chunks_run"]) == 1.0
        assert float(one_new["moe_product_row_share"]) == pytest.approx(
            live / rows)
        for key in new.keys() - {"moe_chunks_run", "moe_product_row_share"}:
            np.testing.assert_array_equal(new[key], one_new[key], key)
        assert grads.keys() == one_grads.keys()
        for name, want in one_grads.items():
            norm = float(jnp.linalg.norm(want))
            assert float(jnp.linalg.norm(grads[name] - want)) \
                <= 1e-5 * norm, name
        ran, worked_on = (2, rows) if live > cut else (1, cut)
        assert float(new["moe_chunks_run"]) == ran
        assert float(new["moe_product_row_share"]) == pytest.approx(
            live / worked_on)
        assert (said["chunk_rows"], said["chunks"], said["rest_rows"]) == (
            cut, 1 + (cut < rows), rows - cut)
        return ({key: float(val) for key, val in new.items()
                 if val.ndim == 0}, ran, live, cut)

    return compare


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
