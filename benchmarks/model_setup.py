"""What every driver kind does before it builds the system under test:
the device check, the compile cache, the dtype policy, and a model whose
weights are made on the device in one jitted call from the seed.
"""
from __future__ import annotations

import os

from benchmarks.manifest import REPO

#: jax's persistent compilation cache, at a FIXED path inside the
#: checkout (the path is part of the cache key). bigdl_tpu would choose
#: the same directory by itself (utils/compile_cache.py); the benchmark
#: sets it first so the choice is the benchmark's.
CACHE_DIR = os.path.join(REPO, ".jax_cache")


class NoAccelerator(RuntimeError):
    """jax found no TPU, or fewer chips than the cell asks for."""


def configure_cache() -> str:
    """Turn jax's persistent cache on BEFORE anything compiles.
    ``JAX_COMPILATION_CACHE_DIR`` wins where it is set."""
    import jax
    if jax.config.jax_compilation_cache_dir is None:
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    # small programs (weight init, gathers) compile in under jax's
    # default 1 s threshold and would be compiled again by every run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir


def pick_devices(chips: int, rehearsal: bool) -> list:
    """Exactly ``chips`` devices of a TPU — or of whatever is there in a
    rehearsal. No fallback: a measurement path that finds no chip
    fails."""
    import jax
    devs = jax.devices()
    if not rehearsal and devs[0].platform != "tpu":
        raise NoAccelerator(
            f"jax reports platform {devs[0].platform!r}: the benchmark "
            "measures on the TPU and does not fall back (--rehearsal "
            "rehearses the control flow elsewhere and is never a result)")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chip(s), jax "
                            f"reports {len(devs)}")
    return list(devs[:chips])


def set_dtype_policy(policy: dict) -> None:
    import jax.numpy as jnp

    from bigdl_tpu.tensor import DTypePolicy, set_policy
    set_policy(DTypePolicy(
        param_dtype=jnp.dtype(policy["param_dtype"]),
        compute_dtype=jnp.dtype(policy["compute_dtype"]),
        activation_dtype=jnp.dtype(policy["activation_dtype"])))


def init_params(model, seed: int, device=None):
    """The model's initial parameters: ONE jitted call of the program's
    pure ``init`` from ``PRNGKey(seed)`` — the same values
    ``model.materialize(PRNGKey(seed))`` would give (same key folding),
    made on the device in the policy's parameter dtype."""
    import jax
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)

    def make():
        try:
            return jax.jit(model.init)(key)
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e) \
                    or not getattr(model, "modules", None):
                raise
        # the one program held every leaf's float32 draw at once: make
        # the top-level children one by one, with the keys
        # Container.init folds (the same values)
        return {str(i): jax.jit(m.init)(jax.random.fold_in(key, i))
                for i, m in enumerate(model.modules)}

    if device is not None:
        with jax.default_device(device):
            return make()
    return make()


def materialize_lean(model, seed: int, device=None):
    """``model`` with parameters and state bound and NO ``grad_params``
    trees.

    ``Module.materialize`` allocates a zero gradient tree at every
    container level (nn/module.py:90, :373): 4.2 x the weights in zeros,
    which at OPT widths is an allocation failure. Neither ``Optimizer``
    training nor serving reads them. This is the benchmark's ONE
    workaround of the program (PERF.md, list for later program PRs: it
    goes when ``grad_params`` is allocated on first use): the weights
    come from the program's own pure ``init`` and are bound with
    ``sync`` — exactly what ``utils/file.load_module`` does after
    ``_strip_runtime`` — so every module's ``grad_params`` stays None.
    """
    params = init_params(model, seed, device)
    model.sync(params, model.init_state())
    return model


def unbind(model) -> None:
    """Let go of the model's parameter and state arrays (every level of
    the module tree holds a reference)."""
    model.params = None
    model.state = None
    for child in getattr(model, "modules", []):
        unbind(child)


def held_bytes(model) -> int:
    """Bytes of every distinct array the module tree holds on to:
    parameters, state and gradient trees at every level (children alias
    their parent's parameter leaves; each array counts once)."""
    import jax
    seen: dict = {}

    def walk(m):
        for tree in (m.params, m.state, m.grad_params):
            for leaf in jax.tree.leaves(tree):
                if hasattr(leaf, "nbytes"):
                    seen[id(leaf)] = leaf.nbytes
        for child in getattr(m, "modules", []):
            walk(child)

    walk(model)
    return sum(seen.values())


def live_device_bytes(device=None) -> int:
    """Bytes of every live jax array (on ``device``, or anywhere)."""
    import jax
    total = 0
    for a in jax.live_arrays():
        for s in a.addressable_shards:
            if device is None or s.device == device:
                total += s.data.nbytes
    return total


def tree_bytes(tree) -> int:
    import jax
    return sum(x.nbytes for x in jax.tree.leaves(tree))


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip, as the backend reports it;
    the live-array total where it reports nothing (CPU rehearsal)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    if max(peaks, default=0) > 0:
        return max(peaks)
    return max((live_device_bytes(d) for d in devices), default=0)


def log(msg: str) -> None:
    """Progress and the earlier output lines go to stdout too, flushed;
    the LAST line is the result and nothing follows it."""
    print(msg, flush=True)
