"""chip_smoke.py off the chip: it refuses to run without a TPU, and its
control flow is rehearsed at tiny widths (every phase, the multi-chip
checks on two virtual devices).

None of this says anything about the chip: the rehearsal compiles nothing
with Mosaic and checks no program text. The proof is the script's own run
through the chip tool.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, cwd, timeout=120):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def _result_lines(stdout: str) -> list:
    out = []
    for line in stdout.splitlines():
        try:
            doc = json.loads(line)
        except ValueError:
            continue
        if isinstance(doc, dict) and "ok" in doc:
            out.append(doc)
    return out


def test_no_chip_is_a_failure_before_any_work():
    p = _run([SMOKE], cwd=REPO)
    assert p.returncode != 0
    assert "platform=cpu" in p.stdout.splitlines()[0]
    # nothing ran: no phase line, no result line
    assert "PASS" not in p.stdout
    assert not _result_lines(p.stdout)
    assert "no TPU" in p.stderr


@pytest.fixture
def _restore_process_state():
    """The smoke sets the bench dtype policy and installs its meshes."""
    from bigdl_tpu.parallel.engine import Engine
    from bigdl_tpu.tensor import get_policy, set_policy
    old = get_policy()
    yield
    set_policy(old)
    Engine.reset()


def test_rehearsal_runs_every_phase_and_is_not_a_pass(
        capsys, _restore_process_state):
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    assert chip_smoke.main(["--rehearsal"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert lines[0].startswith("device: platform=cpu")
    assert lines[1].startswith("compile cache:")
    passed = [ln.split()[1] for ln in lines if ln.startswith("PASS ")]
    assert passed == ["train-lm", "serve-lm", "train-inception", "kernels"]
    # the multi-chip checks ran on two of the eight virtual devices
    for ln in lines:
        if ln.startswith(("PASS train-lm", "PASS train-inception")):
            assert "2 chip(s)" in ln and "no all-gather" in ln \
                and "one-chip parity" in ln
    # last line says rehearsal; nothing on stdout can be read as a result
    assert lines[-1].startswith("REHEARSAL ONLY")
    assert not _result_lines("\n".join(lines))


def test_compile_cache_is_placed_from_outside():
    """utils/compile_cache.py: a directory configured from outside
    (jax reads JAX_COMPILATION_CACHE_DIR into its config) wins and
    nothing else is set; unset, the cache is the fixed
    <checkout>/.jax_cache; a CPU-pinned process — this suite — gets
    none."""
    from bigdl_tpu.utils import compile_cache

    class Config:
        def __init__(self, cache_dir, platforms):
            self.jax_compilation_cache_dir = cache_dir
            self.jax_platforms = platforms

        def update(self, name, value):
            setattr(self, name, value)

    assert compile_cache.configure(Config("/outside", "")) == "/outside"
    assert compile_cache.configure(Config("/outside", "cpu")) == "/outside"
    fixed = os.path.join(REPO, ".jax_cache")
    assert compile_cache.configure(Config(None, "")) == fixed
    assert compile_cache.configure(Config(None, "tpu")) == fixed
    assert compile_cache.configure(Config(None, "cpu")) is None
    # wherever it is on, the key covers the metadata: a scope is how a
    # trace names the program's operations (tracing.ProgramScopes)
    for config, on in ((Config("/outside", "cpu"), True),
                       (Config(None, ""), True), (Config(None, "cpu"), False)):
        compile_cache.configure(config)
        assert getattr(config, "jax_compilation_cache_include_metadata"
                       "_in_key", False) is on
    # the real thing, in this CPU-pinned process
    assert compile_cache.cache_dir() is None
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
