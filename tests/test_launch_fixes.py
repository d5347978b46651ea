"""Launch-layer regression tests: the serve decode-loop off-by-one and the
dry-run XLA_FLAGS clobbering fix."""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np

from repro.launch.serve import decode_loop

# ---------------------------------------------------------------------------
# serve: decode loop
# ---------------------------------------------------------------------------


class _FakeDecode:
    """Deterministic decode stub: step i's argmax is (prev_token + 1); logits
    for a step are *only* correct if that step's call actually happened."""

    def __init__(self, vocab: int = 17):
        self.vocab = vocab
        self.calls = 0
        self.positions = []

    def __call__(self, params, tok, caches, pos):
        self.calls += 1
        self.positions.append(int(pos))
        nxt = (np.asarray(tok)[:, 0] + 1) % self.vocab
        logits = np.full((tok.shape[0], 1, self.vocab), -1e9, np.float32)
        logits[np.arange(tok.shape[0]), 0, nxt] = 0.0
        return jnp.asarray(logits), caches + 1


def test_decode_loop_runs_exactly_max_new_minus_one_steps():
    """max_new tokens out, max_new-1 decode calls — the final step's logits
    are consumed, not computed-and-discarded (the off-by-one regression)."""
    decode = _FakeDecode()
    first = jnp.asarray([[3], [10]], jnp.int32)
    gen, caches, steps = decode_loop(decode, None, 0, first,
                                     prompt_len=5, max_new=4)
    assert gen.shape == (2, 4)
    assert steps == decode.calls == 3          # not 4: no wasted step
    assert caches == 3                          # cache advanced per real step
    # greedy chain: every emitted token after the first came from a decode
    np.testing.assert_array_equal(gen[0], [3, 4, 5, 6])
    np.testing.assert_array_equal(gen[1], [10, 11, 12, 13])
    # positions advance from prompt_len
    assert decode.positions == [5, 6, 7]


def test_decode_loop_single_token_needs_no_decode():
    decode = _FakeDecode()
    gen, _, steps = decode_loop(decode, None, 0,
                                jnp.asarray([[2]], jnp.int32), 3, 1)
    assert gen.shape == (1, 1) and steps == 0 and decode.calls == 0
    np.testing.assert_array_equal(gen[0], [2])


def test_decode_loop_zero_tokens():
    decode = _FakeDecode()
    gen, _, steps = decode_loop(decode, None, 0,
                                jnp.asarray([[2]], jnp.int32), 3, 0)
    assert gen.shape == (1, 0) and steps == 0 and decode.calls == 0


# ---------------------------------------------------------------------------
# dryrun: XLA_FLAGS handling
# ---------------------------------------------------------------------------


def _run_snippet(body: str, env_extra: dict) -> str:
    # inherit the ambient env (JAX_PLATFORMS etc. — backend probing can hang
    # without it) but take explicit control of XLA_FLAGS, the var under test
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = "src"
    env.update(env_extra)
    proc = subprocess.run([sys.executable, "-c", body], capture_output=True,
                          text=True, env=env, timeout=240)
    assert proc.returncode == 0, proc.stderr[-1000:]
    return proc.stdout.strip()


def test_dryrun_appends_instead_of_clobbering_user_flags():
    out = _run_snippet(
        "import os\n"
        "from repro.launch.dryrun import _force_host_devices\n"
        "_force_host_devices()\n"
        "print(os.environ['XLA_FLAGS'])\n",
        {"XLA_FLAGS": "--xla_cpu_enable_fast_math=false"})
    assert "--xla_cpu_enable_fast_math=false" in out
    assert "--xla_force_host_platform_device_count=512" in out


def test_dryrun_respects_existing_device_count_flag():
    out = _run_snippet(
        "import os\n"
        "from repro.launch.dryrun import _force_host_devices\n"
        "_force_host_devices()\n"
        "print(os.environ['XLA_FLAGS'])\n",
        {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert out == "--xla_force_host_platform_device_count=4"


def test_dryrun_leaves_env_alone_after_jax_initialized():
    out = _run_snippet(
        "import os, jax\n"
        "jax.devices()\n"  # initialize backends: too late for the flag
        "from repro.launch.dryrun import _force_host_devices\n"
        "_force_host_devices()\n"
        "print(os.environ.get('XLA_FLAGS', '<unset>'))\n",
        {})
    assert out == "<unset>"


# ---------------------------------------------------------------------------
# compile cache: placed from outside, or at one fixed path in the checkout
# ---------------------------------------------------------------------------


def test_compile_cache_env_var_is_honoured(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, the helper sets no directory of
    its own and compiled programs land in the named one."""
    body = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        f"assert enable_compile_cache() == {str(tmp_path)!r}\n"
        f"assert jax.config.jax_compilation_cache_dir == {str(tmp_path)!r}\n"
        "jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()\n"
    )
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))), "src"))
    proc = subprocess.run([sys.executable, "-c", body], capture_output=True,
                          text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert any(tmp_path.iterdir()), "no compiled program was cached"


def test_compile_cache_defaults_to_fixed_checkout_path(monkeypatch):
    import jax

    from repro.launch import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert got == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        assert compile_cache.enable_compile_cache() == got  # stable path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
