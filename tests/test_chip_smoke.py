"""chip_smoke.py on the CPU sim: its own leg functions at a toy size, the
device gate, the compile-cache placement rule, and backend-free imports.

The real run is ``python chip_smoke.py`` on a TPU through the chip tool;
nothing here is a device number.
"""

import os
import subprocess
import sys

import jax
import pytest

import chip_smoke
from distributed_tpu.utils import compile_cache

TOY_LM = dict(vocab=512, num_layers=2, d_model=64, num_heads=4, seq_len=64,
              batch=8)
TOY_SERVE = dict(max_slots=4, block_size=8, max_len=64)
CPU = {"platform": "cpu", "kind": "cpu", "count": 8}


def test_train_and_serve_legs_at_toy_size(capsys):
    cache = chip_smoke.CacheCounter()
    model, losses, calls = chip_smoke.leg_train(
        CPU, TOY_LM, cache, steps=3, require_mosaic=False)
    assert len(losses) == 3 and calls == []  # interpreted: no Mosaic call
    chip_smoke.leg_serve(CPU, model, TOY_LM, cache, serve=TOY_SERVE,
                         n_requests=5, interpret=True)
    legs = [line.split('"leg": "')[1].split('"')[0]
            for line in capsys.readouterr().out.splitlines()
            if line.startswith('{"leg"')]
    assert legs == ["train", "serve:reference", "serve:fused",
                    "serve:agreement"]


def test_main_exits_nonzero_on_cpu(capsys):
    assert jax.default_backend() == "cpu"
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main()
    assert exc.value.code not in (0, None)
    assert capsys.readouterr().out == ""  # no result line


def test_a_failing_leg_fails_the_run(monkeypatch):
    monkeypatch.setattr(chip_smoke, "device_gate", lambda: CPU)
    monkeypatch.setattr(chip_smoke, "leg_sync", lambda dev: None)

    def boom(dev):
        raise chip_smoke.SmokeFailure("injected")

    monkeypatch.setattr(chip_smoke, "leg_kernels", boom)
    with pytest.raises(chip_smoke.SmokeFailure, match="injected"):
        chip_smoke.main()


@pytest.fixture
def restore_cache_config():
    """compile_cache.enable() sets process-wide jax config; tier-1 itself
    runs without a persistent cache."""
    names = ("jax_compilation_cache_dir",
             "jax_traceback_in_locations_limit")
    from jax._src import cache_key

    before = {n: getattr(jax.config, n) for n in names}
    hook = cache_key.custom_hook
    yield before
    for n, v in before.items():
        jax.config.update(n, v)
    cache_key.custom_hook = hook


def test_cache_dir_is_env_or_one_fixed_in_checkout_path(
        monkeypatch, restore_cache_config):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    fixed = os.path.join(repo, ".jax_cache")
    assert compile_cache.cache_dir() == compile_cache.cache_dir() == fixed
    assert compile_cache.enable() == fixed
    assert jax.config.jax_compilation_cache_dir == fixed
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/else")
    assert compile_cache.cache_dir() == "/somewhere/else"
    # With the variable set the program sets no directory in code.
    assert compile_cache.enable() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir is None


def test_cache_key_does_not_depend_on_the_call_site(
        monkeypatch, restore_cache_config):
    """A Pallas kernel's locations are serialized into its Mosaic body,
    which the cache key hashes: after enable() the same kernel traced from
    two call sites must lower to the same text."""
    import jax.numpy as jnp

    from distributed_tpu.ops import flash_attention as fa

    monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/else")
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    q = jax.ShapeDtypeStruct((1, 512, 2, 64), jnp.bfloat16)

    def lower():
        fa._flash_cached.cache_clear()
        return jax.jit(
            lambda q, k, v: fa.flash_attention(q, k, v, causal=True)
        ).trace(q, q, q).lower(lowering_platforms=("tpu",)).as_text()

    def another_site():
        return lower()

    jax.config.update("jax_traceback_in_locations_limit", 10)  # jax default
    assert "tpu_custom_call" in lower()
    assert lower() != another_site()  # the parent's behaviour
    compile_cache.enable()
    assert lower() == another_site()
    fa._flash_cached.cache_clear()


def test_cache_key_carries_the_scope_scheme(monkeypatch,
                                            restore_cache_config):
    """The key is taken with metadata stripped, so the device scopes' names
    do not move it: a cache warmed before the scopes would serve an
    executable without them. enable() puts a constant into every key, where
    the environment places the directory too."""
    import jax.numpy as jnp
    import numpy as np
    from jax._src import cache_key, compiler

    monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/else")

    def key():
        lowered = jax.jit(lambda x: x + 1).lower(jnp.zeros(4))
        return cache_key.get(
            lowered.compiler_ir(), np.array(jax.devices()[:1]),
            compiler.get_compile_options(num_replicas=1, num_partitions=1),
            jax.devices()[0].client)

    plain = key()
    compile_cache.enable()
    assert cache_key.custom_hook() == compile_cache.SCOPE_SCHEME
    assert jax.config.jax_compilation_cache_dir is None
    scoped = key()
    assert scoped != plain
    monkeypatch.setattr(compile_cache, "SCOPE_SCHEME", "another-scheme")
    assert key() not in (plain, scoped)


def test_imports_initialise_no_backend():
    code = (
        "import distributed_tpu, distributed_tpu.launch, "
        "distributed_tpu.resilience, distributed_tpu.serve_service.service\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, xla_bridge._backends\n"
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=repo, capture_output=True,
        text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
