"""Serving setup shared by the launcher and chip_smoke.py: where the
compile cache lives, params created straight into their serving
shardings, and the synthetic prompt mix."""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.launch import compile_cache, mesh as mesh_mod
from repro.launch.serve import load_params, make_prompts
from repro.models import init_params
from repro.sharding import specs


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_uses_the_environment(monkeypatch, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/shared/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/some/shared/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch,
                                                cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.CHECKOUT / ".jax_cache")
    assert (compile_cache.CHECKOUT / "src" / "repro").is_dir()
    assert jax.config.jax_compilation_cache_dir == path


@pytest.mark.parametrize("replicate", [False, True])
def test_params_are_created_into_their_shardings(replicate):
    cfg = get_config("tiny-target")
    mesh = mesh_mod.make_host_mesh(model=1, data=1)
    key = jax.random.PRNGKey(0)
    got = load_params(cfg, key, mesh=mesh, replicate=replicate)
    want = specs.param_specs(got, mesh, serving=True)
    eager = init_params(key, cfg)
    for x, spec, ref in zip(jax.tree.leaves(got),
                            jax.tree.leaves(want,
                                            is_leaf=lambda s: isinstance(s, P)),
                            jax.tree.leaves(eager)):
        assert x.sharding.spec == (P() if replicate else spec)
        np.testing.assert_allclose(np.asarray(x), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6)


def test_params_without_a_mesh_match_init_params():
    cfg = get_config("tiny-draft")
    key = jax.random.PRNGKey(3)
    for a, b in zip(jax.tree.leaves(load_params(cfg, key)),
                    jax.tree.leaves(init_params(key, cfg))):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("share", [1, 3])
def test_prompt_mix(share):
    prompts = make_prompts(512, 6, 10, seed=4, share=share)
    assert len(prompts) == 6
    again = make_prompts(512, 6, 10, seed=4, share=share)
    assert all(np.array_equal(a, b) for a, b in zip(prompts, again))
    if share == 1:
        assert all(len(p) == 10 for p in prompts)
    else:
        # `share` requests per system prompt, each with an 8-token tail
        assert all(len(p) == 18 for p in prompts)
        assert np.array_equal(prompts[0][:10], prompts[2][:10])
        assert not np.array_equal(prompts[0][10:], prompts[2][10:])
