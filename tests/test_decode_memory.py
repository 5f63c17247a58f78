"""The engine's tick updates the donated slot cache in place: its compiled
program needs less scratch memory than the cache itself holds.  A tick
that emits a second cache (a new stack beside the donated one) needs more
than the whole cache."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models import init_model
from repro.serve import ServeEngine

SLOTS, MAX_LEN = 8, 512


def test_tick_temp_bytes_below_cache_bytes():
    cfg = registry.smoke_config("olmo-1b")
    params = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
    eng = ServeEngine(cfg, params, num_slots=SLOTS, max_len=MAX_LEN)
    state = jax.eval_shape(eng._fresh_state)
    compiled = eng._tick.lower(params, *state).compile()
    analysis = compiled.memory_analysis()
    if analysis is None:
        pytest.skip("this backend returns no memory analysis of a compiled "
                    "program")
    cache_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                      for a in jax.tree.leaves(state[2]))
    assert analysis.temp_size_in_bytes < cache_bytes, (
        analysis.temp_size_in_bytes, cache_bytes)
