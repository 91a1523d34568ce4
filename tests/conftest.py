import sys
import os

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# NOTE: no XLA_FLAGS here on purpose — tests run on the single real CPU
# device; only launch/dryrun.py forces 512 host devices (per spec).


@pytest.fixture(scope="module")
def persistent_compile_cache(tmp_path_factory):
    """Turn JAX's persistent compile cache on for one module, in a
    fresh directory of its own, so a program compiled again with the
    same shapes is loaded instead of rebuilt. Only for modules that
    charge the SimClock a modeled compile constant: nothing there reads
    a measured compile time. The previous settings come back after the
    module, so the other modules keep compiling cold."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = {"jax_compilation_cache_dir":
            str(tmp_path_factory.mktemp("jax_cache")),
            "jax_persistent_cache_min_compile_time_secs": 0.0,
            "jax_persistent_cache_min_entry_size_bytes": -1}
    old = {k: getattr(jax.config, k) for k in keys}
    for k, v in keys.items():
        jax.config.update(k, v)
    cc.reset_cache()
    yield
    for k, v in old.items():
        jax.config.update(k, v)
    cc.reset_cache()
