# NOTE: no XLA_FLAGS here — smoke tests must see exactly 1 device
# (the 512-device override belongs to launch/dryrun.py ONLY).
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import pytest

try:
    import hypothesis  # noqa: F401  (real install: property tests run)
except ImportError:
    # CI installs hypothesis from requirements.txt; a container without it
    # still runs every plain test — only @given property tests skip.  The
    # stub satisfies import-time strategy construction (st.integers(...)
    # etc. are built while the module loads) and turns @given into a skip.
    import types

    class _AnyStrategy:
        def __call__(self, *a, **k):
            return self

        def __getattr__(self, name):
            return self

    def _given(*a, **k):
        del a, k
        return pytest.mark.skip(reason="hypothesis not installed")

    def _settings(*a, **k):
        del a, k
        return lambda f: f

    _h = types.ModuleType("hypothesis")
    _h.given, _h.settings = _given, _settings
    _st = types.ModuleType("hypothesis.strategies")
    _st.__getattr__ = lambda name: _AnyStrategy()
    _extra = types.ModuleType("hypothesis.extra")
    _hnp = types.ModuleType("hypothesis.extra.numpy")
    _hnp.__getattr__ = lambda name: _AnyStrategy()
    _h.strategies, _h.extra, _extra.numpy = _st, _extra, _hnp
    for _name, _mod in [("hypothesis", _h), ("hypothesis.strategies", _st),
                        ("hypothesis.extra", _extra),
                        ("hypothesis.extra.numpy", _hnp)]:
        sys.modules[_name] = _mod


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


def tiny_dense_config(**kw):
    from repro.models.config import ArchConfig
    base = dict(name="tiny", family="dense", n_layers=4, d_model=64,
                n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                head_dim=16, compute_dtype="float32",
                param_dtype="float32")
    base.update(kw)
    return ArchConfig(**base)
