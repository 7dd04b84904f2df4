"""Export hygiene: ``__all__`` is the single source of truth per package.

Every name a package's ``__all__`` declares must resolve (catching drift
between a package's declared surface and what its ``__init__`` imports),
and the deprecation shims in
``repro.core`` / ``repro.distributed`` must keep old imports working while
warning.
"""

import importlib
import warnings

import pytest

PACKAGES = [
    "repro.api",
    "repro.checkpoint",
    "repro.core",
    "repro.distributed",
    "repro.kernels",
    "repro.maintenance",
    "repro.obs",
    "repro.parallel",
    "repro.serve",
    "repro.serving",
]


@pytest.mark.parametrize("pkg", PACKAGES)
def test_every_all_name_imports(pkg):
    mod = importlib.import_module(pkg)
    assert hasattr(mod, "__all__"), f"{pkg} must declare __all__"
    assert len(set(mod.__all__)) == len(mod.__all__), f"{pkg}: duplicate names"
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for name in mod.__all__:
            obj = getattr(mod, name)  # raises AttributeError on drift
            assert obj is not None, f"{pkg}.{name} resolved to None"


def test_core_shim_warns_and_resolves():
    import repro.core
    from repro.core import deltatree

    with pytest.warns(DeprecationWarning, match="make_index"):
        fn = repro.core.update_batch
    assert fn is deltatree.update_batch
    # stable names never warn
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        _ = repro.core.TreeConfig, repro.core.OP_INSERT, repro.core.layout


def test_distributed_shim_warns_and_resolves():
    import repro.distributed
    from repro.distributed import forest

    with pytest.warns(DeprecationWarning, match="make_index"):
        fn = repro.distributed.search_batch
    assert fn is forest.search_batch
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        _ = repro.distributed.ForestConfig, repro.distributed.router


def test_unknown_attribute_still_raises():
    import repro.core
    import repro.distributed

    with pytest.raises(AttributeError):
        _ = repro.core.not_a_real_name
    with pytest.raises(AttributeError):
        _ = repro.distributed.not_a_real_name
