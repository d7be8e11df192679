"""A scope is metadata: the programs of ``tests/test_device_scopes.py``
compiled once more with ``jax.named_scope`` patched to a null context are the
same programs.  That file's cases, in a file of their own so that the second
worker's two minutes lie on another test worker (``--dist loadfile`` hands out
files); the scoped worker's report is made once a session
(``test_device_scopes.report_once``).
"""

import pytest

from chip_bench import scopes

from .test_device_scopes import BLOCKS, EAGER, report_once


@pytest.fixture(scope="module")
def unscoped():
    return report_once(True)


@pytest.fixture(scope="module")
def scoped(unscoped):
    # Behind ``unscoped``: by then the other file's worker has written it.
    return report_once(False)


# -- a scope is metadata -------------------------------------------------------


def test_both_workers_compiled_the_same_programs(scoped, unscoped):
    assert sorted(scoped) == sorted(unscoped)
    assert len(scoped) == len(BLOCKS) + 2 * len(EAGER)
    for program in unscoped.values():
        assert not any(scopes.segments(name) for name in program["names"])


@pytest.mark.parametrize("label", [f"wfbp:{m}" for m in sorted(BLOCKS)]
                         + [f"eager:resnet:{p}" for p in sorted(EAGER)])
def test_the_scopes_add_no_operation(scoped, unscoped, label):
    """The same program compiled with ``jax.named_scope`` a null context: the
    same instructions, and the same text once the metadata is gone."""
    assert scoped[label]["n"] == unscoped[label]["n"]
    assert scoped[label]["sha"] == unscoped[label]["sha"]
