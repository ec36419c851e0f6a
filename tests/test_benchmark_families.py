"""The benchmark finds a model family by name, as a file: tier-1's copy
of ``benchmark/tests/test_new_family.py``'s two tests of that (the
benchmark's own tests are not part of tier-1; PERF.md, PR 28, left the
copy to the first PR that may touch ``tests/``). A ``model_config`` PR
adds ``benchmark/family/<name>.py`` and edits no file of the harness:
these hold the harness to it."""

import os
import sys
import textwrap

import pytest

BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")


@pytest.fixture
def harness():
    """The benchmark's ``families`` and ``lookup``, imported as its own
    runner imports them."""
    sys.path.insert(0, BENCH)
    try:
        import families
        import lookup

        yield families, lookup
    finally:
        sys.path.remove(BENCH)


FAMILY = '''
from families import Family


def build(sizes):
    return Family(
        model_config=None, init=None, loss_fn=None, logical_axes=None,
        apply=None, reference_logits=None, tolerances={},
        flops_per_token=6.0 * sizes["parameters"],
        work={"some_kernel": lambda sizes: (1e9, 1e6)},
    )
'''


def test_family_dropped_into_a_directory_builds(harness, tmp_path):
    families, lookup = harness
    (tmp_path / "family").mkdir()
    (tmp_path / "family" / "dropped.py").write_text(textwrap.dedent(FAMILY))
    sizes = {"family": "dropped", "parameters": 7}
    search = (str(tmp_path),) + lookup.SEARCH
    family = families.build(sizes, search)
    assert family.flops_per_token == 42.0
    assert families.kernel_work(family, sizes, "some_kernel", search) \
        == (1e9, 1e6)
    # the harness's own families are found beside it
    assert families.build(
        lookup.data("configs", "toy-gpt2"), search
    ).flops_per_token > 0
    # and without the directory the family does not exist
    with pytest.raises(ValueError, match="dropped"):
        families.build(sizes)


def test_unknown_family_names_the_directory(harness, tmp_path):
    families, lookup = harness
    with pytest.raises(ValueError) as error:
        families.build({"family": "no_such_family"})
    assert os.path.join(lookup.HERE, "family") in str(error.value)
    assert "no_such_family.py" in str(error.value)
    with pytest.raises(ValueError) as error:
        families.build({"family": "no_such_family"}, (str(tmp_path),))
    assert os.path.join(str(tmp_path), "family") in str(error.value)
