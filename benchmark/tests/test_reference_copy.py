"""benchmark/reference/witt_ref is a copy of the program's oracle DES,
so that no later PR can change what results are held against.  Until a
simplicity PR leaves one copy (tests/ importing witt_ref, PERF.md section
7), the two must not drift: the copy equals the original but for the
package name.  Where the original is gone, there is nothing to drift
from and the test skips."""

import os

import pytest

import cells

COPY = os.path.join(cells.BENCH_DIR, "reference", "witt_ref")
ORIGINAL = os.path.join(cells.ROOT, "wittgenstein_tpu")
# copied up to where the original starts to register the PROGRAM's batched
# protocols, which the reference must not import
CUT_SHORT = {os.path.join("core", "registries.py")}


def _copied_files():
    for folder, _, files in os.walk(COPY):
        for f in sorted(files):
            if f.endswith(".py"):
                yield os.path.relpath(os.path.join(folder, f), COPY)


@pytest.mark.parametrize("rel", sorted(_copied_files()))
def test_the_copy_equals_the_original_but_for_the_package_name(rel):
    original = os.path.join(ORIGINAL, rel)
    if not os.path.exists(original):
        pytest.skip(f"{original} is gone: one copy is left")
    if os.path.basename(rel) == "__init__.py":
        pytest.skip("package files differ by design: the copy exports only the oracle")
    with open(original) as f:
        want = f.read().replace("wittgenstein_tpu", "witt_ref")
    with open(os.path.join(COPY, rel)) as f:
        have = f.read().replace("wittgenstein_tpu", "witt_ref")
    if rel in CUT_SHORT:
        assert want.startswith(have.rstrip("\n")), rel
    else:
        assert have == want, rel
