"""Make the benchmark's modules and `src/` importable by its tests.

Also keep the committed hypothesis examples (`.hypothesis/examples`) as
they are: the property tests still replay them, but a test run saves and
deletes none, so it leaves the tree unchanged. This directory is collected
before `tests/`, so the profile is active when their `@settings` are made.
"""
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_BENCH = os.path.dirname(_HERE)
for _p in (_BENCH, os.path.join(os.path.dirname(_BENCH), "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

try:
    from hypothesis import settings
    from hypothesis.configuration import storage_directory
    from hypothesis.database import (
        DirectoryBasedExampleDatabase,
        ReadOnlyDatabase,
    )
except ImportError:
    pass
else:
    settings.register_profile(
        "read_only_examples",
        database=ReadOnlyDatabase(
            DirectoryBasedExampleDatabase(storage_directory("examples"))
        ),
    )
    settings.load_profile("read_only_examples")
