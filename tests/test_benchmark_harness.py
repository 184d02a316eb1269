"""Tier-1's guard of the code that judges every PR: ``pytest tests/``
collects the cases of the six fast, pure-Python files of
``benchmarks/tests/``, each as its own test and nothing copied, and of
``test_disturbance_readers`` and ``test_setup_readers`` (one short
rehearsal a cell). The slow reference, architecture and rehearsal files
run in CI."""

import importlib
import os
import sys

from _pytest.fixtures import getfixturemarker

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

for _stem in ("test_loadgen", "test_spec", "test_work", "test_trace_reduce",
              "test_idle_by_span", "test_jamba_work", "test_ouro_work",
              "test_disturbance_readers", "test_setup_readers"):
    _mod = importlib.import_module(f"benchmarks.tests.{_stem}")
    for _name, _obj in vars(_mod).items():
        if _name.startswith("test_"):
            globals()[f"{_stem}_{_name[len('test_'):]}"] = _obj
        elif getfixturemarker(_obj) is not None:
            # A test asks for a fixture by name: it keeps it, defined once.
            assert _name not in globals(), (_stem, _name)
            globals()[_name] = _obj
