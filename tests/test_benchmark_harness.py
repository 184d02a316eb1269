"""Tier-1's guard of the code that judges every PR: ``pytest tests/``
collects the cases of the fast, pure-Python files of
``benchmarks/tests/``, each as its own test and nothing copied, and of
``test_disturbance_readers`` and ``test_setup_readers`` (one short
rehearsal a cell) and ``test_axk1_cell`` (that cell's rehearsal and
``--trace 2`` run). The slow reference, architecture and rehearsal files
run in CI.

Two cases of ``test_ouro_work`` hold Ouro's entries to the END of
``BENCHMARK.json``'s lists: true of the file PR 46 left, of no later
one, and a file under ``benchmarks/`` is not a later PR's to edit. They
are listed below as red against the real file (and held to be: an entry
that turns green is taken off) and run instead, by name and with the
benchmark handed to them, against the lists cut after Ouro's entries:
what PR 46 added is still there, in that order, with nothing before it
changed."""

import importlib
import json
import os
import sys

import pytest
from _pytest.fixtures import getfixturemarker

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# Red against the real ``BENCHMARK.json`` since a sixth configuration was
# listed (PR 51; PERF.md section 7 says what a ``benchmark`` PR owes).
RED_SINCE_A_LATER_ENTRY = {
    "test_ouro_work": (
        "test_the_configuration_is_the_published_one_with_nothing_reduced",
        "test_the_cell_is_two_rows_that_fit_the_pool_without_a_preemption",
    ),
}

for _stem in ("test_loadgen", "test_spec", "test_work", "test_trace_reduce",
              "test_idle_by_span", "test_jamba_work", "test_ouro_work",
              "test_axk1_work", "test_choice_ties", "test_axk1_cell",
              "test_disturbance_readers", "test_setup_readers",
              "test_moe_combine_reader"):
    _mod = importlib.import_module(f"benchmarks.tests.{_stem}")
    for _name, _obj in vars(_mod).items():
        if _name in RED_SINCE_A_LATER_ENTRY.get(_stem, ()):
            continue
        if _name.startswith("test_"):
            globals()[f"{_stem}_{_name[len('test_'):]}"] = _obj
        elif getfixturemarker(_obj) is not None:
            # A test asks for a fixture by name: it keeps it, defined once.
            assert _name not in globals(), (_stem, _name)
            globals()[_name] = _obj


def _red_cases():
    return [(stem, name) for stem, names in RED_SINCE_A_LATER_ENTRY.items()
            for name in names]


def _case(stem, name):
    return getattr(importlib.import_module(f"benchmarks.tests.{stem}"), name)


@pytest.fixture(scope="module")
def benchmark_as_pr46_left_it(tmp_path_factory):
    """Every list cut after Ouro's entry (a later metric's cells go with
    the metric)."""
    from benchmarks.harness import spec

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)

    def upto(entries, name):
        return entries[:[e["name"] for e in entries].index(name) + 1]

    bench["configs"] = upto(bench["configs"], "ouro-2.6b")
    bench["workloads"] = upto(bench["workloads"],
                              "ouro-2.6b.decode-probe2-2k")
    bench["per_layer"] = upto(bench["per_layer"], "kv_token_kib")
    path = tmp_path_factory.mktemp("pr46") / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return spec.load(str(path))


@pytest.mark.parametrize("stem, name", _red_cases())
def test_a_case_that_holds_its_entries_last_holds_them_as_they_were_left(
        stem, name, benchmark_as_pr46_left_it):
    _case(stem, name)(benchmark_as_pr46_left_it)


@pytest.mark.parametrize("stem, name", _red_cases())
def test_a_case_listed_as_red_is_red_on_the_real_file(stem, name):
    from benchmarks.harness import spec

    with pytest.raises(AssertionError):
        _case(stem, name)(spec.load())
