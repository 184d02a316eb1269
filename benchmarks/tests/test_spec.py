"""The loader refuses a malformed benchmark before any run."""

import copy
import json
import os

import pytest

from benchmarks.harness import spec


@pytest.fixture()
def raw():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load(tmp_path, raw):
    p = tmp_path / "BENCHMARK.json"
    p.write_text(json.dumps(raw))
    return spec.load(str(p))


def test_the_committed_benchmark_loads(raw, tmp_path):
    b = load(tmp_path, raw)
    assert set(b["cells"]) == {w["name"] for w in raw["workloads"]}
    for m in b["per_layer"].values():
        assert m["moves"] in b["end_to_end"]


@pytest.mark.parametrize("mutate, message", [
    (lambda r: r["workloads"][0].update(name="bad name"), "character outside"),
    (lambda r: r["end_to_end"][0].update(unit="tokens per s"), "character outside"),
    (lambda r: r["workloads"][0].update(traffic="no-such-traffic"), "missing file"),
    (lambda r: r["workloads"][0].update(config="no-such-config"), "not listed"),
    (lambda r: r["end_to_end"][0].update(workloads=[]), "does not report"),
    (lambda r: r["per_layer"][0].update(moves="nothing"), "no end-to-end metric"),
    (lambda r: r["configs"][0].update(reduced=[]), "'reduced' differs"),
    (lambda r: r["end_to_end"].pop(), "setup_s"),
])
def test_refusals(raw, tmp_path, mutate, message):
    bad = copy.deepcopy(raw)
    mutate(bad)
    with pytest.raises(spec.SpecError, match=message):
        load(tmp_path, bad)


def test_a_reduced_key_must_be_what_the_file_runs(tmp_path, monkeypatch):
    cfg = json.load(open(spec.config_path("qwen2.5-7b-d24")))
    cfg["num_hidden_layers"] = 28
    d = tmp_path / "configs"
    d.mkdir()
    (d / "x.json").write_text(json.dumps(cfg))
    monkeypatch.setattr(spec, "config_path", lambda n: str(d / f"{n}.json"))
    with pytest.raises(spec.SpecError, match="reduced key"):
        spec.load_config("x")
