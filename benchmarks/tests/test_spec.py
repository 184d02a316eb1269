"""The loader refuses a malformed benchmark before any run."""

import copy
import json
import os

import pytest

from benchmarks.harness import metrics, spec, work
from benchmarks.tests import toy_hybrid


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


def _configs():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        return [c["name"] for c in json.load(f)["configs"]]


@pytest.mark.parametrize("config", _configs())
def test_a_new_cell_reports_the_whole_yardstick(raw, tmp_path, config):
    """A claim on an added cell must report every share of a roofline or
    a peak that moves the claimed end-to-end metric (the driver refuses
    it otherwise: ledger, PR 31). So such a metric lists no cells, and a
    cell added from files on any configuration is among its cells."""
    paired = {w["traffic"] for w in raw["workloads"] if w["config"] == config}
    traffic = next(
        stem for stem in sorted(
            os.path.splitext(f)[0]
            for f in os.listdir(os.path.join(spec.BENCH_DIR, "traffic")))
        if stem not in paired)
    cell = f"{config}.added-{traffic}"
    raw["workloads"].append({
        "name": cell, "config": config, "traffic": traffic, "chips": 1,
        "why": "a cell a later PR adds from files that are there"})
    b = load(tmp_path, raw)
    reports = {k for k, m in b["end_to_end"].items() if cell in m["cells"]}
    assert "out_tok_s" in reports
    yardstick = [m for name, m in b["per_layer"].items()
                 if ("roofline" in name or "mfu" in name)
                 and m["moves"] in reports]
    assert {m["name"] for m in yardstick} >= {
        "attn_decode_roofline", "decode_step_roofline"}
    for m in yardstick:
        assert cell in m["cells"], (
            f"{m['name']} shuts {cell} out: no claim could be made there")


def test_a_hybrid_with_a_work_file_reports_the_whole_yardstick(
        raw, tmp_path, monkeypatch):
    """What the next ``model_config`` PR does, from new files alone: a
    configuration that names its work file, one entry under ``configs``
    and one under ``workloads``. The cell is among the cells of both
    shares of a roofline, and both read a number there."""
    bench_dir = tmp_path / "benchmarks"
    bench_dir.mkdir()
    name = toy_hybrid.write(str(bench_dir))
    monkeypatch.setattr(spec, "BENCH_DIR", str(bench_dir))
    monkeypatch.setattr(spec, "ROOT", str(tmp_path))
    raw["configs"].append({
        "name": name, "source": toy_hybrid.CONFIG["bench"]["source"],
        "file": f"benchmarks/configs/{name}.json",
        "reduced": ["experts_held"], "why": "a hybrid from files"})
    cell = f"{name}.decode-probe8"
    raw["workloads"].append({
        "name": cell, "config": name, "traffic": "decode-probe8", "chips": 1,
        "why": "a cell a later PR adds with its configuration"})
    b = load(tmp_path, raw)
    config = b["configs"][name]
    assert config["work"]["module"] == "toy_hybrid"
    stage = work.load_stage(config["hf"], config["work"]["path"])
    ctx = toy_hybrid.hand_ctx(stage, toy_hybrid.SPAN_WORK,
                              *toy_hybrid.counts(1_900, 5_600))
    for metric in ("attn_decode_roofline", "decode_step_roofline"):
        m = b["per_layer"][metric]
        assert "workloads" not in m and cell in m["cells"]
        assert 0 < metrics.read_layer_metric(m["reader"], ctx) < 100
    # Without the program's count of the experts its steps read: nothing.
    ctx["scrape_t1"] = {}
    assert metrics.read_layer_metric(
        b["per_layer"]["decode_step_roofline"]["reader"], ctx) is None


def test_a_reduced_key_must_be_what_the_file_runs(tmp_path, monkeypatch):
    cfg = json.load(open(spec.config_path("qwen2.5-7b-d24")))
    cfg["num_hidden_layers"] = 28
    d = tmp_path / "configs"
    d.mkdir()
    (d / "x.json").write_text(json.dumps(cfg))
    monkeypatch.setattr(spec, "config_path", lambda n: str(d / f"{n}.json"))
    with pytest.raises(spec.SpecError, match="reduced key"):
        spec.load_config("x")


META = spec.load_config("qwen2.5-7b-d24")["bench"]


def bench_with(reference, serve_flags=()):
    meta = dict(META, serve_flags=list(serve_flags))
    if reference is not None:
        meta["reference"] = reference
    return meta


ROW = {"prompts": 4, "prompt_tokens": 48, "new_tokens": 16}


def test_a_configuration_without_the_key_gets_the_dense_block():
    for name in ("qwen2.5-7b-d24", "qwen2.5-3b"):
        ref = spec.load_config(name)["reference"]
        assert ref == {"module": "harness/reference",
                       "import": "benchmarks.harness.reference",
                       "rows": [ROW]}


def test_a_reference_module_is_found_by_its_stem(tmp_path, monkeypatch):
    monkeypatch.setattr(spec, "BENCH_DIR", str(tmp_path))
    (tmp_path / "references").mkdir()
    (tmp_path / "references" / "mine.py").write_text(
        "def greedy_continuations(params, cfg, prompts, n_new):\n    return []\n")
    (tmp_path / "references" / "empty.py").write_text("def forward():\n    pass\n")
    long_row = {"prompts": 1, "prompt_tokens": 4096, "new_tokens": 8}
    ref = spec.reference_of(bench_with({"module": "mine",
                                        "rows": [ROW, long_row]}))
    assert ref == {"module": "mine", "import": "benchmarks.references.mine",
                   "rows": [ROW, long_row]}
    # Rows alone: the module stays the dense block's (looked up in the
    # patched directory here, so give it one).
    (tmp_path / "harness").mkdir()
    (tmp_path / "harness" / "reference.py").write_text(
        "from x import greedy_continuations\n")
    assert spec.reference_of(bench_with({"rows": [long_row]}))["rows"] == [long_row]
    for reference, flags, message in [
        ({"module": "absent"}, (), "missing file"),
        ({"module": "empty"}, (), "has no greedy_continuations"),
        ({"module": "../harness/reference"}, (), "is no name"),
        ({"module": "mine", "child": "x"}, (), "has the keys"),
        ({"module": "mine", "rows": []}, (), "list of row shapes"),
        ({"module": "mine", "rows": [{"prompts": 1}]}, (), "a reference row is"),
        ({"module": "mine", "rows": [
            {"prompts": 1, "prompt_tokens": 8190, "new_tokens": 3}]}, (),
         "exceeds --max-model-len 8192"),
        ({"module": "mine", "rows": [long_row]}, ("--max-model-len", "4096"),
         "exceeds --max-model-len 4096"),
    ]:
        with pytest.raises(spec.SpecError, match=message):
            spec.reference_of(bench_with(reference, flags))


def test_a_configuration_without_a_work_file_is_the_dense_block():
    for name in ("qwen2.5-7b-d24", "qwen2.5-3b"):
        assert spec.load_config(name)["work"] == {
            "module": "harness/work", "path": None}
    eva = spec.load_config("evabyte-6.5b-d16")["work"]
    assert eva == {"module": "evabyte", "path": os.path.join(
        spec.BENCH_DIR, "works", "evabyte.py")}


def test_a_work_file_is_found_by_its_stem(tmp_path, monkeypatch):
    monkeypatch.setattr(spec, "BENCH_DIR", str(tmp_path))
    (tmp_path / "works").mkdir()
    (tmp_path / "works" / "mine.py").write_text(
        "def layers(cfg):\n    return []\n")
    (tmp_path / "works" / "assigned.py").write_text(
        "from benchmarks.harness import work\nlayers = work.dense_layers\n")
    (tmp_path / "works" / "empty.py").write_text(
        "def head_elements(cfg):\n    return 0\n")
    for stem in ("mine", "assigned"):
        assert spec.work_of(dict(META, work={"module": stem})) == {
            "module": stem, "path": str(tmp_path / "works" / f"{stem}.py")}
    for given, message in [
        ({"module": "absent"}, "missing file"),
        ({"module": "empty"}, "has no layers"),
        ({"module": "../harness/work"}, "is no name"),
        ({"module": "mine", "kernel": "x"}, "the one key"),
        ("mine", "the one key"),
    ]:
        with pytest.raises(spec.SpecError, match=message):
            spec.work_of(dict(META, work=given))
