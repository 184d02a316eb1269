"""``moe_combine_us_per_layer_step`` over operations as a trace names
them (an event's name is the instruction's text): the scatter-add's last
fusion of the parent of PR 53 and the sum over a token's gathered rows
that took its place, both copied from traces of the chip (PERF.md, PR
53), and nothing on a stage without an expert layer (pure Python: tier-1 collects these
through ``tests/test_benchmark_harness.py``)."""

import pytest

from benchmarks.harness import metrics, spec, work

CONFIG = "a.x-k1-ep16-d7"
CELL = "a.x-k1-ep16-d7.decode-probe128-3k"
NAME = "moe_combine_us_per_layer_step"

KERNEL = ("mla_decode_attention_pallas.2",
          "%mla_decode_attention_pallas.2 = bf16[128,64,512]{2,1,0:T(8,128)"
          "(2,1)} custom-call(s32[128,64]{1,0:T(8,128)} %get-tuple-element.9)",
          1e-3)
GMM = ("gmm.2", "%gmm.2 = f32[1024,7168]{1,0:T(8,128)S(1)} custom-call("
       "s32[]{:T(128)} %get-tuple-element.1484, s32[13]{0:T(128)S(1)} "
       "%pad_add_fusion.42, bf16[1024,2048]{1,0:T(8,128)(2,1)S(1)} "
       "%multiply_convert_fusion.24)", 6e-3)
# The parent's three fusions of the scatter-add: the weighting, the
# updates permuted for the scatter, the scatter itself.
WEIGHTING = (
    "multiply_select_fusion.15",
    "%multiply_select_fusion.15 = f32[1024,7168]{1,0:T(8,128)S(1)} fusion("
    "f32[1024,7168]{1,0:T(8,128)S(1)} %gmm.11, f32[1024]{0:T(1024)S(1)} "
    "%fusion.832, pred[1024]{0:T(1024)(128)(4,1)S(1)} "
    "%broadcast_compare_fusion.15), kind=kLoop, "
    "calls=%fused_computation.242.clone.clone", 1e-4)
PERMUTED = (
    "fusion.813",
    "%fusion.813 = f32[1024,7168]{1,0:T(8,128)S(1)} fusion(f32[1024,7168]"
    "{1,0:T(8,128)S(1)} %multiply_select_fusion.14, s32[1024]{0:T(1024)S(1)} "
    "%broadcast_clamp_fusion.20), kind=kCustom, "
    "calls=%fused_computation.146.clone.clone", 9e-4)
SCATTER = (
    "fusion.772",
    "%fusion.772 = f32[128,7168]{1,0:T(8,128)S(1)} fusion(f32[128,7168]"
    "{1,0:T(8,128)S(1)} %custom-call.235, s32[1024]{0:T(1024)S(1)} "
    "%copy-done.56, f32[1024,7168]{1,0:T(8,128)S(1)} %fusion.771), "
    "kind=kCustom, calls=%fused_computation.133.clone.clone", 4e-3)
# The kept form: one gather of the pairs' rows in token order, which
# writes one row a pair (the dispatch reader's), and the sum over the
# rows viewed ``[K, rows, hidden]``.
GATHER = (
    "fusion.789",
    "%fusion.789 = f32[1024,7168]{1,0:T(8,128)S(1)} fusion(f32[1024,7168]"
    "{1,0:T(8,128)S(1)} %gmm.14, s32[1024]{0:T(1024)S(1)} %copy-done.43), "
    "kind=kCustom, calls=%fused_computation.69.clone.clone", 2e-4)
SUM = (
    "select_reduce_fusion.17",
    "%select_reduce_fusion.17 = f32[128,7168]{1,0:T(8,128)S(1)} fusion("
    "f32[8,128,7168]{2,1,0:T(8,128)S(1)} %bitcast.437, f32[8,128]"
    "{1,0:T(8,128)S(1)} %copy-done.55, pred[8,128]{1,0:T(8,128)(4,1)S(1)} "
    "%copy-done.56), kind=kLoop, calls=%fused_computation.251", 1e-4)
OTHERS = [
    ("fusion.26", "%fusion.26 = f32[128,7168]{1,0} fusion(bf16[128,7168]"
     "{1,0} %fusion.25, f32[128]{0} %rsqrt.3), kind=kLoop", 9e-3),
    ("fusion.983", "%fusion.983 = bf16[1024,7168]{1,0:T(8,128)(2,1)S(1)} "
     "fusion(bf16[128,7168]{1,0:T(8,128)(2,1)S(1)} %multiply_convert_fusion.33,"
     " s32[1024]{0:T(1024)S(1)} %fusion.982), kind=kCustom", 7e-4),
]
PER = 1e6 / (2 * 8 * 6)       # two executions of 8 steps, six expert layers


def _read(ops, config=CONFIG, name=NAME):
    bench, c = spec.load(), spec.load_config(config)
    stage = (work.load_stage(c["hf"], c["work"]["path"]) if "work" in c
             else work.stage(c["hf"]))
    ctx = {"work": stage, "model": c["hf"]}
    ctx.update(ops if isinstance(ops, dict) else {"_decode_ops": (2, ops)})
    return metrics.read_layer_metric(bench["per_layer"][name]["reader"], ctx)


def test_the_metric_is_the_last_of_the_list_and_reads_the_one_cell():
    bench = spec.load()
    assert [e["name"] for e in bench["raw"]["per_layer"]][-1] == NAME
    m = bench["per_layer"][NAME]
    assert (m["cells"], m["moves"], m["unit"], m["layer"], m["better"]) == (
        [CELL], "out_tok_s", "us", "kernels", "lower")


@pytest.mark.parametrize("ops, seconds", [
    ([WEIGHTING, PERMUTED, SCATTER], 4e-3),
    ([GATHER, SUM], 1e-4),
    ([WEIGHTING, PERMUTED, SCATTER, GATHER, SUM], 4e-3 + 1e-4),
], ids=["the-parents-scatter-add", "the-kept-forms-sum", "both"])
def test_the_combine_is_what_writes_token_rows_and_reads_pair_rows(
        ops, seconds):
    assert _read([KERNEL, GMM] + OTHERS + ops) == pytest.approx(seconds * PER)
    # The dispatch reader holds what writes one row a pair, never these.
    dispatch = _read([KERNEL, GMM] + OTHERS + ops,
                     name="moe_dispatch_us_per_layer_step")
    pair_rows = sum(s for n, _, s in OTHERS[1:] + ops
                    if n in ("multiply_select_fusion.15", "fusion.813",
                             "fusion.983", "fusion.789"))
    assert dispatch == pytest.approx(pair_rows * PER)


@pytest.mark.parametrize("ops", [
    [KERNEL, GMM] + OTHERS + [GATHER],       # no operation of the kind
    [GMM, SCATTER],                          # no decode kernel to say the rows
    {"_decode_ops": None},                   # no decode window in the trace
    {"trace": None},                         # no trace
], ids=["nothing-of-the-kind", "no-kernel", "no-window", "no-trace"])
def test_nothing_to_read_is_none(ops):
    assert _read(ops) is None


def test_a_stage_without_an_expert_layer_reads_none():
    assert _read([KERNEL, GMM, SCATTER], config="qwen2.5-3b") is None


def test_an_instructions_text_says_what_it_writes_and_reads():
    reader = spec.import_file("layer_metric_", spec.load()["per_layer"][
        NAME]["reader"]["py"])
    assert reader.written_and_read(SCATTER[1]) == (
        [("f32", (128, 7168))],
        [("f32", (128, 7168)), ("s32", (1024,)), ("f32", (1024, 7168))])
    assert reader.written_and_read(SUM[1]) == (
        [("f32", (128, 7168))],
        [("f32", (8, 128, 7168)), ("f32", (8, 128)), ("pred", (8, 128))])
    wrote, _ = reader.written_and_read(
        "%fusion.9 = (f32[128]{0:T(128)S(1)}, bf16[128,7168]{1,0:T(8,128)"
        "(2,1)S(1)}) fusion(bf16[128,7168]{1,0} %custom-call.269), kind=kLoop")
    assert wrote == [("f32", (128,)), ("bf16", (128, 7168))]
    assert reader.written_and_read("%x = (f32[8]{0}, s32[]) tuple()") == (
        [("f32", (8,)), ("s32", ())], [])
    assert reader.written_and_read("no shape here") is None
