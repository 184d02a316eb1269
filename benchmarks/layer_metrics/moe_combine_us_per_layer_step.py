"""Device microseconds per expert layer and decode step of the way back
from token-expert pairs to tokens: the grouped matmuls' output has one
row a pair, in expert order, and the layer's output one row a token
(``models/moe.py``: a scatter-add until PR 53, since then ``_combine``'s
gather and sum over a token's pairs). The operations are XLA fusions
with numbered names, told by what they write and read, which a device
event's name (the instruction's text, operands with their shapes
included) says: they write ``f32[rows, hidden]`` and read an array with
one row a pair, ``[rows x num_experts_per_tok, hidden]`` or the same
rows viewed ``[num_experts_per_tok, rows, hidden]`` (rows read from the
decode kernel's own output). The scatter-add's last fusion meets the
rule and so does the sum over a token's pairs; the gather before the
sum writes one row a pair and is ``moe_dispatch_us_per_layer_step``'s,
as the gather on the way in is. Counted as ``moe_gmm_us_per_layer_step``
counts. None on a trace without such operations or a stage without an
expert layer."""

import os
import re

from benchmarks.harness import spec

gmm = spec.import_file("layer_metric_", os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "moe_gmm_us_per_layer_step.py"))

SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
# `` fusion(``, `` custom-call(``: where the written shapes end and the
# operands begin (a layout's ``T(8,128)`` follows no blank).
CALL = re.compile(r"\s[a-z][\w\-]*\(")


def written_and_read(text: str):
    """``([(dtype, dims)] written, [(dtype, dims)] read)`` of an
    instruction's text, or None where it is no instruction."""
    _, eq, rest = text.partition(" = ")
    call = CALL.search(rest)
    if not eq or call is None:
        return None

    def shapes(part):
        return [(d, tuple(int(n) for n in dims.split(",") if n))
                for d, dims in SHAPE.findall(part)]

    return shapes(rest[:call.start()]), shapes(rest[call.end():])


def reduce(ctx):
    stage, model, got = ctx.get("work"), ctx.get("model"), gmm.decode_ops(ctx)
    if None in (stage, model, got) or not stage["expert_layers"]:
        return None
    rows = next((gmm.result_of(text)[1][0] for name, text, _ in got[1]
                 if re.search(stage["kernel"], name)
                 and gmm.result_of(text)), None)
    if rows is None:
        return None
    hidden = model["hidden_size"]
    k = stage["experts_per_token"]
    token_rows = ("f32", (rows, hidden))
    pair_rows = ((rows * k, hidden), (k, rows, hidden))

    def match(name, text):
        if re.search(gmm.GMM, name):
            return False
        io = written_and_read(text)
        return io is not None and token_rows in io[0] and any(
            dims in pair_rows for _, dims in io[1])

    return gmm.per_layer_step_us(ctx, stage["expert_layers"], match)
