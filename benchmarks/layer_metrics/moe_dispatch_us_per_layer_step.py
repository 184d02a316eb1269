"""Device microseconds per expert layer and decode step of everything
the expert layer does around its grouped matmuls: the router's matmul
and top-k, the sort of the token-expert pairs, the gather of their rows,
the activation between the matmuls, the weighting and the scatter-add
back (device scopes ``moe_dispatch`` and, for the activation,
``moe_experts`` in ``models/moe.py``). They are XLA fusions with
numbered names, so they are told by what they write: an array with one
row a token-expert pair (``rows x num_experts_per_tok`` leading, rows
read from the decode kernel's own output) or one router score a row and
expert (``[rows, n_routed_experts]``). The scatter-add's last fusion
writes ``[rows, hidden]`` like a dozen others and is left out (PERF.md
section 5). Counted as ``moe_gmm_us_per_layer_step``
counts. None on a trace without such operations or a stage without an
expert layer."""

import os
import re

from benchmarks.harness import spec

gmm = spec.import_file("layer_metric_", os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "moe_gmm_us_per_layer_step.py"))


def reduce(ctx):
    stage, model, got = ctx.get("work"), ctx.get("model"), gmm.decode_ops(ctx)
    if None in (stage, model, got) or not stage["expert_layers"]:
        return None
    rows = next((gmm.result_of(text)[1][0] for name, text, _ in got[1]
                 if re.search(stage["kernel"], name)
                 and gmm.result_of(text)), None)
    if rows is None:
        return None
    pairs = rows * stage["experts_per_token"]
    router = (rows, model["n_routed_experts"])

    def match(name, text):
        if re.search(gmm.GMM, name):
            return False
        wrote = gmm.result_of(text)
        return wrote is not None and bool(wrote[1]) and (
            wrote[1][0] == pairs or wrote[1] == router)

    return gmm.per_layer_step_us(ctx, stage["expert_layers"], match)
