"""Device microseconds per layer and decode step of the one-row latent
append: the XLA scatter that writes each row's new ``[latent ; rope
key]`` into the paged latent cache before the decode kernel reads it
(``ops/mla.store_mla_cache``; the fused kernel that would absorb it does
not lower, ``kernel_select.fused_lowering_gap``). A fusion with a
numbered name, told by what it writes: the cache array itself, as
``[pages, page_size, row width]`` or flat as the scatter sees it,
``[pages x page_size, row width]``, with the row width
``kv_lora_rank + qk_rope_head_dim`` in whole 128-value lane tiles and at
least 4,096 rows (the step's own new rows, ``[rows, row width]``, are a
few hundred), which nothing else in the step program writes. Counted as
``moe_gmm_us_per_layer_step`` counts, over the stage's layers that hold
pages. None on a trace without such an operation."""

import math
import os

from benchmarks.harness import spec

CACHE_ROWS_MIN = 4096

gmm = spec.import_file("layer_metric_", os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "moe_gmm_us_per_layer_step.py"))


def reduce(ctx):
    stage, model = ctx.get("work"), ctx.get("model")
    if stage is None or model is None or "kv_lora_rank" not in model:
        return None
    row = model["kv_lora_rank"] + model["qk_rope_head_dim"]
    width = -(-row // 128) * 128

    def match(name, text):
        wrote = gmm.result_of(text)
        if wrote is None or len(wrote[1]) not in (2, 3):
            return False
        *lead, last = wrote[1]
        return (last == width and math.prod(lead) >= CACHE_ROWS_MIN
                and "custom-call" not in text)

    return gmm.per_layer_step_us(ctx, stage["paged_layers"], match)
