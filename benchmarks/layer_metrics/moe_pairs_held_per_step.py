"""Token-expert pairs a decode step lands on the experts this stage
holds, over the window: the growth of ``parallax_moe_pairs_held`` over
the window's decode steps (counted as ``moe_held_hit_share`` counts
them), summed over the stage's expert layers. What the grouped matmuls
multiply: ``rows x num_experts_per_tok x experts_held /
n_routed_experts`` a layer for a router that spreads evenly (128 x 8 x
12 / 192 x 6 = 384). None on a program without the series."""

import os

from benchmarks.harness import spec

hit = spec.import_file("layer_metric_", os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "moe_held_hit_share.py"))

PAIRS = "parallax_moe_pairs_held"


def reduce(ctx):
    got = hit.window_steps(ctx, PAIRS)
    return None if got is None else got[0] / got[1]
